"""DeepSpeedHybridEngine — one weight set, two compiled programs.

Reference parity: ``runtime/hybrid_engine.py:32`` (``DeepSpeedHybridEngine``)
— the RLHF workhorse that flips a ZeRO-3 training model into injected-kernel
inference for rollout ``generate`` (``:178``), fusing LoRA adapters before
and unfusing after (``:130-165``).

TPU-native design: the training engine owns the fp32 master params under the
ZeRO sharding plan; ``generate`` runs the same jitted prefill+scan decode
loop as ``InferenceEngine`` against a bf16 *view* of those params produced by
one jitted cast-and-reshard program (all-gather of the ZeRO shards happens
once per rollout batch inside that program — the analog of the reference's
inference-container population ``:84-130``).  The view is cached and
invalidated on every optimizer step, so back-to-back rollouts pay the gather
once.  Train step and decode loop are two cached XLA executables over the
same buffers — no weight copying between "modes".
"""

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.monitor.trace import ready_line, span
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.tools.lint.hotpath import hot_path
from deepspeed_tpu.utils.logging import log_dist, logger


class DeepSpeedHybridEngine(DeepSpeedEngine):

    # the rollout's KV workspace and inference view take device memory the
    # train step's compile cannot see: a remat policy of "fit" stays rung 0
    _remat_fit_enabled = False

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._infer_params = None
        self._infer_params_step = -1
        self._gen_compiled = {}
        self._gen_aot = {}       # (id(fn),) + abstract sig -> AOT executable
        self._cast_fn = None
        self._lora_spec = None
        self._lora_fused = False
        self._gen_rng = jax.random.key(0)
        # rollout/train latency bookkeeping (reference hybrid_engine fields)
        self._generate_latency = 0.0
        self._training_latency = 0.0
        # opt-in quantized rollouts (beyond the reference: decode is
        # HBM-bound, so an int8 inference view nearly halves rollout time;
        # training always sees the exact masters)
        he = self._config._param_dict.get("hybrid_engine", {}) \
            if isinstance(getattr(self._config, "_param_dict", None), dict) \
            else {}
        self._rollout_quantizer = None
        if he.get("quantize_rollouts", False):
            self.set_rollout_quantization(
                bits=int(he.get("rollout_quant_bits", 8)))
        # rollout decode-loop form (mirrors the inference config's
        # decode_early_exit): True (default) = bounded while_loop that
        # stops once every row hit EOS; False = the fixed-length scan —
        # the escape hatch if the while form regresses donation or
        # rollout throughput
        self._rollout_early_exit = bool(he.get("decode_early_exit", True))

    def set_rollout_quantization(self, bits=8):
        """Quantize the inference view per rollout (per-channel, fusable
        dequant inside the decode program).  ``bits=0`` disables.  The
        quantization is re-derived from the CURRENT masters after every
        optimizer step — rollouts always track training, just at reduced
        weight precision (an opt-in approximation; the reference's view is
        16-bit)."""
        if not bits:
            self._rollout_quantizer = None
        else:
            from deepspeed_tpu.runtime.weight_quantizer import (
                WeightQuantization)
            # per-channel scales are symmetric-int8-only; int4 falls back
            # to the grouped-scale path
            self._rollout_quantizer = WeightQuantization(
                bits=bits, per_channel=bits == 8)
            if self.topology.tp > 1:
                logger.warning("quantize_rollouts with tp>1: quantized "
                               "payloads are replicated, not TP-sharded")
        self._infer_params = None
        self._infer_params_step = -1
        self._quant_cast_fn = None
        self._gen_compiled = {}
        self._gen_aot = {}

    def _rollout_deq(self, params):
        """In-trace dequantization hook for the rollout program (identity
        when rollout quantization is off)."""
        if self._rollout_quantizer is None:
            return params
        return self._rollout_quantizer.dequantize_tree(
            params, self.compute_dtype)

    def _drop_quantized_view(self):
        # unlike the bf16 view (which ALIASES the master buffers, costing
        # nothing to keep), a quantized view is its own HBM allocation —
        # release it before training so the train step's activations can
        # use that space; back-to-back rollouts still share one view
        if self._rollout_quantizer is not None and \
                self._infer_params is not None:
            self._infer_params = None
            self._infer_params_step = -1
        # the rollout KV-cache workspace is likewise its own HBM
        # allocation (GBs at serving batch sizes) — release it before the
        # train step's activation peak; the next rollout re-zeros it once
        if getattr(self, "_gen_workspace", None) is not None:
            self._gen_workspace.release()

    def train_batch(self, *args, **kwargs):
        self._drop_quantized_view()
        return super().train_batch(*args, **kwargs)

    def forward(self, *args, **kwargs):
        # the fused fwd+bwd program runs inside forward() on the 3-call
        # path — the view must be gone before ITS peak, not backward()'s
        self._drop_quantized_view()
        return super().forward(*args, **kwargs)

    __call__ = forward

    # ------------------------------------------------------------------ #
    # Inference view of the training params
    # ------------------------------------------------------------------ #
    def _inference_view(self):
        """bf16 (compute-dtype), TP-sharded / ZeRO-gathered view of the
        current master params; rebuilt only after an optimizer step.

        NOTE lifetime: when the masters are already compute-dtype and
        inference-placed, the view ALIASES the live master buffers
        (zero-copy) — the next optimizer step donates those buffers, so a
        view held across ``train_batch``/``step`` is dead afterwards.
        Always re-fetch per rollout (``generate`` does)."""
        if self._infer_params is not None and \
                self._infer_params_step == self.global_steps:
            return self._infer_params
        if self._params is None:
            # RLHF generates before the first train step — init params now
            # (sharded at birth), same as the first forward would.
            seq = min(8, self.module.config.max_seq_len) \
                if hasattr(self.module, "config") else 8
            dummy = {"input_ids": jnp.zeros((1, seq), jnp.int32)}
            self._lazy_init((dummy,), {})
        if self._cast_fn is None:
            cast = self.compute_dtype
            self._cast_fn = jax.jit(
                lambda t: jax.tree.map(
                    lambda p: p.astype(cast)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p, t),
                out_shardings=self._infer_shardings())
        params = self._params
        if self._lora_spec is not None and not self._lora_fused:
            params = _fuse_lora(params, self._lora_spec)
        if self._rollout_quantizer is not None:
            # int8/int4-at-rest rollout view: payload+scales, replicated
            # (mirrors InferenceEngine.set_params' quantized placement)
            if getattr(self, "_quant_cast_fn", None) is None:
                from deepspeed_tpu.runtime.weight_quantizer import _is_qw
                cast = self.compute_dtype
                rep = NamedSharding(self.mesh, P())
                q = self._rollout_quantizer

                @hot_path("hybrid.rollout_cast")
                def quantize_and_cast(t):
                    t = q.quantize_tree(t)
                    return jax.tree.map(
                        lambda p: p if _is_qw(p) else (
                            p.astype(cast)
                            if jnp.issubdtype(p.dtype, jnp.floating) else p),
                        t, is_leaf=_is_qw)
                self._quant_cast_fn = jax.jit(quantize_and_cast,
                                              out_shardings=rep)
            self._infer_params = self._quant_cast_fn(params)
            self._infer_params_step = self.global_steps
            return self._infer_params
        if params is self._params and self._view_is_identity():
            # memory-lean masters are already compute-dtype and, on a
            # mesh without live ZeRO scattering, already placed as the
            # inference program wants them: the "view" IS the master
            # buffers — zero-copy weight sharing (what the reference's
            # shared-container design approximates with pointer swaps)
            self._infer_params = params
        else:
            self._infer_params = self._cast_fn(params)
        self._infer_params_step = self.global_steps
        return self._infer_params

    def _infer_shardings(self):
        """Inference placement: keep TP sharding, drop ZeRO scattering
        (replicate over dp) so each decode step is gather-free."""
        from deepspeed_tpu.runtime.zero.partition import (
            is_expert_stacked, path_to_str, tp_spec_for)

        def spec_of(path, leaf):
            ps = path_to_str(path)
            return NamedSharding(
                self.mesh,
                tp_spec_for(ps, leaf.shape, self.mesh,
                            expert_stacked=is_expert_stacked(
                                ps, len(leaf.shape))))
        abstract = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), self._params)
        return jax.tree_util.tree_map_with_path(spec_of, abstract)

    def _view_is_identity(self):
        """True when cast+reshard would be a no-op copy: every float leaf is
        already compute-dtype and every leaf is already placed exactly as
        the inference sharding plan asks.  Computed once — the donating
        update preserves dtypes and out-shardings, so the verdict cannot
        change between steps."""
        if getattr(self, "_view_identity", None) is not None:
            return self._view_identity
        cast = self.compute_dtype
        shardings = jax.tree.leaves(self._infer_shardings())
        leaves = jax.tree.leaves(self._params)
        verdict = True
        for leaf, want in zip(leaves, shardings):
            if jnp.issubdtype(leaf.dtype, jnp.floating) and leaf.dtype != cast:
                verdict = False
                break
            sh = getattr(leaf, "sharding", None)
            if sh is None or not sh.is_equivalent_to(want, leaf.ndim):  # tpu-lint: disable=TL006 -- one-time placement verdict, memoized in _view_identity (donating updates preserve dtype/sharding)
                verdict = False
                break
        self._view_identity = verdict
        return verdict

    # ------------------------------------------------------------------ #
    # LoRA (reference hybrid_engine fuse_lora_weight/unfuse_lora_weight)
    # ------------------------------------------------------------------ #
    def set_lora(self, lora_spec):
        """Register LoRA adapters: {param-path: (A [in,r], B [r,out],
        scaling)} — fused into the inference view (and optionally the master
        weights) like the reference's ``_fuse_lora`` (:130)."""
        self._lora_spec = lora_spec
        self._infer_params = None

    def fuse_lora_weight(self):
        """Fuse LoRA deltas into the master weights in-place."""
        if self._lora_spec is None or self._lora_fused:
            return
        if self._params is None:
            raise RuntimeError("fuse_lora_weight() before parameters exist; "
                               "run a forward or generate first")
        self._params = _fuse_lora(self._params, self._lora_spec)
        self._lora_fused = True
        self._infer_params = None

    def unfuse_lora_weight(self):
        if self._lora_spec is None or not self._lora_fused:
            return
        self._params = _fuse_lora(self._params, self._lora_spec, sign=-1.0)
        self._lora_fused = False
        self._infer_params = None

    # ------------------------------------------------------------------ #
    # Rollout generation (reference hybrid_engine.generate :178)
    # ------------------------------------------------------------------ #
    @hot_path("hybrid.rollout_generate")
    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=-1,
                 seed=None, attention_mask=None):
        """Rollout generation over the shared weights.  ``attention_mask``
        supports RIGHT-padded prompt batches — the usual RLHF rollout input
        (see ``InferenceEngine.generate`` for the layout contract)."""
        from deepspeed_tpu.inference.engine import (KVCacheWorkspace,
                                                    make_generate_fn,
                                                    require_right_padded,
                                                    required_cache_len)
        import time
        t0 = time.time()
        input_ids = jnp.asarray(input_ids)
        if attention_mask is not None:
            require_right_padded(attention_mask)
        if seed is not None:
            self._gen_rng = jax.random.key(seed)
        self._gen_rng, rng = jax.random.split(self._gen_rng)
        # rollouts keep the ONE-PASS prefill: the in-program chunked scan
        # carries an un-aliased partial cache copy (the form the inference
        # engine's split-prefill path exists to avoid), and rollout
        # prompts are short — route long-prompt/big-batch generation
        # through InferenceEngine (the weights are a shared view) to get
        # the split path's memory bounds
        chunk = None
        # the loop form rides the key — it is part of the program's
        # identity and the executable-store key derives from this tuple
        key = (input_ids.shape[1], int(max_new_tokens), bool(do_sample),
               float(temperature), int(top_k), float(top_p),
               attention_mask is not None, chunk,
               self._rollout_early_exit)
        self._get_rollout_fn(key)
        params = self._inference_view()
        if getattr(self, "_gen_workspace", None) is None:
            # donated KV-cache workspace, shared across rollouts (see
            # KVCacheWorkspace: in-place decode, no double-buffered carry)
            self._gen_workspace = KVCacheWorkspace(self.module)
        cache = self._gen_workspace.take(
            input_ids.shape[0],
            required_cache_len(input_ids.shape[1], int(max_new_tokens),
                               chunk),
            self.compute_dtype)
        args = (params, cache, input_ids, rng, jnp.asarray(eos_token_id))
        if attention_mask is not None:
            args += (jnp.asarray(attention_mask),)
        out, cache = self._run_rollout(self._gen_compiled[key], args, key)
        self._gen_workspace.give_back(cache)
        out.block_until_ready()  # tpu-lint: disable=TL001 -- rollout latency metric needs the full program, once per rollout not per token
        self._generate_latency += time.time() - t0
        return out

    def _get_rollout_fn(self, key):
        """Build (or fetch) the rollout generation program for ``key`` =
        (prompt_len, max_new, do_sample, temperature, top_k, top_p,
        with_mask, chunk, early_exit)."""
        if key not in self._gen_compiled:
            from deepspeed_tpu.inference.engine import make_generate_fn
            (P, new, do_sample, temperature, top_k, top_p, with_mask,
             chunk, _early_exit) = key
            # carry the rollout view through the decode scan only when its
            # dequant materializes full weights (see WeightQuantization
            # .materializing_dequant); the plain bf16 view stays an
            # argument buffer (no loop-temp copy)
            self._gen_compiled[key] = make_generate_fn(
                self.module, self.compute_dtype, P, new, do_sample,
                temperature, top_k, top_p,
                param_transform=self._rollout_deq,
                with_mask=with_mask,
                carry_params=self._rollout_quantizer is not None
                and self._rollout_quantizer.materializing_dequant,
                prefill_chunk=chunk,
                early_exit=self._rollout_early_exit)
        return self._gen_compiled[key]

    def _run_rollout(self, fn, args, key):
        """Execute a rollout program — through an AOT executable when one
        exists (``warmup_rollout`` or the compile_cache executable store);
        the plain jit call otherwise (seed behavior)."""
        if self._program_cache is None and not self._gen_aot:
            return fn(*args)
        from deepspeed_tpu.runtime import compile_cache as cc
        sig = (id(fn),) + cc.abstract_signature(args)
        exe = self._gen_aot.get(sig)
        if exe is None:
            exe, _, _ = self._rollout_aot_compile(fn, args, key, sig)
        return exe(*args)

    def _rollout_aot_compile(self, fn, args, key, sig):
        """Returns ``(exe, compile_seconds, store_hit)``."""
        import json as _json
        from deepspeed_tpu.runtime.compile_cache import aot_compile_with_store
        q = self._rollout_quantizer
        # same context discipline as _train_key_parts: mesh layout and the
        # full engine config are part of the program's identity (the
        # runtime fingerprint only sees device kind/count — two different
        # shardings on the same host must not share an executable)
        key_parts = (key, sig[1:],
                     repr(getattr(self.module, "config",
                                  type(self.module).__name__)),
                     self.compute_dtype.__name__,
                     None if q is None else q.bits,
                     tuple(sorted(dict(self.mesh.shape).items())),
                     _json.dumps(self._config._param_dict, sort_keys=True,
                                 default=repr))
        exe, dt, hit = aot_compile_with_store(
            self._program_cache, "rollout", key_parts, fn, args)
        if exe is None:            # AOT failed (warned): plain jit call —
            exe = fn               # no fake 0.0s compile event
        else:
            self._report_compile("rollout", dt, hit)
        self._gen_aot[sig] = exe
        return exe, dt, hit

    def warmup_rollout(self, batch_sizes, prompt_len, max_new_tokens,
                       do_sample=False, temperature=1.0, top_k=0,
                       top_p=1.0, with_mask=False):
        """AOT-compile the rollout ``generate`` program for every batch-
        size bucket (RLHF rollout sweeps run several), reporting per-
        program compile time through the monitor.  Combine with
        ``warmup()`` (the train step) to pay the whole hybrid loop's
        compile cost up front — and, with the ``compile_cache`` block
        enabled, once per machine.  ``with_mask=True`` warms the
        right-padded-prompt variant (int32 masks — the usual RLHF rollout
        input; masked and unmasked are DIFFERENT programs).  Returns
        ``{program: seconds}`` (0.0 = store hit / already warm)."""
        from deepspeed_tpu.inference.engine import required_cache_len
        from deepspeed_tpu.runtime import compile_cache as cc
        params = self._inference_view()
        P, new = int(prompt_len), int(max_new_tokens)
        key = (P, new, bool(do_sample), float(temperature), int(top_k),
               float(top_p), bool(with_mask), None,
               self._rollout_early_exit)
        fn = self._get_rollout_fn(key)
        report = {}
        with span("dstpu.setup.warmup", cat="setup") as sp:
            for B in batch_sizes:
                B = int(B)
                cache = jax.eval_shape(
                    lambda: self.module.init_cache(
                        B, required_cache_len(P, new, None),
                        dtype=self.compute_dtype))
                args = (params, cache,
                        jax.ShapeDtypeStruct((B, P), jnp.int32),
                        jax.eval_shape(lambda: jax.random.key(0)),
                        jnp.asarray(-1))
                if with_mask:
                    args += (jax.ShapeDtypeStruct((B, P), jnp.int32),)
                sig = (id(fn),) + cc.abstract_signature(args)
                name = f"rollout:b{B}p{P}n{new}"
                if sig in self._gen_aot:
                    report[name] = 0.0
                    continue
                _, dt, hit = self._rollout_aot_compile(fn, args, key, sig)
                report[name] = 0.0 if hit else dt
            sp.set(programs=len(report))
        log_dist(ready_line("rollout"), ranks=[0])
        return report


@partial(jax.jit, static_argnames=("sign",))  # tpu-lint: disable=TL002 -- input is the live master tree; donating it would kill the training copy
def _fuse_lora_jit(params, lora_spec, sign):
    from deepspeed_tpu.runtime.zero.partition import path_to_str

    def one(path, w):
        entry = lora_spec.get(path_to_str(path))
        if entry is None:
            return w
        a, b, scale = entry
        delta = (a.reshape(a.shape[0], -1) @ b.reshape(b.shape[0], -1))
        return w + (sign * scale * delta.reshape(w.shape)).astype(w.dtype)

    return jax.tree_util.tree_map_with_path(one, params)


def _fuse_lora(params, lora_spec, sign=1.0):
    """W ← W + sign·scale·(A@B) for every (path, (A, B, scale)) entry.
    Module-level jit so repeated fuses (one per train-step/rollout cycle)
    hit the executable cache."""
    return _fuse_lora_jit(params, lora_spec, sign=float(sign))
