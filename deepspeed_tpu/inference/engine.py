"""InferenceEngine — sharded, jitted generation.

TPU-native re-design of reference ``inference/engine.py:89``
(``InferenceEngine``): the reference swaps model layers for fused CUDA
kernels (``_apply_injection_policy :408``), slices weights for TP
(``module_inject/replace_module.py:31``), manages a KV-cache workspace
(``inference_context.h``), and captures CUDA graphs (``:526``).  Here:

* "kernel injection" is compilation: the whole decode step is one jitted XLA
  program (fused by construction), with Pallas flash attention for prefill
  where supported — there is no separate injected-module zoo to maintain;
* TP weight slicing is a sharding plan (AutoTP name rules,
  ``runtime/zero/partition.py``) applied as param ``NamedSharding``s — XLA
  inserts the per-layer collectives the reference codes by hand;
* the KV cache is a donated, statically-shaped [L, B, S_max, KVH*D] buffer
  (S-major, heads flattened — the decode kernel's full-lane-width DMA
  layout) updated in-place via donation (the workspace allocator
  equivalent);
* CUDA-graph capture/replay == jit compile/execute — every step after the
  first runs from the executable cache.

``generate`` implements greedy + temperature/top-k/top-p sampling with a
``lax.scan`` decode loop (one compiled program for the whole generation).
"""

from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.monitor.trace import ready_line, span
from deepspeed_tpu.parallel import topology as topo_mod
from deepspeed_tpu.runtime import compile_cache as compile_cache_mod
from deepspeed_tpu.runtime.zero.partition import build_sharding_plan
from deepspeed_tpu.runtime.config import ZeroConfig
from deepspeed_tpu.tools.lint.hotpath import hot_path
from deepspeed_tpu.utils.logging import log_dist, logger


class MemoryGuardExceeded(RuntimeError):
    """A generation program's compiled footprint exceeded
    ``memory_guard_fraction`` of device memory under ``strict_memory``.
    With the ``fault`` block's ``bucket_downshift`` on, ``generate``
    catches this and splits the batch instead of failing the request."""


class InferenceEngine:

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 params=None):
        self.module = model
        self._config = config or DeepSpeedInferenceConfig()
        tp = self._config.tensor_parallel.tp_size
        self.topology = topo_mod.initialize_topology(tp=tp, ep=self._config.ep_size)
        self.mesh = self.topology.mesh
        from deepspeed_tpu.inference.config import normalize_dtype_str
        self.compute_dtype = {"bfloat16": jnp.bfloat16,
                              "float16": jnp.float16,
                              "float32": jnp.float32}[
                                  normalize_dtype_str(self._config.dtype)]
        self._quantizer = None
        if self._config.quant.enabled:
            from deepspeed_tpu.runtime.weight_quantizer import (
                WeightQuantization)
            self._quantizer = WeightQuantization(
                bits=self._config.quant.bits,
                group_size=self._config.quant.group_size,
                per_channel=self._config.quant.per_channel)
        self._params = None
        self._compiled = {}
        self._workspace = KVCacheWorkspace(model)
        self._aot = {}
        self._tags = {}          # id(jit fn) -> stable program tag
        # id(jit fn) -> the short kind its dispatch span uses ("decode",
        # "prefill_chunk", ...): the compile span's ``program``
        self._programs = {}
        # ids of jitted fns that must NOT touch the persistent caches —
        # neither the serialized-executable store nor the XLA disk cache.
        # The serving slot programs register here: reloading any of them
        # in a fresh process nondeterministically corrupts the slot
        # workspace or segfaults (see ServingEngine.__init__ /
        # compile_cache.suspended_persistent_cache); they recompile once
        # per process instead
        self._persist_opt_out = set()
        # persistent compile/executable cache (None = disabled: the AOT
        # path below still compiles per process, just without disk reuse)
        self._program_cache = compile_cache_mod.ProgramCache.from_config(
            self._config.compile_cache)
        self._rng = jax.random.key(0)
        # fault/degradation accounting (docs/fault_tolerance.md):
        # transient executable-load retries and strict_memory batch splits
        self.fault_stats = {"exec_load_retries": 0, "bucket_downshifts": 0}
        # signatures the memory guard refused under strict_memory —
        # repeat requests at that bucket skip straight to the downshift
        self._guard_refused = set()
        if params is not None:
            self.set_params(params)
        elif self._config.checkpoint is not None:
            self.load_checkpoint(self._config.checkpoint)

    # ------------------------------------------------------------------ #
    # Weights: the "injection"/TP-slicing step (reference engine.py:408)
    # ------------------------------------------------------------------ #
    def _plan_for(self, abstract):
        # inference: params sharded over tp only (no ZeRO axes), replicated
        # over dp — the AutoTP analog
        return build_sharding_plan(abstract, self.topology, ZeroConfig(stage=0))

    def set_params(self, params):
        """Cast (or quantize) ``params`` and place them on the mesh by the
        tensor-parallel plan, under ``dstpu.setup.weights``."""
        with span("dstpu.setup.weights", cat="setup") as sp:
            self._place_params(params)
            leaves = jax.tree.leaves(self._params)
            sp.set(bytes=sum(l.nbytes for l in leaves), leaves=len(leaves),
                   sharded=int(self.topology.tp > 1
                               and self._quantizer is None))

    def _place_params(self, params):
        if self._quantizer is not None:
            # INT8/INT4-at-rest (reference WeightQuantization at checkpoint
            # load): payload+scales live in HBM; dequant runs inside the
            # jitted programs, fused into each weight's consumer.  Unquantized
            # leaves (biases/norms) still cast to the compute dtype; all
            # leaves are placed replicated (quantized TP is unsupported).
            if self.topology.tp > 1:
                logger.warning("weight quantization with tp>1: quantized "
                               "payloads are replicated, not TP-sharded")
            cast = self.compute_dtype
            rep = NamedSharding(self.mesh, P())

            def quantize_and_cast(t):
                t = self._quantizer.quantize_tree(t)
                from deepspeed_tpu.runtime.weight_quantizer import _is_qw
                return jax.tree.map(
                    lambda p: p if _is_qw(p) else (
                        p.astype(cast)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p),
                    t, is_leaf=_is_qw)
            self._params = jax.jit(quantize_and_cast,
                                   out_shardings=rep)(params)
            n = sum(int(np.prod(l.shape))
                    for l in jax.tree.leaves(self._params))
            log_dist(f"inference params quantized to "
                     f"int{self._quantizer.bits}: {n/1e6:.1f}M values",
                     ranks=[0])
            return
        abstract = jax.eval_shape(lambda t: t, params)
        self._plan = self._plan_for(abstract)
        cast = self.compute_dtype
        # leaf by leaf, so that the cast never holds a second copy of the
        # whole tree (a model that fills most of the chip has no room for
        # one); a leaf already in the compute dtype is placed as it is
        self._params = jax.device_put(
            jax.tree.map(lambda p: p.astype(cast)
                         if jnp.issubdtype(p.dtype, jnp.floating) else p,
                         params),
            self._plan.param_shardings)
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self._params))
        log_dist(f"inference params placed: {n/1e6:.1f}M, tp={self.topology.tp}, "
                 f"dtype={cast.__name__}", ranks=[0])

    def _deq(self, params):
        """Identity for float params; in-trace dequantization when weight
        quantization is on (called inside every compiled program)."""
        if self._quantizer is None:
            return params
        return self._quantizer.dequantize_tree(params, self.compute_dtype)

    def release_params(self):
        """Drop this engine's parameters (their device memory with them)
        before the next ``set_params``: a model that fills more than half
        the chip has no room for its successor beside it."""
        self._params = None

    def init_params(self, example_ids=None, seed=0):
        """Random init (testing / benchmarking without a checkpoint)."""
        if example_ids is None:
            example_ids = jnp.zeros((1, 8), jnp.int32)
        params = self.module.init(jax.random.key(seed), {"input_ids": example_ids})
        self.set_params(params)

    def load_checkpoint(self, path, tag=None):
        """Directory → engine (Orbax) checkpoint; single file → a
        ``save_16bit_model`` export (safetensors / torch state dict with
        flax-named keys).  Files needing full (code-executing) unpickling —
        legacy pickled pytrees, torch files with non-allowlisted objects —
        only load with ``DSTPU_ALLOW_PICKLE_CHECKPOINTS=1``.  HF-named
        exports (``hf_policy=...``) go through ``module_inject`` instead."""
        import os, pickle
        if os.path.isfile(path):
            if path.endswith(".safetensors"):
                from safetensors.numpy import load_file
                self.set_params(_unflatten_flax_paths(load_file(path)))
                return
            sd = None
            try:
                # weights_only=True: never execute pickled code from an
                # untrusted checkpoint during format probing
                import torch
                sd = torch.load(path, map_location="cpu", weights_only=True)
            except (pickle.UnpicklingError, RuntimeError, ImportError):
                pass                     # not a weights-only-loadable file
            if sd is not None:
                self.set_params(_unflatten_flax_paths(
                    {k: (v.float().numpy() if hasattr(v, "numpy") else v)
                     for k, v in sd.items()}))
                return
            # full unpickling executes arbitrary code — only for files the
            # operator explicitly vouches for
            if os.environ.get("DSTPU_ALLOW_PICKLE_CHECKPOINTS") != "1":
                raise ValueError(
                    f"{path}: not loadable with weights_only unpickling; "
                    "full pickle execution is disabled for untrusted files. "
                    "Set DSTPU_ALLOW_PICKLE_CHECKPOINTS=1 to load a legacy "
                    "pickled pytree (or a torch file with non-allowlisted "
                    "objects) you trust.")
            try:                         # torch-zip file with custom objects
                import torch
                sd = torch.load(path, map_location="cpu", weights_only=False)
                self.set_params(_unflatten_flax_paths(
                    {k: (v.float().numpy() if hasattr(v, "numpy") else v)
                     for k, v in sd.items()}))
                return
            except (pickle.UnpicklingError, RuntimeError, ImportError,
                    ValueError):
                pass                     # bare pickle stream → legacy path
            with open(path, "rb") as f:
                self.set_params(pickle.load(f))
            return
        from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import \
            OrbaxCheckpointEngine
        eng = OrbaxCheckpointEngine()
        if tag is None and os.path.exists(os.path.join(path, "latest")):
            with open(os.path.join(path, "latest")) as f:
                tag = f.read().strip()
        state_path = os.path.join(path, str(tag), "state") if tag else path
        arrays, _ = eng.load(state_path)
        self.set_params(arrays["module"] if isinstance(arrays, dict)
                        and "module" in arrays else arrays)

    @property
    def params(self):
        return self._params

    # ------------------------------------------------------------------ #
    # Forward / generation
    # ------------------------------------------------------------------ #
    def forward(self, input_ids, attention_mask=None, **kwargs):
        """Full logits (reference engine.forward :586); ``attention_mask``
        masks padded positions."""
        assert self._params is not None, "no parameters: set_params/init_params first"
        if kwargs:
            raise TypeError(f"unsupported forward arguments: {sorted(kwargs)}")
        key = "fwd" if attention_mask is None else "fwd_masked"
        if key not in self._compiled:
            # decoder families expose a ``logits`` method; encoder modules
            # (BERT) return logits from __call__ directly
            has_logits = hasattr(type(self.module), "logits")
            if attention_mask is None:
                fwd = (lambda p, ids: self.module.apply(
                    self._deq(p), ids, method=type(self.module).logits)) \
                    if has_logits \
                    else (lambda p, ids: self.module.apply(
                        self._deq(p), {"input_ids": ids}))
                self._compiled[key] = jax.jit(fwd)
            else:
                fwd = (lambda p, ids, m: self.module.apply(
                    self._deq(p), ids, m, method=type(self.module).logits)) \
                    if has_logits else \
                    (lambda p, ids, m: self.module.apply(
                        self._deq(p), {"input_ids": ids, "attention_mask": m}))
                self._compiled[key] = jax.jit(fwd)
        args = (self._params, jnp.asarray(input_ids))
        if attention_mask is not None:
            args += (jnp.asarray(attention_mask),)
        return self._compiled[key](*args)

    __call__ = forward

    def _get_generate(self, prompt_len, max_new_tokens, do_sample, temperature,
                      top_k, top_p, with_mask=False, prefill_chunk=None,
                      external_prefill=False):
        # the loop form (early-exit while vs scan) rides the key: it is
        # part of the compiled program's identity, and the executable
        # STORE key derives from this tuple — without it a warm cache
        # would silently reload the other form and decode_early_exit
        # would be a no-op exactly on warm starts
        key = ("gen", prompt_len, max_new_tokens, do_sample, temperature,
               top_k, top_p, with_mask, prefill_chunk, external_prefill,
               self._config.decode_early_exit)
        if key in self._compiled:
            return self._compiled[key]
        # carry the quantized tree through the scan only when its dequant
        # materializes full weights (see WeightQuantization
        # .materializing_dequant for the why of both directions)
        self._compiled[key] = make_generate_fn(
            self.module, self.compute_dtype, prompt_len, max_new_tokens,
            do_sample, temperature, top_k, top_p,
            param_transform=self._deq, with_mask=with_mask,
            carry_params=self._quantizer is not None
            and self._quantizer.materializing_dequant,
            prefill_chunk=prefill_chunk, external_prefill=external_prefill,
            early_exit=self._config.decode_early_exit)
        self._tags[id(self._compiled[key])] = key
        return self._compiled[key]

    def _prefill_chunk_for(self, batch_size, prompt_len):
        cfg = self._config.prefill_chunk_size
        if cfg in (None, 0, "none", "off"):
            return None
        if cfg == "auto":
            return default_prefill_chunk(batch_size, prompt_len)
        # user-specified chunk: align like the fused-write checks do —
        # round UP to a multiple of 8 (Mosaic's sublane granularity; the
        # chunk kernel's q block and the cache-pad arithmetic both assume
        # 8-row alignment) with a floor of 8, and cap at 512 (the kernel's
        # VMEM accumulator bound; a larger chunk would silently fall to the
        # dense attend path whose [B,H,S,S_max] fp32 transient this
        # chunking exists to avoid)
        c = min(512, max(8, -(-int(cfg) // 8) * 8))
        if c != int(cfg):
            from deepspeed_tpu.utils.logging import warning_once
            warning_once(f"prefill_chunk_size={cfg} adjusted to {c} "
                         f"(multiple of 8, min 8, max 512)")
        return c if c < prompt_len else None

    def prefill_plan(self, batch_size, prompt_len, paged=False):
        """Which prefill pipeline ``generate(batch, prompt)`` will take,
        as ``(mode, chunk, reason)`` — ``("chunked", C, ...)`` for the
        split per-chunk path, ``("one_pass", None, ...)`` otherwise.

        Observability for long-prompt serving points (the bench records
        it): the ``"auto"`` chunk policy declines chunking both for small
        working sets AND when the Pallas chunk kernel is unavailable —
        the latter silently drops a long prompt onto the one-pass path,
        whose dense-attention fallback materializes ``[B, H, S, S]``
        fp32 scores (~32 GB at bs16 x 4k) and OOMs where the chunked
        pipeline runs fine.  Pin ``prefill_chunk_size`` to an int to
        force the chunked pipeline regardless of the kernel gate (each
        chunk then attends through ``cached_attention``'s paths, with a
        dense per-chunk fallback of only ``[B, H, C, S_max]``).

        Every reason carries a ``[kernels: ...]`` tail naming the
        attention-registry modes the run will actually dispatch through
        (``pallas_chunked_prefill`` / ``pallas_paged_decode`` /
        ``pallas_decode`` / ``reference_fallback`` — see
        ``ops/transformer/registry.py``), so bench records attribute
        which kernel path ran, not just which pipeline was planned.
        ``paged=True`` asks for the paged-serving attribution (block
        tables + page-pool kernels) instead of the monolithic one, and
        names the form the admission chunk's K/V write takes at the
        ``serving`` block's page and chunk (``chunk_write=page_runs`` or
        ``row_scatter``, ``registry.paged_write_form``)."""
        from deepspeed_tpu.ops.transformer.registry import kernel_modes
        pe = getattr(getattr(self.module, "config", None),
                     "position_embedding", None)
        modes = kernel_modes(paged=bool(paged), has_bias=(pe == "alibi"))
        tail = ("prefill=%s decode=%s"
                % (modes["prefill_chunk"], modes["decode"]))
        if paged:
            from deepspeed_tpu.inference.serving.paging import page_rows
            from deepspeed_tpu.inference.serving.slots import (
                admission_chunk, chunk_write_form)
            from deepspeed_tpu.models import contract as slot_contract
            scfg = self._config.serving
            declared = slot_contract.read(self.module)
            form = chunk_write_form(
                declared, admission_chunk(declared, scfg.prefill_chunk),
                page_rows(scfg.page_size))
            if form is not None:
                tail += " chunk_write=" + form
        tail = " [kernels: " + tail + "]"
        cfg = self._config.prefill_chunk_size
        chunk = self._prefill_chunk_for(int(batch_size), int(prompt_len))
        if chunk is not None and chunk < prompt_len:
            why = "explicit prefill_chunk_size" \
                if cfg not in ("auto",) else "auto policy accepted"
            return "chunked", chunk, why + tail
        if cfg in (None, 0, "none", "off"):
            return "one_pass", None, "chunking disabled by config" + tail
        if cfg == "auto":
            from deepspeed_tpu.ops.transformer.flash_attention import \
                pallas_supported
            if not pallas_supported():
                return ("one_pass", None,
                        "auto policy declined: Pallas chunk kernel "
                        "disabled (DSTPU_DISABLE_FLASH=1)" + tail)
            return ("one_pass", None,
                    "auto policy declined: working set under "
                    "DSTPU_PREFILL_TOKEN_BUDGET" + tail)
        return "one_pass", None, "chunk >= prompt_len" + tail

    @hot_path("inference.generate")
    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=-1, seed=None,
                 attention_mask=None):
        """Autoregressive generation: returns [B, prompt_len+max_new_tokens]
        — prompt followed by new tokens, the HF ``generate`` contract
        (reference ``engine._generate :614``).

        ``attention_mask`` supports RIGHT-padded prompts (1 = real token):
        each row continues from its own prompt length; generated tokens
        occupy the trailing ``max_new_tokens`` columns of the result while
        the prompt columns (including pads) stay in place.
        """
        assert self._params is not None, "no parameters: set_params/init_params first"
        input_ids = jnp.asarray(input_ids)
        if attention_mask is not None:
            require_right_padded(attention_mask)
        if seed is not None:
            self._rng = jax.random.key(seed)
        self._rng, rng = jax.random.split(self._rng)
        try:
            return self._generate_once(
                input_ids, max_new_tokens, do_sample, temperature, top_k,
                top_p, eos_token_id, rng, attention_mask)
        except MemoryGuardExceeded:
            fcfg = getattr(self._config, "fault", None)
            B = input_ids.shape[0]
            if fcfg is None or not (fcfg.enabled and fcfg.bucket_downshift) \
                    or B <= 1:
                raise
            # graceful degradation (fault.bucket_downshift): the request's
            # batch bucket compiles over the memory guard — serve it as two
            # sequential half-batches instead of failing.  Latency roughly
            # doubles for this request; sampling streams differ from the
            # unsplit run (each half draws its own keys).  Recursion
            # bottoms out at batch 1, where the guard verdict is final.
            half = B // 2
            self.fault_stats["bucket_downshifts"] += 1
            logger.warning(  # tpu-lint: disable=TL003 -- generate() is host-side dispatch (the jitted programs live in _get_generate); this handler runs after a compile refusal, never in-trace
                f"strict_memory: generation batch {B} exceeds the memory "
                f"guard — bucket-downshifting to {half}+{B - half} "
                "sequential half-batches (fault.bucket_downshift)")
            kw = dict(max_new_tokens=max_new_tokens, do_sample=do_sample,
                      temperature=temperature, top_k=top_k, top_p=top_p,
                      eos_token_id=eos_token_id)
            mask = attention_mask
            lo = self.generate(input_ids[:half], attention_mask=None
                               if mask is None else mask[:half], **kw)
            hi = self.generate(input_ids[half:], attention_mask=None
                               if mask is None else mask[half:], **kw)
            return jnp.concatenate([lo, hi], axis=0)

    def _generate_once(self, input_ids, max_new_tokens, do_sample,
                       temperature, top_k, top_p, eos_token_id, rng,
                       attention_mask):
        B, P = input_ids.shape
        chunk = self._prefill_chunk_for(B, P)
        n_chunks = -(-P // chunk) if chunk else 1
        if n_chunks > 1:
            # chunked prefill runs as REPEATED CALLS of one per-chunk
            # executable instead of an in-program scan — the scan's
            # while-loop carries a partial extra copy of the cache that
            # XLA will not alias away (measured ~2.8 GB at a 4k cache;
            # the same copy at bs128's 5.1 GB cache OOM'd the 2-chunk
            # in-program form), and per-call the donated cache aliases
            # straight through, so peak memory is max(chunk program,
            # decode program), not their union.  Costs one dispatch per
            # chunk.
            return self._generate_split(
                input_ids, int(max_new_tokens), bool(do_sample),
                float(temperature), int(top_k), float(top_p),
                eos_token_id, rng, attention_mask, chunk)
        fn = self._get_generate(P, int(max_new_tokens),
                                bool(do_sample), float(temperature), int(top_k),
                                float(top_p),
                                with_mask=attention_mask is not None,
                                prefill_chunk=chunk)
        cache = self._workspace.take(
            B, required_cache_len(P, int(max_new_tokens), chunk),
            self.compute_dtype)
        try:
            args = (self._params, cache, input_ids, rng,
                    jnp.asarray(eos_token_id))
            if attention_mask is not None:
                args += (jnp.asarray(attention_mask),)
            out, cache = self._run_guarded(fn, args)
        finally:
            # on failure the (possibly donated-and-dead) buffer still goes
            # back; take() checks liveness before reuse
            self._workspace.give_back(cache)
        return out

    def _make_chunk_fn(self):
        """A fresh (unmemoized) per-chunk prefill program instance over a
        monolithic cache — ``generate()``'s split prefill (the serving
        engine's admission chunk program is ``slots.make_chunk_fn``,
        over the page pool)."""
        module, deq = self.module, self._deq

        @hot_path("inference.prefill_chunk")
        def chunk_step(params, cache, chunk_ids, start, logits_at):
            return module.apply(deq(params), chunk_ids, cache, start,
                                method=type(module).decode,
                                logits_at=logits_at)
        return jax.jit(chunk_step, donate_argnums=(1,))

    def _get_chunk_fn(self, C, B):
        """The per-chunk prefill executable of the split-prefill path (one
        donated-cache program replayed for every chunk)."""
        ck = ("chunkfill", C, B)
        if ck not in self._compiled:
            self._compiled[ck] = self._make_chunk_fn()
            self._tags[id(self._compiled[ck])] = ck
        return self._compiled[ck]

    def _generate_split(self, input_ids, max_new_tokens, do_sample,
                        temperature, top_k, top_p, eos_token_id, rng,
                        attention_mask, chunk):
        """Split-prefill generation: one donated-cache per-chunk prefill
        executable (chunk start and per-row logits positions are traced
        ARGUMENTS, so all chunks replay the same program) followed by the
        decode-only program.  See generate() for when this path wins."""
        B, P = input_ids.shape
        C = int(chunk)
        n = -(-P // C)
        cache = self._workspace.take(
            B, required_cache_len(P, max_new_tokens, C), self.compute_dtype)
        chunk_fn = self._get_chunk_fn(C, B)
        ids_pad = jnp.pad(input_ids, ((0, 0), (0, n * C - P)))
        if attention_mask is not None:
            last = jnp.sum(attention_mask.astype(jnp.int32), axis=1) - 1
        else:
            last = jnp.full((B,), P - 1, jnp.int32)
        try:
            sel = None
            for ci in range(n):
                local = jnp.clip(last - ci * C, 0, C - 1)
                logits, cache = self._run_guarded(
                    chunk_fn,
                    (self._params, cache, ids_pad[:, ci * C:(ci + 1) * C],
                     jnp.asarray(ci * C, jnp.int32), local))
                in_chunk = ((last // C) == ci)[:, None, None]
                sel = logits if sel is None \
                    else jnp.where(in_chunk, logits, sel)
            fn = self._get_generate(P, max_new_tokens, do_sample, temperature,
                                    top_k, top_p,
                                    with_mask=attention_mask is not None,
                                    external_prefill=True)
            args = (self._params, cache, input_ids, rng,
                    jnp.asarray(eos_token_id))
            args += ((jnp.asarray(attention_mask),)
                     if attention_mask is not None else (None,))
            args += (sel,)
            out, cache = self._run_guarded(fn, args)
        finally:
            self._workspace.give_back(cache)
        return out

    def release_workspace(self):
        """Free the persistent KV-cache workspace buffer (reference
        ``release_workspace``, ``inference_context.h``)."""
        self._workspace.release()

    def serve(self, monitor=None, draft_module=None, draft_params=None,
              **overrides):
        """A continuous-batching :class:`~deepspeed_tpu.inference.serving.
        ServingEngine` over this engine (``docs/serving.md``): slot-based
        in-flight batching — ``submit()`` requests, ``drain()`` results;
        new requests join freed KV slots between decode iterations instead
        of waiting for a whole ``generate()`` batch to finish.  Knobs come
        from the ``serving`` config block, overridable per call
        (``engine.serve(num_slots=16, page_size=64)``); the KV cache is
        a block-table page pool with copy-on-write prefix sharing;
        ``serving.speculative=True`` turns on
        draft-assisted speculative decoding — pass the draft model as
        ``engine.serve(speculative=True, draft_module=...,
        draft_params=...)`` or set ``serving.spec_draft_model``
        (``docs/serving.md`` "Speculative decoding")."""
        with span("dstpu.setup.serve", cat="setup") as sp:
            from deepspeed_tpu.inference.serving.engine import ServingEngine
            srv = ServingEngine(self, monitor=monitor,
                                draft_module=draft_module,
                                draft_params=draft_params, **overrides)
            sp.set(num_slots=srv.num_slots, num_pages=srv.num_pages)
        return srv

    def _run_guarded(self, fn, args):
        """Compile-and-check-then-execute: the generation program is
        AOT-compiled ONCE per argument signature (same executable the jit
        path would build — donation included) and its
        ``memory_analysis()`` is checked against ``memory_guard_fraction``
        of device memory before the first execution.  Near the limit XLA
        silently switches to staging buffers and decode collapses ~8x
        (docs/performance.md, "measure the cliff"); the reference's
        workspace allocator bounds-checks the same way
        (``inference_context.h:24-87``).  With the ``compile_cache`` block
        enabled, the executable is reloaded from / persisted to the
        on-disk store (runtime/compile_cache.py), so a warm process skips
        XLA compilation entirely."""
        sig = (id(fn),) + compile_cache_mod.abstract_signature(args)
        if sig in self._guard_refused:
            # this signature's program was already compiled once and
            # refused by the memory guard — refusing from memory spares
            # every subsequent over-budget request the doomed multi-second
            # XLA compile before its bucket downshift
            raise MemoryGuardExceeded(
                f"strict_memory: generation program for this signature was "
                f"previously refused by the memory guard (batch "
                f"{args[2].shape[0] if len(args) > 2 and hasattr(args[2], 'shape') else '?'})")
        compiled = self._aot.get(sig)
        if compiled is None:
            try:
                compiled, _, _ = self._aot_compile_resilient(fn, args)
            except MemoryGuardExceeded:
                self._guard_refused.add(sig)
                raise
            if compiled is None:
                # AOT path is an optimization + guardrail; never let it
                # block generation (fall back to the plain jit call).
                # Opt-out programs must stay cache-detached here too — a
                # fallback jit compile with the XLA disk cache attached
                # could reload exactly the cross-process executable the
                # opt-out exists to avoid
                self._aot[sig] = fn
                if id(fn) in self._persist_opt_out:
                    with compile_cache_mod.suspended_persistent_cache():
                        return fn(*args)
                return fn(*args)
            self._aot[sig] = compiled
        return compiled(*args)

    def _aot_compile_resilient(self, fn, args):
        """``_aot_compile`` under the fault block's bounded
        retry/backoff: a transient I/O failure while loading/persisting
        an executable (shared stores on network filesystems flake)
        retries ``fault.max_retries`` times; exhaustion degrades to the
        plain jit path instead of failing the request.  A
        :class:`MemoryGuardExceeded` refusal is NOT transient and
        propagates immediately."""
        fcfg = getattr(self._config, "fault", None)
        if fcfg is None or not fcfg.enabled or fcfg.max_retries <= 0:
            return self._aot_compile(fn, args)
        from deepspeed_tpu.runtime.fault.retry import (
            retry_call, retry_policy_from_config, TRANSIENT_IO_ERRORS)

        def count(_attempt, _exc):
            self.fault_stats["exec_load_retries"] += 1

        try:
            return retry_call(self._aot_compile, fn, args,
                              label="inference executable load",
                              on_retry=count,
                              **retry_policy_from_config(fcfg))
        except TRANSIENT_IO_ERRORS as e:
            logger.warning(f"executable load still failing after "
                           f"{fcfg.max_retries} retries "
                           f"({type(e).__name__}: {e}) — degrading to the "
                           "plain jit path for this program")
            return None, 0.0, False

    def _cache_context(self):
        """Engine facts that change compiled programs but not arg shapes —
        part of every executable-store key."""
        q = self._config.quant
        return (repr(getattr(self.module, "config",
                             type(self.module).__name__)),
                self.compute_dtype.__name__,
                tuple(sorted(dict(self.mesh.shape).items())),
                (q.enabled, q.bits, q.group_size, q.per_channel))

    def _aot_compile(self, fn, args):
        """Lower+compile ``fn`` for ``args`` (through the executable store
        when enabled), memory-guard the result.  Returns ``(compiled,
        compile_seconds, store_hit)`` — compiled is None on failure.
        ``args`` may be abstract (``ShapeDtypeStruct``) — warmup path."""
        from deepspeed_tpu.runtime.fault import inject as fault_inject
        fault_inject.fire("infer.executable_load")
        tag = self._tags.get(id(fn))
        kind = tag[0] if tag else "untagged"
        program = self._programs.get(id(fn), kind)
        if id(fn) in self._persist_opt_out:
            # fresh compile with BOTH persistent layers detached (see
            # _persist_opt_out above) — once per process per signature
            with compile_cache_mod.suspended_persistent_cache():
                compiled, dt, hit = compile_cache_mod.aot_compile_with_store(
                    None, f"infer:{kind}", (), fn, args, program=program)
        else:
            compiled, dt, hit = compile_cache_mod.aot_compile_with_store(
                self._program_cache if tag is not None else None,
                f"infer:{kind}",
                (tag, compile_cache_mod.abstract_signature(args),
                 self._cache_context()),
                fn, args, program=program)
        if compiled is None:
            return None, 0.0, False
        # guard BEFORE caching: under strict_memory every retry with
        # the same over-budget signature must refuse again, not find
        # a cached executable and run unguarded
        self._guard_memory(compiled)
        return compiled, dt, hit

    def _guard_memory(self, compiled):
        import os
        limit = int(os.environ.get("DSTPU_HBM_BYTES_OVERRIDE", "0"))
        if not limit:
            from deepspeed_tpu.profiling.flops_profiler.profiler import \
                device_hbm_bytes
            limit = device_hbm_bytes()
        if not limit:
            return                        # no budget info (CPU backend)
        try:
            ma = compiled.memory_analysis()
            need = ma.temp_size_in_bytes + ma.argument_size_in_bytes
        except Exception as e:            # introspection is best-effort
            logger.debug(f"memory guardrail skipped: {e}")
            return
        frac = self._config.memory_guard_fraction
        if need <= frac * limit:
            return
        msg = (f"generation program needs {need / 1e9:.1f} GB "
               f"(args {ma.argument_size_in_bytes / 1e9:.1f} + temps "
               f"{ma.temp_size_in_bytes / 1e9:.1f}) — above "
               f"{frac:.0%} of device memory ({limit / 1e9:.1f} GB). "
               f"XLA enters staging mode near this line and decode "
               f"throughput collapses nonlinearly; use a smaller batch or "
               f"shorter max cache (docs/performance.md, 'measure the "
               f"cliff').")
        if self._config.strict_memory:
            raise MemoryGuardExceeded(f"strict_memory: {msg}")
        logger.warning(msg)

    # ------------------------------------------------------------------ #
    # Warmup: pay all compiles up front (and once per machine, with the
    # compile_cache block enabled)
    # ------------------------------------------------------------------ #
    def warmup(self, prompt_len, max_new_tokens, batch_sizes=(1,),
               do_sample=False, temperature=1.0, top_k=0, top_p=1.0,
               with_mask=False, monitor=None):
        """AOT-compile every program a ``generate(prompt_len,
        max_new_tokens)`` call will need, for each batch-size bucket —
        including the split-prefill pair (per-chunk executable + decode-only
        program) when the chunk policy routes that batch there.  Nothing
        executes: arguments are abstract, so no HBM is touched beyond the
        already-placed params.

        Returns ``{program_name: compile_seconds}`` (0.0 = warm already /
        executable-store hit).  ``monitor``: an optional
        ``MonitorMaster``-like object; each program's compile time is
        reported as a ``Compile/<name>_secs`` event."""
        assert self._params is not None, \
            "no parameters: set_params/init_params first"
        report = {}
        with span("dstpu.setup.warmup", cat="setup") as sp:
            for B in batch_sizes:
                report.update(self._warmup_one(
                    int(B), int(prompt_len), int(max_new_tokens),
                    bool(do_sample), float(temperature), int(top_k),
                    float(top_p), bool(with_mask)))
            sp.set(programs=len(report))
        for name, dt in report.items():
            log_dist(f"warmup[{name}]: "
                     + ("cached" if dt == 0.0 else f"{dt:.1f}s"), ranks=[0])
        if monitor is not None and getattr(monitor, "enabled", True):
            monitor.write_events([(f"Compile/{name}_secs", dt, 0)
                                  for name, dt in report.items()])
        log_dist(ready_line("inference"), ranks=[0])
        return report

    precompile = warmup

    def _warmup_one(self, B, P, new, do_sample, temperature, top_k, top_p,
                    with_mask):
        chunk = self._prefill_chunk_for(B, P)
        n_chunks = -(-P // chunk) if chunk else 1
        cache = jax.eval_shape(
            lambda: self.module.init_cache(
                B, required_cache_len(P, new, chunk), dtype=self.compute_dtype))
        ids = jax.ShapeDtypeStruct((B, P), jnp.int32)
        rng = jax.eval_shape(lambda: jax.random.key(0))
        # concrete, WEAK-typed int32 — exactly what generate() builds from
        # the default ``eos_token_id=-1`` (a ShapeDtypeStruct would be
        # strong-typed and the warmed executable would refuse the call)
        eos = jnp.asarray(-1)
        mask = jax.ShapeDtypeStruct((B, P), jnp.int32) if with_mask else None

        def warm(fn, args, name):
            sig = (id(fn),) + compile_cache_mod.abstract_signature(args)
            if sig in self._aot:
                return {name: 0.0}
            compiled, dt, hit = self._aot_compile(fn, args)
            if compiled is None:
                logger.warning(f"warmup: {name} failed to AOT-compile — "
                               f"it will compile on first use instead")
                return {}
            self._aot[sig] = compiled
            return {name: 0.0 if hit else dt}

        report = {}
        if n_chunks > 1:
            C = int(chunk)
            chunk_fn = self._get_chunk_fn(C, B)
            cargs = (self._params, cache, jax.ShapeDtypeStruct((B, C), jnp.int32),
                     jax.ShapeDtypeStruct((), jnp.int32),
                     jax.ShapeDtypeStruct((B,), jnp.int32))
            report.update(warm(chunk_fn, cargs, f"prefill_chunk:b{B}c{C}"))
            # the decode-only program consumes the chunk program's
            # last-position logits — eval_shape gives their exact
            # shape/dtype (and the cache's post-donation abstract value)
            logits, cache = jax.eval_shape(chunk_fn, *cargs)
            fn = self._get_generate(P, new, do_sample, temperature, top_k,
                                    top_p, with_mask=with_mask,
                                    external_prefill=True)
            args = (self._params, cache, ids, rng, eos, mask, logits)
            report.update(warm(fn, args, f"decode:b{B}p{P}n{new}"))
        else:
            fn = self._get_generate(P, new, do_sample, temperature, top_k,
                                    top_p, with_mask=with_mask,
                                    prefill_chunk=chunk)
            args = (self._params, cache, ids, rng, eos)
            if with_mask:
                args += (mask,)
            report.update(warm(fn, args, f"generate:b{B}p{P}n{new}"))
        return report


def _unflatten_flax_paths(flat):
    """{'a/b/c': array} → nested variables dict, re-rooted under 'params'
    when the export stripped that collection prefix (save_16bit_model
    does).  HF-named keys (dots, no flax structure) raise with guidance."""
    if any("." in k and "/" not in k for k in flat):
        raise ValueError(
            "this file carries HF-named keys (hf_policy export); load it "
            "through module_inject's policy convert + _materialize instead")
    from deepspeed_tpu.compression.helper import unflatten_params
    return unflatten_params(
        {(k if k.startswith("params/") else f"params/{k}"): v
         for k, v in flat.items()})


def require_right_padded(attention_mask):
    """Validate a generation attention_mask at the API boundary: every row
    must be RIGHT-padded (1s then 0s) and non-empty — HF tokenizers default
    decoder-only generation to LEFT padding, which would silently index
    mid-prompt logits, and an all-pad row would condition on pad logits."""
    m = np.asarray(attention_mask)  # tpu-lint: disable=TL001 -- API-boundary validation of the caller's (host) mask, once per generate
    if not (np.diff(m.astype(np.int8), axis=1) <= 0).all():
        raise ValueError(
            "attention_mask must be RIGHT-padded (1s then 0s per row); "
            "re-tokenize with padding_side='right'")
    if (m.sum(axis=1) == 0).any():
        raise ValueError("attention_mask has an all-padding row (empty "
                         "prompt) — drop it before generate()")


class KVCacheWorkspace:
    """Engine-owned persistent KV-cache buffer — the TPU analog of the
    reference's reusable inference workspace
    (``csrc/transformer/inference/includes/inference_context.h:24-87``:
    allocate once, decode into it in place, reallocate only when the
    requested shape changes).  The buffer is DONATED into each generation
    program and reclaimed from its output, so the decode scan updates the
    cache in place instead of entry-copying + double-buffering a fresh
    zeros cache per call (measured ~2x-the-cache compiled temps before,
    ~1x after — see docs/performance.md).

    Stale contents are harmless by construction: every attention path masks
    KV positions beyond each row's live length, so a reused buffer's old
    tokens are never read.
    """

    def __init__(self, module):
        self._module = module
        self._key = None
        self._cache = None

    def take(self, batch_size, max_len, dtype):
        """Hand out the workspace for a ``(B, max_len)`` generation; the
        caller must ``give_back`` the program's output cache (the donated
        input buffer is dead after the call)."""
        key = (int(batch_size), int(max_len), jnp.dtype(dtype).name)
        cache, self._cache = self._cache, None
        if cache is not None and any(
                getattr(l, "is_deleted", lambda: False)()
                for l in jax.tree.leaves(cache)):
            # a generation program that failed AFTER donation leaves the
            # given-back buffers dead — reallocate instead of handing a
            # deleted array to the next program
            cache = None
        if cache is None or self._key != key:
            cache = None                    # drop the old buffer first
            self._key = key
            cache = self._module.init_cache(batch_size, max_len, dtype=dtype)
        return cache

    def give_back(self, cache):
        self._cache = cache

    def release(self):
        """Free the workspace buffer (reference ``release_workspace``)."""
        self._cache = None
        self._key = None


def auto_prefill_chunk(batch_size, prompt_len, token_budget=None):
    """Pick the chunked-prefill chunk size (or None for one-pass prefill):
    chunking pays when the prefill working set ``B x P`` is large enough
    that per-layer transients crowd the KV cache out of HBM (measured
    cliff: bs128 x 256 / bs16 x 4k OOM one-pass but run chunked).  The
    chunk targets ``B x C <= token_budget`` (env
    ``DSTPU_PREFILL_TOKEN_BUDGET``, default 16384 tokens), floored at 128
    and capped at 512 (the kernel's VMEM accumulator bound)."""
    import os
    budget = int(token_budget
                 or os.environ.get("DSTPU_PREFILL_TOKEN_BUDGET", "16384"))
    if batch_size * prompt_len <= budget:
        return None
    c = 512
    while c > 128 and batch_size * c > budget:
        c //= 2
    return c if c < prompt_len else None


def default_prefill_chunk(batch_size, prompt_len):
    """The shared chunk policy (serving + hybrid rollouts): auto chunk
    sizing gated on kernel availability."""
    from deepspeed_tpu.ops.transformer.flash_attention import pallas_supported
    if not pallas_supported():
        return None                      # chunk attention needs the kernel
    return auto_prefill_chunk(batch_size, prompt_len)


def required_cache_len(prompt_len, max_new_tokens, prefill_chunk):
    """KV-workspace length for a generation: chunked prefill right-pads
    the prompt to a chunk multiple and WRITES those pad positions, so the
    cache must cover them — a shorter cache would let XLA clamp the last
    chunk's dynamic_update_slice start and silently overwrite real prompt
    K/V.  (Pad K/V beyond the live region are never read, and decode
    overwrites position ``prompt_len + t`` before reading it.)"""
    base = prompt_len + max_new_tokens
    if prefill_chunk and prefill_chunk < prompt_len:
        padded = -(-prompt_len // prefill_chunk) * prefill_chunk
        base = max(base, padded)
    # multiple of 8: the fused decode kernel's write-stripe outputs are
    # 8-sublane-aligned blocks (positions beyond prompt+new are never
    # attended — length-masked like any unwritten tail)
    return -(-base // 8) * 8


def build_sample_fn(do_sample, temperature, top_k, top_p):
    """The one sampling rule every decode path shares (whole-batch
    generation, hybrid rollouts, the serving decode step): greedy argmax,
    or temperature / top-k / top-p sampling over fp32 logits.  Shared so
    the serving engine's per-slot decode samples BITWISE like
    ``generate()`` does — the scheduler-correctness contract."""

    def sample_fn(logits, rng):
        logits = logits.astype(jnp.float32)
        if not do_sample:
            return jnp.argmax(logits, axis=-1)
        if temperature != 1.0:
            logits = logits / jnp.maximum(temperature, 1e-6)
        if top_k > 0:
            kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
            logits = jnp.where(logits < kth, -1e30, logits)
        if 0.0 < top_p < 1.0:
            sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            cutoff_idx = jnp.sum(cum < top_p, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx, axis=-1)
            logits = jnp.where(logits < cutoff, -1e30, logits)
        return jax.random.categorical(rng, logits, axis=-1)

    return sample_fn


def make_generate_fn(module, compute_dtype, prompt_len, max_new_tokens,
                     do_sample, temperature, top_k, top_p,
                     param_transform=None, with_mask=False,
                     carry_params=None, prefill_chunk=None,
                     external_prefill=False, early_exit=True):
    """Build the jitted generation program: one-pass prefill + lax.scan
    decode loop with greedy / temperature / top-k / top-p sampling.  Shared
    by ``InferenceEngine`` and ``DeepSpeedHybridEngine``.

    ``with_mask=True`` supports RIGHT-padded prompts: ``fn`` takes an
    ``attention_mask`` [B, prompt] and each row continues from its own
    prompt length — generated tokens overwrite the pad slots in the KV
    cache (the live region stays contiguous, which is what the Pallas
    decode kernel's per-row length mask expects), while the returned array
    keeps the HF layout ``[prompt columns..., generated columns...]``.

    The KV cache is an explicit, DONATED argument (allocate it with
    ``module.init_cache``/``KVCacheWorkspace``): the donated buffer aliases
    the output cache, so prefill writes and the decode scan's per-token
    updates all land in one workspace buffer — no entry copy, no
    double-buffered loop carry (the in-place workspace semantics of the
    reference's ``inference_context.h``).

    Returns ``fn(params, cache, input_ids, rng, eos_id[, attention_mask,
    prefill_logits]) -> ([B, prompt+new], cache)``.  The cache must be at
    least ``required_cache_len(prompt_len, max_new_tokens, prefill_chunk)``
    positions long (chunked prefill writes the padded prompt tail).
    ``external_prefill=True`` builds the decode-only program: the caller
    prefilled the cache already (engine split-prefill path) and passes the
    last-position ``prefill_logits`` [B, 1, V].

    ``early_exit=True`` (default) hoists the decode scan into a BOUNDED
    ``lax.while_loop`` that stops once every row is ``done`` — short
    completions no longer pay ``max_new_tokens`` masked decode steps.
    Tokens are bitwise-identical either way (post-done steps emit
    ``eos_id`` in both forms; the output buffer is eos-prefilled), only
    the number of executed decode steps differs.  ``early_exit=False``
    keeps the scan form (``decode_early_exit`` in the inference config)."""

    sample_fn = build_sample_fn(do_sample, temperature, top_k, top_p)

    if carry_params is None:
        carry_params = param_transform is not None

    @hot_path("inference.decode")
    def generate(params, cache, input_ids, rng, eos_id,
                 attention_mask=None, prefill_logits=None):
        deq = param_transform if param_transform is not None else (lambda p: p)
        B = input_ids.shape[0]
        # static guard: an undersized cache would let XLA CLAMP the padded
        # last chunk's write start, silently overwriting real prompt K/V
        min_len = prompt_len + max_new_tokens
        if prefill_chunk and prefill_chunk < prompt_len \
                and not external_prefill:
            min_len = max(min_len,
                          -(-prompt_len // prefill_chunk) * prefill_chunk)
        if cache["k"].shape[-2] < min_len:  # tpu-lint: disable=TL006 -- static under-size guard (raises at build time); each generate program sees one cache shape by construction
            raise ValueError(
                f"KV cache has {cache['k'].shape[-2]} positions but this "
                f"generation needs >= {min_len} (prompt {prompt_len} + new "
                f"{max_new_tokens}, chunked-prefill pad included) — size "
                f"it with required_cache_len()")
        # prefill the prompt in one pass (dequant fused into the prefill),
        # projecting ONLY each row's last real position through the vocab
        # head — full [B, prompt, V] prefill logits are a multi-GB
        # temporary at long prompts/large batches
        if with_mask:
            # right-padded rows: each row's next token comes from its LAST
            # REAL position and decoding continues at per-row offsets
            n = jnp.sum(attention_mask.astype(jnp.int32), axis=1)   # [B]
            last_pos = n - 1
        else:
            n = None
            last_pos = jnp.full((B,), prompt_len - 1, jnp.int32)
        if external_prefill:
            # the caller ran prefill (engine split-prefill path) and hands
            # in the last-position logits; the cache already holds the
            # prompt's K/V
            logits = prefill_logits
        elif prefill_chunk and prefill_chunk < prompt_len:
            # memory-bounded chunked prefill (see Transformer.
            # prefill_chunked): per-layer transients are O(B*chunk), the
            # enabler for big-batch and long-prompt serving points
            logits, cache = module.apply(
                deq(params), input_ids, cache, int(prefill_chunk),
                method=type(module).prefill_chunked, logits_at=last_pos)
        else:
            logits, cache = module.apply(deq(params), input_ids, cache, 0,
                                         method=type(module).decode,
                                         logits_at=last_pos)
        rng, sub = jax.random.split(rng)
        last = logits[:, 0]
        if with_mask:
            pos0 = n
        else:
            # scalar position: keeps the row-uniform cache-write fast path
            pos0 = jnp.asarray(prompt_len, jnp.int32)
        next_tok = sample_fn(last, sub)

        # When the dequant MATERIALIZES full weights (grouped scales,
        # int4, the hybrid rollout view) the quantized tree rides the
        # scan CARRY and is dequantized inside the body: carried values
        # are not loop-invariant to XLA, so the compute-dtype weights
        # stay a per-step temporary instead of a hoisted 2x-size loop
        # constant.  When the dequant FUSES into its consumers
        # (per-channel int8, or no quantization at all), carrying would
        # only copy the full tree into the loop's temp allocation
        # (~1.4 GB at 1.3B int8) on top of the argument buffers — at
        # bs128/seq384 that share of HBM pushed the program into XLA's
        # staging mode and decode collapsed 8x — so those cases close
        # over the argument buffers instead.
        def step(carry, _):
            tok, cache, pos, rng, done, qparams = carry
            p = deq(qparams if carry_params else params)
            logits, cache = module.apply(p, tok[:, None], cache,
                                         pos, method=type(module).decode)
            rng, sub = jax.random.split(rng)
            nxt = sample_fn(logits[:, -1], sub)
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
            return (nxt, cache, pos + 1, rng, done, qparams), nxt

        done0 = (next_tok == eos_id)
        T = max_new_tokens - 1
        if early_exit and T > 0:
            # bounded while_loop in place of the scan: stops the moment
            # every row is done, so a batch of short completions pays only
            # the steps it actually decodes.  Post-done steps emit eos_id
            # (same as the scan form) and the output buffer is prefilled
            # with eos_id, so tokens are bitwise-identical to the scan.
            buf0 = jnp.full((B, T), eos_id).astype(jnp.int32)

            def cond(carry):
                t, _, _, _, _, done, _, _ = carry
                return (t < T) & jnp.logical_not(jnp.all(done))

            def body(carry):
                t, tok, cache, pos, rng, done, qparams, buf = carry
                (tok, cache, pos, rng, done, qparams), nxt = step(
                    (tok, cache, pos, rng, done, qparams), None)
                buf = jax.lax.dynamic_update_slice(
                    buf, nxt.astype(jnp.int32)[:, None], (0, t))
                return (t + 1, tok, cache, pos, rng, done, qparams, buf)

            init = (jnp.asarray(0, jnp.int32), next_tok, cache, pos0, rng,
                    done0, params if carry_params else 0, buf0)
            _, _, cache, _, _, _, _, toks_bt = jax.lax.while_loop(
                cond, body, init)
            out = jnp.concatenate(
                [input_ids, next_tok[:, None], toks_bt], axis=1)
            return out, cache
        (_, cache, _, _, _, _), toks = jax.lax.scan(
            step, (next_tok, cache, pos0, rng, done0,
                   params if carry_params else 0),
            None, length=max_new_tokens - 1)
        # HF contract: prompt + generated tokens
        out = jnp.concatenate([input_ids, next_tok[:, None], toks.T], axis=1)
        return out, cache

    return jax.jit(generate, donate_argnums=(1,))
