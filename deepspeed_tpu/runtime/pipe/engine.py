"""PipelineEngine — pipeline-parallel training engine.

Parity with reference ``runtime/pipe/engine.py:42`` (``PipelineEngine``):
``train_batch``/``eval_batch`` consume gradient-accumulation microbatches and
run them through pipeline stages; ``forward``/``backward`` are disallowed
exactly like the reference (``pipe/engine.py:1107-1118``).

TPU realization: the instruction schedule + p2p machinery is replaced by the
differentiable SPMD pipeline (``parallel/pipeline.py``).  The model arrives
as a ``PipelineModule`` (sequence of LayerSpecs).  Layers are initialized
shape-propagated, then split into:

* ``pre``  — leading layers whose param structure differs from the majority
  (e.g. embeddings) — run under plain GSPMD before the pipelined region;
* ``body`` — the uniform run of identical-structure layers (e.g. transformer
  blocks), stacked ``[P, L/P, ...]`` and sharded over the ``pp`` mesh axis;
* ``post`` — trailing non-uniform layers (final norm, LM head) — run under
  GSPMD after the region.

This is the idiomatic TPU pipeline decomposition: embeddings/heads are
sharded over dp/tp like any other op, while the repeated trunk pipelines.
ZeRO/TP sharding composes: the plan shards body-leaf inner dims over
dp/tp *in addition to* the leading pp dim.
"""

from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel import topology as topo_mod
from deepspeed_tpu.parallel.pipeline import spmd_pipeline, stack_stage_params
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, _opt_state_shardings
from deepspeed_tpu.runtime.pipe.module import PipelineModule
from deepspeed_tpu.runtime.zero.partition import build_sharding_plan, ZeroShardingPlan
from deepspeed_tpu.utils.logging import log_dist


class PipelineEngine(DeepSpeedEngine):

    def __init__(self, model: PipelineModule = None, **kwargs):
        assert isinstance(model, PipelineModule), \
            "PipelineEngine requires a PipelineModule"
        self.pipe_module = model
        # honor PipelineModule(num_stages=...) when the config doesn't set
        # pipeline.stages (reference: module carries the stage count)
        cfg = kwargs.get("config")
        if model.num_stages and isinstance(cfg, dict):
            cfg = dict(cfg)
            pipe_blk = dict(cfg.get("pipeline", {}))
            pipe_blk.setdefault("stages", model.num_stages)
            cfg["pipeline"] = pipe_blk
            kwargs["config"] = cfg
        if model.partition_method not in ("parameters", "uniform"):
            from deepspeed_tpu.utils.logging import warning_once
            warning_once(
                f"partition_method={model.partition_method!r}: the SPMD "
                "pipeline stacks a uniform trunk (equal layers per stage); "
                "type-regex balancing is advisory only")
        super().__init__(model=model, **kwargs)
        self.num_stages = self.topology.pp
        if self.num_stages < 1:
            raise ValueError("pipeline requires pp >= 1 in the mesh")
        self.micro_batches = self.gradient_accumulation_steps()
        C = int(self._config.pipeline.max_in_flight_microbatches or 0)
        if C and self.micro_batches % C != 0:
            raise ValueError(
                f"pipeline.max_in_flight_microbatches={C} must divide "
                f"micro_batches={self.micro_batches}")
        self.max_in_flight = C
        sched = self._config.pipeline.schedule
        if sched not in ("fill_drain", "1f1b"):
            raise ValueError(f"pipeline.schedule must be 'fill_drain' or "
                             f"'1f1b', got {sched!r}")
        if sched == "1f1b" and C:
            raise ValueError(
                "pipeline.schedule='1f1b' already bounds the stash to O(P); "
                "it is mutually exclusive with max_in_flight_microbatches")
        self.pipe_schedule = sched

    # the reference forbids forward/backward/step on the pipeline engine —
    # train_batch is the unit of work (pipe/engine.py:1107-1118)
    def forward(self, *a, **k):
        raise RuntimeError("PipelineEngine does not support forward(); "
                           "use train_batch / eval_batch")

    __call__ = forward

    def backward(self, *a, **k):
        raise RuntimeError("PipelineEngine does not support backward(); "
                           "use train_batch")

    def step(self, *a, **k):
        raise RuntimeError("PipelineEngine does not support step(); "
                           "use train_batch")

    # ------------------------------------------------------------------ #
    def _setup_model_fns(self, model, model_parameters):
        self._is_flax = False
        self._init_fn = None
        self._raw_apply = None   # pipeline path doesn't use the base apply

    def _layer_params_and_apply(self, layer, rng, x_abs, abstract=False):
        """Init one layer against the incoming abstract activation.

        Every returned apply has the uniform signature
        ``apply(params, x, train=True)``; the flag is forwarded only to
        modules whose ``__call__`` declares it (MoE gates switch their
        capacity/noise regime on it, like the dense Transformer).
        ``abstract=True`` shape-evaluates the init instead of running it —
        the checkpoint-restore path needs only structure/shapes and must
        not materialize a throwaway random copy of the model."""
        import inspect
        import flax.linen as nn
        if isinstance(layer, nn.Module):
            if abstract:
                params = jax.eval_shape(
                    lambda r: layer.init(r, _zeros_like_abs(x_abs)), rng)
            else:
                params = layer.init(rng, _zeros_like_abs(x_abs))
            takes_train = "train" in inspect.signature(
                type(layer).__call__).parameters
            if takes_train:
                apply = lambda p, x, train=True: layer.apply(p, x, train=train)
            else:
                apply = lambda p, x, train=True: layer.apply(p, x)
            y_abs = jax.eval_shape(lambda p, x: apply(p, x), params, x_abs)
            return params, apply, y_abs
        # paramless callable
        y_abs = jax.eval_shape(layer, x_abs)
        return None, (lambda p, x, train=True: layer(x)), y_abs

    def _build_pipeline(self, example_micro, abstract=False):
        """Initialize all layers, split pre/body/post, stack body.

        ``TiedLayerSpec`` layers sharing a key share parameters (reference
        ``pipe/module.py:76,406-427``): the second occurrence initializes
        nothing and applies its ``forward_fn`` (or the module's apply) to
        the FIRST occurrence's params — the single GSPMD copy makes the
        reference's tied-grad allreduce unnecessary.  Tied layers must sit
        outside the stacked body (pre/post), which embedding/head tying
        always satisfies."""
        from deepspeed_tpu.runtime.pipe.module import TiedLayerSpec
        layers = self.pipe_module.build_layers()
        specs = self.pipe_module.layer_specs
        rng = jax.random.key(self._config.seed)
        x_abs = jax.eval_shape(lambda b: _first_tensor(b), example_micro)
        inits, applies, structs, reuse_of = [], [], [], []
        tied_first = {}
        for i, (spec, layer) in enumerate(zip(specs, layers)):
            tied_key = spec.key if isinstance(spec, TiedLayerSpec) else None
            if tied_key is not None and tied_key in tied_first:
                src = tied_first[tied_key]
                raw = spec.forward_fn or \
                    (lambda p, x, _l=layer: _l.apply(p, x))
                fwd = lambda p, x, train=True, _raw=raw: _raw(p, x)
                x_abs = jax.eval_shape(lambda p, x: fwd(p, x),
                                       inits[src], x_abs)
                inits.append(None)
                applies.append(fwd)
                structs.append(None)
                reuse_of.append(src)
                continue
            if tied_key is not None:
                tied_first[tied_key] = i
            rng, sub = jax.random.split(rng)
            params, apply, x_abs = self._layer_params_and_apply(
                layer, sub, x_abs, abstract=abstract)
            inits.append(params)
            applies.append(apply)
            structs.append(jax.tree.structure(params)
                           if params is not None else None)
            reuse_of.append(None)
        # majority structure = the pipeline body; the run must be contiguous
        # (stacked SPMD stages execute one uniform layer function)
        from collections import Counter
        counted = Counter(s for s in structs if s is not None)
        body_struct, body_count = counted.most_common(1)[0]
        idxs = [i for i, s in enumerate(structs)
                if s is not None and s == body_struct]
        first, last = idxs[0], idxs[-1]
        if last - first + 1 != body_count:
            gaps = [i for i in range(first, last + 1) if structs[i] != body_struct]
            raise ValueError(
                f"pipeline body (majority layer structure) is not contiguous: "
                f"layers {gaps} interrupt the run {first}..{last}. The SPMD "
                f"pipeline stacks a uniform trunk; move non-uniform layers "
                f"before/after the repeated blocks")
        body_types = {type(layers[i]).__name__ for i in range(first, last + 1)}
        if len(body_types) > 1:
            raise ValueError(
                f"pipeline body layers must be one module type, got {body_types}")
        if body_count % self.topology.pp != 0:
            raise ValueError(
                f"{body_count} pipeline body layers not divisible by "
                f"pp={self.topology.pp} stages")
        tied_sources = {r for r in reuse_of if r is not None}
        if any(reuse_of[i] is not None for i in range(first, last + 1)) or \
                any(first <= s <= last for s in tied_sources):
            raise ValueError(
                "TiedLayerSpec sharing with the pipeline body is "
                "unsupported (neither occurrence may fall in the stacked "
                "trunk); tie embedding/head layers (pre/post) only")

        def outer_entry(i):
            return {"apply": applies[i], "params": inits[i],
                    "layer_idx": i, "reuse_of": reuse_of[i]}

        self._pre = [outer_entry(i) for i in range(first)]
        self._post = [outer_entry(i) for i in range(last + 1, len(layers))]
        self._body_apply = applies[first]
        body_params = [inits[i] for i in range(first, last + 1)]
        if abstract:
            self._body_stacked = jax.eval_shape(
                lambda ps: stack_stage_params(ps, self.topology.pp),
                body_params)
        else:
            self._body_stacked = stack_stage_params(body_params,
                                                    self.topology.pp)
        log_dist(f"pipeline split: {first} pre / {body_count} body "
                 f"({self.topology.pp} stages × {body_count // self.topology.pp}) "
                 f"/ {len(layers) - last - 1} post layers", ranks=[0])

    def _assemble_params(self):
        return {
            "pre": [e["params"] for e in self._pre if e["params"] is not None],
            "body": self._body_stacked,
            "post": [e["params"] for e in self._post if e["params"] is not None],
        }

    def _build_pipe_plan(self, abstract):
        """Sharding plan: body gets pp on dim 0, zero/tp on inner dims
        computed per-stage then shifted right by the two stacked dims."""
        mesh = self.mesh
        zero_cfg = self._config.zero_config

        body_inner = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[2:], a.dtype),
            abstract["body"])
        inner_plan = build_sharding_plan(body_inner, self.topology, zero_cfg)

        def lift(spec_tree):
            return jax.tree.map(lambda s: P(topo_mod.PP_AXIS, None, *s),
                                spec_tree, is_leaf=lambda x: isinstance(x, P))

        outer_plan = build_sharding_plan(
            {"pre": abstract["pre"], "post": abstract["post"]},
            self.topology, zero_cfg)

        param_specs = {"pre": outer_plan.param_specs["pre"],
                       "body": lift(inner_plan.param_specs),
                       "post": outer_plan.param_specs["post"]}
        grad_specs = {"pre": outer_plan.grad_specs["pre"],
                      "body": lift(inner_plan.grad_specs),
                      "post": outer_plan.grad_specs["post"]}
        opt_specs = {"pre": outer_plan.opt_specs["pre"],
                     "body": lift(inner_plan.opt_specs),
                     "post": outer_plan.opt_specs["post"]}
        return ZeroShardingPlan(param_specs, grad_specs, opt_specs, mesh)

    def _build_plan(self, abstract_params):
        """Base-engine hook override: the fresh-engine checkpoint-restore
        paths (``load_checkpoint`` → ``_init_params_from`` /
        ``_metadata_restore_targets``) build the plan from loaded shapes —
        a pipe-structured tree must get the pipe plan (pp-lifted body
        specs), not the flat one."""
        if (isinstance(abstract_params, dict)
                and set(abstract_params) == {"pre", "body", "post"}):
            self._plan = self._build_pipe_plan(abstract_params)
            self._abstract_params = abstract_params
        else:
            super()._build_plan(abstract_params)

    def _lazy_init_pipe(self, batch):
        built = getattr(self, "_body_apply", None) is not None
        if self._params is not None and built:
            return
        micro = jax.tree.map(lambda x: x[0], batch)
        loaded = self._params
        # with params already restored, only structure/shapes are needed —
        # don't materialize a throwaway random init of the whole model
        self._build_pipeline(micro, abstract=loaded is not None)
        raw = self._assemble_params()
        abstract = jax.eval_shape(lambda t: t, raw)
        if loaded is not None:
            # params were restored by load_checkpoint into a fresh engine
            # (which already built the pipe plan + optimizer state from the
            # loaded shapes via _build_plan above); only the module
            # structure — the pre/body/post split and layer applies — was
            # missing.  Keep the loaded params; the just-initialized layer
            # values are discarded.
            if jax.tree.structure(loaded) != jax.tree.structure(abstract):
                raise ValueError(
                    "checkpoint params do not match the pipeline module "
                    "structure (different layer split or layer count)")
            mismatch = [f"{a.shape} vs {b.shape}" for a, b in zip(
                jax.tree.leaves(loaded), jax.tree.leaves(abstract))
                if tuple(a.shape) != tuple(b.shape)]
            if mismatch:
                raise ValueError(
                    f"checkpoint param shapes do not match the pipeline "
                    f"module: {mismatch[:3]}")
            return
        with self._weights_span():
            self._plan = self._build_pipe_plan(abstract)
            self._abstract_params = abstract
            put = jax.jit(lambda t: jax.tree.map(
                lambda p: p.astype(jnp.float32)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, t),
                out_shardings=self._plan.param_shardings)
            self._params = put(raw)
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self._params))
        log_dist(f"pipeline params initialized: {n/1e6:.2f}M "
                 f"across {self.topology.pp} stages", ranks=[0])
        self._init_opt_state()

    # ------------------------------------------------------------------ #
    def _pipe_loss(self, params, batch, rng, num_micro=None, train=True):
        """The full pipelined loss: pre → spmd_pipeline → post → loss_fn.

        ``batch``: pytree with leading [M, mb, ...]; convention (inputs,
        labels) tuple or dict with 'labels'.  Activations may be pytrees
        (MoE trunks carry ``(hidden, aux)``).  Tied layers resolve their
        shared params from the first occurrence (``seen``).
        """
        inputs, labels = _split_batch(batch)
        M = num_micro if num_micro is not None else self.micro_batches
        cast = lambda t: jax.tree.map(
            lambda p: p.astype(self.compute_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, t)
        pre_ps = iter(cast(params["pre"]))
        post_ps = iter(cast(params["post"]))
        seen = {}

        def run_outer(entries, ps, x):
            for e in entries:
                if e["reuse_of"] is not None:
                    p = seen[e["reuse_of"]]
                elif e["params"] is not None:
                    p = next(ps)
                    seen[e["layer_idx"]] = p
                else:
                    p = None
                apply = e["apply"]
                x = jax.vmap(lambda xm: apply(p, xm, train=train))(x)
            return x

        x = run_outer(self._pre, pre_ps, inputs)

        body = cast(params["body"])
        layer_apply = self._body_apply

        def stage_fn(stage_params, xm):
            # one stage = scan over its L/P layers
            def one(h, p):
                return layer_apply(p, h, train=train), None
            out, _ = jax.lax.scan(one, xm, stage_params)
            return out

        ys = spmd_pipeline(stage_fn, body, x, M, self.mesh)
        out = run_outer(self._post, post_ps, ys)

        loss_fn = self.pipe_module.loss_fn or _default_loss
        losses = jax.vmap(loss_fn)(out, labels)
        return jnp.mean(losses.astype(jnp.float32))

    def _pipe_loss_and_grads_1f1b(self, params, batch, scale, train=True):
        """Interleaved 1F1B step: hand-rolled per-tick vjp inside the
        ``spmd_pipeline_1f1b`` region (reference ``TrainSchedule``,
        ``schedule.py:189``).  Boundary layers run INSIDE the region like
        the reference's stage placement — the pre chain (embeddings) on
        stage 0, the post chain + per-microbatch loss on the last stage —
        so each microbatch's backward starts the tick its forward finishes
        and the only M-sized buffers are the raw token ids/labels.
        Returns ``(scaled mean loss, grads)`` with the same semantics as
        differentiating ``mean(loss) * scale``."""
        from deepspeed_tpu.parallel.pipeline import spmd_pipeline_1f1b
        inputs, labels = _split_batch(batch)
        M = self.micro_batches
        cast = lambda t: jax.tree.map(
            lambda p: p.astype(self.compute_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, t)

        def run_chain(entries, ps, x, seen):
            for e in entries:
                if e["reuse_of"] is not None:
                    p = seen[e["reuse_of"]]
                elif e["params"] is not None:
                    p = next(ps)
                    seen[e["layer_idx"]] = p
                else:
                    p = None
                x = e["apply"](p, x, train=train)
            return x

        pre_cast, pre_vjp = jax.vjp(cast, params["pre"])
        body_cast, body_vjp = jax.vjp(cast, params["body"])
        post_cast, post_vjp = jax.vjp(cast, params["post"])

        def first_fn(first_p, in_m):
            return run_chain(self._pre, iter(first_p), in_m, {})

        layer_apply = self._body_apply

        def stage_fn(stage_params, xm):
            def one(h, p):
                return layer_apply(p, h, train=train), None
            out, _ = jax.lax.scan(one, xm, stage_params)
            return out

        loss_fn = self.pipe_module.loss_fn or _default_loss
        # post layers may reuse (tied) pre-layer params: thread ONLY the
        # tied subtrees through the last-stage vjp (an untied model must
        # not pay a second embedding-grad accumulator + pp psum for a
        # gradient that is identically zero)
        pre_param_idx = [e["layer_idx"] for e in self._pre
                         if e["params"] is not None]
        # only PRE-sourced ties need threading; a tie between two post
        # layers resolves naturally inside run_chain's `seen`
        pre_set = set(pre_param_idx)
        tied_idx = sorted({e["reuse_of"] for e in self._post
                           if e["reuse_of"] in pre_set})
        tied_pos = [pre_param_idx.index(i) for i in tied_idx]
        tied_cast = [pre_cast[p] for p in tied_pos]

        def last_fn(last_p, y, label):
            post_params, tied_params = last_p
            seen = dict(zip(tied_idx, tied_params))
            out = run_chain(self._post, iter(post_params), y, seen)
            # mean-reduce: fill-drain computes jnp.mean over vmapped losses,
            # so a per-example loss_fn keeps working under 1f1b too
            return jnp.mean(loss_fn(out, label).astype(jnp.float32))

        loss_sum, gbody_c, gfirst_c, glast_c = spmd_pipeline_1f1b(
            stage_fn, body_cast, first_fn, pre_cast, last_fn,
            (post_cast, tied_cast), inputs, labels, M, self.mesh,
            cotangent_seed=scale / M)
        gpost_c, gtied_c = glast_c
        # pre grads: ring-backward contribution + tied-use contribution
        gpre_c = list(gfirst_c)
        for pos, g in zip(tied_pos, gtied_c):
            gpre_c[pos] = jax.tree.map(jnp.add, gpre_c[pos], g)
        match = lambda g, p: jax.tree.map(
            lambda gl, pl: gl.astype(pl.dtype), g, p)
        (gbody,) = body_vjp(match(gbody_c, body_cast))
        (gpost,) = post_vjp(match(gpost_c, post_cast))
        (gpre,) = pre_vjp(match(gpre_c, pre_cast))
        grads = {"pre": gpre, "body": gbody, "post": gpost}
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        return loss_sum * scale / M, grads

    def _get_fused_step(self):
        key = "fused_pipe_step"
        if key not in self._compiled:
            clip = float(self.gradient_clipping() or 0.0)
            scaler = self.loss_scaler

            def train_step(params, opt_state, scaler_state, lr, step, rng, batch):
                M = self.micro_batches
                C = self.max_in_flight

                def loss_of(p, b, n):
                    return self._pipe_loss(p, b, rng, num_micro=n) \
                        * scaler_state.scale

                if self.pipe_schedule == "1f1b":
                    loss, grads = self._pipe_loss_and_grads_1f1b(
                        params, batch, scaler_state.scale)
                elif C and C < M:
                    # 1F1B-class memory bound: differentiate C microbatches
                    # at a time so at most C stage inputs are stashed; the
                    # scan accumulates grads chunk by chunk (reference
                    # TrainSchedule's in-flight bound, schedule.py:189).
                    n_chunks = M // C
                    chunked = jax.tree.map(
                        lambda l: l.reshape(n_chunks, C, *l.shape[1:]), batch)

                    def one_chunk(gacc, cb):
                        l, g = jax.value_and_grad(loss_of)(params, cb, C)
                        return jax.tree.map(jnp.add, gacc, g), l

                    zeros = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    gsum, ls = jax.lax.scan(one_chunk, zeros, chunked)
                    grads = jax.tree.map(lambda g: g / n_chunks, gsum)
                    loss = jnp.mean(ls)
                else:
                    loss, grads = jax.value_and_grad(loss_of)(params, batch, M)
                found_inf = jnp.logical_not(
                    jnp.all(jnp.stack([jnp.all(jnp.isfinite(g))
                                       for g in jax.tree.leaves(grads)])))
                inv = 1.0 / scaler_state.scale
                grads = jax.tree.map(lambda g: g * inv, grads)
                # norm over the UNSCALED grads (clip would otherwise divide
                # by the loss scale)
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                     for g in jax.tree.leaves(grads)))
                if clip > 0.0:
                    factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree.map(lambda g: g * factor, grads)
                new_params, new_opt = self.optimizer.update(
                    grads, opt_state, params, lr=lr, step=step)
                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(found_inf, o, n), new, old)
                new_params = keep(new_params, params)
                new_opt = keep(new_opt, opt_state)
                new_scaler = scaler.update(scaler_state, found_inf)
                return new_params, new_opt, new_scaler, loss * inv, gnorm

            self._compiled[key] = jax.jit(
                train_step,
                donate_argnums=(0, 1, 2),
                out_shardings=(self._plan.param_shardings, self._opt_shardings,
                               None, None, None))
        return self._compiled[key]

    def train_batch(self, data_iter=None, batch=None):
        """One pipelined optimizer step over ``micro_batches`` microbatches
        (reference ``pipe/engine.py:286``)."""
        M = self.micro_batches
        if batch is None:
            mbs = [next(data_iter) for _ in range(M)]
            batch = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                                 *mbs)
        batch = jax.tree.map(jnp.asarray, batch)
        self._lazy_init_pipe(batch)
        self.tput_timer.start()
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        step_no = jnp.asarray(self.global_steps + 1, jnp.int32)
        self._rng, rng = jax.random.split(self._rng)
        (self._params, self._opt_state, self._scaler_state, loss, gnorm) = \
            self._get_fused_step()(self._params, self._opt_state,
                                   self._scaler_state, lr, step_no, rng, batch)
        self._last_global_grad_norm = gnorm
        self._last_loss = loss
        self.global_steps += 1
        self.micro_steps += M
        self.global_samples += self.train_batch_size()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self.tput_timer.stop(global_step=True)
        return loss

    def eval_batch(self, data_iter=None, batch=None):
        M = self.micro_batches
        if batch is None:
            mbs = [next(data_iter) for _ in range(M)]
            batch = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]),
                                 *mbs)
        batch = jax.tree.map(jnp.asarray, batch)
        self._lazy_init_pipe(batch)
        key = "eval_pipe"
        if key not in self._compiled:
            self._compiled[key] = jax.jit(
                lambda p, b, r: self._pipe_loss(p, b, r, train=False))
        self._rng, rng = jax.random.split(self._rng)
        return self._compiled[key](self._params, batch, rng)


def _default_loss(out, labels):
    from deepspeed_tpu.models.transformer import cross_entropy_loss
    if jnp.issubdtype(jnp.asarray(labels).dtype, jnp.integer) and out.ndim >= 2:
        return cross_entropy_loss(out, labels)
    return jnp.mean((out - labels) ** 2)


def _split_batch(batch):
    """Pipeline layers pass a single activation tensor, so inputs reduce to
    the token array; attention_mask (if any) only shapes the labels."""
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        return batch[0], batch[1]
    if isinstance(batch, dict):
        labels = batch.get("labels")
        mask = batch.get("attention_mask")
        inputs = {k: v for k, v in batch.items()
                  if k not in ("labels", "attention_mask")}
        if len(inputs) == 1:
            inputs = next(iter(inputs.values()))
        elif "input_ids" in inputs:
            inputs = inputs["input_ids"]
        else:
            raise ValueError(
                f"pipeline batch dict must contain a single input tensor or "
                f"'input_ids'; got keys {sorted(batch)}")
        if labels is None:
            from deepspeed_tpu.models.transformer import derive_causal_labels
            labels = derive_causal_labels(inputs, mask)
        return inputs, labels
    raise ValueError("pipeline batch must be (inputs, labels) or a dict")


def _zeros_like_abs(abs_tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abs_tree)


def _first_tensor(b):
    if isinstance(b, (tuple, list)):
        return jnp.asarray(b[0])
    if isinstance(b, dict):
        return jnp.asarray(b.get("input_ids", next(iter(b.values()))))
    return jnp.asarray(b)
