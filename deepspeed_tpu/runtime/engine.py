"""DeepSpeedEngine — the training engine façade over jitted XLA programs.

TPU-native re-design of reference ``runtime/engine.py:181`` (DeepSpeedEngine).
The imperative 3-call API is preserved::

    loss = engine(batch)        # forward
    engine.backward(loss)       # gradient production + accumulation
    engine.step()               # optimizer update at the GAS boundary

but the implementation is functional: params / optimizer state / gradient
accumulators are sharded ``jax.Array`` pytrees placed by the ZeRO sharding
plan (see ``runtime/zero/partition.py``), and each phase is ONE compiled XLA
program:

* ``forward``+``backward`` together run a jitted ``value_and_grad`` with
  gradient out-shardings = the ZeRO-2 scattered layout, so XLA lowers the
  grad reduction to overlapped reduce-scatters (what the reference builds by
  hand with IPG buckets + comm streams, ``stage_1_and_2.py:833,900``).
* ``step`` runs a jitted, donated update: unscale → global-norm clip →
  fused optimizer → loss-scale update, skipped branch-free on overflow
  (reference ``stage_1_and_2.py:1642,1791,1808``).
* ``train_batch`` additionally offers the fully-fused whole-step program
  (forward+backward over all accumulation micro-batches via ``lax.scan`` +
  update) — the maximum-overlap hot path used by benchmarks, with the same
  semantics as the 3-call sequence.

Model protocol: a flax ``nn.Module`` (``.init``/``.apply``) or a plain
``apply_fn(params, batch, rng) -> loss``.  Parameters are *born sharded* —
initialization is jitted with the plan's out-shardings, the analog of
``zero.Init`` (reference ``partition_parameters.py:603``) without the
monkey-patching.
"""

import os
import inspect
from contextlib import contextmanager
from typing import Any, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm as dist
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.monitor.monitor import MonitorMaster
from deepspeed_tpu.monitor.trace import ready_line, span
from deepspeed_tpu.parallel import topology as topo_mod
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import OrbaxCheckpointEngine
from deepspeed_tpu.runtime.fp16.loss_scaler import create_loss_scaler
from deepspeed_tpu.runtime.lr_schedules import build_lr_scheduler
from deepspeed_tpu.runtime.optimizers import build_optimizer
from deepspeed_tpu.runtime.zero.partition import build_sharding_plan
from deepspeed_tpu.tools.lint.hotpath import hot_path
from deepspeed_tpu.utils.logging import logger, log_dist
from deepspeed_tpu.utils.timer import (SynchronizedWallClockTimer, ThroughputTimer,
                                       FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                                       STEP_GLOBAL_TIMER)

MEMORY_OPT_ALLREDUCE_SIZE = 500_000_000

# _pending marker: this micro's gradients were already added into the
# running accumulator by the fused forward program (see forward())
_ACCUMULATED = object()


def _finish_grads(grads, acc_dt):
    """Shared epilogue of every backward variant: cast to the accumulation
    dtype and derive the overflow flag (one place — the grouped and
    one-pass paths must never diverge here)."""
    grads = jax.tree.map(lambda g: g.astype(acc_dt), grads)
    leaves = jax.tree.leaves(grads)
    found_inf = jnp.logical_not(jnp.all(jnp.stack(
        [jnp.all(jnp.isfinite(g)) for g in leaves])))
    return grads, found_inf


def _unscale_and_clip(grads, scale, clip):
    """Unscale by the loss scale, compute the global grad norm, clip
    (reference ``stage_1_and_2.py:1791`` unscale_and_clip_grads)."""
    inv = 1.0 / scale
    grads = jax.tree.map(lambda g: g * inv, grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    if clip > 0.0:
        factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
        grads = jax.tree.map(lambda g: g * factor, grads)
    return grads, gnorm


def _bytes_on_first_device(tree):
    """Bytes of ``tree``'s arrays on the first device that holds them."""
    total = 0
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            total += x.addressable_shards[0].data.nbytes
    return total


def _tokens_on_first_device(batches):
    """Tokens of one micro-batch of ``batches`` (``[gas, rows, ...]``
    arrays, placed) on the first device that holds them."""
    return max(int(np.prod(x.addressable_shards[0].data.shape[1:]))
               for x in jax.tree.leaves(batches))


def _remat_record(cfg, rung, tokens, **read):
    """``remat_choice()``'s record: what rung ``rung`` of the model's remat
    ladder saves on a device for ``tokens`` tokens, and what the fit
    ``read``."""
    from deepspeed_tpu.models.transformer import remat_rung_names
    return {"rung": rung, "remat_saved": list(remat_rung_names(rung)),
            "remat_saved_bytes": cfg.remat_saved_bytes(tokens, rung),
            "step_peak_bytes": None, "bytes_limit": 0, "rungs_tried": [rung],
            **read}


def _flat_args(record):
    """A record's values as span args (lists joined)."""
    return {k: ",".join(map(str, v)) if isinstance(v, list) else v
            for k, v in (record or {}).items()}


def _is_flax_module(model):
    try:
        import flax.linen as nn
        return isinstance(model, nn.Module)
    except ImportError:
        return False


class DeepSpeedEngine:
    """Training engine (reference ``engine.py:181``)."""

    def __init__(self,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 collate_fn=None,
                 config=None,
                 config_class: Optional[DeepSpeedConfig] = None,
                 topology: Optional[topo_mod.ParallelTopology] = None,
                 loss_fn=None,
                 dont_change_device=False):
        self.module = model
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_dataloader = None
        self.loss_fn = loss_fn

        dist.init_distributed()

        # ---- config + topology -------------------------------------- #
        raw = config if isinstance(config, dict) else {}
        if isinstance(config, str):
            import json
            with open(config) as f:
                raw = json.load(f)
        tp = raw.get("tensor_parallel", {}).get("tp_size", 1)
        pp = raw.get("pipeline", {}).get("stages", 1) if isinstance(raw.get("pipeline"), dict) else 1
        sp = raw.get("sequence_parallel", {}).get("sp_size", 1)
        ep = raw.get("moe", {}).get("ep_size", 1)
        mics = raw.get("zero_optimization", {}).get("mics_shard_size", 0)
        if topology is not None:
            self.topology = topo_mod.set_topology(topology)
        else:
            self.topology = topo_mod.initialize_topology(tp=tp, pp=pp, sp=sp,
                                                         ep=ep, mics=mics)
        self.mesh = self.topology.mesh

        if config_class is not None:
            self._config = config_class
        else:
            self._config = DeepSpeedConfig(raw if raw else config,
                                           mesh_world_size=self.topology.dp)
        dist.configure(self._config)

        # ---- engine state -------------------------------------------- #
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self._skipped_steps = 0
        self._pending_inf_flags = []   # device overflow flags, drained lazily
        self.training = True
        self._params = None            # master (fp32) param pytree, sharded
        self._opt_state = None
        self._grad_acc = None          # accumulated grads (fp32, ZeRO-sharded)
        self._found_inf_acc = None
        self._plan = None
        self._compiled = {}
        self._last_loss = None
        self.warn_unscaled_loss = True
        # persistent compile/executable cache (runtime/compile_cache.py):
        # None = disabled, the plain jit path below is untouched
        from deepspeed_tpu.runtime.compile_cache import ProgramCache
        self._program_cache = ProgramCache.from_config(
            getattr(self._config, "compile_cache", None))
        self._train_aot = {}     # abstract signature -> AOT executable
        self._remat_choice = None   # remat_choice(): what "fit" chose
        self._remat_limit = None    # the device's bytes_limit, once read

        # ZeRO-Offload (reference stage_1_and_2.py:1037 CPU-offload path /
        # stage3.py:1637 NVMe): host-resident fp32 masters + moments stepped
        # by the native C++ Adam; device keeps bf16 working params only.
        off = self._config.zero_config.offload_optimizer
        self._offload_cfg = off if (off is not None and off.device != "none") else None
        self._host_opt = None

        self.optimizer = self.client_optimizer or build_optimizer(self._config.optimizer)
        if self._offload_cfg is not None and self.optimizer is not None and \
                "adam" not in type(self.optimizer).__name__.lower():
            # the host kernel implements Adam/AdamW only — replacing a
            # non-Adam optimizer silently would change the training
            # trajectory (reference validates the offload optimizer)
            raise ValueError(
                "zero_optimization.offload_optimizer requires an Adam-family "
                f"optimizer, got {type(self.optimizer).__name__}")
        self.lr_scheduler = self.client_lr_scheduler or build_lr_scheduler(
            self._config.scheduler, self.optimizer)
        self.loss_scaler = create_loss_scaler(self._config.fp16)
        self._scaler_state = self._replicate(self.loss_scaler.init())

        # precision
        if self._config.fp16.enabled:
            self.compute_dtype = jnp.float16
        elif self._config.bf16.enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        # persistent master-param storage dtype (fp32 unless the memory-lean
        # bf16 master option is on; optimizer math stays fp32 either way)
        self._master_dtype = jnp.bfloat16 \
            if (self._config.bf16.enabled
                and self._config.bf16.master_weights_in_bf16) else jnp.float32
        if self._config.bf16.master_weights_in_bf16 \
                and not self._config.bf16.enabled:
            logger.warning(
                "bf16.master_weights_in_bf16 is set but bf16.enabled is "
                "false — masters stay fp32; the memory-lean mode requires "
                "bf16 compute")

        accel = get_accelerator()
        accel.manual_seed(self._config.seed)
        self._rng = jax.random.key(self._config.seed)

        self.monitor = MonitorMaster(self._config.monitor_config)
        self.timers = SynchronizedWallClockTimer()

        # Curriculum learning (reference engine.py:1700-1708 curriculum_seqlen
        # kwarg injection): here the engine slices the batch's sequence axis
        # to the scheduler's current difficulty before the jitted step — each
        # quantised seqlen is its own cached XLA program.
        self.curriculum_scheduler = None
        if self._config.curriculum_learning_legacy.enabled:
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler import \
                CurriculumScheduler
            cl = self._config.curriculum_learning_legacy
            self.curriculum_scheduler = CurriculumScheduler({
                "min_difficulty": cl.min_difficulty,
                "max_difficulty": cl.max_difficulty,
                "schedule_type": cl.schedule_type,
                "schedule_config": cl.schedule_config,
            })
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self.steps_per_print())

        # model adapter
        self._setup_model_fns(model, model_parameters)

        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)

        # reference engine.py:858 _configure_checkpointing: nebula block
        # selects the async tiered engine
        if getattr(self._config, "nebula_config", None) is not None \
                and self._config.nebula_config.enabled:
            from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine \
                import NebulaCheckpointEngine
            self.checkpoint_engine = NebulaCheckpointEngine(
                self._config.nebula_config)
        else:
            self.checkpoint_engine = OrbaxCheckpointEngine()
        self.flops_profiler = None
        if self._config.flops_profiler.enabled:
            from deepspeed_tpu.profiling.flops_profiler.profiler import FlopsProfiler
            self.flops_profiler = FlopsProfiler(self)

        log_dist(f"DeepSpeedEngine configured: zero_stage={self.zero_optimization_stage()} "
                 f"mesh={dict(self.mesh.shape)} dtype={self.compute_dtype.__name__} "
                 f"micro_bs={self.train_micro_batch_size_per_gpu()} "
                 f"gas={self.gradient_accumulation_steps()}", ranks=[0])

    # ------------------------------------------------------------------ #
    # Config property accessors (reference engine.py:456-825)
    # ------------------------------------------------------------------ #
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def zero_optimization_stage(self):
        return self._config.zero_config.stage

    def zero_optimization(self):
        return self._config.zero_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def steps_per_print(self):
        return self._config.steps_per_print

    def fp16_enabled(self):
        return self._config.fp16.enabled

    def bfloat16_enabled(self):
        return self._config.bf16.enabled

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def get_global_grad_norm(self):
        return getattr(self, "_last_global_grad_norm", None)

    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()
        lr = getattr(self.optimizer, "lr", 0.0)
        return [lr]

    def learning_rate(self):
        return self.get_lr()[0]

    @property
    def communication_data_type(self):
        return self._config.communication_data_type

    def train(self, mode=True):
        self.training = mode
        return self

    def eval(self):
        return self.train(False)

    # ------------------------------------------------------------------ #
    # Model adapter + lazy sharded init (zero.Init analog)
    # ------------------------------------------------------------------ #
    def _setup_model_fns(self, model, model_parameters):
        self._is_flax = _is_flax_module(model)
        if self._is_flax:
            self._raw_apply = model.apply
            self._init_fn = model.init
        elif callable(model):
            self._raw_apply = model
            self._init_fn = getattr(model, "init", None)
        elif model is None and model_parameters is not None and self.loss_fn is not None:
            self._raw_apply = self.loss_fn
            self._init_fn = None
        else:
            raise ValueError("model must be a flax Module or callable apply_fn")

        if model_parameters is not None and not _is_generator(model_parameters):
            self._init_params_from(model_parameters)

    def _apply_model(self, params, args, kwargs, rng, train, apply=None):
        """Call the model with compute-dtype params (mixed precision: master
        fp32 params cast at use — the bf16/fp16 cast the reference does once
        at wrap time, ``engine.py:1020``).  ``apply``: the module's apply at
        a fitted remat rung (``_get_fused_step``); default the module's own."""
        apply = apply or self._raw_apply
        cast = jax.tree.map(
            lambda p: p.astype(self.compute_dtype)
            if (hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating)) else p,
            params)
        if self._is_flax:
            kw = dict(kwargs)
            if train:
                kw.setdefault("rngs", {"dropout": rng})
            try:
                out = apply(cast, *args, **kw)
            except TypeError:
                kw.pop("rngs", None)
                out = apply(cast, *args, **kw)
        else:
            out = apply(cast, *args, **kwargs)
        return out

    def _extract_loss(self, out):
        if isinstance(out, tuple):
            return out[0], out[1:]
        return out, ()

    def _init_params_from(self, params, materialize_opt=True):
        """Place user-provided params: cast to fp32 master, shard per plan.
        ``materialize_opt=False`` computes optimizer shardings only (the
        caller will install loaded state) — no fresh m/v allocation."""
        with self._weights_span():
            abstract = jax.eval_shape(lambda t: jax.tree.map(
                lambda p: p.astype(self._master_dtype)
                if jnp.issubdtype(jnp.asarray(p).dtype, jnp.floating) else jnp.asarray(p),
                t), params)
            self._build_plan(abstract)
            put = jax.jit(
                lambda t: jax.tree.map(
                    lambda p: p.astype(self._master_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p, t),
                out_shardings=self._plan.param_shardings)
            self._params = put(params)
        self._init_opt_state(materialize=materialize_opt)

    @contextmanager
    def _weights_span(self):
        """``dstpu.setup.weights`` around the plan and the parameters'
        sharded init or placement; sized when they are there."""
        with span("dstpu.setup.weights", cat="setup") as sp:
            yield
            leaves = jax.tree.leaves(self._params)
            split = any(not l.sharding.is_fully_replicated for l in leaves)
            sp.set(bytes=sum(l.nbytes for l in leaves), leaves=len(leaves),
                   sharded=1 if split else 0)

    def _build_plan(self, abstract_params):
        self._plan = build_sharding_plan(abstract_params, self.topology,
                                         self._config.zero_config)
        self._abstract_params = abstract_params

    def _init_opt_state(self, materialize=True):
        if self._offload_cfg is not None:
            from deepspeed_tpu.runtime.zero.offload import HostOffloadedAdam
            opt = self.optimizer
            self._host_opt = HostOffloadedAdam(
                self._abstract_params, self._offload_cfg,
                lr=getattr(opt, "lr", 1e-3),
                betas=(getattr(opt, "beta1", 0.9), getattr(opt, "beta2", 0.999)),
                eps=getattr(opt, "eps", 1e-8),
                weight_decay=getattr(opt, "weight_decay", 0.0),
                adamw_mode=getattr(opt, "adam_w_mode", True),
                bias_correction=getattr(opt, "bias_correction", True))
            self._host_opt.init_from_params(self._params)
            # downcast device params to the compute dtype: the HBM saving
            # that is the point of offload (masters now live on host)
            cast = jax.jit(
                lambda t: jax.tree.map(
                    lambda p: p.astype(self.compute_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p, t),
                out_shardings=self._plan.param_shardings,
                donate_argnums=(0,))
            self._params = cast(self._params)
            self._opt_state = None
            self._opt_shardings = None
            return
        abstract_opt = jax.eval_shape(self.optimizer.init, self._abstract_params)
        self._opt_shardings = _opt_state_shardings(
            abstract_opt, self._abstract_params, self._plan.opt_specs, self.mesh)
        if not materialize:        # caller installs loaded state itself
            self._abstract_opt = abstract_opt
            return
        with span("dstpu.setup.optimizer_state", cat="setup") as sp:
            init_jit = jax.jit(self.optimizer.init,
                               out_shardings=self._opt_shardings)
            self._opt_state = init_jit(self._params)
            sp.set(bytes=sum(l.nbytes
                             for l in jax.tree.leaves(self._opt_state)))

    def _lazy_init(self, args, kwargs):
        """First-forward param init, jitted with sharded out_shardings so
        full weights never materialize on one device (zero.Init analog,
        reference ``partition_parameters.py:603``)."""
        if self._params is not None:
            return
        if self._init_fn is None:
            raise RuntimeError("no parameters: pass model_parameters or use a flax module")
        with self._weights_span():
            self._rng, init_rng = jax.random.split(self._rng)
            abstract = jax.eval_shape(lambda r: self._init_fn(r, *args, **kwargs), init_rng)
            abstract = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, self._master_dtype
                    if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
                abstract)
            self._build_plan(abstract)
            init_jit = jax.jit(
                lambda r, a, kw: jax.tree.map(
                    lambda p: p.astype(self._master_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p,
                    self._init_fn(r, *a, **kw)),
                out_shardings=self._plan.param_shardings)
            self._params = init_jit(init_rng, args, kwargs)
        n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(self._params))
        log_dist(f"initialized {n_params/1e6:.2f}M parameters (sharded at birth)", ranks=[0])
        self._init_opt_state()

    # ------------------------------------------------------------------ #
    # Data placement
    # ------------------------------------------------------------------ #
    def _replicate(self, tree):
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sh), tree)

    def _data_sharding(self, ndim):
        parts = [topo_mod.DP_AXES]
        if self.topology.sp > 1 and ndim >= 2:
            parts.append(topo_mod.SP_AXIS)
        return NamedSharding(self.mesh, P(*parts))

    def put_batch(self, batch):
        """Shard a host batch across the DP (and sp) mesh axes."""
        def put(x):
            x = jnp.asarray(x) if not isinstance(x, jax.Array) else x
            if x.ndim == 0:  # tpu-lint: disable=TL006 -- rank probe for scalar placement; a workload's batch ranks are fixed, not per-step drift
                return jax.device_put(x, NamedSharding(self.mesh, P()))  # tpu-lint: disable=TL010,TL011 -- rank-0 host scalars replicate by definition, and this put is the batch's host->device ADMISSION, not a reshard
            return jax.device_put(x, self._data_sharding(x.ndim))  # tpu-lint: disable=TL011 -- host->device batch admission: the input starts on the host and this is its one placement into the DP/sp layout
        return jax.tree.map(put, batch)

    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, num_workers=0):
        """Build the sharded training loader (reference ``engine.py:1571``)."""
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        return DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or self.train_micro_batch_size_per_gpu() * self.topology.dp,
            collate_fn=collate_fn,
            num_workers=num_workers,
            engine=self)

    # ------------------------------------------------------------------ #
    # forward / backward / step
    # ------------------------------------------------------------------ #
    @hot_path("runtime.fwd_bwd")
    def _fwd_bwd_core(self, params, scale, rng, *args, **kwargs):
        """Traced body shared by ``_get_fwd_bwd`` (fresh grads) and
        ``_get_fwd_bwd_acc`` (fused accumulate)."""
        gas = self.gradient_accumulation_steps()

        def loss_of(p):
            out = self._apply_model(p, args, kwargs, rng, train=True)
            loss, aux = self._extract_loss(out)
            # reference engine.py:1821: scale loss by 1/GAS
            scaled = loss.astype(jnp.float32) * scale / gas
            return scaled, (loss, aux)

        grads, (loss, aux) = jax.grad(loss_of, has_aux=True)(params)
        # grad accumulation dtype: fp32 by default even when working
        # params are 16-bit (offload path; reference stage_1_and_2.py
        # fp32 accum); ``data_types.grad_accum_dtype: "bf16"`` halves the
        # accumulator — the enabler for 2.7B-class offload on a 16 GB
        # chip, at the documented cost of bf16 addition noise across the
        # accumulation window (reference data_types knob)
        grads, found_inf = _finish_grads(grads, self._accum_dtype())
        return grads, loss, found_inf

    def _get_fwd_bwd(self):
        key = "fwd_bwd"
        if key not in self._compiled:
            self._compiled[key] = jax.jit(  # tpu-lint: disable=TL002 -- params must stay live: the same buffers feed every micro-step and the optimizer step
                self._fwd_bwd_core,
                out_shardings=(self._plan.grad_shardings,
                               NamedSharding(self.mesh, P()),
                               NamedSharding(self.mesh, P())))
        return self._compiled[key]

    def _accum_dtype(self):
        table = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                 "fp16": jnp.float16, "float16": jnp.float16,
                 "fp32": jnp.float32, "float32": jnp.float32}
        want = self._config.gradient_accumulation_dtype or "fp32"
        if want not in table:
            raise ValueError(
                f"data_types.grad_accum_dtype={want!r}: expected "
                f"one of {sorted(table)} (or null = fp32)")
        return table[want]

    def _group_bounds(self, n_groups):
        """Contiguous leaf-index ranges of ~equal parameter bytes for the
        partitioned backward (zero_optimization.grad_partition_groups)."""
        sizes = [int(np.prod(l.shape)) * l.dtype.itemsize
                 for l in jax.tree.leaves(self._params)]
        total = sum(sizes)
        bounds, lo, acc = [], 0, 0
        for i, s in enumerate(sizes):
            acc += s
            if acc >= total * (len(bounds) + 1) / n_groups \
                    and len(bounds) < n_groups - 1:
                bounds.append((lo, i + 1))
                lo = i + 1
        bounds.append((lo, len(sizes)))
        return [b for b in bounds if b[0] < b[1]]

    def _get_fwd_bwd_group(self, lo, hi):
        """Partitioned backward: gradients for leaves [lo:hi) only — the
        other parameters enter the loss as constants, so this program's
        gradient temporaries are ~1/N of the tree.  Each group re-runs
        the forward+backward sweep (FLOPs for memory — the trade that
        fits 2.7B's boundary on one 16 GB chip, where the step is
        host-link-bound anyway)."""
        key = ("fwd_bwd_group", lo, hi)
        if key not in self._compiled:
            gas = self.gradient_accumulation_steps()
            acc_dt = self._accum_dtype()

            def fwd_bwd_g(params, acc_slice, scale, rng, *args, **kwargs):
                flat, treedef = jax.tree_util.tree_flatten(params)

                def loss_of(group):
                    flat2 = list(flat)
                    flat2[lo:hi] = group
                    p = jax.tree_util.tree_unflatten(treedef, flat2)
                    out = self._apply_model(p, args, kwargs, rng,
                                            train=True)
                    loss, aux = self._extract_loss(out)
                    return loss.astype(jnp.float32) * scale / gas, loss

                grads, loss = jax.grad(loss_of, has_aux=True)(flat[lo:hi])
                grads, found_inf = _finish_grads(grads, acc_dt)
                acc_slice = [a + g for a, g in zip(acc_slice, grads)]
                return acc_slice, loss, found_inf

            gshard = jax.tree.leaves(self._plan.grad_shardings)[lo:hi]
            self._compiled[key] = jax.jit(
                fwd_bwd_g,
                donate_argnums=(1,),
                out_shardings=(gshard,
                               NamedSharding(self.mesh, P()),
                               NamedSharding(self.mesh, P())))
        return self._compiled[key]

    def _forward_grouped(self, n_groups, step_rng, args, kwargs):
        """One micro-step through the partitioned backward (see
        ``_get_fwd_bwd_group``): every group pass adds its gradient slice
        into the running accumulator in place."""
        if self._grad_acc is None:
            if "acc_zeros" not in self._compiled:
                acc_dt = self._accum_dtype()
                # close over SHAPES only — capturing the live param arrays
                # would pin this window's params forever (they are
                # replaced every optimizer step)
                shapes = jax.tree.map(lambda l: l.shape, self._params)
                self._compiled["acc_zeros"] = jax.jit(
                    lambda: jax.tree.map(
                        lambda s: jnp.zeros(s, acc_dt), shapes,
                        is_leaf=lambda x: isinstance(x, tuple)),
                    out_shardings=self._plan.grad_shardings)
            self._grad_acc = self._compiled["acc_zeros"]()
            self._found_inf_acc = jnp.asarray(False)
        flat_acc, acc_def = jax.tree_util.tree_flatten(self._grad_acc)
        self._grad_acc = None              # detach before donating calls
        loss = found = None
        try:
            for lo, hi in self._group_bounds(n_groups):
                new_slice, loss, fi = self._get_fwd_bwd_group(lo, hi)(
                    self._params, flat_acc[lo:hi], self._scaler_state.scale,
                    step_rng, *args, **kwargs)
                flat_acc[lo:hi] = list(new_slice)
                found = fi if found is None else jnp.logical_or(found, fi)
        except BaseException:
            # a failed pass leaves donated (dead) slices behind — keep the
            # accumulator detached (None) so the next micro-step restarts
            # the window instead of feeding deleted buffers back in
            self._grad_acc = None
            raise
        self._grad_acc = jax.tree_util.tree_unflatten(acc_def, flat_acc)
        return loss, found

    def _get_fwd_bwd_acc(self):
        """Fused gradient-compute + accumulate: like ``_get_fwd_bwd`` but
        the running accumulator rides in as a DONATED argument and the
        program returns ``acc + grads`` — the fresh gradient tree never
        coexists with params AND the accumulator as a third full-size
        tree (see forward())."""
        key = "fwd_bwd_acc"
        if key not in self._compiled:
            fwd_bwd_core = self._fwd_bwd_core

            @hot_path("runtime.fwd_bwd_acc")
            def fwd_bwd_acc(params, acc, scale, rng, *args, **kwargs):
                grads, loss, found_inf = fwd_bwd_core(params, scale, rng,
                                                      *args, **kwargs)
                acc = jax.tree.map(jnp.add, acc, grads)
                return acc, loss, found_inf

            self._compiled[key] = jax.jit(
                fwd_bwd_acc,
                donate_argnums=(1,),
                out_shardings=(self._plan.grad_shardings,
                               NamedSharding(self.mesh, P()),
                               NamedSharding(self.mesh, P())))
        return self._compiled[key]

    def _get_fwd_only(self):
        key = "fwd_only"
        if key not in self._compiled:
            def fwd(params, rng, *args, **kwargs):
                return self._apply_model(params, args, kwargs, rng, train=False)
            self._compiled[key] = jax.jit(fwd)  # tpu-lint: disable=TL002 -- eval forward: params are read-only and stay live for the next step
        return self._compiled[key]

    def _get_accum(self):
        key = "accum"
        if key not in self._compiled:
            self._compiled[key] = jax.jit(
                lambda acc, g: jax.tree.map(jnp.add, acc, g),
                donate_argnums=(0,))
        return self._compiled[key]

    def _curriculum_slice(self, batch, lead_dims):
        """Slice the sequence axis of every leaf to the scheduler's current
        difficulty (reference engine.py:1700-1708 injects curriculum_seqlen;
        here the engine slices directly).  Only axes beyond the leading
        ``lead_dims`` batch axes whose length equals the reference sequence
        length (taken from ``input_ids``) are sliced — square attention
        masks get both seq axes sliced, hidden dims are untouched.
        Init must happen on the full-length batch *before* this runs."""
        if (self.curriculum_scheduler is None or not self.training
                or self._config.curriculum_learning_legacy.curriculum_type != "seqlen"):
            return batch
        seqlen = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
        ref_seq = None
        if isinstance(batch, dict) and "input_ids" in batch:
            ref_seq = batch["input_ids"].shape[-1]
        if ref_seq is None or seqlen >= ref_seq:
            return batch

        def slc(x):
            if getattr(x, "ndim", 0) <= lead_dims:
                return x
            idx = tuple(
                slice(0, seqlen) if d >= lead_dims and x.shape[d] == ref_seq
                else slice(None) for d in range(x.ndim))
            return x[idx]

        return jax.tree.map(slc, batch)

    def _maybe_start_profiler(self, batch):
        """Start the flops profiler at the configured step (reference
        ``engine.py:1692``); training steps only."""
        if self.flops_profiler is not None \
                and not self.flops_profiler.started and self.training \
                and self.global_steps + 1 == \
                self._config.flops_profiler.profile_step:
            self.flops_profiler.start_profile()
            self._profile_batch = batch

    def _maybe_finish_profiler(self):
        """Stop + print when the profiled step completes (reference: the
        profile step's report prints at the end of its step)."""
        if self.flops_profiler is not None and self.flops_profiler.started:
            pcfg = self._config.flops_profiler
            # the step is dispatched, not done: fence it, so that the
            # clock and the trace hold the whole of it
            jax.block_until_ready(self._params)  # tpu-lint: disable=TL001 -- the profiled step only
            self.flops_profiler.stop_profile()
            self.flops_profiler.print_model_profile(
                profile_step=pcfg.profile_step,
                module_depth=pcfg.module_depth,
                top_modules=pcfg.top_modules,
                detailed=pcfg.detailed,
                output_file=pcfg.output_file,
                batch=getattr(self, "_profile_batch", None))

    @hot_path("runtime.forward")
    def forward(self, *args, **kwargs):
        self._lazy_init(args, kwargs)
        args = tuple(self._curriculum_slice(a, 1) if _is_batch_like(a) else a
                     for a in args)
        kwargs = {k: self._curriculum_slice(v, 1) if _is_batch_like(v) else v
                  for k, v in kwargs.items()}
        # capture the batch AFTER curriculum slicing so the profiled program
        # has the shapes the step actually runs; the batch may arrive as a
        # positional OR a keyword argument
        self._maybe_start_profiler(
            next((a for a in (*args, *kwargs.values())
                  if _is_batch_like(a)), None))
        args = tuple(self.put_batch(a) if _is_batch_like(a) else a for a in args)
        kwargs = {k: self.put_batch(v) if _is_batch_like(v) else v
                  for k, v in kwargs.items()}
        if self.wall_clock_breakdown():
            self.timers(FORWARD_GLOBAL_TIMER).start()
        self._rng, step_rng = jax.random.split(self._rng)
        if not self.training:
            out = self._get_fwd_only()(self._params, step_rng, *args, **kwargs)
            if self.wall_clock_breakdown():
                self.timers(FORWARD_GLOBAL_TIMER).stop()
            return out
        self.tput_timer.start()
        if getattr(self, "_pending", None) is not None \
                and self._grad_acc is not None:
            # gradients from the un-backward()ed forward are already IN
            # the running accumulator (fused/grouped paths) or would be
            # silently dropped mid-window — either way the window would
            # train on the wrong gradient sum.  (A fresh forward with NO
            # window in flight stays allowed: loss-only forwards are a
            # legitimate pattern and their pending grads are discarded.)
            raise RuntimeError(
                "forward() called twice without backward() inside an "
                "accumulation window — call backward(loss) after each "
                "forward")
        n_groups = int(getattr(self._config.zero_config,
                               "grad_partition_groups", 1) or 1)
        if n_groups > 1:
            if getattr(self, "_pending", None) is not None:
                # grouped mode accumulates on the FIRST micro too — a
                # pending forward's grads are already in the buffer
                raise RuntimeError(
                    "forward() called twice without backward() (grouped "
                    "accumulation adds into the running buffer)")
            loss, found_inf = self._forward_grouped(n_groups, step_rng,
                                                    args, kwargs)
            self._pending = (_ACCUMULATED, found_inf)
            self._last_loss = loss
            if self.wall_clock_breakdown():
                self.timers(FORWARD_GLOBAL_TIMER).stop()
            return loss
        if self._grad_acc is None:
            grads, loss, found_inf = self._get_fwd_bwd()(
                self._params, self._scaler_state.scale, step_rng,
                *args, **kwargs)
            self._pending = (grads, found_inf)
        else:
            # micro-steps after the first ADD into the donated running
            # accumulator inside the SAME program that computes the
            # gradients: a separate grad tree + accumulate would hold
            # THREE param-sized trees at the boundary (params + acc +
            # fresh grads = 15.9 GB at 2.7B bf16 — the OOM that killed
            # the first single-chip 2.7B run); fused, XLA folds each
            # layer's add into its grad computation and the fresh tree
            # never fully materializes
            # detach the accumulator BEFORE the donating call: a failure
            # mid-program would otherwise leave self._grad_acc bound to
            # the donated (deleted) buffer and poison the next micro-step
            acc, self._grad_acc = self._grad_acc, None
            self._grad_acc, loss, found_inf = self._get_fwd_bwd_acc()(
                self._params, acc, self._scaler_state.scale,
                step_rng, *args, **kwargs)
            self._pending = (_ACCUMULATED, found_inf)
        self._last_loss = loss
        if self.wall_clock_breakdown():
            self.timers(FORWARD_GLOBAL_TIMER).stop()
        return loss

    __call__ = forward

    def backward(self, loss, retain_graph=False):
        """Accumulate the gradients produced by forward (reference
        ``engine.py:1804``; in JAX fwd+bwd are one fused program, so backward
        is the accumulation phase)."""
        if not self.training:
            raise RuntimeError("backward called in eval mode")
        if getattr(self, "_pending", None) is None:
            raise RuntimeError("backward called without a prior forward")
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_GLOBAL_TIMER).start()
        grads, found_inf = self._pending
        self._pending = None
        if grads is _ACCUMULATED:
            # forward already added this micro's grads into the running
            # accumulator (fused program — see forward)
            self._found_inf_acc = jnp.logical_or(self._found_inf_acc,
                                                 found_inf)
        elif self._grad_acc is None:
            self._grad_acc = grads
            self._found_inf_acc = found_inf
        else:
            self._grad_acc = self._get_accum()(self._grad_acc, grads)
            self._found_inf_acc = jnp.logical_or(self._found_inf_acc, found_inf)
        self.micro_steps += 1
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    @property
    def skipped_steps(self):
        """Overflow-skipped step count; reading drains any pending device
        flags in one batched transfer (the per-step flag is never synced on
        the hot path — see step())."""
        self._drain_skipped_steps()
        return self._skipped_steps

    @skipped_steps.setter
    def skipped_steps(self, value):
        self._pending_inf_flags = []
        self._skipped_steps = int(value)

    def _drain_skipped_steps(self):  # tpu-lint: disable=TL001 -- this IS the amortized sync point: one batched read for all queued flags
        if self._pending_inf_flags:
            flags, self._pending_inf_flags = self._pending_inf_flags, []
            # device_get batches the list itself — a jnp.stack would compile
            # a fresh N-scalar program per distinct queue length
            self._skipped_steps += int(np.sum(jax.device_get(flags)))

    def is_gradient_accumulation_boundary(self):
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def zero_grad(self):
        self._grad_acc = None
        self._found_inf_acc = None

    def _get_apply(self):
        key = "apply"
        if key not in self._compiled:
            clip = float(self.gradient_clipping() or 0.0)
            scaler = self.loss_scaler

            @hot_path("runtime.apply_update")
            def apply_update(params, opt_state, scaler_state, grads, found_inf, lr, step):
                grads, gnorm = _unscale_and_clip(grads, scaler_state.scale, clip)
                new_params, new_opt = self.optimizer.update(grads, opt_state, params,
                                                            lr=lr, step=step)
                # branch-free overflow skip (reference stage_1_and_2.py:1808)
                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(found_inf, o, n), new, old)
                new_params = keep(new_params, params)
                new_opt = keep(new_opt, opt_state)
                new_scaler = scaler.update(scaler_state, found_inf)
                return new_params, new_opt, new_scaler, gnorm

            self._compiled[key] = jax.jit(
                apply_update,
                donate_argnums=(0, 1, 2, 3),
                out_shardings=(self._plan.param_shardings, self._opt_shardings,
                               None, None))
        return self._compiled[key]

    @hot_path("runtime.step")
    def step(self, lr_kwargs=None):
        """Optimizer step at the accumulation boundary (reference
        ``engine.py:2000`` / ``_take_model_step:1935``)."""
        if not self.is_gradient_accumulation_boundary():
            return
        if self._grad_acc is None:
            raise RuntimeError("step called with no accumulated gradients")
        if self.wall_clock_breakdown():
            self.timers(STEP_GLOBAL_TIMER).start()
        if self._host_opt is not None:
            self._offload_step(lr_kwargs)
            if self.wall_clock_breakdown():
                self.timers(STEP_GLOBAL_TIMER).stop()
            return
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        step_no = jnp.asarray(self.global_steps + 1, jnp.int32)
        found_inf_acc = self._found_inf_acc
        (self._params, self._opt_state, self._scaler_state, gnorm) = self._get_apply()(
            self._params, self._opt_state, self._scaler_state,
            self._grad_acc, found_inf_acc, lr, step_no)
        self._last_global_grad_norm = gnorm
        self.zero_grad()
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step(**(lr_kwargs or {}))
        if self.fp16_enabled() and found_inf_acc is not None:
            # surface skipped steps for parity with reference loss-scale
            # logs — but do NOT read the flag here: that host sync would
            # serialize every fp16 step.  Flags queue on device and drain
            # in one batched read at the logging boundary (or whenever
            # skipped_steps is read, e.g. checkpoint save).
            self._pending_inf_flags.append(found_inf_acc)
            if self.global_steps % self.steps_per_print() == 0:
                before = self._skipped_steps
                self._drain_skipped_steps()
                if self._skipped_steps > before:
                    log_dist(
                        f"overflow: skipped {self._skipped_steps - before} "
                        f"recent step(s), new loss scale "
                        f"{float(jax.device_get(self._scaler_state.scale))}",  # tpu-lint: disable=TL001 -- print-gated, amortized over steps_per_print
                        ranks=[0])
        self.tput_timer.stop(global_step=True)
        self._maybe_finish_profiler()
        if self.monitor.enabled and self.global_steps % self.steps_per_print() == 0:
            events = [("Train/Samples/lr", self.get_lr()[0], self.global_samples)]
            if self._last_loss is not None:
                events.append(("Train/Samples/train_loss",
                               float(jax.device_get(self._last_loss)), self.global_samples))  # tpu-lint: disable=TL001 -- monitor read, gated on steps_per_print
            self.monitor.write_events(events + self._hbm_events())
        if self.wall_clock_breakdown():
            self.timers(STEP_GLOBAL_TIMER).stop()
            if self.global_steps % self.steps_per_print() == 0:
                self.timers.log([FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                                 STEP_GLOBAL_TIMER])

    def _offload_step(self, lr_kwargs=None):  # tpu-lint: disable=TL001 -- ZeRO-Offload: grads cross to the host BY DESIGN (see docstring)
        """Host optimizer step (ZeRO-Offload): host-side unscale/clip ->
        host C++ Adam -> upload (reference stage_1_and_2.py:1630 CPU Adam
        step + :1750 updated-param gather).  The unscale + global-norm
        clip run ON HOST (numpy, fp32): a device prep program at the
        boundary held grad-sized temps next to params + accumulator —
        the last straw for 2.7B on a 16 GB chip — and the grads are
        crossing to the host anyway."""
        flat_acc = list(jax.tree.leaves(self._grad_acc))
        self._grad_acc = None
        found_inf = bool(jax.device_get(self._found_inf_acc)) \
            if self._found_inf_acc is not None else False
        if not found_inf:
            host_grads = []
            for i in range(len(flat_acc)):
                host_grads.append(np.asarray(jax.device_get(flat_acc[i]),
                                             dtype=np.float32))
                flat_acc[i] = None         # free each device leaf as it
                # lands — never hold the full acc on BOTH sides
            inv = 1.0 / float(jax.device_get(self._scaler_state.scale))
            sq = sum(float(np.dot(g.ravel(), g.ravel()))
                     for g in host_grads)
            gnorm = float(np.sqrt(sq)) * inv
            clip = float(self.gradient_clipping() or 0.0)
            factor = inv * (min(1.0, clip / (gnorm + 1e-6)) if clip > 0.0
                            else 1.0)
            if factor != 1.0:
                for g in host_grads:
                    np.multiply(g, np.float32(factor), out=g)
            self._last_global_grad_norm = gnorm
            # fp32 compute must upload the fp32 masters directly — rounding
            # working params through bf16 every step would silently degrade
            # full-precision training
            want_fp32 = self.compute_dtype != jnp.bfloat16
            leaves = self._host_opt.step(host_grads, lr=self.get_lr()[0],
                                         fp32_out=want_fp32)
            new_tree = self._host_opt.leaves_to_tree(leaves)
            dtypes = jax.tree.map(lambda p: p.dtype, self._params)
            self._params = None            # free old params before upload
            new_tree = jax.tree.map(
                lambda a, dt: a if a.dtype == dt else a.astype(dt),
                new_tree, dtypes)
            # one host->device transfer straight into the sharded layout —
            # an eager asarray + re-placement jit would hold two device
            # copies of the new params
            self._params = jax.device_put(  # tpu-lint: disable=TL011 -- offload path: the host optimizer's new params start on the host; this is their one upload into the sharded layout, not a reshard
                new_tree, self._plan.param_shardings)
        else:
            self.skipped_steps += 1
            # the skipped step's norm is the honest value for telemetry —
            # leaving the previous step's number would make overflow steps
            # invisible in grad-norm logs
            self._last_global_grad_norm = float("inf")
        self._scaler_state = self.loss_scaler.update(
            self._scaler_state, jnp.asarray(found_inf))
        self.zero_grad()
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        if self.lr_scheduler is not None:
            self.lr_scheduler.step(**(lr_kwargs or {}))
        self.tput_timer.stop(global_step=True)

    # ------------------------------------------------------------------ #
    # Fully-fused train step (scan over GAS) — the benchmark hot path
    # ------------------------------------------------------------------ #
    def _get_fused_step(self, rung=0):
        """The fused step's jit.  ``rung`` > 0 (``_fit_train_exe``): the
        module's blocks keep rung ``rung`` of the model's remat ladder —
        same parameters, same operations, another saved set."""
        key = "fused_step" if not rung else f"fused_step:fit{rung}"
        if key not in self._compiled:
            import dataclasses
            apply = None if not rung else self.module.clone(
                config=dataclasses.replace(
                    self.module.config, remat_policy=f"fit:{rung}")).apply
            gas = self.gradient_accumulation_steps()
            clip = float(self.gradient_clipping() or 0.0)
            scaler = self.loss_scaler
            # bf16/fp32 run a static UNIT scale: the overflow check (a full
            # pass over every gradient), the where-select rollback, and the
            # scaler update are dead weight — compile them out.  An explicit
            # fp16 static loss_scale != 1 still needs unscaling AND the
            # overflow skip, so only scale==1.0 takes the fast path.
            from deepspeed_tpu.runtime.fp16.loss_scaler import StaticLossScaler
            static_scale = isinstance(scaler, StaticLossScaler) and \
                float(scaler.scale_value) == 1.0  # tpu-lint: disable=TL001 -- python attribute of the host-side scaler, runs once per compile

            @hot_path("runtime.train_step")
            def train_step(params, opt_state, scaler_state, lr, step, rng, batches):
                # derive this step's stream on-device: the caller passes the
                # same base key every step (no per-step host-side split op)
                rng = jax.random.fold_in(rng, step)

                def micro(carry, mb):
                    acc, inf_acc, r = carry
                    r, sub = jax.random.split(r)

                    def loss_of(p):
                        out = self._apply_model(p, (mb,), {}, sub, train=True,
                                                apply=apply)
                        loss, _ = self._extract_loss(out)
                        return loss.astype(jnp.float32) * scaler_state.scale / gas, loss

                    grads, loss = jax.grad(loss_of, has_aux=True)(params)
                    if not static_scale:
                        with jax.named_scope("optim.clip"):
                            flat = jax.tree.leaves(grads)
                            inf = jnp.logical_not(jnp.all(jnp.stack(
                                [jnp.all(jnp.isfinite(g)) for g in flat])))
                            inf_acc = jnp.logical_or(inf_acc, inf)
                    if acc is None:
                        acc = grads
                    else:
                        with jax.named_scope("optim.accumulate"):
                            acc = jax.tree.map(jnp.add, acc, grads)
                    return (acc, inf_acc, r), loss

                if gas == 1:
                    # no accumulation buffer: saves a full-size zero init +
                    # read-modify-write over the gradients
                    mb = jax.tree.map(lambda x: x[0], batches)
                    (acc, found_inf, _), loss0 = micro(
                        (None, jnp.asarray(False), rng), mb)
                    losses = loss0[None]
                else:
                    zero_acc = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    (acc, found_inf, _), losses = jax.lax.scan(
                        micro, (zero_acc, jnp.asarray(False), rng), batches)
                with jax.named_scope("optim.clip"):
                    grads, gnorm = _unscale_and_clip(
                        acc, 1.0 if static_scale else scaler_state.scale,
                        clip)
                with jax.named_scope("optim.update"):
                    new_params, new_opt = self.optimizer.update(
                        grads, opt_state, params, lr=lr, step=step)
                    if not static_scale:
                        keep = lambda new, old: jax.tree.map(
                            lambda n, o: jnp.where(found_inf, o, n), new, old)
                        new_params = keep(new_params, params)
                        new_opt = keep(new_opt, opt_state)
                    new_scaler = scaler.update(scaler_state, found_inf)
                return new_params, new_opt, new_scaler, jnp.mean(losses), gnorm

            self._compiled[key] = jax.jit(
                train_step,
                donate_argnums=(0, 1, 2),
                out_shardings=(self._plan.param_shardings, self._opt_shardings,
                               None, None, None))
        return self._compiled[key]

    def _run_fused_step(self, args):
        """Execute the fused train step — through an AOT executable when
        one exists (warmup() or the compile_cache executable store),
        through the plain jit call otherwise (exactly the seed behavior
        when the compile_cache block is off)."""
        fused = self._get_fused_step()
        if self._program_cache is None and not self._train_aot \
                and not self._remat_fit_budget()[1]:
            return fused(*args)
        from deepspeed_tpu.runtime import compile_cache as cc
        sig = cc.abstract_signature(args)
        exe = self._train_aot.get(sig)
        if exe is None:
            exe, _, _ = self._train_exe_for(fused, args, sig)
        return exe(*args)

    def _train_key_parts(self, sig):
        """Executable-store key context for the train step: everything that
        changes the compiled program besides the arg shapes."""
        import json as _json
        cfg = _json.dumps(self._config._param_dict, sort_keys=True,
                          default=repr)
        return (sig, cfg,
                repr(getattr(self.module, "config",
                             type(self.module).__name__)),
                tuple(sorted(dict(self.mesh.shape).items())),
                type(self.optimizer).__name__,
                type(self.loss_scaler).__name__)

    def _train_exe_for(self, fused, args, sig):
        """AOT-compile the fused step (consulting the executable store when
        enabled); falls back to the jit callable itself on any failure.
        Returns ``(exe, compile_seconds, store_hit)``.  Where the module's
        remat policy is ``"fit"`` and the device reports a memory limit,
        the step compiled is the one ``_fit_train_exe`` chooses."""
        from deepspeed_tpu.runtime.compile_cache import aot_compile_with_store
        cfg, limit = self._remat_fit_budget()
        with span("dstpu.train.compile") as sp:
            if limit:
                exe, dt, hit = self._fit_train_exe(cfg, limit, args, sig)
            else:
                exe, dt, hit = aot_compile_with_store(
                    self._program_cache, "train_step",
                    self._train_key_parts(sig), fused, args)
                if cfg is not None:     # nothing to fit against: rung 0
                    self._remat_choice = _remat_record(
                        cfg, 0, _tokens_on_first_device(args[-1]))
            sp.set(**_flat_args(self._remat_choice))
        if exe is None:            # AOT failed (warned): plain jit call —
            exe = fused            # no fake 0.0s compile event
        else:
            self._report_compile("train_step", dt, hit, self._remat_choice)
        self._train_aot[sig] = exe
        return exe, dt, hit

    # the share of ``bytes_limit`` a fitted step leaves free: what the
    # allocator loses to fragmentation, and what the caller's loop holds
    # beside the step (the next batch, an eval program's buffers)
    REMAT_FIT_RESERVE = 0.05
    # a subclass whose programs take device memory AFTER the train step is
    # compiled (the hybrid engine's rollout workspace) cannot be fitted
    _remat_fit_enabled = True

    def _remat_fit_budget(self):
        """``(config, bytes_limit)``: the module's config where its
        blocks' remat policy is ``"fit"`` (else None), and the device's
        memory limit where a rung is to be chosen against it (else 0: a
        policy given by name, a backend that reports no limit — the CPU)."""
        cfg = getattr(self.module, "config", None)
        if not (self._is_flax and self._remat_fit_enabled
                and getattr(cfg, "remat", False)
                and getattr(cfg, "remat_policy", None) == "fit"
                and hasattr(cfg, "remat_saved_bytes")):
            return None, 0
        if self._remat_limit is None:       # read once: it does not change
            self._remat_limit = self._device_memory()[0]
        return cfg, self._remat_limit

    def _device_memory(self):
        """``(bytes_limit, bytes_in_use)`` of the fullest local device."""
        snap = max(get_accelerator().memory_snapshots(),
                   key=lambda s: s["bytes_in_use"])
        return snap["bytes_limit"], snap["bytes_in_use"]

    def _fit_train_exe(self, cfg, limit, args, sig):
        """Compile the fused step at the richest rung of the model's remat
        ladder that fits: reckon the first rung from the shapes, read the
        compiled step's memory, step down by what it reads over — at most
        one compile is thrown away — and memoise the choice beside the
        executable, so a warm run compiles one program."""
        from deepspeed_tpu.models.transformer import REMAT_LADDER
        from deepspeed_tpu.runtime.compile_cache import aot_compile_with_store
        key_parts = self._train_key_parts(sig)
        cache = self._program_cache

        def compile_at(rung):
            return aot_compile_with_store(
                cache, "train_step", key_parts + (f"fit:{rung}",),
                self._get_fused_step(rung), args)

        memo = cache.load_note("train_step.remat", key_parts) \
            if cache is not None else None
        if memo is not None and memo.get("bytes_limit") == limit:
            exe, dt, hit = compile_at(memo["rung"])
            self._remat_choice = dict(memo, rungs_tried=[memo["rung"]])
            return exe, dt, hit

        _, in_use = self._device_memory()
        held = _bytes_on_first_device(args)
        # what the step may take: the limit less the reserve less what is
        # resident and is not the step's own arguments
        budget = int(limit * (1 - self.REMAT_FIT_RESERVE)) \
            - max(0, in_use - held)
        tokens = _tokens_on_first_device(args[-1])
        saved = lambda r: cfg.remat_saved_bytes(tokens, r)
        rungs = range(len(REMAT_LADDER), -1, -1)
        # reckoned before any compile: arguments, one gradient tree, and
        # the saved set (the rest of the step's temporaries are the
        # compiler's to say)
        reckoned = held + _bytes_on_first_device(args[0])
        rung = next((r for r in rungs if reckoned + saved(r) <= budget), 0)
        exe, dt, hit = compile_at(rung)
        tried, peak = [rung], self._step_memory(exe)
        if peak is not None and peak > budget and rung > 0:
            # over: the richest lower rung that fits by this reading; it is
            # kept whatever it reads (one compile thrown away, never two)
            rung = next((r for r in rungs if r < rung
                         and peak - saved(tried[0]) + saved(r) <= budget), 0)
            exe, again, hit = compile_at(rung)
            dt += again
            tried.append(rung)
            peak = self._step_memory(exe)
            if peak is not None and peak > budget:
                logger.warning(
                    f"remat fit: the train step at rung {rung} reads "
                    f"{peak / 1e9:.2f} GB against {budget / 1e9:.2f} GB free")
        if exe is None:             # AOT failed: the caller runs rung 0's jit
            rung = 0
        self._remat_choice = _remat_record(
            cfg, rung, tokens, step_peak_bytes=peak, bytes_limit=limit,
            rungs_tried=tried)
        if cache is not None and peak is not None:
            cache.save_note("train_step.remat", key_parts, self._remat_choice)
        return exe, dt, hit

    def _step_memory(self, compiled):
        """Bytes the compiled step holds at its peak on one device — the
        compiler's own figure, what XLA:TPU holds against the chip's HBM —
        or None where there is none to read (the AOT compile failed, the
        backend reports no peak).  Not arguments + outputs + temporaries -
        aliased: that sum counts every temporary as if none shared memory,
        and reads 1.2-3.1 GB over the peak at the benchmark's sizes."""
        from deepspeed_tpu.autotuning.cost_model import xla_memory_analysis
        mem = xla_memory_analysis(compiled) if compiled is not None else None
        return (mem or {}).get("peak_memory_in_bytes") or None

    def remat_choice(self):
        """What the fused step's blocks keep for their backward, where the
        module's remat policy is ``"fit"`` and the step has been compiled
        (else None): ``rung``, ``remat_saved`` (names), ``remat_saved_bytes``
        (by the shapes, a device), ``step_peak_bytes`` (the compiled step's
        peak as ``_step_memory`` reads it), ``bytes_limit`` (the device's;
        0 where it reports none and the rung is 0), ``rungs_tried`` (every
        rung compiled, in order)."""
        return self._remat_choice

    def _report_compile(self, name, seconds, cache_hit, remat=None):
        """``remat``: ``remat_choice()``'s record, where the step was
        compiled under the remat policy ``"fit"``."""
        log_dist(f"compile[{name}]: "
                 + ("executable-cache hit" if cache_hit
                    else f"{seconds:.1f}s")
                 + (f"; remat fit {_flat_args(remat)}" if remat else ""),
                 ranks=[0])
        if self.monitor.enabled:
            numbers = {k: v for k, v in (remat or {}).items()
                       if isinstance(v, int)}
            self.monitor.write_events(
                [(f"Compile/{name}_secs", seconds, self.global_steps)]
                + [(f"Compile/{name}_{k}", v, self.global_steps)
                   for k, v in numbers.items()])

    def warmup(self, batch=None, data_iter=None):
        """Pre-compile the fused whole-step train program for this batch's
        shapes, reporting the compile time through the monitor — so the
        multi-minute large-model compile is paid at a chosen moment (and,
        with the ``compile_cache`` block enabled, once per machine) instead
        of silently inside the first ``train_batch``.  Nothing executes and
        no engine state advances; the batch (same ``[gas, micro, ...]``
        stacked contract as ``train_batch``) is only used for shapes +
        lazy param init.

        Returns ``{"train_step": seconds}`` (0.0 = executable-store hit),
        or ``{}`` on the offload / grouped-backward paths (those run the
        3-call sequence whose programs compile per micro-step).

        NOTE: ``data_iter`` is CONSUMED exactly like ``train_batch`` would
        consume it (``gas`` micro-batches) — pass a throwaway/example
        ``batch`` instead when every sample must reach training."""
        gas = self.gradient_accumulation_steps()
        n_groups = int(getattr(self._config.zero_config,
                               "grad_partition_groups", 1) or 1)
        fused = self._offload_cfg is None and n_groups <= 1
        with span("dstpu.setup.warmup", cat="setup", programs=int(fused)):
            if not fused:
                # before touching data_iter: an engine this cannot warm
                # must not eat a global batch of real training data on the
                # way out
                logger.warning("warmup(): offload/grouped engines run the "
                               "3-call path — no fused step to precompile")
                report = {}
            else:
                if batch is None:
                    mbs = [next(data_iter) for _ in range(gas)]
                    batch = jax.tree.map(lambda *xs: jnp.stack(xs), *mbs)
                self._lazy_init((jax.tree.map(lambda x: x[0], batch),), {})
                # same curriculum slice train_batch applies — without it
                # the warmed signature would never match the sliced batch's
                # and the first real step would recompile anyway
                batch = self._curriculum_slice(batch, 2)
                batch = jax.tree.map(
                    lambda x: jax.device_put(
                        jnp.asarray(x),
                        NamedSharding(self.mesh,
                                      P(None, *(self._data_sharding(x.ndim - 1)
                                                .spec)))),
                    batch)
                lr = jnp.asarray(self.get_lr()[0], jnp.float32)
                step_no = jnp.asarray(self.global_steps + 1, jnp.int32)
                args = (self._params, self._opt_state, self._scaler_state,
                        lr, step_no, self._rng, batch)
                from deepspeed_tpu.runtime import compile_cache as cc
                sig = cc.abstract_signature(args)
                if sig in self._train_aot:
                    report = {"train_step": 0.0}
                else:
                    _, dt, hit = self._train_exe_for(
                        self._get_fused_step(), args, sig)
                    report = {"train_step": 0.0 if hit else dt}
        log_dist(ready_line("training"), ranks=[0])
        return report

    precompile = warmup

    @hot_path("runtime.train_batch")
    def train_batch(self, data_iter=None, batch=None):
        """One full global-batch step as a single XLA program (analog of
        ``PipelineEngine.train_batch``, reference ``pipe/engine.py:286``, for
        the non-pipelined engine)."""
        gas = self.gradient_accumulation_steps()
        if batch is None:
            mbs = [next(data_iter) for _ in range(gas)]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *mbs)
        else:
            # batch already stacked [gas, micro_batch, ...]
            pass
        n_groups = int(getattr(self._config.zero_config,
                               "grad_partition_groups", 1) or 1)
        if self._offload_cfg is not None or n_groups > 1:
            # offload path: the optimizer lives on host, so the step cannot
            # fuse into one XLA program — run the 3-call sequence per micro.
            # Same for the partitioned backward (grad_partition_groups):
            # the memory lever lives in forward()'s grouped passes
            micro_losses = []
            for i in range(gas):
                mb = jax.tree.map(lambda x: x[i], batch)
                loss = self.forward(mb)
                self.backward(loss)
                micro_losses.append(loss)
            # mean over the global batch, assigned BEFORE step() so the
            # monitor event written inside step() logs THIS iteration's loss
            self._last_loss = jnp.mean(jnp.stack(micro_losses))
            self.step()
            return self._last_loss
        self._lazy_init((jax.tree.map(lambda x: x[0], batch),), {})
        step = self.global_steps + 1
        with span("dstpu.train.batch", step=step):
            batch = self._curriculum_slice(batch, 2)
            self._maybe_start_profiler(jax.tree.map(lambda x: x[0], batch))
            batch = jax.tree.map(
                lambda x: jax.device_put(  # tpu-lint: disable=TL011 -- host->device batch admission for the fused step: one placement of the host batch into [gas, dp, ...] layout per train_batch, by design
                    jnp.asarray(x),
                    NamedSharding(self.mesh, P(None, *(self._data_sharding(x.ndim - 1).spec)))),
                batch)
        self.tput_timer.start()
        with span("dstpu.train.dispatch", step=step):
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            step_no = jnp.asarray(step, jnp.int32)
            args = (self._params, self._opt_state, self._scaler_state,
                    lr, step_no, self._rng, batch)
            (self._params, self._opt_state, self._scaler_state, loss,
             gnorm) = self._run_fused_step(args)
        with span("dstpu.train.post", step=step):
            self._last_global_grad_norm = gnorm
            self._last_loss = loss
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += self.train_batch_size()
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self.tput_timer.stop(global_step=True)
            self._maybe_finish_profiler()
            if self.monitor.enabled and self.global_steps % self.steps_per_print() == 0:
                # same Train/Samples series the 3-call path emits — fetching
                # the loss here syncs, but only every steps_per_print steps
                self.monitor.write_events(
                    [("Train/Samples/lr", self.get_lr()[0], self.global_samples),
                     ("Train/Samples/train_loss", float(jax.device_get(loss)),  # tpu-lint: disable=TL001 -- monitor read, gated on steps_per_print
                      self.global_samples)] + self._hbm_events())
        return loss

    def _hbm_events(self):
        """Peak-HBM watermark monitor events, print-gated like the loss
        fetch (one PJRT ``memory_stats()`` host call per device through
        the accelerator's canonical reader; empty on backends with no
        live stats — the CPU test backend stays event-identical to the
        pre-telemetry engine)."""
        try:
            wm = self.hbm_watermark()
        except Exception:
            return []
        if not wm.get("peak_bytes_in_use"):
            return []
        return [("Train/Samples/hbm_bytes_in_use",
                 wm["bytes_in_use"], self.global_samples),
                ("Train/Samples/hbm_peak_bytes",
                 wm["peak_bytes_in_use"], self.global_samples)]

    def hbm_watermark(self):
        """Per-run peak-HBM watermark: the accelerator's canonical
        per-device memory record (process-lifetime peak — one training
        run owns its process in a benchmark run), for callers stamping
        records at run end."""
        from deepspeed_tpu.monitor.memwatch import device_memory_record
        return device_memory_record()

    def eval_batch(self, batch):
        prev = self.training
        self.eval()
        out = self.forward(batch)
        self.train(prev)
        return out

    # ------------------------------------------------------------------ #
    # Checkpointing (reference engine.py:2841 save_checkpoint /
    # :2536 load_checkpoint)
    # ------------------------------------------------------------------ #
    def _fault_config(self):
        fcfg = getattr(self._config, "fault", None)
        return fcfg if (fcfg is not None and fcfg.enabled) else None

    def _checkpoint_arrays(self):
        return {
            "module": self._params,
            "optimizer": self._opt_state,
            "loss_scaler": self._scaler_state,
        }

    def _checkpoint_meta(self, client_state):
        meta = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler else None,
            "ds_config": self._config._param_dict,
            "client_state": client_state or {},
        }
        # the engine RNG key: restoring it is what makes a resumed 3-call
        # trajectory bitwise-identical to an uninterrupted one (the fused
        # path folds the step counter in on-device and is already
        # deterministic given global_steps)
        try:
            meta["rng_key_data"] = np.asarray(
                jax.device_get(jax.random.key_data(self._rng)))
        except Exception:
            pass
        return meta

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        exclude_frozen_parameters=False):
        if self._params is None:
            # nothing trained yet (params are lazily initialized by the
            # first forward) — writing a weightless tag would poison
            # resume walk-back with an unloadable checkpoint
            logger.warning("save_checkpoint called before parameters "
                           "exist; nothing saved")
            return False
        if tag is None:
            tag = f"global_step{self.global_steps}"
        fcfg = self._fault_config()
        if fcfg is not None:
            return self._save_checkpoint_atomic(save_dir, str(tag),
                                                client_state, save_latest,
                                                fcfg)
        ckpt_dir = os.path.join(save_dir, str(tag))
        os.makedirs(ckpt_dir, exist_ok=True)
        self.checkpoint_engine.create(tag)
        if self._host_opt is not None:
            # streamed per-leaf .npy files — never one giant pickle
            self._host_opt.save(os.path.join(ckpt_dir, "host_optimizer"))
        self.checkpoint_engine.save(self._checkpoint_arrays(),
                                    self._checkpoint_meta(client_state),
                                    os.path.join(ckpt_dir, "state"))
        # commit (async engines: wait for durability) BEFORE advancing the
        # 'latest' pointer — a crash mid-save must leave 'latest' on the
        # previous complete checkpoint, never a partial one
        self.checkpoint_engine.commit(tag)
        if save_latest and jax.process_index() == 0:
            # temp-file + os.replace: an in-place truncate-then-write
            # bricked resume when the process died between the two
            from deepspeed_tpu.runtime.fault.atomic import atomic_write_text
            atomic_write_text(os.path.join(save_dir, "latest"), str(tag))
        log_dist(f"saved checkpoint {tag} to {save_dir}", ranks=[0])
        return True

    def _save_checkpoint_atomic(self, save_dir, tag, client_state,
                                save_latest, fcfg):
        """Crash-atomic checkpoint protocol (``fault.enabled``): stage into
        ``<tag>.tmp/``, emit ``MANIFEST.json`` (sizes + checksums +
        fingerprint + step metadata), fsync, atomically rename to
        ``<tag>/``, atomically swap ``latest``, then GC per retention
        policy.  A kill at ANY instruction leaves either the previous
        consistent state or the new one — never a loadable partial.
        Transient I/O during the write stage retries with backoff."""
        import shutil
        import time as _time
        from deepspeed_tpu.runtime.fault import inject
        from deepspeed_tpu.runtime.fault.atomic import (atomic_publish_dir,
                                                        atomic_write_text)
        from deepspeed_tpu.runtime.fault.manifest import (
            build_manifest, gc_checkpoints, is_reserved_tag, write_manifest)
        from deepspeed_tpu.runtime.fault.retry import (
            retry_call, retry_policy_from_config)
        if is_reserved_tag(tag):
            # '<x>.tmp' / '<x>.old.<pid>' are the protocol's staging
            # namespace — a committed dir with such a name would be
            # destroyed (or relocated) by the next GC pass
            raise ValueError(
                f"checkpoint tag {tag!r} collides with the crash-atomic "
                "staging namespace ('*.tmp' / '*.old.<pid>'); pick "
                "another tag")
        os.makedirs(save_dir, exist_ok=True)
        final_dir = os.path.join(save_dir, tag)
        tmp_dir = final_dir + ".tmp"
        # host-side staging surgery (rmtree, manifest, rename, GC) is
        # process-0's job on a shared filesystem — every process still
        # participates in the array save/commit (Orbax coordinates the
        # sharded write + its own cross-process barrier internally)
        lead = jax.process_index() == 0

        def write_stage():
            if lead:
                if os.path.isdir(tmp_dir):  # stale orphan / failed attempt
                    shutil.rmtree(tmp_dir)
                os.makedirs(tmp_dir)
            inject.fire("ckpt.save_io", path=tmp_dir)
            self.checkpoint_engine.create(tag)
            if self._host_opt is not None:
                self._host_opt.save(os.path.join(tmp_dir, "host_optimizer"))
            self.checkpoint_engine.save(self._checkpoint_arrays(),
                                        self._checkpoint_meta(client_state),
                                        os.path.join(tmp_dir, "state"))
            # durability barrier for async engines: array shards AND
            # deferred metadata must be on disk before the manifest walks
            # the staging dir
            self.checkpoint_engine.commit(tag)

        retry_call(write_stage, label=f"checkpoint write ({tag})",
                   **retry_policy_from_config(fcfg))
        if not lead:
            return True
        inject.fire("ckpt.before_manifest", path=tmp_dir)
        t0 = _time.monotonic()
        manifest = build_manifest(
            tmp_dir, tag,
            step_meta={"global_steps": self.global_steps,
                       "global_samples": self.global_samples,
                       "micro_steps": self.micro_steps},
            checksum=fcfg.checksum, mesh_shape=self.mesh.shape,
            advance_latest=bool(save_latest))
        write_manifest(tmp_dir, manifest)
        verify_secs = _time.monotonic() - t0
        inject.fire("ckpt.corrupt_shard", path=os.path.join(tmp_dir, "state"))
        inject.fire("ckpt.before_commit_rename", path=tmp_dir)
        atomic_publish_dir(tmp_dir, final_dir)
        inject.fire("ckpt.before_latest_swap", path=save_dir)
        if save_latest:
            atomic_write_text(os.path.join(save_dir, "latest"), tag)
        # retention never deletes this tag NOR whatever 'latest' points to
        # (they differ under save_latest=False)
        protect = {tag}
        latest_path = os.path.join(save_dir, "latest")
        if os.path.exists(latest_path):
            with open(latest_path) as f:
                protect.add(f.read().strip())
        gc_checkpoints(save_dir, fcfg.keep_last_n, protect=tuple(protect))
        if self.monitor.enabled:
            self.monitor.write_events(
                [("Fault/ckpt_verify_secs", verify_secs, self.global_steps)])
        log_dist(f"saved checkpoint {tag} to {save_dir} "
                 f"(manifest {len(manifest['files'])} files, "
                 f"checksum {verify_secs:.2f}s)", ranks=[0])
        return True

    def _metadata_restore_targets(self, md):
        """Restore targets for a FRESH engine from checkpoint metadata:
        build this engine's sharding plan from the saved module shapes,
        then aim every congruent subtree (params, Adam moments in their
        restored plain-tree form) straight at plan shardings — each device
        reads only its shard, no replicated materialization."""
        from deepspeed_tpu.runtime.zero.partition import spec_or_replicated
        # Orbax ArrayMetadata leaves carry shape/dtype but no ndim — map to
        # ShapeDtypeStructs up front so downstream spec decisions (which
        # rank-check leaves) see real abstract arrays
        abstract = jax.tree.map(
            lambda m: jax.ShapeDtypeStruct(tuple(m.shape), m.dtype), md)
        mod_abs = abstract["module"]
        self._build_plan(mod_abs)
        params_def = jax.tree.structure(mod_abs)
        rep = NamedSharding(self.mesh, P())

        def congruent_shardings(sub):
            try:
                if jax.tree.structure(sub) == params_def:
                    return jax.tree.map(
                        lambda s, leaf: spec_or_replicated(self.mesh, s,
                                                           leaf),
                        self._plan.opt_specs, sub,
                        is_leaf=lambda x: isinstance(x, P))
            except Exception:
                pass
            if isinstance(sub, dict):
                return {k: congruent_shardings(v) for k, v in sub.items()}
            if isinstance(sub, (list, tuple)):
                return type(sub)(congruent_shardings(v) for v in sub)
            return rep

        def with_sh(abs_tree, sh_tree):
            return jax.tree.map(
                lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                  sharding=s),
                abs_tree, sh_tree)

        targets = {"module": with_sh(mod_abs, self._plan.param_shardings)}
        for key in abstract:
            if key == "module":
                continue
            if abstract[key] is None:     # e.g. offload engines save no
                targets[key] = None       # device optimizer state
                continue
            sh = congruent_shardings(abstract[key]) if key == "optimizer" \
                else jax.tree.map(lambda _: rep, abstract[key])
            targets[key] = with_sh(abstract[key], sh)
        return targets

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False):
        fcfg = self._fault_config()
        requested = tag
        if tag is None:
            latest = os.path.join(load_dir, "latest")
            if os.path.exists(latest):
                with open(latest) as f:
                    tag = f.read().strip()
            elif fcfg is None:
                logger.warning(f"no 'latest' file at {load_dir}; nothing loaded")
                return None, {}
        if fcfg is None:
            return self._load_checkpoint_tag(
                load_dir, tag, load_module_strict, load_optimizer_states,
                load_lr_scheduler_states, load_module_only)
        # fault-tolerant load: verify the candidate tag's manifest; on a
        # missing / partial / corrupt tag walk back to the newest valid
        # one instead of crashing (CheckFreq's verified-restore property)
        import time as _time
        from deepspeed_tpu.runtime.fault.manifest import (
            newest_valid_tag, read_manifest, verify_manifest)
        if requested is None:
            # the 'latest' pointer legitimately lags one tag when a crash
            # lands between the atomic tag rename and the pointer swap —
            # manifest step ordering is authoritative for resume-eligible
            # tags (those saved with save_latest=True; side checkpoints
            # record advance_latest=false and never hijack auto-resume)
            tag = None
        tried = []
        t0 = _time.monotonic()
        pre_verified = False
        while True:
            if tag is None:
                tag = newest_valid_tag(load_dir,
                                       checksum_verify=fcfg.verify_on_load,
                                       skip=tried, for_resume=True)
                # newest_valid_tag already deep-checksummed this tag —
                # re-verifying would double the restore's I/O + hashing
                pre_verified = fcfg.verify_on_load
            if tag is None:
                from deepspeed_tpu.runtime.fault.manifest import list_tags
                remaining = [t for t in list_tags(load_dir)
                             if t not in tried]
                # tags that SHOULD have been resume candidates but were
                # rejected (invalid) — distinct from side checkpoints
                # (advance_latest=false), which are not failures
                eligible = [t for t in remaining
                            if (read_manifest(os.path.join(load_dir, t))
                                or {}).get("advance_latest") is not False]
                if tried or eligible:
                    raise RuntimeError(
                        f"no valid checkpoint in {load_dir}: every "
                        "resume-eligible tag failed verification or load "
                        f"(tried={tried or eligible})")
                if remaining:
                    logger.warning(
                        f"{load_dir} holds only side checkpoints "
                        f"(save_latest=False: {remaining}); nothing "
                        "loaded — starting fresh")
                else:
                    logger.warning(f"no checkpoint found at {load_dir}; "
                                   "nothing loaded")
                return None, {}
            ckpt_dir = os.path.join(load_dir, str(tag))
            if fcfg.verify_on_load and not pre_verified \
                    and read_manifest(ckpt_dir) is not None:
                problems = verify_manifest(ckpt_dir, deep=True)
                if problems:
                    if requested is not None:
                        # an EXPLICITLY requested tag that fails must fail
                        # loudly — silently substituting older weights
                        # would poison evals/exports; auto-resume
                        # (tag=None) is where walk-back applies
                        from deepspeed_tpu.runtime.fault.manifest import \
                            CheckpointCorrupt
                        raise CheckpointCorrupt(
                            f"requested checkpoint {tag!r} in {load_dir} "
                            f"failed verification: {problems[:5]}")
                    logger.warning(
                        f"[fault] checkpoint {tag} failed verification "
                        f"({problems[:3]}{'...' if len(problems) > 3 else ''})"
                        " — walking back to the previous valid tag")
                    tried.append(str(tag))
                    tag = None
                    continue
            try:
                # a transient I/O error (NFS EIO/ESTALE mid-restore) must
                # NOT be conflated with a corrupt tag: retry the SAME tag
                # with backoff first — walking back on a flake would
                # silently discard committed steps
                from deepspeed_tpu.runtime.fault.retry import (
                    retry_call, retry_policy_from_config)
                result = retry_call(
                    self._load_checkpoint_tag, load_dir, tag,
                    load_module_strict, load_optimizer_states,
                    load_lr_scheduler_states, load_module_only,
                    label=f"checkpoint load ({tag})",
                    **retry_policy_from_config(fcfg))
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                if requested is not None:
                    # same loud-failure contract for load errors on an
                    # explicitly requested tag
                    raise
                logger.warning(f"[fault] tag {tag} failed to load "
                               f"({type(e).__name__}: {e}); walking back")
                tried.append(str(tag))
                tag = None
                continue
            if requested is None:
                latest_hint = None
                latest_path = os.path.join(load_dir, "latest")
                if os.path.exists(latest_path):
                    with open(latest_path) as f:
                        latest_hint = f.read().strip()
                if latest_hint and latest_hint != str(tag):
                    # newest-eligible-valid wins over the pointer (the
                    # crash window leaves 'latest' lagging) — but say so
                    # loudly: an operator who HAND-EDITED 'latest' to
                    # roll back must instead load an explicit tag or GC
                    # the newer tags (docs/fault_tolerance.md)
                    logger.warning(
                        f"[fault] resuming from {tag} although 'latest' "
                        f"points at {latest_hint} (newest valid "
                        "resume-eligible tag wins; for a manual rollback "
                        "load an explicit tag or remove the newer tags)")
            if self.monitor.enabled:
                self.monitor.write_events(
                    [("Fault/ckpt_verify_secs", _time.monotonic() - t0,
                      self.global_steps)])
            return result

    def _load_checkpoint_tag(self, load_dir, tag, load_module_strict=True,
                             load_optimizer_states=True,
                             load_lr_scheduler_states=True,
                             load_module_only=False):
        path = os.path.join(load_dir, str(tag), "state")
        abstract = None
        if self._params is not None:
            abstract = {
                "module": _abstract_like(self._params),
                "optimizer": _abstract_like(self._opt_state),
                "loss_scaler": _abstract_like(self._scaler_state),
            }
        else:
            # fresh engine: the checkpoint may come from a DIFFERENT
            # process/device topology (cross-world-size resume) — build
            # device-agnostic restore targets from the checkpoint's own
            # metadata, SHARDED under this engine's plan (built from the
            # checkpoint's shapes) so a ZeRO-sized model never
            # materializes replicated during the restore
            md = getattr(self.checkpoint_engine, "metadata",
                         lambda p: None)(path)
            if md is not None:
                abstract = self._metadata_restore_targets(md)
        fresh_engine = self._params is None
        arrays, meta = self.checkpoint_engine.load(path, abstract_arrays=abstract)
        if arrays is None or not isinstance(arrays, dict) \
                or arrays.get("module") is None:
            # missing/partial 'arrays' dir: the seed indexed
            # arrays["module"] with arrays=None and died on a TypeError —
            # surface what actually happened (fault-enabled loads catch
            # this and walk back to the previous tag).  Deliberately NOT
            # an OSError: the retry policy treats those as transient, and
            # this condition is permanent
            from deepspeed_tpu.runtime.fault.manifest import \
                CheckpointCorrupt
            raise CheckpointCorrupt(
                f"checkpoint {tag!r} at {path} has no loadable 'arrays' "
                "payload (partial or corrupt save?) — cannot restore "
                "module weights")
        self._params = arrays["module"]
        if load_module_only:
            if fresh_engine and self._host_opt is None:
                # fresh engine: build the plan and re-place the loaded
                # weights (fresh optimizer state — module only; the
                # metadata path may have pre-built self._plan, so key on
                # fresh_engine, not plan presence)
                self._init_params_from(self._params)
            elif self._host_opt is not None:
                # fresh masters from the loaded weights — stale fp32 masters
                # would overwrite them on the next offload step
                self._host_opt.init_from_params(self._params)
            return path, meta.get("client_state", {})
        host_opt_dir = os.path.join(load_dir, str(tag), "host_optimizer")
        if self._host_opt is not None:
            if load_optimizer_states and os.path.isdir(host_opt_dir):
                self._host_opt.load(host_opt_dir)
            else:
                # no host states loaded: re-seed fp32 masters from the loaded
                # params, else the next step() would run Adam on stale masters
                # and silently overwrite the checkpoint's weights
                self._host_opt.init_from_params(self._params)
        if load_optimizer_states and arrays.get("optimizer") is not None:
            from deepspeed_tpu.runtime.utils import rehydrate_opt_state
            self._opt_state = rehydrate_opt_state(self._opt_state,
                                                  arrays["optimizer"])
        if arrays.get("loss_scaler") is not None:
            sc = arrays["loss_scaler"]
            if isinstance(sc, dict):
                from deepspeed_tpu.runtime.fp16.loss_scaler import LossScalerState
                sc = LossScalerState(**sc)
            self._scaler_state = self._replicate(sc)
        self.global_steps = meta.get("global_steps", 0)
        self.global_samples = meta.get("global_samples", 0)
        self.micro_steps = meta.get("micro_steps", 0)
        self.skipped_steps = meta.get("skipped_steps", 0)
        if meta.get("rng_key_data") is not None:
            # restore the engine RNG stream: resumed runs draw the same
            # dropout/init keys an uninterrupted run would have drawn
            try:
                self._rng = jax.random.wrap_key_data(
                    jnp.asarray(meta["rng_key_data"]))
            except Exception as e:
                logger.warning(f"could not restore engine RNG key: {e}")
        if load_lr_scheduler_states and self.lr_scheduler and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        if fresh_engine and self._host_opt is None:
            # checkpoint loaded into a fresh engine, possibly on a DIFFERENT
            # topology than it was saved from (the reference's universal-
            # checkpoint resize): build this engine's sharding plan from the
            # loaded shapes and re-place params + optimizer state under it.
            loaded_opt = self._opt_state
            have_loaded_opt = load_optimizer_states and loaded_opt is not None
            self._opt_state = None
            # when loaded state exists, compute shardings only — allocating
            # a fresh m/v just to overwrite it would spike HBM
            self._init_params_from(self._params,
                                   materialize_opt=not have_loaded_opt)
            if self._host_opt is not None:
                # offload engine born from this load: prefer the saved host
                # optimizer states over the fresh init_from_params seed
                if load_optimizer_states and os.path.isdir(host_opt_dir):
                    self._host_opt.load(host_opt_dir)
            elif have_loaded_opt and self._opt_shardings is not None:
                from deepspeed_tpu.runtime.utils import rehydrate_opt_state
                loaded_opt = rehydrate_opt_state(
                    getattr(self, "_abstract_opt", None), loaded_opt)
                self._opt_state = jax.jit(
                    lambda t: t,
                    out_shardings=self._opt_shardings)(loaded_opt)
        state = meta
        log_dist(f"loaded checkpoint {tag} from {load_dir}", ranks=[0])
        return path, state.get("client_state", {})

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin",
                         hf_policy=None):
        """Gathered 16-bit weights for serving (reference engine.py:3297:
        emits a consumer-loadable state dict, not an internal format).

        * ``save_filename`` ending in ``.safetensors`` → safetensors file;
          anything else → a REAL ``torch.save`` state dict (bf16 tensors
          round-trip via a uint16 view since numpy has no native bf16).
        * ``hf_policy``: an injection policy instance (or HF ``model_type``
          string, e.g. ``"opt"``) whose ``export_convert`` renames the flax
          params to that family's HF checkpoint keys — the inverse of the
          ``module_inject`` load mapping.  Default: flax dotted paths.
        """
        os.makedirs(save_dir, exist_ok=True)
        dtype = self.compute_dtype if self.compute_dtype != jnp.float32 \
            else jnp.bfloat16
        gathered = jax.device_get(jax.tree.map(
            lambda p: p.astype(dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, self._params))
        from deepspeed_tpu.checkpoint.deepspeed_checkpoint import (
            _flatten_with_paths)
        flat = {k: np.asarray(v)
                for k, v in _flatten_with_paths(gathered).items()}
        # keys relative to the 'params' collection (policy key space)
        flat = {(k[len("params/"):] if k.startswith("params/") else k): v
                for k, v in flat.items()}
        if hf_policy is not None:
            if isinstance(hf_policy, str):
                from deepspeed_tpu.module_inject.containers import ALL_POLICIES
                matches = [p for p in ALL_POLICIES
                           if hf_policy in p.model_types]
                if not matches:
                    raise ValueError(f"no injection policy for model_type="
                                     f"{hf_policy!r}")
                hf_policy = matches[0]()
            cfg = getattr(self.module, "config", None)
            if cfg is None:
                raise ValueError(
                    "hf_policy export requires the module to expose a "
                    ".config (TransformerConfig); wrap or pass the flax "
                    "model family the policy maps")
            flat = hf_policy.export_convert(flat, cfg)
        path = os.path.join(save_dir, save_filename)
        if save_filename.endswith(".safetensors"):
            from safetensors.numpy import save_file
            save_file({k: np.ascontiguousarray(v) for k, v in flat.items()},
                      path)
        else:
            import torch

            def to_torch(a):
                # copy: jax-owned buffers are read-only, torch wants writable
                a = np.ascontiguousarray(a).copy()
                if a.dtype == jnp.bfloat16:
                    return torch.from_numpy(
                        a.view(np.uint16)).view(torch.bfloat16)
                return torch.from_numpy(a)

            torch.save({k: to_torch(v) for k, v in flat.items()}, path)
        log_dist(f"saved 16-bit model ({len(flat)} tensors, "
                 f"{jnp.dtype(dtype).name}) to {path}", ranks=[0])
        return True

    # ------------------------------------------------------------------ #
    @property
    def params(self):
        return self._params

    def load_params(self, tree):
        """Replace the live master params (same structure/shapes), re-placed
        with the plan's shardings — the write-back half of
        ``zero.GatheredParameters`` surgery."""
        if self._params is None or self._plan is None:
            raise RuntimeError("engine params not initialized yet")
        import chex
        chex.assert_trees_all_equal_shapes(tree, self._params)
        put = jax.jit(
            lambda t: jax.tree.map(
                lambda p, old: p.astype(old.dtype), t, self._params),
            out_shardings=self._plan.param_shardings)
        self._params = put(tree)
        if self._host_opt is not None:
            # ZeRO-Offload: the host fp32 masters are authoritative — the
            # next _offload_step overwrites device params from them, so the
            # surgery must be re-seeded there too (values only: Adam
            # moments and step count survive, unlike init_from_params)
            self._host_opt.reseed_masters(self._params)
        # hybrid engine caches a bf16 inference view keyed on global_steps;
        # surgery changes weights without a step, so drop it explicitly
        if getattr(self, "_infer_params", None) is not None:
            self._infer_params = None

    def module_state_dict(self):
        return self._params

    def get_model(self):
        return self.module

    def destroy(self):
        self._compiled.clear()


# --------------------------------------------------------------------- #
def _is_generator(x):
    return inspect.isgenerator(x)


def _is_batch_like(a):
    if hasattr(a, "shape") and getattr(a, "ndim", 0) >= 1:
        return True
    if isinstance(a, dict):
        return all(hasattr(v, "shape") for v in a.values())
    if isinstance(a, (tuple, list)):
        return all(hasattr(v, "shape") for v in a)
    return False


def _abstract_like(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=l.sharding)
        if isinstance(l, jax.Array) else l, tree)


def _opt_state_shardings(abstract_opt, abstract_params, opt_specs, mesh):
    """Build shardings for optimizer state: any field congruent to the param
    tree gets the ZeRO opt-state specs; scalars replicate."""
    params_def = jax.tree.structure(abstract_params)

    def field_shardings(field):
        from deepspeed_tpu.runtime.zero.partition import spec_or_replicated
        try:
            if jax.tree.structure(field) == params_def:
                return jax.tree.map(
                    lambda s, leaf: spec_or_replicated(mesh, s, leaf),
                    opt_specs, field, is_leaf=lambda x: isinstance(x, P))
        except Exception:
            pass
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), field)

    if hasattr(abstract_opt, "_fields"):
        return type(abstract_opt)(*[field_shardings(getattr(abstract_opt, f))
                                    for f in abstract_opt._fields])
    return field_shardings(abstract_opt)
