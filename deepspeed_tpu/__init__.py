"""deepspeed_tpu — a TPU-native distributed training & inference framework
with the capability surface of DeepSpeed v0.9.3 (reference
``deepspeed/__init__.py``), re-designed for JAX/XLA/Pallas/pjit.

Top-level API parity:

* ``initialize()``          (reference ``__init__.py:58``)
* ``init_inference()``      (reference ``__init__.py:260``)
* ``init_distributed``      (re-export, reference ``__init__.py:32``)
* ``add_config_arguments()``(reference ``__init__.py:237``)
"""

import functools as _functools
import time as _time
_T_IMPORT = _time.monotonic()            # dstpu.setup.import opens here

__version__ = "0.1.0"
__git_hash__ = None
__git_branch__ = None

from deepspeed_tpu.accelerator import get_accelerator, set_accelerator  # noqa: F401
from deepspeed_tpu import comm  # noqa: F401
from deepspeed_tpu.comm import init_distributed  # noqa: F401
from deepspeed_tpu.parallel import topology  # noqa: F401
from deepspeed_tpu.parallel.topology import ParallelTopology, initialize_topology  # noqa: F401
from deepspeed_tpu.runtime.config import DeepSpeedConfig  # noqa: F401
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.runtime import zero  # noqa: F401
from deepspeed_tpu.runtime.pipe.module import PipelineModule, LayerSpec, TiedLayerSpec  # noqa: F401
from deepspeed_tpu.ops.transformer.transformer import (  # noqa: F401
    DeepSpeedTransformerConfig, DeepSpeedTransformerLayer)
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing  # noqa: F401
from deepspeed_tpu.utils.logging import logger, log_dist  # noqa: F401
from deepspeed_tpu.monitor.trace import span as _span

from deepspeed_tpu.ops.adam.fused_adam import FusedAdam, FusedAdamW  # noqa: F401
from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb  # noqa: F401


def _setup_engine_span(fn):
    """Run an entry point under ``dstpu.setup.engine`` (config, topology
    and mesh, the engine object; ``docs/observability.md`` "Start-up")."""
    @_functools.wraps(fn)
    def entry(*args, **kwargs):
        with _span("dstpu.setup.engine", cat="setup",
                   entry=fn.__name__) as sp:
            out = fn(*args, **kwargs)
            engine = out[0] if isinstance(out, tuple) else out
            sp.set(chips=engine.mesh.size)
        return out
    return entry


@_setup_engine_span
def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None,
               loss_fn=None,
               topology=None):
    """Initialize the engine (reference ``deepspeed/__init__.py:58``).

    Returns the tuple ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    ``model`` is a flax Module or ``apply_fn(params, batch) -> loss``;
    ``model_parameters`` an optional initial parameter pytree (else params are
    lazily initialized *sharded* at first forward).  The engine choice
    (plain vs pipeline) mirrors reference ``__init__.py:150-190``.
    """
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config"):
        config = args.deepspeed_config
    assert config is not None, "DeepSpeed requires --deepspeed_config or config="

    if topology is None and mpu is not None:
        # honor an external Megatron-style mpu (reference __init__.py:88:
        # the engine adopts mpu's groups) by building the mesh from its
        # parallel degrees
        from deepspeed_tpu.parallel import topology as _topo

        def _mpu_size(*names):
            for n in names:
                fn = getattr(mpu, n, None)
                if callable(fn):
                    return fn()
            return 1

        # probe both naming schemes: legacy Megatron (model_parallel) and
        # Megatron-Core (tensor_model_parallel / pipeline_model_parallel)
        tp_size = _mpu_size("get_model_parallel_world_size",
                            "get_tensor_model_parallel_world_size")
        pp_size = _mpu_size("get_pipe_parallel_world_size",
                            "get_pipeline_model_parallel_world_size")
        topology = _topo.initialize_topology(tp=tp_size, pp=pp_size)

    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    if isinstance(model, PipelineModule):
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
        engine = PipelineEngine(model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                collate_fn=collate_fn,
                                config=config,
                                topology=topology)
    else:
        # Hybrid engine for RLHF rollout+train (reference __init__.py:150-190
        # chooses DeepSpeedHybridEngine on config.hybrid_engine.enabled)
        cfg_dict = config
        if isinstance(config, str):
            import json
            with open(config) as f:
                cfg_dict = json.load(f)
        hybrid = isinstance(cfg_dict, dict) and \
            cfg_dict.get("hybrid_engine", {}).get("enabled", False)
        engine_cls = DeepSpeedEngine
        if hybrid:
            from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
            engine_cls = DeepSpeedHybridEngine
        engine = engine_cls(model=model,
                            optimizer=optimizer,
                            model_parameters=model_parameters,
                            training_data=training_data,
                            lr_scheduler=lr_scheduler,
                            collate_fn=collate_fn,
                            config=cfg_dict,
                            loss_fn=loss_fn,
                            topology=topology)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


@_setup_engine_span
def init_inference(model=None, config=None, **kwargs):
    """Initialize the inference engine (reference ``__init__.py:260``).

    ``model`` may be a flax Module, an HF torch model, or an HF model
    name/path — torch models are converted through the injection policies
    (``module_inject/``), the analog of the reference's kernel injection."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    if isinstance(config, dict):
        config = DeepSpeedInferenceConfig(**config, **kwargs)
    elif config is None:
        config = DeepSpeedInferenceConfig(**kwargs)

    params = None
    is_torch = False
    if isinstance(model, str):
        is_torch = True
    else:
        try:
            # seconds where the process has not imported torch yet (a named
            # part of the engine's set-up)
            with _span("dstpu.setup.lazy_import", cat="setup",
                       module="torch"):
                import torch
            is_torch = isinstance(model, torch.nn.Module)
        except ImportError:
            pass
    if is_torch:
        from deepspeed_tpu.module_inject import convert_hf_model
        from deepspeed_tpu.inference.config import normalize_dtype_str
        model, params = convert_hf_model(
            model, dtype=normalize_dtype_str(config.dtype))
    if config.quant.kv_cache:
        # int8 KV cache: flip the model-config knob (decoder families);
        # warn instead of failing for models without a KV cache
        cfg = getattr(model, "config", None)
        if hasattr(cfg, "kv_cache_quant"):
            if not cfg.kv_cache_quant:
                import dataclasses
                model = model.clone(
                    config=dataclasses.replace(cfg, kv_cache_quant=True))
        else:
            from deepspeed_tpu.utils.logging import warning_once
            warning_once(f"quant.kv_cache: {type(model).__name__} has no "
                         "kv_cache_quant knob — ignored")
    return InferenceEngine(model, config, params=params)


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config CLI args (reference
    ``__init__.py:237``)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to launcher)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--local_rank", type=int, default=-1,
                       help="local rank passed by the launcher")
    return parser


with _span("dstpu.setup.import", cat="setup", start=_T_IMPORT):
    pass                                 # first line of this file -> here
