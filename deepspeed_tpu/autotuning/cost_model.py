"""Memory / performance cost models for the autotuner.

The reference prunes its tuning space with a profile run plus an xgboost cost
model (``deepspeed/autotuning/autotuner.py:664``, ``tuner/cost_model.py``).
On TPU we can do strictly better: XLA tells us the exact per-program memory
footprint at *compile time* (``compiled.memory_analysis()``), so OOM configs
are rejected without ever executing — and an analytic ZeRO memory model
(params/grads/optimizer-states divided across the dp axis by stage) prunes
before even compiling.
"""

import os

from deepspeed_tpu.autotuning.constants import DEFAULT_HBM_BYTES


def device_memory_limit():
    """Per-chip memory budget in bytes.

    Order: ``DSTPU_HBM_BYTES`` env override → the accelerator's
    canonical ``memory_snapshot()['bytes_limit']`` (the runtime's own
    figure on a TPU — the SAME number the flops profiler and the serving
    memory sampler read) → ``DEFAULT_HBM_BYTES`` on the CPU backend,
    which reports none.
    """
    env = os.environ.get("DSTPU_HBM_BYTES")
    if env:
        return int(env)
    from deepspeed_tpu.accelerator.real_accelerator import get_accelerator
    return int(get_accelerator().memory_snapshot()["bytes_limit"]) \
        or DEFAULT_HBM_BYTES


def estimate_zero_memory(num_params,
                         dp_size,
                         zero_stage,
                         micro_batch_size,
                         activation_bytes_per_sample=0,
                         param_dtype_bytes=2,
                         master_dtype_bytes=4,
                         optimizer_slots=2):
    """Analytic per-chip memory for a ZeRO stage (the reference's tuning-space
    prune, ``autotuner.py:524`` ``_generate_experiments``).

    Returns bytes: 16-bit params + fp32 grads-accum + fp32 master & optimizer
    slots, each divided over dp according to what the stage shards, plus a
    linear activation term.
    """
    p = num_params
    param_mem = p * param_dtype_bytes / (dp_size if zero_stage >= 3 else 1)
    grad_mem = p * master_dtype_bytes / (dp_size if zero_stage >= 2 else 1)
    opt_mem = (p * master_dtype_bytes * (1 + optimizer_slots)
               / (dp_size if zero_stage >= 1 else 1))
    act_mem = activation_bytes_per_sample * micro_batch_size
    return int(param_mem + grad_mem + opt_mem + act_mem)


def xla_memory_analysis(compiled):
    """Exact compile-time memory of a lowered+compiled XLA program
    (``compiled.memory_analysis()``): argument / output / temp / alias /
    generated-code bytes, plus ``total_bytes`` = arg + out + temp −
    alias (the program's live working set — what it actually costs the
    device on top of buffers it aliases in place; it counts every
    temporary as if none shared memory) and ``peak_memory_in_bytes``, the
    compiler's own peak over arguments, outputs and temporaries (what
    XLA:TPU holds against the chip's HBM; 0 where it reports none).
    Exact on TPU,
    stable on the tier-1 CPU backend (the memory/FLOP contracts in
    ``PROGRAMS.lock`` are locked from this).  Returns ``None`` when the
    backend does not expose the analysis.
    """
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        out = {}
        for key in ("temp_size_in_bytes", "argument_size_in_bytes",
                    "output_size_in_bytes", "alias_size_in_bytes",
                    "generated_code_size_in_bytes", "peak_memory_in_bytes"):
            out[key] = int(getattr(ma, key, 0) or 0)
        out["total_bytes"] = (out["temp_size_in_bytes"] + out["argument_size_in_bytes"]
                              + out["output_size_in_bytes"] - out["alias_size_in_bytes"])
        return out
    except Exception:
        return None


def xla_cost_analysis(compiled):
    """XLA's raw cost-analysis dict for a compiled program, normalized
    to a plain dict (some backends return a one-element list).  Keys of
    interest: ``'flops'`` and ``'bytes accessed'`` — THE shared cost
    model: the flops profiler and the memory/FLOP program contracts
    (``tools/lint/mem_contract.py``) both read compiled programs through
    this one extraction."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return dict(ca) if hasattr(ca, "get") else {}
    except Exception:
        return {}


def xla_flops_analysis(compiled):
    """XLA's own flop estimate for the program (feeds the FLOPS metric)."""
    return float(xla_cost_analysis(compiled).get("flops", 0.0))


def compiled_costs(compiled):
    """``{"flops", "bytes_accessed", "transcendentals"}`` (floats) from
    a compiled program's cost analysis — the roofline numerators."""
    ca = xla_cost_analysis(compiled)
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }
