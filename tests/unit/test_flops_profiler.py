"""Per-module flops/latency tree (reference ``flops_profiler/profiler.py:239``
``print_model_profile`` / ``:375`` aggregated profile)."""

import numpy as np
import pytest

import jax

from deepspeed_tpu.models.transformer import Transformer, TransformerConfig
from deepspeed_tpu.profiling.flops_profiler.profiler import (
    ModuleProfile, _scope_to_path, aggregate_by_depth, format_profile_tree,
    model_profile_tree)


def tiny_model():
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=32, dtype="float32",
                            use_flash_attention=False, remat=False,
                            scan_layers=False)
    return Transformer(cfg)


def tiny_batch():
    rng = np.random.default_rng(0)
    return {"input_ids": rng.integers(0, 64, (2, 16)).astype(np.int32)}


def test_scope_to_path_strips_transform_and_method_frames():
    assert _scope_to_path(
        "jit(f)/Transformer/Transformer.hidden_states/layers_0/attn/"
        "dot_general") == ("layers_0", "attn", "dot_general")
    assert _scope_to_path(
        "jit(f)/Transformer/layers_1/attn/bhst,bthd->bshd/transpose") == \
        ("layers_1", "attn", "transpose")
    assert _scope_to_path("reduce_sum") == ()


def test_model_profile_tree_structure_params_flops():
    model = tiny_model()
    root, _ = model_profile_tree(model, jax.random.key(0), tiny_batch())
    # module tree mirrors the flax structure
    assert set(root.children) >= {"embed_tokens", "layers_0", "layers_1",
                                  "final_norm", "lm_head"}
    blk = root.children["layers_0"]
    assert set(blk.children) >= {"input_norm", "attn", "mlp"}
    # subtree-aggregated params: root = model total, block > its norms
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
        model.init(jax.random.key(0), tiny_batch())))
    assert root.params == total
    assert blk.params > blk.children["input_norm"].params
    # flops: attention + mlp dominate the block (CPU path uses flax's
    # per-module cost analysis)
    assert root.flops > 0
    assert blk.flops >= blk.children["attn"].flops > 0
    assert blk.children["mlp"].flops > 0


def test_format_and_aggregate_render():
    model = tiny_model()
    root, total_ps = model_profile_tree(model, jax.random.key(0),
                                        tiny_batch())
    txt = format_profile_tree(root, total_ps, depth=2)
    assert "Transformer(" in txt and "(layers_0): Block(" in txt
    assert "% Params" in txt and "MACs" in txt
    agg = aggregate_by_depth(root, max_depth=1)
    assert "depth 0:" in agg and "depth 1:" in agg


def test_engine_prints_profile_tree(tmp_path):
    import deepspeed_tpu
    report_file = tmp_path / "profile.txt"
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "flops_profiler": {"enabled": True, "profile_step": 1,
                                   "output_file": str(report_file)}})
    rng = np.random.default_rng(0)
    b = {"input_ids": rng.integers(0, 64, (8, 16)).astype(np.int32)}
    for _ in range(2):
        loss = engine(b)
        engine.backward(loss)
        engine.step()
    out = report_file.read_text()
    assert "DeepSpeed Flops Profiler" in out
    assert "(layers_0): Block(" in out
    assert "Detailed Profile per GPU" in out


def test_module_profile_walk_depths():
    root = ModuleProfile("", "M")
    root.child("a").child("b")
    depths = {node.name: d for d, node in root.walk()}
    assert depths == {"": 0, "a": 1, "b": 2}


# --------------------------------------------------------------------- #
# The unified device-memory reader + shared cost model (PR: device-memory
# & roofline observatory)
# --------------------------------------------------------------------- #
class _FakeAccel:
    """Accelerator stub with a controllable memory_snapshot."""

    def __init__(self, limit, source):
        self._limit, self._source = limit, source

    def memory_snapshot(self, device_index=None):
        return {"device": "fake:0", "platform": "fake",
                "bytes_in_use": 123, "peak_bytes_in_use": 456,
                "bytes_limit": self._limit, "limit_source": self._source}


def test_device_hbm_bytes_prefers_backend_limit(monkeypatch):
    from deepspeed_tpu.accelerator import real_accelerator
    from deepspeed_tpu.profiling.flops_profiler import profiler
    monkeypatch.setattr(real_accelerator, "_accelerator",
                        _FakeAccel(7 * 2**30, "runtime"))
    assert profiler.device_hbm_bytes() == 7 * 2**30


def test_device_hbm_bytes_cpu_reports_unbounded():
    """The CPU test backend has no memory stats: limit 0 / "unknown",
    and callers skip budget checks — the one entry without a runtime
    limit."""
    from deepspeed_tpu.accelerator.tpu_accelerator import CPU_Accelerator
    from deepspeed_tpu.profiling.flops_profiler import profiler
    snap = CPU_Accelerator().memory_snapshot()
    assert snap["bytes_limit"] == 0 and snap["limit_source"] == "unknown"
    assert profiler.device_hbm_bytes() == 0


def test_memory_snapshot_tpu_without_limit_raises(monkeypatch):
    """TPU_Accelerator.memory_snapshot: the runtime's bytes_limit is the
    only source; a TPU that reports none is an error, never an assumed
    datasheet capacity."""
    from deepspeed_tpu.accelerator.tpu_accelerator import TPU_Accelerator

    class _Dev:
        id = 0
        device_kind = "TPU v4"
        platform = "tpu"
        stats = {}

        def memory_stats(self):
            return self.stats
    accel = TPU_Accelerator()
    monkeypatch.setattr(accel, "devices", lambda: [_Dev()])
    with pytest.raises(RuntimeError, match="no bytes_limit"):
        accel.memory_snapshot()
    _Dev.stats = {"bytes_limit": 7 * 2**30, "bytes_in_use": 5}
    snap = accel.memory_snapshot()
    assert snap["bytes_limit"] == 7 * 2**30
    assert snap["limit_source"] == "runtime"


def test_device_peak_unknown_kind_raises(monkeypatch):
    """A device kind missing from the peak tables is an error, not a
    silent 100 TFLOP/s / 819 GB/s default."""
    from deepspeed_tpu.profiling.flops_profiler import profiler

    class _Dev:
        device_kind = "mystery"
        platform = "mystery"
    monkeypatch.setattr(profiler.jax, "devices", lambda: [_Dev()])
    with pytest.raises(KeyError, match="no published peak"):
        profiler.device_peak_tflops()


def test_cost_analysis_of_routes_through_shared_model():
    """profile-side cost extraction == the contract/roofline cost model
    (autotuning.cost_model.xla_cost_analysis) on the same program."""
    import jax.numpy as jnp
    from deepspeed_tpu.autotuning.cost_model import (compiled_costs,
                                                     xla_cost_analysis)
    from deepspeed_tpu.profiling.flops_profiler.profiler import \
        cost_analysis_of

    def f(x):
        return (x @ x).sum()
    x = jnp.ones((32, 32))
    via_profiler = cost_analysis_of(f, x)
    compiled = jax.jit(f).lower(x).compile()
    assert via_profiler == xla_cost_analysis(compiled)
    costs = compiled_costs(compiled)
    assert costs["flops"] == float(via_profiler.get("flops", 0.0)) > 0
    assert costs["bytes_accessed"] > 0
