"""Smoke tests for the op micro-benchmark CLI (analog of reference
``tests/perf/adam_test.py`` — correctness of the harness, not speed)."""

from deepspeed_tpu.benchmarks import op_bench


def test_bench_adam_smoke():
    r = op_bench.bench_adam(numel=2048, iters=1)
    assert r["op"] == "fused_adamw" and r["ms"] > 0


def test_bench_flash_smoke():
    r = op_bench.bench_flash_attention(b=1, s=256, h=2, d=64, iters=1)
    assert r["ms"] > 0 and "TFLOP/s" in r   # rate rounds to 0 on slow CPU
    r = op_bench.bench_flash_attention(b=1, s=256, h=2, d=64, iters=1,
                                       bwd=True)
    assert r["op"].endswith("bwd")
    # one tile covers S=256: the whole square for half of it, in each kernel
    assert r["pairs_executed_over_needed"] == {"fwd": 1.992, "dq_dkv": 1.992}


def test_bench_flash_reports_the_sft_cells_plan():
    """Defaults are the ``opt13b-sft-1chip`` shape; the executed-over-needed
    figure comes from the plan the kernels walk (10 of 16 512-tiles)."""
    import inspect
    sig = inspect.signature(op_bench.bench_flash_attention).parameters
    assert [sig[n].default for n in "bshd"] == [2, 2048, 32, 64]
    from deepspeed_tpu.ops.transformer.flash_attention import tile_plan
    c = tile_plan("fwd", 2048, 2048, 64, "bfloat16", True).counts()
    assert round(c["tiles_run"] * 512 * 512 / (2048 * 2049 / 2), 3) == 1.249


def test_bench_quant_smoke():
    r = op_bench.bench_quantizer(numel=64 * 2048, iters=1)
    assert r["ms"] > 0


def test_long_context_bench_smoke():
    from deepspeed_tpu.benchmarks.long_context_bench import bench_sp_attention
    from deepspeed_tpu.parallel.topology import (initialize_topology,
                                                 reset_topology)
    reset_topology()
    initialize_topology(sp=8)
    try:
        r = bench_sp_attention("ring", 512, heads=4, head_dim=16, iters=1)
        assert r["sp"] == 8 and r["ms"] > 0
    finally:
        reset_topology()
