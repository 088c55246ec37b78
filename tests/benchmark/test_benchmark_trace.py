"""The reduction from trace events to numbers, on hand-made events with
known answers, and the operation and byte counts against hand counts."""

import os

import pytest

from benchmark import opsbytes, spec, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
D0, D1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS, MODS = trace.OPS_LINE, trace.MODULES_LINE


KERNEL = ('%attn.7 = (bf16[32,32,64]{2,1,0:T(8,128)(2,1)S(1)}, bf16[24,705,64,'
          '2048]{3,2,1,0}) custom-call(s32[32]{0} %p), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')
FUSION1 = "%fusion.1 = bf16[2,2048]{1,0:T(8,128)(2,1)} fusion(bf16[2]{0} %x)"
FUSION2 = "%fusion.2 = bf16[2,2048]{1,0:T(8,128)(2,1)} fusion(bf16[2]{0} %x)"
GATHER = ("%all-gather.3 = bf16[4,2048]{1,0:T(8,128)(2,1)} "
          "all-gather(bf16[1,2048]{1,0} %w), replica_groups={}")
WHILE = ("%while.2 = (s32[]{:T(128)}, bf16[24,705,64,2048]{3,2,1,0:T(8,128)"
         "(2,1)}) while((s32[]{:T(128)}) %tuple), body=%b")


def _events():
    # device 0: a while loop 0-2 holding a fusion and a kernel; a chunk step
    # 3-4 (a collective alone 3-3.5, overlapped 3.5-4); a kernel 6-10;
    # window 0-10 -> busy 7 s, idle 30%
    return [
        (D0, MODS, "jit_decode_block(123)", 0.0, 2.0),
        (D0, MODS, "jit_decode_block(123)", 6.0, 4.0),
        (D0, MODS, "jit_chunk_step(9)", 3.0, 1.0),
        (D0, OPS, WHILE, 0.0, 2.0),
        (D0, OPS, FUSION1, 0.0, 0.5),
        (D0, OPS, KERNEL, 0.5, 1.5),
        (D0, OPS, GATHER, 3.0, 1.0),
        (D0, OPS, FUSION2, 3.5, 0.5),
        (D0, OPS, KERNEL, 6.0, 4.0),
        # device 1: busy 0-5 and 9.5-10 -> mean busy (7 + 5.5) / 2
        (D1, OPS, FUSION1, 0.0, 5.0),
        (D1, OPS, FUSION2, 9.5, 0.5),
        (HOST, "python", "scheduler.step", 1.9, 1.2),
        (HOST, "python", "bookkeeping", 4.0, 2.1),
        (HOST, "python", "inner", 4.5, 1.0),
    ]


def test_short_names_and_kernel_matching():
    assert trace.short_name(KERNEL) == "attn pallas"
    assert trace.short_name(WHILE) == "while while"
    assert trace.short_name(FUSION1) == "fusion fusion"
    assert trace.short_name(GATHER) == "all-gather all-gather"
    assert trace.is_pallas(KERNEL) and trace.is_pallas(KERNEL, "attn")
    assert not trace.is_pallas(KERNEL, "mlp")
    assert not trace.is_pallas(FUSION1)


def test_busy_idle_and_window():
    tr = trace.Trace(_events())
    assert tr.device_planes == [D0, D1]
    assert tr.window == (0.0, 10.0) and tr.window_s == 10.0
    assert tr.busy_s() == pytest.approx((7.0 + 5.5) / 2)
    assert tr.idle_pct() == pytest.approx(100 * (1 - 6.25 / 10))


def test_one_device_and_an_explicit_window():
    one = [e for e in _events() if e[0] != D1]
    tr = trace.Trace(one, window=(1.0, 9.0))
    # clipped to 1-9: busy 1-2, 3-4, 6-9 = 5 s of 8
    assert tr.busy_s() == pytest.approx(5.0)
    assert tr.idle_pct() == pytest.approx(37.5)


def test_module_durations_and_kernel_seconds():
    tr = trace.Trace(_events())
    assert tr.module_durations("decode_block") == [2.0, 4.0]
    assert tr.module_durations("chunk_step") == [1.0]
    assert tr.module_durations("train_step") == []
    secs, calls = tr.op_seconds(trace.is_pallas)
    assert (secs, calls) == (5.5, 2)
    # only the kernel calls that start inside a decode block / a chunk step
    assert tr.op_seconds(trace.is_pallas, module="decode_block") == (5.5, 2)
    assert tr.op_seconds(trace.is_pallas, module="chunk_step") == (0, 0)
    assert tr.op_seconds(lambda n: "all-gather" in n,
                         module="chunk_step") == (1.0, 1)


def test_exposed_collective_share():
    tr = trace.Trace(_events())
    pct, coll_s = tr.exposed_collective_pct()
    assert coll_s == pytest.approx(1.0)
    assert pct == pytest.approx(100 * 0.5 / 10)      # 3.0-3.5 alone


def test_breakdown_names_ops_and_attributes_gaps():
    b = trace.Trace(_events()).breakdown(top=3)
    # the while loop's own time is its span less its body's: 2 - 0.5 - 1.5
    assert b["device_ops"][0] == ["attn pallas", 5.5]
    assert [n for n, _ in b["device_ops"]] == \
        ["attn pallas", "fusion fusion", "all-gather all-gather"]
    assert dict(b["device_ops"])["fusion fusion"] == pytest.approx(1.0)
    # fusion.2 runs inside the all-gather's span and counts as its child
    assert dict(b["device_ops"])["all-gather all-gather"] == pytest.approx(0.5)
    gaps = dict(b["idle_gaps"])
    # gap 2-3 lies under scheduler.step; gap 4-6 under bookkeeping (which
    # covers all of it; 'inner' covers half)
    assert gaps == pytest.approx({"scheduler.step": 1.0, "bookkeeping": 2.0})
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_with_no_device_plane_reads_as_empty():
    tr = trace.Trace([e for e in _events() if e[0] == HOST])
    assert tr.device_planes == [] and tr.busy_s() == 0.0
    assert tr.module_durations("decode_block") == []


# --------------------------------------------------------------------- #
Z13 = dict(h=2048, heads=32, d=64, f=8192, layers=24, vocab=50272,
           positions=2048)


def test_model_flops_per_token_by_hand():
    matmul = 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 50272 * 2048
    assert matmul == 1310916608
    attention = 24 * 3 * (2 * 2 * 2048 * 2048) / 2
    assert opsbytes.model_flops_per_token(Z13, 2048) == \
        6 * matmul + attention == 8469479424.0


def test_flash_flops_by_hand():
    # one head, S=4, D=2, not causal: 6 matmuls of 2*4*4*2 = 64 ops
    assert opsbytes.flash_fwd_bwd_flops(1, 1, 4, 2, causal=False) == 384
    assert opsbytes.flash_fwd_bwd_flops(1, 1, 4, 2) == 192
    # the SFT cell's layer: batch 2, 32 heads of 64, S=2048
    assert opsbytes.flash_fwd_bwd_flops(2, 32, 2048, 64) == \
        6 * 2 * 2 * 32 * 2048 * 2048 * 64 / 2


def test_paged_decode_bytes_by_hand():
    # two live slots of 100 and 28 positions, 32 KV heads of 64, bf16:
    # K and V = 2 x 128 x 2048 x 2 B
    assert opsbytes.paged_decode_bytes([100, 28], 32, 64) == 1048576
    per_token_all_layers = 24 * opsbytes.paged_decode_bytes([1], 32, 64)
    cfg = spec.Benchmark(ROOT).cell("opt13b-serve-chat")["config"]
    assert per_token_all_layers == cfg["kv_bytes_per_token"] == 196608


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    pct, bound = opsbytes.roofline_pct(197e12, 0, 2.0, peaks)
    assert (pct, bound) == (50.0, "compute")
    pct, bound = opsbytes.roofline_pct(0, 819e9, 4.0, peaks)
    assert (pct, bound) == (25.0, "memory")


# --------------------------------------------------------------------- #
RECORDED = os.path.join(ROOT, "benchmark", "testdata", "small_trace.xplane.pb")


def test_the_reader_on_a_small_recorded_trace():
    """``small_trace.xplane.pb`` was recorded on a TPU v5e by
    ``benchmark/testdata/record_small_trace.py``: four executions of
    ``jit_small_step`` (eight matmul fusions each, about 0.72 ms), 20 ms of
    host sleep after each.  The numbers below are what
    ``python3 benchmark/trace.py`` printed for it on the chip (PR 24)."""
    events = trace.read_events(RECORDED)
    tr = trace.Trace(events)
    assert tr.device_planes == ["/device:TPU:0"]
    lines = {(e[0], e[1]) for e in events if e[0] == "/device:TPU:0"}
    assert lines == {("/device:TPU:0", "XLA Modules"),
                     ("/device:TPU:0", "XLA Ops"),
                     ("/device:TPU:0", "Async XLA Ops")}
    runs = tr.module_durations("small_step")
    assert len(runs) == 4 and len(tr.device_ops()) == 40
    assert sum(runs) == pytest.approx(0.00289, abs=2e-5)
    assert all(0.0006 < d < 0.0009 for d in runs)
    assert tr.window_s == pytest.approx(0.0677, abs=2e-4)
    assert tr.busy_s() == pytest.approx(0.0029, abs=5e-5)
    assert tr.idle_pct() == pytest.approx(95.7, abs=0.1)
    assert tr.op_seconds(trace.is_pallas) == (0, 0)
    secs, calls = tr.op_seconds(lambda n: " fusion(" in n,
                                module="small_step")
    assert calls == 32 and secs == pytest.approx(0.0028867, abs=1e-6)
    b = tr.breakdown()
    assert b["device_ops"][0][0] == "fusion fusion"
    assert b["device_ops"][0][1] == pytest.approx(0.0028867, abs=1e-6)
    # the device waits while the host sleeps inside the jitted call's wrapper
    assert b["idle_gaps"][0][0] == "PjitFunction(small_step)"
    assert b["idle_gaps"][0][1] == pytest.approx(0.0648, abs=2e-4)
