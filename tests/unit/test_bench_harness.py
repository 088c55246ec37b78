"""The bench harness must be crash-proof: each phase runs in its own
subprocess, a failed phase is retried once with a safe config, and a
double failure records an ``error`` field instead of erasing the record
(the reference's per-workload process isolation, ``launcher/runner.py:377``;
our round-3 driver capture was lost to exactly this failure mode).

The subprocess-spawning tests here are ``slow`` (nightly tier): each one
boots a full bench parent + calibration child (~15 s calibration on the
1-core container, ~2 min for the module), and the calibration-floor
timing guard still raced the box under tier-1 load — the known flake.
Tier-1 keeps the pure-host scheduling/annotation logic (phase order,
regression thresholds, record normalization), which is where every
actual harness regression so far has been caught."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
BENCH = os.path.join(REPO, "bench.py")


def run_bench(extra_env, out_dir):
    env = dict(os.environ)
    env.update({
        # the parent never touches jax; children read the platform here
        "JAX_PLATFORMS": "cpu",
        "BENCH_PHASE_TIMEOUT": "600",
        # keep scratch/partial files away from a possibly-live real run
        "BENCH_OUT_DIR": str(out_dir),
    })
    env.pop("BENCH_MODEL", None)
    env.update(extra_env)
    proc = subprocess.run([sys.executable, BENCH], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    return json.loads(line), proc.stderr


@pytest.fixture(scope="module")
def calibrate_run(tmp_path_factory):
    """One calibrate-only bench run, shared by the contract test and —
    as a MEASURED floor for the calibration phase's wall clock — by the
    timeout test below (whose budget was a constant 15 s that the slow
    container's ~15 s calibration raced, the known flake)."""
    out = tmp_path_factory.mktemp("calibrate_floor")
    result, stderr = run_bench({"BENCH_PHASES": "calibrate"}, out)
    return result, stderr, out


@pytest.mark.slow
def test_bench_single_phase_json_contract(calibrate_run):
    """One phase on the CPU backend: rc 0, one final JSON line with the
    driver contract fields, calibration populated with measured peaks."""
    result, _, out_dir = calibrate_run
    for field in ("metric", "value", "unit", "vs_baseline"):
        assert field in result, result
    cal = result["calibration"]
    assert cal["platform"] == "cpu"
    assert cal["measured_hbm_gbps"] > 0
    assert cal["measured_mxu_tflops"] > 0
    assert cal["datasheet_hbm_gbps"] > 0
    assert "phase_errors" not in result
    # incremental record exists and holds the phase
    with open(out_dir / ".bench_partial.json") as f:
        partial = json.load(f)
    assert "calibration" in partial


@pytest.mark.slow
def test_bench_fallback_retry_recovers(tmp_path):
    """A phase that dies on its primary attempt is retried with the safe
    config and lands in the record with ``fallback: true``."""
    result, stderr = run_bench({"BENCH_PHASES": "calibrate",
                                "BENCH_TEST_FAIL_PRIMARY": "calibrate"},
                               tmp_path)
    cal = result["calibration"]
    assert cal.get("fallback") is True, cal
    assert cal["measured_hbm_gbps"] > 0
    assert "phase_errors" not in result
    assert "retrying with safe config" in stderr


@pytest.mark.slow
def test_bench_double_failure_records_error_and_continues(tmp_path):
    """A phase that dies on BOTH attempts records an ``error`` field; the
    suite still exits 0 and later phases still run (round-3 regression:
    one late-phase OOM converted the whole record into a stack trace)."""
    result, _ = run_bench({"BENCH_PHASES": "calibrate",
                           "BENCH_TEST_FAIL_ALWAYS": "calibrate"},
                          tmp_path)
    cal = result["calibration"]
    assert "error" in cal
    assert "injected unconditional failure" in cal["error"]
    assert "phase_errors" in result
    # the harness survived: the contract line still came out on stdout
    assert result["unit"] == "tokens/s/chip"


@pytest.mark.slow
def test_bench_parent_never_initializes_backend():
    """The parent orchestrator must never create a jax device client — a
    dead phase's HBM can only be pinned by a process holding the device,
    and the parent must not be one (the round-3 retry-inside-except kept
    1.3B params alive through the traceback frames).  Importing jax is
    harmless; the check is on backend CLIENTS."""
    code = ("import sys; sys.argv=['bench.py']; "
            "import bench; "
            "from jax._src import xla_bridge; "
            "assert not xla_bridge._backends, 'parent created a backend'; "
            "print('CLEAN')")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CLEAN" in proc.stdout


@pytest.mark.slow
def test_bench_timeout_skips_and_records_prior_phases(calibrate_run,
                                                      tmp_path):
    """A phase that exceeds its wall-clock budget is skipped-and-recorded
    (NO fallback retry — a safe config fixes an OOM, not slowness) and
    every already-finished phase survives in BOTH incremental records
    (the round-5 regression: one 40-min phase starved the whole suite and
    the record was rc=124 with zero numbers).

    The budget is scaled off the calibration phase's MEASURED wall clock,
    not a constant: on the slow container calibration takes ~15 s, so a
    flat 15 s budget made this test race its own setup phase (the known
    pre-existing flake) — calibration must comfortably fit while the
    hanging phase still times out quickly."""
    floor = calibrate_run[0]["calibration"]["phase_wall_s"]
    budget = max(15, int(floor * 2.5) + 5)
    result, stderr = run_bench({"BENCH_PHASES": "calibrate,north",
                                "BENCH_TEST_HANG": "north",
                                "BENCH_PHASE_TIMEOUT": str(budget)},
                               tmp_path)
    # the completed phase's numbers survive the later overrun
    assert result["calibration"]["measured_hbm_gbps"] > 0
    ns = result["north_star"]
    assert ns.get("timeout") is True
    assert "timeout" in ns["error"]
    assert "exceeded its" in stderr and "budget" in stderr
    assert "retrying with safe config" not in stderr     # no doubled damage
    # incremental final-format record on disk holds the same story
    with open(tmp_path / "BENCH_partial.json") as f:
        rec = json.load(f)
    assert rec["calibration"]["measured_hbm_gbps"] > 0


@pytest.mark.slow
def test_bench_suite_budget_skips_and_records(tmp_path):
    """BENCH_SUITE_BUDGET caps every phase's timeout at what the suite can
    still afford and records out-of-budget phases as skipped — the suite
    always finishes inside the budget with the contract JSON intact (the
    round-5 rc=124: the budget was only checked between phases, so one
    phase blew straight through the wrapping driver's window)."""
    result, stderr = run_bench({"BENCH_PHASES": "calibrate,north",
                                "BENCH_SUITE_BUDGET": "1"}, tmp_path)
    assert "skipped" in result["calibration"]
    assert "skipped" in result["north_star"]
    assert "suite budget exhausted" in stderr
    assert result["unit"] == "tokens/s/chip"      # contract line survived
    with open(tmp_path / "BENCH_partial.json") as f:
        rec = json.load(f)
    assert "skipped" in rec["calibration"]


def test_bench_round_robin_phase_order(tmp_path, monkeypatch):
    """Under BENCH_SUITE_BUDGET phase order rotates by staleness across
    rounds (the r05 blackout: a fixed cheap-first order measured the same
    3 leading phases every round): phases starved in earlier rounds run
    before phases measured last round, calibration stays pinned first,
    and a fresh machine (no BENCH_r* trail) keeps the registry's
    cheap-first order.  Pure host logic — no jax, no subprocess."""
    monkeypatch.setenv("BENCH_OUT_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(REPO)
    import bench
    base = [k for k, _, _ in bench.PHASES]
    assert "serving_paged" in base          # the paged phase is registered
    # no trail: the pinned head (calibration, memory_snapshot, then the
    # paged-kernel acceptance phase) comes first, the rest keep the
    # registry's cheap-first order verbatim
    head = ["calibration", "memory_snapshot", "serving_paged"]
    assert [k for k, _, _ in bench._phase_order(bench.PHASES)] \
        == head + [k for k in base if k not in head]

    # round 1's budget afforded calibration + guard + north; offload was
    # skipped, decode timed out, the rest never ran
    r1 = {"metric": "m", "unit": "tokens/s/chip",
          "calibration": {"measured_hbm_gbps": 1.0},
          "sft_350m_guard": {"mfu": 0.3},
          "north_star": {"mfu": 0.4},
          "optimizer_offload": {"skipped": "suite budget exhausted"},
          "generation": {"error": "timeout after 900s", "timeout": True}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(r1))
    # a corrupt trail file must be skipped, never wedge scheduling
    (tmp_path / "BENCH_r02.json").write_text("{half a reco")
    order = [k for k, _, _ in bench._phase_order(bench.PHASES)]
    assert order[0] == "calibration"
    # the memory micro-phase is pinned right behind calibration: the
    # per-program memory record commits before any heavy phase can
    # starve it (the r05-blackout lesson on the memory axis)
    assert order[1] == "memory_snapshot"
    # serving_paged is pinned third: it carries the paged-kernel
    # acceptance story and must land in the NEXT record (BENCH_r06)
    # rather than wait out a starvation rotation
    assert order[2] == "serving_paged"
    assert sorted(order) == sorted(base)    # nothing dropped or invented
    measured = {"sft_350m_guard", "__headline__"}
    pinned = {"calibration", "memory_snapshot", "serving_paged"}
    starved = [k for k in base
               if k not in measured and k not in pinned]
    # every starved phase (incl. the skipped + timed-out ones) runs
    # before anything measured in round 1...
    assert max(order.index(k) for k in starved) \
        < min(order.index(k) for k in measured)
    # ...and starved phases keep their cheap-first relative order
    assert [k for k in order if k in starved] \
        == [k for k in base if k in starved]

    # round 2 measures what starved; round 3 then prioritizes round 1's
    # leaders again — full rotation, every phase measured every K rounds
    r2 = {k: {"ok": 1} for k in starved}
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(r2))
    order3 = [k for k, _, _ in bench._phase_order(bench.PHASES)]
    assert order3.index("sft_350m_guard") \
        < min(order3.index(k) for k in starved)


@pytest.mark.slow
def test_bench_interrupt_emits_partial_record(tmp_path):
    """SIGINT mid-suite (a user's Ctrl-C, or a wrapping driver giving up):
    the parent must still emit the driver-contract JSON with every
    completed phase, exit 0, and leave the incremental record on disk."""
    import signal
    import time as _time
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "BENCH_OUT_DIR": str(tmp_path),
                "BENCH_PHASES": "calibrate,north",
                "BENCH_TEST_HANG": "north",
                "BENCH_PHASE_TIMEOUT": "600"})
    env.pop("BENCH_MODEL", None)
    proc = subprocess.Popen([sys.executable, BENCH], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    try:
        # wait until phase 1 (calibrate) has landed in the incremental
        # record, i.e. the suite is inside the hanging phase 2
        partial = tmp_path / "BENCH_partial.json"
        deadline = _time.monotonic() + 300
        while _time.monotonic() < deadline:
            if partial.exists() and "calibration" in partial.read_text():
                break
            _time.sleep(0.5)
        else:
            raise AssertionError("calibrate never finished")
        _time.sleep(1.0)
        proc.send_signal(signal.SIGINT)
        out, err = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 0, err[-2000:]
    assert "interrupted during north" in err
    record = json.loads(out.strip().splitlines()[-1])
    assert record["calibration"]["measured_hbm_gbps"] > 0
    assert record["interrupted_during"] == "north"
    assert record["unit"] == "tokens/s/chip"


# --------------------------------------------------------------------- #
# Per-phase regression thresholds vs the previous BENCH_r* record
# (warn-and-annotate; ROADMAP item 5 leftover).  Pure host logic.
# --------------------------------------------------------------------- #
def test_bench_regression_annotation(tmp_path, monkeypatch):
    """A phase metric that dropped beyond the threshold vs the newest
    previous record is annotated in the phase record; small wobbles and
    non-perf numbers are not."""
    monkeypatch.setenv("BENCH_OUT_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(REPO)
    import bench
    prev = {"decode": {"decode_tokens_per_sec_chip": 1000.0, "mfu": 0.40,
                       "e2e_time_s": 2.0, "batch_size": 64,
                       "sub": {"speedup_vs_sequential": 3.0}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(prev))

    phase = {"decode_tokens_per_sec_chip": 700.0, "mfu": 0.39,
             "e2e_time_s": 2.6, "batch_size": 32,
             "sub": {"speedup_vs_sequential": 3.1}}
    bench._annotate_regressions("decode", phase)
    regs = {r["metric"]: r for r in phase["regressions"]}
    # 30% throughput drop and 23% slowdown (lower-is-better) annotated...
    assert "decode_tokens_per_sec_chip" in regs
    assert regs["decode_tokens_per_sec_chip"]["drop_pct"] == 30.0
    assert "e2e_time_s" in regs
    # ...the 2.5% mfu wobble, the improved speedup, and the non-perf
    # batch_size change are not
    assert "mfu" not in regs and "batch_size" not in regs
    assert "sub.speedup_vs_sequential" not in regs

    # within threshold: no annotation key at all
    ok_phase = {"decode_tokens_per_sec_chip": 950.0, "mfu": 0.41,
                "e2e_time_s": 2.1, "batch_size": 64}
    bench._annotate_regressions("decode", ok_phase)
    assert "regressions" not in ok_phase

    # threshold is tunable; 0 disables
    tight = {"decode_tokens_per_sec_chip": 950.0}
    bench._annotate_regressions("decode", tight, threshold=0.01)
    assert tight["regressions"][0]["drop_pct"] == 5.0
    off = {"decode_tokens_per_sec_chip": 10.0}
    bench._annotate_regressions("decode", off, threshold=0)
    assert "regressions" not in off

    # skipped/errored phases and never-measured phases are untouched
    skipped = {"skipped": "suite budget exhausted"}
    bench._annotate_regressions("decode", skipped)
    assert "regressions" not in skipped
    fresh = {"tokens_per_sec_chip": 1.0}
    bench._annotate_regressions("never_measured_phase", fresh)
    assert "regressions" not in fresh


def test_bench_record_normalization(tmp_path, monkeypatch):
    """The BENCH_r* trail accepts final-format records AND driver
    wrappers ({n, cmd, rc, tail, parsed}): the record is recovered from
    `parsed` or from the last stdout line in `tail`; a tail truncated
    mid-record is skipped rather than wedging the trail."""
    monkeypatch.setenv("BENCH_OUT_DIR", str(tmp_path))
    monkeypatch.syspath_prepend(REPO)
    import bench
    final = {"decode": {"decode_tokens_per_sec_chip": 5.0}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(final))
    wrapper = {"n": 2, "cmd": "python bench.py", "rc": 0, "parsed": None,
               "tail": "[INFO] noise\n" + json.dumps(
                   {"decode": {"decode_tokens_per_sec_chip": 7.0}})}
    (tmp_path / "BENCH_r02.json").write_text(json.dumps(wrapper))
    clipped = {"n": 3, "cmd": "python bench.py", "rc": 124, "parsed": None,
               "tail": '_per_sec_chip": 8.0}}'}      # cut from the left
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(clipped))
    parsed = {"n": 4, "cmd": "python bench.py", "rc": 0, "tail": "x",
              "parsed": {"decode": {"decode_tokens_per_sec_chip": 9.0}}}
    (tmp_path / "BENCH_r04.json").write_text(json.dumps(parsed))

    trail = bench._round_trail()
    vals = [r["decode"]["decode_tokens_per_sec_chip"] for r in trail]
    assert vals == [5.0, 7.0, 9.0]          # clipped r03 skipped

    # regression annotation uses the NEWEST recovered record (r04)
    phase = {"decode_tokens_per_sec_chip": 6.0}
    bench._annotate_regressions("decode", phase, trail=trail)
    assert phase["regressions"][0]["prev"] == 9.0
