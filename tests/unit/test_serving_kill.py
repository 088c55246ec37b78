"""The kill-at-seam acceptance proof of the serving SLO layer
(``docs/serving.md`` "Robustness & SLOs"): a subprocess driver
(``serving_driver.py``) killed at EVERY serving fault-injection seam,
relaunched, whose merged outputs are bitwise-identical to an uninterrupted
run.  In a file of its own — the rest of the layer's tests are
``test_serving_slo.py`` — because under ``--dist loadfile`` a file is one
worker's, and these six scenarios are half of that file's time."""

import os
import subprocess
import sys

import pytest

from deepspeed_tpu.runtime.fault.manifest import list_tags, verify_manifest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
DRIVER = os.path.join(REPO, "tests", "unit", "serving_driver.py")


def _run_serving_driver(ckpt_dir, results_path, cache_dir,
                        inject_spec=None, drain_budget=0.0,
                        speculative=False):
    env = dict(os.environ)
    env["DSTPU_REPO_ROOT"] = REPO
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
    env.pop("DSTPU_FAULT_INJECT", None)
    env.pop("BENCH_MODEL", None)
    if inject_spec:
        env["DSTPU_FAULT_INJECT"] = inject_spec
    return subprocess.run(
        [sys.executable, DRIVER, "--ckpt-dir", str(ckpt_dir),
         "--results", str(results_path),
         "--drain-budget", str(drain_budget)]
        + (["--spec"] if speculative else []),
        env=env, capture_output=True, text=True, timeout=240)


def _merged_results(path):
    out = {}
    with open(path) as f:
        for line in f:
            idx, status, toks = line.strip().split(",", 2)
            out[int(idx)] = (status, toks)
    return out


@pytest.fixture(scope="module")
def serving_driver_reference(tmp_path_factory):
    """One uninterrupted driver run: the bitwise reference (and the
    shared per-module compile cache every scenario reuses — safe: kills
    land at seams, never mid-cache-write)."""
    base = tmp_path_factory.mktemp("serving_driver")
    cache = base / "cache"
    results = base / "ref_results.txt"
    proc = _run_serving_driver(base / "ckpt", results, cache)
    assert proc.returncode == 0, proc.stderr[-3000:]
    ref = _merged_results(results)
    assert sorted(ref) == [0, 1, 2, 3, 4, 5]
    assert ref[5][0] == "SHED_DEADLINE", ref
    assert all(ref[i][0] == "COMPLETED" for i in range(5)), ref
    return {"cache": cache, "ref": ref, "base": base}


# (scenario, DSTPU_FAULT_INJECT spec, expected first-run rc, drain
#  budget, speculative serving)
SERVING_KILL_SCENARIOS = [
    # graceful: SIGTERM mid-serving -> drain -> snapshot -> exit 3
    ("sigterm_graceful",
     "point=serving.sigterm_at_iter,action=sigterm,at=4", 3, 0.0, False),
    # hard kills (os._exit, no cleanup) at each dispatch seam
    ("exit_pre_admit",
     "point=serving.pre_admit,action=exit,at=2", 17, 0.0, False),
    ("exit_pre_decode_dispatch",
     "point=serving.pre_decode_dispatch,action=exit,at=3", 17, 0.0,
     False),
    # hard kill DURING the graceful drain, before the snapshot publishes
    ("exit_mid_drain",
     "point=serving.sigterm_at_iter,action=sigterm,at=5;"
     "point=serving.mid_drain,action=exit,at=1", 17, 5.0, False),
    # SPECULATIVE serving (self-draft, k=2): SIGTERM mid-speculation —
    # the snapshot must hold committed tokens only (uncommitted draft
    # tokens are discarded), the resumed SPECULATIVE run must merge
    # bitwise with the NON-speculative reference (the bitwise-greedy
    # contract and the kill harness, proven together)
    ("sigterm_graceful_spec",
     "point=serving.sigterm_at_iter,action=sigterm,at=4", 3, 0.0, True),
    # hard kill at the decode seam mid-speculation: in-flight verify
    # windows die unprocessed, nothing uncommitted may leak into results
    ("exit_pre_decode_dispatch_spec",
     "point=serving.pre_decode_dispatch,action=exit,at=3", 17, 0.0,
     True),
]


@pytest.mark.parametrize("name,spec,want_rc,budget,speculative",
                         SERVING_KILL_SCENARIOS,
                         ids=[s[0] for s in SERVING_KILL_SCENARIOS])
def test_serving_kill_at_seam_resumes_bitwise(
        name, spec, want_rc, budget, speculative,
        serving_driver_reference, tmp_path):
    """Acceptance: the serving driver killed at each serving seam —
    gracefully (SIGTERM -> drain -> crash-atomic snapshot) or hard
    (os._exit) — relaunches, resumes/resubmits, and every non-shed
    request completes with greedy outputs BITWISE-identical to the
    uninterrupted reference run; the deadline request reports
    SHED_DEADLINE in every scenario.  The *_spec scenarios run the SAME
    workload under speculative serving (self-draft) and must still
    match the non-speculative reference bitwise — mid-speculation kills
    may never surface uncommitted draft tokens."""
    ref = serving_driver_reference["ref"]
    cache = serving_driver_reference["cache"]
    results = tmp_path / "results.txt"
    proc = _run_serving_driver(tmp_path / "ckpt", results, cache,
                               inject_spec=spec, drain_budget=budget,
                               speculative=speculative)
    assert proc.returncode == want_rc, \
        f"{name}: expected rc={want_rc}, got {proc.returncode}\n" \
        + proc.stderr[-3000:] + proc.stdout[-1000:]
    if want_rc == 3:
        # graceful preemption published a manifest-valid snapshot
        tags = list_tags(str(tmp_path / "ckpt"))
        assert tags, "preemption must leave a snapshot"
        assert verify_manifest(str(tmp_path / "ckpt" / tags[0])) == []
    proc = _run_serving_driver(tmp_path / "ckpt", results, cache,
                               drain_budget=budget,
                               speculative=speculative)
    assert proc.returncode == 0, \
        f"{name}: resume failed\n" + proc.stderr[-3000:]
    got = _merged_results(results)
    assert got == ref, \
        f"{name}: resumed outputs diverge from the uninterrupted run\n" \
        f"want {ref}\ngot  {got}"
