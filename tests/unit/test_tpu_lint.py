"""tpu-lint tests: every rule (positive + negative fixture), suppression
semantics, CLI exit codes, a zero-findings gate over the real package, and
the jaxpr-level entry-point checks — all tier-1 (no slow marker), so lint
regressions fail the tier-1 command with no extra CI infra."""

import os
import pathlib

import pytest

from deepspeed_tpu.tools.lint import run_lint
from deepspeed_tpu.tools.lint.__main__ import main as lint_main

HERE = pathlib.Path(__file__).resolve().parent
FIXTURES = HERE / "tpu_lint_fixtures"
PACKAGE = HERE.parents[1] / "deepspeed_tpu"


def lint_fixture(name, rules=None):
    findings, stats = run_lint([str(FIXTURES / name)], rules=rules)
    return findings, stats


@pytest.mark.parametrize("rule_id,expected_min", [
    ("TL001", 7), ("TL002", 3), ("TL003", 4), ("TL004", 2), ("TL005", 2),
    ("TL006", 9), ("TL007", 4), ("TL008", 6), ("TL009", 5), ("TL010", 7),
    ("TL011", 8)])
def test_rule_positive_fixture(rule_id, expected_min):
    findings, _ = lint_fixture(f"{rule_id.lower()}_positive.py")
    hits = [f for f in findings if f.rule == rule_id]
    assert len(hits) >= expected_min, \
        f"{rule_id}: expected >= {expected_min} findings, got {findings}"


@pytest.mark.parametrize("rule_id",
                         ["TL001", "TL002", "TL003", "TL004", "TL005",
                          "TL006", "TL007", "TL008", "TL009", "TL010",
                          "TL011"])
def test_rule_negative_fixture(rule_id):
    findings, _ = lint_fixture(f"{rule_id.lower()}_negative.py")
    hits = [f for f in findings if f.rule == rule_id]
    assert not hits, f"{rule_id} false positives: {hits}"


def test_tl001_reachability_through_helper():
    """A sync inside a plain helper CALLED from a hot path is flagged."""
    findings, _ = lint_fixture("tl001_positive.py")
    helper_hits = [f for f in findings
                   if f.rule == "TL001" and 18 <= f.line <= 20]
    assert helper_hits, "sync in helper reachable from @hot_path not flagged"


def test_suppression_line_function_and_wrong_rule():
    findings, stats = lint_fixture("suppression.py")
    # line- and function-level TL001 suppressions hold (3 sites suppressed)
    assert stats["suppressed"].get("TL001", 0) == 3
    # the wrong-rule suppression does NOT silence TL001
    leaked = [f for f in findings if f.rule == "TL001"]
    assert len(leaked) == 1 and "step_with_wrong_rule" in \
        pathlib.Path(leaked[0].path).read_text().splitlines()[leaked[0].line - 2]


def test_cli_exit_codes(capsys):
    assert lint_main([str(FIXTURES / "tl001_positive.py")]) == 1
    assert lint_main([str(FIXTURES / "tl001_negative.py")]) == 0
    capsys.readouterr()


def test_cli_list_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("TL001", "TL002", "TL003", "TL004", "TL005", "TL006",
                "TL007", "TL008", "TL009", "TL010", "TL011"):
        assert rid in out


def test_cli_update_requires_contracts(capsys):
    assert lint_main(["--update"]) == 2
    capsys.readouterr()


def test_cli_concurrency_exits_nonzero_on_unlocked_access(capsys):
    """`ds_lint --concurrency` on a synthetically introduced unlocked
    guarded-field access must exit nonzero from the STATIC sweep (the
    slow interleaving prover is skipped once the sweep is dirty)."""
    assert lint_main(["--concurrency",
                      str(FIXTURES / "tl008_positive.py")]) == 1
    out = capsys.readouterr().out
    assert "TL008" in out and "tpu-lint[concurrency]" in out


def test_cli_stats_docs_gate_green_and_detects_drift(tmp_path, capsys):
    """`ds_lint --stats-docs` (tier-1): every serving stats key and
    /metrics series must appear backticked in docs/observability.md —
    green on the repo as committed, exit 1 when the doc loses a key,
    exit 2 when the collector loses its sources."""
    from deepspeed_tpu.tools.lint import stats_docs
    assert lint_main(["--stats-docs"]) == 0
    out = capsys.readouterr().out
    assert "stats keys" in out and "documented" in out
    # the collectors see the real metric surface
    keys = stats_docs.collect_stats_keys()
    series = stats_docs.collect_metric_series()
    assert {"iterations", "decode_tokens", "completed",
            "lock_wait_scheduler_s"} <= keys
    assert {"dstpu_serving_queue_depth", "dstpu_serving_ttft_seconds",
            "dstpu_serving_lock_wait_seconds"} <= series
    # drift detection: a doc missing everything but one key fails loudly
    thin = tmp_path / "obs.md"
    thin.write_text("| `iterations` | count |\n")
    assert stats_docs.main(doc_path=str(thin)) == 1
    out = capsys.readouterr().out
    assert "decode_tokens" in out and "dstpu_serving_ttft_seconds" in out
    capsys.readouterr()


def test_cli_comm_exits_nonzero_on_sweep_finding(capsys):
    """`ds_lint --comm` on a source tree with an unsuppressed replicated
    spec must exit 1 from the STATIC sweep (the mesh-scaling prover is
    skipped once the sweep is dirty)."""
    assert lint_main(["--comm", str(FIXTURES / "tl010_positive.py")]) == 1
    out = capsys.readouterr().out
    assert "TL010" in out and "tpu-lint[comm]" in out


def test_cli_comm_synthetic_replication_break(capsys, monkeypatch):
    """Acceptance: `ds_lint --comm` exits 1 on a synthetic replication
    break — a fixture plan whose replicated batch weak-scales with the
    mesh compiles at {1,2,4}, its per-chip all-reduce volume grows, and
    the prover fails READABLY (op, transitions, the smell, the fix)."""
    monkeypatch.setenv("DSTPU_COMM_PLANS_MODULE",
                       str(FIXTURES / "comm_fixture_plans.py"))
    assert lint_main(["--comm", str(FIXTURES / "tl010_negative.py")]) == 1
    out = capsys.readouterr().out
    assert "GROWS with mesh size" in out
    assert "fixture.replicated_batch" in out
    assert "replicated-tensor smell" in out
    assert "allowed_growth" in out


def test_tl011_canonical_axes_mirror_topology():
    """TL011's axis literal set is a pure-data mirror of the topology's
    AXIS_ORDER (the linter never imports the code under analysis) — this
    is the registry-matches-engine test keeping the two in lockstep."""
    from deepspeed_tpu.parallel.topology import AXIS_ORDER
    from deepspeed_tpu.tools.lint.rules.tl011_resharding_seams import \
        _CANONICAL_AXES
    assert _CANONICAL_AXES == AXIS_ORDER


def test_cli_concurrency_clean_paths_reach_the_prover(capsys, monkeypatch):
    """With a clean sweep, --concurrency hands off to the interleaving
    harness (stubbed here — the real harness runs as its own tier-1
    test in test_serving_concurrency.py)."""
    from deepspeed_tpu.tools.lint import interleave_check
    monkeypatch.setattr(interleave_check, "main", lambda: 0)
    assert lint_main(["--concurrency",
                      str(FIXTURES / "tl008_negative.py")]) == 0
    capsys.readouterr()


# ------------------------------------------------------------------ #
# Suppression edge cases: decorated functions + multi-rule disables
# ------------------------------------------------------------------ #
def test_suppression_on_decorated_functions():
    """A function-level disable works from the decorator line, from the
    LAST of stacked decorators, and from the def line under a decorator —
    all three cover the whole body."""
    findings, stats = lint_fixture("suppression_edge.py")
    deco = [f for f in findings if f.rule == "TL001" and f.line <= 23]
    assert not deco, f"decorated-function suppression leaked: {deco}"


def test_multi_rule_disable_on_one_line():
    """`disable=TL001,TL005 -- reason` suppresses BOTH rules on the line;
    a single-rule disable on the same pattern still leaks the other."""
    findings, stats = lint_fixture("suppression_edge.py")
    assert stats["suppressed"].get("TL001", 0) == 5
    assert stats["suppressed"].get("TL005", 0) == 1
    leaked = [f for f in findings if f.rule == "TL005"]
    assert len(leaked) == 1, leaked
    src = pathlib.Path(leaked[0].path).read_text().splitlines()
    assert "disable=TL001 --" in src[leaked[0].line - 1]


def test_package_is_lint_clean():
    """The gate: the real package must carry zero unsuppressed findings —
    new hazards either get fixed or get a reasoned disable comment."""
    findings, stats = run_lint([str(PACKAGE)])
    assert stats["files"] > 100, "package path wrong?"
    assert not findings, "unsuppressed tpu-lint findings:\n" + \
        "\n".join(str(f) for f in findings)


def test_hot_path_decorator_is_identity():
    from deepspeed_tpu.tools.lint.hotpath import REGISTERED, hot_path

    @hot_path("test.path")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    assert ("test.path", fn.__module__, fn.__qualname__) in REGISTERED


# ------------------------------------------------------------------ #
# jaxpr-level entry-point checks (CPU)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("builder_name", [
    "runtime_train_step", "runtime_apply_update", "inference_decode",
    "inference_prefill_chunk", "serving_decode_step",
    "serving_prefill_chunk", "serving_admit", "serving_spec_propose",
    "serving_spec_verify", "serving_spec_draft_prefill",
    "serving_spec_draft_admit", "hybrid_rollout"])
def test_jaxpr_entry_point(builder_name):
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.tools.lint import entry_points, jaxpr_check
    reset_topology()
    try:
        ep = getattr(entry_points, builder_name)()
        result = jaxpr_check.check_entry_point(ep)
        assert result.ok, f"{ep.name}: {result.problems}"
    finally:
        reset_topology()


def test_jaxpr_check_flags_missing_donation():
    """The harness must actually detect an undonated large-buffer program."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.tools.lint.entry_points import EntryPoint
    from deepspeed_tpu.tools.lint.jaxpr_check import check_entry_point

    fn = jax.jit(lambda params: jax.tree.map(lambda p: p * 2, params))
    ep = EntryPoint("synthetic.undonated", fn,
                    ({"w": jnp.ones((4, 4))},), expect_donation=True)
    result = check_entry_point(ep)
    assert not result.ok and "donation" in result.problems[0]


def test_jaxpr_check_flags_callbacks():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.tools.lint.entry_points import EntryPoint
    from deepspeed_tpu.tools.lint.jaxpr_check import check_entry_point

    def with_callback(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1

    ep = EntryPoint("synthetic.callback", jax.jit(with_callback),
                    (jnp.ones((4,)),), expect_donation=False)
    result = check_entry_point(ep)
    assert not result.ok and "callback" in result.problems[0]


# ------------------------------------------------------------------ #
# Runtime retrace counter (the dynamic half of TL006)
# ------------------------------------------------------------------ #
def test_serving_programs_compile_exactly_once_across_rounds():
    """Acceptance: the serving decode (and admit / admission-prefill)
    programs compile EXACTLY ONCE across >= 3 dispatch rounds with
    drifting host bookkeeping — round-varying request counts, prompt
    lengths/contents, eos ids, client ids, deadlines.  One extra
    signature anywhere here is tomorrow's 30 s mid-serve recompile."""
    from deepspeed_tpu.tools.lint.retrace_check import \
        measure_serving_retraces
    result = measure_serving_retraces(rounds=3)
    assert len(result["per_round"]) == 3
    for r, counts in enumerate(result["per_round"], 1):
        for program, n in counts.items():
            assert n == 1, \
                f"round {r}: serving {program} program compiled {n} " \
                f"signatures (retrace drift): {result}"
