"""The program-contract lockfile gate (``tools/lint/contract.py`` +
``PROGRAMS.lock``).

Tier-1 regenerates every contract — primitive multiset, donation-alias
count, collective counts, byte-level comm budgets, abstract signatures —
from the REAL hot-path programs and the ``parallel/`` sharding plans, and
diffs them against the committed lockfile: a lost donation, a new host
callback, a surprise collective, a byte-volume regression, or a drifted
signature fails here with a readable per-program diff instead of
surfacing as an HBM cliff rounds later.

The mesh-scaling tables ({1,2,4,8} bytes/chip per plan) are consistency-
checked here for free; their full regen-and-diff compiles 12 extra plan
points (~2 min) and runs as the ``slow``-marked test at the bottom and as
``ds_lint --comm`` — this container's tier-1 wall-clock budget cannot
absorb the compiles."""

import json
import os
import re
import pathlib
import subprocess
import sys

import pytest

from deepspeed_tpu.tools.lint import comm_contract, contract, mem_contract

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
LOCK = REPO / contract.LOCKFILE_NAME

# hot-path registry names covered by a locked program contract
_COVERED = {
    "runtime.train_step": "runtime.train_step",
    "runtime.apply_update": "runtime.apply_update",
    "inference.decode": "inference.decode",
    "inference.prefill_chunk": "inference.prefill_chunk",
    "serving.decode_step": "serving.decode_step",
    "serving.prefill_chunk": "serving.prefill_chunk",
    "serving.admit": "serving.admit",
    "serving.spec_propose": "serving.spec_propose",
    "serving.spec_verify": "serving.spec_verify",
    "serving.spec_block": "serving.spec_block",
    "serving.spec_draft_prefill": "serving.spec_draft_prefill",
    "serving.spec_draft_admit": "serving.spec_draft_admit",
    "hybrid.rollout_generate": "hybrid.rollout",
}
# host-side orchestrators / sub-programs of a locked contract: no single
# stable jitted program of their own.  A NEW @hot_path lands in neither
# set and fails test_lockfile_covers_registered_hot_paths until its
# contract exists (or it is consciously exempted here).
_ORCHESTRATORS = {
    "runtime.train_batch",      # host loop around runtime.train_step
    "runtime.step",             # 3-call path orchestrator
    "runtime.forward",          # 3-call path orchestrator
    "runtime.fwd_bwd",          # sub-program of the fused/3-call step
    "runtime.fwd_bwd_acc",      # gas>1 variant of fwd_bwd
    "inference.generate",       # host wrapper around inference.decode
    "hybrid.rollout_cast",      # once-per-optimizer-step view builder
    # the HTTP front end's scheduler-owner loop drives the engine's
    # locked serving programs and must never mint one of its own — the
    # e2e zero-new-executables test (test_serving_frontend.py) proves it
    "serving.http_frontend_loop",
}


def _registered_hot_path_names():
    """Static sweep: every ``@hot_path("name")`` in the package source."""
    names = set()
    pkg = REPO / "deepspeed_tpu"
    for path in pkg.rglob("*.py"):
        for m in re.finditer(r'@hot_path\(\s*"([^"]+)"', path.read_text()):
            names.add(m.group(1))
    return names


@pytest.fixture(scope="module")
def lock():
    assert LOCK.exists(), \
        f"{LOCK} missing — generate with bin/ds_lint --contracts --update"
    return json.loads(LOCK.read_text())


def test_lockfile_covers_registered_hot_paths(lock):
    """Every @hot_path in the package is either contract-locked or a
    documented host orchestrator — a new hot path must add its contract
    (ds_lint --contracts --update) or a conscious exemption above."""
    registered = _registered_hot_path_names()
    registered.discard("name")           # the docstring example in hotpath.py
    unknown = registered - set(_COVERED) - _ORCHESTRATORS
    assert not unknown, \
        f"@hot_path entry point(s) with no contract in {LOCK.name}: " \
        f"{sorted(unknown)}"
    programs = lock["programs"]
    missing = {v for v in _COVERED.values()} - set(programs)
    assert not missing, f"contracts missing from {LOCK.name}: {missing}"
    # the slot engine's programs are explicitly part of the acceptance bar
    for name in ("serving.decode_step", "serving.prefill_chunk",
                 "serving.admit"):
        assert name in programs


def test_lockfile_programs_have_sound_contracts(lock):
    """Locked invariants that must hold regardless of drift: no host
    callbacks anywhere, and donated programs actually alias."""
    for name, c in lock["programs"].items():
        assert c["host_callbacks"] == 0, name
        if c["donation"]["declared"]:
            floor = c["donation"]["min_aliased"] or 1
            assert c["donation"]["aliased"] >= floor, (name, c["donation"])


@pytest.mark.parametrize("builder_name", contract.program_names())
def test_program_contract_matches_lockfile(lock, builder_name):
    """The gate: regenerate this program's contract and diff it against
    the committed lockfile — any mismatch fails with the per-program
    field diff."""
    name, fresh = contract.build_program_contract(builder_name)
    locked = lock["programs"].get(name)
    assert locked is not None, \
        f"{name} not in {LOCK.name} — run ds_lint --contracts --update"
    diff = contract.diff_program(name, locked, fresh)
    assert not diff, "contract break (regenerate-and-diff):\n" + \
        "\n".join(diff)


@pytest.mark.parametrize("plan_name",
                         [b.__name__ for b in __import__(
                             "deepspeed_tpu.parallel.plans",
                             fromlist=["PLAN_BUILDERS"]).PLAN_BUILDERS])
def test_collective_schedule_matches_lockfile(lock, plan_name):
    """The static collective-schedule gate: the sharding plan's compiled
    HLO must carry exactly the locked collective counts (and satisfy the
    plan's semantic invariants) — MULTICHIP dry-run totals are locked,
    not re-measured."""
    name, fresh = contract.build_plan_contract(plan_name)
    problems = contract.validate_plan_contract(fresh)
    assert not problems, f"{name}: {problems}"
    locked = lock["collective_schedules"].get(name)
    assert locked is not None, \
        f"{name} not in {LOCK.name} — run ds_lint --contracts --update"
    diff = contract.diff_program(name, locked, fresh)
    assert not diff, "collective-schedule break:\n" + "\n".join(diff)


# ------------------------------------------------------------------ #
# The gate actually fails, readably, on synthetic contract breaks
# ------------------------------------------------------------------ #
def _synthetic_donating_ep(donate=True):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.tools.lint.entry_points import EntryPoint

    def update(params, cache):
        return jax.tree.map(lambda c: c + 1.0, cache)

    fn = jax.jit(update, donate_argnums=(1,)) if donate else jax.jit(update)
    args = ({"w": jnp.ones((4, 4))}, {"k": jnp.zeros((2, 8))})
    return EntryPoint("synthetic.update", fn, args, expect_donation=donate)


def test_dropped_donation_fails_with_readable_diff():
    """Acceptance: a synthetic contract break (the exact PR 5 bug class —
    a donation silently dropped) fails the diff with a per-program,
    per-field message."""
    locked = contract.contract_of_entry_point(_synthetic_donating_ep(True))
    fresh = contract.contract_of_entry_point(_synthetic_donating_ep(False))
    assert locked["donation"]["aliased"] >= 1
    assert fresh["donation"]["aliased"] == 0
    diff = contract.diff_program("synthetic.update", locked, fresh)
    text = "\n".join(diff)
    assert diff and diff[0] == "synthetic.update:"
    assert "donation" in text and "LOST donation" in text


def test_surprise_collective_and_primitive_drift_diff():
    """Tampered lockfile entries produce readable field-level diffs."""
    locked = {"kind": "collective_schedule", "mesh": {"tp": 2},
              "collectives": {"all-gather": 35, "all-reduce": 39},
              "expect": ["all-gather"], "reduction": True}
    fresh = dict(locked, collectives={"all-gather": 37, "all-reduce": 39,
                                      "all-to-all": 2})
    diff = contract.diff_program("parallel.fake", locked, fresh)
    text = "\n".join(diff)
    assert "collectives.all-gather: 35 -> 37" in text
    assert "collectives.all-to-all: 0 -> 2" in text

    # plan semantics (expect / reduction) are part of the schedule contract
    weakened = dict(locked, expect=[], reduction=False)
    text = "\n".join(contract.diff_program("parallel.fake", locked, weakened))
    assert "expect: ['all-gather'] -> []" in text
    assert "reduction: True -> False" in text

    p_locked = {"kind": "program", "primitives": {"scan": 1, "add": 3},
                "primitives_sha256": "aaaa", "host_callbacks": 0,
                "collectives": {}, "donation": {"declared": True,
                                                "aliased": 2,
                                                "min_aliased": 0},
                "in_avals": ["f32[2]"], "out_avals": ["f32[2]"]}
    p_fresh = dict(p_locked, primitives={"scan": 1, "add": 3,
                                         "pure_callback": 1},
                   primitives_sha256="bbbb", host_callbacks=1)
    diff = contract.diff_program("inference.fake", p_locked, p_fresh)
    text = "\n".join(diff)
    assert "primitives.pure_callback: 0 -> 1" in text
    assert "host_callbacks: 0 -> 1" in text


def test_diff_lockfiles_reports_added_and_removed():
    a = {"programs": {"x": {"kind": "program"}}, "collective_schedules": {}}
    b = {"programs": {"y": {"kind": "program"}}, "collective_schedules": {}}
    text = "\n".join(contract.diff_lockfiles(a, b))
    assert "x: locked but no longer extracted" in text
    assert "y: not in PROGRAMS.lock" in text


def test_schedule_diff_prints_old_and_new_side_by_side():
    """A schedule change prints BOTH whole schedules, not only field
    paths — a reviewer reads 'what was the schedule, what is it now' in
    two lines (counts + bytes when budgeted)."""
    locked = {"kind": "collective_schedule", "mesh": {"tp": 2}, "world": 8,
              "collectives": {"all-gather": 40, "all-reduce": 70},
              "comm": {"all-gather": {"count": 40,
                                      "bytes_per_step": 2155872},
                       "all-reduce": {"count": 70,
                                      "bytes_per_step": 1048576}},
              "expect": [], "reduction": True}
    fresh = dict(locked,
                 collectives={"all-gather": 42, "all-reduce": 70},
                 comm={"all-gather": {"count": 42,
                                      "bytes_per_step": 70254592},
                       "all-reduce": {"count": 70,
                                      "bytes_per_step": 1048576}})
    diff = contract.diff_program("parallel.fake", locked, fresh)
    text = "\n".join(diff)
    assert "collectives.all-gather: 40 -> 42" in text
    # the byte story is the reviewable half of the regression
    assert "all-gather bytes: 2.1MB -> 67.0MB per step" in text
    side = [ln for ln in diff if "schedule:" in ln or ln.strip()
            .startswith("->")]
    assert len(side) == 2, diff
    assert "all-gather x40 (2.1MB)" in side[0]
    assert "all-gather x42 (67.0MB)" in side[1]


# ------------------------------------------------------------------ #
# Comm budgets + mesh-scaling tables (the byte-level contract layer)
# ------------------------------------------------------------------ #
def test_lockfile_carries_comm_budgets(lock):
    """Every locked program carries a comm budget; today's single-chip
    programs must budget ZERO bytes (a collective appearing in one is a
    contract break, not a surprise), and every sharding-plan schedule
    budgets every counted collective with nonzero bytes and matching
    instance counts."""
    for name, c in lock["programs"].items():
        assert "comm" in c, f"{name}: no comm budget locked"
        assert c["comm"] == {}, \
            f"{name}: single-chip program budgets {c['comm']}"
    for name, c in lock["collective_schedules"].items():
        assert c["world"] == 8, name
        counts = c["collectives"]
        budget = c["comm"]
        assert set(budget) == set(counts), (name, budget, counts)
        for op, n in counts.items():
            assert budget[op]["count"] == n, (name, op)
            assert budget[op]["bytes_per_step"] > 0, (name, op)


def test_lockfile_scaling_tables_are_sound(lock):
    """The locked {1,2,4,8} tables' internal invariants, checked with no
    compiles: all four plans present with all four mesh points, one chip
    moves zero bytes, the top row equals the locked schedule's budget
    (same compile), and every growing collective carries a declared
    reason (the prover's growth gate on the committed artifact)."""
    scaling = lock["mesh_scaling"]
    assert set(scaling) == set(lock["collective_schedules"])
    for name, sc in scaling.items():
        worlds = [row["world"] for row in sc["points"]]
        assert worlds == [1, 2, 4, 8], (name, worlds)
        assert sc["points"][0]["bytes_per_chip_total"] == 0, \
            f"{name}: phantom collective traffic on a mesh of one"
        top = sc["points"][-1]
        sched = lock["collective_schedules"][name]
        assert top["collectives"] == sched["comm"], \
            f"{name}: scaling table top row disagrees with the locked " \
            f"schedule budget"
        assert top["mesh"] == sched["mesh"], name
        problems = comm_contract.validate_scaling_contract(name, sc)
        assert not problems, "\n".join(problems)
        # the growth flags themselves are locked: every flagged op is
        # declared, and nothing is declared "just in case" for ops that
        # never appear in the table
        seen_ops = set()
        for row in sc["points"]:
            seen_ops |= set(row["bytes_per_chip"])
        for op in sc["allowed_growth"]:
            assert op in seen_ops, \
                f"{name}: allowed_growth for {op!r} which never appears"


def test_growth_prover_flags_synthetic_replication():
    """Unit acceptance for the scaling prover: a per-chip trajectory that
    GROWS (the replicated-tensor smell) is flagged with a readable
    transition trail and fails validation unless declared."""
    table = [
        comm_contract.scaling_entry(1, {"tp": 1}, {}),
        comm_contract.scaling_entry(
            2, {"tp": 2},
            {"all-gather": {"count": 4, "bytes_per_step": 4 * 2048}}),
        comm_contract.scaling_entry(
            4, {"tp": 4},
            {"all-gather": {"count": 4, "bytes_per_step": 4 * 16384}}),
    ]
    flags = comm_contract.growth_flags(table)
    assert "all-gather" in flags
    assert "2->4" in flags["all-gather"][0]
    contract_ = {"kind": "mesh_scaling", "points": table,
                 "grows_with_mesh": flags, "allowed_growth": {}}
    problems = comm_contract.validate_scaling_contract("fixture.bad",
                                                       contract_)
    assert problems and "GROWS with mesh size" in problems[0]
    assert "replicated-tensor smell" in problems[0]
    # a declared reason clears it
    contract_["allowed_growth"] = {"all-gather": "weak-scaling batch"}
    assert not comm_contract.validate_scaling_contract("fixture.ok",
                                                       contract_)
    # flat-or-falling trajectories stay clean
    table[2]["bytes_per_chip"]["all-gather"] = 4096
    assert not comm_contract.growth_flags(table)


def test_scaling_diff_renders_bytes_per_chip():
    """A scaling-table drift diffs readably, per mesh point, in bytes."""
    a = {"points": [comm_contract.scaling_entry(
        2, {"tp": 2}, {"all-gather": {"count": 1,
                                      "bytes_per_step": 2 * 1024}})],
        "grows_with_mesh": {}, "allowed_growth": {}}
    b = {"points": [comm_contract.scaling_entry(
        2, {"tp": 2}, {"all-gather": {"count": 1,
                                      "bytes_per_step": 2 * 1048576}})],
        "grows_with_mesh": {}, "allowed_growth": {}}
    diff = comm_contract.diff_scaling("parallel.fake", a, b)
    text = "\n".join(diff)
    assert "mesh 2 all-gather: 1.0KB -> 1.0MB per chip" in text
    # a drift confined to a declared growth REASON renders the actual
    # strings, not two identical key lists
    c = dict(a, allowed_growth={"all-gather": "old reason"})
    d = dict(a, allowed_growth={"all-gather": "new reason"})
    text = "\n".join(comm_contract.diff_scaling("parallel.fake", c, d))
    assert "allowed_growth[all-gather]: 'old reason' -> 'new reason'" \
        in text
    # an instance-count drift whose bytes (and hence the truncated
    # per-chip number) are unchanged still diffs — the locked per-point
    # schedule entries are compared, not only bytes_per_chip
    e = {"points": [comm_contract.scaling_entry(
        2, {"tp": 2}, {"all-gather": {"count": 2,
                                      "bytes_per_step": 2 * 1024}})],
        "grows_with_mesh": {}, "allowed_growth": {}}
    text = "\n".join(comm_contract.diff_scaling("parallel.fake", a, e))
    assert "mesh 2 all-gather schedule: 1x/2.0KB -> 2x/2.0KB" in text


def test_hlo_comm_parser_formats():
    """The HLO parser handles every replica-group/operand format XLA
    emits: explicit and iota groups, tuple-shaped variadic all-to-all,
    async -start (the -done halves never double-count), permute pair
    lists, and group-free instructions spanning the world."""
    txt = """
%ag = f32[4,8]{1,0} all-gather(f32[2,8]{1,0} %c), replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}, metadata={op_name="x{y}"}
%ar = f32[4,16]{1,0} all-reduce-start(f32[4,16]{1,0} %d), replica_groups=[4,2]<=[8], to_apply=%region
%ard = f32[4,16]{1,0} all-reduce-done(f32[4,16]{1,0} %ar)
%a2a = (f32[2,8]{1,0}, f32[2,8]{1,0}) all-to-all(f32[2,8]{1,0} %p, f32[2,8]{1,0} %q), replica_groups={{0,1},{2,3}}
%cp = f32[4,16]{1,0} collective-permute(f32[4,16]{1,0} %e), source_target_pairs={{0,2},{2,4},{4,6},{6,0}}
%bf = bf16[8]{0} all-reduce(bf16[8]{0} %g), replica_groups={}
%pm = pred[4,16]{1,0} all-reduce(pred[4,16]{1,0} %m), replica_groups=[4,2]<=[8]
"""
    comm = comm_contract.parse_hlo_comm(txt, 8)
    assert comm["all-gather"] == {"count": 1, "bytes_per_step": 512}
    # pred is the one digit-free dtype token: 64 bool bytes x 2 x 4
    assert comm["all-reduce"] == {"count": 3,
                                  "bytes_per_step": 2048 + 128 + 512}
    assert comm["all-to-all"] == {"count": 1, "bytes_per_step": 512}
    assert comm["collective-permute"] == {"count": 1,
                                          "bytes_per_step": 1024}


def test_hlo_comm_parser_untyped_operands():
    """``compiled.as_text()`` of the installed jaxlib prints operands by
    NAME only (``all-gather(%c)``): bytes then come from the operands' own
    definitions — same totals as the typed form above, never a silent 0
    (the first 0.9.0 regeneration locked every schedule at 0 bytes)."""
    txt = """
ENTRY %main {
  %c = f32[2,8]{1,0} parameter(0)
  %p.1 = f32[2,8]{1,0} bitcast(%c)
  %ag = f32[4,8]{1,0} all-gather(%c), replica_groups={{0,1},{2,3},{4,5},{6,7}}, dimensions={0}
  %a2a = (f32[2,8]{1,0}, f32[2,8]{1,0}) all-to-all(%c, %p.1), replica_groups={{0,1},{2,3}}
  ROOT %ar = f32[2,8]{1,0} all-reduce(%p.1), replica_groups=[4,2]<=[8], to_apply=%region
}
"""
    comm = comm_contract.parse_hlo_comm(txt, 8)
    assert comm["all-gather"] == {"count": 1, "bytes_per_step": 512}
    assert comm["all-to-all"] == {"count": 1, "bytes_per_step": 512}
    assert comm["all-reduce"] == {"count": 1, "bytes_per_step": 512}


# ------------------------------------------------------------------ #
# Memory/FLOP contracts (PROGRAMS.lock format 3, tools/lint/
# mem_contract.py) — artifact invariants + the synthetic-break proof
# run fast (no hot-path compiles); the per-program regen-and-diff is
# slow-marked (12 compiles) like the mesh-scaling sweep
# ------------------------------------------------------------------ #
def test_lockfile_format3_carries_memory_and_cost(lock):
    """Every locked program AND plan carries a memory_analysis byte
    footprint and a cost_analysis budget, internally consistent (the
    no-compile half of the acceptance bar)."""
    assert lock["_meta"]["format"] >= 3
    for section in ("programs", "collective_schedules"):
        for name, c in lock[section].items():
            mem, cost = c.get("memory"), c.get("cost")
            assert mem and cost, f"{name}: no memory/cost contract"
            for field in mem_contract.MEM_FIELDS + ("total_bytes",):
                assert isinstance(mem.get(field), int), (name, field)
            assert mem["total_bytes"] == (
                mem["argument_size_in_bytes"]
                + mem["output_size_in_bytes"]
                + mem["temp_size_in_bytes"]
                - mem["alias_size_in_bytes"]), name
            assert cost["flops"] > 0, name
            assert cost["bytes_accessed"] > 0, name
            assert not mem_contract.validate_memory_contract(name, c), \
                mem_contract.validate_memory_contract(name, c)
    # donated programs buy real bytes: every program whose donation
    # aliases buffers aliases >0 bytes in the memory contract
    for name, c in lock["programs"].items():
        if c["donation"]["declared"] and c["donation"]["aliased"]:
            assert c["memory"]["alias_size_in_bytes"] > 0, name


def test_memory_diff_tolerance_band():
    """Within-tolerance drift is silent (compiler noise across patch
    releases must not flip the gate); beyond it, the byte story
    renders."""
    base = {"memory": {"argument_size_in_bytes": 1 << 20,
                       "output_size_in_bytes": 1 << 20,
                       "temp_size_in_bytes": 100 * 1024,
                       "alias_size_in_bytes": 1 << 20,
                       "generated_code_size_in_bytes": 0,
                       "total_bytes": (1 << 20) + 100 * 1024},
            "cost": {"flops": 10 ** 9, "bytes_accessed": 10 ** 8}}
    within = json.loads(json.dumps(base))
    within["memory"]["temp_size_in_bytes"] += 1024      # ~1% < 2%
    assert mem_contract.diff_memory("p", base, within) == []
    beyond = json.loads(json.dumps(base))
    beyond["memory"]["temp_size_in_bytes"] = 612 * 1024
    lines = mem_contract.diff_memory("p", base, beyond)
    text = "\n".join(lines)
    assert "temp HBM: 100.0KB -> 612.0KB" in text
    assert "MEMORY GROWTH beyond tolerance" in text
    # cost drift diffs too
    slower = json.loads(json.dumps(base))
    slower["cost"]["flops"] = 2 * 10 ** 9
    assert any("flops" in ln for ln in
               mem_contract.diff_memory("p", base, slower))


def _synthetic_mem_ep(donate=True):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.tools.lint.entry_points import EntryPoint

    def update(params, cache):
        return jax.tree.map(lambda c: c + 1.0, cache)

    fn = jax.jit(update, donate_argnums=(1,)) if donate else jax.jit(update)
    args = ({"w": jnp.ones((4, 4))}, {"k": jnp.zeros((128, 1024))})
    return EntryPoint("synthetic.update", fn, args, expect_donation=donate)


def test_dropped_donation_memory_break_fails_readably():
    """The acceptance synthetic break: dropping a donation makes the
    aliased bytes vanish and the live total jump by the whole donated
    buffer — the diff renders the byte story, and the update-time
    growth ratchet REFUSES the regression unless declared."""
    locked = contract.contract_of_entry_point(_synthetic_mem_ep(True),
                                              with_memory=True)
    fresh = contract.contract_of_entry_point(_synthetic_mem_ep(False),
                                             with_memory=True)
    assert locked["memory"]["alias_size_in_bytes"] > 0
    assert fresh["memory"]["alias_size_in_bytes"] == 0
    assert fresh["memory"]["total_bytes"] \
        > locked["memory"]["total_bytes"]
    diff = contract.diff_program("synthetic.update", locked, fresh)
    text = "\n".join(diff)
    assert diff and diff[0] == "synthetic.update:"
    assert "donated-alias HBM" in text and "live HBM total" in text
    assert "MEMORY GROWTH beyond tolerance" in text
    problems = mem_contract.growth_problems("synthetic.update", locked,
                                            fresh)
    assert problems and "GROWS" in problems[0] \
        and "cannot land silently" in problems[0]
    # a declared reason clears the ratchet (but never the lock diff)
    assert not mem_contract.growth_problems(
        "synthetic.update", locked, fresh,
        declared={"synthetic.update": "intentional double-buffer"})
    # shrinkage diffs (regen to claim the win) but never trips growth
    assert not mem_contract.growth_problems("synthetic.update", fresh,
                                            locked)
    # the FAST gate regenerates without memory: the same locked
    # contract diffs clean against a fresh side with no memory section
    no_mem = contract.contract_of_entry_point(_synthetic_mem_ep(True))
    assert "memory" not in no_mem
    assert contract.diff_program("synthetic.update", locked, no_mem) \
        == []


def test_mem_gate_unknown_name_fails_not_green():
    """A misspelled program name must NEVER exit 0 having checked
    nothing — the filtered sweep reports unknown names as a failure
    (and, thanks to the static builder->program map, without paying a
    single engine build, which is what keeps this test fast)."""
    ok, lines = mem_contract.check_memory_against_lockfile(
        names={"serving.decode_stpe"})
    assert not ok
    text = "\n".join(lines)
    assert "unknown program name" in text
    assert "serving.decode_stpe" in text
    assert "serving.decode_step" in text          # the known list helps


def test_builder_program_map_is_complete():
    """Every registered builder appears in the static map (the
    cross-check against what each builder actually constructs runs in
    the slow regen test and in every --mem sweep)."""
    from deepspeed_tpu.tools.lint import entry_points
    assert set(entry_points.BUILDER_PROGRAMS) \
        == {b.__name__ for b in entry_points.BUILDERS}


@pytest.mark.slow
@pytest.mark.parametrize("builder_name", contract.program_names())
def test_program_memory_contract_matches_lockfile(lock, builder_name):
    """The full memory regen-and-diff of one program: compile it and
    hold its byte footprint + cost budget against the committed lock
    within tolerance.  ``slow``: one compile per program (the PR 14
    budget discipline — tier-1's wall clock cannot absorb 12 compiles);
    run via ``ds_lint --mem`` or ``-m slow``."""
    name, fresh = contract.build_program_contract(builder_name,
                                                  with_memory=True)
    from deepspeed_tpu.tools.lint import entry_points
    assert entry_points.BUILDER_PROGRAMS[builder_name] == name, \
        "builder->program map drifted — name-filtered --mem sweeps " \
        "would skip the wrong program"
    locked = lock["programs"].get(name)
    assert locked is not None, name
    diff = mem_contract.diff_memory(name, locked, fresh)
    assert not diff, f"memory-contract break for {name}:\n" + \
        "\n".join(diff)
    assert not mem_contract.growth_problems(name, locked, fresh)


@pytest.mark.slow
def test_ds_lint_mem_cli_exits_1_on_memory_break(tmp_path):
    """Acceptance: ``ds_lint --mem`` exits 1 from the CLI on a memory
    break, with the byte story on stdout.  A tampered lockfile (the
    locked temp bytes shrunk 8x, so the real program reads as an 8x
    regression) drives the real subprocess gate on one program."""
    tampered = json.loads(LOCK.read_text())
    m = tampered["programs"]["serving.decode_step"]["memory"]
    m["temp_size_in_bytes"] //= 8
    m["total_bytes"] = (m["argument_size_in_bytes"]
                        + m["output_size_in_bytes"]
                        + m["temp_size_in_bytes"]
                        - m["alias_size_in_bytes"])
    bad = tmp_path / "PROGRAMS.tampered.lock"
    bad.write_text(json.dumps(tampered))
    env = dict(os.environ, DSTPU_MEM_LOCKFILE=str(bad),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.tools.lint", "--mem",
         "serving.decode_step"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
        timeout=900)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "MEMORY-CONTRACT BREAK" in proc.stdout
    assert "temp HBM" in proc.stdout
    assert "GROWS" in proc.stdout
    # and the untampered lock answers 0 for the same program
    env.pop("DSTPU_MEM_LOCKFILE")
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.tools.lint", "--mem",
         "serving.decode_step"],
        capture_output=True, text=True, env=env, cwd=str(REPO),
        timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("plan_name",
                         [b.__name__ for b in __import__(
                             "deepspeed_tpu.parallel.plans",
                             fromlist=["PLAN_BUILDERS"]).PLAN_BUILDERS])
def test_mesh_scaling_matches_lockfile(lock, plan_name):
    """The full regen-and-diff of one plan's scaling table: compile the
    scaled-down mesh points {1,2,4} (the 8-point is derived from the
    locked schedule, whose own fresh compile is proven by
    test_collective_schedule_matches_lockfile), then validate growth and
    diff per chip.  ``slow``: three engine compiles per plan; run via
    ``ds_lint --comm`` or ``-m slow``."""
    sched_name = f"parallel.{plan_name}"
    name, fresh = contract.build_plan_scaling_contract(
        plan_name, full_contract=lock["collective_schedules"][sched_name])
    problems = comm_contract.validate_scaling_contract(name, fresh)
    assert not problems, "\n".join(problems)
    locked = lock["mesh_scaling"].get(name)
    assert locked is not None, \
        f"{name} not in {LOCK.name} — run ds_lint --contracts --update"
    diff = comm_contract.diff_scaling(name, locked, fresh)
    assert not diff, "mesh-scaling break:\n" + "\n".join(diff)
