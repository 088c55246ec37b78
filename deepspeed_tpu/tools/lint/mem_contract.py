"""Memory/FLOP program contracts: HBM footprints and compute budgets in
``PROGRAMS.lock`` (format 3) + the ``ds_lint --mem`` gate.

PR 7 locked what every hot-path program *is* (primitive multisets,
donations, collective schedules); PR 14 locked what it *moves*
(byte-level comm budgets).  This module locks what it *costs* the
device: for every hot-path program and sharding plan,

* ``compiled.memory_analysis()`` — argument / output / temp / alias /
  generated-code bytes, plus the derived ``total_bytes`` = arg + out +
  temp − alias (the live working set).  Exact on TPU, stable on the
  tier-1 CPU backend the contracts are defined under; and
* ``compiled.cost_analysis()`` — flops and bytes-accessed, the roofline
  numerators (``autotuning.cost_model`` is the shared extraction — the
  flops profiler reads the same code).

The regression story the comm layer taught, applied to the resource
that actually produced the BENCH_r04 cliff (decode collapsing 8,673 →
1,193 tok/s/chip with HBM util falling to 0.075): a memory regression
must fail as a readable byte story — ``decode_step temp HBM: 96.0MB ->
612.0MB`` — at lock-diff time, not as an OOM or a bandwidth collapse
three rounds later.  A dropped donation is the canonical break: the
alias bytes vanish and the live total jumps by the whole donated
buffer (the synthetic-break proof in
``tests/unit/test_program_contracts.py``).

**Growth gate**: ``ds_lint --contracts --update`` REFUSES to rewrite a
program's memory contract when any byte field grew beyond
``MEM_TOLERANCE`` over the committed lock, unless the program is
declared in :data:`DECLARED_GROWTH` with a reviewable reason (the
declaration is stamped into the lock as ``memory_growth_declared``, so
the artifact diff carries the why).  Memory bloat cannot land
silently: either the program shrinks back, or the growth is declared
in a committed source file a reviewer reads.

Costs are exact compiler outputs under the tier-1 harness (CPU, 8
virtual devices) — deterministic and diffable; the tolerance band only
absorbs jax/jaxlib patch-level layout jitter.  Compiles are the
expensive half: the fast tier-1 gate diffs program contracts WITHOUT
memory (no compile — the comm probe discipline), plan contracts carry
memory for free (their schedule compile already exists), and the full
per-program memory regen-and-diff runs as the ``slow``-marked half of
``test_program_contracts.py`` and as ``ds_lint --mem`` from the CLI.
"""

import os
from contextlib import contextmanager

# ``compiled.memory_analysis()`` fields locked per program, in story
# order (the host_* twins are all zero on the device backends we lock).
MEM_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
COST_FIELDS = ("flops", "bytes_accessed")

# display names for the byte stories
_STORY = {
    "argument_size_in_bytes": "argument HBM",
    "output_size_in_bytes": "output HBM",
    "temp_size_in_bytes": "temp HBM",
    "alias_size_in_bytes": "donated-alias HBM",
    "generated_code_size_in_bytes": "generated code",
    "total_bytes": "live HBM total",
    "flops": "flops",
    "bytes_accessed": "bytes accessed",
}

# Relative drift below this is compiler noise (padding, fusion-boundary
# layout churn across jax patch releases), not a regression; the
# absolute floor keeps the tiniest programs (the locked entry points
# run at toy shapes — some footprints are a few hundred bytes) from
# tripping on sub-KB scratch shifts.
MEM_TOLERANCE = 0.02
MEM_ABS_FLOOR = 1024

# Programs whose memory is ALLOWED to grow beyond tolerance at the next
# ``--contracts --update``, each with a reviewable reason.  An entry
# here is the only way memory growth lands: the update gate refuses to
# rewrite an undeclared grower.  Entries are meant to be TRANSIENT —
# once the grown contract is locked (the declaration is stamped into
# the lock as ``memory_growth_declared``), the next PR removes the
# entry and the ratchet re-arms.
# This round: the lock's memory baseline was taken under jaxlib 0.4.37;
# the installed 0.9.0 XLA:CPU assigns buffers differently.  No program
# changed — the PARENT tree shows the identical growth under the
# installed JAX (e.g. serving.decode_step temp 58.3KB -> 108.7KB,
# runtime.apply_update 3.3KB -> 4.4KB at both commits).
_JAXLIB_0_9 = ("compiler, not program: XLA:CPU buffer assignment of jaxlib "
               "0.9.0 vs the 0.4.37 baseline; identical growth on the "
               "parent tree under the installed JAX")
DECLARED_GROWTH = {name: _JAXLIB_0_9 for name in (
    "hybrid.rollout", "inference.decode", "inference.prefill_chunk",
    "runtime.apply_update", "serving.decode_step",
    "serving.spec_draft_prefill",
    "serving.spec_propose", "serving.spec_verify", "parallel.moe_ep")}
DECLARED_GROWTH["serving.prefill_chunk"] = (
    "the locked entry point is a dispatch of FOUR chunk rows since PR 38 "
    "(one row before): 4x the activations for 4x the tokens, one pass of "
    "the weights — docs/serving.md 'Prefill dispatches'")
DECLARED_GROWTH["serving.spec_block"] = (
    "the lane kernels walk a call's (live lane, block) pairs as one "
    "pipeline since PR 48: the CPU interpreter lowers the look-ahead over "
    "the table, the hand-over's branches and the zeroed tail as XLA conds "
    "and loops with temporaries of their own (+27 KB at the toy size); on "
    "the chip they are scalar code of a Mosaic kernel whose scratch grew "
    "by one SMEM word — tests/unit/test_tpu_compile.py holds the programs' "
    "memory at the cells' sizes")


# ------------------------------------------------------------------ #
# Extraction
# ------------------------------------------------------------------ #
@contextmanager
def fresh_compile_env():
    """Force a REAL compile: an executable reloaded from jax's
    persistent compilation cache reports a DEGENERATE
    ``memory_analysis()`` (the donated-alias bytes read 0 and the live
    total inflates by the whole aliased buffer — the serialized
    artifact drops the alias table), so a memory contract extracted
    from a warm cache hit would read every donation as dropped.  Every
    memory-bearing compile (contract extraction, the bench
    memory_snapshot phase) runs under this guard; the test harness and
    bench both enable the persistent cache globally.  (The same
    serialization boundary is the prime suspect in the PR 5
    reloaded-executable corruption — ROADMAP item 4.)"""
    import jax

    def _reset():
        # jax memoizes "is the cache used" per process at first compile
        # (compilation_cache._cache_checked), so flipping the config
        # flag alone is a no-op once anything compiled — reset_cache()
        # drops the memo (and the in-memory handle; the disk cache
        # itself is untouched and re-attaches on next use)
        try:
            from jax._src import compilation_cache
            compilation_cache.reset_cache()
        except Exception:
            pass

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    _reset()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        _reset()


def memory_cost_of(compiled):
    """``{"memory": {...}, "cost": {...}}`` of one compiled program —
    exact compiler-reported bytes and flops (``autotuning.cost_model``
    is the shared extraction).  Raises when the backend exposes no
    memory analysis: a contract locked from a backend that cannot
    answer would silently lock zeros."""
    from deepspeed_tpu.autotuning import cost_model
    mem = cost_model.xla_memory_analysis(compiled)
    if mem is None:
        raise RuntimeError(
            "compiled.memory_analysis() unavailable on this backend — "
            "memory contracts are defined under the tier-1 harness "
            "(CPU backend) or on a real TPU")
    costs = cost_model.compiled_costs(compiled)
    if costs["flops"] <= 0 and costs["bytes_accessed"] <= 0:
        # compiled_costs is deliberately lenient for the profiler; a
        # CONTRACT locked from a backend whose cost_analysis answers
        # nothing would silently lock zeros and hide every later
        # flops/bytes regression — fail like the memory branch does
        raise RuntimeError(
            "compiled.cost_analysis() reported no flops and no bytes "
            "accessed — cost contracts need a backend with a working "
            "cost analysis (the tier-1 CPU harness or a real TPU)")
    return {
        "memory": {k: int(mem.get(k, 0)) for k in
                   MEM_FIELDS + ("total_bytes",)},
        "cost": {"flops": int(costs["flops"]),
                 "bytes_accessed": int(costs["bytes_accessed"])},
    }


def filtered_builders(names=None):
    """The registered entry-point builders surviving a program-name
    filter, as ``[(builder, mapped_program_name)]`` — the
    skip-BEFORE-build rule both the ``--mem`` gate and the bench
    ``memory_snapshot`` phase share (a filtered single-program sweep
    must not pay 15 discarded engine builds).  A builder missing from
    the static map is never skipped: better one redundant build than a
    silently unchecked program.  Callers MUST cross-check the built
    ``ep.name`` with :func:`map_drift_problem`."""
    from deepspeed_tpu.tools.lint import entry_points
    out = []
    for build in entry_points.BUILDERS:
        mapped = entry_points.BUILDER_PROGRAMS.get(build.__name__)
        if names and mapped is not None and mapped not in names:
            continue
        out.append((build, mapped))
    return out


def map_drift_problem(builder_name, mapped, actual):
    """The shared cross-check keeping ``BUILDER_PROGRAMS`` honest:
    a message when the map disagrees with what the builder actually
    constructed, else ``None``."""
    if mapped == actual:
        return None
    return (f"entry_points.BUILDER_PROGRAMS[{builder_name!r}] = "
            f"{mapped!r} but the builder constructs {actual!r} — fix "
            f"the map (name-filtered sweeps would skip the wrong "
            f"program)")


def memory_contract_of_entry_point(ep):
    """Memory/FLOP contract of one ``entry_points.EntryPoint`` — pays
    one REAL compile (the expensive half; the fast contract gate skips
    it, the slow gate and ``ds_lint --mem`` pay it)."""
    with fresh_compile_env():
        return memory_cost_of(ep.fn.lower(*ep.args).compile())


def attach_memory_contract(contract, name, compiled):
    """Stamp the memory/cost blocks (and any declared-growth reason)
    onto a program/plan contract dict, in place."""
    contract.update(memory_cost_of(compiled))
    reason = DECLARED_GROWTH.get(name)
    if reason:
        contract["memory_growth_declared"] = str(reason)
    return contract


# ------------------------------------------------------------------ #
# Tolerance-banded diff + byte stories
# ------------------------------------------------------------------ #
def _beyond_tolerance(old, new):
    if old == new:
        return False
    return abs(new - old) > max(MEM_ABS_FLOOR,
                                MEM_TOLERANCE * max(abs(old), 1))


def _fmt(field, n):
    from deepspeed_tpu.tools.lint.comm_contract import fmt_bytes
    if field == "flops":
        return f"{n:,}"
    return fmt_bytes(n)


def _pct(old, new):
    if not old:
        return ""
    return f" ({'+' if new >= old else ''}{100.0 * (new - old) / old:.0f}%)"


def diff_memory(name, locked, fresh):
    """Readable memory/cost diff lines for one program (``name`` is
    prepended by the caller's contract diff).  Empty = within
    tolerance.  Each beyond-tolerance field renders as a byte story —
    ``temp HBM: 96.0MB -> 612.0MB (+537%)`` — with growth flagged as
    the regression it is; a vanished donated-alias is called out as
    the dropped-donation signature."""
    out = []
    for section, fields in (("memory", MEM_FIELDS + ("total_bytes",)),
                            ("cost", COST_FIELDS)):
        lo = locked.get(section) or {}
        fr = fresh.get(section) or {}
        if not lo and not fr:
            continue
        for field in fields:
            a, b = int(lo.get(field, 0)), int(fr.get(field, 0))
            if not _beyond_tolerance(a, b):
                continue
            story = _STORY.get(field, field)
            line = f"  {story}: {_fmt(field, a)} -> {_fmt(field, b)}" \
                   f"{_pct(a, b)}"
            if field == "alias_size_in_bytes" and b < a:
                line += (" (donation lost or shrunk: bytes that aliased "
                         "in place now live twice)")
            elif field in ("temp_size_in_bytes", "total_bytes") and b > a:
                line += " (MEMORY GROWTH beyond tolerance)"
            out.append(line)
    lo_decl = locked.get("memory_growth_declared")
    fr_decl = fresh.get("memory_growth_declared")
    if fr_decl is not None and lo_decl != fr_decl:
        # one-directional on purpose: a NEW or CHANGED declaration must
        # lock (it documents a growth the reviewer should see), but
        # REMOVING a DECLARED_GROWTH entry after its grown contract
        # landed — the documented ratchet re-arm — must not turn the
        # gate red with zero byte change; the stale stamp simply drops
        # out of the lock at the next regen
        out.append(f"  memory_growth_declared: {lo_decl!r} -> "
                   f"{fr_decl!r}")
    return out


def growth_problems(name, locked, fresh, declared=None):
    """The update-time ratchet: byte fields that GREW beyond tolerance
    over the committed contract, for an undeclared program.  Returns
    problem strings (empty = clean or declared)."""
    declared = DECLARED_GROWTH if declared is None else declared
    lo = (locked or {}).get("memory") or {}
    fr = (fresh or {}).get("memory") or {}
    if not lo or not fr:
        return []                 # no committed baseline to ratchet on
    problems = []
    # alias growth is excluded: MORE aliased bytes is the donation WIN
    # (an alias drop shows up as total_bytes growth anyway)
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "total_bytes"):
        a, b = int(lo.get(field, 0)), int(fr.get(field, 0))
        if b > a and _beyond_tolerance(a, b):
            story = _STORY.get(field, field)
            if name in declared:
                continue
            problems.append(
                f"{name}: {story} GROWS {_fmt(field, a)} -> "
                f"{_fmt(field, b)}{_pct(a, b)} beyond the "
                f"{MEM_TOLERANCE:.0%} tolerance — memory bloat cannot "
                f"land silently; shrink the program or declare the "
                f"growth in mem_contract.DECLARED_GROWTH with a reason")
    return problems


def validate_memory_contract(name, contract):
    """Invariants of one LOCKED memory contract, checked with no
    compile: blocks present, totals consistent, a declared-donating
    program actually aliases bytes."""
    problems = []
    mem = contract.get("memory")
    cost = contract.get("cost")
    if not mem or not cost:
        return [f"{name}: no memory/cost contract locked — run "
                f"ds_lint --contracts --update"]
    total = (mem.get("argument_size_in_bytes", 0)
             + mem.get("output_size_in_bytes", 0)
             + mem.get("temp_size_in_bytes", 0)
             - mem.get("alias_size_in_bytes", 0))
    if mem.get("total_bytes") != total:
        problems.append(
            f"{name}: total_bytes {mem.get('total_bytes')} != "
            f"arg + out + temp - alias = {total}")
    if mem.get("alias_size_in_bytes", 0) \
            > mem.get("argument_size_in_bytes", 0):
        problems.append(
            f"{name}: donated-alias bytes exceed argument bytes "
            f"({mem.get('alias_size_in_bytes')} > "
            f"{mem.get('argument_size_in_bytes')})")
    don = contract.get("donation", {})
    if don.get("declared") and don.get("aliased", 0) \
            and not mem.get("alias_size_in_bytes", 0):
        problems.append(
            f"{name}: donation aliases {don.get('aliased')} buffer(s) "
            f"but the memory contract aliases 0 bytes — the donation "
            f"is declared yet buys no memory")
    if cost.get("flops", 0) <= 0 or cost.get("bytes_accessed", 0) <= 0:
        # a zero-flop hot-path program is a cost analysis that answered
        # nothing, not a real budget — it would hide every regression
        problems.append(f"{name}: degenerate cost budget {cost}")
    return problems


# ------------------------------------------------------------------ #
# The ``ds_lint --mem`` gate
# ------------------------------------------------------------------ #
def check_memory_against_lockfile(names=None, progress=None,
                                  lock_path=None):
    """(ok, lines).  Recompile the hot-path programs (``names`` limits
    the sweep — the CLI accepts program names so a single-program proof
    doesn't pay 16 engine builds) and the sharding plans, extract fresh
    memory/cost contracts, and diff them against the committed lock's
    format-3 sections with the tolerance band.  Every line is a byte
    story."""
    from deepspeed_tpu.tools.lint import contract as contract_mod
    try:
        locked = contract_mod.load_lockfile(lock_path)
    except FileNotFoundError:
        return False, [f"{contract_mod.LOCKFILE_NAME} missing — generate "
                       f"with ds_lint --contracts --update"]
    ok, lines = True, []
    meta = locked.get("_meta", {})
    if int(meta.get("format", 0)) < 3:
        return False, [
            f"{contract_mod.LOCKFILE_NAME} is format "
            f"{meta.get('format')} (< 3): no memory contracts locked — "
            f"regenerate with ds_lint --contracts --update"]

    def _check(name, fresh):
        nonlocal ok
        sec = "programs" if name in locked.get("programs", {}) \
            else "collective_schedules"
        lock_c = locked.get(sec, {}).get(name)
        if lock_c is None:
            ok = False
            lines.append(f"{name}: not in {contract_mod.LOCKFILE_NAME} — "
                         f"run ds_lint --contracts --update")
            return
        diff = diff_memory(name, lock_c, fresh)
        if diff:
            ok = False
            lines.append(f"{name}:")
            lines.extend(diff)
        for p in growth_problems(name, lock_c, fresh):
            ok = False
            lines.append(p)

    from deepspeed_tpu.parallel import plans
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.tools.lint import entry_points
    matched = set()
    for build, mapped in filtered_builders(names):
        reset_topology()
        try:
            ep = build()
        finally:
            reset_topology()
        drift = map_drift_problem(build.__name__, mapped, ep.name)
        if drift:
            ok = False
            lines.append(drift)
        if names and ep.name not in names:
            continue
        matched.add(ep.name)
        if progress:
            progress(f"compiling {ep.name}")
        fresh = memory_contract_of_entry_point(ep)
        reason = DECLARED_GROWTH.get(ep.name)
        if reason:
            fresh["memory_growth_declared"] = str(reason)
        _check(ep.name, fresh)
    for build in plans.PLAN_BUILDERS:
        # plans are named "parallel.<builder>" by convention (the
        # contract tests key on it); cross-checked after the build
        guess = f"parallel.{build.__name__}"
        if names and guess not in names:
            continue
        if progress:
            progress(f"compiling plan {build.__name__}")
        pname, c = contract_mod.build_plan_contract(build.__name__)
        if pname != guess:
            ok = False
            lines.append(
                f"plan {build.__name__} constructs {pname!r}, not the "
                f"conventional {guess!r} — name-filtered sweeps would "
                f"miss it")
        matched.add(pname)
        matched.add(guess)
        _check(pname, c)
    if names:
        unknown = set(names) - matched
        if unknown:
            # a misspelled name must NEVER exit 0 having checked nothing
            ok = False
            known = sorted(entry_points.BUILDER_PROGRAMS.values()) + \
                sorted(locked.get("collective_schedules", {}))
            lines.append(
                f"unknown program name(s) {sorted(unknown)} — nothing "
                f"was checked for them; known: {known}")
    # locked-artifact invariants ride along for free
    for sec in ("programs", "collective_schedules"):
        for name, c in sorted(locked.get(sec, {}).items()):
            if names and name not in names:
                continue
            for p in validate_memory_contract(name, c):
                ok = False
                lines.append(p)
    return ok, lines


def main(names=None):
    """``ds_lint --mem [program ...]``: regenerate the memory/FLOP
    contracts under the forced tier-1 env and diff against
    ``PROGRAMS.lock``.  Exit 1 on any beyond-tolerance drift,
    undeclared growth, or missing/invalid contract."""
    lock_path = os.environ.get("DSTPU_MEM_LOCKFILE") or None
    progress = lambda msg: print(f"[mem] {msg}", flush=True)
    ok, lines = check_memory_against_lockfile(
        names=set(names) if names else None, progress=progress,
        lock_path=lock_path)
    if ok:
        print("[mem] OK — every memory/FLOP contract holds (HBM "
              "footprints and cost budgets within tolerance)")
        return 0
    print("[mem] MEMORY-CONTRACT BREAK:")
    for line in lines:
        print(f"  {line}")
    print("[mem] intentional? declare growth in "
          "mem_contract.DECLARED_GROWTH, regenerate with ds_lint "
          "--contracts --update, and review the byte stories like any "
          "lockfile bump")
    return 1


if __name__ == "__main__":
    import sys
    from deepspeed_tpu.tools.lint import contract as _c
    _c.ensure_harness_env()
    sys.exit(main(sys.argv[1:] or None))
