"""Program-contract extraction and the ``PROGRAMS.lock`` lockfile.

Where the AST rules see source and ``jaxpr_check`` sees pass/fail, this
module extracts a MACHINE-CHECKABLE CONTRACT from what the compiler is
actually handed for every registered hot-path entry point, and locks it in
a committed artifact:

* **primitive multiset** (and its sha256) of the traced jaxpr — a new host
  callback, a surprise sort, a dropped fused scatter all change it;
* **donation-alias count** from the lowered module (``tf.aliasing_output``
  / ``jax.buffer_donor``) — a lost donation shows up as a smaller count,
  not as an HBM cliff three rounds later;
* **collective-op counts** — jaxpr-level (psum / all_gather /
  reduce_scatter / ppermute / all_to_all) for the single-chip programs,
  optimized-HLO-level for the ``parallel/`` sharding plans (pp / tp / edp /
  MiCS via :mod:`deepspeed_tpu.parallel.plans`), so the MULTICHIP dry-run's
  re-measured totals become a statically locked schedule;
* **input/output abstract signatures** — a shape or dtype drift in a
  donated workspace is a contract break, not a runtime surprise;
* **communication-cost budgets** (:mod:`.comm_contract`) — per-collective
  byte volumes per step parsed from the optimized HLO, and a
  ``mesh_scaling`` section locking bytes-per-chip for every sharding plan
  at mesh sizes {1, 2, 4, 8}: "all-gather bytes: 2.1MB -> 67MB" is a
  reviewable regression where a bare count change is not, and a per-chip
  volume that GROWS with mesh size is the replicated-tensor smell the
  ``ds_lint --comm`` prover fails on;
* **memory/FLOP budgets** (:mod:`.mem_contract`, format 3) —
  ``compiled.memory_analysis()`` byte footprints (argument / output /
  temp / alias / live total) and ``cost_analysis()`` flops +
  bytes-accessed for every program and plan: "decode_step temp HBM:
  96MB -> 612MB" fails at lock-diff time instead of surfacing as an OOM
  or an HBM-utilization cliff rounds later, and ``--update`` refuses
  undeclared growth (the ``ds_lint --mem`` gate).  Memory needs a
  compile, so the FAST gate diffs program contracts without it (plans
  carry memory for free on their schedule compile); the per-program
  memory regen is the ``slow``-marked half of the contract tests.

``PROGRAMS.lock`` (repo root, committed) is regenerated-and-diffed by a
tier-1 gate and by ``ds_lint --contracts`` (``--update`` rewrites it); a
contract break fails with a readable per-program diff.

The contracts are defined UNDER THE TIER-1 HARNESS: ``JAX_PLATFORMS=cpu``
with 8 virtual devices (the CLI forces the same environment).  A jax
upgrade may legitimately shift primitive multisets — regenerate with
``--update`` and review the diff like any other lockfile bump.
"""

import hashlib
import json
import os
import re
from typing import List

_ALIAS_ATTRS = ("tf.aliasing_output", "jax.buffer_donor")

# jaxpr-level collective primitives (single-program contracts)
JAXPR_COLLECTIVES = ("psum", "all_gather", "reduce_scatter", "ppermute",
                     "all_to_all", "pmax", "pmin", "pbroadcast")
# optimized-HLO collective ops (sharding-plan schedules) — the same names
# the MULTICHIP dry-run counts (__graft_entry__._collectives_since)
HLO_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")

LOCKFILE_NAME = "PROGRAMS.lock"


def lockfile_path():
    """``PROGRAMS.lock`` next to the package (the repo root)."""
    import deepspeed_tpu
    pkg = os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))
    return os.path.join(os.path.dirname(pkg), LOCKFILE_NAME)


def ensure_harness_env():
    """Force the tier-1 trace environment (CPU platform, 8 virtual
    devices) — a no-op when the backend is already initialized that way;
    raises when it is initialized differently (contracts extracted on
    another topology would never match the lockfile)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + " --xla_force_host_platform_device_count=8"
    import jax
    if jax.default_backend() != "cpu" or jax.device_count() < 8:
        raise RuntimeError(
            f"contract extraction needs the tier-1 harness (CPU backend, "
            f">= 8 virtual devices); got {jax.default_backend()!r} with "
            f"{jax.device_count()} device(s) — the JAX backend was "
            f"initialized before ensure_harness_env() could force it")


# --------------------------------------------------------------------- #
# Extraction
# --------------------------------------------------------------------- #
def _walk_counts(jaxpr, out):
    # a param holding a ClosedJaxpr exposes ``.jaxpr``; remat2 and
    # pallas_call carry a RAW Jaxpr (``.eqns``, no ``.jaxpr``) — missing
    # that second shape would leave every rematerialized attention body
    # (and the Pallas kernel bodies inside it) out of the multiset
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] = out.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is None and hasattr(v, "eqns"):
                sub = v
            if sub is not None:
                _walk_counts(sub, out)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    sub = getattr(item, "jaxpr", None)
                    if sub is None and hasattr(item, "eqns"):
                        sub = item
                    if sub is not None:
                        _walk_counts(sub, out)
    return out


def primitive_counts_of(fn, *args):
    """Full primitive multiset {name: count} of the traced program."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    return _walk_counts(closed.jaxpr, {}), closed


def _multiset_hash(counts):
    blob = json.dumps(sorted(counts.items()), separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def contract_of_entry_point(ep, with_memory=False):
    """Machine-checkable contract of one :class:`entry_points.EntryPoint`:
    traced primitive multiset + hash, host-callback count, jaxpr-level
    collective counts, lowered donation-alias count, the abstract
    input/output signatures, and the byte-level comm budget (``{}`` for a
    program whose lowering mentions no collective — the single-chip hot
    paths answer without paying for a compile; a mesh-aware program is
    compiled and its optimized HLO costed).

    ``with_memory=True`` additionally compiles the program and locks its
    memory/FLOP budget (:mod:`.mem_contract`) — the expensive half, paid
    by ``--update``/``--mem``/the slow contract test, never by the fast
    tier-1 per-program diff (whose diff skips the memory sections when
    the fresh side omits them)."""
    import jax
    from deepspeed_tpu.tools.lint import comm_contract, mem_contract
    from deepspeed_tpu.tools.lint.jaxpr_check import FORBIDDEN_PRIMITIVES
    counts, closed = primitive_counts_of(ep.fn, *ep.args)
    lowered = ep.fn.lower(*ep.args)
    text = lowered.as_text()
    aliased = sum(text.count(a) for a in _ALIAS_ATTRS)
    comm = {}
    compiled = None
    if with_memory:
        # memory analysis is only trustworthy on a REAL compile — a
        # persistent-cache reload reports degenerate alias bytes
        with mem_contract.fresh_compile_env():
            compiled = lowered.compile()
    elif comm_contract.lowered_has_collectives(text):
        compiled = lowered.compile()
    if comm_contract.lowered_has_collectives(text):
        hlo = compiled.as_text() or ""
        comm = comm_contract.parse_hlo_comm(hlo, jax.device_count())
    c = {
        "kind": "program",
        "primitives": dict(sorted(counts.items())),
        "primitives_sha256": _multiset_hash(counts),
        "host_callbacks": sum(c for p, c in counts.items()
                              if p in FORBIDDEN_PRIMITIVES),
        "collectives": {p: counts[p] for p in JAXPR_COLLECTIVES
                        if p in counts},
        "comm": comm,
        "donation": {"declared": bool(ep.expect_donation),
                     "aliased": aliased,
                     "min_aliased": int(getattr(ep, "min_aliased", 0))},
        "in_avals": [str(a) for a in closed.in_avals],
        "out_avals": [str(a) for a in closed.out_avals],
    }
    if with_memory:
        mem_contract.attach_memory_contract(c, ep.name, compiled)
    return c


def contract_of_plan(plan):
    """Collective-schedule contract of one
    :class:`parallel.plans.PlanProgram`: the counts AND byte volumes of
    every collective op in the OPTIMIZED HLO the plan's fused train step
    compiles to on the 8-device mesh (what the MULTICHIP dry-run measures
    at runtime).  The one compile feeds the count schedule, the comm
    budget AND the memory/FLOP budget — plans pay no extra compile for
    their memory contract.  The compile runs cache-bypassed
    (``fresh_compile_env``): a persistent-cache reload would report the
    plan's donated-alias bytes as 0 and corrupt the locked footprint."""
    from deepspeed_tpu.tools.lint import comm_contract, mem_contract
    with mem_contract.fresh_compile_env():
        compiled = plan.fn.lower(*plan.args).compile()
    text = compiled.as_text() or ""
    counts = {}
    for op in HLO_COLLECTIVES:
        n = len(re.findall(rf"\b{op}(?:-start)?\(", text))
        if n:
            counts[op] = n
    c = {
        "kind": "collective_schedule",
        "mesh": {k: int(v) for k, v in sorted(plan.mesh.items())},
        "world": int(plan.world),
        "collectives": counts,
        "comm": comm_contract.parse_hlo_comm(text, plan.world),
        "expect": sorted(plan.expect),
        "reduction": bool(plan.reduction),
    }
    return mem_contract.attach_memory_contract(c, plan.name, compiled)


def validate_plan_contract(contract):
    """Semantic invariants of a plan schedule (on top of the exact locked
    counts): every expected collective present; reduction plans carry at
    least one all-reduce/reduce-scatter; the comm budget's instance counts
    agree with the count schedule (the two parsers walk the same HLO)."""
    problems = []
    c = contract.get("collectives", {})
    for op in contract.get("expect", []):
        if not c.get(op):
            problems.append(f"expected collective {op!r} absent: {c}")
    if contract.get("reduction") and not (
            c.get("all-reduce", 0) + c.get("reduce-scatter", 0)):
        problems.append(f"no gradient-reduction collective scheduled: {c}")
    comm = contract.get("comm")
    if comm is not None:
        counted = {op: v.get("count", 0) for op, v in comm.items()}
        if counted != c:
            problems.append(
                f"comm-budget instance counts disagree with the count "
                f"schedule: {counted} vs {c}")
    return problems


# --------------------------------------------------------------------- #
# Building the full lockfile
# --------------------------------------------------------------------- #
def program_names():
    from deepspeed_tpu.tools.lint import entry_points
    return [b.__name__ for b in entry_points.BUILDERS]


def build_program_contract(builder_name, with_memory=False):
    """Contract for one entry point, with the global topology reset around
    the engine build (same discipline as the jaxpr-harness tests).
    ``with_memory`` opts into the compile the memory/FLOP budget costs."""
    from deepspeed_tpu.parallel.topology import reset_topology
    from deepspeed_tpu.tools.lint import entry_points
    reset_topology()
    try:
        ep = getattr(entry_points, builder_name)()
        return ep.name, contract_of_entry_point(ep,
                                                with_memory=with_memory)
    finally:
        reset_topology()


def build_plan_contract(plan_builder_name):
    from deepspeed_tpu.parallel import plans
    from deepspeed_tpu.parallel.topology import reset_topology
    reset_topology()
    try:
        plan = getattr(plans, plan_builder_name)()
        return plan.name, contract_of_plan(plan)
    finally:
        reset_topology()


def build_plan_scaling_contract(plan_builder_name, full_contract=None):
    """The mesh-scaling contract of one plan family.  ``full_contract``
    optionally supplies the already-compiled full-mesh (world=8) schedule
    contract so its point is derived instead of re-compiled — the gate and
    ``build_all`` both reuse the canonical compile, which also makes the
    table's top row definitionally consistent with the locked schedule."""
    from deepspeed_tpu.parallel import plans
    from deepspeed_tpu.tools.lint import comm_contract
    builder = getattr(plans, plan_builder_name)
    reuse_rows = {}
    if full_contract is not None:
        reuse_rows[full_contract["world"]] = comm_contract.scaling_entry(
            full_contract["world"], full_contract["mesh"],
            full_contract.get("comm", {}))
    return comm_contract.build_scaling_contract(builder,
                                                reuse_rows=reuse_rows)


def build_all(progress=None, with_memory=True):
    """Regenerate every contract.  Returns the lockfile dict.
    ``with_memory=True`` (the default — ``--update`` and the CLI gates
    want the full format-3 artifact) compiles every program for its
    memory/FLOP budget; the fast tier-1 tests never call this."""
    import jax
    import jaxlib
    from deepspeed_tpu.parallel import plans
    programs, schedules, scaling = {}, {}, {}
    for bname in program_names():
        if progress:
            progress(f"tracing {bname}"
                     + (" (+memory compile)" if with_memory else ""))
        name, c = build_program_contract(bname, with_memory=with_memory)
        programs[name] = c
    for build in plans.PLAN_BUILDERS:
        if progress:
            progress(f"compiling plan {build.__name__}")
        name, c = build_plan_contract(build.__name__)
        schedules[name] = c
        if progress:
            progress(f"scaling {build.__name__} over mesh "
                     f"{plans.MESH_POINTS}")
        sname, sc = build_plan_scaling_contract(build.__name__,
                                                full_contract=c)
        scaling[sname or name] = sc
    return {
        "_meta": {
            "format": 3,
            "harness": "JAX_PLATFORMS=cpu, 8 virtual devices (tier-1)",
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "regenerate": "bin/ds_lint --contracts --update",
        },
        "programs": programs,
        "collective_schedules": schedules,
        "mesh_scaling": scaling,
    }


def load_lockfile(path=None):
    path = path or lockfile_path()
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_lockfile(lock, path=None):
    path = path or lockfile_path()
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(lock, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    return path


# --------------------------------------------------------------------- #
# Readable per-program diffs
# --------------------------------------------------------------------- #
def _diff_counts(label, old, new, out):
    keys = sorted(set(old) | set(new))
    changed = [(k, old.get(k, 0), new.get(k, 0)) for k in keys
               if old.get(k, 0) != new.get(k, 0)]
    for k, o, n in changed:
        out.append(f"  {label}.{k}: {o} -> {n}")
    return bool(changed)


def _diff_comm(locked, fresh, out):
    """Byte-level comm-budget diff lines — the readable half of a comm
    regression: 'all-gather bytes: 2.1MB -> 67MB per step'."""
    from deepspeed_tpu.tools.lint.comm_contract import fmt_bytes
    for op in sorted(set(locked) | set(fresh)):
        lo = locked.get(op, {})
        fr = fresh.get(op, {})
        lb, fb = lo.get("bytes_per_step", 0), fr.get("bytes_per_step", 0)
        if lb != fb:
            out.append(f"  {op} bytes: {fmt_bytes(lb)} -> {fmt_bytes(fb)} "
                       f"per step")
        if lo.get("count", 0) != fr.get("count", 0):
            out.append(f"  comm.{op} instances: {lo.get('count', 0)} -> "
                       f"{fr.get('count', 0)}")


def _diff_mem(locked, fresh, out):
    """Memory/FLOP budget diff (tolerance-banded byte stories) — only
    when the FRESH side carries the sections: the fast tier-1 gate
    regenerates contracts without the memory compile and must not read
    a locked budget as a break (``ds_lint --mem`` and the slow contract
    test regenerate WITH memory and do diff it)."""
    from deepspeed_tpu.tools.lint import mem_contract
    if "memory" not in fresh and "cost" not in fresh:
        return
    out.extend(mem_contract.diff_memory("", locked, fresh))


def _schedule_summary(contract):
    """One-line schedule rendering (counts + bytes when budgeted) for the
    side-by-side view of a changed schedule."""
    from deepspeed_tpu.tools.lint.comm_contract import fmt_bytes
    counts = contract.get("collectives", {})
    comm = contract.get("comm", {})
    parts = []
    for op in sorted(counts):
        b = comm.get(op, {}).get("bytes_per_step")
        parts.append(f"{op} x{counts[op]}"
                     + (f" ({fmt_bytes(b)})" if b is not None else ""))
    return "{" + ", ".join(parts) + "}" if parts else "{none}"


def diff_program(name, locked, fresh):
    """Readable field-by-field diff of one program's contract.  Empty list
    = contracts match."""
    out: List[str] = []
    if locked.get("kind") != fresh.get("kind"):
        out.append(f"  kind: {locked.get('kind')} -> {fresh.get('kind')}")
    if locked.get("kind") == "collective_schedule" or \
            fresh.get("kind") == "collective_schedule":
        changed = _diff_counts("collectives", locked.get("collectives", {}),
                               fresh.get("collectives", {}), out)
        _diff_comm(locked.get("comm", {}) or {},
                   fresh.get("comm", {}) or {}, out)
        _diff_mem(locked, fresh, out)
        for field in ("mesh", "expect", "reduction", "world"):
            if locked.get(field) != fresh.get(field):
                out.append(f"  {field}: {locked.get(field)} -> "
                           f"{fresh.get(field)}")
        if changed:
            # a schedule change is easier to review whole than as field
            # paths: print the old and new schedules side by side
            out.append(f"  schedule: {_schedule_summary(locked)}")
            out.append(f"         -> {_schedule_summary(fresh)}")
        return [f"{name}:"] + out if out else []
    if locked.get("primitives_sha256") != fresh.get("primitives_sha256"):
        _diff_counts("primitives", locked.get("primitives", {}),
                     fresh.get("primitives", {}), out)
        out.append(f"  primitives_sha256: "
                   f"{locked.get('primitives_sha256')} -> "
                   f"{fresh.get('primitives_sha256')}")
    if locked.get("host_callbacks", 0) != fresh.get("host_callbacks", 0):
        out.append(f"  host_callbacks: {locked.get('host_callbacks', 0)} "
                   f"-> {fresh.get('host_callbacks', 0)} (a host callback "
                   f"stalls every dispatch on the host link)")
    _diff_counts("collectives", locked.get("collectives", {}),
                 fresh.get("collectives", {}), out)
    _diff_comm(locked.get("comm", {}) or {}, fresh.get("comm", {}) or {},
               out)
    _diff_mem(locked, fresh, out)
    ld, fd = locked.get("donation", {}), fresh.get("donation", {})
    if ld != fd:
        out.append(f"  donation: declared={ld.get('declared')} "
                   f"aliased={ld.get('aliased')} -> "
                   f"declared={fd.get('declared')} "
                   f"aliased={fd.get('aliased')}"
                   + (" (LOST donation: input and output copies now both "
                      "live)" if fd.get("aliased", 0) < ld.get("aliased", 0)
                      else ""))
    for field in ("in_avals", "out_avals"):
        lo, fr = locked.get(field, []), fresh.get(field, [])
        if lo != fr:
            if len(lo) != len(fr):
                out.append(f"  {field}: {len(lo)} -> {len(fr)} leaves")
            for i, (a, b) in enumerate(zip(lo, fr)):
                if a != b:
                    out.append(f"  {field}[{i}]: {a} -> {b}")
    return [f"{name}:"] + out if out else []


def diff_lockfiles(locked, fresh):
    """Full diff: per-program field diffs plus added/removed programs.
    Empty list = lockfile up to date."""
    from deepspeed_tpu.tools.lint.comm_contract import diff_scaling
    out: List[str] = []
    for section in ("programs", "collective_schedules", "mesh_scaling"):
        lsec = locked.get(section, {})
        fsec = fresh.get(section, {})
        for name in sorted(set(lsec) | set(fsec)):
            if name not in fsec:
                out.append(f"{name}: locked but no longer extracted — "
                           f"remove via --contracts --update")
            elif name not in lsec:
                out.append(f"{name}: not in {LOCKFILE_NAME} — new program; "
                           f"add via --contracts --update")
            elif section == "mesh_scaling":
                out.extend(diff_scaling(name, lsec[name], fsec[name]))
            else:
                out.extend(diff_program(name, lsec[name], fsec[name]))
    return out


def check_against_lockfile(path=None, progress=None):
    """(ok, diff_lines).  Regenerates every contract and diffs against the
    committed lockfile."""
    path = path or lockfile_path()
    if not os.path.exists(path):
        return False, [f"{path} missing — generate with "
                       f"ds_lint --contracts --update"]
    locked = load_lockfile(path)
    fresh = build_all(progress=progress)
    diff = diff_lockfiles(locked, fresh)
    for name, c in sorted(fresh.get("collective_schedules", {}).items()):
        for problem in validate_plan_contract(c):
            diff.append(f"{name}: plan invariant broken — {problem}")
    from deepspeed_tpu.tools.lint import mem_contract
    from deepspeed_tpu.tools.lint.comm_contract import \
        validate_scaling_contract
    for name, c in sorted(fresh.get("mesh_scaling", {}).items()):
        diff.extend(validate_scaling_contract(name, c))
    for section in ("programs", "collective_schedules"):
        for name, c in sorted(fresh.get(section, {}).items()):
            diff.extend(mem_contract.validate_memory_contract(name, c))
    return not diff, diff


def main(update=False):
    ensure_harness_env()
    progress = lambda msg: print(f"[contracts] {msg}", flush=True)
    if update:
        lock = build_all(progress=progress)
        # memory-growth ratchet: an --update that would lock a byte
        # footprint grown beyond tolerance over the COMMITTED artifact
        # is refused unless the program declares the growth with a
        # reason (mem_contract.DECLARED_GROWTH) — memory bloat cannot
        # land through a routine lockfile bump
        from deepspeed_tpu.tools.lint import mem_contract
        try:
            old = load_lockfile()
        except FileNotFoundError:
            old = {}
        problems = []
        for section in ("programs", "collective_schedules"):
            for name, fresh_c in sorted(lock.get(section, {}).items()):
                problems.extend(mem_contract.growth_problems(
                    name, old.get(section, {}).get(name), fresh_c))
        if problems:
            print(f"[contracts] UPDATE REFUSED — memory growth beyond "
                  f"the {mem_contract.MEM_TOLERANCE:.0%} tolerance:")
            for p in problems:
                print(f"  {p}")
            return 1
        path = write_lockfile(lock)
        n = len(lock["programs"]) + len(lock["collective_schedules"])
        print(f"[contracts] wrote {n} contracts to {path}")
        return 0
    ok, diff = check_against_lockfile(progress=progress)
    if ok:
        print(f"[contracts] OK — {LOCKFILE_NAME} matches every extracted "
              f"contract")
        return 0
    print(f"[contracts] CONTRACT BREAK — {LOCKFILE_NAME} does not match "
          f"the extracted contracts:")
    for line in diff:
        print(f"  {line}")
    print("[contracts] intentional? regenerate with "
          "ds_lint --contracts --update and commit the diff")
    return 1


if __name__ == "__main__":
    import sys
    sys.exit(main(update="--update" in sys.argv))
