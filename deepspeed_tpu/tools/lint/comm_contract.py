"""Communication-cost contracts: byte-level comm budgets + the mesh-scaling
prover (``ds_lint --comm``).

PR 7's contract layer locks collective *counts and schedules*; this module
extends it to *bytes moved*.  For every optimized-HLO program it parses the
collective instructions and computes per-collective byte volumes::

    bytes(instance) = sum(operand shape x dtype width)
                      x replica-group size x number of groups

i.e. the total wire volume the instruction moves across the mesh per step
(``collective-permute`` uses its ``source_target_pairs`` count instead of a
group product).  This is a locked COST MODEL, not a cable measurement — its
value is that it is deterministic, diffable, and monotone in the two things
that regress: shard size and group span.  An accidentally replicated
activation shows up as "all-gather bytes: 2.1MB -> 67MB" in a lockfile
diff, which is reviewable; a bare count bump is not.

The **mesh-scaling prover** compiles every ``parallel/plans.py`` plan at
each mesh point in ``plans.MESH_POINTS`` ({1, 2, 4, 8}) and builds a
bytes-per-chip scaling table.  A collective whose per-chip volume GROWS
with mesh size is the classic replicated-tensor smell (a well-sharded
tensor's per-chip traffic stays flat or falls as chips are added); every
growing op must be declared in the plan's ``allowed_growth`` with a
reviewable reason, or the prover fails.  The locked table is the dry-run
scaling evidence ROADMAP item 1 gates its real-chip bench phase on.

Contracts are defined under the tier-1 harness (CPU, 8 virtual devices);
the CLI forces the same environment as ``--contracts``.
"""

import json
import os
import re

# ------------------------------------------------------------------ #
# Optimized-HLO parsing
# ------------------------------------------------------------------ #
_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "s4": 1, "s8": 1, "u2": 1, "u4": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    # fp8 families print as e.g. f8e4m3fn — all one byte wide
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
}

# dtype tokens carry a digit (f32, bf16, s8) except boolean 'pred'
_SHAPE_RE = re.compile(r"\b(pred|[a-z]+[0-9]+[a-z0-9]*)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_EXPLICIT_RE = re.compile(
    r"replica_groups=\{(\{[0-9, ]*\}(?:,\s*\{[0-9, ]*\})*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\s*\d+\},?\s*)+)\}")
# ``%name = <result shape(s)> opcode(`` — an instruction's definition
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*?)\s[a-z][\w\-]*\(")
_NAME_RE = re.compile(r"%[\w.\-]+")

# StableHLO mnemonics in an un-optimized lowering — a cheap "does this
# program communicate at all?" probe that costs no compile
_STABLEHLO_COLLECTIVES = ("stablehlo.all_reduce", "stablehlo.all_gather",
                          "stablehlo.all_to_all", "stablehlo.reduce_scatter",
                          "stablehlo.collective_permute",
                          "stablehlo.collective_broadcast")


def shape_bytes(dtype, dims):
    """Byte size of one typed HLO shape, e.g. ('bf16', '2,64') -> 256."""
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_hlo_comm(hlo_text, world):
    """``{op: {count, bytes_per_step}}`` from optimized HLO text.

    Handles explicit (``{{0,1},{2,3}}``) and iota (``[4,2]<=[8]``) replica
    groups, tuple-shaped variadic operands, async ``-start`` forms (the
    ``-done`` halves are skipped so nothing double-counts), and
    ``collective-permute``'s pair list.  An instruction with no
    ``replica_groups`` spans the whole ``world``.  Operand bytes come
    from the operands' typed shapes where the text prints them
    (``all-gather(f32[8,64] %x)``) and from the operands' own
    definitions where it prints names only (``all-gather(%x)`` — what
    ``compiled.as_text()`` gives since jaxlib 0.5)."""
    out = {}
    lines = hlo_text.splitlines()
    defs = {}
    for line in lines:
        d = _DEF_RE.match(line)
        if d:
            defs[d.group(1)] = sum(shape_bytes(t, s) for t, s in
                                   _SHAPE_RE.findall(d.group(2)))
    for line in lines:
        m = _OP_RE.search(line)
        if m is None or "-done(" in line:
            continue
        op = m.group(1)
        # balanced-paren scan for the operand span (operand shapes are
        # typed in HLO text; metadata braces never enter this span)
        start = m.end()
        depth, i = 1, start
        while i < len(line) and depth:
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
            i += 1
        operands, tail = line[start:i - 1], line[i:]
        typed = _SHAPE_RE.findall(operands)
        op_bytes = sum(shape_bytes(d, s) for d, s in typed) if typed \
            else sum(defs.get(n, 0) for n in _NAME_RE.findall(operands))
        gi = _GROUPS_IOTA_RE.search(tail)
        ge = _GROUPS_EXPLICIT_RE.search(tail)
        if gi:
            n_groups, group = int(gi.group(1)), int(gi.group(2))
        elif ge:
            groups = re.findall(r"\{([0-9, ]*)\}", ge.group(1))
            n_groups = len(groups)
            group = len([x for x in groups[0].split(",") if x.strip()]) \
                if groups else world
        else:
            n_groups, group = 1, world
        pairs = _PAIRS_RE.search(tail)
        if op == "collective-permute" and pairs:
            total = op_bytes * pairs.group(1).count("{")
        else:
            total = op_bytes * group * n_groups
        entry = out.setdefault(op, {"count": 0, "bytes_per_step": 0})
        entry["count"] += 1
        entry["bytes_per_step"] += total
    return out


def lowered_has_collectives(stablehlo_text):
    """True when an UN-optimized lowering could communicate: it mentions
    an explicit collective (shard_map programs), or a non-replicated
    device assignment (``devices=[...]`` inside a sharding annotation —
    GSPMD inserts the collectives for those only at COMPILE time, so the
    mnemonic probe alone would miss a mesh-sharded jit and lock it an
    empty budget).  The single-chip hot-path programs answer False on
    both, which makes their comm budget ``{}`` without paying for a
    compile; replicated-only sharding annotations don't trip the probe."""
    return any(op in stablehlo_text for op in _STABLEHLO_COLLECTIVES) \
        or "devices=[" in stablehlo_text


def fmt_bytes(n):
    """Human-readable bytes for diffs: 2155872 -> '2.1MB'."""
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GB"


# ------------------------------------------------------------------ #
# Mesh-scaling table + growth analysis
# ------------------------------------------------------------------ #
# per-chip growth below this ratio between consecutive mesh points is
# treated as schedule noise (padding, fusion boundaries), not replication
GROWTH_TOLERANCE = 1.02


def scaling_entry(world, mesh, comm):
    """One scaling-table row: per-op totals and bytes-per-chip at one
    mesh size."""
    per_chip = {op: v["bytes_per_step"] // world
                for op, v in sorted(comm.items())}
    return {
        "world": int(world),
        "mesh": {k: int(v) for k, v in sorted(dict(mesh).items())},
        "collectives": {op: dict(v) for op, v in sorted(comm.items())},
        "bytes_per_chip": per_chip,
        "bytes_per_chip_total": sum(per_chip.values()),
    }


def growth_flags(table):
    """Ops whose per-chip volume grows between consecutive mesh points.

    ``table`` is a list of scaling entries ordered by world.  Returns
    ``{op: ["2->4: 12.3KB -> 45.6KB/chip", ...]}`` — per-chip bytes
    increasing by more than ``GROWTH_TOLERANCE`` anywhere in the
    trajectory flags the op (the replicated-tensor smell: well-sharded
    traffic stays flat or falls per chip as chips are added).  An op
    APPEARING at a larger mesh (absent at the previous multi-chip point)
    is flagged too — new-axis traffic is exactly how a replicated tensor
    sneaks in undeclared; only the 1->2 transition is exempt, since a
    one-chip mesh has no collectives for anything to be "absent" from."""
    flags = {}
    for prev, nxt in zip(table, table[1:]):
        for op, b in nxt["bytes_per_chip"].items():
            was = prev["bytes_per_chip"].get(op)
            if was and b > was * GROWTH_TOLERANCE:
                flags.setdefault(op, []).append(
                    f"{prev['world']}->{nxt['world']}: "
                    f"{fmt_bytes(was)} -> {fmt_bytes(b)}/chip")
            elif not was and b and prev["world"] > 1:
                flags.setdefault(op, []).append(
                    f"appears at mesh {nxt['world']}: "
                    f"{fmt_bytes(b)}/chip")
    return flags


def build_scaling_contract(plan_builder, mesh_points=None, progress=None,
                           reuse_rows=None):
    """Compile one plan family at every mesh point and return its locked
    scaling contract: the per-world table, the growth-flag set, and the
    plan's declared ``allowed_growth`` reasons.

    ``reuse_rows`` optionally maps ``world -> scaling row`` for points
    already compiled elsewhere (the contract gate derives the canonical
    world=8 row from the locked-schedule compile, so the table's top row
    IS the locked schedule's program and is never compiled twice)."""
    import sys
    from deepspeed_tpu.parallel import plans
    from deepspeed_tpu.parallel.topology import reset_topology
    if mesh_points is None:
        owner = sys.modules.get(plan_builder.__module__)
        mesh_points = getattr(owner, "MESH_POINTS", plans.MESH_POINTS)
    table, name, allowed = [], None, {}
    for world in sorted(mesh_points):
        row = (reuse_rows or {}).get(world)
        if row is None:
            if progress:
                progress(f"compiling {plan_builder.__name__} @ mesh "
                         f"{world}")
            reset_topology()
            try:
                plan = plan_builder(world)
                text = plan.fn.lower(*plan.args).compile().as_text() or ""
                comm = parse_hlo_comm(text, world)
            finally:
                reset_topology()
            name = name or plan.name
            if plan.allowed_growth:
                allowed = dict(plan.allowed_growth)
            row = scaling_entry(world, plan.mesh, comm)
        table.append(row)
    flags = growth_flags(table)
    return name, {
        "kind": "mesh_scaling",
        "points": table,
        "grows_with_mesh": {op: trans
                            for op, trans in sorted(flags.items())},
        "allowed_growth": dict(sorted(allowed.items())),
    }


def validate_scaling_contract(name, contract):
    """Semantic invariants of a scaling contract, checked on top of the
    exact locked table: every growing collective must carry a declared
    reason, and a mesh of one chip must move zero bytes."""
    problems = []
    allowed = contract.get("allowed_growth", {})
    for op, transitions in contract.get("grows_with_mesh", {}).items():
        if op not in allowed:
            problems.append(
                f"per-chip {op} volume GROWS with mesh size "
                f"({'; '.join(transitions)}) — the replicated-tensor "
                f"smell; shard the tensor or declare the growth in the "
                f"plan's allowed_growth with a reason")
    for row in contract.get("points", []):
        if row["world"] == 1 and row["bytes_per_chip_total"]:
            problems.append(
                f"mesh of 1 chip schedules collective traffic "
                f"({fmt_bytes(row['bytes_per_chip_total'])}/chip) — "
                f"phantom communication")
    return [f"{name}: {p}" for p in problems]


def diff_scaling(name, locked, fresh):
    """Readable diff of one plan's scaling contract (empty = match)."""
    out = []
    lp = {r["world"]: r for r in locked.get("points", [])}
    fp = {r["world"]: r for r in fresh.get("points", [])}
    for world in sorted(set(lp) | set(fp)):
        lo, fr = lp.get(world), fp.get(world)
        if lo is None or fr is None:
            out.append(f"  mesh {world}: "
                       f"{'added' if lo is None else 'removed'} point")
            continue
        ops = sorted(set(lo["bytes_per_chip"]) | set(fr["bytes_per_chip"]))
        for op in ops:
            a = lo["bytes_per_chip"].get(op, 0)
            b = fr["bytes_per_chip"].get(op, 0)
            if a != b:
                out.append(f"  mesh {world} {op}: {fmt_bytes(a)} -> "
                           f"{fmt_bytes(b)} per chip")
        # the locked per-point schedule entries too: an instance-count or
        # sub-world-byte drift (integer bytes-per-chip truncation) must
        # not slide through a clean-looking per-chip table
        lc, fc = lo.get("collectives", {}), fr.get("collectives", {})
        for op in sorted(set(lc) | set(fc)):
            a, b = lc.get(op), fc.get(op)
            if a != b:
                out.append(
                    f"  mesh {world} {op} schedule: "
                    f"{a and a['count']}x/{fmt_bytes((a or {}).get('bytes_per_step', 0))}"
                    f" -> {b and b['count']}x/"
                    f"{fmt_bytes((b or {}).get('bytes_per_step', 0))}")
        if lo["mesh"] != fr["mesh"]:
            out.append(f"  mesh {world} axes: {lo['mesh']} -> {fr['mesh']}")
    for field in ("grows_with_mesh", "allowed_growth"):
        lo, fr = locked.get(field, {}), fresh.get(field, {})
        for op in sorted(set(lo) | set(fr)):
            if lo.get(op) != fr.get(op):
                out.append(f"  {field}[{op}]: {lo.get(op)!r} -> "
                           f"{fr.get(op)!r}")
    return [f"{name}:"] + out if out else []


# ------------------------------------------------------------------ #
# CLI (``ds_lint --comm``): sweep + extraction + scaling prover
# ------------------------------------------------------------------ #
def _plans_module():
    """The plans module under analysis — overridable for the synthetic-
    break tests (a fixture module with a deliberately replicated plan).
    The override is a dotted module name or a ``.py`` path."""
    import importlib
    override = os.environ.get("DSTPU_COMM_PLANS_MODULE")
    if override and override.endswith(".py"):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "dstpu_comm_fixture_plans", override)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    if override:
        return importlib.import_module(override)
    from deepspeed_tpu.parallel import plans
    return plans


def check_scaling_against_lockfile(progress=None, plans_mod=None):
    """(ok, lines).  Rebuild every plan's scaling contract, validate the
    growth invariants, and diff against the ``mesh_scaling`` section of
    ``PROGRAMS.lock`` (when the plans module is overridden, validation
    still runs but the lockfile diff is skipped — fixture plans are not
    locked)."""
    from deepspeed_tpu.tools.lint import contract as contract_mod
    overridden = plans_mod is not None or \
        bool(os.environ.get("DSTPU_COMM_PLANS_MODULE"))
    plans_mod = plans_mod or _plans_module()
    lines, ok = [], True
    locked = {}
    if not overridden:
        try:
            locked = contract_mod.load_lockfile().get("mesh_scaling", {})
        except FileNotFoundError:
            # nothing to diff against: fail fast instead of paying the
            # full compile sweep for an answer known at the first line
            return False, [
                f"{contract_mod.LOCKFILE_NAME} missing — generate with "
                f"ds_lint --contracts --update"]
    mesh_points = getattr(plans_mod, "MESH_POINTS", None)
    for builder in plans_mod.PLAN_BUILDERS:
        name, fresh = build_scaling_contract(builder, mesh_points,
                                             progress=progress)
        problems = validate_scaling_contract(name, fresh)
        if problems:
            ok = False
            lines.extend(problems)
        if overridden:
            continue
        if name not in locked:
            ok = False
            lines.append(f"{name}: no mesh_scaling contract in "
                         f"{contract_mod.LOCKFILE_NAME} — run "
                         f"ds_lint --contracts --update")
            continue
        diff = diff_scaling(name, locked[name], fresh)
        if diff:
            ok = False
            lines.extend(diff)
    return ok, lines


def main(paths=None):
    """The ``--comm`` gate: TL010/TL011 sweep over ``paths`` (default: the
    installed package), then the mesh-scaling prover.  Exit 1 on any
    unsuppressed finding, growth violation, or lockfile drift."""
    from deepspeed_tpu.tools.lint.core import run_lint
    if not paths:
        import deepspeed_tpu
        paths = [os.path.dirname(os.path.abspath(deepspeed_tpu.__file__))]
    findings, stats = run_lint(paths, rules={"TL010", "TL011"})
    for f in findings:
        print(f)
    suppressed = sum(stats["suppressed"].values())
    print(f"tpu-lint[comm]: {len(findings)} finding(s), {suppressed} "
          f"suppressed, {stats['files']} file(s) checked")
    if findings:
        return 1                      # static break: skip the slow prover
    progress = lambda msg: print(f"[comm] {msg}", flush=True)
    ok, lines = check_scaling_against_lockfile(progress=progress)
    if ok:
        print("[comm] OK — every plan's mesh-scaling contract holds "
              "(per-chip volumes locked, no undeclared growth)")
        return 0
    print("[comm] COMM-CONTRACT BREAK:")
    for line in lines:
        print(f"  {line}")
    print("[comm] intentional? regenerate with ds_lint --contracts "
          "--update and review the bytes diff like any lockfile bump")
    return 1


if __name__ == "__main__":
    import sys
    from deepspeed_tpu.tools.lint import contract as _c
    _c.ensure_harness_env()
    sys.exit(main(sys.argv[1:] or None))
