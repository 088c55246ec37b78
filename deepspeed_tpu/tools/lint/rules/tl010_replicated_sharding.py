"""TL010 — implicit replication at mesh boundaries (sharding lint).

On a multi-chip mesh the default placement is FULL REPLICATION: an array
that enters a ``shard_map``/jit program without a ``PartitionSpec`` (or
with the explicit empty spec ``P()``) is materialized whole on every chip,
and anything downstream that needs it sharded pays an all-gather per step.
For weights that is a capacity bug; for activations — anything whose size
scales with batch or sequence — it is the classic "8-chip run turned into
an all-gather storm" regression the comm-cost contracts exist to catch.
This rule catches it at the SOURCE level, before a byte moves:

* a ``shard_map`` application (direct call, ``functools.partial``
  decorator) carrying a ``mesh=`` but missing
  ``in_specs``/``out_specs`` — every operand silently replicates;
* a ``jax.jit`` call inside a ``with <mesh>:`` block with no
  ``in_shardings``/``out_shardings`` at all — same default, harder to see;
* a bare ``P()`` spec bound to a parameter whose NAME says its size scales
  with batch or sequence (``batch``, ``input_ids``, ``hidden``, ``x`` …) —
  in ``in_specs`` (literal tuples or module-resolvable spec variables;
  outputs have no bindable name, so replicated ``out_specs`` surface
  through the comm budgets instead), or as
  ``device_put(x, NamedSharding(mesh, P()))`` /
  ``with_sharding_constraint(x, ... P())`` on a batch-scaling name.

Deliberate replication (a compressed-collective input that IS the full
local gradient, a pipeline region that slices the global batch in-program)
gets a suppression with the reason — the point is that every fully
replicated batch-scaling array in the package is either a bug or a
documented decision.
"""

import ast
import re

from deepspeed_tpu.tools.lint.core import Finding, dotted_name, rule

# names whose arrays scale with batch and/or sequence length — the ones a
# replicated placement turns into per-step all-gather traffic
_BATCH_SCALED_RE = re.compile(
    r"batch|input|label|ids|tok|seq|hid|act|logit|emb|cache|kv|lane|pool|"
    r"micro|prompt|ctx", re.IGNORECASE)
_BATCH_EXACT_RE = re.compile(r"^[xhqkv][s0-9]?$|^attn$|^out$")


def is_batch_scaled_name(name):
    if not name:
        return False
    leaf = name.split(".")[-1]
    return bool(_BATCH_SCALED_RE.search(leaf) or _BATCH_EXACT_RE.match(leaf))


def _callee_leaf(node):
    name = dotted_name(node)
    return name.split(".")[-1].lstrip("_") if name else None


def is_shard_map_callee(node):
    return _callee_leaf(node) == "shard_map"


def is_bare_partition_spec(node):
    """``P()`` / ``PartitionSpec()`` with no axes — the explicit
    fully-replicated spec."""
    return (isinstance(node, ast.Call)
            and _callee_leaf(node.func) in ("P", "PartitionSpec")
            and not node.args and not node.keywords)


def _positional_params(fn_node):
    a = fn_node.args
    return [p.arg for p in (*a.posonlyargs, *a.args)
            if p.arg not in ("self", "cls")]


def _resolve_name_assign(module, name, before_line):
    """The value of the lexically nearest ``name = <expr>`` assignment
    above ``before_line`` — how ``in_specs = (...)`` variables passed to a
    later shard_map call are resolved."""
    best = None
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == name \
                and node.lineno <= before_line \
                and (best is None or node.lineno > best.lineno):
            best = node
    return best.value if best is not None else None


def _resolve_wrapped_params(module, fn_expr, before_line=None):
    """Positional parameter names of the callable a shard_map wraps, when
    module-locally resolvable (a local ``def`` or a lambda).  Several
    same-named defs (one ``region`` per plan builder) resolve to the
    lexically nearest one above the call."""
    if isinstance(fn_expr, ast.Lambda):
        return _positional_params(fn_expr)
    if isinstance(fn_expr, ast.Name):
        best = None
        for fn in module.functions:
            if fn.name != fn_expr.id:
                continue
            if before_line is not None and fn.node.lineno > before_line:
                continue
            if best is None or fn.node.lineno > best.node.lineno:
                best = fn
        if best is not None:
            return _positional_params(best.node)
    return None


def shard_map_applications(module):
    """Every shard_map application in the module as
    ``(line, col, kwargs: {name: expr}, wrapped_params or None)`` —
    direct calls ``shard_map(f, mesh=..., ...)``, and
    ``functools.partial(shard_map, ...)`` decorators whose specs bind to
    the decorated ``def``."""
    out = []
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Call) and is_shard_map_callee(node.func):
            kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
            wrapped = node.args[0] if node.args else None
            out.append((node.lineno, node.col_offset, kwargs,
                        _resolve_wrapped_params(module, wrapped,
                                                node.lineno)))
    for fn in module.functions:
        for dec in getattr(fn.node, "decorator_list", []):
            if not (isinstance(dec, ast.Call)
                    and _callee_leaf(dec.func) == "partial"
                    and dec.args and is_shard_map_callee(dec.args[0])):
                continue
            kwargs = {kw.arg: kw.value for kw in dec.keywords if kw.arg}
            out.append((dec.lineno, dec.col_offset, kwargs,
                        _positional_params(fn.node)))
    return out


def spec_entries(module, spec_expr, call_line):
    """The per-argument entries of an ``in_specs``/``out_specs``
    expression, resolving one level of ``specs = (...)`` variable
    indirection.  Returns a list of AST nodes, or None when the structure
    is not statically visible (tree-mapped specs, call results)."""
    if isinstance(spec_expr, ast.Name):
        spec_expr = _resolve_name_assign(module, spec_expr.id, call_line)
    if spec_expr is None:
        return None
    if isinstance(spec_expr, (ast.Tuple, ast.List)):
        return list(spec_expr.elts)
    return [spec_expr]


def _mesh_with_blocks(module):
    """Line spans of ``with`` blocks whose context expression mentions a
    mesh (``with mesh:``, ``with self.mesh:``, ``with Mesh(...):``)."""
    spans = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            expr = item.context_expr
            target = expr.func if isinstance(expr, ast.Call) else expr
            name = dotted_name(target) or ""
            if name.split(".")[-1].lower().endswith("mesh"):
                spans.append((node.lineno, node.end_lineno or node.lineno))
                break
    return spans


@rule("TL010", "implicit replication at mesh boundaries")
def check(module):
    # (a) shard_map with a mesh but no specs: every operand replicates
    for line, col, kwargs, params in shard_map_applications(module):
        if "mesh" in kwargs and ("in_specs" not in kwargs
                                 or "out_specs" not in kwargs):
            missing = [k for k in ("in_specs", "out_specs")
                       if k not in kwargs]
            yield Finding(
                "TL010", module.path, line, col,
                f"shard_map over a mesh with no {'/'.join(missing)} — "
                f"every unspecced operand is fully replicated on every "
                f"chip (declare a PartitionSpec per argument)")
            continue
        # (b) bare P() bound to a batch/sequence-scaling parameter.
        # Only in_specs: spec entries bind to the wrapped callable's
        # parameter NAMES, and outputs have no statically visible name
        # to judge batch-scaling by (out_specs axis-name checks live in
        # TL011; an all-replicated out_specs still surfaces through the
        # comm budget the program compiles to).
        entries = spec_entries(module, kwargs.get("in_specs"), line)
        if not entries or params is None:
            continue
        for i, entry in enumerate(entries):
            if not is_bare_partition_spec(entry):
                continue
            bound = params[i] if i < len(params) and len(entries) > 1 \
                else None
            if len(entries) == 1:
                # a single P() broadcasts to every argument
                bound = next((p for p in params
                              if is_batch_scaled_name(p)), None)
            if bound and is_batch_scaled_name(bound):
                yield Finding(
                    "TL010", module.path, entry.lineno,
                    entry.col_offset,
                    f"replicated spec P() on batch/sequence-scaling "
                    f"argument '{bound}' of a shard_map program — "
                    f"every chip holds (and moves) the full array; "
                    f"shard it or suppress with the reason it must "
                    f"replicate")

    # (a2) jit under a mesh context with no shardings anywhere
    spans = _mesh_with_blocks(module)
    if spans:
        for node in ast.walk(module.tree):
            if not (isinstance(node, ast.Call)
                    and _callee_leaf(node.func) in ("jit", "pjit")):
                continue
            if any(lo <= node.lineno <= hi for lo, hi in spans):
                kw = {k.arg for k in node.keywords if k.arg}
                if not kw & {"in_shardings", "out_shardings"}:
                    yield Finding(
                        "TL010", module.path, node.lineno, node.col_offset,
                        f"jit inside a mesh context with neither "
                        f"in_shardings nor out_shardings — large inputs "
                        f"default to full replication across the mesh")

    # (b2) explicit replicated placement of a batch-scaling array
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Call) and _callee_leaf(node.func)
                in ("device_put", "with_sharding_constraint")):
            continue
        if len(node.args) < 2:
            continue
        target, sharding = node.args[0], node.args[1]
        has_bare = any(is_bare_partition_spec(sub)
                       for sub in ast.walk(sharding))
        tname = dotted_name(target)
        if has_bare and tname and is_batch_scaled_name(tname):
            yield Finding(
                "TL010", module.path, node.lineno, node.col_offset,
                f"batch/sequence-scaling array '{tname}' placed with the "
                f"replicated spec P() — every chip holds a full copy")
