"""Flops profiler.

The reference monkey-patches ``torch.nn.functional`` to count flops at
runtime (``profiling/flops_profiler/profiler.py:23,441-``).  On TPU the
compiler already knows: XLA's cost analysis on the compiled executable gives
exact flop/byte counts for the *optimized* program — more accurate than
op-by-op Python counting, and free.  The profiler reads
``compiled.cost_analysis()`` plus wall-clock timing to report
flops / MACs / params / achieved TFLOPS and MFU.

Per-module tree (reference ``print_model_profile``, ``profiler.py:239``):

* flops / MACs / params per module come from flax's module summary
  (exact per-call counts via ``jax.jit`` cost analysis on each submodule);
* measured per-module DEVICE latency comes from one profiled run — XLA-op
  durations in the ``jax.profiler`` trace joined against the compiled
  HLO's ``op_name`` metadata, which carries the flax module scope path
  (the TPU analog of the reference's per-module hook timers).
"""

import glob
import os
import re
import tempfile
import time

import numpy as np

import jax

from deepspeed_tpu.utils.logging import log_dist, logger

# Peak bf16 TFLOP/s per chip for MFU estimates (public figures).
PEAK_TFLOPS = {
    "tpu v4": 275.0,
    "tpu v5 lite": 197.0,   # v5e
    "tpu v5e": 197.0,
    "tpu v5": 459.0,        # v5p
    "tpu v6 lite": 918.0,   # trillium
    "cpu": 0.1,
}

# Peak HBM GB/s per chip for bandwidth-utilization estimates (public figures).
PEAK_HBM_GBPS = {
    "tpu v4": 1228.0,
    "tpu v5 lite": 819.0,   # v5e
    "tpu v5e": 819.0,
    "tpu v5": 2765.0,       # v5p
    "tpu v6 lite": 1640.0,  # trillium
    "cpu": 50.0,
}


def _device_peak(table):
    d = jax.devices()[0]
    kind = d.device_kind.lower()
    for key, val in table.items():
        if kind.startswith(key):
            return val
    raise KeyError(
        f"no published peak for device kind {d.device_kind!r} — add it "
        f"to the tables in {__name__} with its source; a device that is "
        f"not in the table is an error, not a default")


def device_peak_tflops():
    return _device_peak(PEAK_TFLOPS)


def device_peak_hbm_gbps():
    return _device_peak(PEAK_HBM_GBPS)


def device_hbm_bytes():
    """Device memory budget in bytes, via the accelerator's canonical
    ``memory_snapshot`` reader: the runtime's reported ``bytes_limit``
    (0 only on the CPU test backend = unbounded, callers skip budget
    checks; a TPU that reports none raises)."""
    from deepspeed_tpu.accelerator.real_accelerator import get_accelerator
    return int(get_accelerator().memory_snapshot()["bytes_limit"])


def cost_analysis_of(fn, *args, **kwargs):
    """Compile ``fn`` and return XLA's cost analysis dict (flops, bytes)
    — the compiled-program extraction itself is the shared cost model
    (``autotuning.cost_model.xla_cost_analysis``), the same code the
    memory/FLOP contract layer and the bench roofline blocks read."""
    from deepspeed_tpu.autotuning.cost_model import xla_cost_analysis
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    return xla_cost_analysis(compiled)


class FlopsProfiler:
    """Engine-attached profiler (reference ``FlopsProfiler:23``): profile one
    training step at ``profile_step`` and report totals."""

    def __init__(self, engine=None, model=None):
        self.engine = engine
        self.started = False
        self.flops = 0.0
        self.macs = 0.0
        self.params = 0
        self.step_time = 0.0

    def start_profile(self, ignore_list=None):
        self.started = True
        self._t0 = time.perf_counter()

    def stop_profile(self):
        if self.started:
            self.step_time = time.perf_counter() - self._t0
            self.started = False

    def profile_fn(self, fn, *args, **kwargs):
        """Profile an arbitrary jittable function: returns dict of metrics."""
        costs = cost_analysis_of(fn, *args, **kwargs)
        flops = float(costs.get("flops", 0.0))
        # timed execution
        f = jax.jit(fn)
        out = f(*args, **kwargs)          # warmup/compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        n = 3
        for _ in range(n):
            out = f(*args, **kwargs)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / n
        achieved = flops / dt / 1e12 if dt > 0 else 0.0
        peak = device_peak_tflops() * jax.device_count()
        return {
            "flops": flops,
            "latency_s": dt,
            "tflops": achieved,
            "mfu": achieved / peak if peak else 0.0,
            "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
        }

    def get_total_flops(self, as_string=False):
        return _num_to_string(self.flops) + "FLOPS" if as_string else self.flops

    def get_total_params(self, as_string=False):
        return _num_to_string(self.params) if as_string else self.params

    def print_model_profile(self, profile_step=1, module_depth=-1, top_modules=3,
                            detailed=True, output_file=None, batch=None):
        """Reference-format profile report (``profiler.py:239``): totals,
        per-depth aggregates, and the detailed per-module tree (flops/MACs
        exact from the module summary; latency measured from a profiled
        run where a device trace is available)."""
        if self.engine is not None and self.engine.params is not None:
            self.params = sum(int(np.prod(l.shape))
                              for l in jax.tree.leaves(self.engine.params))
        lines = [
            "-------------------------- DeepSpeed Flops Profiler --------------------------",
            f"params per gpu: {_num_to_string(self.params)}",
            f"profile step: {profile_step}",
            f"step latency: {self.step_time*1e3:.2f} ms",
        ]
        tree = None
        module = getattr(self.engine, "module", None) if self.engine else None
        import flax.linen as nn
        if detailed and isinstance(module, nn.Module) and batch is not None:
            try:
                tree, total_ps = model_profile_tree(
                    module, jax.random.key(0), batch,
                    variables=getattr(self.engine, "params", None))
                lines.append(
                    "----------------------------- Aggregated Profile per GPU"
                    " -----------------------------")
                lines.append(aggregate_by_depth(
                    tree, max_depth=module_depth if module_depth > 0 else 3,
                    top=max(int(top_modules), 1)))
                lines.append(
                    "------------------------------ Detailed Profile per GPU"
                    " ------------------------------")
                lines.append(format_profile_tree(
                    tree, total_ps, depth=module_depth))
            except Exception as e:
                lines.append(f"(per-module tree unavailable: {e})")
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report)
        log_dist(report, ranks=[0])
        return report


def _num_to_string(num, precision=2):
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(num) >= div:
            return f"{num/div:.{precision}f} {unit}"
    return str(num)


# --------------------------------------------------------------------- #
# Per-module profile tree (reference profiler.py:239 print_model_profile)
# --------------------------------------------------------------------- #
class ModuleProfile:
    """One node of the per-module tree: subtree-aggregated params / fwd
    flops / bwd (vjp) flops, measured device latency, and children."""

    def __init__(self, name, module_type=""):
        self.name = name
        self.module_type = module_type
        self.params = 0
        self.flops = 0.0          # forward flops (2x MACs)
        self.vjp_flops = 0.0      # fwd+bwd flops of the vjp
        self.latency_ps = 0       # measured device time attributed here
        self.children = {}

    @property
    def macs(self):
        return self.flops / 2.0

    def child(self, name, module_type=""):
        if name not in self.children:
            self.children[name] = ModuleProfile(name, module_type)
        return self.children[name]

    def walk(self, depth=0):
        yield depth, self
        for c in self.children.values():
            yield from c.walk(depth + 1)


def _scope_to_path(op_name):
    """HLO metadata op_name → module path tuple.

    ``jit(fn)/Model/Model.hidden_states/layers_0/attn/dot_general`` →
    ``("layers_0", "attn", ...)``: transform frames (``jit(...)`` etc.),
    method frames (``Class.method``), and einsum-label frames are dropped;
    a trailing primitive name simply stops the tree walk at the owning
    module."""
    parts = [p for p in op_name.split("/")
             if "(" not in p and "." not in p
             and re.match(r"^[A-Za-z_]\w*$", p)]
    # drop the leading model-class frame
    return tuple(parts[1:])


def _hlo_op_scopes(compiled_text):
    """Map HLO instruction name → op_name metadata scope."""
    return dict(re.findall(
        r"%?([\w.\-]+) = [^\n]*metadata=\{[^}]*op_name=\"([^\"]+)\"",
        compiled_text))


def _trace_op_stats(trace_fn):
    """Run ``trace_fn()`` under the jax profiler; return
    {hlo_op: [dur_ps, flops]} summed over the device plane's XLA-op events.
    Returns {} when no device plane with op events is found (e.g. CPU test
    meshes)."""
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    with tempfile.TemporaryDirectory() as d:
        try:
            with jax.profiler.trace(d):
                trace_fn()
            from tensorflow.tsl.profiler.protobuf import xplane_pb2
        except Exception as e:               # pragma: no cover - no tf proto
            logger.warning(f"flops profiler: trace unavailable ({e}); "
                           "per-module latency will be missing")
            return {}
        out = {}
        for path in glob.glob(d + "/**/*.xplane.pb", recursive=True):
            xs = xplane_pb2.XSpace()
            with open(path, "rb") as f:
                xs.ParseFromString(f.read())
            for plane in xs.planes:
                if "/device:" not in plane.name:
                    continue
                ev_meta = plane.event_metadata
                stats_meta = plane.stat_metadata
                for line in plane.lines:
                    if line.name != "XLA Ops":
                        continue
                    for ev in line.events:
                        md = ev_meta[ev.metadata_id]
                        # "%fusion.3 = ..." → "fusion.3"
                        nm = md.name.split(" = ")[0].lstrip("%")
                        flops = 0
                        for st in list(ev.stats) + list(md.stats):
                            if stats_meta[st.metadata_id].name == "flops":
                                flops = int(st.int64_value or st.uint64_value
                                            or 0)
                        rec = out.setdefault(nm, [0, 0])
                        rec[0] += ev.duration_ps
                        # per-occurrence: ops inside rolled loops execute
                        # (and cost) once per iteration
                        rec[1] += flops
        return out


def model_profile_tree(module, rngs, *args, measure_latency=True,
                       variables=None, **kwargs):
    """Build the per-module profile tree for a flax module.

    Structure + params come from flax's module summary.  flops + latency:

    * on accelerators, from ONE profiled run of the compiled program —
      per-XLA-op durations and flop counts joined to module scopes via the
      HLO ``op_name`` metadata (exact for the *optimized* program);
    * on CPU (test meshes, no device trace), flops fall back to flax's
      per-module cost analysis and latency stays unattributed.

    Returns ``(root, total_latency_ps)``.  Ops the join can't see (fully
    fused across module boundaries) stay at the nearest attributed
    ancestor.
    """
    from flax.linen import summary as _summary
    on_cpu = jax.default_backend() == "cpu"
    table_fn = _summary._get_module_table(
        module, depth=None, show_repeated=True,
        compute_flops=on_cpu, compute_vjp_flops=on_cpu)
    rows = table_fn(rngs, *args, **kwargs)

    root = ModuleProfile("", type(module).__name__)
    for row in rows:
        node = root
        for part in row.path:
            node = node.child(part)
        node.module_type = type(row.module_copy).__name__
        if on_cpu:
            node.flops = float(row.flops) if row.flops and row.flops > 0 \
                else 0.0
            node.vjp_flops = float(row.vjp_flops) \
                if row.vjp_flops and row.vjp_flops > 0 else 0.0
        node.params = sum(
            int(np.prod(np.shape(v)))
            for v in jax.tree.leaves(row.module_variables.get("params", {})))

    def _aggregate_params(node):
        # rows carry each module's OWN variables; the tree reports subtree
        # totals like the reference
        node.params += sum(_aggregate_params(c)
                           for c in node.children.values())
        return node.params

    _aggregate_params(root)

    total_ps = 0
    if measure_latency:
        if variables is None:
            # callers profiling a LIVE engine must pass its params instead:
            # a fresh init would duplicate every parameter on a chip that
            # may already be near HBM capacity
            variables = module.init(rngs, *args, **kwargs)
        fn = jax.jit(lambda v, *a: module.apply(v, *a, **kwargs))
        # one compile serves warmup, the profiled run, AND the HLO text
        # (jit dispatch would compile a second executable)
        compiled = fn.lower(variables, *args).compile()
        scopes = _hlo_op_scopes(compiled.as_text())
        jax.block_until_ready(compiled(variables, *args))   # warmup

        def run():
            jax.block_until_ready(compiled(variables, *args))

        stats = _trace_op_stats(run)
        for op, (ps, flops) in stats.items():
            total_ps += ps
            scope = scopes.get(op)
            path = _scope_to_path(scope) if scope else ()
            node = root
            node.latency_ps += ps
            if not on_cpu:
                node.flops += flops
            for part in path:
                nxt = node.children.get(part)
                if nxt is None:
                    break
                node = nxt
                node.latency_ps += ps
                if not on_cpu:
                    node.flops += flops
    return root, total_ps


def format_profile_tree(root, total_latency_ps=0, depth=-1, indent=2):
    """Reference-style detailed tree (``profiler.py:239``): every module
    annotated with subtree params, MACs, and measured latency share."""
    tot_flops = root.flops or 1.0
    tot_params = root.params or 1
    tot_lat = root.latency_ps or total_latency_ps or 1
    lines = []

    def fmt(node, d, prefix):
        ann = (f"{_num_to_string(node.params)} = "
               f"{100.0 * node.params / tot_params:.2f}% Params, "
               f"{_num_to_string(node.macs)}MACs = "
               f"{100.0 * node.flops / tot_flops:.2f}% MACs")
        if node.latency_ps:
            ann += (f", {node.latency_ps / 1e6:.3f} ms = "
                    f"{100.0 * node.latency_ps / tot_lat:.2f}% latency")
        name = f"({node.name}): " if node.name else ""
        lines.append(" " * (d * indent) + f"{name}{node.module_type}({ann})")
        if depth < 0 or d < depth:
            for c in node.children.values():
                fmt(c, d + 1, prefix)

    fmt(root, 0, "")
    return "\n".join(lines)


def aggregate_by_depth(root, max_depth=3, top=3):
    """Reference "aggregated profile": top modules per depth by params /
    MACs / latency (``profiler.py:375``)."""
    by_depth = {}
    for d, node in root.walk():
        by_depth.setdefault(d, []).append(node)
    out = []
    for d in sorted(by_depth)[:max_depth + 1]:
        nodes = by_depth[d]
        top_p = sorted(nodes, key=lambda n: -n.params)[:top]
        top_f = sorted(nodes, key=lambda n: -n.flops)[:top]
        top_l = sorted(nodes, key=lambda n: -n.latency_ps)[:top]
        out.append(f"depth {d}:")
        out.append("    params      - " + str(
            {n.name or n.module_type: _num_to_string(n.params) for n in top_p}))
        out.append("    MACs        - " + str(
            {n.name or n.module_type: _num_to_string(n.macs) for n in top_f}))
        if any(n.latency_ps for n in nodes):
            out.append("    fwd latency - " + str(
                {n.name or n.module_type: f"{n.latency_ps/1e6:.3f} ms"
                 for n in top_l}))
    return "\n".join(out)


def get_model_profile(model_fn, args=(), kwargs=None, print_profile=True,
                      detailed=True, warm_up=1, as_string=True):
    """Standalone API parity (reference ``profiler.py get_model_profile``)."""
    prof = FlopsProfiler()
    metrics = prof.profile_fn(model_fn, *args, **(kwargs or {}))
    flops, macs = metrics["flops"], metrics["flops"] / 2
    params = 0
    if print_profile:
        log_dist(f"flops={_num_to_string(flops)} macs={_num_to_string(macs)} "
                 f"tflops={metrics['tflops']:.2f} mfu={metrics['mfu']*100:.1f}%",
                 ranks=[0])
    if as_string:
        return _num_to_string(flops), _num_to_string(macs), str(params)
    return flops, macs, params
