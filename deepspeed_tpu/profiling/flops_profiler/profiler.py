"""Flops profiler.

The reference monkey-patches ``torch.nn.functional`` to count flops at
runtime (``profiling/flops_profiler/profiler.py:23,441-``).  On TPU the
compiler already knows: XLA's cost analysis on the compiled executable gives
exact flop/byte counts for the *optimized* program — more accurate than
op-by-op Python counting, and free.  The profiler reads
``compiled.cost_analysis()`` plus wall-clock timing to report
flops / MACs / params / achieved TFLOPS and MFU.

Device time by the program's own parts (``docs/observability.md``, "Device
time by part"):

* a device event's name is its HLO instruction's text; the instruction's
  ``op_name`` — the flax module path and every ``jax.named_scope`` — is in
  the compiled module, and the PROFILER stores it in every trace it takes,
  program by program (:func:`trace_scopes`): nothing is kept, parsed or
  compiled for it outside a trace;
* :data:`SCOPE_PARTS` is the one table from an ``op_name`` to a PART
  (:data:`PARTS`) and a PHASE (:data:`PHASES`); :func:`device_time_by_scope`
  is the one join, used by the per-module tree below and by the benchmark's
  per-layer readers (``benchmark/scopes.py``).

Per-module tree (reference ``print_model_profile``, ``profiler.py:239``):

* params and flops / MACs per module come from flax's module summary
  (per-call counts from the lowered submodules; nothing is compiled);
* the latency column is the ENGINE'S OWN fused train step: at
  ``profile_step`` the engine's profiler opens a ``jax.profiler`` trace over
  the real ``train_batch`` and joins the device events of
  ``jit_train_step`` against the table that trace stores — forward,
  backward, remat's replay and the optimizer's update, each instruction's
  own time at the module its ``op_name`` names.  Where no device plane is
  traced (the CPU backend) the column stays empty.
"""

import bisect
import re
import shutil
import tempfile
import time
from collections import defaultdict

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
    memory/FLOP contract layer reads."""
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
        self.device_time = None      # device_time_by_scope of the step
        self._trace_dir = None

    def start_profile(self, ignore_list=None):
        """Start the step's clock and open a ``jax.profiler`` trace over
        it (host and Python tracers off: only the device planes are
        read).  A trace someone else holds open stays theirs: the step is
        timed and the latency column stays empty."""
        self.started = True
        self.device_time = None
        self._trace_dir = tempfile.mkdtemp(prefix="dstpu_flops_profile_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        except Exception as e:
            logger.warning(f"flops profiler: no trace of this step ({e}); "
                           "per-module latency will be missing")
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None
        self._t0 = time.perf_counter()

    def stop_profile(self):
        """Stop the clock and the trace; join the traced device events of
        the fused step, ``jit_train_step`` (the caller has fenced it:
        ``block_until_ready``; the 3-call path runs other programs and
        leaves the latency column empty)."""
        if not self.started:
            return
        self.step_time = time.perf_counter() - self._t0
        self.started = False
        if self._trace_dir is None:
            return
        try:
            jax.profiler.stop_trace()
            self.device_time = traced_device_time(self._trace_dir,
                                                  ("jit_train_step",))
        except Exception as e:
            logger.warning(f"flops profiler: trace unreadable ({e}); "
                           "per-module latency will be missing")
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None

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
        the device time of the profiled step by part and phase, per-depth
        aggregates, and the detailed per-module tree (params and MACs from
        the module summary; latency = the profiled step's own device time,
        where :meth:`stop_profile` read one).  Compiles nothing."""
        if self.engine is not None and self.engine.params is not None:
            self.params = sum(int(np.prod(l.shape))
                              for l in jax.tree.leaves(self.engine.params))
        lines = [
            "-------------------------- DeepSpeed Flops Profiler --------------------------",
            f"params per gpu: {_num_to_string(self.params)}",
            f"profile step: {profile_step}",
            f"step latency: {self.step_time*1e3:.2f} ms",
        ]
        if self.device_time is not None:
            lines.append(
                "------------------------- Device time by part (one device)"
                " -------------------------")
            lines.append(format_device_time(self.device_time))
        tree = None
        module = getattr(self.engine, "module", None) if self.engine else None
        import flax.linen as nn
        if detailed and isinstance(module, nn.Module) and batch is not None:
            try:
                tree, total_ps = model_profile_tree(
                    module, jax.random.key(0), batch,
                    device_time=self.device_time)
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
        self.latency_by_phase = defaultdict(int)     # fwd / bwd / replay
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


def _scope_frames(op_name):
    """The module-name frames of an HLO metadata op_name: transform frames
    (``jit(...)``, ``transpose(jvp(Model))``), method frames
    (``Class.method``) and einsum-label frames are dropped."""
    return [p for p in op_name.split("/")
            if "(" not in p and "." not in p
            and re.match(r"^[A-Za-z_]\w*$", p)]


def _scope_to_path(op_name):
    """HLO metadata op_name → module path tuple.

    ``jit(fn)/Model/Model.hidden_states/layers_0/attn/dot_general`` →
    ``("layers_0", "attn", ...)``: the frames of :func:`_scope_frames`
    less the leading model-class frame; a trailing primitive name simply
    stops the tree walk at the owning module."""
    return tuple(_scope_frames(op_name)[1:])


# --------------------------------------------------------------------- #
# Device time by part (docs/observability.md "Device time by part")
# --------------------------------------------------------------------- #
PARTS = ("embed", "attn.proj", "attn.core", "attn.mla_decompress",
         "attn.eva", "attn.kda", "attn.ssd", "eva.summarise", "cache.write", "mlp", "moe.route",
         "moe.experts", "conv.short", "norm", "residual", "head", "loss",
         "optim", "scan.stack", "slots", "mtp.combine", "comm",
         "xla.prefetch")
PHASES = ("fwd", "bwd", "replay")
# collectives are found by opcode, whatever scope they carry (the list is
# ``benchmark/trace.py::COLLECTIVES``)
COLLECTIVE_OPCODES = ("all-reduce", "all-gather", "reduce-scatter",
                      "all-to-all", "collective-permute",
                      "collective-broadcast")
# the asynchronous copies and slices XLA's scheduler inserts (weights into
# faster memory ahead of their use), found by opcode where their
# ``op_name`` (none, or the consumer's) names no part; the own time of a
# ``-done`` half is the wait for it
PREFETCH_OPCODES = ("copy-start", "copy-done", "async-start", "async-done",
                    "slice-start", "slice-done")
# THE table: one frame of an ``op_name`` ("/"-separated: flax module
# names, ``module.method`` frames, ``jax.named_scope``s, a kernel's
# ``name=``) → part.  A fixed literal set like the span names: never a
# size or an index in a name.  The INNERMOST frame that matches a row
# decides, rows tried in order — ``layers_3/attn/q_norm/mul`` is
# ``attn.proj`` (QK-norm), not ``norm`` —, and a row with "/"s is matched
# against the frame WITH as many parents (``conv/out_proj``).  A fused
# instruction carries its ROOT's ``op_name``: the o_proj / MLP fusions
# swallow the next LayerNorm's sums and count them where the root is.
SCOPE_PARTS = (
    # scopes the programs add where flax gives no module
    (r"attn\.mla_decompress", "attn.mla_decompress"),
    (r"attn\.rope", "attn.proj"),
    (r"cache\.write", "cache.write"),
    (r"head(\.sample)?", "head"),
    (r"loss", "loss"),
    (r"optim\.(accumulate|clip|update)", "optim"),
    (r"slots\.(state|expert_load)", "slots"),
    (r"conv\.short|conv/(in_proj|out_proj)", "conv.short"),
    (r"eva\.summarise", "eva.summarise"),
    # a multi-token-prediction module (``models/glm5.py``): what its three
    # scopes hold outside a module the rows below know — the concatenation
    # and ``eh_proj``, the block's row bookkeeping, the shared head
    (r"mtp\.combine|eh_proj", "mtp.combine"),
    (r"mtp\.block", "residual"),
    (r"mtp\.head", "head"),
    # a shortcut-connected double layer (``models/longcat.py``): what its
    # scopes hold outside a module or kernel the rows below know — the zero
    # experts' identity part and the branch's adds; the dense latent
    # layer's masks and query rows around its kernels
    (r"scmoe\.experts|moe\.zero_experts", "moe.experts"),
    (r"scmoe\.dense_ffn", "mlp"),
    (r"attn\.mla_dense_(chunk|decode)", "attn.core"),
    # a sandwich-norm block with gated attention over two kinds of K/V cache
    # (``models/trinity.py``): its norms' and the embedding multiplier's
    # scopes, the head's, the output gate's projection (named like an MLP's)
    # and what it does around its attention kernels
    (r"norm\.(input|post_attn|pre_mlp|post_mlp)|\w+_layernorm", "norm"),
    (r"embed\.scale", "embed"),
    (r"head\.logits", "head"),
    (r"slots\.tables", "slots"),
    (r"self_attn/gate_proj", "attn.proj"),
    (r"attn\.(qk_norm|out_gate)", "attn.proj"),
    (r"attn\.(window|full)", "attn.core"),
    # gated delta-rule linear attention on a matrix state a slot
    # (``models/solar_open2.py``): the mixer's scopes, its two state kernels
    # by their ``name=``, and what its module holds that no row below knows
    # — the decay's, the step size's and the gate's projections, the
    # per-head output norm.  (Its q / k / v / o projections are
    # ``attn.proj`` and its convolutions ``conv.short`` like any other's;
    # the scope ``attn.kda`` covers them all for a reader that wants the
    # mixer whole)
    (r"attn\.kda/conv\.short", "conv.short"),
    (r"attn\.kda|kda\.(scan|out_gate)|\w*kda\.(chunk_scan|decode_step)\w*",
     "attn.kda"),
    (r"(f|g)_(a|b)_proj|b_proj|o_norm|linear_attn(\.\w+)?", "attn.kda"),
    # Mamba-2 state-space layers on a matrix state a slot
    # (``models/granite_hybrid.py``): the mixer's scopes, its two state
    # kernels by their ``name=``, and what its module holds outside them —
    # the discretisation, the skip, the gate and its one norm.  (``in_proj``
    # / ``out_proj`` are ``attn.proj`` and its convolution ``conv.short``
    # like any other's; the scope ``attn.ssd`` covers them all for a reader
    # that wants the mixer whole)
    (r"attn\.ssd/conv\.short", "conv.short"),
    (r"attn\.ssd/in_proj", "attn.proj"),
    (r"attn\.ssd|ssd\.(scan|gate_norm)|\w*ssd\.(chunk_scan|decode_step)\w*",
     "attn.ssd"),
    (r"mamba(\.\w+)?", "attn.ssd"),
    # a ONE-sublayer block (``models/nemotron_h.py``): its one norm is
    # called ``norm`` as the checkpoint has it (its sublayer is ``mamba`` /
    # ``moe_mlp`` / ``self_attn`` by its kind, rows of their own here; the
    # final ``norm_f`` runs under ``head.logits``)
    (r"layers_\d+/norm", "norm"),
    # the layer scan's own operations (``scan_layers``): a layer's slice
    # out of the stacked parameters and saved residuals, the saves' and the
    # gradients' writes back into the stacks, the stacks' zeros and copies
    # — the name stack does not tell remat's saves from the parameters
    (r"\w+\.hidden_states/while/body/"
     r"(squeeze|dynamic_slice|dynamic_update_slice|broadcast_in_dim)",
     "scan.stack"),
    (r"\w+\.hidden_states/(while|broadcast_in_dim)", "scan.stack"),
    # kernels by their ``name=`` (transforms may wrap it: jvp_, transpose_)
    (r"\w*moe\.route\w*", "moe.route"),
    (r"\w*moe\.experts_(gmm|grouped)\w*", "moe.experts"),
    (r"\w*attn\.(flash_(fwd|dq|dkv)|block_sparse_fwd|chunk_prefill|decode"
     r"|paged_decode|paged_chunk_prefill|dsa_index|dsa_lane_index|dsa_topk"
     r"|mla_chunk_prefill|mla_window|mla_sparse_decode|mla_lane_decode"
     r"|gqa_window_chunk)\w*",
     "attn.core"),
    (r"\w*attn\.eva_(decode|chunk)\w*", "attn.eva"),
    # flax modules and their methods
    (r"\w+\._eva_attend_(chunk|step)", "attn.eva"),
    (r"\w+\._kv_up", "attn.mla_decompress"),
    (r"\w+\._(chunk_full|chunk_window|attend|lanes|kept_rows)", "attn.core"),
    (r"(q|k|v|qkv|o|out)_proj|(q|k)_(layer)?norm|\w+\._(project|out|index)",
     "attn.proj"),
    (r"gate_proj|up_proj|down_proj|shared_(gate|up|down)|mlp(_\d+)?"
     r"|feed_forward", "mlp"),
    (r"moe_mlp(\.\w+)?|ExpertsMLP_\d+", "moe.experts"),
    (r"conv", "conv.short"),
    (r"final_norm|head_norm|embedding_norm|lm_head|project_out|\w+\._head",
     "head"),
    (r"embed_tokens|embed_positions|project_in", "embed"),
    (r"\w+_norm(_\d+)?", "norm"),
    # what the attention module does outside its projections: the head
    # split's reshapes and copies around the kernels and, where no kernel
    # runs (the non-flash fallback), the score and value matmuls themselves
    (r"attn(_\d+)?(\.\w+)?|self_attn(\.\w+)?", "attn.core"),
    # what a block does between its modules: the residual adds
    (r"layers(_\d+)?", "residual"),
)
_SCOPE_PARTS = tuple((re.compile(rx), rx.count("/"), part)
                     for rx, part in SCOPE_PARTS)
_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")


def phase_of(op_name):
    """``replay`` under remat's recomputed forward
    (``checkpoint/rematted_computation``), ``bwd`` under a ``transpose(``
    frame, else ``fwd``."""
    if "rematted_computation" in op_name:
        return "replay"
    return "bwd" if "transpose(" in op_name else "fwd"


def part_of(op_name):
    """``(part, phase)`` of an instruction's ``op_name`` by
    :data:`SCOPE_PARTS`; part None where no frame is in the table."""
    if not op_name:
        return None, "fwd"
    frames = op_name.split("/")
    for i in range(len(frames) - 1, -1, -1):
        for rx, parents, part in _SCOPE_PARTS:
            if rx.fullmatch("/".join(frames[max(i - parents, 0):i + 1])):
                return part, phase_of(op_name)
    return None, phase_of(op_name)


def instruction_name(event_name):
    """``%fusion.158 = bf16[...] fusion(...)`` → ``fusion.158``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def program_of(module_event):
    """``jit_train_step(1234)`` (an ``XLA Modules`` event) →
    ``("jit_train_step", 1234)``, the module's name and the program id that
    keys :func:`trace_scopes`; ``(name, None)`` where there is no id."""
    name, _, ident = module_event.rstrip(")").rpartition("(")
    return (name, int(ident)) if name and ident.isdigit() \
        else (module_event, None)


def own_time_by_instruction(events, modules, names):
    """``{(module name, program id, event name): own seconds}`` of one
    device's ``XLA Ops`` events that START inside an execution (an ``XLA
    Modules`` event) of a program whose module name is in ``names``; both
    lines as ``(name, start_s, duration_s)``.  The ops line nests — a
    ``while`` holds its body's operations — so an event's own time is its
    span less its children's."""
    runs = sorted((start, start + dur) + program_of(name)
                  for name, start, dur in modules
                  if program_of(name)[0] in names)
    starts = [r[0] for r in runs]
    own = defaultdict(float)
    stack = []                               # (end, key)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= runs[i][1]:
            continue
        key = runs[i][2:] + (name,)
        while stack and stack[-1][0] <= start + 1e-12:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        own[key] += dur
        stack.append((start + dur, key))
    return dict(own), len(runs)


def device_time_by_scope(events, modules, scopes, names, top=10):
    """THE join: own device time of ``events`` inside the executions of the
    programs named ``names`` (``("jit_train_step",)``; ``events`` and
    ``modules`` are one device's ``XLA Ops`` and ``XLA Modules`` lines,
    :func:`own_time_by_instruction`) by the part and phase of each
    instruction's ``op_name`` in ``scopes`` (:func:`trace_scopes`: ``{program
    id: {instruction name: op_name}}`` — two programs of one module name,
    two signatures of the chunk step, keep their own tables).

    Returns ``{"parts": {(part, phase): s}, "unattributed_s", "total_s",
    "top_unattributed": [("module:instruction", s, op_name or None)],
    "by_op_name": {op_name: s}, "executions"}``, None where no such program
    executes.  Unattributed: no ``op_name``, or none a row of
    :data:`SCOPE_PARTS` knows.  Nothing is lost: parts + unattributed =
    total = the programs' busy time."""
    own, executions = own_time_by_instruction(events, modules, names)
    if not executions:
        return None
    parts, by_op_name = defaultdict(float), defaultdict(float)
    loose = defaultdict(lambda: [0.0, None])
    for (module, program, event), seconds in own.items():
        name = instruction_name(event)
        op_name = scopes.get(program, {}).get(name)
        part, phase = part_of(op_name)
        opcode = _OPCODE.search(event)
        if opcode and opcode.group(1).startswith(COLLECTIVE_OPCODES):
            part = "comm"
        elif opcode and part is None \
                and opcode.group(1) in PREFETCH_OPCODES:
            part = "xla.prefetch"
        if op_name:
            by_op_name[op_name] += seconds
        if part is None:
            loose[module + ":" + name][0] += seconds
            loose[module + ":" + name][1] = op_name
        else:
            parts[(part, phase)] += seconds
    worst = sorted(loose.items(), key=lambda kv: -kv[1][0])[:top]
    return {"parts": dict(parts),
            "unattributed_s": sum(v[0] for v in loose.values()),
            "total_s": sum(own.values()),
            "top_unattributed": [(n, s, o) for n, (s, o) in worst],
            "by_op_name": dict(by_op_name), "executions": executions}


DEVICE_PLANE = "/device:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def read_device_events(path):
    """``(ops, modules)`` of the first device plane of a ``jax.profiler``
    trace (a directory or an ``.xplane.pb``) that has an ``XLA Ops`` line:
    ``[(name, start_s, duration_s)]`` each; ``([], [])`` where the trace
    holds no such plane (the CPU backend).  Needs only JAX."""
    from jax.profiler import ProfileData
    path = _find_xplane(path)
    if path is None:
        return [], []
    data = ProfileData.from_file(path)
    for plane in sorted(data.planes, key=lambda p: p.name):
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        read = lambda line: [(ev.name, ev.start_ns * 1e-9,
                              ev.duration_ns * 1e-9) for ev in line.events]
        return read(lines[OPS_LINE]), (read(lines[MODULES_LINE])
                                      if MODULES_LINE in lines else [])
    return [], []


def _find_xplane(path):
    import glob
    import os
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        return found[-1] if found else None
    return path if os.path.isfile(path) else None


def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf, start, end):
    """``(field number, wire type, value)`` of one protobuf message's
    bytes; a length-delimited value is its ``(start, end)`` in ``buf``."""
    i = start
    while i < end:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire}")
        yield number, wire, value


def _message(buf, span):
    """``{field number: [values]}`` of one protobuf message's bytes."""
    out = defaultdict(list)
    for number, _, value in _fields(buf, *span):
        out[number].append(value)
    return out


def trace_scopes(path):
    """The join's table, as the PROFILER stores it in every trace:
    ``{program id: {instruction name: op_name}}`` from the event metadata
    of the first device plane of an ``.xplane.pb`` (each ``XLA Ops``
    instruction's ``tf_op`` and ``program_id`` stats; the id is the number
    in the ``XLA Modules`` line's ``jit_train_step(<id>)``).  The
    ``op_name``s are the compiled module's own, there whether or not the
    engine that ran the program still lives, and in a trace file looked at
    by hand.  ``jax.profiler.ProfileData`` does not show metadata stats,
    so the few fields needed are read off the wire (``xplane.proto``:
    XSpace.planes = 1; XPlane name = 2, event_metadata = 4, stat_metadata
    = 5, both maps of key = 1 to value = 2; XEventMetadata display_name =
    4, stats = 5; XStatMetadata name = 2; XStat metadata_id = 1, uint64 =
    3, int64 = 4, str = 5, ref = 7).  ``{}`` where the file holds no such
    plane."""
    path = _find_xplane(path)
    if path is None:
        return {}
    with open(path, "rb") as f:
        buf = f.read()
    text = lambda span: buf[span[0]:span[1]].decode("utf-8", "replace")
    first = lambda values: values[0] if values else None
    planes = {}
    for span in _message(buf, (0, len(buf)))[1]:
        plane = _message(buf, span)
        name = text(plane[2][0]) if plane[2] else ""
        if name.startswith(DEVICE_PLANE):
            planes[name] = plane
    for _, plane in sorted(planes.items()):
        stat_names = {}
        for entry in plane[5]:
            entry = _message(buf, entry)
            label = _message(buf, entry[2][0])[2]
            stat_names[first(entry[1])] = text(label[0]) if label else None
        if "tf_op" not in stat_names.values():
            continue
        out = defaultdict(dict)
        for entry in plane[4]:
            event = _message(buf, _message(buf, entry)[2][0])
            stats = {}
            for span in event[5]:
                stat = _message(buf, span)
                stats[stat_names.get(first(stat[1]))] = (
                    text(stat[5][0]) if stat[5]
                    else stat_names.get(stat[7][0]) if stat[7]   # interned
                    else first(stat[3] or stat[4]))
            op_name, program = stats.get("tf_op"), stats.get("program_id")
            if event[4] and op_name and program is not None:
                # "<op_name>:<op_type>"
                out[program][text(event[4][0])] = \
                    op_name.rpartition(":")[0] or op_name
        return dict(out)
    return {}


def traced_device_time(path, names):
    """:func:`device_time_by_scope` of a finished trace (a directory or an
    ``.xplane.pb``) for the programs named ``names``; None where the trace
    has no device plane or no execution of them."""
    ops, modules = read_device_events(path)
    if not ops:
        return None
    return device_time_by_scope(ops, modules, trace_scopes(path), names)


def format_device_time(dt):
    """The by-part table: one row a part, a column a phase, ms per
    execution where ``executions`` is known."""
    per = 1e3 / max(dt.get("executions", 1), 1)
    rows = defaultdict(dict)
    for (part, phase), s in dt["parts"].items():
        rows[part][phase] = s
    total = dt["total_s"] or 1.0
    lines = [f"{'part':<22}" + "".join(f"{p + ' ms':>12}" for p in PHASES)
             + f"{'share':>9}"]
    for part in sorted(rows, key=lambda p: -sum(rows[p].values())):
        lines.append(
            f"{part:<22}"
            + "".join(f"{rows[part].get(p, 0.0) * per:>12.3f}" for p in PHASES)
            + f"{100.0 * sum(rows[part].values()) / total:>8.2f}%")
    lines.append(f"{'unattributed':<22}{dt['unattributed_s'] * per:>12.3f}"
                 + " " * 24
                 + f"{100.0 * dt['unattributed_s'] / total:>8.2f}%")
    for name, s, op_name in dt["top_unattributed"]:
        lines.append(f"    {s * per:>10.3f} ms  {name}  {op_name or '-'}")
    return "\n".join(lines)


def model_profile_tree(module, rngs, *args, device_time=None, **kwargs):
    """Build the per-module profile tree for a flax module.

    Structure, params and flops come from flax's module summary (the
    lowered submodules' cost analysis: nothing is compiled, nothing runs).
    Latency comes from ``device_time`` — :func:`device_time_by_scope` of
    the program that really ran (``FlopsProfiler.stop_profile``: the
    engine's fused train step) — each ``op_name``'s own device time added
    at the module its path names and at every ancestor, split by phase;
    None (no device trace: the CPU backend) leaves the column empty.

    Returns ``(root, total_latency_ps)``.  Time whose ``op_name`` names no
    module of the tree (the optimizer's update, the loss) stays at the
    root.
    """
    from flax.linen import summary as _summary
    table_fn = _summary._get_module_table(
        module, depth=None, show_repeated=True,
        compute_flops=True, compute_vjp_flops=True)
    rows = table_fn(rngs, *args, **kwargs)

    root = ModuleProfile("", type(module).__name__)
    for row in rows:
        node = root
        for part in row.path:
            node = node.child(part)
        node.module_type = type(row.module_copy).__name__
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
    if device_time is not None:
        per = 1e12 / max(device_time.get("executions", 1), 1)
        total_ps = int(device_time["total_s"] * per)
        root.latency_ps = total_ps
        for op_name, seconds in device_time["by_op_name"].items():
            ps, phase = int(seconds * per), phase_of(op_name)
            root.latency_by_phase[phase] += ps
            node = root
            for part in _scope_frames(op_name):
                child = node.children.get(part)
                if child is None:
                    if node is root:    # the class frame, ``checkpoint``
                        continue
                    break
                node = child
                node.latency_ps += ps
                node.latency_by_phase[phase] += ps
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
            ann += (f", {node.latency_ps / 1e9:.3f} ms = "
                    f"{100.0 * node.latency_ps / tot_lat:.2f}% latency ("
                    + " / ".join(
                        f"{p} {node.latency_by_phase[p] / 1e9:.3f}"
                        for p in PHASES if node.latency_by_phase[p]) + ")")
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
            out.append("    latency     - " + str(
                {n.name or n.module_type: f"{n.latency_ps / 1e9:.3f} ms"
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
