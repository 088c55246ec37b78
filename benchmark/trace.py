"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.  The reader
turns the file into plain event tuples; every reduction below works on those
tuples, so the tests check the arithmetic on hand-made events and the reader
on a small recorded trace (``benchmark/testdata``).

    python3 benchmark/trace.py <dir or .xplane.pb>     # look at a trace by hand

An event is ``(plane, line, name, start_s, duration_s)``.
"""

import glob
import os
import re
import bisect
import sys
from collections import defaultdict

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchmark import stats  # noqa: E402

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")


def short_name(hlo):
    """``%attn.158 = (...) custom-call(...)`` -> ``attn custom-call``: the
    instruction's name without its number, and its opcode.  A Pallas kernel
    (``tpu_custom_call``) reads ``<name> pallas``."""
    head, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    if not rest:
        return base
    if "tpu_custom_call" in rest:
        return base + " pallas"
    m = _OPCODE.search(rest)
    return base + (" " + m.group(1) if m else "")


def is_pallas(hlo, scope=None):
    """A Mosaic kernel's event, optionally one whose instruction is named
    after ``scope`` (the kernels carry no ``name=`` of their own, so the
    instruction takes the enclosing flax module's name, ``attn``)."""
    if "tpu_custom_call" not in hlo:
        return False
    return scope is None or hlo.lstrip("%").startswith(scope + ".") \
        or hlo.lstrip("%").startswith(scope + " ")


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_events(path):
    """Every event of the trace as plain tuples (needs only JAX)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return out


class Trace:
    """The reductions.  ``window`` is ``(start_s, end_s)`` on the trace's
    clock; by default the span of the device events."""

    def __init__(self, events, window=None):
        self.events = events
        self.device_planes = sorted({e[0] for e in events
                                     if e[0].startswith(DEVICE_PLANE)})
        ops = [e for e in events if e[0] in self.device_planes
               and e[1] == OPS_LINE]
        if window is None and ops:
            window = (min(e[3] for e in ops), max(e[3] + e[4] for e in ops))
        self.window = window
        self._ops = defaultdict(list)
        for e in ops:
            self._ops[e[0]].append(e)

    @property
    def window_s(self):
        return self.window[1] - self.window[0] if self.window else 0.0

    def _clip(self, s, d):
        lo, hi = self.window
        return max(s, lo), min(s + d, hi)

    def device_ops(self, plane=None):
        """Device operations (leaf events of the ops line) of one plane,
        default the first device."""
        return self._ops[plane or self.device_planes[0]]

    def busy_s(self):
        """Seconds in which some operation ran on the device, averaged over
        the device planes."""
        if not self.device_planes:
            return 0.0
        per = []
        for p in self.device_planes:
            iv = [self._clip(e[3], e[4]) for e in self._ops[p]]
            per.append(stats.union_seconds([(s, e) for s, e in iv if e > s]))
        return sum(per) / len(per)

    def idle_pct(self):
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def module_durations(self, substring):
        """Device durations of the executions of the jitted program whose
        module name contains ``substring`` (first device)."""
        if not self.device_planes:
            return []
        p = self.device_planes[0]
        return [e[4] for e in self.events
                if e[0] == p and e[1] == MODULES_LINE and substring in e[2]]

    def module_intervals(self, substring):
        p = self.device_planes[0] if self.device_planes else None
        return sorted((e[3], e[3] + e[4]) for e in self.events
                      if e[0] == p and e[1] == MODULES_LINE
                      and substring in e[2])

    def op_seconds(self, match, plane=None, module=None):
        """Summed device time and count of the operations whose name
        ``match`` accepts — with ``module``, only those that start inside
        an execution of the jitted program so named."""
        hits = [e for e in self.device_ops(plane) if match(e[2])]
        if module is not None:
            iv = self.module_intervals(module)
            starts = [s for s, _ in iv]
            inside = []
            for e in hits:
                i = bisect.bisect_right(starts, e[3]) - 1
                if i >= 0 and e[3] < iv[i][1]:
                    inside.append(e)
            hits = inside
        return sum(e[4] for e in hits), len(hits)

    def self_seconds(self, plane=None):
        """Device time by short name, each operation's own time only: the
        ops line nests (a ``while`` holds its body's operations), so a
        parent's time is its span less its children's."""
        by_name = defaultdict(float)
        stack = []          # (end, name, self-time accumulator index)
        for e in sorted(self.device_ops(plane), key=lambda e: (e[3], -e[4])):
            start, end = e[3], e[3] + e[4]
            while stack and stack[-1][0] <= start + 1e-12:
                stack.pop()
            if stack:
                by_name[stack[-1][1]] -= e[4]
            name = short_name(e[2])
            by_name[name] += e[4]
            stack.append((end, name))
        return by_name

    def exposed_collective_pct(self):
        """Share of the window in which a collective runs on the first
        device and no other operation does."""
        ops = self.device_ops()
        is_coll = lambda n: any(c in n for c in COLLECTIVES)
        coll = [self._clip(e[3], e[4]) for e in ops if is_coll(e[2])]
        comp = [self._clip(e[3], e[4]) for e in ops if not is_coll(e[2])]
        coll_s = stats.union_seconds([iv for iv in coll if iv[1] > iv[0]])
        both = stats.union_seconds([iv for iv in coll + comp if iv[1] > iv[0]])
        comp_s = stats.union_seconds([iv for iv in comp if iv[1] > iv[0]])
        return 100.0 * (both - comp_s) / self.window_s, coll_s

    def breakdown(self, top=10):
        """The contract's ``breakdown``: the device operations that took
        most time, and the longest idle gaps by what the host was doing
        (the host event that overlaps a gap longest)."""
        device_ops = sorted(self.self_seconds().items(),
                            key=lambda kv: -kv[1])[:top]
        iv = [(e[3], e[3] + e[4]) for e in self.device_ops()]
        idle = sorted(stats.gaps(iv, *self.window),
                      key=lambda g: g[0] - g[1])[:60]
        host = [e for e in self.events
                if not e[0].startswith("/device:") and e[4] > 2e-5]
        by_host = defaultdict(float)
        for s, t in idle:
            best, best_ov, best_len = "unattributed", 0.0, float("inf")
            for e in host:
                ov = min(t, e[3] + e[4]) - max(s, e[3])
                # the innermost (shortest) event that covers most of the gap
                if ov > best_ov * 1.001 or (ov >= 0.999 * best_ov > 0
                                            and e[4] < best_len):
                    best, best_ov, best_len = e[2][:80], ov, e[4]
            by_host[best] += t - s
        idle_gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in device_ops],
                "idle_gaps": [[n, s] for n, s in idle_gaps]}


def summarize(path, top=25):
    """What a builder looks at before writing a reader: planes, lines, and
    the names that take the time."""
    events = read_events(path)
    lines = defaultdict(lambda: [0, 0.0])
    for p, l, _, _, d in events:
        lines[(p, l)][0] += 1
        lines[(p, l)][1] += d
    print("plane | line | events | summed seconds")
    for (p, l), (n, d) in sorted(lines.items()):
        print(f"  {p} | {l} | {n} | {d:.4f}")
    tr = Trace(events)
    if tr.device_planes:
        print(f"window {tr.window_s:.4f} s, busy {tr.busy_s():.4f} s, "
              f"idle {tr.idle_pct():.1f}%")
        for line in (MODULES_LINE, OPS_LINE):
            agg = defaultdict(lambda: [0, 0.0])
            for e in events:
                if e[0] == tr.device_planes[0] and e[1] == line:
                    agg[e[2]][0] += 1
                    agg[e[2]][1] += e[4]
            print(f"top of {line} on {tr.device_planes[0]}:")
            for n, (c, d) in sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"  {d:.5f} s  x{c}  {n[:300]}")
        print("breakdown:", tr.breakdown())


if __name__ == "__main__":
    summarize(sys.argv[1])
