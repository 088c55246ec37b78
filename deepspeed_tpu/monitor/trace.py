"""Host-side span tracing + fixed-bucket latency histograms for the
serving stack (``docs/observability.md``) — the per-request half of the
monitor layer the reference framework ships as ``deepspeed/monitor/``.

Three primitives, all pure host bookkeeping (zero jitted programs, zero
device syncs — the overhead contract the serving engine's
zero-new-executables proof extends over them):

* :func:`span` — THE way to mark host work, in every layer.  A context
  manager that always enters a ``jax.profiler.TraceAnnotation`` (with a
  profiler session open the span lands in the ``.xplane.pb`` on the
  device trace's own clock, its keyword arguments as event stats; with
  none it is a flag test) and, when the process tracer is on
  (:func:`enable`; ``serving.tracing`` turns it on), also appends the
  span to the :class:`SpanTracer` ring.  Names are the fixed literal
  set ``dstpu.<layer>.<what>`` tabled in ``docs/observability.md`` —
  never an id or a size in a name; those are args.  Spans of category
  ``"setup"`` (the entry points' ``dstpu.setup.*``: a few dozen a
  process, over before anyone could turn the ring on) are ALWAYS kept,
  in a small list of this module's own that :func:`setup_spans` reads.
* :class:`SpanTracer` — a bounded ring of finished spans recorded at the
  serving scheduler's existing seams (submit → queue wait → prefill
  chunks → admit dispatch → decode / spec-propose / spec-verify
  dispatches → terminal), each stamped with BOTH the monotonic clock
  (durations, breakdowns) and the wall clock (cross-process
  correlation).  :meth:`SpanTracer.to_chrome` renders the ring as
  Chrome trace-event JSON (the ``traceEvents`` array of ``"X"``
  complete events plus ``"M"`` thread-name metadata), loadable in
  Perfetto / ``chrome://tracing`` with one track per KV slot plus
  scheduler/queue/handler tracks.
* :class:`Histogram` / :class:`HistogramFamily` /
  :class:`ServingHistograms` — fixed-bucket Prometheus histograms
  (cumulative ``_bucket{le=...}`` counts, ``_sum``, ``_count``) for
  TTFT, time-between-tokens, queue wait, per-program dispatch duration
  and engine-lock wait.  Buckets are FIXED at construction so the
  exposition never allocates on the observe path; ``observe`` takes a
  plain ``threading.Lock`` (never the engine lock — the hot path must
  not contend it).

The tracer's clock is injectable (``clock=``) so tests can drive TTFT /
TBT measurement deterministically; timestamps are stamped ONCE at the
host-mirror drain point, so a late-attached ``TokenStream`` replay can
never re-stamp them and skew the histograms.
"""

import json
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

# Default span-ring bound: ~7 spans per request-lifetime plus 1-3 per
# dispatch; 100k spans ≈ tens of MB and hours of light traffic.
DEFAULT_MAX_SPANS = 100_000

# Latency bucket bounds (seconds) — shared by the TTFT / TBT /
# queue-wait / dispatch-duration histograms.  Fixed so dashboards can
# diff rounds; spans sub-ms host dispatch up to the 60 s shed horizon.
LATENCY_BUCKETS_S = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)

# Lock-wait buckets (seconds) — contention lives orders of magnitude
# below request latency; the 1 µs floor resolves uncontended acquires.
LOCK_WAIT_BUCKETS_S = (1e-6, 1e-5, 1e-4, 1e-3, 0.01, 0.1, 1.0)

# The histogram series the serving engine exports through ``/metrics``
# when ``serving.tracing`` is on.  PURE LITERAL: ``ds_lint
# --stats-docs`` parses this tuple statically (never imports the
# module) to assert every series is documented in
# ``docs/observability.md``.
HISTOGRAM_SERIES = (
    "dstpu_serving_ttft_seconds",
    "dstpu_serving_tbt_seconds",
    "dstpu_serving_queue_wait_seconds",
    "dstpu_serving_dispatch_seconds",
    "dstpu_serving_lock_acquire_wait_seconds",
)


class Histogram:
    """One fixed-bucket Prometheus histogram.  ``observe`` is safe from
    any thread (its own tiny lock, never the engine lock); ``collect``
    returns the cumulative exposition samples."""

    __slots__ = ("buckets", "counts", "sum", "count", "_lock")

    def __init__(self, buckets):
        self.buckets = tuple(float(b) for b in buckets)
        assert list(self.buckets) == sorted(self.buckets), \
            "histogram buckets must be ascending"
        self.counts = [0] * len(self.buckets)     # per-bucket (not cum.)
        self.sum = 0.0
        self.count = 0
        self._lock = threading.Lock()

    def observe(self, v):
        v = float(v)
        with self._lock:
            self.sum += v
            self.count += 1
            for i, b in enumerate(self.buckets):
                if v <= b:
                    self.counts[i] += 1
                    break

    def collect(self, labels=None):
        """``[(suffix, extra_labels, value), ...]`` exposition samples —
        cumulative ``_bucket`` counts (incl. ``+Inf``), ``_sum``,
        ``_count``.  ``labels``: dict merged into every sample."""
        base = dict(labels or {})
        with self._lock:
            counts = list(self.counts)
            total, s = self.count, self.sum
        out, cum = [], 0
        for b, c in zip(self.buckets, counts):
            cum += c
            out.append(("_bucket", {**base, "le": repr(b)}, cum))
        out.append(("_bucket", {**base, "le": "+Inf"}, total))
        out.append(("_sum", base, s))
        out.append(("_count", base, total))
        return out

    def snapshot(self):
        with self._lock:
            return {"count": self.count, "sum": self.sum,
                    "counts": list(self.counts)}


class HistogramFamily:
    """Same-bucket histograms keyed by one label value (e.g. the
    dispatch program name).  Children are created lazily under the
    family lock; each child observes under its own."""

    def __init__(self, label, buckets):
        self.label = label
        self.buckets = tuple(buckets)
        self._children = {}
        self._lock = threading.Lock()

    def child(self, value):
        value = str(value)
        h = self._children.get(value)
        if h is None:
            with self._lock:
                h = self._children.setdefault(value,
                                              Histogram(self.buckets))
        return h

    def observe(self, value, v):
        self.child(value).observe(v)

    def collect(self):
        with self._lock:
            items = sorted(self._children.items())
        out = []
        for value, h in items:
            out.extend(h.collect(labels={self.label: value}))
        return out


class ServingHistograms:
    """The serving engine's histogram set (``serving.tracing``),
    exported through ``/metrics`` as the :data:`HISTOGRAM_SERIES`
    families.  All internally locked — the HTTP scrape thread never
    takes the engine lock to render them."""

    def __init__(self):
        self.ttft = Histogram(LATENCY_BUCKETS_S)
        self.tbt = Histogram(LATENCY_BUCKETS_S)
        self.queue_wait = Histogram(LATENCY_BUCKETS_S)
        self.dispatch = HistogramFamily("program", LATENCY_BUCKETS_S)
        self.lock_wait = HistogramFamily("thread_class",
                                         LOCK_WAIT_BUCKETS_S)

    def collect(self):
        """``[(series_name, help, samples), ...]`` for the Prometheus
        renderer; ``samples`` are ``(suffix, labels, value)``."""
        return [
            ("dstpu_serving_ttft_seconds",
             "submit-to-first-token wall time per request",
             self.ttft.collect()),
            ("dstpu_serving_tbt_seconds",
             "time between consecutive committed tokens, per request",
             self.tbt.collect()),
            ("dstpu_serving_queue_wait_seconds",
             "submit-to-admission-start wait per request",
             self.queue_wait.collect()),
            ("dstpu_serving_dispatch_seconds",
             "host dispatch duration per program",
             self.dispatch.collect()),
            ("dstpu_serving_lock_acquire_wait_seconds",
             "per-acquire engine-lock wait by thread class",
             self.lock_wait.collect()),
        ]


class SpanTracer:
    """Bounded ring of finished spans with Chrome trace-event export.

    ``add`` records one complete span (``t1=None`` = instant event);
    timestamps come from :meth:`now` — the injectable monotonic clock —
    and the wall-clock epoch of the tracer's construction anchors the
    export.  ``add`` and ``span_snapshot`` are safe from any thread
    (a tiny lock of the ring's own, never the engine lock: front-end
    threads record here too); ``to_chrome``/``dump`` take a
    point-in-time copy."""

    def __init__(self, max_spans=DEFAULT_MAX_SPANS, clock=time.monotonic,
                 wallclock=time.time):
        self._clock = clock
        self._t0 = clock()               # monotonic epoch
        self.wall_t0 = wallclock()       # wall-clock anchor of _t0
        self._spans = deque(maxlen=int(max_spans))
        self._ring_lock = threading.Lock()
        self.added = 0                   # total, incl. ring-dropped

    def now(self):
        """The tracer's monotonic clock (injectable for tests)."""
        return self._clock()

    def add(self, name, cat, t0, t1=None, track="scheduler", **args):
        """Record one finished span: ``[t0, t1]`` on ``track`` (a slot
        id int or a named thread track), with ``args`` attached
        (rid/client_id/slot/priority/phase...).  ``None`` args are
        dropped so exports stay compact."""
        rec = (name, cat, float(t0), None if t1 is None else float(t1),
               track, {k: v for k, v in args.items() if v is not None})
        with self._ring_lock:
            self.added += 1
            self._spans.append(rec)

    @property
    def dropped(self):
        return self.added - len(self._spans)

    def span_snapshot(self):
        """A point-in-time ``(spans, added)`` copy of the span ring;
        render/serialize it OUTSIDE any lock:
        :meth:`to_chrome`/:meth:`dump` on a 100k-span ring build tens
        of MB of JSON, far too long to stall the scheduler for.  The
        paired ``added`` counter keeps the export's ``dropped`` figure
        consistent with the copy: spans recorded AFTER the snapshot
        must not read as ring-dropped."""
        with self._ring_lock:
            return list(self._spans), self.added

    def to_chrome(self, spans=None):
        """The Chrome trace-event JSON object (``{"traceEvents": [...]}``
        — the Perfetto-loadable format): one ``pid``, a ``tid`` per
        track (scheduler / queue / handler threads, then one per slot),
        ``"X"`` complete events in microseconds, ``"M"`` thread-name
        metadata, and the wall-clock anchor under ``otherData``.
        ``spans``: a :meth:`span_snapshot` tuple; ``None`` copies the
        live ring."""
        spans, added = self.span_snapshot() if spans is None else spans
        tids, events = {}, []

        def tid_for(track):
            t = tids.get(track)
            if t is None:
                t = tids[track] = len(tids)
                name = f"slot {track}" if isinstance(track, int) \
                    else str(track)
                events.append({"ph": "M", "pid": 1, "tid": t,
                               "name": "thread_name",
                               "args": {"name": name}})
            return t

        # stable track order: the named threads first, slots ascending
        for track in ("scheduler", "queue"):
            tid_for(track)
        for track in sorted({s[4] for s in spans
                             if isinstance(s[4], int)}):
            tid_for(track)
        for name, cat, t0, t1, track, args in spans:
            ev = {"name": name, "cat": cat, "pid": 1,
                  "tid": tid_for(track),
                  "ts": round((t0 - self._t0) * 1e6, 3)}
            if t1 is None:
                ev["ph"] = "i"
                ev["s"] = "t"            # thread-scoped instant
            else:
                ev["ph"] = "X"
                ev["dur"] = round(max(t1 - t0, 0.0) * 1e6, 3)
            if args:
                ev["args"] = args
            events.append(ev)
        return {"traceEvents": events,
                "displayTimeUnit": "ms",
                "otherData": {"wall_t0": self.wall_t0,
                              "spans": len(spans),
                              "dropped": added - len(spans)}}

    def dump(self, path, spans=None):
        """Write :meth:`to_chrome` to ``path``; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(spans=spans), f)
        return path


# ---------------------------------------------------------------------- #
# The process's one tracer, and the one span helper
# ---------------------------------------------------------------------- #
_TRACER = None                           # None = the ring is off

# Finished spans of category "setup", kept whether or not the ring is on:
# an engine's start-up is ~20 of them, so a few hundred hold every engine
# a process builds (the oldest fall off)
SETUP_SPANS_KEPT = 512
_SETUP = deque(maxlen=SETUP_SPANS_KEPT)
_SETUP_LOCK = threading.Lock()
# the package's import span, once it is recorded: a process that builds
# many engines pushes it off the list above, and every ``ready`` line
# begins with it
_IMPORT = []
_SETUP_OPEN = threading.local()          # .names: this thread's open ones


def enable(max_spans=DEFAULT_MAX_SPANS, **clocks):
    """Turn the span ring on: install a FRESH :class:`SpanTracer` as the
    process's one tracer and return it.  The serving engine calls this
    under ``serving.tracing``; the tracer stays installed — and
    readable through :func:`tracer` — after that engine is closed."""
    global _TRACER
    _TRACER = SpanTracer(max_spans, **clocks)
    return _TRACER


def disable():
    """Turn the ring off and drop it (:func:`tracer` gives ``None``)."""
    global _TRACER
    _TRACER = None


def tracer():
    """The process's tracer, or ``None`` while the ring is off."""
    return _TRACER


def now():
    """The clock spans are stamped on: the tracer's (injectable) clock
    while the ring is on, else ``time.monotonic``."""
    tr = _TRACER
    return tr.now() if tr is not None else time.monotonic()


def setup_spans():
    """The finished ``cat="setup"`` spans of this process, oldest first,
    as ``[(name, t0, t1, track, args)]`` — a copy.  They are kept with
    the ring off (``time.monotonic`` stamps then), the newest
    :data:`SETUP_SPANS_KEPT` of them."""
    with _SETUP_LOCK:
        return list(_SETUP)


def ready_line(what):
    """The one line an engine logs when a warm-up ends (``ready[<what>]:
    ...``; ``docs/observability.md`` "Start-up"): of the set-up spans since
    the newest ``dstpu.setup.engine`` opened (and the package's import),
    each phase's OWN seconds — its spans' time less the spans nested in
    them — and each program's compile seconds with where the executable
    came from: ``store`` (the executable store), ``cache`` (every request
    to the persistent cache hit) or ``miss`` (compiled)."""
    spans = setup_spans()
    built = [t0 for name, t0, *_ in spans if name == "dstpu.setup.engine"]
    since = built[-1] if built else float("-inf")
    imported = [s for s in spans if s[0] == "dstpu.setup.import"] or _IMPORT
    spans = sorted(imported + [s for s in spans if s[1] >= since
                               and s[0] != "dstpu.setup.import"],
                   key=lambda s: (s[1], -s[2]))
    own, programs, open_ = {}, {}, {}    # open_: track -> [(t1, name)]
    for name, t0, t1, track, args in spans:
        stack = open_.setdefault(track, [])
        while stack and stack[-1][0] <= t0:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= t1 - t0
        own[name] = own.get(name, 0.0) + t1 - t0
        stack.append((t1, name))
        if name == "dstpu.setup.compile":
            asked, hits = args["persistent_requests"], args["persistent_hits"]
            how = "store" if args["store_hit"] else \
                "cache" if asked and hits == asked else "miss"
            seconds, hows = programs.get(args["program"], (0.0, ()))
            programs[args["program"]] = (
                seconds + t1 - t0, hows if how in hows else hows + (how,))
    phases = ", ".join(f"{name.rpartition('.')[2]} {seconds:.1f}s"
                       for name, seconds in own.items()
                       if name != "dstpu.setup.compile")
    compiles = ", ".join(f"{p} {seconds:.1f}s ({'/'.join(hows)})"
                         for p, (seconds, hows) in programs.items())
    return f"ready[{what}]: {phases}; compiled: {compiles or 'nothing'}"


def setup_open(name):
    """Whether the calling thread is inside a ``cat="setup"`` span called
    ``name`` (a compile asks whether its engine's warm-up is what runs
    it)."""
    return name in getattr(_SETUP_OPEN, "names", ())


class span:
    """``with span("dstpu.sched.step", it=3):`` — one host span, on the
    profiler's clock always and in the ring when it is on (module
    docstring).  ``track`` is the ring's track (default: the calling
    thread's name, so nested spans nest by thread); ``args`` become the
    annotation's stats and the ring span's args (``None`` values are
    dropped).  :meth:`set` adds args learned inside the span.  ``t0`` /
    ``t1`` / ``dur_s`` are stamped on the tracer's clock when the ring
    is on, else ``time.monotonic`` — ONE timing a seam can hand on to
    a histogram or the flight recorder.  ``start`` (a :func:`now`
    stamp made earlier, e.g. on another thread at a hand-off) moves
    ``t0`` — and so the ring span's start — back to that stamp; the
    annotation still covers only the ``with`` block.  ``cat="setup"``
    keeps the finished span for :func:`setup_spans` too, ring or no ring:
    never on a path that runs per iteration, step, layer or request."""

    __slots__ = ("name", "track", "cat", "args", "t0", "t1", "_ann", "_tr")

    def __init__(self, name, track=None, cat="span", start=None, **args):
        self.name, self.track, self.cat = name, track, cat
        self.args = {k: v for k, v in args.items() if v is not None}
        self.t0, self.t1 = start, None

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        tr = self._tr = _TRACER          # one tracer, one clock, per span
        if self.t0 is None:
            self.t0 = tr.now() if tr is not None else time.monotonic()
        if self.cat == "setup":
            if not hasattr(_SETUP_OPEN, "names"):
                _SETUP_OPEN.names = []
            _SETUP_OPEN.names.append(self.name)
        return self

    def set(self, **args):
        args = {k: v for k, v in args.items() if v is not None}
        self.args.update(args)
        self._ann.set_metadata(**args)

    @property
    def dur_s(self):
        return self.t1 - self.t0

    def __exit__(self, *exc):
        tr = self._tr
        self.t1 = tr.now() if tr is not None else time.monotonic()
        self._ann.__exit__(*exc)
        if self.cat == "setup":
            _SETUP_OPEN.names.pop()
            # a serving engine turns the ring on inside its own set-up
            # span: what opened before lands in the ring it finds at exit
            tr = tr if tr is not None else _TRACER
        elif tr is None:
            return False
        track = self.track if self.track is not None \
            else threading.current_thread().name
        if self.cat == "setup":
            kept = (self.name, self.t0, self.t1, track, dict(self.args))
            with _SETUP_LOCK:
                _SETUP.append(kept)
                if self.name == "dstpu.setup.import":
                    _IMPORT[:] = [kept]
        if tr is not None:
            tr.add(self.name, self.cat, self.t0, self.t1, track=track,
                   **self.args)
        return False


__all__ = ["SpanTracer", "span", "now", "tracer", "enable", "disable",
           "setup_spans", "setup_open", "ready_line", "SETUP_SPANS_KEPT",
           "Histogram", "HistogramFamily",
           "ServingHistograms", "LATENCY_BUCKETS_S",
           "LOCK_WAIT_BUCKETS_S", "HISTOGRAM_SERIES",
           "DEFAULT_MAX_SPANS"]
