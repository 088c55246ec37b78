"""Concurrency contract for the serving host path — the machine-checkable
registry behind tpu-lint's TL008/TL009 rules, the
``DSTPU_CONCURRENCY_CHECKS=1`` runtime prover, and the engine-lock wait
meter (``docs/serving.md`` "Network front end", ``docs/tpu_lint.md``
"Concurrency contracts").

The serving engine is genuinely multi-threaded: one engine lock, an
owner-bound scheduler thread, condvar-blocked submits, and an asyncio
loop bridging in via ``run_in_executor``.  Every piece of mutable
scheduler state is therefore DECLARED here, exactly once, as
lock-guarded — and three independent checkers consume the declaration:

* **TL008** (static): every source read/write of a guarded field must
  sit inside a ``with self._lock`` scope or a method annotated
  ``# lock-held: _lock`` (the rule parses THIS file, it never imports
  it — the registry literals below must stay pure literals).
* **TL009** (static): ``async def`` handlers and loop callbacks must
  route calls to :data:`LOCKED_METHODS` through ``run_in_executor`` and
  must never touch :data:`OWNER_BOUND_METHODS` at all.
* **Runtime** (:func:`install_concurrency_checks`): with
  ``DSTPU_CONCURRENCY_CHECKS=1`` every guarded-field access asserts the
  engine lock is held by the current thread — the dynamic half the
  interleaving stress harness (``tools/lint/interleave_check.py``)
  drives under randomized injected yields.

Deliberately NOT in the registry (each with its reason):

* ``wake`` — a ``threading.Event``, internally synchronized.
* ``_flightrec`` — the flight recorder's ring has its OWN lock
  (``flightrec.py``): readers (``/debug/flightrec``, SIGUSR2, crash
  dumps) must never contend the engine lock, and the reference is set
  once in ``__init__``.
* ``_hist`` — the serving histograms are internally locked per bucket
  set (``monitor/trace.py``): the /metrics scrape renders them without
  the engine lock.
* ``_breaker`` — mutated only under the lock; its unlocked reads are
  single-attribute monitoring probes with no compound invariant.
* ``_lock`` / ``_cond`` — the guards themselves.
* ``FairnessTracker`` internals — the tracker has no lock of its own;
  it is reachable ONLY through the engine's ``_fairness`` attribute,
  which IS guarded, so every window read/write inherits the engine
  lock transitively (``frontend/fairness.py``).
* ``SlotPages`` internals (``paging.py``) — likewise lock-less and
  reachable only through the guarded ``_pages``; the counters it adds
  into are the engine's guarded ``stats`` dict, touched only from those
  lock-held calls.
* configuration set once in ``__init__`` and never mutated
  (``num_slots``, ``cache_len``, ``num_pages``, ``config``, ...).
"""

import os
import threading
from collections import deque

from deepspeed_tpu.monitor.trace import span

# ---------------------------------------------------------------------- #
# The registry — pure literals (tpu-lint parses this file statically)
# ---------------------------------------------------------------------- #
# class -> {field -> lock attribute that must be held to touch it}
GUARDED_FIELDS = {
    "ServingEngine": {
        # request queue + admission
        "_queue": "_lock",
        "_pending": "_lock",
        "_requests": "_lock",
        "_next_rid": "_lock",
        # host mirror of device state (the lag-one protocol)
        "_events": "_lock",
        "_slots": "_lock",
        "_free": "_lock",
        "_mirror_active": "_lock",
        "_slot_last_dispatch": "_lock",
        "_state": "_lock",
        "_cache": "_lock",
        "_rng": "_lock",
        # which pages back which slot (paging.SlotPages: pool mirror,
        # prefix index, page table, slot -> pages map and the pool
        # workspace — no lock of its own, reachable only through this
        # field)
        "_pages": "_lock",
        # speculative-decoding draft mirror (the draft KV workspace
        # handle chains dispatch-to-dispatch like _cache/_state; the
        # draft lane pool hands out admission prefill lanes one event
        # behind)
        "_draft_cache": "_lock",
        "_draft_lanes": "_lock",
        # results / lifecycle
        "_results": "_lock",
        "_pending_reports": "_lock",
        "_closed": "_lock",
        "_close_report": "_lock",
        "_it": "_lock",
        "_snap_seq": "_lock",
        "_owner_thread": "_lock",
        # token streams + fairness + counters
        "_streams": "_lock",
        "_fairness": "_lock",
        "stats": "_lock",
        "occupancy_trace": "_lock",
        # observability: the span tracer's ring is appended to at the
        # scheduler seams (lock-held) and copied whole by dump_trace()
        "_tracer": "_lock",
        # the device-memory sampler mutates its cadence/last-sample
        # state at the same scheduler seam stats is grown at (the
        # /metrics render reads its .last snapshot under the lock too)
        "_memwatch": "_lock",
    },
}

# class -> {alias lock attr -> canonical lock attr}: the engine's condvar
# wraps the engine lock, so `with self._cond:` holds `_lock` too
LOCK_ALIASES = {
    "ServingEngine": {"_cond": "_lock"},
}

# ServingEngine methods that acquire the engine lock internally — the
# thread-safe surface.  Calling one from an asyncio event-loop thread
# blocks the loop for as long as the scheduler holds the lock (a whole
# step()), so TL009 requires these to go through run_in_executor.
LOCKED_METHODS = (
    "submit", "cancel", "status", "result", "token_events", "close",
    "restore", "snapshot", "health_snapshot", "work_pending",
    "bind_owner", "release_owner",
)

# Driving methods bound to the single scheduler-owner thread — calling
# (or scheduling) one from any other context raises at runtime, so
# TL009 flags every appearance in an async handler or loop callback.
OWNER_BOUND_METHODS = ("step", "drain", "preempt")

ENV_VAR = "DSTPU_CONCURRENCY_CHECKS"


def checks_enabled():
    """True when ``DSTPU_CONCURRENCY_CHECKS`` requests the debug mode."""
    return os.environ.get(ENV_VAR, "").lower() in ("1", "true", "yes", "on")


class ConcurrencyViolation(AssertionError):
    """A guarded field was touched without its lock held — the runtime
    counterpart of a TL008 finding.  Raised at the access, so the stack
    points at the offending read/write, not at a later corruption."""


def _checked_class(base):
    """A subclass of ``base`` whose ``__getattribute__``/``__setattr__``
    assert the declared lock is held for every guarded-field access."""
    guarded = GUARDED_FIELDS["ServingEngine"]

    def _assert_held(self, name, verb):
        lock = object.__getattribute__(self, guarded[name])
        if not lock._is_owned():
            # last-gasp observability: the flight recorder (own lock —
            # safe to touch here) captures the violation and dumps the
            # ring, so the post-mortem shows what the scheduler was
            # doing when the discipline broke.  Strictly best-effort:
            # the violation must raise regardless.
            try:
                fr = object.__getattribute__(self, "_flightrec")
            except AttributeError:
                fr = None
            if fr is not None:
                try:
                    fr.record("concurrency_violation", field=name,
                              verb=verb)
                    fr.dump("concurrency_violation")
                except Exception:        # noqa: BLE001
                    pass
            raise ConcurrencyViolation(
                f"{verb} of lock-guarded field {name!r} from thread "
                f"{threading.current_thread().name!r} without holding "
                f"self.{guarded[name]} (DSTPU_CONCURRENCY_CHECKS=1; "
                f"see docs/tpu_lint.md 'Concurrency contracts')")

    class _Checked(base):
        def __getattribute__(self, name):
            if name in guarded:
                _assert_held(self, name, "read")
            return super().__getattribute__(name)

        def __setattr__(self, name, value):
            if name in guarded:
                _assert_held(self, name, "write")
            super().__setattr__(name, value)

    _Checked.__name__ = base.__name__ + "+concurrency_checks"
    _Checked.__qualname__ = _Checked.__name__
    return _Checked


_checked_cache = {}


def install_concurrency_checks(srv):
    """Flip ``srv`` (a fully-constructed :class:`ServingEngine`) into the
    held-lock-asserting debug subclass.  Idempotent; called from the
    engine's ``__init__`` tail when :func:`checks_enabled`."""
    base = type(srv)
    if getattr(base, "_dstpu_concurrency_checked", False):
        return srv
    checked = _checked_cache.get(base)
    if checked is None:
        checked = _checked_class(base)
        checked._dstpu_concurrency_checked = True
        _checked_cache[base] = checked
    srv.__class__ = checked
    return srv


class InstrumentedRLock:
    """A re-entrant lock that accounts wall time spent WAITING to acquire
    it, split by thread class (the scheduler owner vs everyone else) —
    the serving engine's lock-contention observability
    (``Serving/lock_wait_s`` monitor events,
    ``dstpu_serving_lock_wait_seconds`` in ``/metrics``, and the
    ``lock_wait_*`` percentiles in the ``serving_http`` bench phase).

    Every non-re-entrant acquire is one ``dstpu.engine.lock_wait`` span
    (``monitor/trace.py``: a profiler annotation always, a ring span on
    the waiting thread's track under tracing); the span's timing is the
    wait that is accounted.  ``last_wait_s`` is the calling thread's
    newest wait (thread-local) — how the front end books a submit's own
    lock wait.

    Accounting is mutated only AFTER a successful acquire — i.e. while
    holding the lock — so the totals need no extra synchronization.
    ``_owner_ref`` is set by the engine to a zero-arg callable returning
    the current scheduler-owner thread.  The thread class is read once,
    BEFORE the acquire (the span carries it): a single-attribute read
    that bypasses the ``DSTPU_CONCURRENCY_CHECKS`` hooks, racing only
    with the owner's own bind/release.  Delegates ``_is_owned`` /
    ``_release_save`` / ``_acquire_restore`` so ``threading.Condition``
    (the engine's blocked-submit condvar) composes; a condvar re-acquire
    after ``wait()`` counts as lock wait — that IS time the thread spent
    blocked on the lock."""

    SAMPLE_WINDOW = 4096                 # newest per-acquire waits kept

    def __init__(self):
        self._inner = threading.RLock()
        self._owner_ref = lambda: None
        self.wait_s = {"scheduler": 0.0, "handler": 0.0}
        self.acquires = {"scheduler": 0, "handler": 0}
        self.samples = {"scheduler": deque(maxlen=self.SAMPLE_WINDOW),
                        "handler": deque(maxlen=self.SAMPLE_WINDOW)}
        # optional per-acquire observer ``(thread_class, wait_s) -> None``
        # — the serving engine points it at its lock-wait histogram
        # under ``serving.tracing``.  Called lock-HELD (right after a
        # successful acquire) and must be internally synchronized and
        # non-raising; exceptions are swallowed so a broken observer
        # can never poison the lock.
        self.on_wait = None
        self._local = threading.local()

    @property
    def last_wait_s(self):
        """The calling thread's newest non-re-entrant acquire wait."""
        return getattr(self._local, "wait_s", 0.0)

    def _wait_span(self):
        cls = ("scheduler"
               if threading.current_thread() is self._owner_ref()
               else "handler")
        return span("dstpu.engine.lock_wait", cat="lock",
                    track="scheduler" if cls == "scheduler" else None,
                    thread_class=cls)

    def _account(self, sp):
        cls, dt = sp.args["thread_class"], sp.dur_s
        self._local.wait_s = dt
        self.wait_s[cls] += dt
        self.acquires[cls] += 1
        self.samples[cls].append(dt)
        cb = self.on_wait
        if cb is not None:
            try:
                cb(cls, dt)
            except Exception:            # noqa: BLE001 — observer only
                pass

    def acquire(self, blocking=True, timeout=-1):
        if self._inner._is_owned():
            # re-entrant acquire: cannot wait by definition — keep it
            # out of the samples so the wait percentiles measure real
            # contention, not the locked monitoring properties
            # re-entering from an already-locked caller
            return self._inner.acquire(blocking, timeout)
        with self._wait_span() as sp:
            ok = self._inner.acquire(blocking, timeout)
        if ok:
            self._account(sp)
        return ok

    def release(self):
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    # ---- threading.Condition integration ----
    def _is_owned(self):
        return self._inner._is_owned()

    def _release_save(self):
        return self._inner._release_save()

    def _acquire_restore(self, state):
        with self._wait_span() as sp:
            self._inner._acquire_restore(state)
        self._account(sp)


__all__ = ["GUARDED_FIELDS", "LOCK_ALIASES", "LOCKED_METHODS",
           "OWNER_BOUND_METHODS", "checks_enabled",
           "ConcurrencyViolation", "install_concurrency_checks",
           "InstrumentedRLock"]
