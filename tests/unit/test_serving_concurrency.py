"""Concurrency-contract tests for the serving host path
(``docs/tpu_lint.md`` "Concurrency contracts", ``docs/serving.md``
"Network front end").

The acceptance contract: the interleaving stress harness drives
concurrent submit/cancel/status/token_events/metrics traffic against a
stepping scheduler with randomized injected yields at the named lock
seams under ``DSTPU_CONCURRENCY_CHECKS=1`` and proves bitwise-identical
serving outputs, exactly one terminal status per request and ZERO
guarded-field assertion trips; a cancel racing the mirror drain's
retirement of the same rid resolves to exactly one terminal record; the
runtime checker actually trips on an unlocked guarded access; and the
engine-lock wait meter feeds ``stats`` and ``/metrics``."""

import threading
import time
from collections import defaultdict

import numpy as np
import pytest

from deepspeed_tpu.inference.serving.concurrency import (
    ConcurrencyViolation, GUARDED_FIELDS, InstrumentedRLock)
from deepspeed_tpu.runtime.fault import inject
from deepspeed_tpu.tools.lint.interleave_check import (
    _tiny_served_engine, run_interleave_check)


@pytest.fixture(scope="module")
def shared_engine():
    return _tiny_served_engine()


# ------------------------------------------------------------------ #
# The tentpole prover: rule + harness pairing (tier-1)
# ------------------------------------------------------------------ #
def test_interleaving_stress_harness():
    """Randomized-seed yields at every lock seam; bitwise outputs,
    single terminal statuses, zero assertion trips (the harness runs
    its engines under DSTPU_CONCURRENCY_CHECKS=1)."""
    result = run_interleave_check(seeds=(0, 1))
    assert result["ok"], "\n".join(result["problems"])
    for seed, rep in result["per_seed"].items():
        assert rep["completed"] == 6, (seed, rep)
        # the harness generates real contention — the meter must see it
        assert rep["lock_acquires"]["handler"] > 0, rep


def test_runtime_checks_trip_on_unlocked_access(shared_engine,
                                                monkeypatch):
    """The dynamic half of TL008: with checks armed, touching a guarded
    field without the lock raises at the access; the same touch under
    the lock (and the whole public surface) works."""
    monkeypatch.setenv("DSTPU_CONCURRENCY_CHECKS", "1")
    srv = shared_engine.serve()
    assert type(srv).__name__.endswith("+concurrency_checks")
    with pytest.raises(ConcurrencyViolation, match="_queue"):
        srv._queue
    with pytest.raises(ConcurrencyViolation, match="stats"):
        srv.stats["completed"] = 999
    with srv._lock:
        assert len(srv._queue) == 0
    rid = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
    out = srv.drain()
    assert rid in out and srv.result(rid).status == "COMPLETED"
    assert sorted(srv.close()) == []


def test_checks_off_by_default(shared_engine, monkeypatch):
    monkeypatch.delenv("DSTPU_CONCURRENCY_CHECKS", raising=False)
    srv = shared_engine.serve()
    assert not type(srv).__name__.endswith("+concurrency_checks")
    srv._queue                           # plain engine: no assertion
    srv.close()


def test_registry_matches_engine_fields(shared_engine):
    """Every registry field must exist on a live engine — a renamed
    field with a stale registry entry would silently uncheck it."""
    spec_only = {"_draft_cache", "_draft_lanes"}
    srv = shared_engine.serve()
    with srv._lock:
        for field in GUARDED_FIELDS["ServingEngine"]:
            if field in spec_only and not srv.speculative:
                continue
            assert hasattr(srv, field), \
                f"registry field {field!r} missing on ServingEngine"
    srv.close()


# ------------------------------------------------------------------ #
# Satellite: cancel-vs-retire race (exactly one terminal status)
# ------------------------------------------------------------------ #
def test_cancel_vs_retire_race_single_terminal(shared_engine,
                                               monkeypatch):
    """cancel(rid) from a non-owner thread in the same window the
    scheduler's mirror drain retires that rid: exactly one terminal
    transition (no double _record_terminal, no KeyError), status
    COMPLETED xor CANCELLED — under DSTPU_CONCURRENCY_CHECKS=1 with a
    yield stretching the retirement window."""
    monkeypatch.setenv("DSTPU_CONCURRENCY_CHECKS", "1")
    srv = shared_engine.serve()
    terminal_counts = defaultdict(int)
    orig_rt, orig_fin = srv._record_terminal, srv._finalize

    def counting_rt(req, status, detail):
        terminal_counts[req.rid] += 1
        return orig_rt(req, status, detail)

    def counting_fin(req):
        terminal_counts[req.rid] += 1
        return orig_fin(req)

    srv._record_terminal = counting_rt
    srv._finalize = counting_fin
    inject.reset_injection()
    inject.configure_injection([{"point": "serving.mirror_drain",
                                 "action": "yield", "at": 1, "times": 0,
                                 "seconds": 0.002, "seed": 42}])
    rng = np.random.default_rng(0)
    errors = []
    try:
        for trial in range(25):
            prompt = rng.integers(1, 97, (8,)).astype(np.int32)
            rid = srv.submit(prompt, max_new_tokens=3)
            delay = float(rng.random()) * 0.02

            def cancel_late(rid=rid, delay=delay):
                try:
                    time.sleep(delay)
                    srv.cancel(rid)      # False when retire won the race
                except Exception as e:   # noqa: BLE001 — KeyError = bug
                    errors.append(f"trial {trial}: {type(e).__name__}: "
                                  f"{e}")

            t = threading.Thread(target=cancel_late)
            t.start()
            deadline = time.monotonic() + 60
            while srv.status(rid) not in ("COMPLETED", "CANCELLED") \
                    and time.monotonic() < deadline:
                srv.step()
            t.join(timeout=30)
            status = srv.status(rid)
            assert status in ("COMPLETED", "CANCELLED"), status
            assert terminal_counts[rid] == 1, \
                f"trial {trial}: rid {rid} recorded " \
                f"{terminal_counts[rid]} terminal transitions ({status})"
            assert srv.result(rid) is not None
        assert not errors, errors
    finally:
        inject.reset_injection()
        srv.close()


# ------------------------------------------------------------------ #
# Satellite: lock-contention observability
# ------------------------------------------------------------------ #
def test_lock_wait_observability(shared_engine):
    """Wall time a handler thread spends blocked on the engine lock
    lands in the meter, in stats after the next step, and as labeled
    ``dstpu_serving_lock_wait_seconds`` lines in /metrics."""
    srv = shared_engine.serve()
    rid = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
    held = threading.Event()

    def contender():
        held.wait(timeout=10)
        srv.status(rid)                  # blocks while we hold the lock

    t = threading.Thread(target=contender)
    t.start()
    with srv._lock:
        held.set()
        time.sleep(0.05)                 # the contender waits this out
    t.join(timeout=10)
    assert srv._lock.wait_s["handler"] >= 0.04
    srv.drain()                          # a step refreshes the stats copy
    assert srv.stats["lock_wait_handler_s"] >= 0.04
    assert srv.stats["lock_wait_scheduler_s"] >= 0.0

    from deepspeed_tpu.inference.serving.frontend.transport import \
        ServingHTTPFrontend
    body = ServingHTTPFrontend(srv)._metrics_body().decode()
    assert 'dstpu_serving_lock_wait_seconds{thread_class="handler"}' \
        in body
    assert 'dstpu_serving_lock_wait_seconds{thread_class="scheduler"}' \
        in body
    assert "dstpu_serving_lock_wait_handler_s" in body  # stats export
    srv.close()


def test_instrumented_rlock_condition_compat():
    """The meter composes with threading.Condition (the blocked-submit
    condvar): wait/notify round-trips and the re-acquire after wait()
    counts as lock wait."""
    lock = InstrumentedRLock()
    cond = threading.Condition(lock)
    hits = []

    def waiter():
        with lock:
            cond.wait(timeout=5)
            hits.append(True)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with lock:
        cond.notify_all()
    t.join(timeout=10)
    assert hits == [True]
    assert not lock._is_owned()
    assert sum(lock.acquires.values()) >= 3


# ------------------------------------------------------------------ #
# Satellite: TokenStream bridge drops are counted and logged
# ------------------------------------------------------------------ #
def test_stream_bridge_drop_counted_in_stats(shared_engine):
    srv = shared_engine.serve()
    rid = srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)

    def dead_bridge(ev):
        raise RuntimeError("Event loop is closed")

    stream = srv.token_events(rid, on_event=dead_bridge)
    srv.drain()
    assert srv.stats["stream_bridge_drops"] == 1, \
        "dropped bridge must be counted exactly once"
    toks, end = stream.tokens(timeout=10)
    assert end["status"] == "COMPLETED" and len(toks) == 4
    srv.close()


# ------------------------------------------------------------------ #
# health_snapshot: the locked /healthz view
# ------------------------------------------------------------------ #
def test_health_snapshot_locked_view(shared_engine):
    srv = shared_engine.serve()
    snap = srv.health_snapshot()
    assert snap["closed"] is False and snap["queue_depth"] == 0
    assert snap["num_slots"] == srv.num_slots
    assert snap["breaker"]["open"] is False
    srv.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=2)
    assert srv.health_snapshot()["queue_depth"] == 1
    srv.drain()
    srv.close()
    assert srv.health_snapshot()["closed"] is True
