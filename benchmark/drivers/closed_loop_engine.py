"""Traffic kind ``closed_loop_engine``: offline callers that keep the engine
saturated.  ``callers`` requests are always outstanding: each caller submits
its next request in-process (``srv.submit``) the moment its last one
completes, so the queue is never empty and no front end is involved.  The
metric is the tokens the requests GAINED between two marks in time,
``ramp_s`` after the loop began and ``--seconds`` later, per second between
them (``benchmark/credit.py``): a request is credited where its tokens are
produced, one in flight at a mark is split there, and a mark that falls
inside a scheduler step reads between the step's two returns — so the number
turns neither on which requests complete just inside an edge nor on which
step happens to cross it.

Mix parameters: ``callers``, ``prompt_len``, ``output_len``, ``cycle`` (size
of the fixed multiset of request sizes), ``base_seed``, ``ramp_s`` (seconds
run before the window so that it opens on a steady state).
"""

import collections
import queue
import time

import numpy as np

from benchmark import credit, harness, serving, stats, trafficgen

# a mark is read at every step return from this many of the longest recent
# step before it: the step that crosses it then has a reading on either side
REACH = 2


class _Loop:
    """The callers' side of the loop, and what is read around a mark."""

    def __init__(self, srv, stream, clock):
        self.srv, self.stream, self.clock = srv, stream, clock
        # the engine's count of prefilled tokens: when the loop began, at
        # the step return before the last, at the last (see ``read``)
        self.prefilled = [srv.stats["prefill_tokens"]] * 3
        self.order = []         # rids, as submitted
        self.live = {}          # rid -> (prompt, max_new)
        self.sizes = []         # (len(prompt), max_new), beside order
        self.generated = {}     # rid -> tokens generated, as last read
        self.streams = {}       # rid -> its event stream, opened by a read
        self.steps = 0
        self.took = collections.deque(maxlen=32)    # recent steps' seconds
        # (step, its return's time, ``credit.progress`` then): nobody has
        # anything when the loop begins
        self.readings = [(0, clock(), [])]
        self.now = self.readings[0][1]      # the last step's return

    def submit_one(self):
        _, prompt, new = next(self.stream)
        rid = self.srv.submit(prompt, max_new_tokens=new)
        self.live[rid] = (prompt, new)
        self.order.append(rid)
        self.sizes.append((len(prompt), new))
        self.generated[rid] = 0

    def finish(self, rid, output):
        """The request left the loop; its generated tokens, or None where
        it failed.  The caller submits its next."""
        prompt, new = self.live.pop(rid)
        self.streams.pop(rid, None)
        ok = output is not None and len(output) == len(prompt) + new
        self.generated[rid] = new if ok else None
        self.submit_one()
        return (prompt, np.asarray(output[len(prompt):], np.int32)) \
            if ok else None

    def turn(self, mark):
        """One scheduler iteration and the callers' answers to it: the
        requests it completed (None for one that failed).  Within reach of
        ``mark`` the step's return is read."""
        began = self.clock()
        finished = self.srv.step()
        now = self.now = self.clock()
        self.steps += 1
        self.took.append(now - began)
        self.prefilled[1:] = self.prefilled[2], \
            self.srv.stats["prefill_tokens"]
        pairs = [self.finish(rid, out) for rid, out in finished.items()]
        if mark - now < REACH * max(self.took):
            self.readings.append((self.steps, now, self.read()))
        return pairs

    def read(self):
        """``credit.progress`` of every request submitted so far, at the
        step return just past.  A live request's generated tokens are
        counted off its event stream (the program's public read: subscribing
        replays what it has); outside a mark's reach no request is read or
        subscribed.

        Both readings are of what the DEVICE has produced by then.  A step
        dispatches its chunk rows and its decode block and returns once the
        block BEFORE has come back (and the first token of every prompt it
        finished), so at a step's return the events hold the tokens up to
        the step before, and of a prompt still in prefill the chunk rows
        the device has surely run are those counted up to the step before:
        ``stats["prefill_tokens"]`` counts at dispatch, a step ahead (the one
        number ``turn`` keeps at every step).  Read as it stands, a window
        would be credited a step's last chunks at its close for time it did
        not hold (GLM-5: 2% of a window; PERF.md section 6, PR 44)."""
        for rid in self.live:
            if rid not in self.streams:
                self.streams[rid] = self.srv.token_events(rid)
            try:
                while True:
                    ev = self.streams[rid].get(timeout=0)
                    self.generated[rid] += ev["event"] == "token"
            except queue.Empty:
                pass
        return credit.progress(
            self.sizes, [self.generated[rid] for rid in self.order],
            self.prefilled[1] - self.prefilled[0], self.srv.chunk)

    def at(self, mark):
        """``(time, progress)`` at ``mark``: between the readings at the two
        step returns around it, by the share of that step's time.  Where the
        step before was not read (a step longer than the reach) the mark
        moves to the first return past it."""
        later = next(i for i, (_, t, _) in enumerate(self.readings)
                     if t >= mark)
        step, t, progress = self.readings[later]
        if later and step - self.readings[later - 1][0] == 1 and t > mark:
            _, t_before, before = self.readings[later - 1]
            return mark, credit.between(
                before, progress, (mark - t_before) / (t - t_before))
        return t, progress


def drive(srv, stream, mix, seconds, profiler, window_started,
          clock=time.monotonic):
    """Fill the loop, run ``ramp_s``, then the window.  The ``window``
    record is of whole iterations, as ever: it opens right after the step
    that crosses ``ramp_s`` and closes right after the one that crosses
    ``seconds`` from there.  The credited tokens are those of the ``seconds``
    from ``ramp_s`` on, to the clock.  Returns the record and the requests
    completed in the window's iterations."""
    loop, done, failed = _Loop(srv, stream, clock), [], 0
    for _ in range(mix["callers"]):
        loop.submit_one()
    # ramp: compiles the admit program on first use and fills the slots
    opens = loop.readings[0][1] + mix["ramp_s"]
    closes = opens + seconds
    # both marks are held against the time of a step's RETURN, which is what
    # a reading carries: the callers' answers and a reading take host time,
    # and a loop that looked at the clock after them could pass a mark with
    # no reading past it
    while loop.now < opens:
        loop.turn(opens)
    t0 = clock()
    window_started(t0)
    stats0, occ0 = dict(srv.stats), len(srv.occupancy_trace)
    while True:
        now = clock() - t0
        if now >= seconds and loop.now >= closes:
            break
        profiler.poll(now)
        for pair in loop.turn(closes):
            if pair is None:
                failed += 1
            else:
                done.append(pair)
    window = clock() - t0
    profiler.finish()
    stats1 = dict(srv.stats)
    (opened, at_open), (closed, at_close) = loop.at(opens), loop.at(closes)
    before, inside, after = credit.split(loop.sizes, at_open, at_close)
    delta = lambda key: stats1[key] - stats0[key]
    return {
        "window_s": window, "completed": len(done), "failed": failed,
        "tokens": sum(len(p) + len(n) for p, n in done),
        "credited_tokens": inside, "credited_s": closed - opened,
        "credited_before": before, "credited_after": after,
        "decode_tokens": delta("decode_tokens"),
        "prefill_tokens": delta("prefill_tokens"),
        "iterations": delta("iterations"), "sync_secs": delta("sync_secs"),
        "paged_attention_fallback": stats1["paged_attention_fallback"],
        "occupancy": [n for _, n in srv.occupancy_trace[occ0:]]}, done


def run(ctx):
    mix = ctx.cell["traffic"]
    engine, srv = serving.build_server(ctx, tracing=False)
    stream = trafficgen.closed_loop_requests(
        mix, ctx.cell["config"]["vocab_size"], ctx.seed)
    try:
        window, done = drive(srv, stream, mix, ctx.seconds, ctx.profiler,
                             ctx.window_started)
    finally:
        srv.close()
    occupancy = window.pop("occupancy")
    harness.say(phase="window", **window)
    check = serving.check_outputs(ctx, done)
    return {
        "attempted": window["completed"] + window["failed"],
        "failed": window["failed"], "checks": [check],
        "end_to_end": {"batch_tokens_per_s": stats.rate(
            window["credited_tokens"], window["credited_s"])},
        "observed": {"occupancy": occupancy, "num_slots": srv.num_slots},
    }
