"""The traffic generator, the load generator's clock, and the arithmetic
between samples and metrics."""

import json
import os
import queue
import socket
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from benchmark import credit, spec, stats, trafficgen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = spec.Benchmark(ROOT)
CHAT = BENCH.cell("opt13b-serve-chat")["traffic"]
BATCH = BENCH.cell("opt13b-serve-longprompt-batch")["traffic"]
TRAIN = BENCH.cell("opt13b-sft-1chip")["traffic"]
BIG = 3000000019            # more than 32 signed bits hold


def test_open_loop_schedule_is_deterministic_in_the_seed():
    a = trafficgen.open_loop_schedule(CHAT, 50272, 10.0, BIG)
    b = trafficgen.open_loop_schedule(CHAT, 50272, 10.0, BIG)
    assert a == b
    c = trafficgen.open_loop_schedule(CHAT, 50272, 10.0, BIG + 1)
    assert [r["input_ids"] for r in a] != [r["input_ids"] for r in c]


def test_every_seed_gets_the_same_work():
    """Every run replays one schedule of sizes and arrivals; the seed draws
    the tokens."""
    a = trafficgen.open_loop_schedule(CHAT, 50272, 10.0, 1)
    b = trafficgen.open_loop_schedule(CHAT, 50272, 10.0, 2)
    shape = lambda s: [(r["due_s"], len(r["input_ids"]), r["max_new_tokens"])
                       for r in s]
    assert shape(a) == shape(b) and len(a) == round(CHAT["rate_per_s"] * 10)
    assert all(0 <= r["due_s"] < 10.0 for r in a)
    assert sorted(r["due_s"] for r in a) == [r["due_s"] for r in a]
    assert a[0]["input_ids"] != b[0]["input_ids"]


def test_lengths_respect_the_mix():
    sizes = trafficgen.sizes(CHAT, 400)
    p, o = np.array(sizes).T
    assert p.min() >= 32 and p.max() <= 1024
    assert o.min() >= 16 and o.max() <= 384
    assert 200 <= np.median(p) <= 320 and 100 <= np.median(o) <= 160
    sizes = trafficgen.sizes(BATCH, 64)
    p, o = np.array(sizes).T
    assert p.min() >= 1024 and p.max() <= 1792
    assert o.min() >= 32 and o.max() <= 64


def test_closed_loop_stream_and_train_batches_are_seeded():
    take = lambda g, n: [next(g) for _ in range(n)]
    a = take(trafficgen.closed_loop_requests(BATCH, 50272, 5), 70)
    b = take(trafficgen.closed_loop_requests(BATCH, 50272, 5), 70)
    assert all(x[0] == y[0] and (x[1] == y[1]).all() and x[2] == y[2]
               for x, y in zip(a, b))
    c = take(trafficgen.closed_loop_requests(BATCH, 50272, 6), 70)
    assert any((x[1][:8] != y[1][:8]).any() for x, y in zip(a, c))
    # every seed, and every cycle of 64, is the same sizes in the same order
    shape = lambda reqs: [(len(x[1]), x[2]) for x in reqs]
    assert shape(a) == shape(c) and shape(a[:6]) == shape(a[64:70])
    x = next(trafficgen.train_batches(TRAIN, 50272, 2, BIG))
    y = next(trafficgen.train_batches(TRAIN, 50272, 2, BIG))
    assert x.shape == (2, 2048) and (x == y).all()
    assert len(np.unique(x)) <= TRAIN["support"]


# --------------------------------------------------------------------- #
class _StallingServer:
    """A fake server: one worker answers requests in order, 20 ms each, and
    stalls once for ``stall_s`` — so open-loop latency, timed from the due
    time, must show the stall in the requests queued behind it."""

    def __init__(self, stall_at, stall_s):
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.port = self.sock.getsockname()[1]
        self.stall_at, self.stall_s, self.served = stall_at, stall_s, 0
        self.lock = threading.Lock()
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        data = b""
        while b"\r\n\r\n" not in data:
            data += conn.recv(65536)
        with self.lock:                      # one request at a time
            self.served += 1
            if self.served == self.stall_at:
                time.sleep(self.stall_s)
            time.sleep(0.02)
            rid = self.served
        events = [{"event": "token", "rid": rid, "index": 0, "token": 7},
                  {"event": "end", "rid": rid, "status": "COMPLETED",
                   "detail": ""}]
        conn.sendall(b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n")
        for ev in events:
            line = (json.dumps(ev) + "\n").encode()
            conn.sendall(f"{len(line):x}\r\n".encode() + line + b"\r\n")
        conn.sendall(b"0\r\n\r\n")
        conn.close()


def test_open_loop_latency_is_timed_from_the_due_time(tmp_path):
    server = _StallingServer(stall_at=3, stall_s=0.6)
    schedule = [{"index": i, "due_s": 0.1 * i, "input_ids": [1, 2, 3],
                 "max_new_tokens": 1} for i in range(10)]
    job, out = tmp_path / "job.json", tmp_path / "out.json"
    job.write_text(json.dumps({
        "port": server.port, "start_at": time.monotonic() + 0.5,
        "seconds": 1.0, "drain_grace_s": 10.0, "schedule": schedule}))
    rc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "loadgen.py"),
         str(job), str(out)], timeout=60).returncode
    server.sock.close()
    assert rc == 0
    records = json.loads(out.read_text())["records"]
    assert [r["status"] for r in records] == ["COMPLETED"] * 10
    ttft = [r["token_s"][0] - r["due_s"] for r in records]
    late = [r["sent_s"] - r["due_s"] for r in records]
    # the generator kept to its schedule through the stall ...
    assert max(late) < 0.2
    # ... so the stall shows in the stalled request AND in those behind it
    assert ttft[0] < 0.2 and ttft[1] < 0.3
    assert ttft[2] > 0.6 and ttft[3] > 0.5 and ttft[4] > 0.4
    assert ttft[9] < ttft[2]


def test_the_load_generator_never_imports_jax():
    src = open(os.path.join(ROOT, "benchmark", "loadgen.py")).read()
    assert "import jax" not in src and "deepspeed_tpu" not in src \
        and "from benchmark" not in src


# --------------------------------------------------------------------- #
class _Stream:
    """What ``token_events`` hands out: every event so far, then more."""

    def __init__(self, n):
        self.events = [{"event": "token", "index": i} for i in range(n)]

    def get(self, timeout=None):
        if not self.events:
            raise queue.Empty
        return self.events.pop(0)


class _ScriptedServer:
    """A fake slot engine on a scripted timeline: ``slots`` requests run
    side by side, first in first out; a step takes ``step_s`` on the fake
    clock, dispatches up to ``rows_per_step`` chunk rows of ``chunk`` tokens
    (one prompt's rows before the next one's) and ``tokens_per_step`` tokens
    for every request that already had its prompt.  As the real engine it
    counts chunk rows when it dispatches them and hands out a dispatch's
    tokens and completions a step later, when the device has run it.
    ``inflate`` multiplies what a subscription replays and what
    ``prefill_tokens`` counts — a program whose counters lie."""

    chunk = 16

    def __init__(self, slots, step_s, rows_per_step, tokens_per_step,
                 inflate=1, submit_s=0.0):
        self.now, self.step_s, self.slots = 0.0, step_s, slots
        self.submit_s = submit_s    # host time a caller's answer takes
        self.rows_per_step, self.tokens_per_step = rows_per_step, \
            tokens_per_step
        self.inflate = inflate
        self.stats = {"prefill_tokens": 0, "decode_tokens": 0,
                      "iterations": 0, "sync_secs": 0.0,
                      "paged_attention_fallback": 0}
        self.occupancy_trace, self.reqs, self.queue, self.running = \
            [], {}, [], []
        self.flying = []            # (rid, tokens) of the last dispatch
        self.subscribed = []        # (time, rid): nothing between marks

    def clock(self):
        return self.now

    def submit(self, prompt, max_new_tokens):
        self.now += self.submit_s
        rid = len(self.reqs)
        self.reqs[rid] = {"prompt": prompt, "new": max_new_tokens,
                          "rows": -(-len(prompt) // self.chunk), "sent": 0,
                          "got": 0, "streams": []}
        self.queue.append(rid)
        return rid

    def token_events(self, rid):
        self.subscribed.append((self.now, rid))
        stream = _Stream(self.reqs[rid]["got"] * self.inflate)
        self.reqs[rid]["streams"].append(stream)
        return stream

    def step(self):
        self.now += self.step_s
        self.stats["iterations"] += 1
        finished = {}
        for rid, gain in self.flying:           # the dispatch before lands
            r = self.reqs[rid]
            r["got"] += gain
            self.stats["decode_tokens"] += gain
            for s in r["streams"]:
                s.events += [{"event": "token"}] * gain
            if r["got"] == r["new"]:
                self.running.remove(rid)
                finished[rid] = np.concatenate(
                    [r["prompt"], np.zeros(r["new"], np.int32)])
        while self.queue and len(self.running) < self.slots:
            self.running.append(self.queue.pop(0))
        rows, fresh, self.flying = self.rows_per_step, set(), []
        for rid in self.running:                # first in, first prefilled
            take = min(rows, self.reqs[rid]["rows"])
            if take:
                self.reqs[rid]["rows"] -= take
                rows -= take
                fresh.add(rid)
                self.stats["prefill_tokens"] += \
                    take * self.chunk * self.inflate
        for rid in self.running:
            r = self.reqs[rid]
            if not r["rows"] and rid not in fresh and r["sent"] < r["new"]:
                gain = min(self.tokens_per_step, r["new"] - r["sent"])
                r["sent"] += gain
                self.flying.append((rid, gain))
        self.occupancy_trace.append((self.stats["iterations"],
                                     len(self.running)))
        return finished


MIX = {"callers": 6, "cycle": 4, "base_seed": 5, "ramp_s": 20.0,
       "prompt_len": {"dist": "uniform", "min": 40, "max": 90},
       "output_len": {"dist": "uniform", "min": 20, "max": 30}}
QUIET = types.SimpleNamespace(poll=lambda now: None, finish=lambda: None)


def _drive(mix=MIX, seconds=30.0, **server):
    srv = _ScriptedServer(**{"slots": 4, "step_s": 1.0, "rows_per_step": 3,
                             "tokens_per_step": 2, **server})
    driver = BENCH.driver("closed_loop_engine")
    opened = []
    window, done = driver.drive(
        srv, trafficgen.closed_loop_requests(mix, 512, 9), mix, seconds,
        QUIET, opened.append, clock=srv.clock)
    return srv, window, done, opened


def test_closed_loop_credit_conserves_every_requests_tokens():
    """(a) before + inside + after is every submitted request's real size,
    and the window's count is what the engine produced between the marks.
    The window's record is of whole iterations, and no request is read or
    subscribed but within reach of a mark (two steps before it)."""
    srv, w, done, opened = _drive()
    sizes = [len(r["prompt"]) + r["new"] for r in srv.reqs.values()]
    assert w["credited_before"] + w["credited_tokens"] \
        + w["credited_after"] == sum(sizes)
    assert opened == [20.0] and w["window_s"] == 30.0 \
        and w["iterations"] == 30 and w["credited_s"] == 30.0
    assert {t for t, _ in srv.subscribed} <= {18.0, 19.0, 20.0,
                                              48.0, 49.0, 50.0}
    assert w["completed"] == len(done) > 3 and w["failed"] == 0
    assert w["tokens"] == sum(len(p) + len(n) for p, n in done)
    # what the fake engine produced between the marks, by its own books:
    # the same tokens, but for the padding of prompts' last chunk rows and
    # for the rows it had dispatched and not yet run at either mark
    produced = w["decode_tokens"] + w["prefill_tokens"]
    ahead = 3 * srv.chunk                   # rows_per_step, a mark
    assert -ahead <= produced - w["credited_tokens"] \
        < srv.chunk * (w["completed"] + MIX["callers"]) + ahead


def test_a_request_wholly_inside_the_window_is_credited_whole():
    """(b) a cycle of ONE size, one caller, and a window that holds whole
    requests only: each counts ``len(prompt) + len(new)``, as before."""
    one = dict(MIX, callers=1, cycle=1, ramp_s=0.0)
    p, o = trafficgen.sizes(one, 1)[0]
    per = -(-p // 16) + -(-o // 2) + 1      # steps a request takes alone
    srv, w, done, _ = _drive(one, seconds=3 * per, slots=1, rows_per_step=1)
    assert w["completed"] == 3 and w["credited_after"] == p + o
    assert w["credited_tokens"] == w["tokens"] == 3 * (p + o)


def test_closed_loop_credit_does_not_turn_on_where_the_marks_fall():
    """(c) a periodic schedule — one size of whole chunk rows, one slot, a
    chunk row or as many tokens a step — read through windows of four and
    a half periods whose marks slide a step at a time: the credited rate
    is the same wherever they fall, where whole-request credit computed
    from the same windows jumps by a request.  (It holds because a mark
    reads the chunk rows the device has RUN: with the engine's count as it
    stands at the mark, a dispatch ahead of the tokens, the rate reads 16.6
    where a window closes on a prompt's rows.)"""
    one = dict(MIX, callers=2, cycle=1,
               prompt_len={"dist": "uniform", "min": 64, "max": 64},
               output_len={"dist": "uniform", "min": 32, "max": 32})
    per = 64 // 16 + 32 // 16
    credited, whole = [], []
    for slide in range(2 * per):            # by half steps, too
        mix = dict(one, ramp_s=2 * per + slide / 2)
        _, w, _, _ = _drive(mix, seconds=4.5 * per, slots=1,
                            rows_per_step=1, tokens_per_step=16)
        credited.append(w["credited_tokens"] / w["credited_s"])
        whole.append(w["tokens"] / w["window_s"])
    assert credited == [16.0] * 2 * per
    assert sorted(set(whole)) == pytest.approx(
        [4 * 96 / (4.5 * per), 5 * 96 / (4.5 * per)])


def test_a_mark_inside_a_step_reads_between_its_two_returns():
    """A mark that moves across a step's return by a hundredth of a step
    moves the credited count by about a hundredth of a step's tokens (and
    a token a live request for the rounding), where the window's record of
    whole iterations moves by the step."""
    got = {}
    for ramp in (19.99, 20.0, 20.01):
        _, w, _, opened = _drive(dict(MIX, ramp_s=ramp))
        assert w["credited_s"] == pytest.approx(30.0)
        got[ramp] = (w["credited_tokens"], opened[0])
    assert got[19.99][1] == got[20.0][1] == 20.0 and got[20.01][1] == 21.0
    a, b, c = (got[r][0] for r in (19.99, 20.0, 20.01))
    assert abs(a - b) <= 6 and abs(c - b) <= 6 and b > 500
    # the pure function: a share of what the step gave, rounded down; a
    # request the earlier reading did not know had nothing then
    assert credit.between([10, 40], [20, 40, 9], 0.5) == [15, 40, 4]
    assert credit.between([10], [20], 0.0) == [10]
    assert credit.between([30], [20], 0.7) == [30]


def test_no_mark_is_lost_in_the_host_time_after_a_steps_return():
    """The callers' answers take host time after a step's return, and a
    mark can fall in it: the loop then runs one more step, so both marks
    still have a reading past them and the credited window is ``seconds``
    long wherever the marks fall (a loop that looked at the clock after the
    answers closed its ramp with no reading past the mark, and the window
    was credited from its close on: lfm2, one run in six, PERF.md section 6,
    PR 44)."""
    rates = []
    for k in range(40):
        ramp = 20.0 + k * 0.05
        srv, w, _, _ = _drive(dict(MIX, ramp_s=ramp), submit_s=0.2)
        assert w["credited_s"] == pytest.approx(30.0)
        sizes = [len(r["prompt"]) + r["new"] for r in srv.reqs.values()]
        assert w["credited_before"] + w["credited_tokens"] \
            + w["credited_after"] == sum(sizes)
        rates.append(w["credited_tokens"] / w["credited_s"])
    assert max(rates) < 1.1 * min(rates)


def test_closed_loop_credit_cannot_be_raised_by_the_programs_counters():
    """(d) a program that replays three times the tokens it generated and
    counts three times its chunk rows: every reading past a request's size
    is clipped, the three sides still sum to the real sizes, and the window
    is credited no more than the requests that touched it hold."""
    honest = _drive()[1]
    srv, w, done, _ = _drive(inflate=3)
    sizes = [len(r["prompt"]) + r["new"] for r in srv.reqs.values()]
    assert w["credited_before"] + w["credited_tokens"] \
        + w["credited_after"] == sum(sizes)
    assert w["tokens"] == honest["tokens"]          # the same schedule
    straddlers = 2 * max(sizes) * 4                 # 4 slots a mark
    assert w["credited_tokens"] <= honest["credited_tokens"] + straddlers
    assert w["credited_tokens"] <= w["tokens"] + straddlers
    # the pure function, on hand-made readings
    sizes = [(32, 4), (20, 6), (40, 8)]
    at = credit.progress(sizes, [4, 99, 0], 1000, 16)
    assert at == [36, 26, 40]
    assert credit.progress(sizes, [0, 0, 0], 32 + 16, 16) == [32, 10, 0]
    assert credit.progress(sizes, [None, 1, 0], 0, 16) == [0, 21, 0]
    assert credit.split(sizes, [500, 0], [0, 26, 41]) == (36, 26 + 41, 7)


# --------------------------------------------------------------------- #
@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0), ([1, 2, 3, 4, 5], 95, 4.8),
    ([10], 95, 10.0), ([4, 1, 3, 2], 25, 1.75), (list(range(101)), 95, 95.0)])
def test_percentile_on_hand_made_samples(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


def test_rate_spread_rms_and_intervals():
    assert stats.rate(81920, 40.0) == 2048.0
    with pytest.raises(ValueError):
        stats.rate(1, 0)
    # quartiles as statistics.quantiles gives them: [1..6] -> 1.75, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.rms([3, 4]) == pytest.approx((12.5) ** 0.5)
    iv = [(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]
    assert stats.union_seconds(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0, 5) == [(2, 3), (4, 5)]
    assert stats.gaps([], 1, 2) == [(1, 2)]
