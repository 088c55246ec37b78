"""The traffic generator, the load generator's clock, and the arithmetic
between samples and metrics."""

import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmark import spec, stats, trafficgen

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
