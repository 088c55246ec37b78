#!/usr/bin/env python3
"""Open-loop HTTP load generator — a process of its own that never imports
JAX (the chip belongs to the server's process, and a generator thread there
would share the scheduler's interpreter lock).

    python3 benchmark/loadgen.py <job.json> <out.json>

``job.json``: ``{"port", "start_at" (time.monotonic() of the window's start:
CLOCK_MONOTONIC is one clock for every process of the host), "seconds",
"drain_grace_s", "schedule": [{"index", "due_s", "input_ids",
"max_new_tokens"}]}``.  Each request is sent when it is due whether or not
earlier ones have finished, streams its tokens, and is timed from its DUE
time, so a stall shows in the requests behind it.  ``out.json`` gets one
record per request: when it was due, how late it was sent, when each token
arrived, how it ended.
"""

import asyncio
import json
import sys
import time


async def _one(job, req, t0, deadline, out):
    rec = {"index": req["index"], "due_s": req["due_s"], "sent_s": None,
           "token_s": [], "tokens": [], "status": None, "rid": None,
           "error": None, "prompt_len": len(req["input_ids"]),
           "max_new_tokens": req["max_new_tokens"]}
    out.append(rec)
    delay = t0 + req["due_s"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    body = json.dumps({"input_ids": req["input_ids"],
                       "max_new_tokens": req["max_new_tokens"],
                       "stream": True}).encode()
    writer = None
    try:
        rec["sent_s"] = time.monotonic() - t0
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       job["port"])
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        await writer.drain()
        left = lambda: max(0.001, deadline - time.monotonic())
        head = await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"), left())
        status = int(head.split(b" ", 2)[1])
        if status != 200:
            rec["error"] = f"HTTP {status}"
            return
        while True:      # chunked NDJSON: size line, event line, blank line
            line = await asyncio.wait_for(reader.readline(), left())
            if not line:
                rec["error"] = "stream closed before its end event"
                return
            line = line.strip()
            if not line.startswith(b"{"):
                continue
            ev = json.loads(line)
            if ev["event"] == "token":
                rec["token_s"].append(time.monotonic() - t0)
                rec["tokens"].append(ev["token"])
            elif ev["event"] == "end":
                rec["status"], rec["rid"] = ev["status"], ev["rid"]
                return
    except asyncio.TimeoutError:
        rec["error"] = "not complete when the drain grace ended"
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def _run(job):
    t0 = job["start_at"]
    deadline = t0 + job["seconds"] + job["drain_grace_s"]
    out = []
    tasks = [asyncio.ensure_future(_one(job, r, t0, deadline, out))
             for r in job["schedule"]]
    await asyncio.gather(*tasks)
    return sorted(out, key=lambda r: r["index"])


def main(argv):
    with open(argv[1]) as f:
        job = json.load(f)
    records = asyncio.run(_run(job))
    with open(argv[2], "w") as f:
        json.dump({"records": records,
                   "ended_s": time.monotonic() - job["start_at"]}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
