"""Async HTTP front end for the serving engine (``docs/serving.md``
"Network front end") — the transport between "millions of users" and the
fixed-capacity slot scheduler.  Stdlib-only: an ``asyncio`` HTTP/1.1
server (no framework dependency survives a hermetic TPU pod image).

Endpoints
---------
- ``POST /v1/generate`` — submit one request.  JSON body::

      {"input_ids": [...], "max_new_tokens": 32, "eos_token_id": -1,
       "deadline_s": null, "client_id": "tenant-a", "priority": 0,
       "stream": false}

  Blocking (default): responds once the request reaches a terminal
  status with ``{"rid", "status", "output", "detail", "ttft_s",
  "client_id"}``.  ``"stream": true``: responds immediately with
  ``Transfer-Encoding: chunked`` + ``application/x-ndjson`` and writes
  one JSON line per token event as the host mirror drains it —
  ``{"event": "token", "rid", "index", "token"}`` per token, then
  exactly one ``{"event": "end", "rid", "status", "detail"}`` — so TTFT
  and time-between-tokens are observable on the wire.  A client that
  disconnects mid-stream cancels its request (its slot frees at the
  next scheduling point).
- ``GET /v1/requests/<rid>`` — status poll (``404`` for ids this server
  never issued); terminal requests include the result payload.
- ``DELETE /v1/requests/<rid>`` — cancel (``404`` unknown; ``200`` with
  ``{"cancelled": bool}`` — ``false`` when already terminal).
- ``GET /healthz`` — scheduler snapshot: breaker state, queue depth,
  slot occupancy, in-flight events, uptime (``503`` once the engine is
  closed/preempted).
- ``GET /metrics`` — Prometheus text (``dstpu_serving_*``) from the
  engine's monitor counters, plus per-client fairness window balances.
  Every series carries ``# HELP``/``# TYPE`` lines and label values are
  fully escaped (``\\``, ``"``, newline) — the exposition round-trips
  through the text-format parser the tests ship.  With
  ``serving.tracing`` on, the TTFT / time-between-tokens / queue-wait /
  per-program dispatch-duration / lock-wait histograms are exported too
  (``docs/observability.md``).
- ``GET /debug/flightrec`` — the flight-recorder ring as JSON (``404``
  unless ``serving.flight_recorder`` is on).  SIGUSR2 (when signal
  handlers are installed) dumps the same ring to a file without
  touching the engine lock.
- ``POST /debug/profile?secs=N`` — on-demand ``jax.profiler`` capture
  for device-level traces (``404`` unless ``serving.profile_endpoint``;
  ``409`` while another capture runs); responds with the trace
  directory.

Error mapping: over-quota / full queue → ``429`` (:class:`QueueFull`),
open circuit breaker / closed engine → ``503``, malformed request →
``400``, unknown rid → ``404``.

Threading model (the part the engine's lock alone cannot give you)
------------------------------------------------------------------
THREE kinds of thread, one scheduler owner:

1. The **asyncio loop thread** parses HTTP and serializes responses.
   Handlers only ever call the engine's thread-safe surface
   (``submit``/``result``/``cancel``/``status``/``token_events``) — via
   ``run_in_executor`` so a blocked ``submit()`` (queue_policy="block")
   never stalls the event loop.
2. The **scheduler-owner thread** is the ONLY caller of ``step()`` /
   ``preempt()`` — the engine binds its owner on the first driving call
   and raises for any other thread (the host mirror's lag-one protocol
   is stateful across calls).  Idle, it sleeps on ``srv.wake`` which
   ``submit()``/``restore()`` set, so an empty server burns no CPU.
3. Engine → loop bridging is ``loop.call_soon_threadsafe`` from the
   ``token_events`` ``on_event`` hook (never blocks, safe under the
   engine lock).

One decode executable for the server lifetime: the front end adds ZERO
jitted programs — it is pure orchestration over the engine's existing
traced-argument programs (the ``@hot_path`` registration below is the
lint/contract gate's conscious-orchestrator marker, not a program).

SIGTERM (``install_signal_handlers=True``) requests graceful preemption:
the scheduler thread stops admission, drains under the config budget,
snapshots undrained requests crash-atomically (fairness balances and
priorities ride the snapshot), and every active stream ends with a typed
``PREEMPTED`` event instead of a dead socket.  A restarted server
``restore()``s and finishes them bitwise
(``tests/unit/test_serving_frontend.py``).
"""

import asyncio
import json
from contextlib import nullcontext
import signal
import threading
import time

import numpy as np

from deepspeed_tpu.inference.serving.slo import (CircuitOpen, QueueFull,
                                                 RequestStatus,
                                                 TERMINAL_STATUSES)
from deepspeed_tpu.monitor.trace import now as span_now, span
from deepspeed_tpu.tools.lint.hotpath import hot_path
from deepspeed_tpu.utils.logging import logger

_MAX_BODY = 8 << 20                      # request bodies past this: 413


class _HTTPError(Exception):
    def __init__(self, code, message):
        super().__init__(message)
        self.code = code


_STATUS_TEXT = {200: "OK", 400: "Bad Request", 404: "Not Found",
                405: "Method Not Allowed", 408: "Request Timeout",
                413: "Payload Too Large", 429: "Too Many Requests",
                500: "Internal Server Error", 503: "Service Unavailable"}


class ServingHTTPFrontend:
    """Asyncio HTTP server over one :class:`ServingEngine`.

    ``port=0`` binds an ephemeral port (read ``self.port`` after
    :meth:`start`).  ``snapshot_dir`` is where SIGTERM preemption
    publishes its crash-atomic snapshot — without it a preempt request
    degrades to ``close()`` (undrained work ABORTED, never silently
    lost).  Use as a context manager or call :meth:`start` /
    :meth:`shutdown` explicitly::

        with ServingHTTPFrontend(srv, snapshot_dir=d) as fe:
            requests.post(f"http://127.0.0.1:{fe.port}/v1/generate", ...)
    """

    def __init__(self, srv, host="127.0.0.1", port=0, snapshot_dir=None,
                 idle_poll_s=0.05, max_body_bytes=_MAX_BODY):
        self.srv = srv
        self.host = host
        self.port = int(port)
        self.snapshot_dir = snapshot_dir
        self.idle_poll_s = float(idle_poll_s)
        self.max_body_bytes = int(max_body_bytes)
        self._loop = None
        self._server = None
        self._loop_thread = None
        self._sched_thread = None
        self._stop = threading.Event()
        self._preempt = threading.Event()
        self._profile_lock = threading.Lock()   # one capture at a time
        self._sched_error = None
        self.preempt_result = None       # (tag, rids, finished) after SIGTERM
        self._t0 = time.monotonic()
        self._prev_handlers = {}

    # ------------------------------------------------------------------ #
    # Lifecycle
    # ------------------------------------------------------------------ #
    def start(self):
        """Start the scheduler-owner thread (which claims the engine's
        owner role), then bind the port and the asyncio loop thread —
        in that order, so no HTTP request can race the ownership claim
        (a blocked ``queue_policy="block"`` submit would otherwise bind
        ITSELF as owner and wedge the scheduler).  Returns ``self``
        (``self.port`` holds the bound port)."""
        if self._loop is not None:
            raise RuntimeError("ServingHTTPFrontend already started")
        self._owner_ready = threading.Event()
        self._sched_thread = threading.Thread(
            target=self._scheduler_loop, name="dstpu-serving-scheduler",
            daemon=True)
        self._sched_thread.start()
        if not self._owner_ready.wait(timeout=30):
            self._stop.set()             # unwind the scheduler thread
            self.srv.wake.set()
            raise RuntimeError(
                "scheduler thread failed to claim the engine's owner "
                "role — was the engine already driven by another thread? "
                f"({self._sched_error})")
        if self._sched_error is not None:
            raise RuntimeError(f"scheduler thread failed to start: "
                               f"{self._sched_error}")
        self._loop = asyncio.new_event_loop()
        self._loop_thread = threading.Thread(
            target=self._run_loop, name="dstpu-http-loop", daemon=True)
        self._loop_thread.start()
        fut = asyncio.run_coroutine_threadsafe(self._start_server(),
                                               self._loop)
        try:
            fut.result(timeout=30)
        except Exception:
            # e.g. the port is already bound: unwind BOTH threads — the
            # scheduler releases its owner binding on exit, so a retry
            # frontend (or the caller driving step() directly) can claim
            # the engine instead of finding it wedged forever
            self._stop.set()
            self.srv.wake.set()
            self._sched_thread.join(timeout=10)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._loop_thread.join(timeout=10)
            raise
        logger.info(f"[serving] HTTP front end listening on "
                    f"{self.host}:{self.port}")
        return self

    def _run_loop(self):
        asyncio.set_event_loop(self._loop)
        try:
            self._loop.run_forever()
        finally:
            self._loop.close()

    async def _start_server(self):
        # the StreamReader limit must cover the largest allowed body:
        # readexactly() on a body larger than the buffer limit deadlocks
        self._server = await asyncio.start_server(
            self._handle_conn, self.host, self.port,
            limit=self.max_body_bytes + 65536)
        self.port = self._server.sockets[0].getsockname()[1]

    def request_preempt(self):
        """Ask the scheduler thread to preempt gracefully (the SIGTERM
        path, callable from any thread/signal handler — sets a flag and
        wakes the owner; never touches the engine directly)."""
        self._preempt.set()
        self.srv.wake.set()

    def install_signal_handlers(self, signals=(signal.SIGTERM,)):
        """Route SIGTERM to :meth:`request_preempt` (main thread only —
        CPython restricts ``signal.signal``).  Previous handlers are
        restored by :meth:`shutdown`.  When the engine carries a flight
        recorder, SIGUSR2 additionally dumps its ring to a file
        (``docs/observability.md`` — the recorder never takes the
        engine lock, so the dump is safe from a signal frame)."""
        for sig in signals:
            self._prev_handlers[sig] = signal.signal(
                sig, lambda *_: self.request_preempt())
        if getattr(self.srv, "flightrec_enabled", False):
            self.install_flightrec_signal_handler()

    def install_flightrec_signal_handler(self, sig=None):
        """Route SIGUSR2 (or ``sig``) to a flight-recorder dump.  Main
        thread only; restored by :meth:`shutdown`."""
        sig = sig if sig is not None else signal.SIGUSR2
        self._prev_handlers[sig] = signal.signal(
            sig, lambda *_: self._dump_flightrec_signal())

    def _dump_flightrec_signal(self):
        try:
            path = self.srv.dump_flightrec(reason="sigusr2")
            logger.warning(f"[serving] SIGUSR2: flight recorder dumped "
                           f"to {path}")
        except Exception as e:           # noqa: BLE001 — signal frame
            logger.warning(f"[serving] SIGUSR2 flight-recorder dump "
                           f"failed: {type(e).__name__}: {e}")

    def _scheduler_loop(self):
        """The single scheduler owner: drives ``step()`` while work is
        pending, sleeps on ``srv.wake`` when idle, and runs the graceful
        preemption on request.  Registered as a conscious ORCHESTRATOR
        with the lint/contract gates — it dispatches the engine's
        existing programs and must never mint one of its own."""
        self._scheduler_body()

    @hot_path("serving.http_frontend_loop")
    def _scheduler_body(self):
        srv = self.srv
        try:
            srv.bind_owner()             # before any request can arrive
        except Exception as e:           # noqa: BLE001
            self._sched_error = f"{type(e).__name__}: {e}"
            self._owner_ready.set()
            return
        self._owner_ready.set()
        try:
            while not self._stop.is_set():
                if self._preempt.is_set():
                    self._do_preempt()
                    return
                if srv.work_pending():   # one lock round-trip, not three
                    srv.step()
                else:
                    with span("dstpu.sched.idle", track="scheduler",
                              cat="scheduler"):
                        srv.wake.wait(timeout=self.idle_poll_s)
                    srv.wake.clear()
        except Exception as e:           # noqa: BLE001 — surfaced via healthz
            self._sched_error = f"{type(e).__name__}: {e}"
            logger.error(f"[serving] scheduler thread died: "
                         f"{self._sched_error}")
            # a dead scheduler is exactly what the flight recorder
            # exists for: dump the ring BEFORE close() clears the scene
            try:
                if getattr(srv, "flightrec_enabled", False):
                    srv._flightrec.record("scheduler_thread_death",
                                          error=self._sched_error[:200])
                    srv.dump_flightrec(reason="scheduler_thread_death")
            except Exception:            # noqa: BLE001 — best effort
                pass
            # nothing will drive the engine again: close it so every
            # in-flight request ends with a typed ABORTED event (waiting
            # handlers unblock) and new submits get 503 instead of
            # queueing into a void
            try:
                srv.close()
            except Exception as ce:      # noqa: BLE001
                logger.error(f"[serving] close after scheduler death "
                             f"failed: {type(ce).__name__}: {ce}")
        finally:
            # the exiting owner releases its binding so a successor
            # driver (a retry frontend after a failed start(), or the
            # caller after shutdown(close_engine=False)) can claim the
            # engine instead of finding it bound to a dead thread
            try:
                srv.release_owner()
            except Exception:            # noqa: BLE001
                pass

    def _do_preempt(self):
        srv = self.srv
        try:
            if self.snapshot_dir:
                self.preempt_result = srv.preempt(self.snapshot_dir)
                tag, snapped, _ = self.preempt_result
                logger.warning(f"[serving] HTTP front end preempted — "
                               f"snapshot {tag!r} holds {len(snapped)} "
                               f"request(s)")
            else:
                logger.warning("[serving] preempt requested with no "
                               "snapshot_dir — closing (undrained work "
                               "ABORTED, typed status preserved)")
                srv.close()
        except Exception as e:           # noqa: BLE001
            self._sched_error = f"{type(e).__name__}: {e}"
            logger.error(f"[serving] preempt failed: {self._sched_error}")
            try:                         # same rationale as scheduler death
                srv.close()
            except Exception:            # noqa: BLE001
                pass

    def shutdown(self, close_engine=False):
        """Stop the scheduler thread, close the listener and the loop;
        ``close_engine=True`` also retires the engine (undrained work
        ABORTED).  Idempotent."""
        self._stop.set()
        self.srv.wake.set()
        if self._sched_thread is not None:
            self._sched_thread.join(timeout=30)
        if self._loop is not None and not self._loop.is_closed():
            async def _close():
                self._server.close()
                await self._server.wait_closed()
                # keep-alive connections park in readuntil() waiting for
                # a next request that will never come — cancel them so
                # the loop stops clean instead of destroying live tasks
                mine = asyncio.current_task()
                pending = [t for t in asyncio.all_tasks()
                           if t is not mine]
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)
            try:
                asyncio.run_coroutine_threadsafe(
                    _close(), self._loop).result(timeout=10)
            except Exception:            # noqa: BLE001 — already closing
                pass
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._loop_thread.join(timeout=10)
        for sig, prev in self._prev_handlers.items():
            signal.signal(sig, prev)
        self._prev_handlers.clear()
        if close_engine:
            self.srv.close()             # idempotent; takes its own lock

    def join_preempted(self, timeout=60):
        """Block until the scheduler thread has finished a requested
        preemption (snapshot published); returns ``preempt_result``."""
        self._sched_thread.join(timeout=timeout)
        if self._sched_thread.is_alive():
            raise TimeoutError("scheduler thread still running — "
                               "preemption did not complete")
        return self.preempt_result

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # ------------------------------------------------------------------ #
    # HTTP plumbing
    # ------------------------------------------------------------------ #
    async def _handle_conn(self, reader, writer):
        try:
            while True:
                try:
                    req = await self._read_request(reader)
                except _HTTPError as e:
                    # malformed head / oversized body: the request
                    # framing can't be trusted past this point — answer
                    # the error, then drop the connection
                    await self._respond(writer, e.code,
                                        {"error": str(e)})
                    break
                if req is None:
                    break
                keep_alive = await self._route(req, writer)
                if not keep_alive:
                    break
        except (asyncio.IncompleteReadError, ConnectionError,
                asyncio.LimitOverrunError):
            pass                         # client went away / oversized head
        finally:
            try:
                writer.close()
                await writer.wait_closed()
            except (ConnectionError, OSError):
                pass

    async def _read_request(self, reader):
        try:
            head = await reader.readuntil(b"\r\n\r\n")
        except asyncio.IncompleteReadError:
            return None                  # clean EOF between requests
        t_head = span_now()              # where dstpu.frontend.parse starts
        lines = head.decode("latin-1").split("\r\n")
        try:
            method, path, _version = lines[0].split(" ", 2)
        except ValueError:
            raise _HTTPError(400, f"malformed request line: {lines[0]!r}")
        headers = {}
        for line in lines[1:]:
            if ":" in line:
                k, v = line.split(":", 1)
                headers[k.strip().lower()] = v.strip()
        raw_n = headers.get("content-length")
        try:
            n = int(raw_n) if raw_n else 0
        except ValueError:
            raise _HTTPError(400, f"malformed Content-Length: {raw_n!r}")
        if n < 0:
            raise _HTTPError(400, f"negative Content-Length: {raw_n!r}")
        if n > self.max_body_bytes:
            raise _HTTPError(413, f"body of {n} bytes exceeds the "
                                  f"{self.max_body_bytes}-byte limit")
        body = await reader.readexactly(n) if n else b""
        return {"method": method.upper(), "path": path,
                "headers": headers, "body": body, "t_head": t_head}

    @staticmethod
    def _head(code, ctype, extra=""):
        return (f"HTTP/1.1 {code} {_STATUS_TEXT.get(code, 'Unknown')}\r\n"
                f"Content-Type: {ctype}\r\n{extra}")

    async def _respond(self, writer, code, payload, ctype=None):
        if isinstance(payload, (dict, list)):
            body = (json.dumps(payload) + "\n").encode()
            ctype = ctype or "application/json"
        else:
            body = payload if isinstance(payload, bytes) \
                else str(payload).encode()
            ctype = ctype or "text/plain; charset=utf-8"
        writer.write(self._head(code, ctype).encode()
                     + f"Content-Length: {len(body)}\r\n"
                       f"Connection: keep-alive\r\n\r\n".encode() + body)
        await writer.drain()
        return True

    async def _route(self, req, writer):
        method = req["method"]
        path, _, query = req["path"].partition("?")
        try:
            if path == "/v1/generate" and method == "POST":
                return await self._generate(req, writer)
            if path == "/healthz" and method == "GET":
                return await self._healthz(writer)
            if path == "/metrics" and method == "GET":
                return await self._metrics(writer)
            if path == "/debug/flightrec" and method == "GET":
                return await self._debug_flightrec(writer)
            if path == "/debug/profile" and method == "POST":
                return await self._debug_profile(query, writer)
            if path.startswith("/v1/requests/"):
                return await self._request_resource(method, path, writer)
            return await self._respond(
                writer, 404, {"error": f"no route {method} {path}"})
        except _HTTPError as e:
            return await self._respond(writer, e.code, {"error": str(e)})
        except (ConnectionError, asyncio.IncompleteReadError):
            raise
        except Exception as e:           # noqa: BLE001 — 500, keep serving
            logger.error(f"[serving] handler error on {method} {path}: "
                         f"{type(e).__name__}: {e}")
            try:
                return await self._respond(
                    writer, 500, {"error": f"{type(e).__name__}: {e}"})
            except (ConnectionError, OSError):
                return False

    # ------------------------------------------------------------------ #
    # /v1/generate
    # ------------------------------------------------------------------ #
    def _parse_generate(self, body):
        try:
            spec = json.loads(body.decode() or "{}")
        except (ValueError, UnicodeDecodeError) as e:
            raise _HTTPError(400, f"request body is not JSON: {e}")
        if not isinstance(spec, dict):
            raise _HTTPError(400, "request body must be a JSON object")
        ids = spec.get("input_ids")
        if not isinstance(ids, list) or not ids \
                or not all(isinstance(t, int) for t in ids):
            raise _HTTPError(400, "input_ids: non-empty list of ints "
                                  "required")
        known = {"input_ids", "max_new_tokens", "eos_token_id",
                 "deadline_s", "client_id", "priority", "stream"}
        unknown = set(spec) - known
        if unknown:
            raise _HTTPError(400, f"unknown field(s) {sorted(unknown)} — "
                                  f"accepted: {sorted(known)}")
        return spec

    def _submit_from_spec(self, spec, t_handoff=None):
        """Engine submit with the HTTP error mapping (runs in an
        executor thread: queue_policy='block' may wait here).  One
        ``dstpu.frontend.submit`` span: the annotation covers this
        thread's part; the ring span starts at ``t_handoff`` — the loop
        thread's stamp when it handed the request to the executor — so
        executor queueing is inside it, and it records the request's
        ``rid`` and ``lock_wait_s``, this submit's own engine-lock
        wait."""
        try:
            with span("dstpu.frontend.submit", cat="frontend",
                      start=t_handoff) as sp:
                rid = self.srv.submit(
                    np.asarray(spec["input_ids"], np.int32),
                    max_new_tokens=int(spec.get("max_new_tokens", 32)),
                    eos_token_id=int(spec.get("eos_token_id", -1)),
                    deadline_s=spec.get("deadline_s"),
                    client_id=spec.get("client_id"),
                    priority=int(spec.get("priority", 0)))
                sp.set(rid=rid, lock_wait_s=self.srv.last_lock_wait_s())
                return rid
        except QueueFull as e:           # over quota / full queue
            raise _HTTPError(429, str(e))
        except CircuitOpen as e:
            raise _HTTPError(503, str(e))
        except (TypeError, ValueError) as e:
            raise _HTTPError(400, str(e))
        except RuntimeError as e:        # closed engine
            raise _HTTPError(503, str(e))

    def _subscribe(self, rid, on_event):
        """``token_events`` on an executor thread (it takes the engine
        lock), as one ``dstpu.frontend.subscribe`` span."""
        with span("dstpu.frontend.subscribe", cat="frontend", rid=rid):
            return self.srv.token_events(rid, on_event)

    def _result_payload(self, rid):
        res = self.srv.result(rid)
        if res is None:                  # PREEMPTED ends without a result
            return {"rid": rid, "status": self.srv.status(rid),
                    "output": None, "detail": "", "ttft_s": None,
                    "client_id": None}
        return {"rid": rid, "status": res.status,
                "output": res.output.tolist()
                if res.output is not None else None,
                "detail": res.detail, "ttft_s": res.ttft_s,
                "client_id": res.client_id}

    async def _generate(self, req, writer):
        # the ring span reaches back to the request head's arrival, so
        # the awaited body read is inside it; the annotation covers the
        # synchronous parse (an await inside an annotation would let
        # other connections' spans interleave on the loop thread)
        with span("dstpu.frontend.parse", cat="frontend",
                  start=req.get("t_head"), bytes=len(req["body"])):
            spec = self._parse_generate(req["body"])
        loop = asyncio.get_running_loop()
        if not spec.get("stream"):
            rid = await loop.run_in_executor(
                None, self._submit_from_spec, spec, span_now())
            done = asyncio.Event()

            def on_ev(ev, _loop=loop, _done=done):
                # called under the engine lock — hand off, never block
                if ev.get("event") == "end":
                    _loop.call_soon_threadsafe(_done.set)

            # engine calls take the engine lock, which the scheduler
            # thread holds across step() — keep them off the loop thread
            await loop.run_in_executor(None, self._subscribe, rid, on_ev)
            await done.wait()
            payload = await loop.run_in_executor(
                None, self._result_payload, rid)
            return await self._respond(writer, 200, payload)
        # streaming: subscribe BEFORE any await so no event can slip
        # between submit and subscription (token_events replays anyway —
        # this just keeps the replay empty in the common case)
        rid = await loop.run_in_executor(
            None, self._submit_from_spec, spec, span_now())
        q = asyncio.Queue()

        def on_ev(ev, _loop=loop, _q=q):
            _loop.call_soon_threadsafe(_q.put_nowait, ev)

        await loop.run_in_executor(None, self._subscribe, rid, on_ev)
        traced = bool(getattr(self.srv, "tracing", False))
        writer.write(
            self._head(200, "application/x-ndjson",
                       "Transfer-Encoding: chunked\r\n"
                       "Connection: close\r\n"
                       "X-Accel-Buffering: no\r\n").encode() + b"\r\n")
        try:
            while True:
                ev = await q.get()
                line = (json.dumps(ev) + "\n").encode()
                # a span per token event: under serving.tracing only
                with span("dstpu.frontend.write", cat="frontend",
                          rid=rid) if traced else nullcontext():
                    writer.write(f"{len(line):x}\r\n".encode() + line
                                 + b"\r\n")
                    await writer.drain()     # flush per token event
                if ev.get("event") == "end":
                    break
            writer.write(b"0\r\n\r\n")
            await writer.drain()
        except (ConnectionError, OSError):
            # client hung up mid-stream: release its slot
            def _cancel():
                try:
                    self.srv.cancel(rid)
                except KeyError:
                    pass
            await loop.run_in_executor(None, _cancel)
            return False
        return False                     # Connection: close after a stream

    # ------------------------------------------------------------------ #
    # /v1/requests/<rid>
    # ------------------------------------------------------------------ #
    async def _request_resource(self, method, path, writer):
        tail = path[len("/v1/requests/"):]
        try:
            rid = int(tail)
        except ValueError:
            raise _HTTPError(400, f"request id must be an int, got "
                                  f"{tail!r}")
        srv = self.srv
        loop = asyncio.get_running_loop()
        if method == "GET":
            def _status_payload():
                status = srv.status(rid)
                payload = {"rid": rid, "status": status}
                if status in TERMINAL_STATUSES \
                        or status == RequestStatus.PREEMPTED:
                    payload.update(self._result_payload(rid))
                return payload
            try:
                payload = await loop.run_in_executor(
                    None, _status_payload)
            except KeyError as e:
                raise _HTTPError(404, str(e))
            return await self._respond(writer, 200, payload)
        if method == "DELETE":
            def _cancel_payload():
                return {"rid": rid, "cancelled": bool(srv.cancel(rid)),
                        "status": srv.status(rid)}
            try:
                payload = await loop.run_in_executor(
                    None, _cancel_payload)
            except KeyError as e:
                raise _HTTPError(404, str(e))
            return await self._respond(writer, 200, payload)
        raise _HTTPError(405, f"{method} not allowed on {path}")

    # ------------------------------------------------------------------ #
    # /healthz and /metrics
    # ------------------------------------------------------------------ #
    async def _healthz(self, writer):
        # ONE locked engine snapshot, taken off the loop thread: piecing
        # the payload together from unlocked field reads both raced the
        # scheduler and (worse) blocked the event loop on the engine
        # lock across a step() — the TL008/TL009 bug classes
        snap = await asyncio.get_running_loop().run_in_executor(
            None, self.srv.health_snapshot)
        payload = {
            "ok": not snap["closed"] and self._sched_error is None,
            "uptime_s": round(time.monotonic() - self._t0, 3),
            **snap,
            "scheduler_error": self._sched_error,
        }
        return await self._respond(
            writer, 503 if snap["closed"] else 200, payload)

    @staticmethod
    def _esc_label(v):
        """Prometheus text-format label-value escaping: backslash,
        double quote and newline (exposition-format spec)."""
        return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                .replace("\n", "\\n"))

    @staticmethod
    def _esc_help(v):
        """HELP-line escaping: backslash and newline."""
        return str(v).replace("\\", "\\\\").replace("\n", "\\n")

    def _metrics_body(self):
        """Render the Prometheus text (runs in an executor thread; the
        snapshot is taken under the engine lock — the scheduler thread
        grows ``stats`` and the fairness tracker compacts its window
        map in place, so an unlocked iteration can race both).  Every
        series carries ``# HELP``/``# TYPE``; label values are escaped;
        the round-trip test parses the full output back
        (``tests/unit/test_serving_trace.py``)."""
        srv = self.srv
        with srv._lock:
            mem = None
            if srv._memwatch is not None:
                # the scheduler seam owns the sampling cadence; the
                # scrape only forces a sample when none exists yet (a
                # server scraped before its first step)
                mem = srv._memwatch.last
                if mem is None:
                    mem = srv._memwatch.sample()
                    srv._sample_memory_into_stats(mem)
            stats = dict(srv.stats)
            lock_wait = dict(srv._lock.wait_s)
            snap = {
                "queue_depth": srv.queue_depth,
                "active_slots": srv.active_slots,
                "in_flight": srv.in_flight,
                "breaker_open": srv._breaker.open,
                "page_pool_util": srv.page_pool_utilization,
                "fairness": None if srv._fairness is None
                else sorted(srv._fairness.window_usage().items()),
                "fairness_budget": None if srv._fairness is None
                else srv._fairness.budget,
                # serving.memory_telemetry: the newest interval sample
                "memory": mem,
            }
        hist = srv.histograms()          # internally locked; may be None
        lines = []

        def series(name, help_, type_, samples):
            """One metric family: HELP/TYPE exactly once, then every
            sample — ``samples`` is ``[(suffix, labels_dict, value)]``
            (suffix: ``""`` for gauges, ``_bucket``/``_sum``/``_count``
            for histograms)."""
            lines.append(f"# HELP {name} {self._esc_help(help_)}")
            lines.append(f"# TYPE {name} {type_}")
            for suffix, labels, value in samples:
                lab = ""
                if labels:
                    inner = ",".join(
                        f'{k}="{self._esc_label(v)}"'
                        for k, v in labels.items())
                    lab = "{" + inner + "}"
                lines.append(f"{name}{suffix}{lab} {float(value)}")

        def gauge(name, value, help_, labels=None):
            series(f"dstpu_serving_{name}", help_, "gauge",
                   [("", labels or {}, value)])

        for key, val in sorted(stats.items()):
            if isinstance(val, str):
                # a name, not a number (``chunk_write``): the value is a
                # label of a gauge that reads 1
                gauge(key, 1.0, help_=f"serving engine setting {key!r}",
                      labels={"value": val})
                continue
            gauge(key, val, help_=f"serving engine counter {key!r}")
        gauge("queue_depth", snap["queue_depth"],
              "queued + pending prefill")
        gauge("active_slots", snap["active_slots"],
              "host-mirror live slots")
        gauge("slot_occupancy", snap["active_slots"] / srv.num_slots,
              "live / total slots")
        gauge("in_flight_events", snap["in_flight"],
              "dispatched device events not yet processed")
        gauge("breaker_open", 1.0 if snap["breaker_open"] else 0.0,
              "dispatch circuit breaker state")
        gauge("uptime_seconds", time.monotonic() - self._t0,
              "front-end uptime")
        series("dstpu_serving_lock_wait_seconds",
               "cumulative wall time waiting on the engine lock per "
               "thread class", "gauge",
               [("", {"thread_class": cls}, lock_wait[cls])
                for cls in sorted(lock_wait)])
        gauge("page_pool_utilization", snap["page_pool_util"],
              "allocated fraction of the KV page pool")
        if snap["fairness"] is not None:
            series("dstpu_serving_fairness_window_tokens",
                   "per-client decayed window balance", "gauge",
                   [("", {"client": key}, bal)
                    for key, bal in snap["fairness"]])
            gauge("fairness_budget", snap["fairness_budget"],
                  "window budget above which submit() is 429'd")
        if snap["memory"] is not None:
            # serving.memory_telemetry (docs/observability.md "Device
            # memory & roofline"): per-device in-use/peak/limit from the
            # accelerator's canonical reader, the engine's owner
            # reconciliation, and the unattributed gap — the family
            # names are the memwatch.MEMORY_SERIES literal the
            # stats-docs gate pins to the docs
            mem = snap["memory"]
            series("dstpu_device_memory_bytes_in_use",
                   "device bytes in use (accelerator memory_snapshot)",
                   "gauge",
                   [("", {"device": d["device"]}, d["bytes_in_use"])
                    for d in mem["devices"]])
            series("dstpu_device_memory_peak_bytes",
                   "peak device bytes in use since process start",
                   "gauge",
                   [("", {"device": d["device"]}, d["peak_bytes_in_use"])
                    for d in mem["devices"]])
            series("dstpu_device_memory_limit_bytes",
                   "device memory budget (runtime bytes_limit or "
                   "datasheet capacity; 0 = unknown)", "gauge",
                   [("", {"device": d["device"],
                          "source": d["limit_source"]},
                     d["bytes_limit"]) for d in mem["devices"]])
            series("dstpu_device_memory_owned_bytes",
                   "bytes attributed to a known serving-engine owner",
                   "gauge",
                   [("", {"owner": o}, b)
                    for o, b in sorted(mem["owners"].items())])
            series("dstpu_device_memory_unattributed_bytes",
                   "device bytes in use beyond every known owner — "
                   "where leaks hide", "gauge",
                   [("", {}, mem["unattributed_bytes"])])
        if hist is not None:
            # serving.tracing: the TTFT / TBT / queue-wait / dispatch /
            # lock-wait histograms (docs/observability.md)
            for name, help_, samples in hist.collect():
                series(name, help_, "histogram", samples)
        return ("\n".join(lines) + "\n").encode()

    async def _metrics(self, writer):
        body = await asyncio.get_running_loop().run_in_executor(
            None, self._metrics_body)
        return await self._respond(
            writer, 200, body,
            ctype="text/plain; version=0.0.4; charset=utf-8")

    # ------------------------------------------------------------------ #
    # /debug/flightrec and /debug/profile (docs/observability.md)
    # ------------------------------------------------------------------ #
    async def _debug_flightrec(self, writer):
        """The flight-recorder ring as JSON.  The snapshot never takes
        the engine lock (the ring is self-locked), but it copies up to
        ``flight_recorder_events`` dicts — off the loop thread."""
        snap = await asyncio.get_running_loop().run_in_executor(
            None, self.srv.flightrec_snapshot)
        if snap is None:
            raise _HTTPError(
                404, "flight recorder disabled — set "
                     "serving.flight_recorder (docs/observability.md)")
        return await self._respond(writer, 200, snap)

    async def _debug_profile(self, query, writer):
        """On-demand ``jax.profiler`` capture: blocks an executor
        thread for ``secs`` (clamped to 60), never the loop; one
        capture at a time (409 while one runs)."""
        if not getattr(self.srv.config, "profile_endpoint", False):
            raise _HTTPError(
                404, "profiling endpoint disabled — set "
                     "serving.profile_endpoint (docs/observability.md)")
        import math
        import urllib.parse
        params = urllib.parse.parse_qs(query)
        try:
            secs = float(params.get("secs", ["1"])[0])
        except ValueError:
            raise _HTTPError(400, f"secs must be a number, got "
                                  f"{params.get('secs')!r}")
        if not math.isfinite(secs):      # NaN slips through min/max
            raise _HTTPError(400, f"secs must be finite, got {secs!r}")
        secs = min(max(secs, 0.0), 60.0)

        def capture():
            if not self._profile_lock.acquire(blocking=False):
                raise _HTTPError(409, "a profile capture is already "
                                      "running — retry when it ends")
            try:
                import tempfile
                import jax
                d = tempfile.mkdtemp(prefix="dstpu_profile_")
                jax.profiler.start_trace(d)
                try:
                    time.sleep(secs)
                finally:
                    jax.profiler.stop_trace()
                return d
            finally:
                self._profile_lock.release()

        d = await asyncio.get_running_loop().run_in_executor(
            None, capture)
        return await self._respond(
            writer, 200, {"trace_dir": d, "secs": secs})


def serve_http(srv, **kwargs):
    """Convenience: ``ServingHTTPFrontend(srv, **kwargs).start()``."""
    return ServingHTTPFrontend(srv, **kwargs).start()


__all__ = ["ServingHTTPFrontend", "serve_http"]
