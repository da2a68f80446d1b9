"""Asyncio HTTP/SSE serving surface over the thread-based pump.

Stdlib-only (``asyncio`` + sockets — no third-party server, matching the
repo's no-new-deps stance): :class:`HttpServingServer` exposes

- ``POST /v1/generate`` — submit one request, stream its tokens back as
  Server-Sent Events (``event: start`` with the request id, one
  ``event: token`` per generated token, a terminal ``event: done`` or
  ``event: error``; the connection closes after the stream —
  ``Connection: close`` framing, docs/http.md);
- ``POST /v1/cancel/<request_id>`` — cancel a live stream (the request
  retires at its next sync boundary; the SSE stream terminates with
  ``finish_reason: "cancelled"``);
- ``GET /healthz`` / ``/metrics`` / ``/metrics.json`` — the
  observability endpoints ``apex_tpu.obs.export`` has always served,
  unified on the serving port (``health_doc`` grows an ``http`` block
  and — when the target is a router — the per-replica block).

The robustness contract (the reason this layer exists):

- **Backpressure feeds admission.** The SSE writer acks a token's
  consumption (``StreamHandle.ack``) only after ``await writer.drain()``
  returned for its bytes, so a reader that stalls past the frontend's
  ``backpressure_window`` gets its slot spilled through the preemption
  path — pages into the radix cache, resume on consumption. Pool pages
  are never pinned by a socket.
- **Disconnect-safe streaming.** A watch task reads the connection; EOF
  or a reset cancels the request at the next sync boundary and every
  page frees through the normal retire path.
- **Timeouts map to the deadline machinery.** ``ttft_timeout_s`` is
  folded into ``Request.deadline_ms`` (so a miss counts in
  ``serving.deadline_misses``); wall ``timeout_s`` cancels the stream
  with ``finish_reason: "timeout"``.
- **Overload is explicit.** A router's
  :class:`~apex_tpu.serving.router.OverloadError` (or the server's own
  ``max_queue_depth`` bound) becomes HTTP 429 with ``Retry-After``.
- **Graceful drain.** ``server.drain()`` stops accepting generates
  (503), lets active streams finish (cancelling stragglers at the
  deadline), then the socket closes — the SIGTERM path.

:class:`HttpReplicaClient` is the same transport from the other side: a
frontend-SHAPED client (submit/queue_depth/failure/pump/shutdown plus
engine/tracer shims) that a :class:`~apex_tpu.serving.router.
ReplicaRouter` can supervise exactly like an in-process replica — the
ROADMAP item-3 process boundary in minimal form: router-as-client
against N HTTP replicas, failover folding delivered tokens into the
resubmission, token-identically.

Concurrency coloring (the conc-lint tier checks this file): the event
loop runs on one thread (``serving-http-loop``); coroutines are asyncio
tasks — await points are interleaving points, and the shared server
state that submit/cancel/drain touch from OTHER threads is guarded by a
``threading.Lock`` that is never held across an ``await``
(``conc-await-under-lock``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import socket
import threading
import time
from typing import Dict, Optional

import numpy as np

from apex_tpu.obs import export as obs_export
from apex_tpu.obs import fleet
from apex_tpu.obs.spans import SpanTracer
from apex_tpu.serving.aio import AsyncStreamHandle
from apex_tpu.serving.frontend import ServingError, StreamHandle
from apex_tpu.serving.router import OverloadError
from apex_tpu.serving.scheduler import _RUN_COUNTERS, Request
from apex_tpu.utils import metrics

__all__ = ["HttpServingServer", "HttpReplicaClient"]

_HTTP_COUNTERS = ("requests", "streams", "tokens", "disconnects",
                  "timeouts", "rejected", "cancelled", "errors")


def _json_bytes(doc) -> bytes:
    return (json.dumps(doc, sort_keys=True) + "\n").encode()


class HttpServingServer:
    """One port, one event loop (on its own daemon thread), one serving
    target — a :class:`~apex_tpu.serving.frontend.ServingFrontend` or a
    :class:`~apex_tpu.serving.router.ReplicaRouter` (detected by its
    ``replicas`` attribute; router submits carry the body's
    ``affinity_key``). The server does NOT own the target: start the
    frontend's pump (``frontend.start()``) / the router's supervisor
    before serving, and shut them down after ``server.shutdown()``.

    ``sse_pad_bytes``/``sndbuf`` shrink the transport's elasticity so
    socket backpressure reaches the frontend window quickly — chaos
    scenarios use them; production defaults leave the kernel alone.
    """

    def __init__(self, target, *, host: str = "127.0.0.1", port: int = 0,
                 max_queue_depth: Optional[int] = None,
                 retry_after_s: float = 0.05,
                 default_timeout_s: Optional[float] = None,
                 sse_pad_bytes: int = 0, sndbuf: Optional[int] = None):
        self.target = target
        self.host = host
        self._want_port = port
        self.port: Optional[int] = None
        self.is_router = hasattr(target, "replicas")
        self.max_queue_depth = max_queue_depth
        self.retry_after_s = retry_after_s
        self.default_timeout_s = default_timeout_s
        self.sse_pad_bytes = sse_pad_bytes
        self.sndbuf = sndbuf
        # cross-thread server state: the loop thread, submit-side
        # threads (cancel endpoint bookkeeping), and drain()/close()
        # callers all touch these — one lock, NEVER held across an await
        self._lock = threading.Lock()
        self._streams: Dict[str, StreamHandle] = {}
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server = None
        self._boot_error: Optional[BaseException] = None
        self._C = {name: metrics.counter(f"http.{name}")
                   for name in _HTTP_COUNTERS}
        self._c0 = {name: c.value for name, c in self._C.items()}
        self._g_conns = metrics.gauge("http.connections")
        self._g_streams = metrics.gauge("http.streams_active")
        self._g_unread = metrics.gauge("http.stream_unread")
        self._n_conns = 0

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "HttpServingServer":
        """Bind and serve on a background event-loop thread; returns
        once the port is bound (read it from ``self.port``)."""
        if self._thread is not None:
            raise RuntimeError("server already started")
        ready = threading.Event()
        self._thread = threading.Thread(target=self._run, args=(ready,),
                                        name="serving-http-loop",
                                        daemon=True)
        self._thread.start()
        ready.wait()
        if self._boot_error is not None:
            self._thread.join()
            self._thread = None
            raise self._boot_error
        return self

    def _run(self, ready: threading.Event) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        try:
            self._server = loop.run_until_complete(asyncio.start_server(
                self._handle, self.host, self._want_port))
            self.port = self._server.sockets[0].getsockname()[1]
        except BaseException as exc:     # noqa: BLE001 — boot surface
            self._boot_error = exc
            ready.set()
            loop.close()
            return
        ready.set()
        try:
            loop.run_forever()
        finally:
            # zero-dangling-tasks contract: every connection task is
            # cancelled, awaited, and the loop closed before the thread
            # exits
            pending = asyncio.all_tasks(loop)
            for task in pending:
                task.cancel()
            if pending:
                loop.run_until_complete(asyncio.gather(
                    *pending, return_exceptions=True))
            loop.run_until_complete(loop.shutdown_asyncgens())
            loop.close()

    def drain(self, deadline_s: float = 30.0) -> None:
        """Graceful drain: stop accepting ``/v1/generate`` (503 with
        ``Retry-After``), let active streams finish, cancel the
        stragglers once ``deadline_s`` expires, and return when every
        stream resolved (observability endpoints keep serving)."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + deadline_s
        cancelled = False
        while True:
            with self._lock:
                live = list(self._streams.values())
            if not live:
                return
            if not cancelled and time.monotonic() >= deadline:
                for handle in live:
                    handle.cancel()
                cancelled = True
                deadline = time.monotonic() + max(deadline_s, 1.0)
            if cancelled and time.monotonic() >= deadline:
                return                   # handles cancelled; streams
            #                              resolve at the pump's pace
            time.sleep(0.005)

    def close(self) -> None:
        """Stop the listener, cancel every connection task, stop the
        loop, and join the thread. Live streams terminate (their
        handles are cancelled so the pump releases their pages)."""
        if self._thread is None:
            return
        with self._lock:
            self._draining = True
            live = list(self._streams.values())
        for handle in live:
            handle.cancel()
        loop = self._loop

        def _stop():
            if self._server is not None:
                self._server.close()
            loop.stop()

        loop.call_soon_threadsafe(_stop)
        self._thread.join(timeout=10.0)
        self._thread = None

    def shutdown(self, deadline_s: float = 30.0) -> None:
        """``drain()`` then ``close()`` — the SIGTERM path."""
        self.drain(deadline_s)
        self.close()

    # -- metrics / health ----------------------------------------------------

    def http_counter_deltas(self) -> Dict[str, float]:
        return {name: c.value - self._c0[name]
                for name, c in self._C.items()}

    def _http_block(self) -> dict:
        with self._lock:
            streams = len(self._streams)
            draining = self._draining
            conns = self._n_conns
        return {"streams_active": streams, "draining": draining,
                "connections": conns,
                **{name: int(c.value - self._c0[name])
                   for name, c in self._C.items()}}

    def _queue_depth(self) -> int:
        if self.is_router:
            return sum(rep.frontend.queue_depth
                       for rep in self.target.replicas if rep.alive)
        return self.target.queue_depth

    def _health_doc(self) -> dict:
        if self.is_router:
            doc = obs_export.health_doc(router=self.target)
            eng = self.target.replicas[0].frontend.engine
        else:
            doc = obs_export.health_doc(frontend=self.target)
            eng = self.target.engine
        doc["http"] = self._http_block()
        doc["http"]["eos_token_id"] = eng.eos_token_id
        return doc

    # -- connection handling -------------------------------------------------

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter) -> None:
        with self._lock:
            self._n_conns += 1
            self._g_conns.set(self._n_conns)
        try:
            sock = writer.get_extra_info("socket")
            if sock is not None:
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                if self.sndbuf is not None:
                    sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF,
                                    self.sndbuf)
            if self.sndbuf is not None:
                # make drain() track the kernel, not an elastic user-
                # space buffer — the chaos scenarios' backpressure knob
                writer.transport.set_write_buffer_limits(high=0)
            await self._dispatch(reader, writer)
        except (asyncio.CancelledError, ConnectionError,
                asyncio.IncompleteReadError):
            pass                         # peer went away / shutdown
        finally:
            with self._lock:
                self._n_conns -= 1
                self._g_conns.set(self._n_conns)
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionError, asyncio.CancelledError):
                pass

    async def _dispatch(self, reader, writer) -> None:
        line = await reader.readline()
        if not line:
            return
        try:
            method, path, _ = line.decode("latin-1").split(" ", 2)
        except ValueError:
            await self._resp(writer, 400, _json_bytes(
                {"error": "malformed request line"}))
            return
        headers = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            key, _, val = h.decode("latin-1").partition(":")
            headers[key.strip().lower()] = val.strip()
        body = b""
        clen = int(headers.get("content-length", "0") or 0)
        if clen:
            body = await reader.readexactly(clen)
        path, _, query = path.partition("?")
        if method == "POST" and path == "/v1/generate":
            await self._generate(reader, writer, body, headers)
        elif method == "POST" and path.startswith("/v1/cancel/"):
            await self._cancel(writer, path[len("/v1/cancel/"):])
        elif method == "GET" and path == "/events":
            await self._events(writer, query)
        elif method == "GET" and path == "/healthz":
            await self._resp(writer, 200, _json_bytes(self._health_doc()))
        elif method == "GET" and path in ("/metrics", "/"):
            await self._resp(
                writer, 200, obs_export.prometheus_text().encode(),
                ctype="text/plain; version=0.0.4; charset=utf-8")
        elif method == "GET" and path == "/metrics.json":
            await self._resp(writer, 200,
                             _json_bytes(obs_export.json_snapshot()))
        else:
            await self._resp(writer, 404, _json_bytes(
                {"error": f"no route {method} {path}"}))

    async def _resp(self, writer, status: int, body: bytes,
                    ctype: str = "application/json",
                    extra=()) -> None:
        reason = {200: "OK", 400: "Bad Request", 404: "Not Found",
                  429: "Too Many Requests", 500: "Internal Server Error",
                  503: "Service Unavailable"}.get(status, "?")
        head = [f"HTTP/1.1 {status} {reason}",
                f"Content-Type: {ctype}",
                f"Content-Length: {len(body)}",
                "Connection: close"]
        head.extend(extra)
        writer.write(("\r\n".join(head) + "\r\n\r\n").encode() + body)
        await writer.drain()

    async def _events(self, writer, query: str) -> None:
        """``GET /events?since_seq=N`` — the replica's event ring as an
        incremental, cursor-based read (the federation scrape's second
        endpoint): events past the cursor plus the count the ring
        lapped past it (``dropped`` — the scraper's gap detector)."""
        since = -1
        for part in query.split("&"):
            key, _, val = part.partition("=")
            if key == "since_seq" and val:
                try:
                    since = int(val)
                except ValueError:
                    await self._resp(writer, 400, _json_bytes(
                        {"error": f"since_seq must be an integer, "
                                  f"got {val!r}"}))
                    return
        log = self.target.events if self.is_router \
            else self.target.engine.events
        events, dropped = log.since(since)
        await self._resp(writer, 200, _json_bytes(
            {"kind": "event_log", "capacity": log.capacity,
             "total": log.total, "dropped": dropped,
             "since_seq": since, "events": events}))

    async def _cancel(self, writer, request_id: str) -> None:
        with self._lock:
            handle = self._streams.get(request_id)
        if handle is None:
            await self._resp(writer, 404, _json_bytes(
                {"error": f"no live stream {request_id!r}"}))
            return
        handle.cancel()
        self._C["cancelled"].inc()
        await self._resp(writer, 200, _json_bytes(
            {"ok": True, "request_id": request_id}))

    # -- the generate stream -------------------------------------------------

    def _submit(self, body: dict, headers: Optional[dict] = None):
        """Parse + submit (sync — the frontend's submit path is
        non-blocking bookkeeping). Returns ``(handle, request_id)``;
        raises ValueError (400), OverloadError (429), ServingError
        (503)."""
        prompt = body.get("prompt")
        if not isinstance(prompt, list) or not prompt:
            raise ValueError("body.prompt must be a non-empty token list")
        deadline_ms = body.get("deadline_ms")
        ttft_timeout_s = body.get("ttft_timeout_s")
        if ttft_timeout_s is not None:
            # the TTFT timeout IS a deadline: fold it into the deadline
            # machinery so a miss lands in serving.deadline_misses
            ttft_ms = float(ttft_timeout_s) * 1e3
            deadline_ms = ttft_ms if deadline_ms is None \
                else min(float(deadline_ms), ttft_ms)
        # trace propagation: the traceparent header (or a bare body
        # trace_id) carries the caller's fleet-wide trace into this
        # replica's Request, so the local tracer's spans stitch with
        # the router side's; absent/malformed degrades to a local mint
        # downstream, never to a 400
        trace_id = fleet.parse_traceparent(
            (headers or {}).get("traceparent")) \
            or fleet.parse_traceparent(body.get("trace_id"))
        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(body.get("max_new_tokens", 16)),
                      priority=int(body.get("priority", 0)),
                      deadline_ms=deadline_ms,
                      tpot_slo_ms=body.get("tpot_slo_ms"),
                      trace_id=trace_id)
        if self.max_queue_depth is not None:
            depth = self._queue_depth()
            if depth >= self.max_queue_depth:
                raise OverloadError(
                    f"queue depth {depth} >= {self.max_queue_depth}",
                    retry_after_s=self.retry_after_s)
        request_id = body.get("request_id")
        if request_id is not None:
            try:
                # the frontend contract: ids are ints (they seed the
                # request's sampling stream via fold_in)
                request_id = int(request_id)
            except (TypeError, ValueError):
                raise ValueError(
                    f"request_id must be an integer, got {request_id!r}")
        if self.is_router:
            handle = self.target.submit(
                req, request_id=request_id,
                affinity_key=body.get("affinity_key"))
        else:
            handle = self.target.submit(req, request_id=request_id)
        return handle, str(handle.request_id)

    async def _generate(self, reader, writer, raw: bytes,
                        headers: Optional[dict] = None) -> None:
        self._C["requests"].inc()
        with self._lock:
            draining = self._draining
        if draining:
            await self._resp(
                writer, 503, _json_bytes({"error": "draining"}),
                extra=(f"Retry-After: {max(self.retry_after_s, 1.0):g}",))
            return
        try:
            body = json.loads(raw.decode() or "{}")
            if not isinstance(body, dict):
                raise ValueError("body must be a JSON object")
            handle, rid = self._submit(body, headers)
        except OverloadError as exc:
            self._C["rejected"].inc()
            retry = getattr(exc, "retry_after_s", self.retry_after_s)
            await self._resp(writer, 429,
                             _json_bytes({"error": str(exc),
                                          "retry_after_s": retry}),
                             extra=(f"Retry-After: {retry:g}",))
            return
        except (ValueError, json.JSONDecodeError) as exc:
            await self._resp(writer, 400,
                             _json_bytes({"error": str(exc)}))
            return
        except ServingError as exc:
            await self._resp(writer, 503,
                             _json_bytes({"error": str(exc)}))
            return
        with self._lock:
            self._streams[rid] = handle
            self._g_streams.set(len(self._streams))
        self._C["streams"].inc()
        watcher = None
        try:
            loop = asyncio.get_event_loop()
            ah = AsyncStreamHandle(handle, loop)
            head = ["HTTP/1.1 200 OK",
                    "Content-Type: text/event-stream",
                    "Cache-Control: no-cache",
                    "Connection: close"]
            writer.write(("\r\n".join(head) + "\r\n\r\n").encode())
            await writer.drain()
            watcher = loop.create_task(
                self._watch_disconnect(reader, handle))
            await self._stream_tokens(writer, handle, ah, body, rid)
        finally:
            if watcher is not None:
                watcher.cancel()
            # belt-and-braces: whatever path ended the stream, the
            # handle must not keep pages pinned — cancel is idempotent
            # and a no-op on a finished request
            if not handle.done:
                handle.cancel()
            with self._lock:
                self._streams.pop(rid, None)
                self._g_streams.set(len(self._streams))

    async def _watch_disconnect(self, reader, handle) -> None:
        """Read the (request-complete) connection: EOF or an error means
        the client went away — cancel at the next sync boundary so every
        page frees. Cancelled (by the stream finishing) without ever
        firing on a healthy connection."""
        try:
            await reader.read(1)
        except asyncio.CancelledError:
            raise
        except Exception:                # noqa: BLE001 — reset == gone
            pass
        if not handle.done:
            handle.cancel()
            self._C["disconnects"].inc()

    async def _sse(self, writer, event: str, data: dict) -> None:
        lines = [f"event: {event}", f"data: {json.dumps(data, sort_keys=True)}"]
        if self.sse_pad_bytes:
            lines.append(":" + "p" * self.sse_pad_bytes)
        writer.write(("\n".join(lines) + "\n\n").encode())
        await writer.drain()

    async def _stream_tokens(self, writer, handle, ah, body: dict,
                             rid: str) -> None:
        loop = asyncio.get_event_loop()
        timeout_s = body.get("timeout_s", self.default_timeout_s)
        ttft_timeout_s = body.get("ttft_timeout_s")
        t0 = loop.time()
        wall_dl = t0 + float(timeout_s) if timeout_s is not None else None
        ttft_dl = t0 + float(ttft_timeout_s) \
            if ttft_timeout_s is not None else None
        n = 0
        finish = "stop"
        try:
            # the `start` frame is informational preamble; both in-repo
            # clients key on token/done/error and skip unknown events,
            # per the SSE spec. Kept for curl users and future clients.
            # tpu-lint: disable=contract-endpoint-undocumented -- see above
            await self._sse(writer, "start", {"request_id": rid})
            while True:
                dl = ttft_dl if (n == 0 and ttft_dl is not None) \
                    else wall_dl
                try:
                    if dl is None:
                        tok = await ah.get()
                    else:
                        left = dl - loop.time()
                        if left <= 0:
                            raise asyncio.TimeoutError
                        tok = await asyncio.wait_for(ah.get(), left)
                except asyncio.TimeoutError:
                    finish = "timeout"
                    self._C["timeouts"].inc()
                    handle.cancel()
                    break
                if tok is None:
                    finish = "cancelled" if handle.cancelled else "stop"
                    break
                await self._sse(writer, "token",
                                {"token": tok, "index": n})
                n += 1
                # consumption = the transport accepted the bytes (drain
                # returned). A stalled reader stops this ack, unread()
                # grows, and the frontend spills the slot.
                ah.ack()
                self._C["tokens"].inc()
                self._g_unread.set(handle.unread())
            await self._sse(writer, "done", {
                "request_id": rid, "finish_reason": finish,
                "completion_tokens": n})
        except ServingError as exc:
            self._C["errors"].inc()
            try:
                await self._sse(writer, "error",
                                {"request_id": rid, "error": str(exc)})
            except (ConnectionError, asyncio.CancelledError):
                pass
        except (ConnectionError, asyncio.CancelledError):
            # the peer vanished mid-write — the watcher (or the finally
            # in _generate) cancels the handle; nothing to send to
            raise


# ---------------------------------------------------------------------------
# router-as-client: the frontend-shaped HTTP replica
# ---------------------------------------------------------------------------


class _ClientEngineShim:
    """The slice of the engine surface a
    :class:`~apex_tpu.serving.router.ReplicaRouter` touches on replica
    0: request validation (delegated to the server — a bad request
    fails its stream with 400) and ``eos_token_id`` (for the router's
    resume-request fold)."""

    def __init__(self, eos_token_id=None):
        self.eos_token_id = eos_token_id

    def _validate_request(self, request) -> None:
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class _ClientHandle(StreamHandle):
    """The client-side stream handle: ``cancel()`` additionally tears
    down the socket, which the server's disconnect watcher turns into a
    server-side cancel — the wire form of the in-process contract."""

    def __init__(self, request_id):
        super().__init__(request_id)
        self._sock: Optional[socket.socket] = None

    def cancel(self) -> None:
        super().cancel()
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


class HttpReplicaClient:
    """One remote HTTP replica, wearing the frontend surface the router
    supervises: ``submit`` opens one streaming connection per request on
    a short-lived reader thread, tokens land in a local
    :class:`StreamHandle` (so the router's forwarding/failover reads
    ``tokens_so_far()`` exactly as in-process), and a transport-level
    failure publishes ``failure`` — the supervisor marks the replica
    dead and re-homes its in-flight requests with their delivered
    tokens folded in, token-identically on the survivor.

    Counter aggregation is server-side (scrape ``/metrics``);
    ``counter_deltas()`` reports zeros so ``router.stats()`` stays
    well-formed across the process boundary (docs/http.md Limits)."""

    def __init__(self, host: str, port: int, *, eos_token_id=None,
                 connect_timeout_s: float = 5.0):
        self.host = host
        self.port = port
        self.connect_timeout_s = connect_timeout_s
        self.engine = _ClientEngineShim(eos_token_id)
        self.tracer = SpanTracer()
        self.fault_hook = None
        self._lock = threading.Lock()
        self._live: Dict[object, _ClientHandle] = {}
        self._threads: Dict[object, threading.Thread] = {}
        self._failure: Optional[BaseException] = None
        self._accepting = True
        self._seq = 0

    # -- frontend surface ----------------------------------------------------

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._live)

    @property
    def pump_alive(self) -> bool:
        with self._lock:
            return self._failure is None and self._accepting

    @property
    def failure(self) -> Optional[BaseException]:
        with self._lock:
            return self._failure

    def submit(self, request: Request, *,
               request_id=None) -> StreamHandle:
        self.engine._validate_request(request)
        if request.trace_id is None:
            # a direct client submit mints its own trace id (the router
            # mints before it reaches us) — minted HERE so the wire
            # request carries it and the server tags the same trace
            request = dataclasses.replace(
                request, trace_id=fleet.mint_trace_id())
        with self._lock:
            if self._failure is not None:
                raise ServingError("http replica has failed") \
                    from self._failure
            if not self._accepting:
                raise ServingError("http replica client is shut down")
            if request_id is None:
                request_id = self._seq
            self._seq += 1
            handle = _ClientHandle(request_id)
            self._live[request_id] = handle
            thread = threading.Thread(
                target=self._stream, args=(request, request_id, handle),
                name=f"http-replica-stream-{request_id}", daemon=True)
            self._threads[request_id] = thread
        # the client-side enqueue binds this request to its fleet-wide
        # trace — the span dump this tracer produces is one of the
        # inputs stitch_traces() joins across replicas
        self.tracer.event(request_id, "enqueue",
                          prompt_tokens=int(np.asarray(
                              request.prompt).reshape(-1).shape[0]),
                          max_new_tokens=request.max_new_tokens,
                          priority=request.priority,
                          deadline_ms=request.deadline_ms,
                          trace_id=request.trace_id)
        thread.start()
        return handle

    def pump(self) -> bool:
        """No local pump — the remote server drives itself; report
        whether streams are still in flight so ``router.drain()``
        keeps ticking."""
        with self._lock:
            return bool(self._live)

    def start(self) -> None:
        pass                             # the remote pump is remote

    def stop(self, timeout: Optional[float] = None) -> None:
        pass                             # nothing local to stop

    def counter_deltas(self) -> Dict[str, float]:
        return {name: 0.0 for name in _RUN_COUNTERS}

    # -- fleet scrape (blocking; caller must hold NO lock) --------------------

    def _get_json(self, path: str) -> dict:
        """Blocking GET against the remote replica; returns the parsed
        JSON body.  Raises :class:`ServingError` on connect failure or a
        non-200 status — the fleet collector treats that as a missed
        scrape, not a fatal error."""
        sock = socket.create_connection(
            (self.host, self.port), timeout=self.connect_timeout_s)
        try:
            sock.sendall((f"GET {path} HTTP/1.1\r\n"
                          f"Host: {self.host}:{self.port}\r\n"
                          f"Connection: close\r\n\r\n").encode())
            f = sock.makefile("rb")
            status_line = f.readline().decode("ascii", "replace")
            parts = status_line.split(" ", 2)
            status = int(parts[1]) if len(parts) > 1 else 0
            while True:                  # headers; Connection: close ⇒
                line = f.readline()      # body runs to EOF
                if line in (b"\r\n", b"\n", b""):
                    break
            body = f.read()
            if status != 200:
                raise ServingError(
                    f"scrape GET {path} -> {status}: {body[:200]!r}")
            return json.loads(body.decode())
        finally:
            sock.close()

    def fleet_scrape(self, since_seq: int = -1) -> dict:
        """One federation scrape: the replica's metrics snapshot plus its
        event ring past ``since_seq``.  Shape is consumed by
        :func:`apex_tpu.obs.fleet.FleetCollector.tick`."""
        return {
            "metrics": self._get_json("/metrics.json"),
            "events": self._get_json(f"/events?since_seq={since_seq}"),
        }

    def shutdown(self, deadline_s: float = 30.0, *,
                 mode: str = "drain") -> None:
        with self._lock:
            self._accepting = False
            live = list(self._live.values())
            threads = list(self._threads.values())
        if mode == "cancel":
            for handle in live:
                handle.cancel()
        deadline = time.monotonic() + deadline_s
        for thread in threads:
            thread.join(max(deadline - time.monotonic(), 0.05))
        with self._lock:
            live = list(self._live.values())
        for handle in live:              # stragglers past the deadline
            handle.cancel()
            handle._fail(ServingError(
                "http replica client shutdown with stream unresolved"))

    # -- the per-request stream thread ---------------------------------------

    def _mark_failed(self, exc: BaseException) -> None:
        with self._lock:
            if self._failure is None:
                self._failure = exc if isinstance(exc, ServingError) \
                    else ServingError(f"http replica failed: {exc!r}")

    def _finish_stream(self, request_id) -> None:
        with self._lock:
            self._live.pop(request_id, None)
            self._threads.pop(request_id, None)

    def _stream(self, request, request_id, handle: _ClientHandle) -> None:
        tr = self.tracer
        sock = None
        started_decode = False
        try:
            sock = socket.create_connection(
                (self.host, self.port), timeout=self.connect_timeout_s)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            handle._sock = sock
            body = json.dumps({
                "prompt": [int(t) for t in
                           np.asarray(request.prompt).reshape(-1)],
                "max_new_tokens": int(request.max_new_tokens),
                "priority": int(request.priority),
                "deadline_ms": request.deadline_ms,
                "tpot_slo_ms": request.tpot_slo_ms,
                "request_id": str(request_id),
                "trace_id": request.trace_id,
            }).encode()
            trace_hdr = "" if request.trace_id is None else \
                (f"traceparent: "
                 f"{fleet.traceparent(request.trace_id)}\r\n")
            head = (f"POST /v1/generate HTTP/1.1\r\n"
                    f"Host: {self.host}:{self.port}\r\n"
                    f"Content-Type: application/json\r\n"
                    f"Content-Length: {len(body)}\r\n"
                    f"{trace_hdr}"
                    f"Connection: close\r\n\r\n").encode()
            sock.sendall(head + body)
            sock.settimeout(None)        # SSE streams at the pump's pace
            f = sock.makefile("rb")
            status_line = f.readline().decode("latin-1")
            parts = status_line.split(" ", 2)
            status = int(parts[1]) if len(parts) >= 2 else 0
            while True:                  # skip response headers
                h = f.readline()
                if h in (b"\r\n", b"\n", b""):
                    break
            if status != 200:
                payload = f.read()
                exc = ServingError(
                    f"http replica returned {status}: "
                    f"{payload.decode(errors='replace')[:200]}")
                handle._fail(exc)
                if status not in (400, 429):
                    self._mark_failed(exc)
                return
            ended = False
            for event, data in _iter_sse(f):
                if event == "token":
                    tok = int(data["token"])
                    if not started_decode:
                        started_decode = True
                        tr.event(request_id, "admit", remote=True)
                        tr.event(request_id, "first_token")
                        tr.begin(request_id, "decode")
                    handle._push(tok)
                elif event == "done":
                    if started_decode:
                        tr.end(request_id, "decode",
                               new_tokens=len(handle.tokens_so_far()))
                    tr.event(request_id, "retire",
                             finish_reason=data.get("finish_reason"))
                    handle._finish(np.asarray(handle.tokens_so_far(),
                                              np.int32))
                    ended = True
                    break
                elif event == "error":
                    exc = ServingError(
                        f"remote stream failed: {data.get('error')}")
                    handle._fail(exc)
                    self._mark_failed(exc)
                    ended = True
                    break
            if not ended:
                # connection dropped mid-stream without a terminal event
                raise ConnectionError("stream ended without done/error")
        except Exception as exc:         # noqa: BLE001 — transport edge
            if handle.cancelled and not handle.done:
                # our own cancel tore the socket down — terminate the
                # stream with the truncated output, like in-process
                if started_decode:
                    tr.end(request_id, "decode",
                           new_tokens=len(handle.tokens_so_far()))
                tr.event(request_id, "retire", cancelled=True)
                handle._finish(np.asarray(handle.tokens_so_far(),
                                          np.int32))
            elif not handle.done:
                wrapped = ServingError(
                    f"http replica stream {request_id!r} failed: "
                    f"{exc!r}")
                handle._fail(wrapped)
                self._mark_failed(wrapped)
        finally:
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
            self._finish_stream(request_id)


def _iter_sse(f):
    """Minimal SSE parser over a binary file-like: yields
    ``(event, data_dict)`` per event block; comment lines (padding)
    skipped; returns on EOF."""
    event, data = None, None
    for raw in f:
        line = raw.decode("utf-8", errors="replace").rstrip("\r\n")
        if not line:
            if event is not None and data is not None:
                yield event, json.loads(data)
            event, data = None, None
            continue
        if line.startswith(":"):
            continue
        if line.startswith("event:"):
            event = line[len("event:"):].strip()
        elif line.startswith("data:"):
            data = line[len("data:"):].strip()
