"""The network front door: asyncio HTTP in front of the serving stack.

Everything below ``submit()`` already speaks overload fluently — typed
shedding, adaptive bucketing, engine health — but none of it had ever
faced a socket. This module is the thinnest honest wire layer over
:class:`~.batching.PolicyServer` (single engine or
:class:`~.router.EngineRouter` fleet alike), built so that every
failure the serving tier can produce has ONE well-defined HTTP shape:

- ``POST /v1/decide`` carries one request's observation + action-mask
  bytes raw in the body (shapes/dtypes fixed at construction from an
  example request). The body is read once off the socket and viewed
  **zero-copy** with ``np.frombuffer`` — the first copy of a request's
  bytes is the batch stack itself, exactly like an in-process submit.
- ``X-Deadline-Ms`` propagates the client's latency SLO into the
  admission/shedding path. A shed request returns **503** with a
  ``Retry-After`` derived from the LEARNED service-time Ewma (plus the
  predicted excess wait on admission sheds) — the server tells the
  client how long the queue actually needs, instead of a made-up
  constant.
- **Backpressure is connection-level**: past a queue-depth high-water
  mark the listener simply stops reading sockets (an ``asyncio.Event``
  gate ahead of every read), resuming at low-water — unread bytes pile
  up in kernel buffers and TCP pushes back on the client, so overload
  never manifests as an unbounded server-side queue.
- **Graceful drain** (SIGTERM or :meth:`ServeFrontend.drain`): stop
  accepting connections, let every in-flight request resolve, then
  :meth:`~.batching.PolicyServer.close` the server so late submits get
  a typed :class:`~.batching.ServerClosedError` → **503** — never a
  hung future, never a silently dropped request.

Since ISSUE 17 connections are **persistent**: the HTTP/1.1 loop keeps
the connection alive between requests (``Connection: close`` — from the
client, or from the server on drain refusals — ends it), and the same
port speaks a second, cheaper dialect: a connection whose first 4 bytes
are :data:`~.wire.MAGIC` is **framed** for its whole life
(:mod:`.wire` — length-prefixed v2 frames, 32-byte prefix, descriptor
validated by byte equality, no per-request parse; legacy 24-byte v1
frames still decode). Either way ``np.frombuffer`` stays the only
decode, and the views point straight at the arena slot write inside
``submit`` — one copy, wire to slab.

Request causality (ISSUE 20): every decide carries a 64-bit request id
— inbound via the ``X-Request-Id`` header (HTTP) or the v2 frame's
``req_id`` field, minted by the server when absent — and every reply
shape echoes it (the ``request_id`` JSON field / the response frame's
``req_id``), including sheds, timeouts, and drain refusals. The id is
the join key ``obs.report --request`` uses to reconstruct the request's
full timeline across the bus, the flight log, and the canary ledger.

The listener is stdlib-only (``asyncio.start_server`` + hand-rolled
HTTP/1.1) on purpose: no new dependency, and the protocol surface is
small enough to pin completely in tier-1 tests. gRPC and multi-node
ingestion stay ROADMAP open ends.
"""
from __future__ import annotations

import asyncio
import json
import math
import signal
import socket
import threading
from concurrent.futures import Future
from typing import Any

import numpy as np

from . import wire
from .batching import DeadlineSheddedError, PolicyServer, ServerClosedError

DECIDE_PATH = "/v1/decide"
HEALTH_PATH = "/healthz"

# Retry-After sanity band (ISSUE 17 satellite): below 10ms a retry hint
# is noise (the client's RTT dwarfs it), above 30s it reads as an
# outage, and a poisoned/stale estimator must not be able to advertise
# either extreme.
RETRY_AFTER_MIN_S = 0.01
RETRY_AFTER_MAX_S = 30.0


def _response(status: str, payload: dict,
              extra_headers: "tuple[str, ...]" = (),
              close: bool = False) -> bytes:
    body = json.dumps(payload).encode()
    head = [f"HTTP/1.1 {status}",
            "Content-Type: application/json",
            f"Content-Length: {len(body)}",
            "Connection: close" if close else "Connection: keep-alive",
            *extra_headers]
    return ("\r\n".join(head) + "\r\n\r\n").encode() + body


class _BadRequest(Exception):
    """Malformed wire input; maps to 400 without killing the connection."""


class ServeFrontend:
    """One asyncio HTTP listener over a :class:`PolicyServer`.

    Run it natively with ``await fe.start()`` inside an event loop, or
    from synchronous code via :func:`start_frontend` (dedicated loop
    thread). ``example_obs`` / ``example_mask`` fix the wire schema:
    one request's body is exactly ``obs.nbytes + mask.nbytes`` raw
    bytes in that order, C-contiguous, same dtypes.
    """

    def __init__(self, server: PolicyServer, example_obs: Any,
                 example_mask: Any, host: str = "127.0.0.1",
                 port: int = 0, registry=None,
                 high_water: int = 256, low_water: int = 64,
                 poll_s: float = 0.005, request_timeout_s: float = 120.0,
                 drain_grace_s: float = 30.0):
        if not 0 <= low_water < high_water:
            raise ValueError(f"need 0 <= low_water < high_water, got "
                             f"{low_water} / {high_water}")
        self.server = server
        self.host = host
        self.port = int(port)            # 0 = ephemeral; set by start()
        self.high_water = int(high_water)
        self.low_water = int(low_water)
        self.poll_s = float(poll_s)
        self.request_timeout_s = float(request_timeout_s)
        self.drain_grace_s = float(drain_grace_s)
        obs0 = np.ascontiguousarray(example_obs)
        mask0 = np.ascontiguousarray(example_mask)
        self._obs_shape, self._obs_dtype = obs0.shape, obs0.dtype
        self._mask_shape, self._mask_dtype = mask0.shape, mask0.dtype
        self._obs_nbytes, self._mask_nbytes = obs0.nbytes, mask0.nbytes
        # frame mode validates the request schema by byte equality
        # against this descriptor — one ==, no parse on the hot path
        self._req_descriptor = (wire.descriptor(obs0) + b"|"
                                + wire.descriptor(mask0))
        # pre-size the arena from the wire schema so the first request
        # never pays slab construction mid-traffic
        ensure = getattr(server, "ensure_arena", None)
        if callable(ensure):
            ensure(obs0, mask0)
        self._draining = False
        # strong refs to backlog-refusal tasks (see _refuse_backlog);
        # a done callback prunes each when it finishes
        self._backlog_refusals: "list[asyncio.Task]" = []
        self._inflight = 0
        self._tcp: "asyncio.base_events.Server | None" = None
        self._gate: "asyncio.Event | None" = None       # set = reads flow
        self._idle: "asyncio.Event | None" = None       # set = no inflight
        self._bp_task: "asyncio.Task | None" = None
        reg = registry if registry is not None else server.registry
        self._http_requests = reg.counter(
            "serve_frontend_requests_total",
            "HTTP decide requests read off the wire")
        self._http_shed = reg.counter(
            "serve_frontend_shed_total",
            "HTTP decide requests answered 503 with Retry-After "
            "(deadline shed)")
        self._http_closed = reg.counter(
            "serve_frontend_closed_total",
            "HTTP decide requests refused because the server is "
            "draining/closed")
        self._http_bad = reg.counter(
            "serve_frontend_bad_requests_total",
            "HTTP requests answered 400 (malformed wire input)")
        self._pauses = reg.counter(
            "serve_frontend_backpressure_pauses_total",
            "times the listener stopped reading sockets at the "
            "queue-depth high-water mark")
        self._g_paused = reg.gauge(
            "serve_frontend_paused",
            "1 while socket reads are paused for backpressure")

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    @property
    def draining(self) -> bool:
        return self._draining

    # ---- lifecycle ---------------------------------------------------

    async def start(self) -> int:
        """Bind and serve (returns immediately; the listener runs on
        the current event loop). Returns the bound port."""
        if self._tcp is not None:
            raise RuntimeError("frontend already started")
        self._gate = asyncio.Event()
        self._gate.set()
        self._idle = asyncio.Event()
        self._idle.set()
        self._tcp = await asyncio.start_server(
            self._on_connection, self.host, self.port)
        self.port = self._tcp.sockets[0].getsockname()[1]
        self._bp_task = asyncio.get_running_loop().create_task(
            self._backpressure_loop())
        return self.port

    async def drain(self) -> None:
        """Graceful shutdown: stop accepting, flush in-flight requests,
        then permanently close the policy server so any straggler
        submit raises :class:`ServerClosedError` — the never-a-hung-
        future half of the contract. Idempotent."""
        already = self._draining
        self._draining = True
        if self._tcp is not None:
            # A connection that finished its TCP handshake but is not
            # yet a transport when the listener closes is silently
            # orphaned — the client hangs on a dead socket. Two windows:
            # (a) accepted by the selector, accept-task still queued:
            #     Server.close() makes Server._attach assert (3.12
            #     still does), the error is swallowed and the socket
            #     leaks;
            # (b) still in the kernel accept queue: the listener close
            #     strands it (Linux does NOT reset queued connections).
            # Close both: stop the accept reader FIRST, tick the loop so
            # queued accept tasks attach while the server is still open
            # (their handlers then serve the typed refusal), dup the
            # listening sockets (the accept queue lives on the shared
            # file description), close the listener, and hand every
            # still-queued connection to the normal handler.
            #
            # Server.wait_closed() is NOT awaited: close() has already
            # closed the listening sockets, and since 3.12.1 what
            # wait_closed() waits for is every open CONNECTION to end.
            # An idle keep-alive client is left connected on purpose
            # (its next request gets the typed refusal), so that wait
            # belongs to the clients, not to the drain: it held the
            # drain until the handle's timeout. What the drain owes is
            # below: no request in flight (_idle), within the grace.
            loop = asyncio.get_running_loop()
            for ts in self._tcp.sockets:
                try:
                    loop.remove_reader(ts.fileno())
                except (ValueError, OSError):
                    pass
            await asyncio.sleep(0)
            await asyncio.sleep(0)
            backlog = [ts.dup() for ts in self._tcp.sockets]
            self._tcp.close()
            await self._refuse_backlog(backlog)
        if self._gate is not None:
            # wake paused readers: their next request gets a typed 503
            self._gate.set()
        if self._idle is not None:
            await asyncio.wait_for(self._idle.wait(), self.drain_grace_s)
        if self._bp_task is not None:
            self._bp_task.cancel()   # idempotent; keep the handle
        if not already:
            # PolicyServer.close joins dispatcher threads — off-loop
            await asyncio.to_thread(self.server.close)

    async def _refuse_backlog(self, socks: "list[socket.socket]") -> None:
        """Accept whatever the kernel queued on the (now closed)
        listener and serve each straggler through the normal handler —
        ``_draining`` is already set, so they get the typed 503/ERR
        refusal with ``Connection: close`` instead of dead air. The
        accept pass is non-blocking and the handlers run as loop tasks
        (NOT awaited here — a straggler that connected but never sends
        must not hold the drain hostage in the protocol sniff; it is
        closed when the loop shuts down, which is an EOF to the client,
        not a hang)."""
        for ls in socks:
            ls.setblocking(False)
            while True:
                try:
                    conn, _ = ls.accept()
                except (BlockingIOError, InterruptedError, OSError):
                    break
                reader, writer = await asyncio.open_connection(sock=conn)
                task = asyncio.ensure_future(
                    self._on_connection(reader, writer))
                self._backlog_refusals.append(task)
                task.add_done_callback(self._backlog_refusals.remove)
            ls.close()

    # ---- backpressure ------------------------------------------------

    async def _backpressure_loop(self) -> None:
        """Sample queue depth; gate socket reads between the high- and
        low-water marks (classic hysteresis so the gate cannot flap on
        a depth hovering at one threshold)."""
        assert self._gate is not None
        while not self._draining:
            depth = self.server.queue_depth()
            if self._gate.is_set():
                if depth >= self.high_water:
                    self._gate.clear()
                    self._pauses.inc()
                    self._g_paused.set(1)
            elif depth <= self.low_water:
                self._gate.set()
                self._g_paused.set(0)
            await asyncio.sleep(self.poll_s)

    # ---- connection handling -----------------------------------------

    async def _on_connection(self, reader: asyncio.StreamReader,
                             writer: asyncio.StreamWriter) -> None:
        assert self._gate is not None and self._idle is not None
        try:
            # protocol sniff: a framed connection announces itself with
            # the 4 magic bytes; anything else is HTTP (the sniffed
            # bytes are re-threaded into the request-line parse)
            sniff = b""
            while len(sniff) < len(wire.MAGIC):
                chunk = await reader.read(len(wire.MAGIC) - len(sniff))
                if not chunk:
                    break
                sniff += chunk
            if not sniff:
                return
            if sniff == wire.MAGIC:
                await self._serve_framed(reader, writer, sniff)
            else:
                await self._serve_http(reader, writer, sniff)
        except (asyncio.IncompleteReadError, ConnectionResetError):
            return   # client went away mid-request; nothing to answer
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except (ConnectionResetError, BrokenPipeError):
                pass

    async def _serve_http(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter,
                          prefix: bytes) -> None:
        """HTTP/1.1 keep-alive loop: one connection serves N requests
        until the client asks ``Connection: close``, EOF, or the server
        refuses further work (drain) — refusals carry
        ``Connection: close`` so a well-behaved client re-resolves
        instead of pipelining into a dying socket."""
        while True:
            # connection-level backpressure: do not even READ the
            # next request while the queue is past high-water
            if not self._gate.is_set():
                await self._gate.wait()
            try:
                req = await self._read_request(reader, prefix)
            except _BadRequest as e:
                # the request FRAMING is broken — answer 400 and close,
                # since the stream cannot be resynchronized
                self._http_bad.inc()
                writer.write(_response("400 Bad Request",
                                       {"error": "bad-request",
                                        "detail": str(e)}, close=True))
                await writer.drain()
                return
            prefix = b""
            if req is None:
                return
            try:
                resp, close = await self._handle(*req)
            except _BadRequest as e:
                self._http_bad.inc()
                resp, close = _response("400 Bad Request",
                                        {"error": "bad-request",
                                         "detail": str(e)}), False
            headers = req[2]
            if headers.get("connection", "").lower() == "close":
                if not close:
                    resp = resp.replace(b"Connection: keep-alive",
                                        b"Connection: close", 1)
                close = True
            writer.write(resp)
            await writer.drain()
            if close:
                return

    async def _read_request(self, reader: asyncio.StreamReader,
                            prefix: bytes = b""):
        line = await reader.readline()
        if not line and not prefix:
            return None       # clean EOF between requests
        line = prefix + line
        parts = line.decode("latin-1").split()
        if len(parts) != 3:
            raise _BadRequest("malformed request line")
        method, path = parts[0], parts[1]
        headers: dict[str, str] = {}
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            key, _, val = h.decode("latin-1").partition(":")
            headers[key.strip().lower()] = val.strip()
        try:
            length = int(headers.get("content-length", "0") or "0")
        except ValueError as e:
            raise _BadRequest("bad Content-Length") from e
        body = await reader.readexactly(length) if length > 0 else b""
        return method, path, headers, body

    def _parse_body(self, body: bytes) -> "tuple[Any, Any]":
        """Decode (obs, mask) as read-only **views** over ``body`` —
        never copies; the first copy is the batch stack, same as an
        in-process submit. The views are only safe while ``body`` is
        alive, which submit guarantees by memcpying into the arena slab
        before this frame returns."""
        expected = self._obs_nbytes + self._mask_nbytes
        if len(body) != expected:
            raise _BadRequest(
                f"body must be exactly {expected} bytes "
                f"(obs {self._obs_shape} {self._obs_dtype} + mask "
                f"{self._mask_shape} {self._mask_dtype}), got {len(body)}")
        obs = np.frombuffer(
            body, dtype=self._obs_dtype,
            count=int(np.prod(self._obs_shape, dtype=np.int64)),
        ).reshape(self._obs_shape)
        mask = np.frombuffer(
            body, dtype=self._mask_dtype, offset=self._obs_nbytes,
            count=int(np.prod(self._mask_shape, dtype=np.int64)),
        ).reshape(self._mask_shape)
        return obs, mask

    def _retry_after_s(self, exc: DeadlineSheddedError) -> float:
        """Honest backoff hint: one learned service time (the cost of
        the dispatch that has to finish before the queue moves), plus
        the predicted excess wait on admission sheds. Always finite and
        positive; 1s only when the estimator is still cold (a shed with
        a cold estimator can only be an in-queue expiry — and a
        ``set_active`` weight-swap re-warm RESETS the estimator, so a
        stale pre-swap value can never leak into this hint). Clamped to
        [``RETRY_AFTER_MIN_S``, ``RETRY_AFTER_MAX_S``]: a degenerate
        estimate must not advertise a microsecond retry storm or an
        hour-long outage."""
        svc = self.server.service_time_s()
        retry = svc if svc is not None else 1.0
        if exc.predicted_wait_s is not None:
            retry += max(exc.predicted_wait_s - exc.deadline_s, 0.0)
        return min(max(retry, RETRY_AFTER_MIN_S), RETRY_AFTER_MAX_S)

    async def _decide(self, obs, mask, stall: int,
                      deadline_s: "float | None", req_id: int = 0):
        """The transport-agnostic decide core: submit, await, classify.
        Returns ``(status, payload)`` where status is one of ``"ok"``
        (payload = :class:`~.batching.ServeResult`), ``"shed"``
        (payload = (exc, retry_after_s)), ``"closed"`` (payload = detail
        str), ``"timeout"``. ``req_id`` threads the causality key into
        the server (0 = let ``submit`` mint one)."""
        assert self._idle is not None
        self._inflight += 1
        self._idle.clear()
        try:
            try:
                fut = self.server.submit(obs, mask, stall=stall,
                                         deadline_s=deadline_s,
                                         req_id=req_id)
            except ServerClosedError:
                return "closed", "server is draining"
            try:
                result = await asyncio.wait_for(
                    asyncio.wrap_future(fut), self.request_timeout_s)
            except DeadlineSheddedError as e:
                return "shed", (e, self._retry_after_s(e))
            except ServerClosedError:
                return "closed", "server closed mid-request"
            except asyncio.TimeoutError:
                return "timeout", None
            return "ok", result
        finally:
            self._inflight -= 1
            if self._inflight == 0:
                self._idle.set()

    async def _handle(self, method: str, path: str, headers: dict,
                      body: bytes) -> "tuple[bytes, bool]":
        """One HTTP request -> (response bytes, close-connection flag).
        Drain/closed refusals close: a kept-alive client pipelining
        into a draining server gets the typed 503 AND the signal to
        re-resolve, never a hang."""
        if method == "GET" and path == HEALTH_PATH:
            return _response("200 OK", {
                "status": "draining" if self._draining else "ok",
                "queue_depth": self.server.queue_depth()}), False
        if method != "POST" or path != DECIDE_PATH:
            return _response("404 Not Found", {"error": "unknown route",
                                               "path": path}), False
        self._http_requests.inc()
        if self._draining:
            self._http_closed.inc()
            return _response("503 Service Unavailable",
                             {"error": "closed",
                              "detail": "server is draining"},
                             close=True), True
        obs, mask = self._parse_body(body)
        deadline_s = None
        if "x-deadline-ms" in headers:
            try:
                deadline_s = float(headers["x-deadline-ms"]) / 1e3
            except ValueError as e:
                raise _BadRequest("bad X-Deadline-Ms") from e
            if not (math.isfinite(deadline_s) and deadline_s > 0):
                raise _BadRequest("X-Deadline-Ms must be finite and > 0")
        try:
            stall = int(headers.get("x-stall", "0") or "0")
        except ValueError as e:
            raise _BadRequest("bad X-Stall") from e
        req_id = 0
        if "x-request-id" in headers:
            try:
                req_id = int(headers["x-request-id"], 0)
            except ValueError as e:
                raise _BadRequest("bad X-Request-Id") from e
            if not 0 <= req_id < (1 << 63):
                raise _BadRequest("X-Request-Id must be in [0, 2**63)")
        if not req_id:
            req_id = self.server.mint_request_id()

        status, payload = await self._decide(obs, mask, stall, deadline_s,
                                             req_id)
        if status == "closed":
            self._http_closed.inc()
            return _response("503 Service Unavailable",
                             {"error": "closed", "detail": payload,
                              "request_id": req_id},
                             close=True), True
        if status == "shed":
            exc, retry = payload
            self._http_shed.inc()
            return _response(
                "503 Service Unavailable",
                {"error": "shed", "reason": exc.reason,
                 "deadline_ms": exc.deadline_s * 1e3,
                 "waited_ms": exc.waited_s * 1e3,
                 "retry_after_s": retry,
                 "request_id": req_id},
                (f"Retry-After: {retry:.3f}",)), False
        if status == "timeout":
            return _response("504 Gateway Timeout",
                             {"error": "timeout",
                              "timeout_s": self.request_timeout_s,
                              "request_id": req_id}), False
        result = payload
        import jax
        action = jax.tree.map(lambda x: np.asarray(x).tolist(),
                              result.action)
        return _response("200 OK",
                         {"action": action,
                          "latency_ms": result.latency_s * 1e3,
                          "request_id": req_id}), False

    # ---- frame mode --------------------------------------------------

    async def _read_frame(self, reader: asyncio.StreamReader,
                          preread: bytes = b""):
        # sniff the version byte: v1 prefixes are 24 bytes, v2 are 32
        # (8 extra bytes of req_id) — same logic as wire.recv_frame
        head = preread + await reader.readexactly(
            wire.PREFIX_V1_SIZE - len(preread))
        if head[4] == wire.VERSION:
            head += await reader.readexactly(
                wire.PREFIX_SIZE - wire.PREFIX_V1_SIZE)
        kind, hlen, blen, meta64, meta32, req_id = wire.unpack_prefix(head)
        header = await reader.readexactly(hlen) if hlen else b""
        body = await reader.readexactly(blen) if blen else b""
        return kind, header, body, meta64, meta32, req_id

    async def _serve_framed(self, reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter,
                            sniffed: bytes) -> None:
        """The binary dialect: one persistent connection, N request
        frames, same shedding/drain semantics as HTTP — an ERR frame
        with reason ``closed`` is terminal for the connection, exactly
        like ``Connection: close`` on a 503."""
        preread = sniffed
        while True:
            if not self._gate.is_set():
                await self._gate.wait()
            try:
                frame = await self._read_frame(reader, preread)
            except wire.WireError as e:
                self._http_bad.inc()
                writer.write(wire.pack_error("bad-request",
                                             {"detail": str(e)}))
                await writer.drain()
                return      # framing is lost; the stream cannot resync
            preread = b""
            kind, header, body, meta64, meta32, req_id = frame
            resp, close = await self._handle_frame(kind, header, body,
                                                   meta64, meta32, req_id)
            writer.write(resp)
            await writer.drain()
            if close:
                return

    async def _handle_frame(self, kind: int, header: bytes, body: bytes,
                            meta64: int, meta32: int, req_id: int = 0):
        if kind != wire.KIND_REQ:
            self._http_bad.inc()
            return wire.pack_error(
                "bad-request",
                {"detail": f"expected KIND_REQ, got {kind}"},
                req_id=req_id), True
        if req_id >= (1 << 63):
            # the wire field is uint64 but the causality lane is int64
            # (flight-log column) — reject rather than truncate
            self._http_bad.inc()
            return wire.pack_error(
                "bad-request",
                {"detail": "req_id must be < 2**63"}), False
        self._http_requests.inc()
        if not req_id:
            req_id = self.server.mint_request_id()
        if self._draining:
            self._http_closed.inc()
            return wire.pack_error(
                "closed", {"detail": "server is draining"},
                req_id=req_id), True
        if header != self._req_descriptor:
            self._http_bad.inc()
            return wire.pack_error(
                "bad-request",
                {"detail": f"descriptor mismatch: got {header!r}, "
                           f"serving {self._req_descriptor.decode()}"},
                req_id=req_id), False
        expected = self._obs_nbytes + self._mask_nbytes
        if len(body) != expected:
            self._http_bad.inc()
            return wire.pack_error(
                "bad-request",
                {"detail": f"body must be exactly {expected} bytes, "
                           f"got {len(body)}"},
                req_id=req_id), False
        obs, mask = self._parse_body(body)
        deadline_s = meta64 / 1e6 if meta64 else None
        status, payload = await self._decide(obs, mask, int(meta32),
                                             deadline_s, req_id)
        if status == "closed":
            self._http_closed.inc()
            return wire.pack_error("closed", {"detail": payload},
                                   req_id=req_id), True
        if status == "shed":
            exc, retry = payload
            self._http_shed.inc()
            return wire.pack_error(
                f"shed:{exc.reason}",
                {"deadline_ms": exc.deadline_s * 1e3,
                 "waited_ms": exc.waited_s * 1e3,
                 "retry_after_s": retry},
                retry_after_s=retry, req_id=req_id), False
        if status == "timeout":
            return wire.pack_error(
                "timeout", {"timeout_s": self.request_timeout_s},
                req_id=req_id), False
        result = payload
        return wire.pack_response(np.asarray(result.action),
                                  result.latency_s, req_id=req_id), False


class FrontendHandle:
    """Synchronous handle over a :class:`ServeFrontend` running on its
    own event-loop thread (:func:`start_frontend`). Every wait is
    bounded — a handle can never hang its caller."""

    def __init__(self, frontend: ServeFrontend,
                 loop: asyncio.AbstractEventLoop,
                 thread: threading.Thread):
        self.frontend = frontend
        self._loop = loop
        self._thread = thread
        self._prev_sigterm = None

    @property
    def port(self) -> int:
        return self.frontend.port

    @property
    def url(self) -> str:
        return self.frontend.url

    def drain(self, timeout: float = 60.0) -> None:
        """Run the graceful drain to completion (blocking, bounded)."""
        asyncio.run_coroutine_threadsafe(
            self.frontend.drain(), self._loop).result(timeout=timeout)

    def install_sigterm(self) -> None:
        """SIGTERM → graceful drain (scheduled on the loop thread; the
        signal handler itself never blocks). Main thread only."""
        def _on_sigterm(signum, frame):
            asyncio.run_coroutine_threadsafe(
                self.frontend.drain(), self._loop)
        self._prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)

    def close(self, timeout: float = 60.0) -> None:
        """Drain (if not already) then stop and join the loop thread."""
        try:
            self.drain(timeout=timeout)
        finally:
            if self._prev_sigterm is not None:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
                self._prev_sigterm = None
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)


def start_frontend(server: PolicyServer, example_obs: Any,
                   example_mask: Any, **kw: Any) -> FrontendHandle:
    """Start a :class:`ServeFrontend` on a dedicated event-loop thread
    and block (bounded) until it is bound. Keyword args pass through to
    the :class:`ServeFrontend` constructor."""
    fe = ServeFrontend(server, example_obs, example_mask, **kw)
    loop = asyncio.new_event_loop()
    bound: Future = Future()

    def _frontend_loop():
        asyncio.set_event_loop(loop)
        try:
            port = loop.run_until_complete(fe.start())
        except BaseException as e:   # bind failure must not hang callers
            bound.set_exception(e)
            loop.close()
            return
        bound.set_result(port)
        try:
            loop.run_forever()
        finally:
            # cancel stragglers so close() leaves a clean loop behind
            for task in asyncio.all_tasks(loop):
                task.cancel()
            loop.run_until_complete(
                loop.shutdown_asyncgens())
            loop.close()

    t = threading.Thread(target=_frontend_loop, name="serve-frontend",
                         daemon=True)
    t.start()
    bound.result(timeout=30)
    return FrontendHandle(fe, loop, t)
