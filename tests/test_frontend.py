"""HTTP frontend tests (ISSUE 16, rebuilt ISSUE 17): the wire contract
of the serving front door — zero-copy decide path, deadline propagation
to a 503 + ``Retry-After`` derived from the LEARNED service-time Ewma
(a cold server admits instead of guessing; the hint is clamped to a
sanity band), malformed-input 400s, queue-depth connection
backpressure, the graceful-drain contract (late submits get a typed
:class:`ServerClosedError`, never a hung future), the keep-alive
HTTP/1.1 loop (one connection, many requests, pipelining, mid-stream
SIGTERM drain -> 503 + ``Connection: close``), and the framed binary
dialect sniffed off the same port."""
import contextlib
import json
import signal
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from rlgpuschedule_tpu.obs import Registry
from rlgpuschedule_tpu.serve import (PolicyServer, ServerClosedError,
                                     next_bucket, start_frontend, wire)
from rlgpuschedule_tpu.serve.batching import DeadlineSheddedError
from rlgpuschedule_tpu.serve.frontend import (DECIDE_PATH, HEALTH_PATH,
                                              RETRY_AFTER_MAX_S,
                                              RETRY_AFTER_MIN_S)

OBS_D, ACT_D = 6, 9


class HostEngine:
    """Host-only engine stand-in: argmax over the observation row, an
    optional real sleep per dispatch so the service-time Ewma learns a
    controllable value."""

    def __init__(self, max_bucket=8, cost_s=0.0):
        self.max_bucket = max_bucket
        self.cost_s = cost_s

    def bucket_for(self, n):
        return next_bucket(n, self.max_bucket)

    def decide(self, obs, mask, stall=None):
        if self.cost_s:
            time.sleep(self.cost_s)
        n = int(np.asarray(obs).shape[0])
        return (np.argmax(np.asarray(obs), axis=-1).astype(np.int32),
                self.bucket_for(n))


def example(seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(OBS_D).astype(np.float32),
            np.ones(ACT_D, bool))


@contextlib.contextmanager
def serving_stack(cost_s=0.0, max_bucket=8, clock=time.perf_counter,
                  **fe_kw):
    reg = Registry()
    server = PolicyServer(HostEngine(max_bucket, cost_s), registry=reg,
                          clock=clock)
    server.start()
    obs, mask = example()
    handle = start_frontend(server, obs, mask, port=0, **fe_kw)
    try:
        yield handle, server, reg, obs, mask
    finally:
        handle.close()


def post(url, body, headers=None, timeout=30):
    req = urllib.request.Request(url, data=body, method="POST",
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, json.loads(r.read())


class TestDecidePath:
    def test_decide_200_round_trip(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            status, headers, payload = post(
                handle.url + DECIDE_PATH, obs.tobytes() + mask.tobytes())
            assert status == 200
            assert payload["action"] == int(np.argmax(obs))
            assert payload["latency_ms"] >= 0
            assert reg.counter("serve_frontend_requests_total").value == 1

    def test_healthz_and_unknown_route(self):
        with serving_stack() as (handle, *_):
            status, payload = get(handle.url + HEALTH_PATH)
            assert status == 200
            assert payload["status"] == "ok"
            assert payload["queue_depth"] == 0
            status, _, payload = post(handle.url + "/nope", b"")
            assert status == 404 and payload["error"] == "unknown route"

    def test_wrong_length_body_is_400_with_expected_bytes(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            want = obs.nbytes + mask.nbytes
            status, _, payload = post(handle.url + DECIDE_PATH, b"x" * 3)
            assert status == 400
            assert f"{want} bytes" in payload["detail"]
            assert reg.counter(
                "serve_frontend_bad_requests_total").value == 1

    def test_bad_deadline_header_is_400(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            for bad in ("junk", "nan", "inf", "-5", "0"):
                status, _, payload = post(
                    handle.url + DECIDE_PATH, body,
                    headers={"X-Deadline-Ms": bad})
                assert status == 400, bad
                assert "X-Deadline-Ms" in payload["detail"]


class TestShedMapping:
    """The satellite contract: wire deadline -> 503 with a finite,
    positive ``Retry-After`` derived from the learned Ewma; a COLD
    server (no service-time observation yet) admits instead."""

    def test_cold_server_admits_deadlined_request(self):
        # the server's clock stands still, so the 1 ms deadline cannot
        # run out in the queue: the only shed left is admission's, the
        # one this contract is about (on the wall clock a loaded machine
        # took over 1 ms to reach the pump's expiry scan)
        with serving_stack(cost_s=0.0, clock=lambda: 0.0) as (
                handle, server, reg, obs, mask):
            assert server.service_time_s() is None      # nothing learned
            status, _, payload = post(
                handle.url + DECIDE_PATH, obs.tobytes() + mask.tobytes(),
                headers={"X-Deadline-Ms": "1"})
            assert status == 200
            assert payload["action"] == int(np.argmax(obs))
            assert reg.counter("serve_frontend_shed_total").value == 0

    def test_shed_503_retry_after_from_learned_ewma(self):
        with serving_stack(cost_s=0.05, max_bucket=1) as (
                handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            status, _, _ = post(handle.url + DECIDE_PATH, body)
            assert status == 200                        # learns svc
            svc = server.service_time_s()
            assert svc is not None and svc > 0
            status, headers, payload = post(
                handle.url + DECIDE_PATH, body,
                headers={"X-Deadline-Ms": "1"})
            assert status == 503
            assert payload["error"] == "shed"
            assert payload["reason"] == "admission"
            assert payload["deadline_ms"] == pytest.approx(1.0)
            retry = float(headers["Retry-After"])
            assert np.isfinite(retry) and retry > 0
            assert retry == pytest.approx(payload["retry_after_s"],
                                          abs=1e-3)
            # one learned service time + the predicted excess wait
            # (queue empty at admission: predicted == one svc)
            assert payload["retry_after_s"] == pytest.approx(
                svc + max(svc - 1e-3, 0.0), rel=1e-6)
            assert reg.counter("serve_frontend_shed_total").value == 1
            assert reg.counter("serve_shed_total").value == 1


class TestBackpressure:
    def test_high_water_pauses_reads_and_all_requests_resolve(self):
        with serving_stack(cost_s=0.02, max_bucket=1, high_water=2,
                           low_water=1) as (handle, server, reg, obs,
                                            mask):
            body = obs.tobytes() + mask.tobytes()
            results = []

            def one():
                results.append(post(handle.url + DECIDE_PATH, body)[0])

            threads = [threading.Thread(target=one) for _ in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert results == [200] * 12
            assert reg.counter(
                "serve_frontend_backpressure_pauses_total").value >= 1


def raw_request(obs, mask, headers=()):
    """One HTTP/1.1 decide request as raw bytes (keep-alive by default
    — urllib always sends ``Connection: close``, so the keep-alive
    tests speak the protocol themselves)."""
    body = obs.tobytes() + mask.tobytes()
    head = [f"POST {DECIDE_PATH} HTTP/1.1", "Host: test",
            f"Content-Length: {len(body)}", *headers]
    return ("\r\n".join(head) + "\r\n\r\n").encode() + body


def read_response(f):
    """Read exactly one framed-by-Content-Length HTTP response off a
    socket file; returns (status, headers, payload)."""
    status_line = f.readline()
    if not status_line:
        raise EOFError("connection closed before a status line")
    status = int(status_line.split()[1])
    headers = {}
    while True:
        line = f.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    body = f.read(int(headers.get("content-length", "0")))
    return status, headers, (json.loads(body) if body else None)


def onehot(i, d=OBS_D):
    x = np.zeros(d, np.float32)
    x[i] = 1.0
    return x


class TestKeepAlive:
    """ISSUE 17 satellite: the persistent-connection HTTP loop."""

    def test_one_connection_serves_many_requests(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s, \
                    s.makefile("rb") as f:
                for i in range(10):
                    s.sendall(raw_request(onehot(i % OBS_D), mask))
                    status, headers, payload = read_response(f)
                    assert status == 200
                    assert headers["connection"] == "keep-alive"
                    assert payload["action"] == i % OBS_D  # FIFO, mine
            assert reg.counter(
                "serve_frontend_requests_total").value == 10

    def test_pipelined_requests_answered_in_order(self):
        """N requests written back-to-back before any read: responses
        come back 1:1, in order — the loop never interleaves or drops."""
        n = 6
        with serving_stack() as (handle, server, reg, obs, mask):
            burst = b"".join(raw_request(onehot(i % OBS_D), mask)
                             for i in range(n))
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s, \
                    s.makefile("rb") as f:
                s.sendall(burst)
                for i in range(n):
                    status, _, payload = read_response(f)
                    assert status == 200
                    assert payload["action"] == i % OBS_D

    def test_client_connection_close_is_honored(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s, \
                    s.makefile("rb") as f:
                s.sendall(raw_request(obs, mask,
                                      ("Connection: close",)))
                status, headers, _ = read_response(f)
                assert status == 200
                assert headers["connection"] == "close"
                assert f.readline() == b""          # server closed it

    def test_bad_request_line_closes_after_400(self):
        """HTTP framing cannot resync after a malformed request line:
        400, ``Connection: close``, EOF — never a hang."""
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s, \
                    s.makefile("rb") as f:
                s.sendall(b"NONSENSE\r\n\r\n")
                status, headers, _ = read_response(f)
                assert status == 400
                assert headers["connection"] == "close"
                assert f.readline() == b""

    def test_mid_stream_sigterm_drains_typed_never_hangs(self):
        """S3 core: a keep-alive client mid-stream when SIGTERM lands
        gets the typed 503 + ``Connection: close`` on its next request
        — a signal to re-resolve, never a hung read."""
        prev = signal.getsignal(signal.SIGTERM)
        try:
            with serving_stack() as (handle, server, reg, obs, mask):
                handle.install_sigterm()
                with socket.create_connection(
                        ("127.0.0.1", handle.port), timeout=30) as s, \
                        s.makefile("rb") as f:
                    s.sendall(raw_request(obs, mask))
                    assert read_response(f)[0] == 200   # mid-stream now
                    signal.raise_signal(signal.SIGTERM)
                    deadline = time.monotonic() + 30
                    while not server.closed:
                        assert time.monotonic() < deadline, \
                            "drain never completed"
                        time.sleep(0.01)
                    s.sendall(raw_request(obs, mask))
                    status, headers, payload = read_response(f)
                    assert status == 503
                    assert payload["error"] == "closed"
                    assert headers["connection"] == "close"
                    assert f.readline() == b""          # then EOF
                assert reg.counter(
                    "serve_frontend_closed_total").value == 1
        finally:
            signal.signal(signal.SIGTERM, prev)


class TestFramedDialect:
    """ISSUE 17 tentpole: the binary frame mode, sniffed off the magic
    on the shared port."""

    def test_framed_round_trip_many_on_one_connection(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                for i in range(8):
                    o = onehot(i % OBS_D)
                    s.sendall(wire.pack_request(o, mask))
                    kind, header, body, meta64, _, _ = wire.recv_frame(s)
                    assert kind == wire.KIND_RESP
                    action = wire.unpack_action(header, body)
                    assert int(np.ravel(action)[0]) == i % OBS_D
                    assert meta64 > 0                   # latency in us
            assert reg.counter(
                "serve_frontend_requests_total").value == 8

    def test_http_and_framed_coexist_on_one_port(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            status, _, payload = post(
                handle.url + DECIDE_PATH, obs.tobytes() + mask.tobytes())
            assert status == 200
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(wire.pack_request(obs, mask))
                kind, header, body, _, _, _ = wire.recv_frame(s)
                assert kind == wire.KIND_RESP
                assert int(np.ravel(
                    wire.unpack_action(header, body))[0]) == \
                    payload["action"]

    def test_descriptor_mismatch_errs_but_keeps_connection(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                # wrong dtype in the descriptor, right body length
                bad = wire.pack_frame(
                    wire.KIND_REQ, b"float64:(6,)|bool:(9,)",
                    obs.tobytes() + mask.tobytes())
                s.sendall(bad)
                kind, header, body, _, _, _ = wire.recv_frame(s)
                assert kind == wire.KIND_ERR
                assert header == b"bad-request"
                assert "descriptor" in json.loads(body)["detail"]
                # the stream is still framed: a good request serves
                s.sendall(wire.pack_request(obs, mask))
                assert wire.recv_frame(s)[0] == wire.KIND_RESP
            assert reg.counter(
                "serve_frontend_bad_requests_total").value == 1

    def test_wrong_kind_errs_and_closes(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(wire.pack_response(np.int32(0), 0.0))
                kind, header, _, _, _, _ = wire.recv_frame(s)
                assert kind == wire.KIND_ERR
                assert header == b"bad-request"
                with pytest.raises(EOFError):
                    wire.recv_frame(s)                  # server hung up

    def test_framed_shed_carries_retry_after_micros(self):
        with serving_stack(cost_s=0.05, max_bucket=1) as (
                handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(wire.pack_request(obs, mask))
                assert wire.recv_frame(s)[0] == wire.KIND_RESP  # learns
                s.sendall(wire.pack_request(obs, mask,
                                            deadline_s=0.001))
                kind, header, body, meta64, _, _ = wire.recv_frame(s)
                assert kind == wire.KIND_ERR
                assert header == b"shed:admission"
                detail = json.loads(body)
                assert detail["retry_after_s"] > 0
                assert meta64 == pytest.approx(
                    detail["retry_after_s"] * 1e6, rel=1e-3)
                assert meta64 >= RETRY_AFTER_MIN_S * 1e6
                # a shed is not terminal: the connection still serves
                s.sendall(wire.pack_request(obs, mask))
                assert wire.recv_frame(s)[0] == wire.KIND_RESP

    def test_framed_drain_is_typed_and_terminal(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(wire.pack_request(obs, mask))
                assert wire.recv_frame(s)[0] == wire.KIND_RESP
                handle.drain()
                s.sendall(wire.pack_request(obs, mask))
                kind, header, _, _, _, _ = wire.recv_frame(s)
                assert kind == wire.KIND_ERR
                assert header == b"closed"
                with pytest.raises(EOFError):
                    wire.recv_frame(s)


class TestRequestCausality:
    """ISSUE 20: the 64-bit request id rides every reply shape on both
    dialects — inbound via ``X-Request-Id`` / the v2 frame field,
    server-minted when absent, echoed even on sheds."""

    def test_http_keepalive_echoes_inbound_id(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s, \
                    s.makefile("rb") as f:
                for rid in (1, 0xABC123, (1 << 62) + 5):
                    s.sendall(raw_request(obs, mask,
                                          (f"X-Request-Id: {rid}",)))
                    status, _, payload = read_response(f)
                    assert status == 200
                    assert payload["request_id"] == rid

    def test_http_mints_distinct_ids_when_absent(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            ids = set()
            for _ in range(4):
                status, _, payload = post(handle.url + DECIDE_PATH, body)
                assert status == 200
                ids.add(payload["request_id"])
            assert len(ids) == 4 and 0 not in ids

    def test_http_bad_request_id_is_400(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            for bad in ("junk", "-3", str(1 << 63)):
                status, _, payload = post(
                    handle.url + DECIDE_PATH, body,
                    headers={"X-Request-Id": bad})
                assert status == 400, bad
                assert "X-Request-Id" in payload["detail"]

    def test_http_shed_echoes_id(self):
        with serving_stack(cost_s=0.05, max_bucket=1) as (
                handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            assert post(handle.url + DECIDE_PATH, body)[0] == 200
            status, _, payload = post(
                handle.url + DECIDE_PATH, body,
                headers={"X-Deadline-Ms": "1",
                         "X-Request-Id": "314159"})
            assert status == 503 and payload["error"] == "shed"
            assert payload["request_id"] == 314159

    def test_framed_echoes_and_mints(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(wire.pack_request(obs, mask, req_id=0x5150))
                kind, _, _, _, _, rid = wire.recv_frame(s)
                assert kind == wire.KIND_RESP and rid == 0x5150
                # id 0 = unassigned: the server mints one and echoes it
                s.sendall(wire.pack_request(obs, mask))
                kind, _, _, _, _, rid = wire.recv_frame(s)
                assert kind == wire.KIND_RESP and rid > 0

    def test_framed_error_frames_echo_id(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                bad = wire.pack_frame(
                    wire.KIND_REQ, b"float64:(6,)|bool:(9,)",
                    obs.tobytes() + mask.tobytes(), req_id=0x77)
                s.sendall(bad)
                kind, header, _, _, _, rid = wire.recv_frame(s)
                assert kind == wire.KIND_ERR
                assert header == b"bad-request" and rid == 0x77

    def test_framed_v1_frame_still_served(self):
        """A legacy client's 24-byte v1 frame decodes on the live port:
        the server mints an id and answers with a v2 response frame."""
        with serving_stack() as (handle, server, reg, obs, mask):
            desc = wire.descriptor(obs) + b"|" + wire.descriptor(mask)
            body = obs.tobytes() + mask.tobytes()
            v1 = wire.PREFIX_V1.pack(wire.MAGIC, 1, wire.KIND_REQ,
                                     len(desc), len(body), 0, 0) \
                + desc + body
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(v1)
                kind, header, rbody, _, _, rid = wire.recv_frame(s)
                assert kind == wire.KIND_RESP and rid > 0
                assert int(np.ravel(
                    wire.unpack_action(header, rbody))[0]) == \
                    int(np.argmax(obs))

    def test_framed_int64_overflow_id_rejected(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            with socket.create_connection(("127.0.0.1", handle.port),
                                          timeout=30) as s:
                s.sendall(wire.pack_request(obs, mask,
                                            req_id=(1 << 63) + 1))
                kind, header, body, _, _, _ = wire.recv_frame(s)
                assert kind == wire.KIND_ERR
                assert header == b"bad-request"
                assert "2**63" in json.loads(body)["detail"]
                # not terminal: the stream stays framed
                s.sendall(wire.pack_request(obs, mask))
                assert wire.recv_frame(s)[0] == wire.KIND_RESP


class TestRetryAfterClamp:
    """ISSUE 17 satellite: the Retry-After hint is clamped to
    [RETRY_AFTER_MIN_S, RETRY_AFTER_MAX_S] — a poisoned or degenerate
    estimator can advertise neither a microsecond retry storm nor an
    hour-long outage."""

    def _exc(self, predicted=None):
        return DeadlineSheddedError("admission", deadline_s=0.001,
                                    waited_s=0.0,
                                    predicted_wait_s=predicted)

    def test_clamp_band_on_degenerate_estimates(self, monkeypatch):
        with serving_stack() as (handle, server, reg, obs, mask):
            fe = handle.frontend
            monkeypatch.setattr(server, "service_time_s", lambda: 1e9)
            assert fe._retry_after_s(self._exc()) == RETRY_AFTER_MAX_S
            monkeypatch.setattr(server, "service_time_s", lambda: 1e-9)
            assert fe._retry_after_s(self._exc()) == RETRY_AFTER_MIN_S
            # cold estimator: 1s fallback, inside the band untouched
            monkeypatch.setattr(server, "service_time_s", lambda: None)
            assert fe._retry_after_s(self._exc()) == 1.0
            # a sane learned value passes through unclamped, plus the
            # predicted excess wait on admission sheds
            monkeypatch.setattr(server, "service_time_s", lambda: 0.25)
            assert fe._retry_after_s(self._exc()) == 0.25
            assert fe._retry_after_s(self._exc(predicted=0.101)) == \
                pytest.approx(0.25 + 0.1)

    def test_wire_shed_retry_after_is_clamped(self, monkeypatch):
        """End-to-end: with a poisoned (huge) estimator the shed 503's
        Retry-After header is the ceiling, not the raw estimate."""
        with serving_stack(cost_s=0.02, max_bucket=1) as (
                handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            assert post(handle.url + DECIDE_PATH, body)[0] == 200
            monkeypatch.setattr(server, "service_time_s", lambda: 1e9)
            status, headers, payload = post(
                handle.url + DECIDE_PATH, body,
                headers={"X-Deadline-Ms": "1"})
            assert status == 503
            assert float(headers["Retry-After"]) == RETRY_AFTER_MAX_S
            assert payload["retry_after_s"] == RETRY_AFTER_MAX_S


class TestDrain:
    def test_drain_refuses_late_work_typed(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            body = obs.tobytes() + mask.tobytes()
            assert post(handle.url + DECIDE_PATH, body)[0] == 200
            handle.drain()
            assert server.closed
            # a straggler submit gets the typed refusal, never a future
            # no dispatcher will resolve
            with pytest.raises(ServerClosedError):
                server.submit(obs, mask)
            # and the listener is gone: connect refused, not a hang
            with pytest.raises((urllib.error.URLError, ConnectionError,
                                OSError)):
                post(handle.url + DECIDE_PATH, body, timeout=5)
            handle.drain()                              # idempotent

    def test_frontend_counts_draining_rejections(self):
        with serving_stack() as (handle, server, reg, obs, mask):
            handle.drain()
            assert reg.counter("serve_frontend_closed_total").value == 0
            assert handle.frontend.draining

    def test_drain_refuses_backlog_connection_never_hangs(self):
        # A client whose TCP handshake completed but which the loop has
        # not yet turned into a transport when drain() runs (kernel
        # accept backlog, or a still-queued asyncio accept task) must
        # STILL get the typed draining refusal — not an orphaned socket
        # that hangs forever. Park the event loop so the connection is
        # guaranteed un-accepted at drain time.
        with serving_stack() as (handle, server, reg, obs, mask):
            handle._loop.call_soon_threadsafe(time.sleep, 0.3)
            time.sleep(0.05)          # the park is now running
            with socket.create_connection(
                    ("127.0.0.1", handle.port), timeout=30) as c:
                handle.drain()
                c.sendall(raw_request(obs, mask))
                c.settimeout(30)
                f = c.makefile("rb")
                status, headers, payload = read_response(f)
                assert status == 503
                assert payload["error"] == "closed"
                assert headers["connection"] == "close"
                assert f.readline() == b""      # server hung up after
