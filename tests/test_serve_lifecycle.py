"""Serve request-lifecycle hardening: HTTP edge cases (chunked request
bodies, keep-alive reuse, header/body limits, slow-loris deadlines,
connection/queue caps) plus graceful draining and the handle-side
backoff/circuit-breaker layer.

Reference intent: uvicorn/h11 give the reference proxy these behaviors for
free (serve/_private/http_proxy.py); a hand-rolled HTTP/1.1 stack must
prove each one (VERDICT weak #5).
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _addr():
    host, _, port = serve.proxy_address().rpartition(":")
    return host, int(port)


def _set_limits(**limits):
    proxy = serve.start_http_proxy()
    ray_tpu.get(proxy.set_limits.remote(**limits))


def _recv_response(sock, timeout=30.0):
    """Read one full HTTP response (status, headers, body) off a socket."""
    sock.settimeout(timeout)
    buf = b""
    while b"\r\n\r\n" not in buf:
        b = sock.recv(4096)
        if not b:
            raise ConnectionError(f"EOF before response head: {buf!r}")
        buf += b
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode("latin1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        headers[k.strip().lower()] = v.strip()
    if "content-length" in headers:
        n = int(headers["content-length"])
        while len(rest) < n:
            b = sock.recv(4096)
            if not b:
                raise ConnectionError("EOF mid-body")
            rest += b
        body = rest[:n]
    elif headers.get("transfer-encoding", "").lower() == "chunked":
        while b"0\r\n\r\n" not in rest:
            b = sock.recv(4096)
            if not b:
                raise ConnectionError("EOF mid-chunked-body")
            rest += b
        body = rest
    else:
        body = rest
    return status, headers, body


def _deploy_echo_size(name="sz", prefix="/sz"):
    @serve.deployment(name="size_of_" + name)
    def size_of(body=None):
        return {"n": len(body) if body is not None else 0}

    serve.run(size_of.bind(), name=name, route_prefix=prefix)


# ---------------------------------------------------------------- HTTP edges


def test_chunked_request_body(serve_cluster):
    """Chunked request bodies decode (incl. chunk extensions + trailers) —
    the old proxy answered 411 (VERDICT weak #5)."""
    _deploy_echo_size()
    host, port = _addr()
    payload = b"x" * 5000
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(
            b"POST /sz HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: application/octet-stream\r\n"
            b"Transfer-Encoding: chunked\r\n\r\n"
        )
        # two data chunks (one with an extension), then terminator+trailer
        s.sendall(b"1000;ext=1\r\n" + payload[:0x1000] + b"\r\n")
        s.sendall(b"388\r\n" + payload[0x1000:] + b"\r\n")
        s.sendall(b"0\r\nX-Trailer: t\r\n\r\n")
        status, _, body = _recv_response(s)
    assert status == 200
    assert json.loads(body)["result"]["n"] == 5000


def test_malformed_chunk_size_400(serve_cluster):
    _deploy_echo_size()
    host, port = _addr()
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(
            b"POST /sz HTTP/1.1\r\nHost: x\r\n"
            b"Transfer-Encoding: chunked\r\n\r\nZZZ\r\n"
        )
        status, _, _ = _recv_response(s)
    assert status == 400


def test_keep_alive_reuse_across_posts(serve_cluster):
    """Several sequential requests ride ONE connection; the proxy must not
    close between them (HTTP/1.1 default keep-alive)."""
    _deploy_echo_size()
    host, port = _addr()
    with socket.create_connection((host, port), timeout=30) as s:
        for i in (1, 17, 400):
            body = b"y" * i
            s.sendall(
                b"POST /sz HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/octet-stream\r\n"
                + f"Content-Length: {i}\r\n\r\n".encode() + body
            )
            status, headers, resp = _recv_response(s)
            assert status == 200
            assert json.loads(resp)["result"]["n"] == i
            assert headers.get("connection") != "close"


def test_oversized_header_431(serve_cluster):
    _deploy_echo_size()
    _set_limits(max_header_bytes=1024)
    host, port = _addr()
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(
            b"GET /sz HTTP/1.1\r\nHost: x\r\nX-Big: " + b"a" * 4096 + b"\r\n\r\n"
        )
        status, headers, _ = _recv_response(s)
        assert status == 431
        assert headers.get("connection") == "close"
        # the hostile connection is closed, not reused
        assert s.recv(4096) == b""


def test_oversized_body_413_content_length(serve_cluster):
    _deploy_echo_size()
    _set_limits(max_body_bytes=1024)
    host, port = _addr()
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(
            b"POST /sz HTTP/1.1\r\nHost: x\r\nContent-Length: 999999\r\n\r\n"
        )
        status, _, _ = _recv_response(s)
    assert status == 413


def test_oversized_body_413_chunked(serve_cluster):
    """Chunked bodies hit the cap as they accumulate — no Content-Length to
    pre-screen, the decoder itself must enforce the limit."""
    _deploy_echo_size()
    _set_limits(max_body_bytes=1024)
    host, port = _addr()
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(
            b"POST /sz HTTP/1.1\r\nHost: x\r\n"
            b"Transfer-Encoding: chunked\r\n\r\n"
        )
        s.sendall(b"800\r\n" + b"z" * 0x800 + b"\r\n")
        s.sendall(b"800\r\n" + b"z" * 0x800 + b"\r\n")
        status, _, _ = _recv_response(s)
    assert status == 413


def test_slow_loris_reaped_others_served(serve_cluster):
    """A client trickling its header is 408-reaped at the deadline while
    well-behaved requests on other connections complete normally."""
    _deploy_echo_size()
    _set_limits(keep_alive_timeout_s=1.0, read_timeout_s=1.0)
    host, port = _addr()

    loris = socket.create_connection((host, port), timeout=30)
    loris.sendall(b"GET /sz HTTP/1.1\r\nHost: x\r\nX-Slow: ")
    t0 = time.time()

    # while the loris trickles, normal requests sail through
    for _ in range(3):
        with urllib.request.urlopen(f"http://{host}:{port}/sz", timeout=30) as r:
            assert r.status == 200
        try:
            loris.sendall(b"a")
        except OSError:
            pass  # already reaped: exactly what the deadline promises
        time.sleep(0.2)

    # the loris connection gets 408 and EOF within a bounded window
    loris.settimeout(10)
    buf = b""
    try:
        while True:
            b = loris.recv(4096)
            if not b:
                break
            buf += b
    except (ConnectionError, OSError):
        pass
    finally:
        loris.close()
    elapsed = time.time() - t0
    assert b"408" in buf.split(b"\r\n")[0], buf[:200]
    assert elapsed < 8.0, f"loris lingered {elapsed:.1f}s"


def test_slow_body_408(serve_cluster):
    """Head arrives whole but the body trickles: the read deadline fires."""
    _deploy_echo_size()
    _set_limits(read_timeout_s=1.0)
    host, port = _addr()
    with socket.create_connection((host, port), timeout=30) as s:
        s.sendall(
            b"POST /sz HTTP/1.1\r\nHost: x\r\nContent-Length: 100\r\n\r\nabc"
        )
        status, _, _ = _recv_response(s, timeout=10)
    assert status == 408


def test_connection_cap_503_retry_after(serve_cluster):
    _deploy_echo_size()
    _set_limits(max_connections=2)
    host, port = _addr()
    held = [socket.create_connection((host, port), timeout=30) for _ in range(2)]
    try:
        time.sleep(0.2)  # let the proxy register both connections
        with socket.create_connection((host, port), timeout=30) as s:
            s.sendall(b"GET /sz HTTP/1.1\r\nHost: x\r\n\r\n")
            status, headers, _ = _recv_response(s)
        assert status == 503
        assert int(headers["retry-after"]) >= 1
    finally:
        for h in held:
            h.close()
    # capacity freed: requests flow again
    time.sleep(0.2)
    with urllib.request.urlopen(f"http://{host}:{port}/sz", timeout=30) as r:
        assert r.status == 200


def test_queued_call_cap_503(serve_cluster):
    """Saturation backpressure: beyond max_queued_calls in-flight replica
    calls, new requests get an immediate 503 + Retry-After instead of
    queueing toward a 504."""

    @serve.deployment
    def slow(x=None):
        time.sleep(1.5)
        return {"ok": True}

    serve.run(slow.bind(), name="slowapp", route_prefix="/slow")
    _set_limits(max_queued_calls=1)
    host, port = _addr()

    statuses = []
    lock = threading.Lock()

    def one():
        try:
            with urllib.request.urlopen(
                f"http://{host}:{port}/slow", timeout=30
            ) as r:
                code = r.status
        except urllib.error.HTTPError as e:
            code = e.code
        except Exception:
            code = -1
        with lock:
            statuses.append(code)

    threads = [threading.Thread(target=one) for _ in range(4)]
    for t in threads:
        t.start()
        time.sleep(0.1)  # stagger: first occupies the single slot
    for t in threads:
        t.join(timeout=60)
    assert statuses.count(200) >= 1, statuses
    assert statuses.count(503) >= 1, statuses
    assert -1 not in statuses, statuses


def test_set_limits_roundtrip(serve_cluster):
    proxy = serve.start_http_proxy()
    ray_tpu.get(proxy.set_limits.remote(max_header_bytes=2048,
                                        retry_after_s=7.0))
    limits = ray_tpu.get(proxy.limits.remote())
    assert limits["max_header_bytes"] == 2048
    assert limits["retry_after_s"] == 7.0
    with pytest.raises(Exception):
        ray_tpu.get(proxy.set_limits.remote(nonsense_knob=1))


# ------------------------------------------------------- backoff + breaker


def test_backoff_is_capped_exponential_with_jitter():
    from ray_tpu._private.config import GLOBAL_CONFIG as cfg
    from ray_tpu.serve.handle import _backoff_s

    base = cfg.serve_handle_backoff_base_s
    cap = cfg.serve_handle_backoff_max_s
    for attempt in range(8):
        expected_cap = min(cap, base * (2 ** attempt))
        for _ in range(20):
            v = _backoff_s(attempt)
            assert expected_cap / 2 <= v <= expected_cap, (attempt, v)


def test_circuit_breaker_state_machine():
    from ray_tpu.serve.handle import _CircuitBreaker

    b = _CircuitBreaker(failure_threshold=3, reset_s=0.3)
    assert b.allow() and not b.is_open
    for _ in range(2):
        b.record_failure()
    assert b.allow()  # below threshold: still closed
    b.record_failure()
    assert b.is_open
    assert not b.allow()  # open: fail fast
    assert b.seconds_until_probe() > 0
    time.sleep(0.35)
    assert b.allow()       # half-open: exactly one probe slot
    assert not b.allow()   # second caller while probing: rejected
    b.record_failure()     # failed probe re-opens a fresh window
    assert not b.allow()
    time.sleep(0.35)
    assert b.allow()
    b.record_success()     # probe succeeded: closed again
    assert not b.is_open and b.allow()


def test_plane_timeout_retries_same_replica_never_trips_breaker(monkeypatch):
    """A PlaneRequestTimeout is a plane blip, not a replica verdict: the
    handle retries the SAME replica once (the replica may hold the answer;
    idempotent re-execution / rid dedup make the duplicate safe) and the
    circuit breaker is never fed a failure."""
    import ray_tpu
    from ray_tpu.exceptions import PlaneRequestTimeout
    from ray_tpu.serve import handle as handle_mod

    handle_mod._reset_breakers()

    retry_log = []

    class FakeMethod:
        def remote(self, method, args, kwargs, model_id=None, ctx=None):
            retry_log.append((method, args, kwargs, model_id))
            return "retry-ref"

    class FakeReplica:
        handle_request = FakeMethod()

    class FakeHandle:
        deployment_name = "Dep"
        method_name = "__call__"
        multiplexed_model_id = ""

    resp = handle_mod.DeploymentResponse(
        "orig-ref", handle=FakeHandle(), call=((7,), {})
    )
    resp.replica = FakeReplica()

    def fake_get(ref, timeout=None):
        if ref == "orig-ref":
            raise PlaneRequestTimeout("handle_request", 9, 3, 1.5)
        return "answer"

    monkeypatch.setattr(ray_tpu, "get", fake_get)
    assert resp.result(timeout_s=5) == "answer"
    assert resp.retries == 1
    assert retry_log == [("__call__", (7,), {}, "")]  # same replica, once
    b = handle_mod.get_breaker("Dep")
    assert not b.is_open and b._consecutive == 0


def test_plane_timeout_exhaustion_releases_probe_not_failure(monkeypatch):
    """Every attempt times out at the plane: the final exception is
    PlaneRequestTimeout and the breaker's failure count stays untouched
    (an unresponsive plane says nothing about deployment health) —
    whereas replica DEATH (retryable error) does feed the breaker."""
    import ray_tpu
    from ray_tpu._private.config import GLOBAL_CONFIG as cfg
    from ray_tpu.exceptions import ActorDiedError, PlaneRequestTimeout
    from ray_tpu.serve import handle as handle_mod

    handle_mod._reset_breakers()
    monkeypatch.setitem(cfg._overrides, "serve_handle_retry_attempts", 1)
    monkeypatch.setitem(cfg._overrides, "serve_handle_backoff_base_s", 0.01)
    monkeypatch.setitem(cfg._overrides, "serve_handle_backoff_max_s", 0.02)

    class FakeMethod:
        def remote(self, method, args, kwargs, model_id=None, ctx=None):
            return "retry-ref"

    class FakeReplica:
        handle_request = FakeMethod()

    def make_handle(exc):
        class FakeHandle:
            deployment_name = "Dep2"
            method_name = "__call__"
            multiplexed_model_id = ""

            def _refresh(self, force=False):
                pass

            def remote(self, *a, **k):
                r = handle_mod.DeploymentResponse("reroute-ref")
                return r

        return FakeHandle()

    def fake_get_always_timeout(ref, timeout=None):
        raise PlaneRequestTimeout("handle_request", 1, 3, 0.5)

    resp = handle_mod.DeploymentResponse(
        "orig-ref", handle=make_handle(None), call=((), {})
    )
    resp.replica = FakeReplica()
    monkeypatch.setattr(ray_tpu, "get", fake_get_always_timeout)
    import pytest as _pytest
    with _pytest.raises(PlaneRequestTimeout):
        resp.result(timeout_s=2)
    b = handle_mod.get_breaker("Dep2")
    assert b._consecutive == 0 and not b.is_open  # plane blips never trip

    # contrast: replica death IS a verdict — the breaker counts it
    def fake_get_died(ref, timeout=None):
        raise ActorDiedError("replica died")

    resp2 = handle_mod.DeploymentResponse(
        "orig-ref", handle=make_handle(None), call=((), {})
    )
    resp2.replica = FakeReplica()
    monkeypatch.setattr(ray_tpu, "get", fake_get_died)
    with _pytest.raises(ActorDiedError):
        resp2.result(timeout_s=2)
    assert b._consecutive == 1
    handle_mod._reset_breakers()


def test_breaker_fails_fast_when_deployment_gone(serve_cluster):
    """After every replica of a deployment is gone, repeated calls trip the
    per-deployment breaker and fail fast with DeploymentUnavailableError —
    no hot-loop against the dead set."""
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.serve.handle import get_breaker

    @serve.deployment(name="Doomed", graceful_shutdown_timeout_s=1.0)
    def doomed(x=None):
        return "alive"

    h = serve.run(doomed.bind(), name="doomedapp")
    assert h.remote().result() == "alive"

    GLOBAL_CONFIG.apply({
        "serve_handle_retry_attempts": 2,
        "serve_handle_backoff_base_s": 0.01,
        "serve_handle_backoff_max_s": 0.05,
        "serve_breaker_failure_threshold": 3,
        "serve_breaker_reset_s": 0.5,
    })
    try:
        serve.delete("doomedapp")
        deadline = time.time() + 10
        saw_unavailable = False
        while time.time() < deadline:
            try:
                h.remote().result(timeout_s=5)
            except serve.DeploymentUnavailableError:
                saw_unavailable = True
                break
            except Exception:
                continue  # drain raced the call; retry
            time.sleep(0.05)
        assert saw_unavailable
        # hammering the dead deployment fails FAST (breaker or drain flag:
        # no remote round-trip, no sleep-retry loop)
        t0 = time.time()
        for _ in range(20):
            with pytest.raises(serve.DeploymentUnavailableError):
                h.remote()
        assert time.time() - t0 < 2.0
        assert get_breaker("Doomed") is not None
    finally:
        GLOBAL_CONFIG._overrides.clear()


# ------------------------------------------------------------- drain paths


def test_downscale_drains_inflight(serve_cluster):
    """Redeploy 3 -> 1 replicas while requests are in flight: every
    in-flight request completes (victims drain before reaping)."""

    @serve.deployment(name="Shrink", num_replicas=3,
                      graceful_shutdown_timeout_s=15.0)
    def work(x):
        time.sleep(1.2)
        return x * 2

    h = serve.run(work.bind(), name="shrinkapp")
    responses = [h.remote(i) for i in range(6)]
    time.sleep(0.2)  # ensure requests are on replicas before the shrink

    @serve.deployment(name="Shrink", num_replicas=1,
                      graceful_shutdown_timeout_s=15.0)
    def work2(x):
        time.sleep(0.1)
        return x * 2

    h2 = serve.run(work2.bind(), name="shrinkapp")
    # old in-flight requests complete (drained, not dropped) or were
    # transparently re-routed by the handle's retry — never lost
    assert [r.result(timeout_s=60) for r in responses] == [0, 2, 4, 6, 8, 10]
    assert h2.remote(7).result(timeout_s=30) == 14
    deadline = time.time() + 30
    while time.time() < deadline:
        if serve.status()["Shrink"]["live"] == 1:
            break
        time.sleep(0.25)
    assert serve.status()["Shrink"]["live"] == 1


def test_deleted_deployment_returns_503_over_http(serve_cluster):
    @serve.deployment(name="Gone", graceful_shutdown_timeout_s=1.0)
    def gone(x=None):
        return {"ok": True}

    serve.run(gone.bind(), name="goneapp", route_prefix="/gone")
    host, port = _addr()
    with urllib.request.urlopen(f"http://{host}:{port}/gone", timeout=30) as r:
        assert r.status == 200
    serve.delete("goneapp")
    # route still exists on the proxy; the deployment is draining/gone ->
    # 503 + Retry-After (NOT a hang, NOT a 500)
    deadline = time.time() + 15
    saw_503 = False
    while time.time() < deadline:
        try:
            urllib.request.urlopen(f"http://{host}:{port}/gone", timeout=10)
        except urllib.error.HTTPError as e:
            if e.code == 503:
                assert int(e.headers["Retry-After"]) >= 1
                saw_503 = True
                break
        time.sleep(0.2)
    assert saw_503


def test_replica_drain_gate_and_stats(serve_cluster):
    """Replica-level drain contract: prepare_to_drain closes the gate (new
    requests raise ReplicaDrainingError), in-flight ones finish, stats
    reports the drain state."""
    from ray_tpu.serve.replica import Replica, ReplicaDrainingError

    r = Replica("d", lambda x: x + 1, (), {})
    assert r.handle_request("__call__", (1,), {}) == 2
    assert r.prepare_to_drain() == 0
    assert r.stats()["draining"] is True
    with pytest.raises(ReplicaDrainingError):
        r.handle_request("__call__", (1,), {})
    assert r.num_ongoing() == 0
