"""One request, one id, one stage clock (serve/telemetry.py: RequestClock):
minted where the request enters, carried beside `model_id` to the replica,
stamped at eight boundaries from the proxy's socket to the first token's
pull, and written to the sinks `profiling.span()` already has.

Everything here runs on the CPU; no duration read here is a device time."""

import dataclasses
import json
import re
import signal
import socket
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import batching, telemetry
from ray_tpu.serve.batching import ContinuousBatcher
from ray_tpu.serve.replica import Replica
from ray_tpu.serve.telemetry import RequestClock, request_scope

STAGES_IN = ("proxy_dispatch", "handle_transit", "replica_presubmit")


@pytest.fixture
def own_timeout():
    """A test that waits on a cluster fails after three minutes instead of
    hanging its worker (the suite runs close to its limit)."""
    def expired(signum, frame):
        raise TimeoutError("the test passed its own three minutes")

    old = signal.signal(signal.SIGALRM, expired)
    signal.alarm(180)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, old)


@pytest.fixture
def serve_cluster(own_timeout):
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


@serve.deployment
class TinyGen:
    """The paged engine at the tiny CPU model behind a ContinuousBatcher,
    streamed as SSE: the shape of the benchmark's deployment."""

    def __init__(self):
        from ray_tpu.models import CONFIGS
        from ray_tpu.models.kv_paging import PagedDecodeEngine

        cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=256)
        eng = PagedDecodeEngine(cfg, max_batch_size=4, seed=0,
                                prefill_buckets=(16,))
        self.batcher = ContinuousBatcher(eng, max_batch_size=4,
                                         batch_wait_timeout_s=0.0)

    def __call__(self, body):
        return serve.sse_stream(self.batcher.submit(
            tokens=body["tokens"], max_new_tokens=body["max_new_tokens"]))


def _sse(host, port, route, body_obj, request_id=None):
    """One streamed request on a raw socket -> (every byte of the
    response, seconds from the send to the first `data:` byte)."""
    body = json.dumps(body_obj).encode()
    head = (f"POST {route} HTTP/1.1\r\nHost: x\r\n"
            "Content-Type: application/json\r\n")
    if request_id is not None:
        head += f"X-Request-Id: {request_id}\r\n"
    head += f"Content-Length: {len(body)}\r\n\r\n"
    with socket.create_connection((host, int(port)), timeout=60) as s:
        t0 = time.time()
        s.sendall(head.encode() + body)
        buf, first = b"", None
        while not buf.endswith(b"0\r\n\r\n"):
            data = s.recv(65536)
            assert data, buf
            buf += data
            if first is None and b"data: " in buf:
                first = time.time() - t0
    return buf, first


def _stage_sums(text):
    """serve_request_stage_s and serve_ttft_s from a /metrics scrape:
    name -> (sum of seconds, count)."""
    out = {}
    for line in text.splitlines():
        m = re.match(r'(serve_request_stage_s|serve_ttft_s)_(sum|count)'
                     r'\{([^}]*)\} (\S+)$', line)
        if m:
            stage = re.search(r'stage="([^"]+)"', m.group(3))
            key = stage.group(1) if stage else "ttft"
            ent = out.setdefault(key, [0.0, 0.0])
            ent[m.group(2) == "count"] += float(m.group(4))
    return {k: tuple(v) for k, v in out.items()}


def _scrape(host, port):
    import http.client

    c = http.client.HTTPConnection(host, int(port), timeout=30)
    c.request("GET", "/metrics")
    text = c.getresponse().read().decode()
    c.close()
    return text


# ------------------------- (a) proxy -> handle -> replica -> batcher, (c)


def test_one_id_from_the_socket_to_the_first_pull(serve_cluster):
    tel = telemetry.get_telemetry(force=True)
    if tel.recorder is not None:
        tel.recorder.clear()  # this process's earlier in-process tests
    handle = serve.run(TinyGen.bind(), name="clock", route_prefix="/gen")
    host, port = serve.proxy_address().split(":")
    body = {"tokens": [3] * 8, "max_new_tokens": 20}
    # compile outside the measured request
    _sse(host, port, "/gen", {"tokens": [5] * 8, "max_new_tokens": 3})
    telemetry.dump_timeline()  # every process pushes its metrics
    before = _stage_sums(_scrape(host, port))

    raw, client_ttft = _sse(host, port, "/gen", body, request_id="req-A1")
    trace = telemetry.dump_timeline()  # also flushes every process's metrics
    after = _stage_sums(_scrape(host, port))

    # the id is telemetry, not payload: head and frames are the parent's
    assert raw.startswith(
        b"HTTP/1.1 200 OK\r\nContent-Type: text/event-stream\r\n"
        b"Transfer-Encoding: chunked\r\n\r\n")
    assert b"req-A1" not in raw
    events = [ln for ln in raw.split(b"\n") if ln.startswith(b"data: ")]
    assert len(events) == 21 and events[-1] == b"data: [DONE]"
    plain, _ = _sse(host, port, "/gen", body)  # no header: the same bytes
    assert plain == raw

    # one request's events share `req` across two processes
    mine = [e for e in trace if e.get("args", {}).get("req") == "req-A1"]
    assert sorted(e["name"] for e in mine) == ["proxy.request", "request"]
    proxy_ev, = [e for e in mine if e["name"] == "proxy.request"]
    replica_ev, = [e for e in mine if e["name"] == "request"]
    assert proxy_ev["pid"] != replica_ev["pid"]
    assert proxy_ev["args"]["status"] == 200
    assert proxy_ev["ts"] <= replica_ev["ts"]
    assert (replica_ev["ts"] + replica_ev["dur"]
            <= proxy_ev["ts"] + proxy_ev["dur"])
    for k in ("dispatch_us", "answer_us", "pull_us"):
        assert proxy_ev["args"][k] >= 0, k
    assert (proxy_ev["args"]["dispatch_us"] + proxy_ev["args"]["answer_us"]
            <= proxy_ev["dur"])

    # every stage once for that request, none negative, and together no
    # longer than what the client itself waited for its first token
    took = {}
    for stage in STAGES_IN + ("first_pull_wait", "return_to_client", "ttft"):
        (s1, n1), (s0, n0) = after[stage], before[stage]
        assert n1 - n0 == 1, stage
        took[stage] = s1 - s0
        assert took[stage] >= 0.0, took
    assert sum(took.values()) <= client_ttft, (took, client_ttft)
    assert sum(took.values()) <= proxy_ev["dur"] / 1e6 + 1e-4

    # (c) a handle caller without the proxy: an id minted by the handle,
    # and none of the proxy's two stages
    toks = list(handle.remote(body).iter_stream(timeout_s=60))
    assert len(toks) == 21
    trace = telemetry.dump_timeline()
    last = _stage_sums(_scrape(host, port))
    minted = [e["args"]["req"] for e in trace if e["name"] == "request"
              and re.fullmatch(r"[0-9a-f]+-\d+", str(e["args"].get("req")))]
    # the two proxy requests without a header, then the handle's
    assert len(minted) == 3 and len(set(minted)) == 3
    assert sum(e["name"] == "proxy.request" for e in trace) == 3
    n = {k: last[k][1] - after[k][1] for k in last}
    # (+ the header-less proxy request above)
    assert n["replica_presubmit"] == n["first_pull_wait"] == n["ttft"] == 2
    assert n["proxy_dispatch"] == n["handle_transit"] == 1
    assert n["return_to_client"] == 1


# ------------------------------------------- in one process, no cluster


class _Engine:
    """Emits '<i>' per step. `chunked`: the admission returns no token
    (the prompt is still streaming in) and the first one comes from a
    step; `preempt_first`: the first step evicts the slot instead."""

    max_batch_size = 2

    def __init__(self, chunked=False, preempt_first=False):
        self.chunked, self.preempt_first = chunked, preempt_first
        self.seqs, self._evicted = {}, []

    def admit(self, slot, req):
        self.seqs[slot] = {"n": 0, "max": int(req["max_new_tokens"]),
                           "req": req}
        if self.chunked:
            return None, False
        return self._next(slot)

    def _next(self, slot):
        st = self.seqs[slot]
        st["n"] += 1
        return str(st["n"] - 1), st["n"] >= st["max"]

    def step(self, slots):
        time.sleep(0.002)
        if self.preempt_first:
            self.preempt_first = False
            self._evicted = [(s, self.seqs.pop(s)["req"]) for s in slots]
            return {}
        return {s: self._next(s) for s in slots}

    def take_preempted(self):
        out, self._evicted = self._evicted, []
        return out

    def release(self, slot):
        pass


def _stage_counts(tel):
    snap = tel.request_stage._snapshot()["values"]
    return {dict(k)["stage"]: e["count"] for k, e in snap.items()}


def test_a_handle_callers_clock_has_no_proxy_stages():
    tel = telemetry.ServeTelemetry(recorder_capacity=64)
    counts0 = _stage_counts(tel)  # the registry is the process's
    b = ContinuousBatcher(_Engine(), batch_wait_timeout_s=0.0, telemetry=tel)
    try:
        ctx = telemetry.outgoing_request()  # what DeploymentHandle.remote sends
        assert re.fullmatch(r"[0-9a-f]+-\d+", ctx.rid)
        assert (ctx.t_recv, ctx.t_call) == (None, None)
        ctx.received()
        with request_scope(ctx):
            assert telemetry.current_request() is ctx
            # a handle called from INSIDE the request forwards the id alone
            onward = telemetry.outgoing_request()
            assert onward is not ctx and onward.rid == ctx.rid
            assert onward.t_replica is None
            stream = b.submit(max_new_tokens=4)
        assert telemetry.current_request() is None
        assert list(stream) == ["0", "1", "2", "3"]
    finally:
        b.close()
    assert stream._clock is ctx
    assert set(ctx.stages_in(stream.t_submit)) == {"replica_presubmit"}
    grew = {k: n - counts0.get(k, 0) for k, n in _stage_counts(tel).items()}
    assert {k: n for k, n in grew.items() if n} == {
        "replica_presubmit": 1, "first_pull_wait": 1}
    ev, = [e for e in tel.recorder.snapshot() if e["name"] == "request"]
    assert ev["args"] == {"rid": stream.request_id, "req": ctx.rid}


def test_the_clock_pickles_as_what_the_caller_stamped():
    import pickle

    ctx = telemetry.new_request("a b#c,d=e" + "x" * 80)
    assert ctx.rid == ("a_b_c_d_e" + "x" * 80)[:64]
    ctx.t_recv, ctx.t_call = 10.0, 10.5
    ctx.received()
    ctx.status, ctx.pull_s = 200, 0.1
    wire = pickle.dumps(ctx)
    assert len(wire) < 160
    got = pickle.loads(wire)
    assert (got.rid, got.t_recv, got.t_call) == (ctx.rid, 10.0, 10.5)
    assert got.t_replica is got.status is got.pull_s is None
    # all five stamps: the three stages before submit, on their own clocks
    stages = ctx.stages_in(ctx.t_replica_mono + 0.25)
    assert stages["proxy_dispatch"] == 0.5
    assert stages["handle_transit"] == ctx.t_replica - 10.5
    assert stages["replica_presubmit"] == pytest.approx(0.25)


def test_a_replica_called_without_a_clock_serves_as_before():
    class Gen:
        def __init__(self):
            self.batcher = ContinuousBatcher(
                _Engine(), batch_wait_timeout_s=0.0, telemetry=False)

        def __call__(self, n):
            return serve.sse_stream(self.batcher.submit(max_new_tokens=n))

    r = Replica("d", Gen, (), {})
    try:
        for ctx in (None, telemetry.new_request("with-clock")):
            sh = r.handle_request("__call__", (3,), {}, ctx=ctx)
            chunks, done = [], False
            deadline = time.monotonic() + 30
            while not done and time.monotonic() < deadline:
                got, done = r.stream_next(sh.stream_id, 64, 0.25)
                chunks += got
            assert chunks == ["data: 0\n\n", "data: 1\n\n", "data: 2\n\n",
                              "data: [DONE]\n\n"]
            assert telemetry.current_request() is None  # reset behind it
    finally:
        r.callable.batcher.close()


def test_a_readmitted_streams_queue_wait_is_from_its_last_enqueue(
        monkeypatch):
    """Preempted before its first token, parked, readmitted: `queue_us` is
    the wait of the READMISSION (as serve_queue_wait_s has it), not the
    time since submit, and the stream still has one first-token span."""
    tel = telemetry.ServeTelemetry(recorder_capacity=64)
    seen = []
    monkeypatch.setattr(batching, "mark",
                        lambda name, **kw: seen.append((name, kw)))
    eng = _Engine(chunked=True, preempt_first=True)
    b = ContinuousBatcher(eng, batch_wait_timeout_s=0.0, telemetry=tel)
    try:
        stream = b.submit(max_new_tokens=3)
        assert list(stream) == ["0", "1", "2"]
    finally:
        b.close()
    assert stream.preempted
    names = [e["name"] for e in tel.recorder.snapshot()]
    assert names.count("request") == 1 and names.count("readmit") == 1
    (_, first), (_, pull) = seen  # one of each, in this order
    assert set(first) == {"slot", "rid", "queue_us", "prefill_us"}
    waited = (stream.t_admit - stream.t_enqueue) * 1e6
    assert first["queue_us"] == int(waited)
    assert stream.t_enqueue > stream.t_submit  # re-stamped at the re-park
    assert first["queue_us"] < (stream.t_first - stream.t_submit) * 1e6
    assert set(pull) == {"rid", "waited_us"} and pull["waited_us"] >= 0


# ---------------------------------------------- (d) nothing per token


class _CountingClock:
    """batching.py's `time`, counting its clock reads."""

    def __init__(self):
        self.reads = {"time": 0, "monotonic": 0}
        self.sleep = time.sleep

    def time(self):
        self.reads["time"] += 1
        return time.time()

    def monotonic(self):
        self.reads["monotonic"] += 1
        return time.monotonic()


@pytest.mark.parametrize("carried", [False, True], ids=["bare", "carried"])
def test_without_telemetry_a_token_costs_the_parents_one_clock_read(
        monkeypatch, carried):
    """At the parent a request costs batching.py three monotonic reads
    (the stream's birth, the admission's start, and `_push` once a token)
    and no wall-clock read; with `telemetry=False` that is still all,
    whether or not the request carries a clock."""
    clock = _CountingClock()
    monkeypatch.setattr(batching, "time", clock)
    b = ContinuousBatcher(_Engine(), batch_wait_timeout_s=0.0,
                          telemetry=False)
    ctx = telemetry.new_request("r") if carried else None
    try:
        for n in (10, 60):
            before = dict(clock.reads)
            with request_scope(ctx):
                stream = b.submit(max_new_tokens=n)
            got = []
            while len(got) < n:  # the proxy's pulls, not the iterator
                items, _ = stream.next_batch(64, 1.0)
                got += items
            assert stream._clock is ctx  # the id rides: it is not telemetry
            assert clock.reads["monotonic"] - before["monotonic"] == n + 2
            assert clock.reads["time"] == 0
    finally:
        b.close()
