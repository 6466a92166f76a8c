"""Continuous batching + token streaming: the serving fast path end to end.

Acceptance (ISSUE 2): concurrent clients' generations provably interleave
within ONE running batch (asserted via the batcher's per-step occupancy
counters), per-token SSE chunks observed on raw sockets, and the drain
semantics — an in-flight generation finishes or is cut at the drain
deadline, a queued-but-unadmitted call is retried on a live replica.
"""

import json
import os
import socket
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.batching import ContinuousBatcher
from ray_tpu.serve.replica import ReplicaDrainingError


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


class FakeEngine:
    """Deterministic pure-python engine: emits '<tag><i>' per step, one
    step per `step_s`. Lets batcher semantics be tested without jax."""

    def __init__(self, step_s=0.0, max_batch_size=4):
        self.step_s = step_s
        self.max_batch_size = max_batch_size
        self.seqs = {}

    def admit(self, slot, req):
        self.seqs[slot] = {"n": 1, "max": int(req.get("max_new_tokens", 5)),
                           "tag": req.get("tag", "t")}
        st = self.seqs[slot]
        return f"{st['tag']}0", st["n"] >= st["max"]

    def step(self, slots):
        if self.step_s:
            time.sleep(self.step_s)
        out = {}
        for s in slots:
            st = self.seqs[s]
            st["n"] += 1
            out[s] = (f"{st['tag']}{st['n'] - 1}", st["n"] >= st["max"])
        return out

    def release(self, slot):
        pass


# ------------------------------------------------------------ batcher unit


def test_batcher_interleaves_and_retires_at_token_granularity():
    b = ContinuousBatcher(FakeEngine(step_s=0.005), max_batch_size=4,
                          batch_wait_timeout_s=0.05)
    try:
        s1 = b.submit(tag="a", max_new_tokens=6)
        s2 = b.submit(tag="b", max_new_tokens=3)
        assert list(s1) == [f"a{i}" for i in range(6)]
        assert list(s2) == [f"b{i}" for i in range(3)]
        occ = b.occupancy_log()
        assert any(n >= 2 for _, n, _ in occ), occ
        # b retired while a kept stepping: a step with a alone AFTER a
        # step they shared — token-granularity retirement, not
        # stop-the-world between generations
        shared = [step for step, n, ids in occ if n == 2]
        solo_a = [step for step, n, ids in occ if n == 1]
        assert shared and solo_a and min(shared) < max(solo_a)

        # admission INTO the running batch: start a long generation, then
        # submit another mid-flight; they must share steps
        s3 = b.submit(tag="c", max_new_tokens=40)
        time.sleep(0.05)
        s4 = b.submit(tag="d", max_new_tokens=3)
        assert list(s4) == ["d0", "d1", "d2"]
        assert len(list(s3)) == 40
        pairs = [set(ids) for _, n, ids in b.occupancy_log() if n >= 2]
        assert any(s3.request_id in p and s4.request_id in p for p in pairs)
    finally:
        b.close()


def test_batcher_drain_cuts_running_and_bounces_queued():
    b = ContinuousBatcher(FakeEngine(step_s=0.01, max_batch_size=1),
                          max_batch_size=1, batch_wait_timeout_s=0.0)
    try:
        running = b.submit(tag="r", max_new_tokens=10**6)
        time.sleep(0.1)
        queued = b.submit(tag="q", max_new_tokens=5)  # no free slot: queued
        b.drain(deadline_s=0.4)
        # post-drain submits are gated outright
        with pytest.raises(ReplicaDrainingError):
            b.submit(tag="x")
        # the queued-but-unadmitted request is bounced with the retryable
        # error (no tokens were generated for it)
        with pytest.raises(ReplicaDrainingError):
            list(queued)
        # the running generation is CUT at the deadline, never orphaned
        t0 = time.monotonic()
        toks = list(running)
        assert time.monotonic() - t0 < 2.0
        assert running.cut and len(toks) > 0
    finally:
        b.close()


def test_batcher_cancel_retires_slot():
    b = ContinuousBatcher(FakeEngine(step_s=0.01, max_batch_size=1),
                          max_batch_size=1, batch_wait_timeout_s=0.0)
    try:
        s1 = b.submit(tag="a", max_new_tokens=10**6)
        time.sleep(0.05)
        s1.cancel()
        deadline = time.time() + 5
        while not s1.finished and time.time() < deadline:
            time.sleep(0.01)
        assert s1.finished
        # the freed slot serves the next request
        s2 = b.submit(tag="b", max_new_tokens=3)
        assert list(s2) == ["b0", "b1", "b2"]
    finally:
        b.close()


# ------------------------------------------------------- end-to-end serving


def _sse_client(host, port, body_obj, out, key):
    """Raw-socket SSE client: records every recv() burst with its arrival
    time so per-token chunked delivery is observable on the wire."""
    s = socket.create_connection((host, int(port)), timeout=60)
    body = json.dumps(body_obj).encode()
    s.sendall(
        b"POST /generate HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    bursts = []
    buf = b""
    t0 = time.monotonic()
    while True:
        data = s.recv(65536)
        if not data:
            break
        bursts.append((time.monotonic() - t0, data))
        buf += data
        if b"0\r\n\r\n" in buf:
            break
    s.close()
    out[key] = (buf, bursts)


def test_generation_e2e_interleaved_sse(serve_cluster):
    """4 concurrent clients against the REAL PagedDecodeEngine (tiny model):
    generations share one running batch (occupancy counters prove it) and
    every token arrives as its own SSE event over chunked transfer."""

    @serve.deployment
    class Gen:
        def __init__(self):
            from ray_tpu.models import CONFIGS, PagedDecodeEngine

            self.engine = PagedDecodeEngine(
                CONFIGS["tiny"], max_batch_size=4, seed=0,
                prefill_buckets=(16,),
            )
            self.batcher = ContinuousBatcher(
                self.engine, max_batch_size=4, batch_wait_timeout_s=0.5
            )

        def __call__(self, body):
            stream = self.batcher.submit(
                tokens=body["tokens"],
                max_new_tokens=body.get("max_new_tokens"),
            )
            return serve.sse_stream(stream)

        def occupancy(self):
            return self.batcher.occupancy_log()

    h = serve.run(Gen.bind(), name="gen", route_prefix="/generate")
    host, port = serve.proxy_address().split(":")

    lengths = {0: 6, 1: 9, 2: 12, 3: 15}
    outs = {}
    threads = [
        threading.Thread(
            target=_sse_client, args=(
                host, port,
                {"tokens": [1 + i] * (5 + i), "max_new_tokens": lengths[i]},
                outs, i,
            )
        )
        for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert set(outs) == {0, 1, 2, 3}, f"clients missing: {set(outs)}"

    for i, (buf, bursts) in outs.items():
        events = [ln for ln in buf.split(b"\n") if ln.startswith(b"data: ")]
        # max_new_tokens data events + the [DONE] terminator
        assert len(events) == lengths[i] + 1, (i, events)
        assert events[-1] == b"data: [DONE]"
        # per-token on the wire: tokens arrived across multiple recv()
        # bursts, not one terminal blob
        data_bursts = [t for t, d in bursts if b"data: " in d]
        assert len(data_bursts) >= 3, (i, bursts)

    occ = h.occupancy.remote().result(timeout_s=10)
    peak = max(n for _, n, _ in occ)
    assert peak >= 2, occ  # provably shared a running batch
    ids_seen = set()
    for _, _, ids in occ:
        ids_seen.update(ids)
    assert len(ids_seen) == 4
    # token-granularity retirement: after the peak step, shorter
    # generations retire while longer ones keep decoding
    peak_step = next(s for s, n, _ in occ if n == peak)
    assert any(s > peak_step and n < peak for s, n, _ in occ), occ


def test_generation_handle_iter_stream(serve_cluster):
    @serve.deployment
    class Gen:
        def __init__(self):
            self.batcher = ContinuousBatcher(
                FakeEngine(), max_batch_size=4, batch_wait_timeout_s=0.0
            )

        def __call__(self, body):
            return serve.sse_stream(self.batcher.submit(**body))

    h = serve.run(Gen.bind(), name="gen_handle")
    resp = h.remote({"tag": "z", "max_new_tokens": 4})
    chunks = list(resp.iter_stream(timeout_s=30))
    assert chunks == [f"data: z{i}\n\n" for i in range(4)] + ["data: [DONE]\n\n"]


def test_generation_drain_cuts_inflight_stream(serve_cluster):
    """PR 1 drain semantics composed with streaming: deleting the app cuts
    an in-flight generation at the drain deadline — the client's SSE
    stream terminates cleanly (event: cut) instead of being orphaned."""

    @serve.deployment(graceful_shutdown_timeout_s=1.5)
    class Gen:
        def __init__(self):
            self.batcher = ContinuousBatcher(
                FakeEngine(step_s=0.05), max_batch_size=4,
                batch_wait_timeout_s=0.0,
            )

        def __call__(self, body):
            return serve.sse_stream(self.batcher.submit(**body))

    serve.run(Gen.bind(), name="gen_drain", route_prefix="/generate")
    host, port = serve.proxy_address().split(":")

    outs = {}
    t = threading.Thread(
        target=_sse_client,
        args=(host, port, {"tag": "long", "max_new_tokens": 10**6}, outs, 0),
    )
    t.start()
    time.sleep(0.6)  # generation demonstrably in flight
    t0 = time.monotonic()
    serve.delete("gen_drain")
    t.join(timeout=20)
    cut_after = time.monotonic() - t0
    assert 0 in outs, "client never finished — stream orphaned by drain"
    buf, _ = outs[0]
    assert b"event: cut" in buf and b"data: [DONE]" in buf, buf[-200:]
    assert buf.endswith(b"0\r\n\r\n")  # clean chunked termination
    assert cut_after < 8.0, cut_after


def test_batch_drain_inflight_completes_queued_retried(serve_cluster):
    """@serve.batch x graceful drain (ISSUE 2 satellite): the batched call
    EXECUTING on a draining replica completes there within
    graceful_shutdown_timeout_s; calls still queued behind it are bounced
    with ReplicaDrainingError and transparently retried on a live replica
    of the new set."""

    @serve.deployment(graceful_shutdown_timeout_s=8.0)
    class Batched:
        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        def __call__(self, items):
            time.sleep(3.0)
            return [{"item": i, "pid": os.getpid()} for i in items]

    h = serve.run(Batched.bind(), name="batched_drain")
    resp_a = h.remote("a")
    time.sleep(0.5)  # a is executing inside the batch fn (3s window)
    resp_b = h.remote("b")
    resp_c = h.remote("c")
    time.sleep(0.1)  # b, c are queued behind a (flusher busy with a)

    # redeploy: new replica set spawns, old set drains
    h = serve.run(Batched.bind(), name="batched_drain")

    a = resp_a.result(timeout_s=30)
    b = resp_b.result(timeout_s=30)
    c = resp_c.result(timeout_s=30)
    assert a["item"] == "a" and b["item"] == "b" and c["item"] == "c"
    # a finished on the OLD (draining) replica; b and c were re-routed to
    # the new set (the retry counter proves the bounce happened)
    assert b["pid"] != a["pid"] and c["pid"] != a["pid"], (a, b, c)
    assert resp_b.retries + resp_c.retries >= 1
