"""The serving runner: chip_smoke.py's run_serve / sse_generate, turned from
"four closed-loop clients" into an open loop at the rate fixed in the cell's
file. One thread drives every connection through a selector: requests go
out when they are DUE whether or not earlier ones have finished, and every
time is taken from the due time, not the send time.

Runs in the driver process and never touches JAX: the chip belongs to the
replica."""

from __future__ import annotations

import json
import os
import selectors
import socket
import time

import numpy as np

from benchmark import common, traffic


class _Conn:
    __slots__ = ("req", "sock", "out", "buf", "scan", "sent_at", "token_at",
                 "done", "closed", "status")

    def __init__(self, req, sock, payload):
        self.req, self.sock, self.out = req, sock, payload
        self.buf, self.scan = bytearray(), 0
        self.sent_at, self.token_at = None, []
        self.done = self.closed = False
        self.status = ""


def _payload(path: str, req: dict) -> bytes:
    body = json.dumps({"tokens": req["tokens"],
                       "max_new_tokens": req["max_new_tokens"],
                       "stream": True}).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: x\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def _absorb(c: _Conn, data: bytes, now: float) -> None:
    """Count the complete `data: ` lines that have arrived; each but
    [DONE] is one token, stamped with the time its bytes were read."""
    c.buf += data
    while True:
        nl = c.buf.find(b"\n", c.scan)
        if nl < 0:
            break
        line = bytes(c.buf[c.scan:nl]).strip()
        c.scan = nl + 1
        if not c.status and line.startswith(b"HTTP/"):
            c.status = line.decode(errors="replace")
        if line.startswith(b"data: "):
            if line[6:].strip() == b"[DONE]":
                c.done = True
            else:
                c.token_at.append(now)


def open_loop(address: str, path: str, requests: list, drain_s: float,
              on_tick=None) -> list:
    """Send each request at t0 + due_s; return one record per request.
    `on_tick(elapsed_s)` is called between events (the traced run uses it
    to start and stop the profiler in the replica)."""
    host, port = address.split(":")
    sel = selectors.DefaultSelector()
    payloads = [_payload(path, r) for r in requests]  # built before t0
    conns, live, nxt = [], 0, 0
    t0 = time.monotonic()
    last_due = requests[-1]["due_s"] if requests else 0.0
    while nxt < len(requests) or live:
        now = time.monotonic() - t0
        if nxt >= len(requests) and now > last_due + drain_s:
            break
        if on_tick is not None:
            on_tick(now)
        while nxt < len(requests) and requests[nxt]["due_s"] <= now:
            s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.setblocking(False)
            s.connect_ex((host, int(port)))
            c = _Conn(requests[nxt], s, payloads[nxt])
            conns.append(c)
            sel.register(s, selectors.EVENT_WRITE | selectors.EVENT_READ, c)
            live += 1
            nxt += 1
        wait = 0.05
        if nxt < len(requests):
            wait = min(wait, max(0.0, requests[nxt]["due_s"] - now))
        for key, mask in sel.select(timeout=wait):
            c = key.data
            now = time.monotonic() - t0
            try:
                if mask & selectors.EVENT_WRITE and c.out:
                    n = c.sock.send(c.out)
                    if c.sent_at is None:
                        c.sent_at = now
                    c.out = c.out[n:]
                    if not c.out:
                        sel.modify(c.sock, selectors.EVENT_READ, c)
                if mask & selectors.EVENT_READ:
                    data = c.sock.recv(65536)
                    if data:
                        _absorb(c, data, now)
                    if not data or c.buf.endswith(b"0\r\n\r\n"):
                        c.closed = True
            except (BlockingIOError, InterruptedError):
                continue
            except OSError as e:
                c.status = c.status or f"socket error: {e!r}"
                c.closed = True
            if c.closed:
                sel.unregister(c.sock)
                c.sock.close()
                live -= 1
    for c in conns:
        if not c.closed:
            sel.unregister(c.sock)
            c.sock.close()
    sel.close()
    records = []
    for c in conns:
        want = c.req["max_new_tokens"]
        records.append({
            "due_s": c.req["due_s"], "sent_s": c.sent_at,
            "first_s": c.token_at[0] if c.token_at else None,
            "end_s": c.token_at[-1] if c.token_at else None,
            "token_at": c.token_at, "tokens": len(c.token_at), "want": want,
            "ok": bool(c.done and len(c.token_at) == want
                       and " 200" in c.status),
            "status": c.status,
        })
    # anything never sent (cannot happen unless the loop was cut) failed
    for r in requests[len(conns):]:
        records.append({"due_s": r["due_s"], "sent_s": None, "first_s": None,
                        "end_s": None, "token_at": [], "tokens": 0,
                        "want": r["max_new_tokens"], "ok": False,
                        "status": "never sent"})
    return records


def client_metrics(records: list, seconds: float) -> dict:
    """The client's side of the end-to-end metrics, from the records of
    one window. A failed request has the worst first-token time of the
    run: it is counted, not dropped."""
    due = [r for r in records if r["due_s"] < seconds]
    ttft = [(r["first_s"] - r["due_s"]) * 1e3
            for r in due if r["ok"] and r["first_s"] is not None]
    worst = max(ttft) if ttft else float(seconds) * 1e3
    failed = [r for r in due if not r["ok"]]
    worst = max([worst] + [
        ((r["first_s"] if r["first_s"] is not None else seconds + 60.0)
         - r["due_s"]) * 1e3 for r in failed])
    ttft_all = ttft + [worst] * len(failed)
    gaps = []
    for r in due:
        t = r["token_at"]
        gaps.extend((b - a) * 1e3 for a, b in zip(t, t[1:]))
    tokens_in_window = sum(1 for r in due for t in r["token_at"]
                           if t <= seconds)
    late = [(r["sent_s"] - r["due_s"]) * 1e3 for r in due
            if r["sent_s"] is not None]
    in_flight_end = sum(1 for r in due
                        if r["end_s"] is None or r["end_s"] > seconds)
    return {
        "attempted": len(due), "failed": len(failed),
        "ttft_ms": ttft_all, "itl_ms": gaps, "late_ms": late,
        "tokens_in_window": tokens_in_window,
        "tokens_streamed": sum(r["tokens"] for r in due),
        "in_flight_at_end": in_flight_end,
        "statuses": sorted({r["status"] for r in failed})[:5],
    }


def _deploy(cell, conf, seed):
    from ray_tpu import serve
    from ray_tpu.serve import deployment as serve_deployment
    from ray_tpu.serve import run as serve_run

    from benchmark.server import BenchServer

    name = "bench"
    Dep = serve_deployment(name=name, num_replicas=1)(BenchServer)
    app = Dep.bind(
        common.load_block(conf).transformer_kwargs(conf), conf=conf,
        weights_seed=common.jax_seed(seed),
        engine_kwargs=dict(conf["engine"]), deployment=name,
    )
    handle = serve_run(app, name=name, route_prefix=cell["route"])
    return name, handle, serve.proxy_address()


def _call(method, *a, timeout_s=1200):
    return method.remote(*a).result(timeout_s=timeout_s)


def _warm_up(cell, handle, address, sched, vocab, seed) -> dict:
    """Send the system prompts once (their blocks are then cached, as in a
    replica that has been up for a minute), then one request for every
    prefill program the run's requests can reach; each also takes a few
    decode steps. Nothing may compile after this."""
    path = cell["route"]
    systems = sched["system_prompts"]
    first = [{"due_s": 0.0, "tokens": sp + [1], "max_new_tokens": 4}
             for sp in systems]
    for r in first:  # one at a time: each prefills alone, whole
        rec = open_loop(address, path, [r], drain_s=1200)
        if not rec[0]["ok"]:
            raise RuntimeError(f"warm-up request failed: {rec[0]['status']}")
    pairs = [(len(systems[r["sys"]]), r["user_len"])
             for r in sched["requests"]]
    keys = _call(handle.shape_keys, pairs)
    # fresh user turns: a warm-up that sent a scheduled request's own tokens
    # would leave that request's whole prompt in the prefix cache
    rng = np.random.default_rng([int(seed), 3])
    seen, reps = set(), []
    for r, k in zip(sched["requests"], keys):
        if tuple(k) not in seen:
            seen.add(tuple(k))
            turn = rng.integers(1, vocab, size=r["user_len"]).tolist()
            reps.append({"due_s": 0.0, "max_new_tokens": 4,
                         "tokens": systems[r["sys"]] + turn})
    for r in reps:
        rec = open_loop(address, path, [r], drain_s=1200)
        if not rec[0]["ok"]:
            raise RuntimeError(f"warm-up request failed: {rec[0]['status']}")
    return {"system_prompts": len(first), "shape_requests": len(reps)}


def run_serve(cell: dict, conf: dict, args, rates=None) -> dict:
    """Deploy, warm up, run the window (or, with `rates`, one short window
    per rate: the sweep that finds the knee), check against the reference,
    delete. -> facts."""
    from ray_tpu import serve

    seconds, seed = float(args.seconds), int(args.seed)
    vocab = int(conf["vocab_size"])
    name, handle, address = _deploy(cell, conf, seed)
    facts: dict = {"windows": []}
    try:
        plans = [(r, traffic.build_schedule(cell, vocab, seed, seconds, rate=r))
                 for r in (rates or [None])]
        for _, sched in plans:
            warm = _warm_up(cell, handle, address, sched, vocab, seed)
        facts["warm_up"] = warm
        trace_dir = os.path.join(common.ROOT, "chiprun_out", "trace",
                                 cell["name"])
        for rate, sched in plans:
            tr = {"state": 0, "at": float(cell["trace_at_fraction"]) * seconds,
                  "len": float(cell["trace_seconds"])}

            def on_tick(now, tr=tr):
                if tr["state"] == 0 and now >= tr["at"]:
                    tr["start"] = handle.start_trace.remote(trace_dir)
                    tr["state"] = 1
                elif tr["state"] == 1 and now >= tr["at"] + tr["len"]:
                    tr["start"].result(timeout_s=120)
                    tr["stop"] = handle.stop_trace.remote()
                    tr["state"] = 2

            before = _call(handle.facts)
            setup_done_wall = time.time()
            records = open_loop(
                address, cell["route"], sched["requests"],
                drain_s=float(cell["drain_s"]),
                on_tick=on_tick if args.trace and not rates else None)
            after = _call(handle.facts)
            win = {"rate": rate if rate is not None else cell["rate_per_s"],
                   "setup_done_wall": setup_done_wall,
                   "before": before, "after": after,
                   "client": client_metrics(records, seconds),
                   "drained_s": time.time() - setup_done_wall}
            if tr["state"] == 2:
                tr["stop"].result(timeout_s=300)
                win["trace"] = _call(
                    handle.trace_facts, trace_dir,
                    bool(getattr(args, "describe_trace", False)))
            facts["windows"].append(win)
        n_ref = int(cell["reference_prompts"])
        facts["reference"] = _call(
            handle.reference_check,
            [r["tokens"] for r in plans[0][1]["requests"][:n_ref]],
            int(cell["reference_new_tokens"]), float(cell["logit_tolerance"]))
        facts["final"] = _call(handle.facts)
    finally:
        serve.delete(name)
    return facts
