"""Serving telemetry plane: request-lifecycle metrics + engine flight
recorder with Chrome-trace export.

Reference parity: Ray's per-node metrics agent -> Prometheus pipeline
(python/ray/_private/metrics_agent.py), the Serve request metrics
(serve/_private/metrics_utils.py: serve_request_latency/ttft/queue-wait
families), and `ray timeline` (python/ray/_private/profiling.py) — here
extended down to the DECODE ENGINE: a bounded, lock-cheap ring buffer of
step-level events (admit, prefill_chunk, decode, verify, rollback,
preempt, readmit, retire, eos) with monotonic timestamps and slot ids,
dumpable as Chrome trace-event JSON.

One request, one id: a `RequestClock` is minted where a request enters
(the HTTP proxy, or `DeploymentHandle.remote` for a handle caller), rides
the actor call beside `model_id` and is stamped at eight boundaries from
the proxy's socket to the first token's pull; its stage durations go to
the sinks `profiling.span()` already has (trace attributes, one histogram
family, the flight recorder), where `req` lines one request's events up
across processes.

Three layers, all behind the `serve_telemetry` flag:

  ServeTelemetry   per-process singleton bundling the metric handles
                   (util/metrics.py Counters/Gauges/Histograms, tagged by
                   deployment/replica[/phase/outcome]) and the flight
                   recorder. Engines/batchers take it as `telemetry=`;
                   `False` disables per-instance (zero per-token work),
                   `None` resolves the process singleton per the flag.
  FlightRecorder   deque(maxlen) ring of (ts, name, slot, dur, args)
                   tuples — appends are GIL-atomic, no lock on the hot
                   path; `snapshot()` converts to wall-clock dicts so
                   recorders from many processes merge on one axis.
  dump_timeline()  flush every live replica's recorder to the head
                   (controller fan-out), pull the merged store, convert
                   to Chrome trace events (`ph`/`ts`/`pid`/`tid`), write
                   a chrome://tracing-loadable JSON file. The CLI twin is
                   `python -m ray_tpu.scripts timeline` (which also
                   merges the head's task timeline into the same file).

The recorder is ALSO force-pushed by the paths that precede a post-mortem:
replica drain, batcher close, engine-step faults, and the data-plane
orphaned-request watchdog (protocol.Connection.request) — so the head
holds the last `serve_telemetry_recorder_events` events of a wedged
process even when nobody got to call dump_timeline() in time.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import re
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from ray_tpu.util.metrics import Counter, Gauge, Histogram

# finer-than-default low end: TTFT/inter-token on a warm decode path sit
# in the 1-50ms band; the default boundaries would dump them into 3 buckets
LATENCY_BOUNDARIES = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
]
# the request-path stages sit in the 0.05-5 ms band: three finer buckets
# under the latency families' lowest
STAGE_BOUNDARIES = [0.0001, 0.00025, 0.0005] + LATENCY_BOUNDARIES

# the three stages before `submit` with the `batcher.first_token` span's
# attribute for each, and all of `serve_request_stage_s{stage}`
STAGE_ATTRS = {"proxy_dispatch": "proxy_us", "handle_transit": "ingress_us",
               "replica_presubmit": "replica_us"}
REQUEST_STAGES = tuple(STAGE_ATTRS) + ("first_pull_wait", "return_to_client")


class RequestClock:
    """One request's id and its stamps on the way IN, carried from the
    process where the request entered to the replica that serves it.

    The eight stages of a first token: (1) `proxy.recv` the proxy has read
    the request, (2) `proxy.call` a pool thread is about to call the handle,
    (3) `replica.recv` `Replica.handle_request` begins, (4) `batcher.submit`,
    (5) `batcher.admit`, (6) `batcher.first_token` the first `_push`,
    (7) `batcher.first_pull` a consumer takes it from the stream's queue,
    (8) `proxy.first_write` the first chunk is drained to the client's
    socket. 1-3 live here; 4-6 are the `GenerationStream`'s own monotonic
    `t_submit` / `t_admit` / `t_first`, 7 is read in `next_batch`, 8 in the
    proxy.

    Stamps compared ACROSS a process boundary (1, 2 against 3) are wall
    clock: proxy and replica share a host in every cell the benchmark runs;
    across hosts `handle_transit` includes the two clocks' skew. Stage 3
    also reads the monotonic clock, which 3 -> 4 is measured on. Pickles as
    `(rid, t_recv, t_call)`: what the replica stamps stays in the replica,
    and so does the proxy's bookkeeping of the way back (`status`,
    `answer_s`, `pull_s`, `t_pulled`: see http_proxy.py)."""

    __slots__ = ("rid", "t_recv", "t_call", "t_replica", "t_replica_mono",
                 "status", "answer_s", "pull_s", "t_pulled")

    def __init__(self, rid: str, t_recv: Optional[float] = None,
                 t_call: Optional[float] = None):
        self.rid = rid
        self.t_recv, self.t_call = t_recv, t_call
        self.t_replica = self.t_replica_mono = None
        self.status = self.answer_s = self.pull_s = self.t_pulled = None

    def __reduce__(self):
        return (RequestClock, (self.rid, self.t_recv, self.t_call))

    def received(self) -> None:
        """Stage 3, first line of `Replica.handle_request`. The replica
        cannot know yet which batcher the callable will submit to, so the
        process's flag decides: off, no clock is read."""
        if get_telemetry() is not None:
            self.t_replica = time.time()
            self.t_replica_mono = time.monotonic()

    def stages_in(self, t_submit: float) -> Dict[str, float]:
        """Seconds of each stage before `submit` whose two stamps exist,
        by `REQUEST_STAGES` name: none without stage 3 (telemetry off in
        the replica's process), the proxy's two absent for a handle
        caller."""
        out: Dict[str, float] = {}
        if self.t_replica is None:
            return out
        if self.t_call is not None:
            if self.t_recv is not None:
                out["proxy_dispatch"] = self.t_call - self.t_recv
            out["handle_transit"] = self.t_replica - self.t_call
        out["replica_presubmit"] = t_submit - self.t_replica_mono
        return out


_REQUEST: "contextvars.ContextVar[Optional[RequestClock]]" = (
    contextvars.ContextVar("ray_tpu_serve_request", default=None))
_REQUEST_IDS = itertools.count(1)
_RID_UNSAFE = re.compile(r"[^A-Za-z0-9_.:-]")


def current_request() -> Optional[RequestClock]:
    """The request this thread (or asyncio task) is serving, if any."""
    return _REQUEST.get()


def new_request(rid: str = "") -> RequestClock:
    """A clock under the caller's id (a client's `x-request-id`, cut to 64
    characters a trace attribute can hold) or a minted `<pid hex>-<n>`."""
    rid = _RID_UNSAFE.sub("_", rid)[:64] if rid else ""
    return RequestClock(rid or f"{os.getpid():x}-{next(_REQUEST_IDS)}")


def outgoing_request() -> RequestClock:
    """What a handle sends with a call: the clock the proxy set on this
    thread; from inside a request a replica is serving, that request's id
    alone (its stamps belong to the first hop); else a newly minted one."""
    ctx = _REQUEST.get()
    if ctx is None:
        return new_request()
    if ctx.t_replica is not None:
        return RequestClock(ctx.rid)
    return ctx


@contextlib.contextmanager
def request_scope(ctx: Optional[RequestClock]) -> Iterator[None]:
    """`ctx` is `current_request()` inside the block, on this thread."""
    token = _REQUEST.set(ctx)
    try:
        yield
    finally:
        _REQUEST.reset(token)


class FlightRecorder:
    """Bounded ring of step-level engine events.

    record() is the hot path: one uncontended lock, one tuple build, one
    deque append. Oldest events fall off the end — the recorder is a
    crash/hang post-mortem window, not a complete log. `dur` is seconds
    and dates the event's START at now-dur, so spans nest correctly in
    the trace viewer."""

    def __init__(self, capacity: int = 4096):
        self.capacity = int(capacity)
        self._buf: "deque" = deque(maxlen=self.capacity)
        self.total = 0
        self._seq_lock = threading.Lock()
        # monotonic->wall anchor: events are stamped monotonic (immune to
        # clock steps) and converted once at snapshot so recorders from
        # different processes merge on one wall-clock axis
        self._wall_offset = time.time() - time.monotonic()

    def record(self, name: str, slot: int = -1, dur: float = 0.0,
               args: Optional[Dict[str, Any]] = None) -> None:
        # total doubles as the event's sequence number, which the delta
        # push + head merge key on — minting and appending happen under
        # one (uncontended, ~100ns) lock so two racing recorders (batcher
        # loop + a watchdog thread) can neither duplicate a seq nor
        # append out of order, either of which would silently drop an
        # event from the head's merge
        with self._seq_lock:
            self.total += 1
            self._buf.append(
                (time.monotonic() - dur, name, slot, dur, args, self.total))

    def __len__(self) -> int:
        return len(self._buf)

    @property
    def dropped(self) -> int:
        return max(0, self.total - len(self._buf))

    def clear(self) -> None:
        self._buf.clear()

    def snapshot(self) -> List[Dict[str, Any]]:
        """Wall-clock event dicts, oldest first (safe from any thread:
        list(deque) is atomic)."""
        off = self._wall_offset
        return [
            {"ts": t + off, "name": n, "slot": s, "dur": d, "seq": q,
             **({"args": a} if a else {})}
            for t, n, s, d, a, q in list(self._buf)
        ]


class ServeTelemetry:
    """Metric handles + flight recorder for one process. Handles are
    registry-backed (util/metrics.py), so two instances with the same
    metric names share values; `set_context` stamps deployment/replica
    default tags on everything at replica construction."""

    def __init__(self, recorder_capacity: Optional[int] = None):
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        cap = (int(cfg.serve_telemetry_recorder_events)
               if recorder_capacity is None else int(recorder_capacity))
        self.recorder = FlightRecorder(cap) if cap > 0 else None
        base = ("deployment", "replica")
        self.ttft = Histogram(
            "serve_ttft_s", "time to first generated token",
            boundaries=LATENCY_BOUNDARIES, tag_keys=base)
        self.inter_token = Histogram(
            "serve_inter_token_latency_s",
            "gap between consecutive streamed tokens",
            boundaries=LATENCY_BOUNDARIES, tag_keys=base)
        self.queue_wait = Histogram(
            "serve_queue_wait_s",
            "submit->engine-admission wait (readmissions measure from "
            "their re-enqueue)",
            boundaries=LATENCY_BOUNDARIES, tag_keys=base)
        self.request_latency = Histogram(
            "serve_request_latency_s", "submit->finish generation latency",
            boundaries=LATENCY_BOUNDARIES, tag_keys=base)
        self.engine_step = Histogram(
            "serve_engine_step_s", "engine dispatch latency by phase",
            boundaries=LATENCY_BOUNDARIES, tag_keys=base + ("phase",))
        self.requests = Counter(
            "serve_requests_total", "finished generations by outcome",
            tag_keys=base + ("outcome",))
        self.preemptions = Counter(
            "serve_preemptions_total",
            "generations evicted under KV-pool pressure", tag_keys=base)
        self.tokens = Counter(
            "serve_tokens_total", "tokens streamed to consumers",
            tag_keys=base)
        self.kv_util = Gauge(
            "serve_kv_pool_utilization",
            "live fraction of the paged KV block pool", tag_keys=base)
        self.occupancy = Gauge(
            "serve_batch_occupancy",
            "slots active in the last engine step", tag_keys=base)
        self.spec_accept = Gauge(
            "serve_spec_accept_rate",
            "speculative drafts accepted / proposed (cumulative)",
            tag_keys=base)
        # cluster-wide KV plane (serve/kv_transfer.py): cross-replica
        # prefix traffic, by direction ("export" = bytes packed for a
        # peer, "import" = bytes pulled and installed locally)
        self.kv_transfer_bytes = Counter(
            "serve_kv_transfer_bytes_total",
            "cross-replica KV block bytes by direction",
            tag_keys=base + ("direction",))
        self.kv_transfer_hits = Counter(
            "serve_kv_transfer_hits_total",
            "remote prefix pulls that installed blocks locally",
            tag_keys=base)
        self.prefix_remote_hit_rate = Gauge(
            "serve_prefix_remote_hit_rate",
            "remote pulls installed / remote pulls attempted (cumulative)",
            tag_keys=base)
        # live weight plane (serve/weight_swap.py): the version the
        # engine is CURRENTLY serving — advances mid-stream on a hot swap
        self.weight_version = Gauge(
            "serve_weight_version",
            "learner weight version the replica's engine is serving",
            tag_keys=base)
        # the request path outside submit -> first token (which stay
        # serve_queue_wait_s and serve_ttft_s): observed once a request, in
        # the replica at the first token and its first pull, in the proxy
        # (`return_to_client`) at the first chunk's write
        self.request_stage = Histogram(
            "serve_request_stage_s",
            "a request's way to its first token by stage: proxy_dispatch "
            "(proxy has the request -> pool thread calls the handle), "
            "handle_transit (-> replica has the call), replica_presubmit "
            "(-> batcher.submit), first_pull_wait (first token pushed -> a "
            "pull takes it), return_to_client (the pull's reply at the "
            "proxy -> first chunk written)",
            boundaries=STAGE_BOUNDARIES, tag_keys=base + ("stage",))
        self._all = [
            self.request_stage,
            self.ttft, self.inter_token, self.queue_wait,
            self.request_latency, self.engine_step, self.requests,
            self.preemptions, self.tokens, self.kv_util, self.occupancy,
            self.spec_accept, self.kv_transfer_bytes, self.kv_transfer_hits,
            self.prefix_remote_hit_rate, self.weight_version,
        ]
        self._last_push = 0.0
        self._last_push_total = -1  # recorder.total at the last push
        self._rebuild_phase_keys()

    def _rebuild_phase_keys(self) -> None:
        # precomputed observe keys for the per-step phase histogram: the
        # engine hot loop must not pay a dict merge + sort per dispatch
        self._phase_keys = {
            p: self.engine_step.tags_key({"phase": p})
            for p in ("prefill", "decode", "verify")
        }
        self._stage_keys = {
            s: self.request_stage.tags_key({"stage": s})
            for s in REQUEST_STAGES
        }

    def observe_stage(self, stage: str, dur: float) -> None:
        # a wall-clock pair across hosts can read negative by their skew
        self.request_stage.observe_key(max(0.0, dur), self._stage_keys[stage])

    def observe_phase(self, phase: str, dur: float) -> None:
        self.engine_step.observe_key(dur, self._phase_keys[phase])

    def set_context(self, deployment: str = "", replica: str = "") -> None:
        tags = {}
        if deployment:
            tags["deployment"] = deployment
        if replica:
            tags["replica"] = replica
        for m in self._all:
            m.set_default_tags(tags)
        self._rebuild_phase_keys()

    # -------------------------------------------------- cross-process push

    def flush_events(self, force: bool = False) -> None:
        """Throttled DELTA push of the flight-recorder ring to the head
        (the metrics-push channel's sibling: `push_serve_events`). Must
        never break the workload. Only events past the last pushed seq go
        on the wire — a busy replica must not re-serialize its whole
        4096-event ring every interval, and an idle one (no new events)
        pushes nothing; the head appends by seq (`_h_push_serve_events`),
        so already-delivered events survive there past the local ring."""
        if self.recorder is None or not len(self.recorder):
            return
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        now = time.monotonic()
        if not force:
            if now - self._last_push < float(cfg.serve_telemetry_push_s):
                return
            if self.recorder.total == self._last_push_total:
                return
        self._last_push = now
        try:
            from ray_tpu._private.worker import global_worker

            if global_worker.connected:
                snap = self.recorder.snapshot()
                if self._last_push_total > 0:
                    snap = [e for e in snap
                            if e["seq"] > self._last_push_total]
                if not snap:
                    return
                node = getattr(global_worker, "node_id", None) or "node"
                global_worker.send({
                    "t": "push_serve_events",
                    "proc": f"{node}:pid-{os.getpid()}",
                    "events": snap,
                    "dropped": self.recorder.dropped,
                })
                self._last_push_total = snap[-1]["seq"]
        except Exception:
            pass


_TEL: Optional[ServeTelemetry] = None
_TEL_FLAG_OFF = False  # singleton was force-built while the flag was off
_TEL_LOCK = threading.Lock()


def get_telemetry(force: bool = False) -> Optional[ServeTelemetry]:
    """The process singleton; None when `serve_telemetry` is off (pass
    force=True to build one regardless — benches that compare on vs off).
    A force-built singleton under a disabled flag stays invisible to
    non-forced callers: one bench row must not re-enable telemetry for
    every later telemetry=None engine in the same process."""
    global _TEL, _TEL_FLAG_OFF
    if _TEL is None:
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        enabled = bool(cfg.serve_telemetry)
        if not force and not enabled:
            return None
        with _TEL_LOCK:
            if _TEL is None:
                _TEL = ServeTelemetry()
                _TEL_FLAG_OFF = not enabled
    if _TEL_FLAG_OFF and not force:
        return None
    return _TEL


def resolve(telemetry) -> Optional[ServeTelemetry]:
    """The engine/batcher `telemetry=` contract: None -> process singleton
    per the flag, False -> off for this instance, anything else passes
    through (tests inject their own)."""
    if telemetry is None:
        return get_telemetry()
    if telemetry is False:
        return None
    return telemetry


def set_context(deployment: str = "", replica: str = "") -> None:
    tel = get_telemetry()
    if tel is not None:
        tel.set_context(deployment, replica)


def flush_events(force: bool = False) -> None:
    tel = _TEL
    if tel is not None:
        tel.flush_events(force=force)


def flush_to_head() -> bool:
    """Force-push this process's flight recorder and metrics to the head
    and wait for them to land: `dump_timeline()`'s fan-out target in every
    replica and proxy, also called on drain."""
    try:
        from ray_tpu.util import metrics

        flush_events(force=True)
        metrics.flush()
        # pushes are fire-and-forget on the worker socket: a round trip
        # behind them barriers delivery, so a dump_timeline() reading the
        # head right after this fan-out returns sees these events.
        # BOUNDED: this sits on the drain path, and a wedged head must not
        # park a replica's reap forever
        try:
            from ray_tpu._private.worker import global_worker

            global_worker.request({"t": "ping"}, timeout=10)
        except Exception:
            pass
        return True
    except Exception:
        return False


def record_orphaned_request(mtype: str, rid: int, tag: str = "") -> None:
    """Data-plane watchdog hook (protocol.Connection.request): a request
    with no reply past the warn deadline lands in BOTH planes — the
    `data_plane_orphaned_requests_total` counter (scrapable at /metrics)
    and a flight-recorder instant next to whatever the engine was doing —
    then force-flushes so the head holds the evidence at hang time."""
    try:
        from ray_tpu.util import metrics

        metrics.data_plane_orphaned_counter().inc(
            tags={"kind": tag or str(mtype)})
        tel = get_telemetry()
        if tel is not None and tel.recorder is not None:
            tel.recorder.record(
                "orphaned_request",
                args={"mtype": str(mtype), "rid": int(rid), "tag": tag},
            )
            tel.flush_events(force=True)
        metrics.flush()
    except Exception:
        pass  # telemetry must never break the data plane


def record_request_recovered(mtype: str, rid: int, attempts: int) -> None:
    """The self-healing counterpart of record_orphaned_request: a
    retransmitted plane request got its reply. Lands in
    `data_plane_requests_recovered_total` and as a `request_recovered`
    flight-recorder instant, so recovery is as visible in the timeline as
    loss was."""
    try:
        from ray_tpu.util import metrics

        metrics.data_plane_recovered_counter().inc(tags={"kind": str(mtype)})
        tel = get_telemetry()
        if tel is not None and tel.recorder is not None:
            tel.recorder.record(
                "request_recovered",
                args={"mtype": str(mtype), "rid": int(rid),
                      "attempts": int(attempts)},
            )
            tel.flush_events(force=True)
        metrics.flush()
    except Exception:
        pass  # telemetry must never break the data plane


# --------------------------------------------------------------------------
# Chrome trace export
# --------------------------------------------------------------------------


def to_chrome_trace(snapshots: Dict[str, List[Dict[str, Any]]]) -> List[dict]:
    """Convert per-process flight-recorder snapshots into Chrome
    trace-event JSON (the `ray timeline` format): pid = process, tid =
    engine slot, `X` complete events for spans (dur > 0), `i` instants
    otherwise. Batch-wide events carrying args["slots"] expand to one
    event per slot so each slot's lane shows its own decode/verify work;
    slot-LESS events (slot -1, e.g. orphaned_request) render on a
    dedicated "process-wide" lane (tid -1) so a post-mortem reader never
    misattributes them to slot 0's request."""
    out: List[dict] = []
    for pid, (proc, events) in enumerate(sorted(snapshots.items()), start=1):
        out.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": proc},
        })
        proc_lane_named = False
        for ev in events:
            args = dict(ev.get("args") or {})
            slots = args.pop("slots", None)
            slot = int(ev.get("slot", -1))
            if slots:
                tids = [int(s) for s in slots]
            elif slot >= 0:
                tids = [slot]
            else:
                tids = [-1]
                if not proc_lane_named:
                    proc_lane_named = True
                    out.append({
                        "name": "thread_name", "ph": "M", "pid": pid,
                        "tid": -1, "args": {"name": "process-wide"},
                    })
            ts_us = float(ev["ts"]) * 1e6
            dur_s = float(ev.get("dur", 0.0))
            for tid in tids:
                e = {
                    "name": ev["name"], "cat": "serve", "pid": pid,
                    "tid": tid, "ts": ts_us, "args": args,
                }
                if dur_s > 0:
                    e["ph"] = "X"
                    e["dur"] = dur_s * 1e6
                else:
                    e["ph"] = "i"
                    e["s"] = "t"
                out.append(e)
    return out


def dump_timeline(path: Optional[str] = None) -> List[dict]:
    """Dump the cluster-wide engine flight recorder as Chrome trace
    events (`ray timeline` parity for the serving plane). Asks every live
    serve replica to push its recorder to the head first (controller
    fan-out), then merges the head's store with this process's own
    recorder. Writes chrome://tracing-loadable JSON when `path` is given;
    returns the event list either way."""
    try:
        import ray_tpu
        from .handle import CONTROLLER_NAME

        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        ray_tpu.get(controller.flush_telemetry.remote(), timeout=15)
    except Exception:
        pass  # no controller (engine driven in-process): local-only dump
    flush_events(force=True)
    snapshots: Dict[str, List[Dict[str, Any]]] = {}
    try:
        from ray_tpu._private.worker import global_worker

        if global_worker.connected:
            store = global_worker.request({"t": "get_serve_events"})
            snapshots = {
                proc: entry.get("events", [])
                for proc, entry in (store or {}).items()
            }
    except Exception:
        pass
    if not snapshots:
        tel = _TEL
        if tel is not None and tel.recorder is not None:
            snapshots = {f"local:pid-{os.getpid()}": tel.recorder.snapshot()}
    trace = to_chrome_trace(snapshots)
    if path:
        with open(path, "w") as f:
            json.dump(trace, f)
    return trace
