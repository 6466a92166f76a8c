"""Head service: the control plane of a ray_tpu cluster.

Reference parity (collapsed, by design): the reference splits the control
plane into a GCS server (src/ray/gcs/gcs_server/gcs_server.cc:130-178 —
node/actor/job/KV/health managers), a per-node raylet
(src/ray/raylet/node_manager.h:117 — leases, worker pool, scheduling), and a
per-process CoreWorker (src/ray/core_worker/core_worker.h:284). On a TPU pod
the natural control-plane unit is the *host* (one Python process drives 4-8
chips via one XLA client; compute parallelism lives inside compiled SPMD
programs, not in process fan-out), so ray_tpu runs ONE asyncio head service
holding the GCS tables, the cluster scheduler, and the object directory, with
per-node worker pools hanging off it. This trades the reference's
multi-daemon fault isolation for a dramatically shorter hot path — the same
trade the reference itself makes inside a node via lease reuse
(direct_task_transport.cc:191).

Subcomponents kept 1:1 with the reference inventory (SURVEY §2.1):
  - KV store               <- GcsKVManager (store_client_kv.h)
  - ObjectDirectory        <- CoreWorkerMemoryStore + ownership directory
  - ActorManager           <- GcsActorManager (gcs_actor_manager.h:281)
  - NodeTable + Scheduler  <- ClusterTaskManager/ClusterResourceScheduler
                              (cluster_task_manager.h:42, hybrid policy)
  - PlacementGroupManager  <- GcsPlacementGroupManager (gcs_placement_group_manager.h:225)
  - WorkerPool             <- worker_pool.h:156 (lease reuse = idle pool)
"""

from __future__ import annotations

import asyncio
import collections
import logging
import os
import shutil
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from . import faults, protocol
from .config import GLOBAL_CONFIG as cfg

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# Records
# --------------------------------------------------------------------------


@dataclass
class NodeRecord:
    node_id: str
    resources: Dict[str, float]
    available: Dict[str, float] = field(default_factory=dict)
    alive: bool = True
    labels: Dict[str, str] = field(default_factory=dict)
    # agent connection for REAL remote nodes (reference: the raylet's gRPC
    # channel, node_manager.h:117); None for the head node and for logical
    # resource-only nodes (autoscaler simulations)
    conn: Optional["protocol.Connection"] = None
    health_failures: int = 0
    probing: bool = False
    # last load report from the node's agent (ray_syncer analogue)
    load_report: Optional[Dict[str, Any]] = None
    # the node's peer-facing bulk plane listener (object_manager.h:117);
    # consumers dial it directly — the head only serves this location
    buffer_addr: Optional[str] = None

    def __post_init__(self):
        if not self.available:
            self.available = dict(self.resources)

    @property
    def remote(self) -> bool:
        return self.conn is not None


@dataclass
class WorkerRecord:
    worker_id: str
    node_id: str
    proc: Optional[subprocess.Popen] = None
    conn: Optional[protocol.Connection] = None
    state: str = "starting"  # starting | idle | busy | actor | dead
    actor_id: Optional[str] = None
    registered: Optional[asyncio.Future] = None
    num_running: int = 0
    pooled: bool = True
    health_failures: int = 0
    probing: bool = False
    # caller->worker push endpoint (unix path or host:port) for the direct
    # actor-call transport (direct_actor_task_submitter.h:67)
    direct_address: Optional[str] = None
    # set by the OOM killing policy so the task-failure path can surface an
    # OutOfMemoryError instead of a generic crash (worker_killing_policy.h)
    kill_reason: Optional[str] = None


@dataclass
class TaskRecord:
    spec: dict  # the wire-format task spec
    retries_left: int = 0
    resources: Dict[str, float] = field(default_factory=dict)
    node_id: Optional[str] = None
    state: str = "pending"  # pending|waiting_deps|scheduled|running|done|failed|cancelled
    deps_remaining: int = 0
    worker_id: Optional[str] = None
    # set by _h_cancel_task; queued records are dropped lazily when popped
    cancel_requested: bool = False
    # (state, wall-time) transitions — feeds the state API + `timeline()`
    # (reference: core_worker/task_event_buffer.h -> gcs_task_manager.h:61)
    events: List = field(default_factory=list)

    def mark(self, state: str):
        self.state = state
        self.events.append((state, time.time()))


@dataclass
class ActorRecord:
    actor_id: str
    spec: dict
    state: str = "pending"  # pending|starting|alive|restarting|dead
    worker_id: Optional[str] = None
    name: Optional[str] = None
    restarts_left: int = 0
    death_reason: str = ""
    # queued calls submitted while (re)starting
    backlog: List[dict] = field(default_factory=list)
    # set once the scheduler has reserved node resources for this actor
    # (autoscaler demand accounting: acquired != unmet)
    node_acquired: bool = False
    # serializes dep-resolution + send so per-caller submission order is
    # preserved (reference: actor_scheduling_queue.cc sequence numbers)
    send_lock: Optional[asyncio.Lock] = None


@dataclass
class BundleState:
    index: int
    resources: Dict[str, float]
    node_id: Optional[str] = None
    available: Dict[str, float] = field(default_factory=dict)


@dataclass
class PlacementGroupRecord:
    pg_id: str
    bundles: List[BundleState]
    strategy: str
    state: str = "pending"  # pending | created | removed
    name: Optional[str] = None
    ready_event: Optional[asyncio.Event] = None


def _advertise_host(bind_host: str) -> str:
    """The address peers should dial. For a wildcard bind, find this host's
    outbound IP (remote agents relay it to the workers they spawn — a
    loopback advert would make those workers dial themselves)."""
    if bind_host not in ("0.0.0.0", ""):
        return bind_host
    import socket

    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("8.8.8.8", 80))  # no packet sent; picks the route
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


def _fits(avail: Dict[str, float], demand: Dict[str, float]) -> bool:
    return all(avail.get(k, 0.0) + 1e-9 >= v for k, v in demand.items())


def _acquire(avail: Dict[str, float], demand: Dict[str, float]) -> None:
    for k, v in demand.items():
        avail[k] = avail.get(k, 0.0) - v


def _release(avail: Dict[str, float], demand: Dict[str, float]) -> None:
    for k, v in demand.items():
        avail[k] = avail.get(k, 0.0) + v


# --------------------------------------------------------------------------
# Object directory
# --------------------------------------------------------------------------


class ObjectDirectory:
    """Owner-side object table: envelopes + availability events + refcounts.

    Reference: CoreWorkerMemoryStore (memory_store/memory_store.h:43) for
    small objects and the ownership table of reference_count.h:61. Buffers of
    large objects live in the shared-memory plane; only envelopes live here.
    """

    def __init__(self, on_free=None):
        self.objects: Dict[str, Any] = {}
        self.events: Dict[str, asyncio.Event] = {}
        self.refcounts: collections.Counter = collections.Counter()
        self.task_pins: collections.Counter = collections.Counter()
        self.errors: Dict[str, Any] = {}
        self.on_free = on_free  # called with the envelope when freed
        self.on_free_oid = None  # called with the object id when freed
        # oids with a wait_available coroutine between entry and wakeup.
        # Incremented SYNCHRONOUSLY before the first await — unlike
        # ev._waiters, which only gains the waiter one loop iteration
        # later (asyncio.wait_for wraps ev.wait() in ensure_future), so
        # _maybe_free can trust this counter where ev._waiters lies.
        # The PR-5..PR-10 lost-get_objects wedge lived in exactly that
        # gap: a transient refcount 0 popped the "waiterless" event, the
        # producer's put minted+set a NEW event, and the parked handler
        # then registered on the orphaned old one forever.
        self._waiting: collections.Counter = collections.Counter()
        # free generation per oid (bounded breadcrumb): bumped every time a
        # STORED envelope is actually freed. Lets wait_available distinguish
        # "not arrived yet" (park) from "freed out from under me" (raise, so
        # the get_objects handler can reconstruct from lineage or fail
        # loudly) — without this, the arrived-then-freed refcount interleave
        # (a consumer's add_refs borrow still in flight when the last
        # existing ref dropped) parks the getter forever and retransmits
        # just re-execute into the same void.
        self.freed_gen: Dict[str, int] = {}
        self._freed_order: collections.deque = collections.deque()
        self._freed_cap = 4096

    def _event(self, oid: str) -> asyncio.Event:
        ev = self.events.get(oid)
        if ev is None:
            ev = self.events[oid] = asyncio.Event()
        return ev

    def put(self, oid: str, envelope: Any):
        self.objects[oid] = envelope
        self._event(oid).set()

    def invalidate(self, oid: str):
        """Drop a stale envelope (its shm buffers were lost) so waiters
        block until reconstruction re-puts it. Refcounts are untouched."""
        self.objects.pop(oid, None)
        ev = self.events.get(oid)
        if ev is not None:
            ev.clear()

    def contains(self, oid: str) -> bool:
        return oid in self.objects

    async def wait_available(self, oid: str, timeout: Optional[float] = None):
        if oid in self.objects:
            return
        deadline = None if timeout is None else time.monotonic() + timeout
        # snapshot the free generation: a bump DURING this wait means the
        # object existed and was freed under us — parking again would never
        # end (nothing re-puts a freed object except reconstruction, which
        # is the caller's job once we raise). Entry-time staleness (freed
        # long before this wait began) is the caller's to check via
        # freed_gen — snapshot semantics keep _reconstruct's own
        # wait_available from insta-raising on the very oid it is reviving.
        start_gen = self.freed_gen.get(oid, 0)
        self._waiting[oid] += 1  # BEFORE any await: guards the event entry
        try:
            while oid not in self.objects:
                if self.freed_gen.get(oid, 0) != start_gen:
                    from ..exceptions import ObjectLostError

                    raise ObjectLostError(oid)
                ev = self._event(oid)  # re-fetch: identity may have changed
                remaining = (
                    None if deadline is None
                    else deadline - time.monotonic()
                )
                if remaining is not None and remaining <= 0:
                    raise asyncio.TimeoutError()
                await asyncio.wait_for(ev.wait(), remaining)
                if oid not in self.objects:
                    # stale wakeup: the envelope was freed/invalidated
                    # between set and wake — clear so the loop parks again
                    # instead of spinning (other loop-waiters re-check the
                    # same way, so clearing a shared event is safe)
                    ev.clear()
        finally:
            self._waiting[oid] -= 1
            if self._waiting[oid] <= 0:
                del self._waiting[oid]

    def get(self, oid: str):
        return self.objects[oid]

    def add_ref(self, oid: str, n: int = 1):
        self.refcounts[oid] += n

    def remove_ref(self, oid: str, n: int = 1):
        self.refcounts[oid] -= n
        self._maybe_free(oid)

    def pin(self, oid: str):
        self.task_pins[oid] += 1

    def unpin(self, oid: str):
        self.task_pins[oid] -= 1
        self._maybe_free(oid)

    def _maybe_free(self, oid: str):
        if self.refcounts[oid] <= 0 and self.task_pins[oid] <= 0:
            env = self.objects.pop(oid, None)
            if env is None and self.refcounts[oid] < 0:
                # a remove_refs outran its object's arrival (direct-path
                # results carry the caller's +1 on the put itself): keep
                # the debt so the late put reconciles to zero and frees
                self.task_pins.pop(oid, None)
                return
            # NEVER drop an event someone is parked on: a later put would
            # mint a NEW event and set that one, stranding the old waiters
            # forever (the direct-path free/put interleave hits this —
            # get_objects parks, a transient count reaches 0, the producer's
            # put lands after). ev._waiters ALONE is not enough: between
            # wait_available's entry and asyncio.wait_for scheduling the
            # ev.wait() waiter there is a full loop iteration where the
            # waiter is invisible — the root cause of the carried
            # lost-get_objects wedge — so the _waiting counter (bumped
            # synchronously before the first await) must hold the event
            # alive through that gap.
            ev = self.events.get(oid)
            if ev is not None and not ev._waiters and not self._waiting.get(oid):
                self.events.pop(oid, None)
            self.refcounts.pop(oid, None)
            self.task_pins.pop(oid, None)
            if env is not None:
                # a STORED envelope died: leave a bounded breadcrumb so a
                # parked (or future) getter can tell freed from not-yet-put,
                # and wake anyone currently parked so they observe the free
                # (their wait_available raises ObjectLostError and the
                # get_objects handler takes the reconstruction path)
                if oid not in self.freed_gen:
                    self._freed_order.append(oid)
                    while len(self._freed_order) > self._freed_cap:
                        self.freed_gen.pop(self._freed_order.popleft(), None)
                self.freed_gen[oid] = self.freed_gen.get(oid, 0) + 1
                if ev is not None and (ev._waiters or self._waiting.get(oid)):
                    ev.set()
            if env is not None and self.on_free is not None:
                self.on_free(env)
            if self.on_free_oid is not None:
                self.on_free_oid(oid, None)


# --------------------------------------------------------------------------
# Head
# --------------------------------------------------------------------------


class Head:
    def __init__(self, session_dir: str, head_node_resources: Dict[str, float]):
        self.session_dir = session_dir
        self.socket_path = os.path.join(session_dir, "head.sock")
        self.kv: Dict[str, Dict[str, bytes]] = collections.defaultdict(dict)
        self.objects = ObjectDirectory(on_free=self._free_shm_buffers)
        self.nodes: Dict[str, NodeRecord] = {}
        self.workers: Dict[str, WorkerRecord] = {}
        self.actors: Dict[str, ActorRecord] = {}
        self.named_actors: Dict[Tuple[str, str], str] = {}  # (namespace, name) -> actor_id
        # name -> {conn, functions, inflight: call_id->return_id, next_call}
        # (cross-language task execution; reference: cpp/src/ray/runtime
        # task_executor — C++ processes registering callables by name)
        self.cpp_executors: Dict[str, dict] = {}
        self.placement_groups: Dict[str, PlacementGroupRecord] = {}
        self.tasks: Dict[str, TaskRecord] = {}
        self.pending_queue: collections.deque = collections.deque()
        # demand shapes with no current placement; persists across pumps
        # (see _pump/_capacity_changed) so submit storms stay O(1) each.
        # Their tasks wait in _parked, OUT of pending_queue, so pumps stay
        # O(new work) even with a 100k-task unplaceable backlog
        self._blocked_sigs: Set[Any] = set()
        self._parked: Dict[Any, collections.deque] = {}
        # head-routed actor calls in flight: task_id -> worker_id, so
        # cancel_task can reach a call that has no TaskRecord
        self._actor_inflight: Dict[str, str] = {}
        # streaming-generator bookkeeping: the yields' baseline refs are
        # owned by the task's completion object — freeing it frees them
        # (reference: dynamic returns are freed with their generator ref)
        self._stream_children: Dict[str, List[str]] = {}  # task_id -> oids
        self._stream_completion: Dict[str, str] = {}  # completion oid -> task_id
        self.idle_workers: Dict[str, List[str]] = collections.defaultdict(list)
        self.server: Optional[asyncio.base_events.Server] = None
        self.tcp_server: Optional[asyncio.base_events.Server] = None
        self.tcp_address: Optional[str] = None
        self._worker_counter = 0
        self._client_conns: Set[protocol.Connection] = set()
        self._head_node_id = "node-head"
        self.nodes[self._head_node_id] = NodeRecord(self._head_node_id, dict(head_node_resources))
        self._shutdown = False
        # fire-and-forget control-plane coroutines (actor starts, actor-task
        # runs, PG scheduling, dispatches). Tracked so stop() cancels them —
        # an untracked pending task spews "Task was destroyed but it is
        # pending!" at interpreter exit and buries real close regressions.
        self._bg_tasks: Set[asyncio.Task] = set()
        self._max_task_workers: Dict[str, int] = {}
        self._spawning_task_workers: collections.Counter = collections.Counter()
        self._driver_conn: Optional[protocol.Connection] = None
        self.job_config: Dict[str, Any] = {}
        self._shm = None
        self._shm_tried = False
        # lineage: return-object id -> creating task id (stateless tasks
        # only; reference: task_manager.h:164 lineage pinning). Entries die
        # with their object's last reference.
        self.object_lineage: Dict[str, str] = {}
        # lineage of FREED objects (bounded): when a free retires a lineage
        # entry, the oid->task mapping moves here so a getter that lost the
        # refcount race (its add_refs borrow still in flight when the last
        # ref dropped) can re-run the creating task instead of wedging
        self._freed_lineage: "collections.OrderedDict[str, str]" = (
            collections.OrderedDict()
        )
        self._reconstructing: Dict[str, asyncio.Future] = {}
        self.objects.on_free_oid = self._on_object_freed
        # per-process metric snapshots: proc key -> {metric key -> snapshot}
        self.metrics_store: Dict[str, dict] = {}
        # serve flight-recorder snapshots (serve/telemetry.py): proc key ->
        # {"ts", "events", "dropped"}. Deliberately NOT pruned at conn
        # close — a reaped/crashed replica's last events are exactly the
        # post-mortem this store exists for; bounded by proc count instead.
        self.serve_events_store: Dict[str, dict] = {}
        # named-channel pubsub (reference: src/ray/pubsub publisher.h:307 /
        # subscriber.h:329; serve's long-poll rides the same channels,
        # serve/_private/long_poll.py:68). Per channel: latest (seq, data)
        # snapshot + push-subscribed connections + long-poll wakeup event.
        self.channels: Dict[str, Tuple[int, Any]] = {}
        self.channel_subscribers: Dict[str, Set[protocol.Connection]] = (
            collections.defaultdict(set)
        )
        self._channel_events: Dict[str, asyncio.Event] = {}
        self._channel_waiters: Dict[str, int] = {}
        self._push_tasks: Set[asyncio.Task] = set()
        # handler name -> {count, total_ms, max_ms} (event_stats.h analogue)
        self.event_stats: Dict[str, dict] = {}
        # object bytes relayed through the head (fetch_buffers fallback
        # path only — the direct node-to-node plane keeps this ~0)
        self.relay_bytes: int = 0
        # direct task leases: worker_id -> {conn, node_id, resources}
        # (direct_task_transport.cc:191 lease bookkeeping)
        self._task_leases: Dict[str, dict] = {}
        # dashboard observability: per-worker log rings + per-node load
        # history (reference: dashboard/modules/{log,reporter})
        self.log_ring: Dict[str, "collections.deque"] = {}
        self.node_history: Dict[str, "collections.deque"] = {}
        self._log_interest_until = 0.0
        # submitted jobs: submission_id -> record (entrypoint subprocess)
        self.jobs: Dict[str, dict] = {}
        self._prestart_tasks: List[asyncio.Task] = []

    def _spawn_bg(self, coro) -> asyncio.Task:
        """create_task with shutdown bookkeeping: stop() cancels whatever is
        still pending so nothing leaks past the event loop's lifetime."""
        task = asyncio.get_running_loop().create_task(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def _shm_client(self):
        if not self._shm_tried:
            self._shm_tried = True
            from .shm import connect_for_session

            self._shm = connect_for_session(self.session_dir)
            if self._shm is not None:
                # one pretouch per machine: producers then run at memcpy
                # speed instead of paying first-touch faults per put
                self._shm.pretouch_async()
        return self._shm

    def _free_shm_buffers(self, env):
        from .serialization import shm_buffer_refs

        try:
            refs = shm_buffer_refs(env)
        except Exception:
            return
        if not refs:
            return
        by_node: Dict[str, List[str]] = collections.defaultdict(list)
        for r in refs:
            by_node[r.node or self._head_node_id].append(r.name)
        for node_id, names in by_node.items():
            node = self.nodes.get(node_id)
            if node is not None and node.remote:
                if not node.conn.closed:
                    try:
                        asyncio.get_running_loop().create_task(
                            node.conn.send({"t": "delete_buffers", "names": names})
                        )
                    except RuntimeError:
                        pass  # loop gone (shutdown)
                continue
            # head node AND logical nodes: workers share the head machine's
            # session shm plane, so delete locally
            shm = self._shm_client()
            if shm is not None:
                for n in names:
                    shm.delete(n)

    async def _h_buffer_addrs(self, conn, msg):
        """Owner-directed location lookup (pull_manager.h:52): where is each
        node's bulk plane? Consumers dial the addr directly (and, when the
        peer's shm session lives on THEIR machine, attach it instead of
        using TCP at all) and cache the answer; the head never sees the
        object bytes."""
        session = os.path.basename(self.session_dir)
        out = {}
        for nid in msg["nodes"]:
            node = self.nodes.get(nid)
            if node is None or not node.alive or not node.buffer_addr:
                out[nid] = None
                continue
            out[nid] = {
                "addr": node.buffer_addr,
                "shm_session": f"{session}_{nid}",
            }
        return out

    async def _h_fetch_buffers(self, conn, msg):
        """RELAY FALLBACK for cross-node pulls (consumers first try the
        owner's bulk plane via buffer_addrs; reference analogue:
        object_manager.h:117). Relayed bytes are counted — tests and the
        dashboard assert the bulk plane stays off the head."""
        node_id = msg.get("node") or self._head_node_id
        names: List[str] = msg["names"]
        node = self.nodes.get(node_id)
        if node is not None and node.remote:
            if not node.alive or node.conn.closed:
                return {name: None for name in names}
            try:
                got = await node.conn.request(
                    {"t": "read_buffers", "names": names}, timeout=60
                )
            except Exception:
                return {name: None for name in names}
            self.relay_bytes += sum(len(v) for v in got.values() if v)
            # re-wrap for the consumer leg: the agent's WireBuffers arrived
            # as out-of-band views; send them onward the same way instead
            # of re-pickling the payload inline
            return {
                name: None if v is None else protocol.WireBuffer(v)
                for name, v in got.items()
            }
        # head node and logical nodes share the head machine's shm plane:
        # serve slab views out-of-band, zero head-side copies
        shm = self._shm_client()
        out = {}
        for name in names:
            mv = None if shm is None else shm.get_or_spilled(name)
            out[name] = None if mv is None else protocol.WireBuffer(mv)
        return out

    async def start(self, tcp_host: Optional[str] = None, tcp_port: Optional[int] = None):
        """Listen on the session unix socket AND on TCP (the multi-host
        plane; reference: grpc_server.h:73). The bound host:port is written
        to <session_dir>/head_addr for discovery by `init(address=...)`."""
        # a stale socket file survives a crashed head whose session this
        # start is restoring; binding over it needs the unlink
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass
        self.server = await asyncio.start_unix_server(self._on_client, path=self.socket_path)
        self._shm_client()  # connect early: kicks off the slab pretouch
        if cfg.head_restore_path:
            try:
                self._load_snapshot(cfg.head_restore_path)
            except FileNotFoundError:
                logger.warning("no head snapshot at %s", cfg.head_restore_path)
            except Exception:
                # a corrupt/incompatible snapshot must not keep the head
                # from starting; whatever restored before the failure stays
                logger.exception(
                    "failed to restore head snapshot %s; starting fresh",
                    cfg.head_restore_path,
                )
        if cfg.head_snapshot_period_ms > 0:
            self._snapshot_task = asyncio.get_running_loop().create_task(
                self._snapshot_loop()
            )
        self._prestart_workers(self._head_node_id)
        if cfg.dashboard_enabled:
            from ..dashboard import Dashboard

            self.dashboard = Dashboard(self)
            addr = await self.dashboard.start(cfg.dashboard_host, cfg.dashboard_port)
            if addr:
                with open(os.path.join(self.session_dir, "dashboard_addr"), "w") as f:
                    f.write(addr)
        # liveness prober: a hung worker/agent keeps its socket open, so
        # connection-close detection alone misses it (reference:
        # gcs_health_check_manager.h:39 periodic health checks)
        self._health_task = asyncio.get_running_loop().create_task(self._health_loop())
        if cfg.memory_monitor_refresh_ms > 0:
            self._memory_task = asyncio.get_running_loop().create_task(
                self._memory_loop()
            )
        if cfg.log_to_driver:
            self._log_tail_task = asyncio.get_running_loop().create_task(
                self._log_tail_loop()
            )
        host = tcp_host if tcp_host is not None else cfg.head_tcp_host
        port = tcp_port if tcp_port is not None else cfg.head_tcp_port
        try:
            self.tcp_server = await asyncio.start_server(self._on_client, host=host, port=port)
        except OSError as e:
            logger.warning("head TCP listener failed (%s); single-host only", e)
            self.tcp_server = None
            return
        bound = self.tcp_server.sockets[0].getsockname()
        self.tcp_address = f"{_advertise_host(host)}:{bound[1]}"
        with open(os.path.join(self.session_dir, "head_addr"), "w") as f:
            f.write(self.tcp_address)

    # ------------------------------------------------------------------
    # persistence (reference: gcs_table_storage.h:252 + gcs_init_data.h —
    # periodic snapshot instead of per-write Redis mirroring: the metadata
    # volume is small and the fsync cost of per-write mirroring would sit
    # on the control hot path)
    # ------------------------------------------------------------------

    def _snapshot_path(self) -> str:
        return cfg.head_snapshot_path or os.path.join(self.session_dir, "head_state.pkl")

    def _write_snapshot(self):
        """Capture + write in one go (event-loop context only)."""
        self._write_state(self._snapshot_state())

    def _snapshot_state(self) -> dict:
        """Capture the state dict ON the event loop — mutations are loop-
        serialized, so capturing here (it's small metadata) avoids racing
        dict iteration against handlers; only the file IO leaves the loop."""
        state = {
            "version": 1,
            "time": time.time(),
            "session_id": os.path.basename(self.session_dir),
            "kv": {ns: dict(table) for ns, table in self.kv.items()},
            "named_actors": dict(self.named_actors),
            "actors": {
                aid: {
                    "actor_id": aid,
                    "name": rec.name,
                    "state": rec.state,
                    "spec": {
                        k: rec.spec.get(k)
                        for k in (
                            "actor_id", "cls_key", "cls_name", "name",
                            "namespace", "resources", "max_restarts",
                            "max_concurrency", "method_names", "lifetime",
                        )
                    },
                }
                for aid, rec in self.actors.items()
            },
            "jobs": {sid: self._job_view(j) for sid, j in self.jobs.items()},
            "placement_groups": {
                pid: {
                    "pg_id": pid,
                    "strategy": rec.strategy,
                    "name": rec.name,
                    "bundles": [dict(b.resources) for b in rec.bundles],
                }
                for pid, rec in self.placement_groups.items()
            },
        }
        return state

    def _write_state(self, state: dict):
        import pickle

        from .snapshot_store import store_for

        store_for(self._snapshot_path()).save(pickle.dumps(state))

    def _load_snapshot(self, target: str):
        """Reload metadata from a previous head's snapshot (any snapshot
        store: plain file, sqlite:// versioned db, gs:// object). Processes
        are gone: actors come back as DEAD records (name registry + specs
        kept so they are discoverable and re-creatable), jobs that were
        RUNNING are marked FAILED, the KV store (function/class exports
        included) is restored verbatim."""
        import pickle

        from .snapshot_store import store_for

        data = store_for(target).load()
        if data is None:
            raise FileNotFoundError(f"no snapshot in store {target!r}")
        state = pickle.loads(data)
        if state.get("version") != 1:
            raise ValueError(f"unsupported snapshot version {state.get('version')!r}")
        for ns, table in state.get("kv", {}).items():
            self.kv[ns].update(table)
        for aid, meta in state.get("actors", {}).items():
            self.actors[aid] = ActorRecord(
                actor_id=aid,
                spec=dict(meta["spec"] or {}),
                name=meta.get("name"),
                state="dead",
                death_reason="head restarted (restored from snapshot)",
            )
        self.named_actors.update(
            {tuple(k) if isinstance(k, list) else k: v
             for k, v in state.get("named_actors", {}).items()}
        )
        for sid, job in state.get("jobs", {}).items():
            job = dict(job)
            if job.get("status") == "RUNNING":
                job["status"] = "FAILED"
                job["message"] = "head restarted"
            job["proc"] = None
            self.jobs[sid] = job
        for pid, meta in state.get("placement_groups", {}).items():
            bundles = [
                BundleState(i, dict(b), available=dict(b))
                for i, b in enumerate(meta["bundles"])
            ]
            rec = PlacementGroupRecord(
                pg_id=pid,
                bundles=bundles,
                strategy=meta["strategy"],
                name=meta.get("name"),
                ready_event=asyncio.Event(),
            )
            self.placement_groups[pid] = rec
            # re-place on whatever capacity this cluster grows
            self._spawn_bg(self._schedule_pg(rec))
        logger.info(
            "restored head state from %s: %d kv namespaces, %d actors, %d jobs",
            target, len(state.get("kv", {})), len(state.get("actors", {})),
            len(state.get("jobs", {})),
        )

    async def _snapshot_loop(self):
        period = cfg.head_snapshot_period_ms / 1000.0
        loop = asyncio.get_running_loop()
        while not self._shutdown:
            await asyncio.sleep(period)
            try:
                state = self._snapshot_state()  # on-loop: race-free capture
                self._snapshot_inflight = loop.run_in_executor(
                    None, self._write_state, state
                )
                await self._snapshot_inflight
            except Exception:
                logger.exception("head snapshot failed")
            finally:
                self._snapshot_inflight = None

    async def _health_loop(self):
        period = cfg.health_check_period_ms / 1000.0
        loop = asyncio.get_running_loop()
        while not self._shutdown:
            await asyncio.sleep(period)
            # safety valve for the persistent blocked-shape memo: any
            # capacity transition that forgot to call _capacity_changed
            # costs at most one health period of scheduling delay. The
            # incremental probe (O(#shapes), promotes until the probe
            # misses) is sufficient to make progress — a bulk requeue here
            # would re-walk a 100k parked backlog every tick forever
            if self._blocked_sigs or self._parked:
                self._capacity_changed(bulk=False)
            for w in list(self.workers.values()):
                if w.state in ("dead", "starting") or w.conn is None or w.probing:
                    continue
                loop.create_task(self._probe(w, w.conn, self._declare_worker_hung(w)))
            for n in list(self.nodes.values()):
                if n.alive and n.remote and not n.conn.closed and not n.probing:
                    loop.create_task(self._probe(n, n.conn, self._declare_node_hung(n)))

    async def _probe(self, target, conn, on_dead):
        """One liveness probe. The timeout covers the SEND too — a hung peer
        can block the connection's send lock (e.g. mid-drain backpressure),
        and a probe stuck in send would otherwise never fail."""
        target.probing = True
        try:
            await asyncio.wait_for(
                conn.request({"t": "ping"}), cfg.health_check_period_ms / 1000.0
            )
            target.health_failures = 0
            on_dead.close()
        except Exception:
            target.health_failures += 1
            if target.health_failures >= cfg.health_check_failure_threshold:
                await on_dead
            else:
                on_dead.close()
        finally:
            target.probing = False

    # ------------------------------------------------------------------
    # OOM killing policy (reference: memory_monitor.h:52 sampling +
    # worker_killing_policy.h retriable-LIFO victim selection — kill the
    # newest retriable task first so older work survives pressure)
    # ------------------------------------------------------------------

    async def _memory_loop(self):
        from .memory_monitor import MemoryMonitor

        mon = MemoryMonitor()
        period = cfg.memory_monitor_refresh_ms / 1000.0
        while not self._shutdown:
            await asyncio.sleep(period)
            try:
                pressured, used, total = mon.is_pressured()
            except Exception:
                continue
            if pressured:
                await self._oom_kill(self._head_node_id, used, total)

    async def _h_memory_pressure(self, conn, msg):
        """A node agent's monitor reported pressure; run the policy there."""
        await self._oom_kill(msg["node_id"], msg["used"], msg["total"])

    # ------------------------------------------------------------------
    # worker log forwarding (reference: _private/log_monitor.py tails
    # per-process files and pushes lines to the driver for printing)
    # ------------------------------------------------------------------

    async def _publish_logs(self, worker_id: str, data: str):
        # bounded per-worker ring for the dashboard's log viewer
        # (reference: dashboard/modules/log — file tail over HTTP)
        ring = self.log_ring.get(worker_id)
        if ring is None:
            ring = self.log_ring[worker_id] = collections.deque(maxlen=400)
        for line in data.splitlines():
            ring.append(line)
        await self._h_publish(
            None, {"channel": "__logs__",
                   "data": {"worker_id": worker_id, "data": data}}
        )

    async def _h_worker_logs(self, conn, msg):
        """Remote agents forward their workers' output here."""
        await self._publish_logs(msg["worker_id"], msg["data"])

    async def _log_tail_loop(self):
        from . import log_tail

        log_dir = os.path.join(self.session_dir, "logs")
        offsets: Dict[str, int] = {}
        pending: Dict[str, tuple] = {}
        loop = asyncio.get_running_loop()
        while not self._shutdown:
            await asyncio.sleep(0.3)
            if not self._logs_wanted():
                # nobody listening: don't read content, but keep offsets at
                # the file ends — a later subscriber gets LIVE output, not
                # the accumulated backlog of the unsubscribed gap
                log_tail.fast_forward(log_dir, offsets)
                continue
            for worker_id, data in await loop.run_in_executor(
                None, log_tail.read_increments, log_dir, offsets, pending
            ):
                await self._publish_logs(worker_id, data)

    def _logs_wanted(self) -> bool:
        """True when a driver subscribed to __logs__ OR the dashboard's log
        viewer asked recently (interest expires so idle dashboards don't
        keep cross-host log traffic flowing forever)."""
        return bool(self.channel_subscribers.get("__logs__")) or (
            time.monotonic() < self._log_interest_until
        )

    async def _h_logs_wanted(self, conn, msg):
        """Agents poll this to gate their log forwarding (no subscribers ->
        no cross-host log traffic)."""
        return self._logs_wanted()

    async def _h_tail_logs(self, conn, msg):
        """Dashboard log viewer: last N buffered lines for one worker (and
        the list of workers with any buffered output). Requesting marks log
        interest for 30s so agents start forwarding."""
        self._log_interest_until = time.monotonic() + 30.0
        worker_id = msg.get("worker_id")
        out = {"workers": sorted(self.log_ring.keys())}
        if worker_id:
            ring = self.log_ring.get(worker_id)
            limit = int(msg.get("limit", 200))
            out["lines"] = list(ring)[-limit:] if ring else []
        return out

    async def _oom_kill(self, node_id: str, used: int, total: int):
        # per-node cooldown: the previous victim's memory takes time to
        # return to the OS, so killing once per sample would cascade through
        # the pool — but pressure on one node must not shield another
        now = time.monotonic()
        if not hasattr(self, "_oom_cooldowns"):
            self._oom_cooldowns: Dict[str, float] = {}
        if now < self._oom_cooldowns.get(node_id, 0.0):
            return
        victim: Optional[TaskRecord] = None
        # newest-first over running stateless tasks on the pressured node;
        # retriable tasks are preferred victims (their work is recoverable)
        for rec in reversed(list(self.tasks.values())):
            if rec.state != "running" or rec.node_id != node_id:
                continue
            w = self.workers.get(rec.worker_id or "")
            if w is None or w.state == "dead":
                continue
            if rec.retries_left > 0:
                victim = rec
                break
            if victim is None:
                victim = rec
        if victim is None:
            logger.warning(
                "node %s under memory pressure (%.0f%%) but no killable task "
                "worker found", node_id, 100.0 * used / max(total, 1),
            )
            # shorter cooldown than the kill path: rate-limits the warning
            # under sustained pressure with only unkillable work (actors),
            # while re-checking soon in case a killable task starts
            self._oom_cooldowns[node_id] = now + max(
                1.0, cfg.memory_monitor_refresh_ms / 1000.0
            )
            return
        w = self.workers[victim.worker_id]
        w.kill_reason = (
            f"worker OOM-killed on {node_id}: node memory {used}/{total} bytes "
            f"({100.0 * used / max(total, 1):.0f}%) exceeded "
            f"memory_usage_threshold={cfg.memory_usage_threshold}; task "
            f"{victim.spec['task_id']} was the newest "
            f"{'retriable' if victim.retries_left > 0 else 'running'} task"
        )
        logger.warning(w.kill_reason)
        self._oom_cooldowns[node_id] = now + max(
            2.0, 2 * cfg.memory_monitor_refresh_ms / 1000.0
        )
        # force-kill; the broken connection routes the running task through
        # _retry_or_fail, which surfaces kill_reason as OutOfMemoryError
        await self._terminate_worker(w, force=True)

    async def _declare_worker_hung(self, w: WorkerRecord):
        if w.state == "dead":
            return
        logger.warning("worker %s failed health checks; declaring dead", w.worker_id)
        # force-kill FIRST: a replacement (possibly TPU-owning) worker must
        # not start while the hung process may still hold the chips
        await self._terminate_worker(w, force=True, close_conn=False)
        await self._on_worker_death(w, reason="unresponsive (health prober)")
        if w.conn is not None:
            await w.conn.close()  # after death handling: reason stays accurate

    async def _declare_node_hung(self, n: NodeRecord):
        if not n.alive:
            return
        logger.warning("node %s failed health checks; declaring dead", n.node_id)
        # death handling first, then close (the close callback's
        # "connection closed" path is a guarded no-op afterwards)
        await self._on_node_death(n, reason="unresponsive (health prober)")
        await n.conn.close()

    async def stop(self):
        self._shutdown = True
        if getattr(self, "_health_task", None) is not None:
            self._health_task.cancel()
        if getattr(self, "_memory_task", None) is not None:
            self._memory_task.cancel()
        if getattr(self, "_log_tail_task", None) is not None:
            self._log_tail_task.cancel()
        if getattr(self, "_snapshot_task", None) is not None:
            self._snapshot_task.cancel()
        for t in list(self._prestart_tasks):
            t.cancel()  # no fresh workers after the kill sweep below
        # cancel fire-and-forget control-plane work (actor starts/calls,
        # PG scheduling, dispatches) and let the cancellations settle —
        # otherwise actor-heavy runs print "Task was destroyed but it is
        # pending!" at interpreter exit
        bg = [t for t in (self._bg_tasks | self._push_tasks) if not t.done()]
        for t in bg:
            t.cancel()
        if bg:
            await asyncio.gather(*bg, return_exceptions=True)
        for job in self.jobs.values():
            if job["status"] == "RUNNING":
                job["status"] = "STOPPED"
                self._terminate_job_proc(job["proc"])
        if cfg.head_snapshot_period_ms > 0:
            # an in-flight periodic write (executor thread: cancel doesn't
            # stop it) must land BEFORE the final write, or its stale state
            # would clobber the clean-shutdown snapshot
            inflight = getattr(self, "_snapshot_inflight", None)
            if inflight is not None:
                try:
                    await asyncio.wait_for(asyncio.shield(inflight), timeout=10)
                except Exception:
                    pass
            try:
                # final snapshot AFTER settling jobs: a clean shutdown must
                # not read as a crash (RUNNING -> FAILED) on restore
                self._write_snapshot()
            except Exception:
                pass
        for w in list(self.workers.values()):
            await self._kill_worker(w, reason="shutdown")
        for n in list(self.nodes.values()):
            if n.conn is not None and not n.conn.closed:
                try:
                    await n.conn.request({"t": "shutdown"}, timeout=2)
                except Exception:
                    pass
                await n.conn.close()
        if self.server is not None:
            self.server.close()
        if self.tcp_server is not None:
            self.tcp_server.close()
        if getattr(self, "dashboard", None) is not None:
            await self.dashboard.stop()
        # Close remaining client connections (incl. the driver's); 3.12's
        # Server.wait_closed would otherwise wait on them forever.
        for conn in list(self._client_conns):
            try:
                await conn.close()
            except Exception:
                pass
        # tear down the shared-memory plane
        shm = self._shm_client()
        if shm is not None:
            try:
                for env in self.objects.objects.values():
                    self._free_shm_buffers(env)
                shm.disconnect()
                from .shm import ShmClient

                ShmClient.destroy(os.path.basename(self.session_dir))
            except Exception:
                pass

    async def _on_client(self, reader, writer):
        conn: protocol.Connection = None  # type: ignore

        async def handler(msg):
            return await self.handle(conn, msg)

        async def on_close():
            self._client_conns.discard(conn)
            await self._on_conn_closed(conn)

        conn = protocol.Connection(reader, writer, handler, on_close)
        self._client_conns.add(conn)
        conn.start()

    async def _on_conn_closed(self, conn):
        # prune metric snapshots pushed over this connection (drivers AND
        # workers); doing it at conn-close means a racing in-flight push
        # can't resurrect the entry after an earlier prune
        for proc in getattr(conn, "_metric_procs", ()):
            self.metrics_store.pop(proc, None)
        for ch in getattr(conn, "_subscribed_channels", ()):
            subs = self.channel_subscribers.get(ch)
            if subs is not None:
                subs.discard(conn)
                if not subs:
                    del self.channel_subscribers[ch]
        # caller died holding direct task leases: reclaim the workers
        had_leases = bool(getattr(conn, "_task_leases", None))
        for wid in list(getattr(conn, "_task_leases", ())):
            self._drop_task_lease(wid)
            w = self.workers.get(wid)
            if w is not None and w.state != "dead":
                await self._return_leased_worker(w)
        if had_leases:
            self._capacity_changed(bulk=False)
        self._drop_cpp_executor(conn)
        for n in list(self.nodes.values()):
            if n.conn is conn and n.alive:
                await self._on_node_death(n, reason="agent connection closed")
        for w in list(self.workers.values()):
            if w.conn is conn and w.state != "dead":
                await self._on_worker_death(w, reason="connection closed")

    async def _on_node_death(self, node: NodeRecord, reason: str):
        """Agent died: the node and everything on it is gone (reference:
        GcsNodeManager node-death broadcast + NodeManager lease cleanup)."""
        if not node.alive:
            return
        node.alive = False
        if not self._shutdown:
            logger.warning("node %s died: %s", node.node_id, reason)
        for w in list(self.workers.values()):
            if w.node_id == node.node_id and w.state != "dead":
                # best effort: tell orphaned workers (agent-spawned procs
                # survive an agent SIGKILL) to exit, then run death handling
                if w.conn is not None and not w.conn.closed:
                    try:
                        await w.conn.send({"t": "shutdown"})
                    except Exception:
                        pass
                    await w.conn.close()
                await self._on_worker_death(w, reason=f"node died ({reason})")

    # ------------------------------------------------------------------
    # message handling
    # ------------------------------------------------------------------

    async def handle(self, conn, msg) -> Any:
        t = msg["t"]
        fn = getattr(self, f"_h_{t}", None)
        if fn is None:
            raise ValueError(f"unknown message type {t!r}")
        if faults.ACTIVE:
            delay = faults.handler_delay(t)
            if delay:
                await asyncio.sleep(delay)
        # per-handler latency/count accounting (reference: event_stats.h
        # instruments the asio loops); total-time includes awaits, so slow
        # entries here mean "long-running", busy_ms means "loop-hogging"
        start = time.perf_counter()
        try:
            return await fn(conn, msg)
        finally:
            dt = (time.perf_counter() - start) * 1000.0
            st = self.event_stats.get(t)
            if st is None:
                st = self.event_stats[t] = {"count": 0, "total_ms": 0.0, "max_ms": 0.0}
            st["count"] += 1
            st["total_ms"] += dt
            if dt > st["max_ms"]:
                st["max_ms"] = dt

    async def _h_worker_kill_reason(self, conn, msg):
        """Why the head killed a worker (OOM policy), if it did. Direct-path
        callers consult this when a lease breaks mid-task so an OOM kill
        surfaces as OutOfMemoryError, not a generic crash (reference:
        worker_killing_policy.h + task failure cause plumbing)."""
        w = self.workers.get(msg["worker_id"])
        return w.kill_reason if w is not None else None

    async def _h_event_stats(self, conn, msg):
        return {
            t: dict(st, avg_ms=st["total_ms"] / max(1, st["count"]))
            for t, st in self.event_stats.items()
        }

    async def _h_object_stats(self, conn, msg):
        """Bulk-plane accounting: relayed bytes must stay ~0 when the
        direct node-to-node plane is healthy. bulk_* roll up the pushed
        per-process counters (bytes/pulls by path, relay fallbacks)."""
        out = {"relay_bytes": self.relay_bytes}
        try:
            from ray_tpu.util.metrics import merge_snapshots

            merged = merge_snapshots(self.metrics_store)
            for name, key in (
                ("bulk_plane_bytes_total", "bulk_bytes_by_path"),
                ("bulk_plane_pulls_total", "bulk_pulls_by_path"),
            ):
                m = merged.get(name)
                if m:
                    out[key] = {
                        (dict(tags).get("path", "") or "untagged"): v
                        for tags, v in m["values"].items()
                    }
            m = merged.get("bulk_plane_fallbacks_total")
            if m:
                out["bulk_fallbacks"] = sum(m["values"].values())
        except Exception:
            pass
        return out

    async def _h_debug_object(self, conn, msg):
        """Per-object directory introspection (ops/debugging)."""
        oid = msg["oid"]
        return {
            "present": self.objects.contains(oid),
            "refcount": self.objects.refcounts.get(oid, 0),
            "pins": self.objects.task_pins.get(oid, 0),
            "has_event": oid in self.objects.events,
            "lineage_task": self.object_lineage.get(oid),
        }

    # --- registration ---

    async def _h_register_driver(self, conn, msg):
        protocol.check_protocol_version(msg, "driver")
        self._driver_conn = conn
        return {"node_id": self._head_node_id, "job_config": self.job_config}

    async def _h_register_node(self, conn, msg):
        """A per-host agent joined over TCP (reference: raylet registration
        with GcsNodeManager). An agent whose previous connection is gone may
        RE-register under the same node id — the reconnect path after a head
        restart or a network blip (reference: raylet re-register against a
        restarted GCS, gcs_server.cc:130-178 init-from-stored-state)."""
        protocol.check_protocol_version(msg, f"node agent {msg.get('node_id')}")
        node_id = msg["node_id"]
        prev = self.nodes.get(node_id)
        if prev is not None and prev.alive and prev.conn is not None and not prev.conn.closed:
            raise ValueError(f"node id {node_id!r} already registered")
        self.nodes[node_id] = NodeRecord(
            node_id, dict(msg["resources"]), labels=msg.get("labels", {}), conn=conn,
            buffer_addr=msg.get("buffer_addr"),
        )
        # reconnect ordering is arbitrary: actors adopted BEFORE their node
        # re-registered must be charged against the fresh availability
        for rec in self.actors.values():
            if rec.state == "alive" and not rec.node_acquired:
                w = self.workers.get(rec.worker_id or "")
                if w is not None and w.node_id == node_id and w.state != "dead":
                    self._adopt_actor_resources(rec, node_id)
        self._prestart_workers(node_id)
        self._capacity_changed()
        return {"session": os.path.basename(self.session_dir),
                "session_dir": self.session_dir}

    def _prestart_workers(self, node_id: str):
        """Pre-warm the node's idle pool so first tasks skip the process
        cold start (interpreter spawn + register, ~0.5-2s). Reference:
        worker_pool.h:420 prestarts workers up to the soft limit."""
        n = cfg.worker_pool_prestart
        if n <= 0:
            return

        async def _one():
            w = await self._spawn_worker(node_id)
            try:
                await asyncio.wait_for(w.registered, cfg.worker_register_timeout_s)
            except asyncio.TimeoutError:
                await self._kill_worker(w, reason="prestart register timeout")
                return
            if w.state == "idle" and not self._shutdown:
                self.idle_workers[node_id].append(w.worker_id)
                # a worker joining adds EXECUTION slots, not node resource
                # capacity — the incremental probe suffices, and a bulk
                # requeue here would re-walk the whole parked backlog per
                # spawn (quadratic under worker churn)
                self._capacity_changed(bulk=False)

        async def _spawn_idle():
            # concurrent spawns: the pool warms in ONE cold-start interval,
            # and a hung worker doesn't serialize the rest
            await asyncio.gather(*(_one() for _ in range(n)), return_exceptions=True)

        # keep a strong reference (loop holds tasks weakly) and cancel at stop
        task = asyncio.get_running_loop().create_task(_spawn_idle())
        self._prestart_tasks.append(task)
        task.add_done_callback(lambda t: self._prestart_tasks.remove(t))

    async def _h_register_worker(self, conn, msg):
        protocol.check_protocol_version(msg, f"worker {msg.get('worker_id')}")
        w = self.workers.get(msg["worker_id"])
        if w is None:
            if not msg.get("adopt"):
                raise ValueError(f"unknown worker {msg['worker_id']}")
            # a SURVIVING worker re-registering after a head restart: the
            # process (and any actor state in it) is intact — re-adopt it
            # instead of forcing a cold respawn (reference: workers
            # re-register with a restarted GCS via the raylet)
            w = WorkerRecord(
                worker_id=msg["worker_id"],
                node_id=msg.get("node_id") or "",
                state="starting",
            )
            self.workers[w.worker_id] = w
        w.conn = conn
        w.direct_address = msg.get("direct_address")
        aid = msg.get("actor_id")
        if aid:
            w.state = "actor"
            w.actor_id = aid
            rec = self.actors.get(aid)
            if rec is not None and rec.state != "alive":
                # snapshot restore marked it dead; the live process proves
                # otherwise — revive the record so routes resolve again
                rec.state = "alive"
                rec.worker_id = w.worker_id
                rec.death_reason = None
                # a revived actor still OCCUPIES its node: without the
                # deduction the scheduler double-books the host
                self._adopt_actor_resources(rec, w.node_id)
        if w.state == "starting":
            w.state = "idle"
            if msg.get("adopt"):
                self.idle_workers[w.node_id].append(w.worker_id)
        if w.registered is not None and not w.registered.done():
            w.registered.set_result(None)
        # worker registration adds execution slots only (see prestart note):
        # incremental probe, not a bulk parked-backlog requeue
        self._capacity_changed(bulk=False)
        return {"node_id": w.node_id, "session_dir": self.session_dir}

    async def _h_get_actor_route(self, conn, msg):
        """Direct-transport route lookup: where does this actor live RIGHT
        NOW? Callers cache the answer and re-resolve on connection failure
        (actor restarts move it)."""
        rec = self.actors.get(msg["actor_id"])
        if rec is None:
            return None
        w = self.workers.get(rec.worker_id or "")
        return {
            "state": rec.state,
            "worker_id": rec.worker_id,
            "node_id": None if w is None else w.node_id,
            "address": None if w is None else w.direct_address,
            "death_reason": rec.death_reason,
        }

    # --- KV (GcsKVManager) ---

    async def _h_kv_put(self, conn, msg):
        ns = msg.get("ns", "")
        overwrite = msg.get("overwrite", True)
        table = self.kv[ns]
        if not overwrite and msg["key"] in table:
            return False
        table[msg["key"]] = msg["value"]
        return True

    async def _h_kv_get(self, conn, msg):
        return self.kv[msg.get("ns", "")].get(msg["key"])

    async def _h_kv_exists(self, conn, msg):
        return msg["key"] in self.kv[msg.get("ns", "")]

    async def _h_kv_del(self, conn, msg):
        return self.kv[msg.get("ns", "")].pop(msg["key"], None) is not None

    async def _h_kv_keys(self, conn, msg):
        prefix = msg.get("prefix", "")
        return [k for k in self.kv[msg.get("ns", "")] if k.startswith(prefix)]

    # --- objects ---

    def _on_object_freed(self, oid: str, _default=None):
        tid = self.object_lineage.pop(oid, None)
        if tid is not None and tid in self.tasks:
            # keep a bounded breadcrumb: a late getter revives the object
            # by re-running this task (stateless lineage only)
            self._freed_lineage[oid] = tid
            self._freed_lineage.move_to_end(oid)
            while len(self._freed_lineage) > 4096:
                self._freed_lineage.popitem(last=False)
        tid = self._stream_completion.pop(oid, None)
        if tid is not None:
            # the stream's terminal object died: release every yield's
            # baseline ref (consumers hold their own borrows)
            for child in self._stream_children.pop(tid, []):
                self.objects.remove_ref(child, 1)

    async def _h_put_object(self, conn, msg):
        oid = msg["object_id"]
        tid = msg.get("stream_of")
        if tid is not None:
            kids = self._stream_children.get(tid)
            if kids is None:
                # Late yield: it traveled on the worker's client conn while
                # the completion reply rode the head->worker request conn, so
                # the stream's terminal object was stored AND freed before
                # this put arrived. Registering it now would re-create
                # _stream_children for a dead stream and leak the baseline
                # ref forever. Store the envelope (a consumer may hold its
                # own borrow) but drop the baseline +1 immediately.
                self.objects.put(oid, msg["envelope"])
                self.objects.add_ref(oid, msg.get("initial_refs", 1))
                self.objects.remove_ref(oid, 1)
                return
            kids.append(oid)
        self.objects.put(oid, msg["envelope"])
        self.objects.add_ref(oid, msg.get("initial_refs", 1))
        # direct-transport results carry the caller's +1 here; if the caller
        # already dropped its ref (counter went negative), reconcile now
        self.objects._maybe_free(oid)

    async def _h_put_objects(self, conn, msg):
        """Batched put_object: direct-transport callers coalesce result
        forwards so the head pays one message per batch, not per call
        (reference: the task-event/object-report batching in
        core_worker/task_event_buffer.h)."""
        for oid, env in msg["objects"]:
            self.objects.put(oid, env)
            self.objects.add_ref(oid, 1)
            self.objects._maybe_free(oid)

    # ------------------------------------------------------------------
    # direct task transport: leases + post-hoc records
    # (reference: direct_task_transport.cc:588 lease-worker push, :191
    # lease reuse — the head grants a leased worker; the caller pushes
    # task specs straight to it and reuses the lease across tasks)
    # ------------------------------------------------------------------

    async def _h_request_task_lease(self, conn, msg):
        res = dict(msg.get("resources") or {"CPU": 1.0})
        nid = self._select_node(res, None)
        if nid is None:
            return None  # no capacity: caller queues via submit_task
        w = await self._lease_worker(
            nid, needs_tpu=res.get("TPU", 0) > 0,
            runtime_env=msg.get("runtime_env"),
        )
        if w is None or not w.direct_address:
            self._release_node(nid, res, None)
            if w is not None:  # un-dialable worker: back to the pool
                await self._return_leased_worker(w)
                self._capacity_changed(bulk=False)
            return None
        self._task_leases[w.worker_id] = {
            "conn": conn, "node_id": nid, "resources": res,
        }
        if not hasattr(conn, "_task_leases"):
            conn._task_leases = set()
        conn._task_leases.add(w.worker_id)
        return {
            "worker_id": w.worker_id, "address": w.direct_address,
            "node_id": w.node_id,
        }

    def _drop_task_lease(self, worker_id: str) -> None:
        """Release the lease's node resources + caller bookkeeping (the
        worker itself is settled separately — it may be dead)."""
        lease = self._task_leases.pop(worker_id, None)
        if lease is None:
            return
        s = getattr(lease["conn"], "_task_leases", None)
        if s is not None:
            s.discard(worker_id)
        self._release_node(lease["node_id"], lease["resources"], None)

    async def _return_leased_worker(self, w: WorkerRecord) -> None:
        if w.state != "busy":
            return
        if w.pooled:
            w.state = "idle"
            self.idle_workers[w.node_id].append(w.worker_id)
        else:
            await self._kill_worker(w, reason="direct lease done")

    async def _h_release_task_lease(self, conn, msg):
        wid = msg["worker_id"]
        self._drop_task_lease(wid)
        w = self.workers.get(wid)
        if w is not None:
            await self._return_leased_worker(w)
        # AFTER the lease drop, regardless of worker state: the node
        # capacity was freed by _drop_task_lease even when the worker died
        # mid-lease, and parked tasks that now fit must not wait for the
        # health valve
        self._capacity_changed(bulk=False)
        return True

    async def _h_record_tasks(self, conn, msg):
        """Post-hoc records for direct-pushed tasks: lineage (so evicted
        results reconstruct through the normal scheduler) + observability
        (state API / timeline). Best-effort and batched, like the
        reference's task event buffer (task_event_buffer.h ->
        gcs_task_manager.h:61)."""
        for r in msg["records"]:
            spec = r["spec"]
            rec = self.tasks.get(spec["task_id"])
            if rec is None:
                rec = TaskRecord(
                    spec=spec,
                    resources=spec.get("resources") or {"CPU": 1.0},
                )
                self.tasks[spec["task_id"]] = rec
            rec.node_id = r.get("node_id")
            rec.worker_id = r.get("worker_id")
            rec.retries_left = spec.get("max_retries", 0)
            rec.mark(r["state"])
            for oid in spec["return_ids"]:
                self.object_lineage[oid] = spec["task_id"]
        return True

    async def _h_get_objects(self, conn, msg):
        from ..exceptions import ObjectLostError

        ids: List[str] = msg["object_ids"]
        timeout = msg.get("timeout")
        deadline = None if timeout is None else time.monotonic() + timeout
        out = []
        for oid in ids:
            # freed before this get even arrived (e.g. a retransmitted
            # attempt landing after the refcount race resolved the wrong
            # way): recover up front — wait_available would park forever
            if not self.objects.contains(oid) and self.objects.freed_gen.get(oid):
                await self._recover_freed(oid)
            for attempt in range(2):
                remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
                try:
                    await self.objects.wait_available(oid, remaining)
                    break
                except asyncio.TimeoutError:
                    from ..exceptions import GetTimeoutError

                    raise GetTimeoutError(
                        f"Get timed out after {timeout}s waiting for object {oid}"
                    ) from None
                except ObjectLostError:
                    # freed while we waited: the last existing ref dropped
                    # with this getter's borrow still in flight. Re-run the
                    # creator from lineage (or fail loudly) — never re-park.
                    if attempt > 0:
                        raise
                    await self._recover_freed(oid)
            out.append(self.objects.get(oid))
        return out

    async def _recover_freed(self, oid: str):
        """A getter raced the free of `oid`: revive it by re-running its
        creating task (lineage breadcrumb survives the free), or raise
        ObjectLostError so the caller gets a fast, loud, typed failure
        instead of an unbounded park. Recoveries count in
        protocol.PLANE_STATS['freed_object_recoveries']."""
        from ..exceptions import ObjectLostError

        if oid not in self.object_lineage:
            tid = self._freed_lineage.get(oid)
            if tid is None or tid not in self.tasks:
                logger.warning(
                    "get_objects hit freed object %s with no lineage to "
                    "re-run; surfacing ObjectLostError", oid,
                )
                raise ObjectLostError(oid)
            self.object_lineage[oid] = tid
        logger.warning(
            "get_objects hit freed object %s (refcount race: a borrow was "
            "in flight when the last ref dropped); re-running task %s from "
            "lineage", oid, self.object_lineage[oid],
        )
        await self._reconstruct(oid)
        protocol._stat("freed_object_recoveries")

    async def _wait_dep_available(self, oid: str):
        """wait_available with the freed-object recovery path: entry-time
        staleness (freed before this wait began) and mid-wait frees both
        route through lineage re-execution instead of parking forever."""
        from ..exceptions import ObjectLostError

        if not self.objects.contains(oid) and self.objects.freed_gen.get(oid):
            await self._recover_freed(oid)
        try:
            await self.objects.wait_available(oid)
        except ObjectLostError:
            await self._recover_freed(oid)
            await self.objects.wait_available(oid)

    async def _h_wait_objects(self, conn, msg):
        ids: List[str] = msg["object_ids"]
        num_returns = msg["num_returns"]
        timeout = msg.get("timeout")
        # at most num_returns ids come back ready (reference ray.wait
        # contract) — input order breaks ties among already-ready objects
        ready = [oid for oid in ids if self.objects.contains(oid)][:num_returns]
        if len(ready) < num_returns:
            pending = {
                asyncio.ensure_future(self.objects.wait_available(oid)): oid
                for oid in ids
                if not self.objects.contains(oid)
            }
            deadline = None if timeout is None else time.monotonic() + timeout
            try:
                while len(ready) < num_returns and pending:
                    remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
                    if remaining is not None and remaining == 0.0:
                        break
                    done, _ = await asyncio.wait(
                        pending.keys(), timeout=remaining, return_when=asyncio.FIRST_COMPLETED
                    )
                    if not done:
                        break
                    for fut in done:
                        oid = pending.pop(fut)
                        # a waiter can now finish exceptionally (object
                        # freed mid-wait raises ObjectLostError): a lost
                        # object is NOT ready — report it as pending
                        if fut.exception() is None:
                            ready.append(oid)
            finally:
                for fut in pending:
                    fut.cancel()
        # a FIRST_COMPLETED batch can deliver several at once: re-cap
        ready_set = set(ready)
        ready_list = [oid for oid in ids if oid in ready_set][:num_returns]
        ready_set = set(ready_list)
        return ready_list, [oid for oid in ids if oid not in ready_set]

    # --- cross-language object exchange (JSON-codec clients, cpp/client/;
    # reference: the msgpack cross-language serialization the C++/Java
    # worker APIs use, cpp/src/ray/runtime) ---

    async def _h_xput_object(self, conn, msg):
        """Put from a non-Python client: "raw" = base64 bytes (stored as
        Python bytes), "json" = a JSON value. Stored as a normal envelope,
        so Python consumers just ray_tpu.get() it."""
        import base64

        from .serialization import serialize

        if msg.get("format") == "raw":
            value = base64.b64decode(msg["data"])
        else:
            value = msg.get("value")
        oid = msg["object_id"]
        self.objects.put(oid, serialize(value))
        self.objects.add_ref(oid, msg.get("initial_refs", 1))
        return oid

    async def _h_xget_objects(self, conn, msg):
        """Get for a non-Python client: values come back as JSON when they
        are JSON-representable, base64-tagged bytes otherwise."""
        import base64

        from .serialization import deserialize, materialize

        envs = await self._h_get_objects(conn, msg)
        out = []
        loop = asyncio.get_running_loop()
        for env in envs:
            # materialize OFF the loop: fetching cross-node buffers performs
            # a blocking round-trip back through this very event loop, so
            # doing it inline would deadlock the whole control plane
            def _load(env=env):
                e = materialize(env, self._shm_client())
                return e, deserialize(e)

            env, value = await loop.run_in_executor(None, _load)
            if getattr(env, "is_error", False):
                out.append({"format": "error", "error": repr(value)})
            elif isinstance(value, bytes):
                out.append({"format": "raw", "data": base64.b64encode(value).decode()})
            else:
                out.append({"format": "json", "value": value})
        return out

    # --- cross-language task execution (cpp/client Executor; reference:
    # cpp/src/ray/runtime task execution — the C++ worker registers named
    # functions and the runtime pushes calls to it) ---

    async def _h_register_cpp_executor(self, conn, msg):
        protocol.check_protocol_version(msg, f"cpp executor {msg.get('name')}")
        name = msg["name"]
        prev = self.cpp_executors.get(name)
        if prev is not None and not prev["conn"].closed:
            raise ValueError(f"cpp executor {name!r} already registered")
        conn._cpp_executor_name = name
        self.cpp_executors[name] = {
            "conn": conn,
            "functions": list(msg.get("functions") or []),
            "inflight": {},
            "next_call": 0,
        }
        return {"name": name}

    async def _h_list_cpp_executors(self, conn, msg):
        return {
            name: rec["functions"]
            for name, rec in self.cpp_executors.items()
            if not rec["conn"].closed
        }

    async def _h_cpp_call(self, conn, msg):
        """Python -> C++ call: push {fn, args} to the named executor; its
        cpp_result lands in the object directory under return_id, so the
        caller's ordinary get() resolves it."""
        rec = self.cpp_executors.get(msg["executor"])
        if rec is None or rec["conn"].closed:
            raise ValueError(f"no live cpp executor {msg['executor']!r}")
        return_id = msg["return_id"]
        rec["next_call"] += 1
        call_id = rec["next_call"]
        # register BEFORE the send: the await can yield to the read loop,
        # and an instant cpp_result must find its inflight entry — but
        # unwind on send failure (the closed flag lags the actual death),
        # or the +1 and entry would leak an error object nobody holds
        self.objects.add_ref(return_id, 1)
        rec["inflight"][call_id] = return_id
        try:
            await rec["conn"].send(
                {"t": "cpp_exec", "call_id": call_id, "fn": msg["fn"],
                 "args": msg.get("args") or []}
            )
        except Exception:
            rec["inflight"].pop(call_id, None)
            self.objects.remove_ref(return_id, 1)
            raise
        return return_id

    async def _h_cpp_result(self, conn, msg):
        from .serialization import serialize

        rec = self.cpp_executors.get(getattr(conn, "_cpp_executor_name", "") or "")
        if rec is None or rec["conn"] is not conn:
            return
        return_id = rec["inflight"].pop(msg["call_id"], None)
        if return_id is None:
            return
        # the caller may have dropped its ref while the call ran: the
        # refcount entry is gone, and storing now would leak the envelope
        # forever (no decrement will ever arrive)
        if return_id not in self.objects.refcounts:
            return
        if msg.get("ok"):
            env = serialize(msg.get("value"))
        else:
            from ..exceptions import CrossLanguageError

            env = serialize(CrossLanguageError(str(msg.get("error"))))
            env.is_error = True  # type: ignore[attr-defined]
        self.objects.put(return_id, env)

    def _drop_cpp_executor(self, conn) -> None:
        """Executor connection died: surface every in-flight call as an
        error object (callers are parked in get())."""
        from .serialization import serialize

        name = getattr(conn, "_cpp_executor_name", None)
        rec = self.cpp_executors.get(name or "")
        if rec is None or rec["conn"] is not conn:
            return
        del self.cpp_executors[name]
        if rec["inflight"]:
            from ..exceptions import CrossLanguageError

            env = serialize(
                CrossLanguageError(f"cpp executor {name!r} died mid-call")
            )
            env.is_error = True  # type: ignore[attr-defined]
            for return_id in rec["inflight"].values():
                if return_id in self.objects.refcounts:  # see _h_cpp_result
                    self.objects.put(return_id, env)
            rec["inflight"].clear()

    async def _h_add_refs(self, conn, msg):
        for oid, n in msg["counts"].items():
            self.objects.add_ref(oid, n)

    async def _h_remove_refs(self, conn, msg):
        for oid, n in msg["counts"].items():
            self.objects.remove_ref(oid, n)

    async def _h_free_objects(self, conn, msg):
        for oid in msg["object_ids"]:
            self.objects.refcounts[oid] = 0
            self.objects._maybe_free(oid)

    # --- tasks ---

    async def _h_submit_task(self, conn, msg):
        spec = msg["spec"]
        # the caller's +1 on each return id, folded into the submit message
        for oid in spec["return_ids"]:
            self.objects.add_ref(oid, 1)
            self.object_lineage[oid] = spec["task_id"]
        rec = TaskRecord(
            spec=spec,
            retries_left=spec.get("max_retries", 0),
            resources=spec.get("resources") or {"CPU": 1.0},
        )
        self.tasks[spec["task_id"]] = rec
        if spec.get("streaming"):
            self._stream_completion[spec["return_ids"][0]] = spec["task_id"]
            # pre-register the children list so a yield arriving AFTER the
            # completion object was freed (different conn, no FIFO guarantee)
            # is distinguishable from a live stream in _h_put_object
            self._stream_children.setdefault(spec["task_id"], [])
        for oid in spec.get("deps", []):
            self.objects.pin(oid)
        rec._resolve_task = self._spawn_bg(self._resolve_and_enqueue(rec))

    async def _resolve_and_enqueue(self, rec: TaskRecord):
        if rec.cancel_requested:
            # cancelled before this coroutine first ran, or re-entered via
            # the lost_deps re-dispatch path after a cancel: settle (the
            # _finish_cancel no-ops if the pending-branch already did)
            self._finish_cancel(rec)
            return
        rec.mark("waiting_deps")
        try:
            for oid in rec.spec.get("deps", []):
                await self._wait_dep_available(oid)
        except asyncio.CancelledError:
            return  # _finish_cancel cancelled us; returns already settled
        except Exception as e:
            # unrecoverable dep (freed with no lineage): settle the returns
            # with the typed error — parking here would strand every getter.
            # Dep pins stay held (a cancel racing this path may unpin via
            # _finish_cancel; double-unpinning could free live objects)
            rec.mark("failed")
            self._fail_task_returns(rec.spec, e)
            return
        if rec.cancel_requested:
            self._finish_cancel(rec)
            return
        rec.mark("pending")
        # known-blocked shape: park silently; the next capacity change
        # requeues everything (keeps a same-shape submit storm O(1) each)
        sig = rec._sig = self._demand_sig(rec)
        if sig in self._blocked_sigs:
            self._parked.setdefault(sig, collections.deque()).append(rec)
            return
        self.pending_queue.append(rec)
        self._pump()

    # --- lineage reconstruction (object_recovery_manager.h:41) ---

    async def _h_reconstruct_objects(self, conn, msg):
        """A consumer hit ObjectLostError (shm eviction / node death): re-run
        the creating tasks and wait until the objects exist again."""
        results = {}
        for oid in msg["object_ids"]:
            try:
                await self._reconstruct(oid)
                results[oid] = True
            except Exception:
                results[oid] = False
        return results

    async def _reconstruct(self, oid: str):
        fut = self._reconstructing.get(oid)
        if fut is not None:
            return await fut
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self._reconstructing[oid] = fut
        try:
            tid = self.object_lineage.get(oid)
            if tid is None:
                # the free retired the live lineage entry; the bounded
                # breadcrumb (_on_object_freed) may still know the creator
                tid = self._freed_lineage.get(oid)
                if tid is not None:
                    self.object_lineage[oid] = tid
            rec = self.tasks.get(tid or "")
            if rec is None:
                from ..exceptions import ObjectLostError

                raise ObjectLostError(oid)
            # deps whose ENVELOPES are gone must be reconstructed first
            # (deps with stale buffers surface as lost_deps at execution
            # and loop back through here)
            for dep in rec.spec.get("deps", []):
                if not self.objects.contains(dep):
                    await self._reconstruct(dep)
            for rid in rec.spec["return_ids"]:
                self.objects.invalidate(rid)
            for dep in rec.spec.get("deps", []):
                self.objects.pin(dep)
            rec.retries_left = max(rec.retries_left, rec.spec.get("max_retries", 0))
            await self._resolve_and_enqueue(rec)
            await self.objects.wait_available(oid)
            fut.set_result(True)
        except Exception as e:
            fut.set_exception(e)
            raise
        finally:
            self._reconstructing.pop(oid, None)
            if not fut.done():
                # this task died mid-reconstruction (e.g. head shutdown);
                # concurrent waiters on the shared future must not hang —
                # set a real exception, NOT cancel(): CancelledError would
                # escape the waiters' `except Exception` handlers and
                # strand their clients without a reply
                from ..exceptions import ObjectLostError

                fut.set_exception(ObjectLostError(oid))
            if fut.done() and fut.exception() is not None:
                # the future may never be awaited by anyone else
                fut.exception()  # mark retrieved

    # --- actors ---

    async def _h_create_actor(self, conn, msg):
        spec = msg["spec"]
        aid = spec["actor_id"]
        rec = ActorRecord(
            actor_id=aid,
            spec=spec,
            name=spec.get("name"),
            restarts_left=spec.get("max_restarts", 0),
        )
        if rec.name:
            key = (spec.get("namespace", ""), rec.name)
            prev = self.actors.get(self.named_actors.get(key, ""))
            if prev is not None and prev.state != "dead":
                raise ValueError(f"Actor name {rec.name!r} already taken")
            # dead holders (incl. snapshot-restored metadata) are replaceable
            self.named_actors[key] = aid
        self.actors[aid] = rec
        for oid in spec.get("deps", []):
            self.objects.pin(oid)
        self._spawn_bg(self._start_actor(rec))

    async def _start_actor(self, rec: ActorRecord):
        if rec.state == "dead":
            return  # killed while queued for (re)start — stay dead
        rec.state = "starting"
        rec.node_acquired = False
        # a restart must not leave the PREVIOUS incarnation's worker id
        # visible: a concurrent kill would otherwise release resources
        # against the old worker's node
        rec.worker_id = None
        spec = rec.spec
        strategy = spec.get("scheduling_strategy")
        resources = dict(spec.get("resources") or {})

        def release_here():
            # release against the node id THIS start acquired (the kill
            # path can only release once worker_id is assigned; these two
            # are mutually exclusive via node_acquired)
            if rec.node_acquired:
                rec.node_acquired = False
                self._release_node(node_id, resources, strategy)

        for oid in spec.get("deps", []):
            await self._wait_dep_available(oid)
        node_id = await self._acquire_node(resources, strategy)
        if rec.state == "dead":
            # kill_actor landed during the waits above (worker not yet
            # assigned, so the kill path couldn't release this acquisition)
            self._release_node(node_id, resources, strategy)
            return
        rec.node_acquired = True  # stop counting as unmet autoscaler demand
        w = await self._spawn_worker(
            node_id,
            dedicated_actor_id=rec.actor_id,
            runtime_env=spec.get("runtime_env"),
            needs_tpu=resources.get("TPU", 0) > 0,
        )
        if rec.state == "dead":
            # killed during the spawn await, before worker_id was visible
            # to the kill path: release here and reap the fresh worker
            release_here()
            await self._kill_worker(w, reason="actor killed during start")
            return
        rec.worker_id = w.worker_id  # visible to the kill path from here on
        try:
            await asyncio.wait_for(w.registered, cfg.worker_register_timeout_s)
        except asyncio.TimeoutError:
            pass
        if rec.state == "dead":
            # killed mid-registration: _h_kill_actor saw worker_id and
            # released (node_acquired guard makes a second release a no-op)
            release_here()
            await self._kill_worker(w, reason="actor killed during start")
            return
        if w.state not in ("idle", "starting") or w.conn is None:
            rec.state = "dead"
            rec.death_reason = "worker failed to start"
            release_here()
            return
        w.state = "actor"
        try:
            await w.conn.request(
                {
                    "t": "start_actor",
                    "actor_id": rec.actor_id,
                    "cls_key": spec["cls_key"],
                    "args": self._resolve_args(spec),
                    "max_concurrency": spec.get("max_concurrency", 1),
                }
            )
        except Exception as e:  # init failed (or killed mid-init)
            if rec.state != "dead":
                rec.state = "dead"
                rec.death_reason = f"__init__ failed: {e!r}"
            self._release_actor_node(rec, w)
            await self._kill_worker(w, reason="actor init failed")
            await self._fail_backlog(rec)
            return
        if rec.state == "dead":  # killed while __init__ was running
            await self._kill_worker(w, reason="actor killed during start")
            return
        rec.state = "alive"
        backlog, rec.backlog = rec.backlog, []
        for call in backlog:
            self._spawn_bg(self._run_actor_task(rec, call))

    async def _h_submit_actor_task(self, conn, msg):
        spec = msg["spec"]
        for oid in spec["return_ids"]:
            self.objects.add_ref(oid, 1)
        rec = self.actors.get(spec["actor_id"])
        from ..exceptions import ActorDiedError

        if rec is None:
            # submits are fire-and-forget: surface the error through the
            # return objects, not the (absent) reply channel
            self._fail_task_returns(spec, ActorDiedError(spec["actor_id"], "unknown actor"))
            return
        for oid in spec.get("deps", []):
            self.objects.pin(oid)
        if rec.state == "dead":
            for oid in spec.get("deps", []):
                self.objects.unpin(oid)
            self._fail_task_returns(spec, ActorDiedError(rec.actor_id, rec.death_reason))
            return
        if rec.state in ("pending", "starting", "restarting"):
            rec.backlog.append(spec)
            return
        self._spawn_bg(self._run_actor_task(rec, spec))

    async def _run_actor_task(self, rec: ActorRecord, spec: dict):
        from ..exceptions import ActorDiedError

        if rec.send_lock is None:
            rec.send_lock = asyncio.Lock()
        async with rec.send_lock:
            for oid in spec.get("deps", []):
                await self._wait_dep_available(oid)
            w = self.workers.get(rec.worker_id or "")
            if w is None or w.conn is None or w.conn.closed:
                self._fail_task_returns(spec, ActorDiedError(rec.actor_id, "actor worker gone"))
                return
            # visible to cancel_task while the call is in flight (actor
            # calls have no TaskRecord; see _cancel_actor_call)
            self._actor_inflight[spec["task_id"]] = w.worker_id
            reply_fut = asyncio.ensure_future(
                w.conn.request(
                    {
                        "t": "run_task",
                        "task_id": spec["task_id"],
                        "actor_id": rec.actor_id,
                        "method": spec["method"],
                        "args": self._resolve_args(spec),
                        "return_ids": spec["return_ids"],
                        "trace_ctx": spec.get("trace_ctx"),
                    }
                )
            )
        try:
            reply = await reply_fut
            for _ in range(3):
                lost = reply.get("lost_deps")
                if not lost:
                    break
                # dep buffers evicted before the actor read them: the user
                # method never ran, so reconstruct + resend is side-effect
                # safe (same contract as the stateless-task path)
                for oid in lost:
                    await self._reconstruct(oid)
                w = self.workers.get(rec.worker_id or "")
                if w is None or w.conn is None or w.conn.closed:
                    raise ConnectionError("actor worker gone during reconstruction")
                reply = await w.conn.request(
                    {
                        "t": "run_task",
                        "task_id": spec["task_id"],
                        "actor_id": rec.actor_id,
                        "method": spec["method"],
                        "args": self._resolve_args(spec),
                        "return_ids": spec["return_ids"],
                        "trace_ctx": spec.get("trace_ctx"),
                    }
                )
            if "results" not in reply:
                raise RuntimeError(f"unrecoverable deps for {spec['task_id']}")
        except Exception as e:
            # Worker died mid-call (restart path handles backlog) or deps
            # were unrecoverable: fail the returns so consumers never hang.
            self._fail_task_returns(spec, ActorDiedError(rec.actor_id, repr(e)))
            return
        finally:
            self._actor_inflight.pop(spec["task_id"], None)
            for oid in spec.get("deps", []):
                self.objects.unpin(oid)
        self._store_task_results(spec, reply)

    async def _fail_backlog(self, rec: ActorRecord):
        from ..exceptions import ActorDiedError

        backlog, rec.backlog = rec.backlog, []
        for spec in backlog:
            self._fail_task_returns(spec, ActorDiedError(rec.actor_id, rec.death_reason))

    def _unregister_name(self, rec: ActorRecord):
        """Remove the name ONLY if it still maps to this actor — a dead
        holder's name may have been legitimately taken by a replacement
        (e.g. after a snapshot restore), and killing the stale record must
        not unregister the live one."""
        key = (rec.spec.get("namespace", ""), rec.name)
        if self.named_actors.get(key) == rec.actor_id:
            self.named_actors.pop(key, None)

    async def _h_get_named_actor(self, conn, msg):
        key = (msg.get("namespace", ""), msg["name"])
        aid = self.named_actors.get(key)
        if aid is None:
            raise ValueError(f"Failed to look up actor with name {msg['name']!r}")
        rec = self.actors[aid]
        return {"actor_id": aid, "spec_meta": {k: rec.spec.get(k) for k in ("cls_name", "method_names")}}

    async def _h_kill_actor(self, conn, msg):
        rec = self.actors.get(msg["actor_id"])
        if rec is None:
            return False
        rec.restarts_left = 0 if msg.get("no_restart", True) else rec.restarts_left
        rec.state = "dead"
        rec.death_reason = "killed via kill_actor"
        if rec.name:
            self._unregister_name(rec)
        w = self.workers.get(rec.worker_id or "")
        # release the actor's node resources NOW: state is already "dead",
        # so the worker-death path's release is skipped — without this the
        # resources leak and pending actors starve (deadlock under kill-
        # and-replace loops like Tune teardown / Serve scale-down).
        # Except a TPU share: the chip is free only once the process that
        # opened it is gone, and the next TPU actor must not start before.
        holds_chip = (rec.spec.get("resources") or {}).get("TPU", 0) > 0
        if not holds_chip:
            self._release_actor_node(rec, w)
        if w is not None:
            await self._kill_worker(w, reason="actor killed")
            if holds_chip and w.proc is not None:
                await self._wait_proc_exit(w.proc)
        self._release_actor_node(rec, w)  # idempotent
        await self._fail_backlog(rec)
        return True

    @staticmethod
    async def _wait_proc_exit(proc: subprocess.Popen, grace_s: float = 10.0):
        """Wait for a terminated local worker to be gone; SIGKILL it when
        the grace runs out."""
        deadline = time.monotonic() + grace_s
        while proc.poll() is None:
            if time.monotonic() > deadline:
                proc.kill()
                deadline = float("inf")
            await asyncio.sleep(0.02)

    def _adopt_actor_resources(self, rec: ActorRecord, node_id: str) -> None:
        """Charge a re-adopted (head-restart survivor) actor against its
        node's availability — the inverse of _release_actor_node."""
        node = self.nodes.get(node_id)
        if node is None or rec.node_acquired:
            return
        _acquire(node.available, dict(rec.spec.get("resources") or {}))
        rec.node_acquired = True

    def _release_actor_node(self, rec: ActorRecord, w: Optional[WorkerRecord]):
        """Idempotently return an actor's acquired node resources
        (node_acquired guards double release across the kill and
        worker-death paths)."""
        if not rec.node_acquired or w is None:
            return
        rec.node_acquired = False
        self._release_node(
            w.node_id,
            dict(rec.spec.get("resources") or {}),
            rec.spec.get("scheduling_strategy"),
        )

    async def _h_actor_state(self, conn, msg):
        rec = self.actors.get(msg["actor_id"])
        return None if rec is None else rec.state

    # --- placement groups ---

    async def _h_create_placement_group(self, conn, msg):
        spec = msg["spec"]
        bundles = [BundleState(i, dict(b), available=dict(b)) for i, b in enumerate(spec["bundles"])]
        rec = PlacementGroupRecord(
            pg_id=spec["pg_id"],
            bundles=bundles,
            strategy=spec.get("strategy", "PACK"),
            name=spec.get("name"),
            ready_event=asyncio.Event(),
        )
        self.placement_groups[rec.pg_id] = rec
        self._spawn_bg(self._schedule_pg(rec))

    async def _schedule_pg(self, rec: PlacementGroupRecord):
        while rec.state == "pending" and not self._shutdown:
            if self._try_place_pg(rec):
                rec.state = "created"
                rec.ready_event.set()
                # tasks targeting this PG may have parked while it was
                # pending — their sigs become placeable exactly now
                self._capacity_changed(bulk=False)
                return
            await asyncio.sleep(0.05)

    def _try_place_pg(self, rec: PlacementGroupRecord) -> bool:
        """All-or-nothing bundle placement (bundle_scheduling_policy.cc analogue)."""
        nodes = [n for n in self.nodes.values() if n.alive]
        avail = {n.node_id: dict(n.available) for n in nodes}
        assignment: List[Tuple[BundleState, str]] = []
        strategy = rec.strategy

        def place(bundle, node_ids):
            for nid in node_ids:
                if _fits(avail[nid], bundle.resources):
                    _acquire(avail[nid], bundle.resources)
                    assignment.append((bundle, nid))
                    return True
            return False

        node_ids = [n.node_id for n in nodes]
        used_nodes: List[str] = []
        for b in rec.bundles:
            if strategy in ("PACK", "STRICT_PACK"):
                order = used_nodes + [n for n in node_ids if n not in used_nodes]
            elif strategy in ("SPREAD", "STRICT_SPREAD"):
                fresh = [n for n in node_ids if n not in used_nodes]
                order = fresh + (used_nodes if strategy == "SPREAD" else [])
            else:
                order = node_ids
            if not place(b, order):
                return False
            nid = assignment[-1][1]
            if nid not in used_nodes:
                used_nodes.append(nid)
        if strategy == "STRICT_PACK" and len({nid for _, nid in assignment}) > 1:
            return False
        if strategy == "STRICT_SPREAD" and len({nid for _, nid in assignment}) < len(rec.bundles):
            return False
        for b, nid in assignment:
            b.node_id = nid
            _acquire(self.nodes[nid].available, b.resources)
        return True

    async def _h_pg_ready(self, conn, msg):
        rec = self.placement_groups.get(msg["pg_id"])
        if rec is None:
            raise ValueError("unknown placement group")
        timeout = msg.get("timeout")
        # timeout=0 is a state POLL: wait_for(coro, 0) raises TimeoutError
        # before the fresh event.wait() coroutine can even observe a set
        # event, so check the flag directly first
        if rec.ready_event.is_set():
            return True
        if timeout == 0:
            return False
        try:
            await asyncio.wait_for(rec.ready_event.wait(), timeout)
            return True
        except asyncio.TimeoutError:
            return False

    async def _h_remove_placement_group(self, conn, msg):
        rec = self.placement_groups.pop(msg["pg_id"], None)
        if rec is None:
            return False
        if rec.state == "created":
            for b in rec.bundles:
                if b.node_id:
                    # return only what the PG still holds
                    held = {k: v - (b.resources[k] - b.available.get(k, 0.0)) for k, v in b.resources.items()}
                    _release(self.nodes[b.node_id].available, held)
            # bundle resources returned to their nodes: parked tasks may fit
            self._capacity_changed(bulk=False)
        rec.state = "removed"
        return True

    async def _h_pg_table(self, conn, msg):
        out = {}
        for pid, rec in self.placement_groups.items():
            out[pid] = {
                "state": rec.state,
                "strategy": rec.strategy,
                "bundles": [
                    {"index": b.index, "resources": b.resources, "node_id": b.node_id}
                    for b in rec.bundles
                ],
            }
        return out

    # --- cluster info / nodes ---

    async def _h_add_node(self, conn, msg):
        node_id = msg["node_id"]
        self.nodes[node_id] = NodeRecord(node_id, dict(msg["resources"]), labels=msg.get("labels", {}))
        self._capacity_changed()
        return node_id

    async def _h_remove_node(self, conn, msg):
        rec = self.nodes.get(msg["node_id"])
        if rec is None:
            return False
        rec.alive = False
        for w in list(self.workers.values()):
            if w.node_id == rec.node_id:
                await self._kill_worker(w, reason="node removed")
        if rec.remote and not rec.conn.closed:
            try:
                await rec.conn.request({"t": "shutdown"}, timeout=2)
            except Exception:
                pass
            await rec.conn.close()
        return True

    async def _h_pending_demands(self, conn, msg):
        """Unfulfilled resource demands: queued tasks + unscheduled actors +
        pending placement-group bundles (reference: LoadMetrics fed to the
        autoscaler from GCS resource reports, autoscaler.py:172)."""
        demands: List[Dict[str, float]] = []
        for rec in self.pending_queue:
            demands.append(dict(rec.resources))
        for dq in self._parked.values():
            for rec in dq:
                demands.append(dict(rec.resources))
        for a in self.actors.values():
            if a.state in ("pending", "starting") and not a.node_acquired:
                res = dict(a.spec.get("resources") or {})
                if res:  # zero-resource actors place anywhere: no demand
                    demands.append(res)
        bundles = []
        for pg in self.placement_groups.values():
            if pg.state == "pending":
                bundles.append([dict(b.resources) for b in pg.bundles])
        return {"demands": demands, "pg_bundles": bundles}

    async def _h_cluster_resources(self, conn, msg):
        total: Dict[str, float] = collections.Counter()
        avail: Dict[str, float] = collections.Counter()
        for n in self.nodes.values():
            if n.alive:
                for k, v in n.resources.items():
                    total[k] += v
                for k, v in n.available.items():
                    avail[k] += v
        return {"total": dict(total), "available": dict(avail)}

    async def _h_resource_report(self, conn, msg):
        """Fold an agent's periodic load report into the node table
        (reference: ray_syncer resource gossip landing in GCS)."""
        node = self.nodes.get(msg["node_id"])
        if node is not None:
            node.load_report = msg["report"]
            self._record_node_history(msg["node_id"], msg["report"])

    def _record_node_history(self, node_id: str, report: dict) -> None:
        """Bounded per-node time series feeding the dashboard's resource
        sparklines (reference: dashboard/modules/reporter metrics)."""
        hist = self.node_history.get(node_id)
        if hist is None:
            hist = self.node_history[node_id] = collections.deque(maxlen=150)
        hist.append(
            {
                "ts": report.get("ts", time.time()),
                "load_1m": report.get("load_1m"),
                "mem_frac": (
                    report.get("mem_used", 0) / report["mem_total"]
                    if report.get("mem_total")
                    else None
                ),
                "workers": report.get("workers"),
            }
        )

    async def _h_node_history(self, conn, msg):
        # the head node has no agent reporting for it: sample locally on
        # each poll (dashboard ticks ~2s — plenty for a sparkline)
        try:
            from .memory_monitor import MemoryMonitor

            used, total = MemoryMonitor().sample()
            self._record_node_history(
                self._head_node_id,
                {
                    "ts": time.time(),
                    "load_1m": os.getloadavg()[0],
                    "mem_used": used,
                    "mem_total": total,
                    "workers": sum(
                        1 for w in self.workers.values() if w.state != "dead"
                    ),
                },
            )
        except Exception:
            pass
        return {nid: list(h) for nid, h in self.node_history.items()}

    async def _h_nodes(self, conn, msg):
        return [
            {
                "node_id": n.node_id,
                "alive": n.alive,
                "resources": n.resources,
                "available": n.available,
                "labels": n.labels,
                "load_report": n.load_report,
            }
            for n in self.nodes.values()
        ]

    async def _h_list_actors(self, conn, msg):
        return [
            {
                "actor_id": a.actor_id,
                "state": a.state,
                "name": a.name,
                "class_name": a.spec.get("cls_name"),
                "worker_id": a.worker_id,
            }
            for a in self.actors.values()
        ]

    async def _h_ping(self, conn, msg):
        return "pong"

    async def _h_profile_worker(self, conn, msg):
        """On-demand profiling of a live worker (reference:
        dashboard/modules/reporter/profile_manager.py). Forwards the request
        to the worker's own sampler (worker_main._profile) and relays the
        collapsed-stack / allocation report back to the caller."""
        wid = msg.get("worker_id")
        w = self.workers.get(wid or "")
        if w is None or w.conn is None or w.conn.closed or w.state == "dead":
            raise ValueError(f"no live worker {wid!r}")
        duration = min(60.0, float(msg.get("duration_s", 2.0)))
        return await asyncio.wait_for(
            w.conn.request(
                {
                    "t": "profile",
                    "kind": msg.get("kind", "cpu"),
                    "duration_s": duration,
                    # floor keeps the sampler from busy-spinning the GIL
                    # inside the very worker it's observing
                    "interval_s": max(0.001, float(msg.get("interval_s", 0.01))),
                }
            ),
            timeout=duration + 30.0,
        )

    # ------------------------------------------------------------------
    # pubsub (reference: src/ray/pubsub — long-poll publisher/subscriber
    # for object-location/actor/node/log channels; serve's config push,
    # serve/_private/long_poll.py:68, is the same mechanism)
    # ------------------------------------------------------------------

    async def _h_publish(self, conn, msg):
        ch = msg["channel"]
        seq, _ = self.channels.get(ch, (0, None))
        seq += 1
        self.channels[ch] = (seq, msg["data"])
        # wake long-pollers (they loop and re-check the seq)
        ev = self._channel_events.pop(ch, None)
        if ev is not None:
            ev.set()
        # push to streaming subscribers (strong task refs: the loop holds
        # tasks weakly, and a dropped push would silently strand a
        # latest-snapshot subscriber on stale data)
        loop = asyncio.get_running_loop()
        for c in list(self.channel_subscribers.get(ch, ())):
            if c.closed:
                self.channel_subscribers[ch].discard(c)
                continue
            task = loop.create_task(
                self._push_one(c, {"t": "pub", "channel": ch, "seq": seq,
                                   "data": msg["data"]})
            )
            self._push_tasks.add(task)
            task.add_done_callback(self._push_tasks.discard)
        return seq

    @staticmethod
    async def _push_one(conn, msg):
        try:
            await conn.send(msg)
        except Exception:
            pass  # conn died mid-push; conn-close cleanup drops the sub

    async def _h_subscribe(self, conn, msg):
        ch = msg["channel"]
        self.channel_subscribers[ch].add(conn)
        if not hasattr(conn, "_subscribed_channels"):
            conn._subscribed_channels = set()
        conn._subscribed_channels.add(ch)
        seq, data = self.channels.get(ch, (0, None))
        return {"seq": seq, "data": data}

    async def _h_unsubscribe(self, conn, msg):
        ch = msg["channel"]
        subs = self.channel_subscribers.get(ch)
        if subs is not None:
            subs.discard(conn)
            if not subs:
                del self.channel_subscribers[ch]
        if hasattr(conn, "_subscribed_channels"):
            conn._subscribed_channels.discard(ch)
        return True

    async def _h_poll_channel(self, conn, msg):
        """Long-poll: return (seq, data) as soon as seq > last_seq, or
        {"timeout": True} after `timeout` seconds (client re-polls)."""
        ch = msg["channel"]
        last = msg.get("last_seq", 0)
        timeout = msg.get("timeout", 30.0)
        deadline = time.monotonic() + timeout
        while True:
            seq, data = self.channels.get(ch, (0, None))
            if seq > last:
                return {"seq": seq, "data": data}
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return {"seq": last, "timeout": True}
            ev = self._channel_events.setdefault(ch, asyncio.Event())
            self._channel_waiters[ch] = self._channel_waiters.get(ch, 0) + 1
            try:
                # no shield: cancelling Event.wait() is side-effect free, and
                # shielding would leak one pending waiter per poll timeout
                await asyncio.wait_for(ev.wait(), remaining)
            except asyncio.TimeoutError:
                return {"seq": last, "timeout": True}
            finally:
                # last waiter out drops the Event — churning channel names
                # that time out without a publish must not grow head memory
                n = self._channel_waiters.get(ch, 1) - 1
                if n <= 0:
                    self._channel_waiters.pop(ch, None)
                    if self._channel_events.get(ch) is ev and not ev.is_set():
                        self._channel_events.pop(ch, None)
                else:
                    self._channel_waiters[ch] = n

    # ------------------------------------------------------------------
    # state API + observability (reference: dashboard/state_aggregator.py,
    # experimental/state/api.py; task events: gcs_task_manager.h:61)
    # ------------------------------------------------------------------

    async def _h_cancel_task(self, conn, msg):
        """Cancel a task (reference: python/ray/_private/worker.py
        ray.cancel -> CoreWorker::CancelTask). Queued tasks are dropped and
        their returns resolve to TaskCancelledError; running tasks get the
        cancellation raised asynchronously in the executing worker thread;
        force=True kills the worker process instead. Returns True when the
        cancel took effect (False: unknown/already finished)."""
        tid = msg["task_id"]
        rec = self.tasks.get(tid)
        if rec is None:
            return await self._cancel_actor_call(tid, msg.get("force", False))
        if rec.state in ("done", "failed", "cancelled"):
            return False
        rec.cancel_requested = True
        if rec.state in ("pending", "waiting_deps"):
            # sits in pending_queue/_parked (or a dep/retry wait): finish
            # now, the queues drop the record lazily when they pop it
            self._finish_cancel(rec)
            return True
        if rec.state == "scheduled":
            return True  # _dispatch_task checks the flag before pushing
        # running
        w = self.workers.get(rec.worker_id or "")
        if w is not None and w.state != "dead":
            if msg.get("force"):
                # the 'running' state may be a LAGGED batched record for a
                # direct-pushed task that already finished — ask the worker
                # whether it is actually executing this task before killing
                # it (the probe itself async-cancels when it is)
                running = "executing"
                if w.conn is not None and not w.conn.closed:
                    try:
                        running = await w.conn.request(
                            {"t": "cancel_task", "task_id": tid}, timeout=5
                        )
                    except Exception:
                        running = "executing"  # conn broken: the kill is moot/safe
                if not running:
                    return False
                if running == "queued":
                    # dispatched but never started: the worker flagged it
                    # for drop-before-run — cancel took effect; killing the
                    # worker would only murder whatever OTHER task is on
                    # its executor thread
                    return True
                await self._kill_worker(w, reason=f"task {tid} force-cancelled")
            elif w.conn is not None and not w.conn.closed:
                try:
                    await w.conn.send({"t": "cancel_task", "task_id": tid})
                except Exception:
                    pass
        return True

    async def _cancel_actor_call(self, tid: str, force: bool) -> bool:
        """Cancel a head-routed actor method call — these have no
        TaskRecord. Backlogged (actor still starting/restarting): drop the
        spec and settle its returns. In flight on the actor's worker:
        forward so the worker raises in the executing thread. force is
        deliberately ignored for actor calls (killing the worker would
        destroy actor state; reference rejects force on actor tasks)."""
        from ..exceptions import TaskCancelledError

        for a in self.actors.values():
            for spec in a.backlog:
                if spec["task_id"] == tid:
                    a.backlog.remove(spec)
                    for oid in spec.get("deps", []):
                        self.objects.unpin(oid)
                    self._fail_task_returns(
                        spec, TaskCancelledError(f"task {tid} was cancelled")
                    )
                    return True
        wid = self._actor_inflight.get(tid)
        if wid:
            w = self.workers.get(wid)
            if w is not None and w.state != "dead" and w.conn is not None:
                try:
                    await w.conn.send({"t": "cancel_task", "task_id": tid})
                except Exception:
                    pass
                return True
        return False

    def _finish_cancel(self, rec: TaskRecord):
        from ..exceptions import TaskCancelledError

        if rec.state == "cancelled":
            return  # idempotent: racing paths must not double-unpin deps
        rec.mark("cancelled")
        for oid in rec.spec.get("deps", []):
            self.objects.unpin(oid)
        self._fail_task_returns(
            rec.spec,
            TaskCancelledError(f"task {rec.spec.get('task_id')} was cancelled"),
        )
        t = getattr(rec, "_resolve_task", None)
        if t is not None and t is not asyncio.current_task():
            # a dep-waiting coroutine would otherwise park on
            # wait_available forever if the dep never materializes
            t.cancel()

    async def _h_task_count(self, conn, msg):
        # O(1) backlog probe: stress monitors must not pay the O(n) pickle
        # of list_tasks just to watch a 100k-task queue fill
        return len(self.tasks)

    async def _h_list_tasks(self, conn, msg):
        # limit=0 means "all" (client-side filters need the full set)
        limit = msg.get("limit", 1000)
        items = list(self.tasks.items())
        if limit:
            items = items[-limit:]
        out = []
        for tid, t in items:
            out.append(
                {
                    "task_id": tid,
                    "name": t.spec.get("name") or t.spec.get("fn_key", ""),
                    "state": t.state,
                    "node_id": t.node_id,
                    "worker_id": t.worker_id,
                    "events": list(t.events),
                    "retries_left": t.retries_left,
                }
            )
        return out

    async def _h_list_objects(self, conn, msg):
        limit = msg.get("limit", 1000)  # 0 = all
        out = []
        from .serialization import shm_buffer_names

        items = list(self.objects.objects.items())
        if limit:
            items = items[:limit]
        for oid, env in items:
            try:
                size = env.total_bytes()
            except Exception:
                size = 0
            try:
                in_shm = bool(shm_buffer_names(env))
            except Exception:
                in_shm = False
            out.append(
                {
                    "object_id": oid,
                    "size_bytes": size,
                    "refcount": int(self.objects.refcounts.get(oid, 0)),
                    "pins": int(self.objects.task_pins.get(oid, 0)),
                    "is_error": bool(getattr(env, "is_error", False)),
                    "in_shm": in_shm,
                }
            )
        return out

    async def _h_list_workers(self, conn, msg):
        return [
            {
                "worker_id": w.worker_id,
                "node_id": w.node_id,
                "state": w.state,
                "actor_id": w.actor_id,
                "pid": w.proc.pid if w.proc else None,
            }
            for w in self.workers.values()
        ]

    async def _h_timeline(self, conn, msg):
        """Chrome-tracing events (reference: python/ray/_private/profiling.py
        `ray timeline`): one complete event per task run + instant events
        for failures."""
        events = []
        for tid, t in self.tasks.items():
            times = dict(t.events)
            start = times.get("running")
            if start is None:
                continue
            end = times.get("done") or times.get("failed") or time.time()
            events.append(
                {
                    "name": t.spec.get("name") or t.spec.get("fn_key", "task"),
                    "cat": "task",
                    "ph": "X",
                    "ts": start * 1e6,
                    "dur": (end - start) * 1e6,
                    "pid": t.node_id or "?",
                    "tid": t.worker_id or "?",
                    "args": {"task_id": tid, "state": t.state},
                }
            )
        return events

    # ------------------------------------------------------------------
    # job submission (reference: dashboard/modules/job/job_manager.py —
    # JobSupervisor subprocess per submission; collapsed onto the head)
    # ------------------------------------------------------------------

    async def _h_submit_job(self, conn, msg):
        import uuid as _uuid

        sid = msg.get("submission_id") or f"raysubmit_{_uuid.uuid4().hex[:16]}"
        if sid in self.jobs:
            raise ValueError(f"submission_id {sid!r} already exists")
        runtime_env = msg.get("runtime_env") or {}
        log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(log_dir, exist_ok=True)
        log_path = os.path.join(log_dir, f"job-{sid}.log")
        env = dict(os.environ)
        env["RAY_TPU_ADDRESS"] = self.socket_path
        env["RAY_TPU_SUBMISSION_ID"] = sid
        if runtime_env:
            # the job's runtime_env (env_vars included) is the DEFAULT for
            # every task/actor the job driver submits (reference: job-level
            # runtime_env semantics)
            import json as _json

            env["RAY_TPU_JOB_RUNTIME_ENV"] = _json.dumps(dict(runtime_env))
        for k, v in (runtime_env.get("env_vars") or {}).items():
            env[k] = str(v)
        # the job runs a fresh interpreter: the cluster's code (this package)
        # must stay importable, MERGED with any user-supplied PYTHONPATH
        from .spawn import child_pythonpath, framework_root

        # framework root FIRST (a stale vendored ray_tpu must not shadow
        # the cluster's), then the user's PYTHONPATH with its normal
        # precedence over site-packages, then this process's sys.path
        env["PYTHONPATH"] = child_pythonpath(
            [framework_root()], inherited=env.get("PYTHONPATH")
        )
        cwd = os.getcwd()
        loop = asyncio.get_running_loop()
        if runtime_env.get("working_dir"):
            cwd = await loop.run_in_executor(
                None, self._stage_dir, runtime_env["working_dir"]
            )
            env["PYTHONPATH"] = cwd + os.pathsep + env["PYTHONPATH"]
        for mod in runtime_env.get("py_modules") or []:
            staged = await loop.run_in_executor(None, self._stage_dir, mod)
            mod_path = staged if os.path.isdir(staged) else os.path.dirname(staged)
            env["PYTHONPATH"] = mod_path + os.pathsep + env["PYTHONPATH"]
        logf = open(log_path, "ab")
        # own session/process group: stop_job must reach grandchildren of the
        # shell (compound entrypoints), not just /bin/sh
        proc = subprocess.Popen(
            msg["entrypoint"],
            shell=True,
            env=env,
            cwd=cwd,
            stdout=logf,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        logf.close()
        self.jobs[sid] = {
            "submission_id": sid,
            "entrypoint": msg["entrypoint"],
            "status": "RUNNING",
            "proc": proc,
            "log_path": log_path,
            "start_time": time.time(),
            "end_time": None,
            "metadata": msg.get("metadata") or {},
        }
        self._spawn_bg(self._watch_job(sid))
        return sid

    async def _watch_job(self, sid: str):
        job = self.jobs[sid]
        code = await asyncio.get_running_loop().run_in_executor(None, job["proc"].wait)
        if job["status"] == "STOPPED":
            pass  # stop_job already settled it
        else:
            job["status"] = "SUCCEEDED" if code == 0 else "FAILED"
        job["end_time"] = time.time()
        job["exit_code"] = code

    def _job_view(self, job: dict) -> dict:
        return {k: v for k, v in job.items() if k != "proc"}

    async def _h_job_status(self, conn, msg):
        job = self.jobs.get(msg["submission_id"])
        if job is None:
            raise ValueError(f"no such job {msg['submission_id']!r}")
        return job["status"]

    async def _h_job_info(self, conn, msg):
        job = self.jobs.get(msg["submission_id"])
        if job is None:
            raise ValueError(f"no such job {msg['submission_id']!r}")
        return self._job_view(job)

    async def _h_list_jobs(self, conn, msg):
        return [self._job_view(j) for j in self.jobs.values()]

    async def _h_job_logs(self, conn, msg):
        job = self.jobs.get(msg["submission_id"])
        if job is None:
            raise ValueError(f"no such job {msg['submission_id']!r}")
        try:
            with open(job["log_path"], "rb") as f:
                return f.read().decode(errors="replace")
        except FileNotFoundError:
            return ""

    async def _h_stop_job(self, conn, msg):
        job = self.jobs.get(msg["submission_id"])
        if job is None:
            raise ValueError(f"no such job {msg['submission_id']!r}")
        if job["status"] == "RUNNING":
            job["status"] = "STOPPED"
            self._terminate_job_proc(job["proc"])
            self._spawn_bg(self._escalate_kill(job["proc"]))
        return True

    # ------------------------------------------------------------------
    # head:// storage plane (reference: the role object storage / a redis-
    # backed GCS plays for air checkpoints — here a chunked tar transfer
    # onto the head host's stable storage dir; train/storage.py is the
    # client). Keys are sanitized relative paths; payloads stream in
    # bounded chunks so a multi-GB checkpoint never lands in one message.
    # ------------------------------------------------------------------

    def _stor_path(self, key: str) -> str:
        root = os.path.abspath(cfg.head_storage_dir)
        norm = os.path.normpath(key)
        if norm.startswith("..") or os.path.isabs(norm) or not norm or norm == ".":
            raise ValueError(f"bad storage key {key!r}")
        return os.path.join(root, norm + ".tar")

    _STOR_UPLOAD_IDLE_S = 3600.0  # reap sessions abandoned by dead clients
    _STOR_REAP_PERIOD_S = 300.0

    def _stor_reap_sessions(self):
        """Close + delete upload/read sessions idle past the reap window,
        and sweep orphaned .up-* tmp files (e.g. from a previous head
        crash). Lazy + rate-limited from stor_begin; the filesystem walk
        runs in an executor so the control loop never blocks on it."""
        now = time.time()
        if now - getattr(self, "_stor_last_reap", 0.0) < self._STOR_REAP_PERIOD_S:
            return
        self._stor_last_reap = now
        for token, (f, tmp, _path, last) in list(
            getattr(self, "_stor_uploads", {}).items()
        ):
            if now - last > self._STOR_UPLOAD_IDLE_S:
                del self._stor_uploads[token]
                f.close()
                try:
                    os.remove(tmp)
                except OSError:
                    pass
        for token, (f, last) in list(getattr(self, "_stor_reads", {}).items()):
            if now - last > self._STOR_UPLOAD_IDLE_S:
                del self._stor_reads[token]
                f.close()
        live_tmp = {t[1] for t in getattr(self, "_stor_uploads", {}).values()}
        root = os.path.abspath(cfg.head_storage_dir)

        def _sweep():
            for dirpath, _dirs, files in os.walk(root):
                for name in files:
                    p = os.path.join(dirpath, name)
                    if ".up-" in name and p not in live_tmp:
                        try:
                            if now - os.path.getmtime(p) > self._STOR_UPLOAD_IDLE_S:
                                os.remove(p)
                        except OSError:
                            pass

        self._spawn_bg(asyncio.to_thread(_sweep))

    async def _h_stor_begin(self, conn, msg):
        import uuid as _uuid

        path = self._stor_path(msg["key"])  # validates the key up front
        if not hasattr(self, "_stor_uploads"):
            self._stor_uploads = {}
        self._stor_reap_sessions()
        token = _uuid.uuid4().hex
        tmp = f"{path}.up-{token}"
        os.makedirs(os.path.dirname(tmp), exist_ok=True)
        self._stor_uploads[token] = (open(tmp, "wb"), tmp, path, time.time())
        return token

    async def _h_stor_chunk(self, conn, msg):
        f, tmp, path, _last = self._stor_uploads[msg["token"]]
        self._stor_uploads[msg["token"]] = (f, tmp, path, time.time())
        await asyncio.get_running_loop().run_in_executor(None, f.write, msg["data"])
        return True

    async def _h_stor_end(self, conn, msg):
        f, tmp, path, _last = self._stor_uploads.pop(msg["token"])
        f.close()
        os.replace(tmp, path)
        return True

    async def _h_stor_size(self, conn, msg):
        try:
            return os.path.getsize(self._stor_path(msg["key"]))
        except FileNotFoundError:
            return None

    async def _h_stor_open(self, conn, msg):
        """Open a read session: the held fd pins ONE version of the object
        (os.replace swaps the directory entry, not the open inode), so a
        download that races a concurrent overwrite still sees a consistent
        snapshot instead of interleaved bytes. Returns (token, size) or
        None when absent."""
        import uuid as _uuid

        path = self._stor_path(msg["key"])
        if not hasattr(self, "_stor_reads"):
            self._stor_reads = {}
        self._stor_reap_sessions()  # download-heavy workloads reap too
        try:
            f = open(path, "rb")
        except FileNotFoundError:
            return None
        token = _uuid.uuid4().hex
        f.seek(0, os.SEEK_END)
        size = f.tell()
        self._stor_reads[token] = (f, time.time())
        return token, size

    async def _h_stor_read(self, conn, msg):
        f, _last = self._stor_reads[msg["token"]]
        self._stor_reads[msg["token"]] = (f, time.time())
        offset, size = msg["offset"], msg["size"]

        def _read():
            f.seek(offset)
            return f.read(size)

        return await asyncio.get_running_loop().run_in_executor(None, _read)

    async def _h_stor_close(self, conn, msg):
        entry = self._stor_reads.pop(msg["token"], None)
        if entry is not None:
            entry[0].close()
        return True

    async def _h_stor_del(self, conn, msg):
        path = self._stor_path(msg["key"])

        def _del():
            try:
                os.remove(path)
            except FileNotFoundError:
                pass
            # a key may also be a PREFIX of per-file keys (workflow sync
            # lays out <wf>/meta.json, <wf>/steps/... as individual objects)
            shutil.rmtree(path[: -len(".tar")], ignore_errors=True)

        # off-loop: deleting a multi-GB prefix must not stall the control
        # plane (reference: GCS store ops never run on the main loop)
        await asyncio.get_running_loop().run_in_executor(None, _del)
        return True

    async def _h_stor_list(self, conn, msg):
        root = os.path.abspath(cfg.head_storage_dir)
        norm = os.path.normpath(msg["prefix"])
        if norm.startswith("..") or os.path.isabs(norm) or not norm or norm == ".":
            raise ValueError(f"bad storage prefix {msg['prefix']!r}")
        prefix = os.path.join(root, norm)
        if not os.path.isdir(prefix):
            return []
        out = []
        for name in sorted(os.listdir(prefix)):
            if name.endswith(".tar") and ".up-" not in name:
                out.append(name[: -len(".tar")])
            elif os.path.isdir(os.path.join(prefix, name)):
                out.append(name)
        return out

    async def _h_report_data_stats(self, conn, msg):
        """Driver-reported Dataset execution stats (reference: the data
        module's StatsActor feeding the dashboard's DataHead). Bounded ring:
        the dashboard shows recent executions, not history."""
        if not hasattr(self, "_data_stats"):
            from collections import deque

            self._data_stats = deque(maxlen=50)
        self._data_stats.append(msg["stats"])
        return True

    async def _h_data_stats(self, conn, msg):
        return list(getattr(self, "_data_stats", ()))

    async def _h_get_package(self, conn, msg):
        """Serve an uploaded working-dir package's bytes to a node agent so
        pkg:// runtime envs stage on remote nodes too (reference:
        runtime_env_agent downloading from GCS object storage —
        _private/runtime_env/packaging.py download_and_unpack_package)."""
        name = msg["name"]
        if "/" in name or ".." in name or not name:
            raise ValueError(f"bad package name {name!r}")
        path = os.path.join(self.session_dir, "packages", name)
        loop = asyncio.get_running_loop()

        def _read():
            with open(path, "rb") as f:
                return f.read()

        try:
            return await loop.run_in_executor(None, _read)
        except FileNotFoundError:
            raise ValueError(f"no such uploaded package {name!r}") from None

    async def _h_delete_job(self, conn, msg):
        """Remove a TERMINAL job's record (reference: job_head.py DELETE
        /api/jobs/{id} — running jobs must be stopped first)."""
        job = self.jobs.get(msg["submission_id"])
        if job is None:
            raise ValueError(f"no such job {msg['submission_id']!r}")
        if job["status"] in ("PENDING", "RUNNING"):
            raise ValueError(
                f"job {msg['submission_id']!r} is {job['status']}; stop it first"
            )
        del self.jobs[msg["submission_id"]]
        return True

    async def _escalate_kill(self, proc, grace_s: float = 3.0):
        """SIGTERM then, if the group ignores it, SIGKILL (reference:
        JobSupervisor stop escalation)."""
        import signal

        await asyncio.sleep(grace_s)
        if proc.poll() is None:
            self._terminate_job_proc(proc, sig=signal.SIGKILL)

    @staticmethod
    def _terminate_job_proc(proc, sig=None):
        import signal

        sig = sig if sig is not None else signal.SIGTERM
        try:  # whole process group (start_new_session at spawn)
            os.killpg(os.getpgid(proc.pid), sig)
        except (ProcessLookupError, PermissionError, OSError):
            try:
                proc.send_signal(sig)
            except Exception:
                pass

    async def _h_push_metrics(self, conn, msg):
        # snapshots merged per (process, metric); aggregation happens at read
        if conn.closed:
            return  # connection already torn down: don't resurrect pruned state
        if not hasattr(conn, "_metric_procs"):
            conn._metric_procs = set()
        conn._metric_procs.add(msg["proc"])
        self.metrics_store[msg["proc"]] = {"ts": time.time(), "metrics": msg["metrics"]}

    async def _h_get_metrics(self, conn, msg):
        return dict(self.metrics_store)

    async def _h_push_serve_events(self, conn, msg):
        # pushes are DELTAS (events past the proc's last pushed seq, see
        # serve/telemetry.py flush_events): append by seq, bounded per
        # proc — the head's window can outlive the pusher's local ring
        prev = self.serve_events_store.get(msg["proc"])
        events = msg.get("events", [])
        if prev is not None and events:
            last = prev["events"][-1].get("seq", 0) if prev["events"] else 0
            fresh = [e for e in events if e.get("seq", 0) > last]
            if fresh:
                merged = prev["events"] + fresh
            else:
                # seq RESTARTED under a reused proc key (pid reuse, or a
                # rebuilt recorder): a non-empty batch entirely at-or-
                # below the stored seq is a new generation — replace, or
                # the new process's recorder would never reach the head
                merged = list(events)
        else:
            merged = list(events) if events else (
                prev["events"] if prev is not None else []
            )
        self.serve_events_store[msg["proc"]] = {
            "ts": time.time(),
            "events": merged[-8192:],
            "dropped": msg.get("dropped", 0),
        }
        # proc-count bound: prefer evicting entries stale for a while
        # (their post-mortem window has had time to be read); a crashed
        # replica's FINAL snapshot must not be the first thing churn
        # evicts, so fresh-but-silent entries go only when nothing stale
        # remains
        while len(self.serve_events_store) > 256:
            now = time.time()
            stale = [p for p, v in self.serve_events_store.items()
                     if now - v["ts"] > 900.0]
            pool = stale or list(self.serve_events_store)
            oldest = min(pool,
                         key=lambda p: self.serve_events_store[p]["ts"])
            del self.serve_events_store[oldest]

    async def _h_get_serve_events(self, conn, msg):
        return dict(self.serve_events_store)

    # ------------------------------------------------------------------
    # scheduling + worker pool
    # ------------------------------------------------------------------

    def _select_node(self, resources: Dict[str, float], strategy) -> Optional[str]:
        """Hybrid policy (hybrid_scheduling_policy.h:50): prefer the head/local
        node below the utilization threshold, else least-utilized feasible."""
        if isinstance(strategy, dict) and strategy.get("type") == "placement_group":
            pg = self.placement_groups.get(strategy["pg_id"])
            if pg is None or pg.state != "created":
                return None
            idx = strategy.get("bundle_index", -1)
            bundles = pg.bundles if idx == -1 else [pg.bundles[idx]]
            for b in bundles:
                if _fits(b.available, resources):
                    _acquire(b.available, resources)
                    return b.node_id
            return None
        if isinstance(strategy, dict) and strategy.get("type") == "node_affinity":
            n = self.nodes.get(strategy["node_id"])
            if n is not None and n.alive and _fits(n.available, resources):
                _acquire(n.available, resources)
                return n.node_id
            if strategy.get("soft"):
                pass  # fall through to hybrid
            else:
                return None
        candidates = []
        for n in self.nodes.values():
            if n.alive and _fits(n.available, resources):
                used = sum(
                    1 - (n.available.get(k, 0) / v) for k, v in n.resources.items() if v
                ) / max(1, len(n.resources))
                candidates.append((used, n.node_id != self._head_node_id, n.node_id))
        if not candidates:
            return None
        if strategy == "SPREAD":
            candidates.sort(key=lambda c: c[0])
        else:
            head = [c for c in candidates if not c[1] and c[0] < cfg.scheduler_spread_threshold]
            if head:
                candidates = head
            else:
                candidates.sort(key=lambda c: c[0])
        nid = candidates[0][2]
        _acquire(self.nodes[nid].available, resources)
        return nid

    async def _acquire_node(self, resources: Dict[str, float], strategy=None) -> str:
        while True:
            nid = self._select_node(resources, strategy)
            if nid is not None:
                return nid
            await asyncio.sleep(0.02)

    def _release_node(self, node_id: str, resources: Dict[str, float], strategy=None):
        if isinstance(strategy, dict) and strategy.get("type") == "placement_group":
            pg = self.placement_groups.get(strategy["pg_id"])
            if pg is not None and pg.state == "created":
                idx = strategy.get("bundle_index", -1)
                bundles = pg.bundles if idx == -1 else [pg.bundles[idx]]
                for b in bundles:
                    if b.node_id == node_id:
                        _release(b.available, resources)
                        return
            return
        n = self.nodes.get(node_id)
        if n is not None:
            _release(n.available, resources)

    @staticmethod
    def _demand_sig(rec: TaskRecord):
        strategy = rec.spec.get("scheduling_strategy")
        return (
            tuple(sorted(rec.resources.items())),
            strategy if isinstance(strategy, str) else repr(strategy),
        )

    def _capacity_changed(self, bulk: bool = True):
        """Cluster capacity moved: previously-unplaceable demand shapes may
        fit now. Two regimes, because requeue cost must match the size of
        the capacity event, or a 100k-task parked backlog melts the head:

        - bulk=True (node joined/registered ONLY — the sites that add node
          resource capacity): rare, arbitrarily large capacity — requeue
          EVERYTHING and re-pump.
        - bulk=False (everything else: lease released, worker registered/
          died, PG created/removed, safety valve): probe each parked
          shape's HEAD and keep promoting until the probe misses —
          O(#shapes + #promoted) per event, never O(parked tasks).

        Submit paths must NOT call this; they call _pump() (or park
        directly when their shape is known-blocked)."""
        if bulk:
            if self._parked:
                for dq in self._parked.values():
                    self.pending_queue.extend(dq)
                self._parked.clear()
            self._blocked_sigs.clear()
            self._pump()
            return
        for sig in list(self._parked):
            dq = self._parked[sig]
            promoted_any = False
            # keep promoting this shape until the probe misses: a freed
            # lease can be bigger than one task (e.g. {CPU: 4} released
            # over 1-CPU parked tasks) and under-promoting serializes the
            # node until the next capacity event
            while dq:
                head = dq[0]
                if head.state == "cancelled":
                    dq.popleft()  # cancelled while parked: drop lazily
                    continue
                # _select_node ACQUIRES capacity on success — dispatch the
                # head directly on the returned node rather than requeueing
                # it for _pump (which would acquire a second time and leak
                # the probe's acquisition, wedging the node as full)
                nid = self._select_node(head.resources, head.spec.get("scheduling_strategy"))
                if nid is None:
                    break
                dq.popleft()
                promoted_any = True
                self._dispatch_on(head, nid)
            if not dq:
                # deque gone (promoted out, or emptied purely by dropping
                # cancelled records): the sig MUST unblock too, else new
                # same-shape submits keep parking despite free capacity and
                # only recover at the next health-valve tick
                del self._parked[sig]
                self._blocked_sigs.discard(sig)
            if promoted_any:
                # unblock so new same-shape submits pump normally; a
                # placement miss simply re-blocks. Whatever stays parked
                # does so because the probe just missed — only as much
                # work unparks as capacity arrived
                self._blocked_sigs.discard(sig)
        if self.pending_queue:
            self._pump()

    def _pump(self):
        if self._shutdown:
            return
        # demand signatures that already failed: with thousands of queued
        # same-shape tasks, one placement miss proves the rest can't place
        # either. Blocked shapes PARK out of the queue until
        # _capacity_changed requeues them, so both a same-shape submit
        # storm AND later unrelated submits cost O(1) each — the per-pass
        # memo alone still melted the head quadratically at many_tasks
        # scale (each new submit re-walked the whole backlog)
        blocked: Set[Any] = self._blocked_sigs
        while self.pending_queue:
            rec = self.pending_queue.popleft()
            if rec.state == "cancelled":
                continue  # cancelled while queued: drop lazily
            # sig cached on the record: a parked backlog is rescanned many
            # times and the tuple/sort/repr per record dominates the scan
            sig = getattr(rec, "_sig", None)
            if sig is None:
                sig = rec._sig = self._demand_sig(rec)
            if sig in blocked:
                self._parked.setdefault(sig, collections.deque()).append(rec)
                continue
            nid = self._select_node(rec.resources, rec.spec.get("scheduling_strategy"))
            if nid is None:
                blocked.add(sig)
                self._parked.setdefault(sig, collections.deque()).append(rec)
                continue
            self._dispatch_on(rec, nid)

    def _dispatch_on(self, rec: TaskRecord, nid: str):
        """Hand a task whose node capacity is ALREADY acquired (by
        _select_node) to the dispatch coroutine — the single handshake for
        both the pump and the parked-promotion path."""
        rec.node_id = nid
        rec.mark("scheduled")
        self._spawn_bg(self._dispatch_task(rec))

    async def _release_dispatch(self, rec: TaskRecord, w: Optional[WorkerRecord]):
        """Give back everything _dispatch_task holds: the node capacity
        acquired at scheduling and (if leased) the worker — then probe the
        parked backlog. The single teardown for the normal finally, the
        cancel short-circuits, and any future exit path."""
        self._release_node(rec.node_id, rec.resources, rec.spec.get("scheduling_strategy"))
        if w is not None and w.state == "busy":
            if w.pooled:
                w.state = "idle"
                self.idle_workers[w.node_id].append(w.worker_id)
            else:
                await self._kill_worker(w, reason="lease done")
        # probe even with no worker to return: the released NODE capacity
        # alone can unblock parked tasks
        self._capacity_changed(bulk=False)

    async def _dispatch_task(self, rec: TaskRecord):
        if rec.cancel_requested:
            # cancelled between scheduling and dispatch: give the acquired
            # capacity back and settle the returns
            await self._release_dispatch(rec, None)
            self._finish_cancel(rec)
            return
        w = await self._lease_worker(
            rec.node_id,
            needs_tpu=rec.resources.get("TPU", 0) > 0,
            runtime_env=rec.spec.get("runtime_env"),
        )
        if w is None:
            self._release_node(rec.node_id, rec.resources, rec.spec.get("scheduling_strategy"))
            await self._retry_or_fail(rec, RuntimeError("failed to lease a worker"))
            return
        if rec.cancel_requested:
            # cancelled during the lease await (state was still
            # "scheduled", so _h_cancel_task relies on this check)
            await self._release_dispatch(rec, w)
            self._finish_cancel(rec)
            return
        rec.worker_id = w.worker_id
        rec.mark("running")
        spec = rec.spec
        try:
            reply = await w.conn.request(
                {
                    "t": "run_task",
                    "task_id": spec["task_id"],
                    "fn_key": spec["fn_key"],
                    "args": self._resolve_args(spec),
                    "return_ids": spec["return_ids"],
                    "trace_ctx": spec.get("trace_ctx"),
                    "streaming": spec.get("streaming", False),
                }
            )
        except Exception as e:
            await self._retry_or_fail(rec, e)
            return
        finally:
            await self._release_dispatch(rec, w)
        if reply.get("lost_deps"):
            # dep buffers were evicted under the worker: rebuild them from
            # lineage and re-dispatch this task (pins stay held; not a retry)
            for oid in reply["lost_deps"]:
                try:
                    await self._reconstruct(oid)
                except Exception as e:
                    await self._retry_or_fail(rec, e)
                    return
            await self._resolve_and_enqueue(rec)
            return
        for oid in spec.get("deps", []):
            self.objects.unpin(oid)
        self._store_task_results(spec, reply)
        rec.mark("done")

    async def _retry_or_fail(self, rec: TaskRecord, error: Exception):
        from ..exceptions import OutOfMemoryError, WorkerCrashedError

        if rec.cancel_requested:
            # a cancelled task never retries; a force-kill's broken conn
            # lands here and must surface as cancellation, not a crash
            self._finish_cancel(rec)
            return
        w = self.workers.get(rec.worker_id or "")
        if w is not None and w.kill_reason:
            error = OutOfMemoryError(w.kill_reason)
        if rec.retries_left > 0 and not self._shutdown:
            rec.retries_left -= 1
            await asyncio.sleep(cfg.task_retry_delay_ms / 1000.0)
            rec.mark("pending")
            self.pending_queue.append(rec)
            self._pump()
            return
        rec.mark("failed")
        for oid in rec.spec.get("deps", []):
            self.objects.unpin(oid)
        if isinstance(error, OutOfMemoryError):
            self._fail_task_returns(rec.spec, error)
        else:
            self._fail_task_returns(rec.spec, WorkerCrashedError(f"task failed: {error!r}"))

    def _fail_task_returns(self, spec: dict, error: Exception):
        from .serialization import serialize

        env = serialize(error)
        env.is_error = True  # type: ignore[attr-defined]
        for oid in spec["return_ids"]:
            self.objects.put(oid, env)

    def _store_task_results(self, spec: dict, reply: dict):
        envs = reply["results"]
        for oid, env in zip(spec["return_ids"], envs):
            self.objects.put(oid, env)
            # returns start with one reference held by the submitting frontend's ObjectRef
            self.objects.add_ref(oid, 0)

    def _resolve_args(self, spec: dict) -> dict:
        """Attach resolved dependency envelopes to an argument payload."""
        deps = {}
        for oid in spec.get("deps", []):
            if self.objects.contains(oid):
                deps[oid] = self.objects.get(oid)
        return {"env": spec["args"], "resolved": deps}

    async def _lease_worker(
        self, node_id: str, needs_tpu: bool = False, runtime_env: Optional[dict] = None
    ) -> Optional[WorkerRecord]:
        pooled = not needs_tpu and not runtime_env
        if pooled:
            idle = self.idle_workers[node_id]
            while idle:
                wid = idle.pop()
                w = self.workers.get(wid)
                if w is not None and w.state == "idle" and w.conn and not w.conn.closed:
                    w.state = "busy"
                    return w
        w = await self._spawn_worker(node_id, runtime_env=runtime_env, needs_tpu=needs_tpu)
        w.pooled = pooled
        try:
            await asyncio.wait_for(w.registered, cfg.worker_register_timeout_s)
        except asyncio.TimeoutError:
            await self._kill_worker(w, reason="register timeout")
            return None
        if w.state != "idle":
            return None
        w.state = "busy"
        return w

    async def _spawn_worker(
        self,
        node_id: str,
        dedicated_actor_id: Optional[str] = None,
        runtime_env: Optional[dict] = None,
        needs_tpu: bool = False,
    ) -> WorkerRecord:
        self._worker_counter += 1
        worker_id = f"worker-{self._worker_counter}"
        w = WorkerRecord(worker_id=worker_id, node_id=node_id, actor_id=dedicated_actor_id)
        w.registered = asyncio.get_running_loop().create_future()
        self.workers[worker_id] = w
        node = self.nodes.get(node_id)
        if node is not None and node.remote:
            # remote node: the agent spawns; the worker dials us back over TCP
            try:
                await node.conn.request(
                    {
                        "t": "spawn_worker",
                        "worker_id": worker_id,
                        "head_address": self.tcp_address,
                        "runtime_env": runtime_env,
                        "needs_tpu": needs_tpu,
                    }
                )
            except Exception as e:
                logger.warning("agent spawn failed on %s: %r", node_id, e)
                w.state = "dead"
                if not w.registered.done():
                    w.registered.set_result(None)
            return w
        env = dict(os.environ)
        env["RAY_TPU_SOCKET"] = self.socket_path
        env["RAY_TPU_WORKER_ID"] = worker_id
        env["RAY_TPU_NODE_ID"] = node_id
        env["RAY_TPU_SESSION_DIR"] = self.session_dir
        user_env_vars = (runtime_env or {}).get("env_vars") or {}
        for k, v in user_env_vars.items():
            env[k] = str(v)
        # working_dir / py_modules: stage into the session dir (content-hash
        # cached) and point the worker at the staged copies (reference:
        # _private/runtime_env/working_dir.py + the per-node runtime-env
        # agent, runtime_env_agent.py:161 — collapsed into spawn here)
        cwd = os.getcwd()
        extra_paths = []
        if runtime_env:
            loop = asyncio.get_running_loop()
            if runtime_env.get("working_dir"):
                # stage off-loop: a large copy must not stall cluster RPC
                cwd = await loop.run_in_executor(
                    None, self._stage_dir, runtime_env["working_dir"]
                )
                extra_paths.append(cwd)
            for mod in runtime_env.get("py_modules") or []:
                staged = await loop.run_in_executor(None, self._stage_dir, mod)
                # a staged single-file module is importable via its parent
                extra_paths.append(staged if os.path.isdir(staged) else os.path.dirname(staged))
        # the worker imports what the driver imports: staged dirs first,
        # a user-specified PYTHONPATH next, then the driver's sys.path
        from .spawn import child_pythonpath, set_worker_jax_env

        env["PYTHONPATH"] = child_pythonpath(
            extra_paths,
            inherited=env["PYTHONPATH"] if "PYTHONPATH" in user_env_vars else None,
        )
        set_worker_jax_env(env, needs_tpu, user_env_vars)
        argv = [sys.executable, "-m", "ray_tpu._private.worker_main"]
        log_file = None
        if cfg.log_to_driver:
            # per-worker log file, tailed by _log_tail_loop and pushed to
            # drivers over the "__logs__" pubsub channel (reference:
            # _private/log_monitor.py tail + worker.py print redirection)
            log_dir = os.path.join(self.session_dir, "logs")
            os.makedirs(log_dir, exist_ok=True)
            log_file = open(os.path.join(log_dir, f"{worker_id}.out"), "ab")
        if log_file is not None:
            env["PYTHONUNBUFFERED"] = "1"  # prints reach the tail promptly
            w.proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=log_file, stderr=subprocess.STDOUT
            )
            log_file.close()  # child holds its own fd
        else:
            w.proc = subprocess.Popen(argv, env=env, cwd=cwd)
        return w

    def _stage_dir(self, src: str) -> str:
        from .staging import stage_into

        return stage_into(self.session_dir, src)

    async def _kill_worker(self, w: WorkerRecord, reason: str = ""):
        if w.state == "dead":
            return
        w.state = "dead"
        await self._terminate_worker(w)
        if w.worker_id in self.idle_workers[w.node_id]:
            self.idle_workers[w.node_id].remove(w.worker_id)

    async def _terminate_worker(
        self, w: WorkerRecord, force: bool = False, close_conn: bool = True
    ):
        """Tear down the worker's connection and process (local or via its
        node agent). Idempotent; independent of record state."""
        if close_conn and w.conn is not None:
            await w.conn.close()
        if w.proc is not None and w.proc.poll() is None:
            try:
                w.proc.kill() if force else w.proc.terminate()
            except Exception:
                pass
        elif w.proc is None:
            # remote worker: the owning agent holds the process handle
            node = self.nodes.get(w.node_id)
            if node is not None and node.remote and not node.conn.closed:
                try:
                    await node.conn.request(
                        {"t": "kill_worker", "worker_id": w.worker_id, "force": force},
                        timeout=5,
                    )
                except Exception:
                    pass

    async def _on_worker_death(self, w: WorkerRecord, reason: str):
        if w.state == "dead":
            return
        was_actor = w.actor_id
        w.state = "dead"
        self._drop_task_lease(w.worker_id)  # frees the lease's node share
        if w.worker_id in self.idle_workers[w.node_id]:
            self.idle_workers[w.node_id].remove(w.worker_id)
        # actor restart path
        for rec in self.actors.values():
            if rec.worker_id == w.worker_id and rec.state in ("alive", "starting"):
                if self._shutdown:
                    rec.state = "dead"
                    continue
                self._release_actor_node(rec, w)
                if rec.restarts_left != 0:
                    if rec.restarts_left > 0:
                        rec.restarts_left -= 1
                    rec.state = "restarting"
                    await asyncio.sleep(cfg.actor_restart_delay_ms / 1000.0)
                    self._spawn_bg(self._start_actor(rec))
                else:
                    rec.state = "dead"
                    rec.death_reason = f"worker died ({reason})"
                    if rec.name:
                        self._unregister_name(rec)
                    await self._fail_backlog(rec)
        _ = was_actor
        if not self._shutdown:
            # the dropped lease / released actor node share may unblock
            # parked tasks
            self._capacity_changed(bulk=False)
