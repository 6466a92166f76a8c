"""Per-host node agent: the remote half of the control plane.

Reference parity: src/ray/raylet (node_manager.h:117) — the per-node daemon
that registers with the GCS, owns the local worker pool, and serves the
local object plane. ray_tpu's agent is deliberately thinner: scheduling
stays centralized in the head (one scheduler, no resource gossip needed at
TPU-pod scale — tens of hosts, not thousands), so the agent only
  - registers the node + its resources over TCP (ray_syncer / node table),
  - spawns/kills local worker processes on the head's behalf
    (worker_pool.h:420 StartWorkerProcess),
  - serves reads/deletes against the node-local shared-memory object plane
    so the head can pull cross-node dependencies (object_manager.h:117's
    chunked pull, collapsed to request/response over the same framing).

Workers spawned here connect STRAIGHT to the head over TCP — task dispatch
never relays through the agent, keeping the hot path at one hop (the same
reason the reference pushes tasks worker-to-worker, direct_task_transport).
"""

from __future__ import annotations

import asyncio
import os
import shutil
import subprocess
import sys
import threading
import time
from typing import Dict, Optional

from . import protocol
from .config import GLOBAL_CONFIG as cfg

_DEF_GRACE_S = 3.0


class Agent:
    def __init__(
        self,
        head_address: str,
        node_id: str,
        resources: Dict[str, float],
        labels: Optional[Dict[str, str]] = None,
    ):
        self.head_address = head_address
        self.node_id = node_id
        self.resources = resources
        self.labels = labels or {}
        self.conn: protocol.Connection = None  # type: ignore
        self.session: str = ""
        self.scratch_dir: str = ""
        self.shm_session: str = ""
        self._shm = None
        self._shm_tried = False
        self._shm_lock = threading.Lock()
        self.workers: Dict[str, subprocess.Popen] = {}
        self._stop = asyncio.Event()
        self._quit = False  # explicit shutdown (no reconnect attempts)
        self.buffer_addr: str = ""
        self._bulk_server = None

    # ------------------------------------------------------------------

    def _shm_client(self):
        # called from the event loop AND the bulk server's serve threads —
        # the lock keeps a half-initialized None from leaking to a
        # concurrent first caller (stripe pulls arrive N-at-once)
        with self._shm_lock:
            if not self._shm_tried:
                self._shm_tried = True
                from .shm import ShmClient

                try:
                    self._shm = ShmClient(self.shm_session, cfg.shm_store_bytes)
                    self._shm.pretouch_async()  # one pretouch per node slab
                except Exception:
                    self._shm = None
            return self._shm

    async def _start_buffer_server(self) -> str:
        """Start the node-to-node bulk plane (bulk.BulkServer): dedicated
        blocking sender threads doing sock.sendall straight from the shm
        mapping (os.sendfile for spilled buffers) — off this event loop, so
        a 256MB pull never contends with control-plane handlers. The head
        only hands out locations; object bytes never relay through it
        (reference: object_manager.h:117 chunked push/pull)."""
        from .bulk import BulkServer

        # honor the cluster's bind policy: the control plane's bind host
        # (head_tcp_host) decides whether this unauthenticated plane is
        # loopback-only or LAN-exposed — serving raw object bytes on all
        # interfaces of a loopback-configured cluster would leak data
        bind = cfg.head_tcp_host or "0.0.0.0"
        self._bulk_server = BulkServer(self._shm_client, bind)
        port = self._bulk_server.start()
        from .head import _advertise_host

        return f"{_advertise_host(bind)}:{port}"

    async def _connect_and_register(self) -> dict:
        reader, writer = await protocol.open_stream(self.head_address)
        self.conn = protocol.Connection(reader, writer, self.handle, self._on_close)
        self.conn.start()
        return await self.conn.request(
            {
                "t": "register_node",
                "proto": protocol.PROTOCOL_VERSION,
                "node_id": self.node_id,
                "resources": self.resources,
                "labels": self.labels,
                "buffer_addr": self.buffer_addr,
            }
        )

    async def run(self):
        self.buffer_addr = await self._start_buffer_server()
        info = await self._connect_and_register()
        self.session = info["session"]
        self.shm_session = f"{self.session}_{self.node_id}"
        self.scratch_dir = os.path.join(
            cfg.session_dir_root, self.session, "nodes", self.node_id
        )
        os.makedirs(self.scratch_dir, exist_ok=True)
        aux_tasks = []
        if cfg.memory_monitor_refresh_ms > 0:
            aux_tasks.append(
                asyncio.get_running_loop().create_task(self._memory_loop())
            )
        if cfg.log_to_driver:
            aux_tasks.append(
                asyncio.get_running_loop().create_task(self._log_forward_loop())
            )
        if cfg.resource_report_period_ms > 0:
            aux_tasks.append(
                asyncio.get_running_loop().create_task(self._resource_report_loop())
            )
        while True:
            await self._stop.wait()
            if self._quit or not await self._reconnect():
                break
            self._stop.clear()
        for t in aux_tasks:
            t.cancel()
        self._cleanup()

    async def _reconnect(self) -> bool:
        """The head connection died (head crash/restart): keep this node —
        and its live workers — alive and re-register against the head at
        the SAME address (reference: raylet reconnect to a restarted GCS,
        gcs_server.cc:130-178). Workers re-register themselves over their
        own connections; we only re-offer the node + bulk plane."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + cfg.head_reconnect_timeout_s
        while loop.time() < deadline and not self._quit:
            await asyncio.sleep(0.5)
            try:
                info = await self._connect_and_register()
            except Exception:
                continue
            if info["session"] != self.session:
                # a DIFFERENT cluster took the address: this node's shm
                # plane / scratch belong to the old session — bail out
                logger = __import__("logging").getLogger(__name__)
                logger.warning(
                    "head at %s now runs session %s (was %s); shutting down",
                    self.head_address, info["session"], self.session,
                )
                return False
            return True
        return False

    async def _memory_loop(self):
        """Sample this node's memory and report pressure to the head, which
        owns the kill policy (reference: memory_monitor.h sampling in the
        raylet; policy in worker_killing_policy.h)."""
        from .memory_monitor import MemoryMonitor

        mon = MemoryMonitor()
        period = cfg.memory_monitor_refresh_ms / 1000.0
        while not self._stop.is_set():
            await asyncio.sleep(period)
            try:
                pressured, used, total = mon.is_pressured()
            except Exception:
                continue
            if pressured and not self.conn.closed:
                try:
                    await self.conn.send(
                        {"t": "memory_pressure", "node_id": self.node_id,
                         "used": used, "total": total}
                    )
                except Exception:
                    pass

    async def _resource_report_loop(self):
        """Periodic node load report to the head (reference: ray_syncer
        resource gossip, ray_syncer.h:86 — collapsed to agent->head pushes
        since scheduling is centralized; the head folds the reports into
        the node table for the state API / dashboard / autoscaler)."""
        from .memory_monitor import MemoryMonitor

        mon = MemoryMonitor()
        while not self._stop.is_set():
            await asyncio.sleep(cfg.resource_report_period_ms / 1000.0)
            if self.conn is None or self.conn.closed:
                continue
            try:
                used, total = mon.sample()
                report = {
                    "load_1m": os.getloadavg()[0],
                    "mem_used": used,
                    "mem_total": total,
                    "workers": sum(
                        1 for p in self.workers.values() if p.poll() is None
                    ),
                    "ts": time.time(),
                }
                await self.conn.send(
                    {"t": "resource_report", "node_id": self.node_id,
                     "report": report}
                )
            except Exception:
                pass

    async def _on_close(self):
        self._stop.set()

    def _cleanup(self):
        for proc in self.workers.values():
            if proc.poll() is None:
                try:
                    proc.terminate()
                except Exception:
                    pass
        deadline = time.time() + _DEF_GRACE_S
        for proc in self.workers.values():
            try:
                proc.wait(timeout=max(0.0, deadline - time.time()))
            except Exception:
                try:
                    proc.kill()
                except Exception:
                    pass
        if self._bulk_server is not None:
            try:
                self._bulk_server.stop()
            except Exception:
                pass
        shm = self._shm_client()
        if shm is not None:
            try:
                shm.disconnect()
                from .shm import ShmClient

                ShmClient.destroy(self.shm_session)
            except Exception:
                pass
        shutil.rmtree(self.scratch_dir, ignore_errors=True)

    # ------------------------------------------------------------------

    async def handle(self, msg):
        t = msg["t"]
        fn = getattr(self, f"_h_{t}", None)
        if fn is None:
            raise ValueError(f"agent got unknown message {t!r}")
        return await fn(msg)

    async def _h_ping(self, msg):
        return "pong"

    async def _h_shutdown(self, msg):
        self._stop.set()
        return True

    async def _ensure_package(self, src: str):
        """For a pkg:// runtime-env source, pull the zip from the head into
        this node's package store if it isn't cached yet, so stage_into
        resolves it locally (reference: the per-node runtime-env agent
        downloading packages from GCS object storage)."""
        if not src.startswith("pkg://"):
            return
        name = src[len("pkg://"):]
        pkg_dir = os.path.join(self.scratch_dir, "packages")
        pkg_path = os.path.join(pkg_dir, name)
        if os.path.exists(pkg_path):
            return
        data = await self.conn.request({"t": "get_package", "name": name}, timeout=120)
        loop = asyncio.get_running_loop()

        def _write():
            import threading

            os.makedirs(pkg_dir, exist_ok=True)
            # pid+tid: concurrent spawns fetching the same package must not
            # share a tmp path (staging.py stage_into pattern)
            tmp = f"{pkg_path}.tmp-{os.getpid()}-{threading.get_ident()}"
            with open(tmp, "wb") as f:
                f.write(data)
            os.replace(tmp, pkg_path)

        await loop.run_in_executor(None, _write)

    async def _h_spawn_worker(self, msg):
        """Spawn a local worker that dials the head directly over TCP."""
        worker_id = msg["worker_id"]
        runtime_env = msg.get("runtime_env") or {}
        needs_tpu = msg.get("needs_tpu", False)
        env = dict(os.environ)
        env["RAY_TPU_ADDRESS"] = msg["head_address"]
        env["RAY_TPU_WORKER_ID"] = worker_id
        env["RAY_TPU_NODE_ID"] = self.node_id
        env["RAY_TPU_SESSION_DIR"] = self.scratch_dir
        env["RAY_TPU_SHM_SESSION"] = self.shm_session
        user_env_vars = runtime_env.get("env_vars") or {}
        for k, v in user_env_vars.items():
            env[k] = str(v)
        cwd = self.scratch_dir
        extra_paths = []
        loop = asyncio.get_running_loop()
        if runtime_env.get("working_dir"):
            await self._ensure_package(runtime_env["working_dir"])
            cwd = await loop.run_in_executor(
                None, _stage_dir, self.scratch_dir, runtime_env["working_dir"]
            )
            extra_paths.append(cwd)
        for mod in runtime_env.get("py_modules") or []:
            await self._ensure_package(mod)
            staged = await loop.run_in_executor(None, _stage_dir, self.scratch_dir, mod)
            extra_paths.append(staged if os.path.isdir(staged) else os.path.dirname(staged))
        argv = [sys.executable, "-m", "ray_tpu._private.worker_main"]
        # the worker imports what this agent imports (staged dirs first)
        from .spawn import child_pythonpath, set_worker_jax_env

        env["PYTHONPATH"] = child_pythonpath(
            extra_paths,
            inherited=env["PYTHONPATH"] if "PYTHONPATH" in user_env_vars else None,
        )
        set_worker_jax_env(env, needs_tpu, user_env_vars)
        if cfg.log_to_driver:
            # per-worker log file; _log_forward_loop tails it and sends
            # increments to the head, which republishes to drivers
            # (reference: the per-node log monitor)
            log_dir = os.path.join(self.scratch_dir, "logs")
            os.makedirs(log_dir, exist_ok=True)
            env["PYTHONUNBUFFERED"] = "1"
            logf = open(os.path.join(log_dir, f"{worker_id}.out"), "ab")
            proc = subprocess.Popen(
                argv, env=env, cwd=cwd, stdout=logf, stderr=subprocess.STDOUT
            )
            logf.close()
        else:
            proc = subprocess.Popen(argv, env=env, cwd=cwd)
        self.workers[worker_id] = proc
        return {"pid": proc.pid}

    async def _log_forward_loop(self):
        from . import log_tail

        log_dir = os.path.join(self.scratch_dir, "logs")
        offsets: Dict[str, int] = {}
        pending: Dict[str, tuple] = {}
        wanted = False
        wanted_checked = float("-inf")  # first tick polls immediately
        while not self._stop.is_set():
            await asyncio.sleep(0.3)
            if self.conn is None or self.conn.closed:
                continue
            now = time.monotonic()
            if now - wanted_checked >= 5.0:
                wanted_checked = now
                try:
                    wanted = await self.conn.request({"t": "logs_wanted"}, timeout=5)
                except Exception:
                    wanted = False
            if not wanted:
                # no driver subscribed: ship nothing over TCP, but keep the
                # offsets current so subscription starts with live output
                log_tail.fast_forward(log_dir, offsets)
                continue
            for worker_id, data in log_tail.read_increments(log_dir, offsets, pending):
                try:
                    await self.conn.send(
                        {"t": "worker_logs", "worker_id": worker_id, "data": data}
                    )
                except Exception:
                    pass

    async def _h_kill_worker(self, msg):
        proc = self.workers.pop(msg["worker_id"], None)
        if proc is None:
            return False
        if proc.poll() is None:
            try:
                proc.kill() if msg.get("force") else proc.terminate()
            except Exception:
                pass
        return True

    async def _h_read_buffers(self, msg):
        """Serve node-local shm buffers to the head (relay fallback for
        cross-node pulls). WireBuffer: the slab views ride the control
        socket as out-of-band segments — no pickle copy on this side."""

        shm = self._shm_client()
        out: Dict[str, Optional[protocol.WireBuffer]] = {}
        for name in msg["names"]:
            mv = None if shm is None else shm.get_or_spilled(name)
            out[name] = None if mv is None else protocol.WireBuffer(mv)
        return out

    async def _h_delete_buffers(self, msg):
        shm = self._shm_client()
        if shm is not None:
            for name in msg["names"]:
                shm.delete(name)
        return True


def _stage_dir(scratch_dir: str, src: str) -> str:
    from .staging import stage_into

    return stage_into(scratch_dir, src)
