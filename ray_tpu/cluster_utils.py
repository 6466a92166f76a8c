"""In-process multi-node cluster for tests.

Reference parity: python/ray/cluster_utils.py:99 (Cluster, add_node :165) —
the highest-leverage test fixture in the reference (SURVEY §4.2). Like the
reference (which starts real raylet processes, add_node :165), add_node
starts a REAL per-host agent process that joins the head over localhost TCP:
node death, cross-node object pulls, and failover are all exercised for
real. `add_node(logical=True)` keeps the old resource-record-only mode for
pure scheduling tests.
"""

from __future__ import annotations

import itertools
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, Optional

from ._private.worker import global_worker

_node_counter = itertools.count(1)


class Cluster:
    def __init__(self, initialize_head: bool = True, head_node_args: Optional[dict] = None):
        import ray_tpu

        self._nodes = []
        self._procs: Dict[str, subprocess.Popen] = {}
        if initialize_head:
            head_node_args = head_node_args or {}
            ray_tpu.init(**head_node_args)

    @property
    def head_tcp_address(self) -> Optional[str]:
        node = global_worker.node
        return None if node is None else node.head.tcp_address

    def add_node(
        self,
        num_cpus: float = 1,
        num_tpus: float = 0,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        logical: bool = False,
        wait: bool = True,
    ) -> str:
        res = {"CPU": float(num_cpus)}
        if num_tpus:
            res["TPU"] = float(num_tpus)
        res.update({k: float(v) for k, v in (resources or {}).items()})
        node_id = f"node-{next(_node_counter)}"
        if logical:
            global_worker.request(
                {"t": "add_node", "node_id": node_id, "resources": res, "labels": labels or {}}
            )
            self._nodes.append(node_id)
            return node_id
        address = self.head_tcp_address
        if address is None:
            raise RuntimeError("head has no TCP listener; cannot start real nodes")
        argv = [
            sys.executable,
            "-m",
            "ray_tpu._private.agent_main",
            "--address",
            address,
            "--node-id",
            node_id,
            "--resources",
            json.dumps(res),
            "--labels",
            json.dumps(labels or {}),
        ]
        env = dict(os.environ)
        from ._private.spawn import child_pythonpath

        env["PYTHONPATH"] = child_pythonpath(inherited=env.get("PYTHONPATH"))
        # agents never own the chips; workers they spawn default to cpu jax
        env.setdefault("JAX_PLATFORMS", "cpu")
        # own process group: kill_node(force) can take the whole node (agent
        # + its workers) down at once, like killing a host
        proc = subprocess.Popen(argv, env=env, start_new_session=True)
        self._procs[node_id] = proc
        self._nodes.append(node_id)
        if wait:
            self.wait_for_node(node_id)
        return node_id

    def wait_for_node(self, node_id: str, timeout: float = 20.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            nodes = global_worker.request({"t": "nodes"})
            if any(n["node_id"] == node_id and n["alive"] for n in nodes):
                return
            proc = self._procs.get(node_id)
            if proc is not None and proc.poll() is not None:
                raise RuntimeError(
                    f"agent for {node_id} exited rc={proc.returncode} before registering"
                )
            time.sleep(0.05)
        raise TimeoutError(f"node {node_id} did not register within {timeout}s")

    def kill_node(self, node_id: str) -> None:
        """SIGKILL the node's whole process group (agent + workers) — the
        chaos path (reference: test_utils.py:1370 NodeKillerActor)."""
        proc = self._procs.pop(node_id, None)
        if proc is not None and proc.poll() is None:
            try:
                os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
            except (ProcessLookupError, OSError):
                proc.kill()
        if node_id in self._nodes:
            self._nodes.remove(node_id)

    def remove_node(self, node_id: str) -> None:
        global_worker.request({"t": "remove_node", "node_id": node_id})
        proc = self._procs.pop(node_id, None)
        if proc is not None:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                proc.kill()
        if node_id in self._nodes:
            self._nodes.remove(node_id)

    def shutdown(self):
        import ray_tpu

        ray_tpu.shutdown()
        # one SIGTERM pass over every agent group FIRST: agents exit on it
        # immediately (default handler), where the old per-proc wait(5)
        # expired serially and SIGKILLed the group anyway — a flat
        # multi-second tax on every cluster-using test's teardown
        for proc in self._procs.values():
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGTERM)
                except (ProcessLookupError, OSError):
                    proc.terminate()
        for node_id, proc in list(self._procs.items()):
            try:
                proc.wait(timeout=2)
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
                except (ProcessLookupError, OSError):
                    proc.kill()
        self._procs.clear()
