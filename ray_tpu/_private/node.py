"""Node: starts and supervises the in-driver head service.

Reference parity: python/ray/_private/node.py (Node.start_head_processes) —
but where the reference spawns separate gcs_server/raylet daemons, ray_tpu
hosts the head service on a background asyncio thread of the driver process
(see head.py for why this is the right shape on a TPU host).
"""

from __future__ import annotations

import os
import shutil
import time
import uuid
from typing import Dict, Optional

from .config import GLOBAL_CONFIG as cfg
from .head import Head
from .spawn import detect_tpu_chips
from .worker import EventLoopThread


def default_resources(num_cpus=None, num_tpus=None, resources=None) -> Dict[str, float]:
    out: Dict[str, float] = {}
    out["CPU"] = float(num_cpus if num_cpus is not None else (os.cpu_count() or 1))
    tpus = num_tpus if num_tpus is not None else detect_tpu_chips()
    if tpus:
        out["TPU"] = float(tpus)
    out["memory"] = float(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
    out["node:__internal_head__"] = 1.0
    if resources:
        out.update({k: float(v) for k, v in resources.items()})
    return out


def _snapshot_session_id(target: str):
    """The session id recorded in a head snapshot (None if unreadable).
    `target` may name any snapshot store (file path, sqlite://, gs://)."""
    import pickle

    from .snapshot_store import store_for

    try:
        data = store_for(target).load()
        return pickle.loads(data).get("session_id") if data else None
    except Exception:
        return None


class Node:
    def __init__(self, resources: Dict[str, float]):
        self.session_id = f"session_{time.strftime('%Y%m%d_%H%M%S')}_{uuid.uuid4().hex[:8]}"
        if cfg.head_restore_path:
            # restoring = resuming the SAME logical cluster: adopt the
            # snapshot's session id so surviving agents/workers (whose shm
            # planes, scratch dirs and sockets are keyed by session)
            # re-register instead of being orphaned
            sid = _snapshot_session_id(cfg.head_restore_path)
            if sid:
                self.session_id = sid
        self.session_dir = os.path.join(cfg.session_dir_root, self.session_id)
        os.makedirs(self.session_dir, exist_ok=True)
        self.socket_path = os.path.join(self.session_dir, "head.sock")
        self.io = EventLoopThread()
        self.head = Head(self.session_dir, resources)
        self.io.run(self.head.start())
        self._stopped = False

    def stop(self):
        if self._stopped:
            return
        self._stopped = True
        try:
            self.io.run(self.head.stop(), timeout=10)
        except Exception:
            pass
        self.io.stop()
        shutil.rmtree(self.session_dir, ignore_errors=True)
