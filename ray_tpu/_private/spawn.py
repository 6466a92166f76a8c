"""Helpers for spawning child interpreters (workers, agents, job drivers).

A child must import the same `ray_tpu` and the same user modules as its
parent, which may have found them through a `sys.path.insert` the child
never ran — so the parent's sys.path rides down via PYTHONPATH. One
implementation: the merge rules used to be hand-rolled at every spawn
site and drifted. This is also where a worker's JAX environment is
decided: which platform it may open, and where it keeps compiled code.
"""

from __future__ import annotations

import glob
import os
import sys
from typing import Dict, Optional, Sequence


def child_pythonpath(
    prefix_paths: Sequence[str] = (), inherited: Optional[str] = None
) -> str:
    """PYTHONPATH for a child: explicit prefixes first (staged dirs, the
    framework root), then any inherited/user PYTHONPATH (keeping its
    normal precedence over site-packages), then this process's full
    sys.path."""
    parts = [p for p in prefix_paths if p]
    if inherited:
        parts.append(inherited)
    parts.extend(p for p in sys.path if p)
    return os.pathsep.join(parts)


def framework_root() -> str:
    """The directory containing the ray_tpu package — prefixed where the
    cluster's OWN code must win over user paths (job drivers)."""
    import ray_tpu

    return os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))


def detect_tpu_chips() -> int:
    """Count this host's TPU chips from its device nodes, without importing
    jax (the process that asks must not open the chip). A chip is a
    `/dev/accel<n>` node under the accel driver, or a numbered VFIO group
    `/dev/vfio/<n>` — how a v5e host shows its chips. The TPU_* environment
    describes the image's slice type, not what this machine holds: a
    one-chip v5e host carries the same TPU_CHIPS_PER_HOST_BOUNDS=2,2,1 as a
    four-chip one."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def compile_cache_dir() -> str:
    """Where this checkout's processes keep compiled XLA programs: the
    ambient JAX_COMPILATION_CACHE_DIR when there is one, else a fixed
    directory beside the package. The path is part of the cache key, so it
    is never derived from a temp name, a pid or the time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        framework_root(), ".jax_cache"
    )


def set_worker_jax_env(
    env: Dict[str, str], needs_tpu: bool, user_env_vars: Dict[str, str]
) -> None:
    """One process for each chip: only a worker that was granted TPU may
    open the device; every other worker is pinned to the CPU backend. Names
    the caller's runtime_env set itself are left alone."""
    if needs_tpu:
        if "JAX_PLATFORMS" not in user_env_vars and detect_tpu_chips():
            # name the platform: JAX then fails at start-up when it cannot
            # open the chip, instead of quietly computing on the CPU. (On
            # a host without chips a TPU *resource* is a test's fiction
            # and the worker inherits the driver's platform.)
            env["JAX_PLATFORMS"] = "tpu,cpu"
        if "JAX_COMPILATION_CACHE_DIR" not in user_env_vars:
            env["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    elif "JAX_PLATFORMS" not in user_env_vars:
        env["JAX_PLATFORMS"] = "cpu"
