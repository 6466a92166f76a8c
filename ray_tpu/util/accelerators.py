"""Accelerator types and TPU-topology helpers.

Reference parity: python/ray/util/accelerators/accelerators.py — the
reference enumerates NVIDIA types only and has no TPU resource anywhere in
core (SURVEY §5.5); ray_tpu makes TPU generations and pod-slice topologies
first-class, since slice-aware placement is the whole point of this
framework.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

# generation constants (mirror the reference's NVIDIA_TESLA_* style)
TPU_V4 = "TPU-V4"
TPU_V5E = "TPU-V5E"  # a.k.a. v5 lite
TPU_V5P = "TPU-V5P"
TPU_V6E = "TPU-V6E"

# chips per host for each generation's standard TPU-VM shape
CHIPS_PER_HOST: Dict[str, int] = {
    TPU_V4: 4,
    TPU_V5E: 8,
    TPU_V5P: 4,
    TPU_V6E: 8,
}


def parse_accelerator_type(name: str) -> Tuple[str, int]:
    """"v4-32" / "v5e-16" / "v5p-128" -> (generation constant, chip count).

    The numeric suffix follows cloud naming: TensorCore count for v4/v5p
    (2 cores per chip), chip count for v5e/v6e.
    """
    gen_map = {"v4": TPU_V4, "v5e": TPU_V5E, "v5litepod": TPU_V5E,
               "v5p": TPU_V5P, "v6e": TPU_V6E}
    base, _, suffix = name.lower().partition("-")
    if base not in gen_map or not suffix.isdigit():
        raise ValueError(f"unknown TPU accelerator type {name!r}")
    n = int(suffix)
    gen = gen_map[base]
    chips = n // 2 if gen in (TPU_V4, TPU_V5P) else n
    return gen, max(1, chips)


def slice_hosts(accelerator_type: str) -> int:
    """Host count in a pod slice (drives placement-group bundle counts)."""
    gen, chips = parse_accelerator_type(accelerator_type)
    per = CHIPS_PER_HOST[gen]
    return max(1, (chips + per - 1) // per)


def slice_bundles(accelerator_type: str, cpus_per_host: float = 1.0) -> list:
    """Placement-group bundles for a full slice: one bundle per host with
    its TPU chips — pass to placement_group(..., strategy="STRICT_SPREAD")
    for gang scheduling over a slice (SURVEY §7.2 gang semantics)."""
    gen, chips = parse_accelerator_type(accelerator_type)
    per = CHIPS_PER_HOST[gen]
    hosts = slice_hosts(accelerator_type)
    bundles = []
    remaining = chips
    for _ in range(hosts):
        take = min(per, remaining)
        bundles.append({"CPU": cpus_per_host, "TPU": float(take)})
        remaining -= take
    return bundles


def detect_local_generation() -> Optional[str]:
    """Best-effort generation of this host's chips (env hints on TPU VMs)."""
    import os

    env = os.environ.get("TPU_ACCELERATOR_TYPE") or os.environ.get(
        "ACCELERATOR_TYPE", ""
    )
    if env:
        try:
            return parse_accelerator_type(env)[0]
        except ValueError:
            return None
    return None


def require_tpus(n: float, what: str) -> None:
    """Raise now, with a message, when `what` asks for more TPU chips than
    any node of this cluster has: its actor could never be placed and the
    caller would otherwise wait on it in silence."""
    import ray_tpu

    have = max(
        (nd["resources"].get("TPU", 0.0) for nd in ray_tpu.nodes() if nd["alive"]),
        default=0.0,
    )
    if have < n:
        raise RuntimeError(
            f"{what} asks for TPU={n:g} but the largest node of this cluster "
            f"has TPU={have:g}. Chips are counted from the host's device "
            "nodes (/dev/accel<n>, /dev/vfio/<n>) when ray_tpu.init() starts "
            "the head; pass num_tpus= to init() to state them yourself."
        )
