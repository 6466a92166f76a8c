"""Device meshes for TPU slices.

The backbone of every parallelism in ray_tpu: a named `jax.sharding.Mesh`
with axes (dp, fsdp, sp, tp, pp, ep). The reference's analogue is NCCL
process-group bootstrap (train/torch/config.py:113 dist.init_process_group);
here the "process group" is the mesh and XLA inserts the collectives.

Axis conventions (scaling-book style):
  dp    pure data parallel (gradient all-reduce over ICI/DCN)
  fsdp  fully-sharded data parallel (ZeRO-3: params/opt-state sharded here)
  sp    sequence/context parallel (ring attention neighbors on ICI ring)
  tp    tensor/operator parallel (Megatron-style, innermost = fastest ICI)
  pp    pipeline stages (usually across DCN / multi-slice)
  ep    expert parallel (MoE all-to-all)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

AXIS_ORDER: Tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Degrees for each parallelism axis. -1 on one axis = use all remaining."""

    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1

    def degrees(self) -> dict:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def total(self) -> int:
        t = 1
        for v in self.degrees().values():
            t *= v
        return t

    def resolve(self, n_devices: int) -> "MeshSpec":
        d = self.degrees()
        wild = [a for a, v in d.items() if v == -1]
        if len(wild) > 1:
            raise ValueError("At most one mesh axis may be -1")
        if wild:
            known = 1
            for a, v in d.items():
                if v != -1:
                    known *= v
            if n_devices % known:
                fixed = {a: v for a, v in d.items() if v not in (-1, 1)}
                raise ValueError(
                    f"cannot infer mesh axis {wild[0]!r}: the fixed axes "
                    f"{fixed} use {known} devices, which does not divide "
                    f"the {n_devices} available"
                )
            d[wild[0]] = n_devices // known
        if math.prod(d.values()) != n_devices:
            # name the first axis that fails to divide what remains, so the
            # user sees WHICH degree is wrong instead of an opaque
            # reshape/product error downstream
            rem = n_devices
            for a, v in d.items():
                if v > 1 and (rem % v or v > rem):
                    raise ValueError(
                        f"mesh axis {a!r}={v} does not divide the remaining "
                        f"{rem} of {n_devices} devices (requested degrees "
                        f"{ {k: x for k, x in d.items() if x > 1} })"
                    )
                rem //= max(v, 1)
            raise ValueError(
                f"Mesh degrees {d} use {math.prod(d.values())} devices, have {n_devices}"
            )
        return MeshSpec(**{k: d[k] for k in ("dp", "fsdp", "sp", "tp", "pp", "ep")})


def local_device_count() -> int:
    return len(jax.devices())


def build_mesh(
    spec: MeshSpec | None = None,
    devices: Optional[Sequence] = None,
    axis_order: Tuple[str, ...] = AXIS_ORDER,
) -> Mesh:
    """Build a Mesh laying the innermost axes (tp, sp) on the fastest
    interconnect: jax's device order within a host follows the ICI torus, so
    contiguous device blocks get the last mesh dims (mesh_utils does the
    topology-aware assignment on real slices)."""
    devices = list(devices if devices is not None else jax.devices())
    spec = (spec or MeshSpec()).resolve(len(devices))
    shape = tuple(spec.degrees()[a] for a in axis_order)
    from jax.experimental import mesh_utils

    # raises when the degrees cannot be laid on the chips' physical
    # topology — a plain reshape there would run, on the wrong links
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, axis_order)


def data_axes() -> Tuple[str, ...]:
    """Mesh axes a global batch is sharded over."""
    return ("dp", "fsdp")


def host_local_mesh(spec: MeshSpec | None = None) -> Mesh:
    return build_mesh(spec, devices=jax.local_devices())
