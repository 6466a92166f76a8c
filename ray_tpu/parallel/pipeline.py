"""Pipeline parallelism: microbatch pipeline over the `pp` axis.

Net-new vs the reference (SURVEY §2.4: PP "Not in-repo; Alpa release tests
only"). Stages live on the `pp` mesh axis (typically across DCN / multi-
slice); activations hop stage-to-stage with `ppermute`; a scan over
v*n_microbatches + pp - 1 ticks keeps every stage busy after warmup. The
backward pipeline falls out of autodiff (ppermute transposes to the reverse
permutation), so one combinator serves training and inference.

Two schedules share one tick loop:

  GPipe (virtual_stages_per_device=1): each device owns one CONTIGUOUS
  block of stages; bubble fraction (pp-1)/(n_mb + pp - 1).

  Interleaved (virtual_stages_per_device=v>1): each device owns v
  NON-contiguous stage chunks placed round-robin — logical stage chunk q
  lives on device q % pp — so a tick is 1/v of a device's layers and the
  warmup bubble shrinks to (pp-1)/(v*n_mb + pp - 1). Microbatches run in
  groups of pp (n_mb % pp == 0 required); device d executes chunk
  (u//pp) % v on microbatch (u//(pp*v))*pp + u%pp at tick t = u + d, a
  decomposition that is conflict-free (one chunk per device per tick) and
  keeps every activation hop on the same nearest-neighbour ring as GPipe.
  Interleaving multiplies ICI hops (v*n_mb ticks instead of n_mb), but the
  per-tick DCN cost is unchanged: still exactly ONE `dcn` ppermute — the
  byte-counter tests assert this.

Runs inside shard_map manual over `pp` only — dp/fsdp/tp/sp stay auto, so
GSPMD still shards each stage's internals from the sharding table.

Multi-slice placement (parallel/multislice.py pp-outer): `axis_name` may be
a PAIR ("dcn", "pp") — slice-major stage→slice placement where global stage
s = slice_index * stages_per_slice + local_stage. The stage-to-stage hop is
then two-tier: intra-slice hops ride a `pp` ppermute (ICI) and the slice-
boundary hop rides ONE `dcn` ppermute (DCN). For stages_per_slice>1 the
boundary activation is first reduce-scattered over the intra-slice `pp`
axis (ICI), so each device ships only its 1/stages_per_slice shard across
DCN and the receiving slice all-gathers it back (ICI) — DCN carries exactly
one copy of the microbatch activation per tick regardless of
stages_per_slice. (When the microbatch dim does not divide by
stages_per_slice the hop falls back to a masked full-payload ppermute,
which is correct but ships stages_per_slice zero-padded copies — keep the
microbatch divisible to hold the one-copy invariant.)
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def bubble_fraction(n_microbatches: int, pp: int, virtual_stages_per_device: int = 1) -> float:
    """Idle fraction of device-tick slots for the schedule this module
    executes: (pp-1)/(v*n_mb + pp - 1). v=1 is the GPipe figure. Derived
    from the same tick count the scan below runs, so bench rows report the
    schedule actually executed."""
    v = virtual_stages_per_device
    return (pp - 1) / (v * n_microbatches + pp - 1)


def interleaved_stage_order(
    n_stage_rows: int, n_stage_devices: int, virtual_stages_per_device: int
) -> np.ndarray:
    """Permutation taking stage rows from MODEL order (row r applied r-th)
    to SCHEDULE order (consecutive-block sharding over the stage devices
    gives device d chunks d, pp+d, ..., (v-1)*pp+d in local rows
    [j*C,(j+1)*C)). Identity when v == 1 or pp == 1."""
    pp, v = n_stage_devices, virtual_stages_per_device
    if n_stage_rows % (pp * v):
        raise ValueError(
            f"{n_stage_rows} stage rows do not divide over {pp} devices x "
            f"{v} virtual stages"
        )
    C = n_stage_rows // (pp * v)
    return np.concatenate(
        [
            np.arange((j * pp + d) * C, (j * pp + d + 1) * C)
            for d in range(pp)
            for j in range(v)
        ]
    )


def _pipeline_local(
    stage_fn,
    stage_params,
    x_mb,
    *,
    axis_names: Tuple[str, ...],
    n_microbatches: int,
    virtual_stages_per_device: int = 1,
):
    """Runs on one stage (inside shard_map). x_mb: [n_mb, mb, ...] full input
    (only stage 0 reads it); returns [n_mb, mb, ...] outputs (valid on the
    last stage, zeros elsewhere — caller psums over the stage axes to
    broadcast). axis_names is ("pp",) or ("dcn", "pp") — outer axis first.
    stage_params rows are in SCHEDULE order (see interleaved_stage_order)."""
    inner = axis_names[-1]
    outer = axis_names[0] if len(axis_names) == 2 else None
    pp_in = lax.psum(1, inner)
    n_outer = lax.psum(1, outer) if outer is not None else 1
    pp = n_outer * pp_in
    v = virtual_stages_per_device
    dev = lax.axis_index(inner)
    if outer is not None:
        dev = lax.axis_index(outer) * pp_in + dev
    n_mb = n_microbatches
    total_ticks = v * n_mb + pp - 1
    mb_shape = x_mb.shape[1:]
    local_rows = jax.tree.leaves(stage_params)[0].shape[0]
    rows_per_chunk = local_rows // v

    # a chunk is rows_per_chunk consecutive local rows applied in order —
    # with pp=1 (and the identity schedule order) this degenerates to the
    # sequential stack with identical microbatch windows, so a single-
    # device run is a bit-for-bit oracle for the sharded pipeline
    def _fwd(x, chunk):
        def body(xc, p_one):
            return stage_fn(p_one, xc), None

        y, _ = lax.scan(body, x, chunk)
        return y

    fwd = jax.checkpoint(_fwd)

    intra_perm = [(i, i + 1) for i in range(pp_in - 1)]
    if v > 1:
        cross_perm = [(s, (s + 1) % n_outer) for s in range(n_outer)]
        ring_perm = [(i, (i + 1) % pp) for i in range(pp)]
    else:
        cross_perm = [(s, s + 1) for s in range(n_outer - 1)]
        ring_perm = intra_perm  # single-axis GPipe: no wrap needed
    wrap_perm = [(pp_in - 1, 0)]

    def hop(y):
        """Pass activations one stage downstream along the global device
        ring. Single-axis: one ppermute. Two-tier: intra-slice neighbors
        over `inner` (ICI); the slice boundary crosses `outer` (DCN) once —
        reduce-scattered over `inner` first so DCN carries ONE copy of the
        activation, re-gathered on the receiving slice (both ICI legs).
        Devices without an upstream receive zeros (masked by the chunk-0
        ingest select)."""
        if pp == 1:
            return y  # chunk-to-chunk handoff on a single device
        if outer is None:
            return lax.ppermute(y, inner, ring_perm)
        if n_outer == 1:
            # degenerate two-tier (one slice): the ring wrap is intra-slice
            cross = (
                lax.ppermute(y, inner, wrap_perm) if v > 1 else jnp.zeros_like(y)
            )
        elif pp_in == 1:
            cross = lax.ppermute(y, outer, cross_perm)
        elif y.shape[0] % pp_in == 0:
            # one-copy DCN hop: scatter the boundary stage's activation
            # across the slice (ICI), ship 1/pp_in per device (DCN),
            # gather on the other side (ICI). psum_scatter in f32: narrow-
            # dtype all-reduce hits an XLA CHECK on the CPU backend.
            boundary = lax.axis_index(inner) == pp_in - 1
            z = jnp.where(boundary, y, jnp.zeros_like(y)).astype(jnp.float32)
            z = lax.psum_scatter(z, inner, scatter_dimension=0, tiled=True)
            z = lax.ppermute(z.astype(y.dtype), outer, cross_perm)
            cross = lax.all_gather(z, inner, axis=0, tiled=True)
        else:
            # fallback (mb not divisible by stages_per_slice): masked full-
            # payload ppermute — non-boundary coordinates ship zeros
            boundary = lax.axis_index(inner) == pp_in - 1
            z = jnp.where(boundary, y, jnp.zeros_like(y))
            cross = lax.ppermute(z, outer, cross_perm)
            cross = lax.ppermute(cross, inner, wrap_perm)
        if pp_in == 1:
            return cross
        intra = lax.ppermute(y, inner, intra_perm)
        return jnp.where(lax.axis_index(inner) == 0, cross, intra)

    def tick(carry, t):
        recv, out_buf = carry
        # schedule decomposition: device d is active at tick t on chunk j,
        # microbatch m (see module docstring); inactive ticks are masked
        u = t - dev
        valid = jnp.logical_and(u >= 0, u < v * n_mb)
        uc = jnp.clip(u, 0, v * n_mb - 1)
        j = (uc // pp) % v
        m = (uc // (pp * v)) * pp + uc % pp
        # first logical stage ingests microbatch m (clamped when masked)
        x0 = lax.dynamic_index_in_dim(x_mb, m, axis=0, keepdims=False)
        is_ingest = jnp.logical_and(dev == 0, j == 0)
        x_in = jnp.where(is_ingest, x0, recv)
        if v == 1:
            chunk = stage_params
        else:
            chunk = jax.tree.map(
                lambda p: lax.dynamic_slice_in_dim(
                    p, j * rows_per_chunk, rows_per_chunk, axis=0
                ),
                stage_params,
            )
        y = fwd(x_in, chunk)
        # pass activations downstream for the next tick
        new_recv = hop(y)
        # final logical stage stores its (active) output for microbatch m
        is_active_last = jnp.logical_and(
            valid, jnp.logical_and(dev == pp - 1, j == v - 1)
        )
        cur = lax.dynamic_index_in_dim(out_buf, m, axis=0, keepdims=False)
        upd = jnp.where(is_active_last, y, cur)
        out_buf = lax.dynamic_update_index_in_dim(out_buf, upd, m, axis=0)
        return (new_recv, out_buf), None

    recv0 = jnp.zeros(mb_shape, x_mb.dtype)
    out0 = jnp.zeros((n_mb,) + mb_shape, x_mb.dtype)
    (recv, out_buf), _ = lax.scan(tick, (recv0, out0), jnp.arange(total_ticks))
    # only the last stage device holds real outputs; zero elsewhere then
    # psum to broadcast. psum in f32: bf16 all-reduce hits an XLA CHECK on
    # the CPU backend (hlo_instruction.cc "Invalid binary instruction
    # opcode copy").
    out_buf = jnp.where(dev == pp - 1, out_buf, jnp.zeros_like(out_buf))
    bcast_axes = axis_names if len(axis_names) > 1 else axis_names[0]
    return lax.psum(out_buf.astype(jnp.float32), bcast_axes).astype(out_buf.dtype)


def pipeline_apply(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    *,
    mesh,
    n_microbatches: int,
    axis_name: Union[str, Tuple[str, ...]] = "pp",
    virtual_stages_per_device: int = 1,
    stage_order: str = "model",
):
    """Apply a pipelined stage stack to x: [B, ...].

    stage_params: pytree whose leaves have leading dim = total stages
    (sharded on the stage axes). stage_fn(params_one_stage, x_mb) -> y_mb
    with matching shapes.

    axis_name: mesh axis the stages live on, or a ("dcn", "pp") pair for
    multi-slice stage→slice placement — stages are laid out slice-major
    (dcn-major), so stage s lives on slice s // stages_per_slice.

    virtual_stages_per_device: v>1 switches to the interleaved schedule —
    each device runs v round-robin stage chunks (stage chunk q on device
    q % pp), cutting the warmup bubble to (pp-1)/(v*n_mb + pp - 1).
    Requires n_microbatches % pp == 0 and stage rows divisible by v*pp.

    stage_order: "model" (default) — stage_params rows are in sequential
    model order and this function permutes them into schedule order (a
    one-time gather over the stage axes per compiled call). "schedule" —
    the caller already permuted rows with interleaved_stage_order(); no
    gather is emitted, which keeps the compiled HLO free of any setup
    collective (the per-tick byte measurements use this).
    """
    from jax.sharding import PartitionSpec as P

    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    if not 1 <= len(axes) <= 2:
        raise ValueError(
            f"axis_name must be one mesh axis or an (outer, inner) pair, "
            f"got {axis_name!r}"
        )
    v = int(virtual_stages_per_device)
    if v < 1:
        raise ValueError(f"virtual_stages_per_device must be >= 1, got {v}")
    if stage_order not in ("model", "schedule"):
        raise ValueError(f"stage_order must be 'model' or 'schedule', got {stage_order!r}")
    n_stage_devices = 1
    for a in axes:
        if a not in mesh.shape:
            raise ValueError(f"pipeline axis {a!r} not in mesh axes {tuple(mesh.shape)}")
        n_stage_devices *= mesh.shape[a]
    lead = jax.tree.leaves(stage_params)[0].shape[0]
    if lead % (n_stage_devices * v):
        raise ValueError(
            f"stage_params leading dim {lead} does not divide over the "
            f"{n_stage_devices} stage devices x {v} virtual stages of mesh "
            f"axes {axes} ({ {a: mesh.shape[a] for a in axes} })"
        )
    if v > 1 and n_microbatches % n_stage_devices:
        raise ValueError(
            f"interleaved schedule needs n_microbatches ({n_microbatches}) "
            f"divisible by the {n_stage_devices} stage devices"
        )
    if v > 1 and stage_order == "model":
        order = interleaved_stage_order(lead, n_stage_devices, v)
        stage_params = jax.tree.map(lambda p: jnp.take(p, order, axis=0), stage_params)

    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} not divisible by n_microbatches {n_microbatches}")
    mb = b // n_microbatches
    x_mb = x.reshape((n_microbatches, mb) + x.shape[1:])

    x_spec = P()

    stage_spec = P(axes if len(axes) > 1 else axes[0])
    pspec = jax.tree.map(lambda _: stage_spec, stage_params)
    fn = partial(
        _pipeline_local,
        stage_fn,
        axis_names=axes,
        n_microbatches=n_microbatches,
        virtual_stages_per_device=v,
    )
    from .sharding import manual_shard_map

    out_mb = manual_shard_map(
        fn, mesh, (pspec, x_spec), x_spec, set(axes)
    )(stage_params, x_mb)
    return out_mb.reshape((b,) + out_mb.shape[2:])
