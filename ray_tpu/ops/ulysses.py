"""Ulysses-style sequence parallelism: all-to-all head scatter.

Alternative to ring attention for the `sp` axis: instead of rotating K/V
around a ring, one all-to-all converts sequence-sharded activations into
head-sharded ones, dense attention runs locally on full sequences, and a
second all-to-all converts back. Cheaper than ring for moderate L (2
all-to-alls vs sp-1 neighbor steps) but requires heads % sp == 0.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .attention import causal_attention


def ulysses_attention(
    q: jnp.ndarray,  # [B, L/sp, H, D] local block (manual over sp)
    k: jnp.ndarray,  # [B, L/sp, Hkv, D]
    v: jnp.ndarray,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    sp = lax.psum(1, axis_name)
    h, hkv = q.shape[2], k.shape[2]
    if h % sp:
        raise ValueError(f"heads ({h}) must be divisible by sp ({sp})")
    if hkv % sp:
        # GQA with fewer kv heads than sp: replicate kv heads up to sp
        rep = sp // hkv if sp % hkv == 0 else None
        if rep is None:
            raise ValueError(f"kv_heads ({hkv}) must divide or be divisible by sp ({sp})")
        b, s, _, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, rep, d)).reshape(b, s, hkv * rep, d)
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, s, hkv, rep, d)).reshape(b, s, hkv * rep, d)
    # seq-sharded -> head-sharded: split heads, concat seq
    qg = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = causal_attention(qg, kg, vg, causal=causal, scale=scale)
    # head-sharded -> seq-sharded
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def make_sharded_ulysses_attention(mesh, axis_name: str = "sp", causal: bool = True):
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)

    from ..parallel.sharding import manual_shard_map

    fn = partial(ulysses_attention, axis_name=axis_name, causal=causal)
    return manual_shard_map(fn, mesh, (spec, spec, spec), spec, {axis_name})
