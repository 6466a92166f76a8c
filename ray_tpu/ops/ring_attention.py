"""Ring attention: causal attention over a sequence-parallel mesh axis.

Net-new relative to the reference (SURVEY §2.4: SP/CP "Absent — must be
built new"). Each device on the `sp` ring holds one contiguous sequence
block of Q/K/V. K/V blocks rotate around the ring with `ppermute` while a
flash-style (m, l, o) accumulator folds in one block per step — peak memory
is O(block²) instead of O(L²), and XLA overlaps the ICI neighbor exchange
with the block matmuls (the ppermute for step s+1 is independent of step
s's compute).

Designed to run INSIDE shard_map, manual over the `sp` axis only — dp/fsdp
(batch) and tp (heads) stay auto so GSPMD shards the block matmuls as usual.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attend(q, k, v, m, l, o, q_block_idx, kv_block_idx, scale, causal):
    """Fold one K/V block into the (m, l, o) flash accumulator. f32 state."""
    blk_q, blk_k = q.shape[1], k.shape[1]
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        b, s, h, d = k.shape
        k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)
        v = jnp.broadcast_to(v[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_block_idx * blk_q + jnp.arange(blk_q)[:, None]
        kpos = kv_block_idx * blk_k + jnp.arange(blk_k)[None, :]
        mask = qpos >= kpos  # [blk_q, blk_k]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    else:
        mask = jnp.ones((blk_q, blk_k), dtype=bool)
    m_new = jnp.maximum(m, jnp.max(logits, axis=-1))  # [B,H,Lq]
    # zero masked probs explicitly: robust even when a row is fully masked
    p = jnp.where(mask[None, None], jnp.exp(logits - m_new[..., None]), 0.0)
    alpha = jnp.exp(m - m_new)  # [B,H,Lq]
    l_new = alpha * l + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(
    q: jnp.ndarray,  # [B, L/sp, H, D] local block (manual over sp)
    k: jnp.ndarray,  # [B, L/sp, Hkv, D]
    v: jnp.ndarray,
    *,
    axis_name: str = "sp",
    causal: bool = True,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    sp = lax.psum(1, axis_name)
    my_block = lax.axis_index(axis_name)
    b, blk, h, d = q.shape
    m0 = jnp.full((b, h, blk), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, blk), dtype=jnp.float32)
    o0 = jnp.zeros((b, blk, h, d), dtype=jnp.float32)
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(s, carry):
        m, l, o, ck, cv = carry
        src_block = (my_block - s) % sp
        m, l, o = _block_attend(q, ck, cv, m, l, o, my_block, src_block, scale, causal)
        # rotate AFTER attending; the last rotation is skipped via cond-free
        # arithmetic (an extra rotate is harmless and keeps the loop uniform)
        ck = lax.ppermute(ck, axis_name, perm)
        cv = lax.ppermute(cv, axis_name, perm)
        return m, l, o, ck, cv

    m, l, o, _, _ = lax.fori_loop(0, sp, step, (m0, l0, o0, k, v))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def make_sharded_ring_attention(mesh, axis_name: str = "sp", causal: bool = True):
    """Wrap ring_attention in shard_map: manual over `sp`, auto elsewhere."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, axis_name, None, None)

    from ..parallel.sharding import manual_shard_map

    fn = partial(ring_attention, axis_name=axis_name, causal=causal)
    return manual_shard_map(fn, mesh, (spec, spec, spec), spec, {axis_name})
