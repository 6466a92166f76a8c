"""FlashAttention for TPU in Pallas.

The reference has no fused attention of its own (torch SDPA/CUDA kernels
arrive via integrations; SURVEY.md §2.4 sequence parallel row). This is the
TPU-native equivalent: a Pallas kernel that never materializes the [L, L]
score matrix — online softmax over KV blocks held in VMEM, both matmuls on
the MXU in f32 accumulation.

Layout convention matches ray_tpu.ops.attention: q/k/v are [B, L, H, D].

Grid: (batch, head, q_block, kv_block); the kv axis is innermost, so the
f32 accumulator/max/denominator scratch persists across kv iterations of
one q block (the sequential-last-dim contract of Pallas TPU grids). Causal
skipping is predicated per block pair — fully-masked pairs never touch the
MXU.

Backward is a custom VJP with two more Pallas kernels (FlashAttention-2
structure): a dq kernel (grid over q blocks, kv innermost, dq accumulator in
VMEM) and a dk/dv kernel (grid over kv blocks, q innermost). Probabilities
are recomputed from the saved log-sum-exp rows, so backward memory is
O(L * BLOCK) instead of O(L^2) and all four matmuls per block pair run on
the MXU in f32 accumulation. Causally-dead block pairs are skipped in both
kernels.

Each pallas_call carries a `name` — flash_attention_fwd, flash_attention_bwd
(the fused single-block case), flash_attention_bwd_dq, flash_attention_bwd_dkv
— which pallas_call also enters as a named scope, so the HLO instruction and
with it the profiler's event is `%flash_attention_fwd.<n>` whatever JAX
wrapper (checkpoint, jvp, shard_map) the kernel was called under. The
benchmark's reduction finds the kernels by these names.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger(__name__)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_i, l_i, *, scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_i[:] = jnp.full_like(m_i, NEG_INF)
        l_i[:] = jnp.zeros_like(l_i)

    # causal: the whole block pair is masked out iff its lowest q position
    # is below its lowest k position
    run = (not causal) or (qi * block_q + block_q - 1 >= kj * block_k)

    @pl.when(run)
    def _attend():
        # matmul inputs stay bf16 (f32 operands run the MXU at a fraction of
        # bf16 rate); accumulation is f32 via preferred_element_type
        q = q_ref[0, 0, :, :]  # [BQ, D]
        k = k_ref[0, 0, :, :]  # [BK, D]
        v = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_i[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_i[:] = alpha * l_i[:] + jnp.sum(p, axis=1, keepdims=True)
        m_i[:] = m_new
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        # fully-masked q rows (never occur under causal q>=k layouts, but do
        # with padding) get l=0: emit zeros, not NaNs
        l = l_i[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :, :] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0, :, :] = m_i[:] + jnp.log(safe_l)


def _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    """q in [B, H, L, D], k/v in [B, Hkv, L, D] — the kernel's native
    layout (Mosaic requires the last two BLOCK dims to tile (8, 128) or
    equal the array dims, so L and D must be innermost). GQA is folded
    into the k/v index maps (q head h reads kv head h // n_rep), so
    repeated KV heads are never materialized. Returns out [B, H, Lq, D],
    lse [B, H, Lq]."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    n_rep = h // k.shape[1]
    qt, kt, vt = q, k, v
    nq = lq // block_q
    nk = lk // block_k
    grid = (b, h, nq, nk)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return out, lse[..., 0]


def _recompute_p_ds(refs, qi, kj, *, scale, causal, block_q, block_k):
    """Shared backward recompute for one (q block, kv block) pair: rebuilds
    the probabilities from the saved lse row stats and derives dS. Inputs
    stay bf16 into the MXU; accumulation is f32. Returns (p, ds, q, k, v, do)."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs
    q = q_ref[0, 0, :, :]                          # [BQ, D] bf16
    k = k_ref[0, 0, :, :]                          # [BK, D]
    v = v_ref[0, 0, :, :]                          # [BK, D]
    do = do_ref[0, 0, :, :]                        # [BQ, D]
    lse = lse_ref[0, 0, :, :]                      # [BQ, 1]
    delta = delta_ref[0, 0, :, :]                  # [BQ, 1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jnp.exp(s - lse)                           # [BQ, BK] f32
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = (p * (dp - delta) * scale).astype(k.dtype)
    return p, ds, q, k, v, do


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
               *, scale, causal, block_q, block_k):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (not causal) or (qi * block_q + block_q - 1 >= kj * block_k)

    @pl.when(run)
    def _accum():
        _, ds, _, k, _, _ = _recompute_p_ds(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qi, kj,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, causal, block_q, block_k, nq):
    """Grid (b, kv_head, kv_block, n_rep * nq): the innermost axis walks
    every (q head in the GQA group, q block) pair while the dk/dv output
    block stays fixed, so the group-sum over repeated q heads lands in the
    same VMEM accumulator that already sums over q blocks — the repeated-KV
    materialization (and its gradient reduction) never exists."""
    kj = pl.program_id(2)
    i = pl.program_id(3)
    ni = pl.num_programs(3)
    qi = i % nq

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (not causal) or (qi * block_q + block_q - 1 >= kj * block_k)

    @pl.when(run)
    def _accum():
        p, ds, q, _, _, do = _recompute_p_ds(
            (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), qi, kj,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k,
        )
        # dV += P^T @ dO
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dK += dS^T @ Q
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _dqkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, *, scale, causal, block_q, block_k):
    """Fused backward for the single-block-pair case (nq == nk == 1): the
    recomputed s/p serve dq AND dk/dv in one pass — 5 MXU matmuls + 1 exp
    instead of the 7 + 2 the split kernels pay. Every output block is
    written exactly once per (b, h), so no cross-iteration accumulation is
    needed."""
    p, ds, q, k, v, do = _recompute_p_ds(
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref),
        pl.program_id(2), pl.program_id(3),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k,
    )
    dq_ref[0, 0, :, :] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dq_ref.dtype)
    dv_ref[0, 0, :, :] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv_ref.dtype)
    dk_ref[0, 0, :, :] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(dk_ref.dtype)


def _flash_backward(scale, causal, block_q, block_k, interpret, res, do):
    """FlashAttention-2 backward: two Pallas kernels over [B, H, L, D]
    (fused into one when the whole sequence fits a single block pair and
    there is no GQA group to reduce). k/v/dk/dv stay [B, Hkv, L, D]: the
    group fold lives in the index maps (dq) and the folded innermost grid
    axis (dk/dv)."""
    q, k, v, out, lse = res
    b, h, lq, d = q.shape
    h_kv = k.shape[1]
    n_rep = h // h_kv
    lk = k.shape[2]
    qt, kt, vt, dot = q, k, v, do
    # Delta_i = rowsum(dO * O)  [B, H, L, 1]
    delta = jnp.einsum(
        "bhld,bhld->bhl", do.astype(jnp.float32), out.astype(jnp.float32)
    )[..., None]
    lse4 = lse[..., None]  # [B, H, L, 1]
    nq = lq // block_q
    nk = lk // block_k

    q_spec = pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // n_rep, j, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i, j: (b_, h_, i, 0))
    if nq == 1 and nk == 1 and n_rep == 1:
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _dqkv_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k,
            ),
            grid=(b, h, 1, 1),
            in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
            out_specs=[q_spec, k_spec, k_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
                jax.ShapeDtypeStruct((b, h, lk, d), k.dtype),
                jax.ShapeDtypeStruct((b, h, lk, d), v.dtype),
            ],
            interpret=interpret,
            name="flash_attention_bwd",
        )(qt, kt, vt, dot, lse4, delta)
        return dq, dk, dv
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k
        ),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, lq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, dot, lse4, delta)[0]

    # kv kernel: grid (b, kv_head, kv_block, n_rep * q_blocks) — the whole
    # GQA group runs while the dk/dv block is resident, so group-sum and
    # q-block-sum share one accumulator (see _dkv_kernel)
    def _qh(g_, i_):
        return g_ * n_rep + i_ // nq

    qi_spec = pl.BlockSpec(
        (1, 1, block_q, d), lambda b_, g_, j_, i_: (b_, _qh(g_, i_), i_ % nq, 0)
    )
    kj_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda b_, g_, j_, i_: (b_, g_, j_, 0)
    )
    rowi_spec = pl.BlockSpec(
        (1, 1, block_q, 1), lambda b_, g_, j_, i_: (b_, _qh(g_, i_), i_ % nq, 0)
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, nq=nq,
        ),
        grid=(b, h_kv, nk, nq * n_rep),
        in_specs=[qi_spec, kj_spec, kj_spec, qi_spec, rowi_spec, rowi_spec],
        out_specs=[kj_spec, kj_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h_kv, lk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h_kv, lk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qt, kt, vt, dot, lse4, delta)

    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_forward(q, k, v, scale, causal, block_q, block_k, interpret)
    # name the residuals so remat policies can SAVE them — without this the
    # forward kernel re-runs inside backward just to regenerate lse
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, do):
    return _flash_backward(scale, causal, block_q, block_k, interpret, res, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # [B, Lq, H, D]  (or [B, H, Lq, D] with layout="bhsd")
    k: jnp.ndarray,  # [B, Lk, Hkv, D]
    v: jnp.ndarray,  # [B, Lk, Hkv, D]
    *,
    scale: Optional[float] = None,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
    layout: str = "bshd",
) -> jnp.ndarray:
    """Drop-in replacement for ops.attention.causal_attention on block-
    aligned shapes. GQA is folded into the kernel's k/v index maps (q head
    h reads kv head h // n_rep, forward and backward) — repeated KV heads
    are never materialized and dk/dv group-sum inside the kernel. Falls
    back to the dense einsum path when the sequence doesn't tile evenly.

    layout="bhsd" runs the kernel on head-major inputs with NO relayout —
    the fast path the model uses (transposes around the kernel cost more
    than the attention itself at small d_head)."""
    from .attention import causal_attention, causal_attention_bhsd

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    seq_axis = 2 if layout == "bhsd" else 1
    head_axis = 1 if layout == "bhsd" else 2
    block_q = min(block_q, q.shape[seq_axis])
    block_k = min(block_k, k.shape[seq_axis])
    if q.shape[seq_axis] % block_q or k.shape[seq_axis] % block_k:
        # loud, once per trace: dense attention materializes [L, L] scores
        logger.warning(
            "flash_attention: seq %d/%d does not tile by blocks %d/%d — "
            "tracing DENSE attention instead of the kernel",
            q.shape[seq_axis], k.shape[seq_axis], block_q, block_k,
        )
        dense = causal_attention_bhsd if layout == "bhsd" else causal_attention
        return dense(q, k, v, scale=scale, causal=causal)
    if q.shape[head_axis] % k.shape[head_axis]:
        raise ValueError(
            f"q heads {q.shape[head_axis]} not a multiple of kv heads "
            f"{k.shape[head_axis]}"
        )
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if layout == "bhsd":
        return _flash(q, k, v, scale, causal, block_q, block_k, interpret)
    out = _flash(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        scale, causal, block_q, block_k, interpret,
    )
    return out.transpose(0, 2, 1, 3)
