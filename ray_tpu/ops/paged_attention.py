"""Fused multi-query paged attention for TPU in Pallas.

The paged decode step (ray_tpu/models/transformer.py make_paged_decoder)
historically gathered every slot's logical sequence through its block
table inside the jit — materializing [B, Nmax*block_tokens] keys AND
values per layer before attending. At long contexts that gather, not the
matmuls, is what caps tokens/s/chip: attention reads every live KV byte
once per step, so doubling the traffic halves the rate.

This kernel attends block-in-place over the pool layout instead, for ANY
number of queries per slot — one fused implementation serves

  decode            q = 1   (the original single-query walk)
  speculative verify q = k+1 (the draft window scored in one pass)
  prefill           q = chunk (chunked prefill of a prompt span)

  grid = (live slot, q_tile, block)
                          block innermost, so the online-softmax scratch
                          (f32 acc / running max / denominator) persists
                          across one (slot, q-tile)'s walk of the slot's
                          block table. The first and the last bound are
                          TRACED: how many slots hold a visible key, and how
                          many blocks the longest of them walks
                          (`_live_walk`, from the call's own tables,
                          positions and kv_len). One compiled program per
                          (B, Nmax) serves every occupancy
  k/v BlockSpec           index_map reads the slot's block table (a
                          scalar-prefetch operand) and DMAs physical
                          block `table[b, j]` directly from the pool —
                          no gathered copy ever exists
  stacked pool + `layer`  the pool may be ONE layer's [N, bt, KV, D] or
                          the model's stacked [L, N, bt, KV, D] with a
                          `layer` index (traced under the layer scan). The
                          index is a fourth scalar-prefetch operand and the
                          leading coordinate of the k/v index_map, so the
                          DMA addresses block `table[b, j]` of layer `l` in
                          the stacked buffer: the caller never slices a
                          layer out of it. (A 4-D pool is a stack of one.)
  dead entries            a table entry < 0 is dead: the padding past a
                          slot's last block, a released slot's whole row,
                          an out-of-shard block of a block-sharded pool.
                          The walk's length is what lives, not the table's
                          shape: a slot with no visible key in a live entry
                          is not in the grid at all (its rows are the
                          outputs' initial zeros, aliased in), and no slot
                          is walked past the longest live slot's last live
                          block. What is left of the old cost: a shorter
                          slot's steps under a longer one's walk, and holes
                          inside a live range — those clamp to block 0 in
                          the index map (Pallas skips the re-fetch when the
                          block index repeats) and are skipped in-body, at
                          about 0.1 us a step on a v5e where a live 64-token
                          block costs 0.65 us (8 kv heads) to 1.5 us (32)
  causal masking          query i sits at global position positions[b]+i;
                          key position j*block + t is visible iff
                          t' <= positions[b]+i AND t' < kv_len[b]. The
                          kv_len cap is what lets the verify step attend a
                          window that does NOT yet contain the in-flight
                          tokens (kv_len = positions, strictly before the
                          first query), while prefill uses pure causality
                          over keys its own layer pass just wrote.

GQA never materializes repeated KV heads: q is reshaped so both matmuls
run batched over the kv-head dim, with the query tile folded into the
repeat dim.

int8 pools (per-block, per-kv-head fp32 scales — see
transformer.init_paged_kv_cache) dequantize INSIDE the kernel: the HBM
read is half the bytes of bf16, which is the whole point at decode. The
scales ([N, KV], or stacked [L, N, KV]) are gathered through the block
table outside the kernel — [B, Nmax, KV], small whatever the pool holds —
and follow their K/V tile by grid position.

Sharded pools (blocks split across dp/fsdp shards) run the kernel
per-shard with `partial_out=True`: the kernel returns the unnormalized
accumulator plus the online-softmax (m, l) statistics, and the caller
merges shards with the standard log-sum-exp combine (see
`merge_partials`). kv_heads sharded on tp need no merge — heads are
independent. The same partial triple is how the verify step folds its
tiny in-flight K1 x K1 causal tail into the fused window pass.

A chunked XLA implementation (`impl="xla"`) computes the identical
online-softmax walk without Pallas — the CPU/CI path (interpret-mode
Pallas is a python-per-grid-step debugger, not an implementation), and
the reference the kernel is tested against.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# last resolved implementation ("kernel" | "xla"), recorded at trace time —
# test observability: parity suites assert the path they intended to
# exercise actually ran instead of silently falling back
_LAST_IMPL: Optional[str] = None


def _group_scores(q, k):
    """[KV, R, D] x [bt, KV, D] -> [KV, R, bt] without repeating KV heads
    (batched over the kv-head dim; R folds n_rep and the query tile)."""
    kt = k.transpose(1, 0, 2)  # [KV, bt, D]
    return lax.dot_general(
        q, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )


def _group_values(p, v):
    """[KV, R, bt] x [bt, KV, D] -> [KV, R, D]."""
    vt = v.transpose(1, 0, 2)  # [KV, bt, D]
    return lax.dot_general(
        p, vt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )


def _pa_kernel(tables_ref, pos_ref, kvlen_ref, layer_ref, slots_ref, q_ref,
               k_ref, v_ref, *rest, bt, qb, n_rep, scale, quantized,
               partial_out, out_dtype):
    del layer_ref  # read by the k/v index maps only
    if quantized:
        ks_ref, vs_ref = rest[0], rest[1]
        rest = rest[2:]
    # the outputs' initial values ride in as aliased HBM operands the body
    # never touches: a slot the grid does not visit keeps them
    n_out = 3 if partial_out else 1
    rest = rest[n_out:]
    if partial_out:
        o_ref, m_ref, l_ref = rest[:3]
        acc, m_i, l_i = rest[3:]
    else:
        o_ref = rest[0]
        acc, m_i, l_i = rest[1:]
    b = slots_ref[pl.program_id(0)]  # the grid's first axis walks LIVE slots
    qt = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    kv_heads = k_ref.shape[2]
    rows = qb * n_rep  # scratch rows per kv head (query tile x GQA repeat)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_i[:] = jnp.full_like(m_i, NEG_INF)
        l_i[:] = jnp.zeros_like(l_i)

    pos = pos_ref[b]
    kvl = kvlen_ref[b]
    qbase = pos + qt * qb  # global position of this tile's first query
    # the block matters iff any of the tile's queries can see any key in it:
    # its first key must precede both the kv_len cap and the LAST query
    live = jnp.logical_and(
        tables_ref[b, j] >= 0,
        jnp.logical_and(j * bt < kvl, j * bt <= qbase + qb - 1),
    )

    @pl.when(live)
    def _attend():
        k = k_ref[0]  # [bt, KV, D]
        v = v_ref[0]
        if quantized:
            # scale tiles are [1, KV, 1]: KV already sits on the sublane
            # axis it has in k/v, so this is a lane broadcast, no relayout
            k = k.astype(jnp.float32) * ks_ref[...]
            v = v.astype(jnp.float32) * vs_ref[...]
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        d = k.shape[2]
        # [qb, H, D] -> [KV, qb*n_rep, D]: fold the query tile into the GQA
        # repeat dim so both matmuls stay batched over kv heads
        qr = q_ref[0].astype(jnp.float32)
        qr = qr.reshape(qb, kv_heads, n_rep, d).transpose(1, 0, 2, 3)
        qr = qr.reshape(kv_heads, rows, d)
        s = (_group_scores(qr, k) * scale).reshape(kv_heads * rows, bt)
        # flat row = g*rows + qi*n_rep + r  ->  query index (row % rows)//n_rep
        kpos = j * bt + jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads * rows, bt), 1
        )
        qi = (jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads * rows, bt), 0
        ) % rows) // n_rep
        mask = jnp.logical_and(kpos <= qbase + qi, kpos < kvl)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_i[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # masked p, not exp(NEG_INF - m): a row whose every key this block
        # is masked (an early query under a later block) keeps m_new at
        # NEG_INF, and exp(s - m_new) would be exp(0) = 1 garbage
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_i[:] = alpha * l_i[:] + jnp.sum(p, axis=1, keepdims=True)
        m_i[:] = m_new
        pv = _group_values(p.reshape(kv_heads, rows, bt), v)
        acc[:] = acc[:] * alpha + pv.reshape(kv_heads * rows, d)

    @pl.when(j == nj - 1)
    def _finalize():
        def unflat(x):  # [KV*qb*n_rep, X] -> [qb, H, X]
            x = x.reshape(kv_heads, qb, n_rep, x.shape[-1])
            return x.transpose(1, 0, 2, 3).reshape(
                qb, kv_heads * n_rep, x.shape[-1]
            )

        if partial_out:
            o_ref[0] = unflat(acc[:])
            m_ref[0] = unflat(m_i[:])
            l_ref[0] = unflat(l_i[:])
        else:
            l = l_i[:]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = unflat(acc[:] / safe_l).astype(out_dtype)


def _walk_blocks(ptable, positions, kv_len, n_queries, bt):
    """[B] int32: how many table entries each slot's walk covers. A walk
    ends with the slot's last live entry (>= 0) among the blocks that hold a
    key some query may see, i.e. before ceil(min(kv_len, positions + Q) /
    bt): a released slot (a table of dead entries) walks nothing, and
    neither does the table's tail."""
    nmax = ptable.shape[1]
    seen = jnp.minimum(kv_len, positions + n_queries)
    j = jnp.arange(nmax, dtype=jnp.int32)[None, :]
    visible = jnp.logical_and(ptable >= 0, j * bt < seen[:, None])
    return jnp.max(jnp.where(visible, j + 1, 0), axis=1)


def _live_walk(ptable, positions, kv_len, n_queries, bt):
    """What a per-head call has to visit, from its own scalars: (`slots` [B]
    int32, the live slots' ids first and in slot order; how many are live;
    the longest live walk in blocks, `_walk_blocks`)."""
    b = ptable.shape[0]
    walk = _walk_blocks(ptable, positions, kv_len, n_queries, bt)
    live = walk > 0
    # slots[i] = the i-th live slot: the first slot with i + 1 live slots at
    # or before it ([B, B] compares; no sort, nothing the pool's size touches)
    i = jnp.arange(b, dtype=jnp.int32)
    upto = jnp.sum(jnp.logical_and(live[None, :], i[None, :] <= i[:, None]),
                   axis=1, dtype=jnp.int32)
    slots = jnp.sum(upto[None, :] <= i[:, None], axis=1, dtype=jnp.int32)
    return (jnp.minimum(slots, b - 1), jnp.sum(live, dtype=jnp.int32),
            jnp.max(walk))


def _paged_attention_pallas(q, k_pool, v_pool, ptable, positions, kv_len,
                            layer, k_scale, v_scale, scale, partial_out,
                            interpret, block_q):
    b, Q, h, d = q.shape
    _, _, bt, kv, _ = k_pool.shape
    n_rep = h // kv
    quantized = k_scale is not None
    qb = max(1, min(int(block_q), Q))
    qp = -(-Q // qb) * qb
    if qp != Q:
        # padded queries sit past every real one; their rows mask to zeros
        # and are sliced off below
        q = jnp.pad(q, ((0, 0), (0, qp - Q), (0, 0), (0, 0)))
    # the walk's length comes from the call's scalars, not from the table's
    # shape: live slots x q tiles x the longest live slot's blocks, traced
    # grid bounds of ONE compiled program. (At least one step a dimension:
    # with nothing live, the last slot's first entry is visited, found dead
    # and finalized to what `init` holds already.)
    slots, n_live, n_blocks = _live_walk(ptable, positions, kv_len, Q, bt)
    grid = (jnp.maximum(n_live, 1), qp // qb, jnp.maximum(n_blocks, 1))

    def tile(i_, qt_, j_, tbl, pos, kvl, lyr, slt):
        return slt[i_], qt_, 0, 0

    q_spec = pl.BlockSpec((1, qb, h, d), tile)
    kv_spec = pl.BlockSpec(
        # the layer dim is squeezed: the body sees [1, bt, KV, D], block
        # `table[b, j]` of layer `lyr[0]`, DMA'd straight from the stacked
        # pool — no per-layer slice of it ever exists
        (None, 1, bt, kv, d),
        # dead entries (< 0) clamp to block 0: repeated indices skip the
        # DMA, so a shorter slot's steps under a longer one's walk cost one
        # null-block fetch in all. (Asking here what the body asks — is the
        # block past kv_len or the tile's last query — saves a prefill tile
        # at most Q / bt fetches and costs every step of every walk ~0.03 us
        # of scalar work, +0.3 to +4.7 % a call: measured, not kept.)
        lambda i_, qt_, j_, tbl, pos, kvl, lyr, slt: (
            lyr[0], jnp.maximum(tbl[slt[i_], j_], 0), 0, 0, 0),
    )
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [q, k_pool, v_pool]
    if quantized:
        # the scales of the blocks a slot's table names, gathered by that
        # table outside the kernel: [B, Nmax, KV] is small whatever the
        # pool holds, where a relayout of the [L, N, KV] leaf would cost a
        # padded tile per pool block per layer. It goes in as
        # [B, Nmax, KV, 1] so the (KV, 1) tile spans the array's last two
        # dims whole — a (1, KV) block of the 3-D array would break the
        # sublane tiling rule
        sc_spec = pl.BlockSpec(
            (None, 1, kv, 1),
            lambda i_, qt_, j_, tbl, pos, kvl, lyr, slt: (slt[i_], j_, 0, 0),
        )
        idx = jnp.maximum(ptable, 0)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale[layer, idx][..., None],
                     v_scale[layer, idx][..., None]]
    if partial_out:
        out_specs = [
            pl.BlockSpec((1, qb, h, d), tile),
            pl.BlockSpec((1, qb, h, 1), tile),
            pl.BlockSpec((1, qb, h, 1), tile),
        ]
        # what a slot with no live key reads: acc 0, m NEG_INF, l 0
        init = [
            jnp.zeros((b, qp, h, d), jnp.float32),
            jnp.full((b, qp, h, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, qp, h, 1), jnp.float32),
        ]
    else:
        out_specs = [pl.BlockSpec((1, qb, h, d), tile)]
        init = [jnp.zeros((b, qp, h, d), q.dtype)]
    # the outputs start as `init` (aliased, left in HBM): the rows of a slot
    # the grid never visits are exactly what a walk over no key would write
    n_scalars = 5
    aliases = {n_scalars + len(operands) + i: i for i in range(len(init))}
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(init)
    operands += init

    kernel = functools.partial(
        _pa_kernel, bt=bt, qb=qb, n_rep=n_rep, scale=scale,
        quantized=quantized, partial_out=partial_out, out_dtype=q.dtype,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((qb * h, d), jnp.float32),
                pltpu.VMEM((qb * h, 1), jnp.float32),
                pltpu.VMEM((qb * h, 1), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in init],
        input_output_aliases=aliases,
        # slot and q-tile iterations are independent (scratch re-inits at
        # j == 0); the block walk is sequential — it carries the
        # online-softmax scratch. Telling Mosaic lets it
        # parallelize/pipeline over (slot, qt) while keeping each walk ordered.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        # the HLO instruction, and so the profiler's event, is
        # %paged_attention.<n> whatever wrapper (closed_call, shard_map) the
        # kernel is called under: the benchmark's reduction finds it by this
        name="paged_attention",
    )(ptable, positions, kv_len, jnp.reshape(layer, (1,)), slots, *operands)
    if partial_out:
        acc, m, l = outs
        return acc[:, :Q], m[:, :Q, :, 0], l[:, :Q, :, 0]
    return outs[0][:, :Q]


def _paged_attention_xla(q, k_pool, v_pool, ptable, positions, kv_len,
                         layer, k_scale, v_scale, scale, partial_out,
                         chunk_blocks):
    """The same block walk as the kernel, chunked for XLA: each chunk
    gathers `chunk_blocks` physical blocks and folds them into the online
    softmax. Never materializes the full [B, Nmax*bt] window or repeated
    KV heads — on CPU this beats the gather path on exactly the traffic
    the kernel saves on TPU."""
    b, Q, h, d = q.shape
    _, _, bt, kv, _ = k_pool.shape
    nmax = ptable.shape[1]
    n_rep = h // kv
    quantized = k_scale is not None
    cb = max(1, min(chunk_blocks, nmax))
    nch = -(-nmax // cb)
    if nch * cb != nmax:
        ptable = jnp.pad(ptable, ((0, 0), (0, nch * cb - nmax)),
                         constant_values=-1)
    qr = (q.astype(jnp.float32) * scale).reshape(b, Q, kv, n_rep, d)
    m = jnp.full((b, Q, h, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((b, Q, h, 1), jnp.float32)
    acc = jnp.zeros((b, Q, h, d), jnp.float32)
    qpos = positions[:, None].astype(jnp.int32) + jnp.arange(Q)[None, :]
    kvl = kv_len.astype(jnp.int32)[:, None, None, None]
    for c in range(nch):
        tb = ptable[:, c * cb:(c + 1) * cb]  # [B, cb]
        idx = jnp.maximum(tb, 0)
        kc = k_pool[layer, idx]  # [B, cb, bt, KV, D]
        vc = v_pool[layer, idx]
        if quantized:
            ks = k_scale[layer, idx][:, :, None, :, None]
            vs = v_scale[layer, idx][:, :, None, :, None]
            kc = kc.astype(jnp.float32) * ks
            vc = vc.astype(jnp.float32) * vs
        kc = kc.astype(jnp.float32).reshape(b, cb * bt, kv, d)
        vc = vc.astype(jnp.float32).reshape(b, cb * bt, kv, d)
        s = jnp.einsum(
            "bqgnd,btgd->bqgnt", qr, kc, preferred_element_type=jnp.float32
        ).reshape(b, Q, h, cb * bt)
        kpos = c * cb * bt + jnp.arange(cb * bt)
        live = jnp.repeat(tb >= 0, bt, axis=1)[:, None, None, :]
        mask = (
            live
            & (kpos[None, None, None, :] <= qpos[:, :, None, None])
            & (kpos[None, None, None, :] < kvl)
        )
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # NEG_INF is finite: a fully-masked row would otherwise see
        # exp(NEG_INF - NEG_INF) = 1 and sum garbage into l/acc
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum(
            "bqgnt,btgd->bqgnd", p.reshape(b, Q, kv, n_rep, cb * bt), vc,
            preferred_element_type=jnp.float32,
        ).reshape(b, Q, h, d)
        acc = acc * alpha + pv
        m = m_new
    if partial_out:
        return acc, m[..., 0], l[..., 0]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def paged_attention(
    q: jnp.ndarray,        # [B, H, D] one query per slot, or [B, Q, H, D]
    k_pool: jnp.ndarray,   # [N, block_tokens, KV, D] physical blocks, or the
    v_pool: jnp.ndarray,   # stacked [L, N, block_tokens, KV, D] with `layer`
    tables: jnp.ndarray,   # [B, Nmax] int32 block table per slot
    positions: jnp.ndarray,  # [B] int32 global position of query 0
    *,
    layer=None,            # int32 scalar (traced or not): which layer of a
                           # stacked 5-D pool to attend; 4-D pools take none
    k_scale: Optional[jnp.ndarray] = None,  # [N, KV] f32 (int8 pools), or
    v_scale: Optional[jnp.ndarray] = None,  # stacked [L, N, KV]
    scale: Optional[float] = None,
    impl: str = "auto",            # auto | kernel | xla
    interpret: Optional[bool] = None,
    signed_tables: bool = False,   # True: entries < 0 are dead (sharded
                                   # callers pre-remap); False: entry 0 is
                                   # the null-block sentinel
    partial_out: bool = False,     # return (acc, m, l) for cross-shard merge
    chunk_blocks: int = 8,
    kv_len: Optional[jnp.ndarray] = None,  # [B] live cached keys; keys at
                                   # kpos >= kv_len are dead regardless of
                                   # causality (verify: kv_len = positions;
                                   # default positions + Q covers decode
                                   # and prefill, whose own K/V is written)
    block_q: int = 16,             # kernel query-tile size (q axis padded
                                   # to a multiple; XLA handles Q whole)
) -> jnp.ndarray | Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Multi-query paged attention over a block pool (module docstring).

    Query i of slot b sits at global position positions[b] + i and
    attends key position t iff t <= positions[b] + i and t < kv_len[b].
    Returns out in q's dtype and shape ([B, H, D] for 3-D q, else
    [B, Q, H, D]), or with `partial_out=True` the unnormalized f32
    (acc, m, l) triple for `merge_partials` (m/l drop the head_dim axis).
    Slots whose table is fully dead return zeros."""
    global _LAST_IMPL
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if (k_pool.ndim == 5) != (layer is not None):
        raise ValueError(
            "a stacked [L, N, bt, KV, D] pool takes `layer`, a per-layer "
            f"[N, bt, KV, D] pool does not: got a {k_pool.ndim}-D pool with "
            f"layer={layer!r}"
        )
    if layer is None:
        # one code path below: a per-layer pool is a stack of one (a
        # leading unit dim is a bitcast, not a copy)
        layer = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    layer = jnp.asarray(layer, jnp.int32)
    if q.shape[2] % k_pool.shape[3]:
        raise ValueError(
            f"q heads {q.shape[2]} not a multiple of kv heads {k_pool.shape[3]}"
        )
    if impl not in ("auto", "kernel", "xla"):
        raise ValueError(f"impl must be auto|kernel|xla, got {impl!r}")
    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    if signed_tables:
        ptable = tables.astype(jnp.int32)
    else:
        ptable = jnp.where(tables > 0, tables, -1).astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    if kv_len is None:
        kv_len = positions + q.shape[1]
    kv_len = kv_len.astype(jnp.int32)
    _LAST_IMPL = impl
    if impl == "kernel":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        out = _paged_attention_pallas(
            q, k_pool, v_pool, ptable, positions, kv_len, layer, k_scale,
            v_scale, scale, partial_out, interpret, block_q,
        )
    else:
        out = _paged_attention_xla(
            q, k_pool, v_pool, ptable, positions, kv_len, layer, k_scale,
            v_scale, scale, partial_out, chunk_blocks,
        )
    if squeeze:
        if partial_out:
            acc, m, l = out
            return acc[:, 0], m[:, 0], l[:, 0]
        return out[:, 0]
    return out


# --------------------------------------------------------------------------
# latent (MLA) pools: one row per token, shared by every head
# --------------------------------------------------------------------------


def _live_steps(ptable, positions, kv_len, n_queries, bt, nb):
    """The latent kernel's work list, from the call's own scalars: the
    (slot, step) pairs that hold a key some query may see, in slot order,
    a step being `nb` consecutive table entries (`ptable` [B, Nmax], Nmax a
    multiple of nb). Returns

      step_slot, step_j  [B * Nmax / nb] int32: pair i is step `step_j[i]`
                         of slot `step_slot[i]`, for i < n_steps
      step_blocks        [B * Nmax] int32: pair i's pool blocks, its nb
                         table entries at i * nb (a dead one is block 0),
                         gathered ONCE here so that each of the kernel's nb
                         index maps reads one scalar
      n_steps            int32 scalar = sum over slots of ceil(walk / nb),
                         `walk` the slot's `_walk_blocks`
      keys               [B] int32: how many of a slot's cached keys exist
                         and may be seen, min(kv_len, positions + Q,
                         walk * bt) (a latent table is live from the front)

    A slot's last step is the one with (step_j + 1) * nb * bt >= keys. With
    nothing live, pair 0 is the LAST slot's step 0 and that slot's `keys` is
    0: visited, found dead, finalized to zeros."""
    b, nmax = ptable.shape
    walk = _walk_blocks(ptable, positions, kv_len, n_queries, bt)
    counts = (walk + (nb - 1)) // nb  # [B] steps a slot
    ends = jnp.cumsum(counts)  # slot s owns pairs [ends - counts, ends)
    i = jnp.arange(b * (nmax // nb), dtype=jnp.int32)
    # the slots that end at or before pair i are the slots before its own
    # ([steps, B] compares, as `_live_walk`: no sort)
    before = ends[None, :] <= i[:, None]
    step_slot = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), b - 1)
    step_j = i - jnp.sum(jnp.where(before, counts[None, :], 0), axis=1,
                         dtype=jnp.int32)
    # past the list's end step_j runs on: clamped, never visited
    step_blocks = jnp.maximum(
        ptable.reshape(b, nmax // nb, nb)[
            step_slot, jnp.minimum(step_j, nmax // nb - 1)], 0)
    keys = jnp.minimum(jnp.minimum(kv_len, positions + n_queries), walk * bt)
    return step_slot, step_j, step_blocks.reshape(-1), ends[-1], keys


def _mla_kernel(blocks_ref, pos_ref, keys_ref, layer_ref, slot_ref, step_ref,
                q_ref, *rest, bt, qb, nb, heads, rank, scale, out_dtype):
    del blocks_ref, layer_ref  # read by the index maps only
    # after the step's tiles, the output's aliased zeros: an HBM operand the
    # body never touches (a slot the grid does not visit keeps them)
    tiles, (_, o_ref, acc, m_i, l_i) = rest[:nb], rest[nb:]
    qt = pl.program_id(0)
    i = pl.program_id(1)  # the second axis walks LIVE (slot, step) pairs
    b = slot_ref[i]
    j = step_ref[i]
    rows = qb * heads
    span = nb * bt  # tokens one grid step attends

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_i[:] = jnp.full_like(m_i, NEG_INF)
        l_i[:] = jnp.zeros_like(l_i)

    keys = keys_ref[b]
    qbase = pos_ref[b] + qt * qb  # global position of this tile's first query
    # a listed step holds a key SOME query sees (the one step of an empty
    # list holds none); this tile attends it iff its own last query does (an
    # early prefill tile under later steps)
    live = jnp.logical_and(j * span < keys, j * span <= qbase + qb - 1)

    @pl.when(live)
    def _attend():
        # [span, W]: the step's blocks, rows = consecutive token positions
        kv = jnp.concatenate([t[...] for t in tiles], axis=0)
        q = q_ref[0].reshape(rows, kv.shape[1])
        s = lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [rows, span]
        kpos = j * span + lax.broadcasted_iota(jnp.int32, (rows, span), 1)
        # row = query * heads + head
        qi = lax.broadcasted_iota(jnp.int32, (rows, span), 0) // heads
        mask = jnp.logical_and(kpos <= qbase + qi, kpos < keys)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_i[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_i[:] = alpha * l_i[:] + jnp.sum(p, axis=1, keepdims=True)
        m_i[:] = m_new
        # the values are the rows' first `rank` columns
        pv = lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc[:] = acc[:] * alpha + pv

    @pl.when((j + 1) * span >= keys)  # the slot's last step
    def _finalize():
        l = l_i[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe_l).reshape(qb, heads, rank).astype(out_dtype)


def _mla_attention_pallas(q, pool, ptable, positions, kv_len, layer, rank,
                          scale, interpret, block_q, blocks_per_step,
                          block_rows):
    """The latent walk as a Pallas call: grid (query tile, LIVE (slot, step)
    pair), `_mla_kernel` the body.

    What is walked. A step is `blocks_per_step` consecutive table entries
    (16: 1,024 tokens), and the grid's second axis is the flat list of the
    steps that hold a key some query of the call may see (`_live_steps`,
    computed inside the program from tables, positions and kv_len): slot
    after slot, each for ceil(its walk / blocks_per_step) steps. The list's
    length is a TRACED grid bound, so one compiled program per (B, Nmax, Q)
    serves every occupancy; index maps and body read the pair's slot, step
    and pool blocks from three scalar-prefetch arrays. Scratch is
    initialised at a slot's step 0 and the output tile written at its last
    step; the output starts as aliased zeros, so a slot with no visible key
    is never visited and reads exactly zero. Nothing of the table's shape is
    left in the walk: not dead slots, not the tail of a live slot's row, not
    a short slot's steps under a longer one's (the rectangle the per-head
    kernel keeps). A dead entry inside a slot's last step is block 0 in the
    list, as the per-head maps clamp it, and its keys are masked by `keys`.

    How wide a step is. A grid step costs ~0.4 us whatever it moves (a fit
    to the sweep) and a slot's last step is on average half empty, so a wide
    step suits long contexts and a narrow one short ones. Swept on a v5e at
    both latent cells' shapes (PERF.md, PR 43; us a decode call at 8 / 16
    entries a step): 6 live slots of 4k-16k keys under 32 heads 117 / 103,
    22 such slots 459 / 389; 64 live slots of ~1.2k keys under 128 heads
    319 / 324; 4 entries lose everywhere but a short prefill. What decides
    is the context's length, not the head count, so nothing is derived from
    `heads`: ONE width, 16, which loses 1.5 % at most where 8 loses 15 %.

    How a tile is sized. Every head of a query scores the same cached row,
    so a tile's matmul rows are queries x HEADS, and what the body keeps in
    VMEM goes by rows, not by queries: the f32 accumulator [rows, rank]
    (2 KB a row), the score tile [rows, span] (span = blocks_per_step x
    block_tokens = 1,024: 4 KB a row) and a second such tile for the
    probabilities, beside the query tile [rows, W] bf16 and the output tile
    [rows, rank] bf16, both double-buffered by the pipeline (2 x (1,280 +
    1,024) B a row), and the step's pool blocks (16 x 64 x 640 x 2 B, twice:
    2.6 MB). A tile is therefore min(block_q, block_rows // heads) queries:

      32 heads   16 queries = 512 rows: 5 MB f32 + 2.3 MB of q and output
                 + 2.6 MB of blocks = 9.9 MB
      128 heads  16 queries would be 2,048 rows: 20 MB + 9.2 MB + 2.6 MB,
                 over the 16 MB of scoped VMEM a kernel gets on a v5e;
                 4 queries = 512 rows is the same 9.9 MB

    and in decode (one query) a tile is `heads` rows: 128 heads fill the
    MXU's 128 rows with one slot's query."""
    b, Q, h, w = q.shape
    n_layers, n_blocks, bt = pool.shape[:3]
    # [L, N, bt, 1, W] -> [L, N, bt, W]: the unit head dim would otherwise
    # be the tile's sublane dim (a bitcast: it is degenerate)
    pool = pool.reshape(n_layers, n_blocks, bt, w)
    nb = max(1, min(int(blocks_per_step), ptable.shape[1]))
    nmax = -(-ptable.shape[1] // nb) * nb
    if nmax != ptable.shape[1]:
        ptable = jnp.pad(ptable, ((0, 0), (0, nmax - ptable.shape[1])),
                         constant_values=-1)
    qb = max(1, min(int(block_q), int(block_rows) // h, Q))
    qp = -(-Q // qb) * qb
    if qp != Q:
        q = jnp.pad(q, ((0, 0), (0, qp - Q), (0, 0), (0, 0)))
    step_slot, step_j, step_blocks, n_steps, keys = _live_steps(
        ptable, positions, kv_len, Q, bt, nb)
    # at least one step: with nothing live the list's first pair is visited,
    # found dead and finalized to the zeros the output holds already
    grid = (qp // qb, jnp.maximum(n_steps, 1))

    def tile_spec(k):  # block k of the pair's step
        return pl.BlockSpec(
            (None, None, bt, w),
            lambda qt_, i_, blk, pos, kys, lyr, slt, stp: (
                lyr[0], blk[i_ * nb + k], 0, 0))

    o_map = lambda qt_, i_, blk, pos, kys, lyr, slt, stp: (slt[i_], qt_, 0, 0)
    kernel = functools.partial(
        _mla_kernel, bt=bt, qb=qb, nb=nb, heads=h, rank=rank, scale=scale,
        out_dtype=q.dtype,
    )
    n_scalars = 6
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalars,
            grid=grid,
            # the pool goes in once per block of a step, each with its own
            # index map: `nb` table entries' blocks are in flight together,
            # so a step attends nb * bt tokens (a grid step costs the same
            # whatever it moves; at one 64-token block a step the walk of a
            # 16k context is all overhead)
            in_specs=[pl.BlockSpec((1, qb, h, w), o_map)]
            + [tile_spec(k) for k in range(nb)]
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, qb, h, rank), o_map)],
            scratch_shapes=[
                pltpu.VMEM((qb * h, rank), jnp.float32),
                pltpu.VMEM((qb * h, 1), jnp.float32),
                pltpu.VMEM((qb * h, 1), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, qp, h, rank), q.dtype)],
        # the output starts as zeros (aliased, left in HBM): the rows of a
        # slot the grid never visits are what a walk over no key would write
        input_output_aliases={n_scalars + 1 + nb: 0},
        # query tiles are independent; the pairs are walked in order — a
        # slot's steps carry its online-softmax scratch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        # the profiler's event is %mla_paged_attention.<n>: the benchmark's
        # readers find the kernel by this name
        name="mla_paged_attention",
    )(step_blocks, positions, keys, jnp.reshape(layer, (1,)), step_slot,
      step_j, q, *([pool] * nb), jnp.zeros((b, qp, h, rank), q.dtype))
    return out[0][:, :Q]


def mla_paged_attention(
    q: jnp.ndarray,        # [B, Q, H, W]: [q absorbed into the latent | q_rope],
                           # zero-padded to the pool's row width
    pool: jnp.ndarray,     # [L, N, block_tokens, 1, W] latent rows
    tables: jnp.ndarray,   # [B, Nmax] int32, 0 = the null block
    positions: jnp.ndarray,  # [B] int32 global position of query 0
    *,
    layer,                 # int32 scalar (traced or not)
    rank: int,             # the rows' first `rank` columns are the values
    scale: float,
    kv_len: Optional[jnp.ndarray] = None,
    impl: str = "auto",    # auto | kernel | xla
    interpret: Optional[bool] = None,
    block_q: int = 16,       # queries a tile at most (the per-head op's default)
    blocks_per_step: int = 16,  # pool blocks in flight a grid step
    block_rows: int = 512,   # queries x heads a tile at most: what VMEM holds
) -> jnp.ndarray:
    """Absorbed multi-query attention over a latent (MLA) pool: every head
    of query i of slot b scores ALL W columns of each cached row at key
    positions t <= positions[b] + i, t < kv_len[b], and the output is the
    softmax-weighted sum of the rows' first `rank` columns -> [B, Q, H,
    rank] in q's dtype. One row serves as key and value for all H heads, so
    the walk reads a token's row once. The kernel visits the call's live
    (slot, step) pairs and nothing else of the [B, Nmax] table
    (`_mla_attention_pallas`); a slot with no visible key reads zeros.
    Tables are live from the front (no signed / sharded tables here). The
    XLA twin is the per-head op's chunked walk with the pool as K and as V
    over one kv head."""
    global _LAST_IMPL
    if pool.ndim != 5 or pool.shape[3] != 1 or q.shape[-1] != pool.shape[-1]:
        raise ValueError(
            "mla_paged_attention takes a stacked latent pool [L, N, bt, 1, W]"
            f" and q [B, Q, H, W]: got pool {pool.shape}, q {q.shape}"
        )
    if impl not in ("auto", "kernel", "xla"):
        raise ValueError(f"impl must be auto|kernel|xla, got {impl!r}")
    if impl == "auto":
        impl = "kernel" if jax.default_backend() == "tpu" else "xla"
    layer = jnp.asarray(layer, jnp.int32)
    positions = positions.astype(jnp.int32)
    if kv_len is None:
        kv_len = positions + q.shape[1]
    kv_len = kv_len.astype(jnp.int32)
    ptable = jnp.where(tables > 0, tables, -1).astype(jnp.int32)
    _LAST_IMPL = impl
    if impl == "kernel":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return _mla_attention_pallas(
            q, pool, ptable, positions, kv_len, layer, rank, float(scale),
            interpret, block_q, blocks_per_step, block_rows,
        )
    out = _paged_attention_xla(
        q, pool, pool, ptable, positions, kv_len, layer, None, None,
        float(scale), False, 8,
    )
    return out[..., :rank]


def merge_partials(acc, m, l, axis_names=None, out_dtype=jnp.float32):
    """Combine per-shard online-softmax partials into the final output.

    acc [..., D] unnormalized, m/l [...] (any shared leading shape —
    [B, H] for single-query, [B, Q, H] for multi-query). With
    `axis_names`, the combine runs across those shard_map axes (pmax +
    psum); without, acc/m/l carry a leading shard dim to reduce over.
    Rows with no live keys anywhere (l == 0 everywhere) come out zero,
    mirroring the kernel."""
    if axis_names:
        m_g = lax.pmax(m, axis_names)
        e = jnp.exp(m - m_g)
        num = lax.psum(acc * e[..., None], axis_names)
        den = lax.psum(l * e, axis_names)
    else:
        m_g = jnp.max(m, axis=0)
        e = jnp.exp(m - m_g)
        num = jnp.sum(acc * e[..., None], axis=0)
        den = jnp.sum(l * e, axis=0)
    safe = jnp.where(den == 0.0, 1.0, den)
    return (num / safe[..., None]).astype(out_dtype)
