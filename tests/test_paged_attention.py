"""Fused paged-attention kernel vs the dense gather reference (ISSUE 6).

Tier-1 CI contract (the "skip-guard"): these tests run the Pallas kernel
in INTERPRET mode on CPU and must fail loudly — never skip — when the
kernel diverges from the dense reference, when a forced implementation
silently falls back to another one (asserted via ops.paged_attention
_LAST_IMPL), or when interpret mode degenerates past the module's wall
clock budget. A green tier-1 therefore certifies the kernel's math, not
just its importability.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

# the ops package re-exports the FUNCTION under the same name; go through
# importlib for the module itself (its _LAST_IMPL observability var)
pa_mod = importlib.import_module("ray_tpu.ops.paged_attention")
merge_partials = pa_mod.merge_partials
paged_attention = pa_mod.paged_attention

pytestmark = pytest.mark.pallas

# interpret-mode wall budget for the CANONICAL shapes below; blowing it
# means interpret-mode grids grew past what tier-1 can afford — fail loud
# so the suite shrinks the shapes instead of silently eating minutes
INTERPRET_BUDGET_S = 120.0
_t0 = time.perf_counter()


@pytest.fixture(autouse=True, scope="module")
def _module_clock():
    # anchor the budget at the module's FIRST test, not at import:
    # pytest imports every test module during collection, so an
    # import-time clock would bill this module for the whole suite
    # that runs before it
    global _t0
    _t0 = time.perf_counter()
    yield


def _setup(b=3, h=4, kv=2, d=16, bt=8, n_pool=12, n_max=5, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pool, bt, kv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pool, bt, kv, d)), jnp.float32)
    # slot 0 short (mid-block position), slot 1 full table, slot 2 dead
    tables = np.zeros((b, n_max), np.int32)
    tables[0, :2] = [3, 7]
    tables[1, :n_max] = rng.choice(
        np.arange(1, n_pool), size=n_max, replace=False
    )
    positions = jnp.asarray([9, n_max * bt - 4, 0], jnp.int32)
    return q, kp, vp, jnp.asarray(tables), positions


def _dense_reference(q, kp, vp, tables, positions):
    """Gather + masked softmax — the exact math the gather decode path
    (transformer._cached_attend) runs, with repeated KV heads."""
    b, h, d = q.shape
    _, bt, kv, _ = kp.shape
    n_max = tables.shape[1]
    n_rep = h // kv
    kw = kp[tables].reshape(b, n_max * bt, kv, d)
    vw = vp[tables].reshape(b, n_max * bt, kv, d)
    kr = jnp.repeat(kw, n_rep, axis=2)
    vr = jnp.repeat(vw, n_rep, axis=2)
    logits = jnp.einsum("bhd,bkhd->bhk", q, kr) * (d ** -0.5)
    kpos = jnp.arange(n_max * bt)[None, None, :]
    live = jnp.repeat(tables > 0, bt, axis=1)[:, None, :]
    mask = live & (kpos <= positions[:, None, None])
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask.any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhk,bkhd->bhd", p, vr)


def _quantize_pool(kp):
    sc = jnp.abs(kp).max(axis=(1, 3)) / 127.0
    q8 = jnp.clip(
        jnp.round(kp / jnp.maximum(sc, 1e-20)[:, None, :, None]), -127, 127
    ).astype(jnp.int8)
    return q8, sc


@pytest.mark.parametrize("chunk_blocks", [1, 2, 3, 8])
def test_xla_matches_reference(chunk_blocks):
    q, kp, vp, tables, positions = _setup()
    ref = _dense_reference(q, kp, vp, tables, positions)
    out = paged_attention(
        q, kp, vp, tables, positions, impl="xla", chunk_blocks=chunk_blocks
    )
    assert pa_mod._LAST_IMPL == "xla"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_kernel_interpret_matches_reference():
    """The skip-guard proper: the PALLAS kernel (interpret mode on CPU)
    against the dense reference. A silent fallback to XLA would pass the
    numbers but fail the _LAST_IMPL assertion; a divergence fails the
    tolerance. Either way the failure is loud."""
    q, kp, vp, tables, positions = _setup()
    ref = _dense_reference(q, kp, vp, tables, positions)
    out = paged_attention(
        q, kp, vp, tables, positions, impl="kernel", interpret=True
    )
    assert pa_mod._LAST_IMPL == "kernel", "kernel path silently not taken"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_gqa_fold_no_materialized_repeat():
    """n_rep = 4: the kernel indexes kv head h // n_rep instead of
    repeating KV — outputs must still match the repeated-KV reference."""
    q, kp, vp, tables, positions = _setup(h=8, kv=2)
    ref = _dense_reference(q, kp, vp, tables, positions)
    for impl, kw in (("xla", {}), ("kernel", {"interpret": True})):
        out = paged_attention(q, kp, vp, tables, positions, impl=impl, **kw)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=impl,
        )


def test_null_block_and_past_length_masked():
    """Entries past a slot's live blocks are the null block (0) and the
    write block's tail positions exceed `positions` — neither may leak
    into the softmax. Poison the null block and every past-length
    position with huge values; outputs must not move."""
    q, kp, vp, tables, positions = _setup()
    ref = _dense_reference(q, kp, vp, tables, positions)
    kp_p = kp.at[0].set(1e4)
    vp_p = vp.at[0].set(1e4)
    # poison position 9+1.. of slot 0's tail block (table[0,1] = 7)
    kp_p = kp_p.at[7, 2:].set(1e4)
    vp_p = vp_p.at[7, 2:].set(1e4)
    for impl, kw in (("xla", {}), ("kernel", {"interpret": True})):
        out = paged_attention(
            q, kp_p, vp_p, tables, positions, impl=impl, **kw
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4,
            err_msg=impl,
        )
        # the fully-dead slot (all-null table) returns zeros, not NaNs
        assert bool(jnp.all(out[2] == 0.0)), impl


def test_int8_dequant_inside_kernel():
    q, kp, vp, tables, positions = _setup()
    ref = _dense_reference(q, kp, vp, tables, positions)
    k8, ks = _quantize_pool(kp)
    v8, vs = _quantize_pool(vp)
    outs = {}
    for impl, kw in (("xla", {}), ("kernel", {"interpret": True})):
        outs[impl] = paged_attention(
            q, k8, v8, tables, positions, k_scale=ks, v_scale=vs,
            impl=impl, **kw,
        )
        # within quantization tolerance of the fp reference
        np.testing.assert_allclose(
            np.asarray(outs[impl]), np.asarray(ref), atol=0.05, rtol=0.05,
            err_msg=impl,
        )
    # and the two implementations agree with each other tightly
    np.testing.assert_allclose(
        np.asarray(outs["xla"]), np.asarray(outs["kernel"]),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("impl,kw", [("xla", {}), ("kernel", {"interpret": True})])
def test_partial_merge_equals_full(impl, kw):
    """Split the pool into two 'shards', attend each with partial_out and
    signed local tables, merge — must equal the single full-pool pass.
    This is exactly the shard_map composition the sharded decode uses."""
    q, kp, vp, tables, positions = _setup()
    full = paged_attention(q, kp, vp, tables, positions, impl=impl, **kw)
    half = kp.shape[0] // 2
    accs, ms, ls = [], [], []
    for sh in range(2):
        lo = sh * half
        local = jnp.where(
            (tables > 0) & (tables >= lo) & (tables < lo + half),
            tables - lo, -1,
        )
        a, m, l = paged_attention(
            q, kp[lo:lo + half], vp[lo:lo + half], local, positions,
            impl=impl, signed_tables=True, partial_out=True, **kw,
        )
        accs.append(a), ms.append(m), ls.append(l)
    merged = merge_partials(jnp.stack(accs), jnp.stack(ms), jnp.stack(ls))
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(full), atol=2e-5, rtol=2e-5
    )


def _dense_reference_mq(q, kp, vp, tables, positions, kv_len=None):
    """Multi-query twin of _dense_reference: q [B, Q, H, D], query i of
    slot b at global position positions[b] + i, keys visible iff
    kpos <= positions[b] + i AND kpos < kv_len[b]."""
    b, Q, h, d = q.shape
    _, bt, kv, _ = kp.shape
    n_max = tables.shape[1]
    n_rep = h // kv
    kw = kp[tables].reshape(b, n_max * bt, kv, d)
    vw = vp[tables].reshape(b, n_max * bt, kv, d)
    kr = jnp.repeat(kw, n_rep, axis=2)
    vr = jnp.repeat(vw, n_rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * (d ** -0.5)
    live = jnp.repeat(tables > 0, bt, axis=1)
    qpos = positions[:, None] + jnp.arange(Q)[None, :]
    mask = (
        live[:, None, :]
        & (jnp.arange(n_max * bt)[None, None, :] <= qpos[:, :, None])
    )
    if kv_len is not None:
        mask = mask & (
            jnp.arange(n_max * bt)[None, None, :] < kv_len[:, None, None]
        )
    logits = jnp.where(mask[:, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask[:, None].any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vr)


def _setup_mq(Q=5, b=2, h=4, kv=2, d=16, bt=8, n_pool=12, n_max=5, seed=1):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, Q, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pool, bt, kv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pool, bt, kv, d)), jnp.float32)
    tables = np.zeros((b, n_max), np.int32)
    # slot 0: prefill-chunk shape — 3 live blocks, queries straddle the
    # block 1 -> 2 boundary (first query mid-block 1)
    tables[0, :3] = [3, 7, 9]
    # slot 1: verify shape — full table, queries at the very tail
    tables[1, :n_max] = rng.choice(
        np.arange(1, n_pool), size=n_max, replace=False
    )
    positions = jnp.asarray([bt + 3, n_max * bt - Q], jnp.int32)
    return q, kp, vp, jnp.asarray(tables), positions


@pytest.mark.parametrize("impl,kw", [("xla", {}), ("kernel", {"interpret": True})])
def test_multiquery_matches_reference(impl, kw):
    """The q-tile grid axis (ISSUE 13): Q=5 queries per slot, causal
    within the window, one straddling a block boundary — both impls must
    match the multi-query dense reference."""
    q, kp, vp, tables, positions = _setup_mq()
    ref = _dense_reference_mq(q, kp, vp, tables, positions)
    out = paged_attention(q, kp, vp, tables, positions, impl=impl, **kw)
    assert pa_mod._LAST_IMPL == impl
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_multiquery_q_tile_padding():
    """Q not a multiple of block_q: the kernel pads the q axis and the
    padded rows must be sliced off without touching real outputs."""
    q, kp, vp, tables, positions = _setup_mq(Q=5)
    ref = _dense_reference_mq(q, kp, vp, tables, positions)
    for bq in (1, 2, 4, 16):
        out = paged_attention(
            q, kp, vp, tables, positions, impl="kernel", interpret=True,
            block_q=bq,
        )
        assert out.shape == q.shape, bq
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5,
            err_msg=f"block_q={bq}",
        )


@pytest.mark.parametrize("impl,kw", [("xla", {}), ("kernel", {"interpret": True})])
def test_multiquery_kv_len_hides_unwritten_span(impl, kw):
    """Verify semantics: kv_len = positions means the cached window ends
    strictly BEFORE the first query (its K/V is in-flight, not yet
    written). Poison every pool position at or past kv_len — outputs must
    match a reference masked the same way, and must NOT equal the
    default (kv_len = positions + Q) formulation."""
    q, kp, vp, tables, positions = _setup_mq()
    # pin slot 1's table away from the poisoned blocks so the poison hits
    # ONLY positions the kv_len cap must hide (its own tail block aside)
    tables = tables.at[1].set(jnp.asarray([1, 2, 4, 5, 6], jnp.int32))
    kv_len = positions  # strictly before the first query
    ref = _dense_reference_mq(q, kp, vp, tables, positions, kv_len=kv_len)
    # poison the span [kv_len, ...) of each slot's own blocks: slot 0's
    # block 1 (positions 8..15, kv_len=11) + block 2 entirely, and slot
    # 1's last block past offset 3 (positions 35..39, kv_len=35)
    kp_p = kp.at[7, 3:].set(1e4).at[9].set(1e4).at[6, 3:].set(1e4)
    vp_p = vp.at[7, 3:].set(1e4).at[9].set(1e4).at[6, 3:].set(1e4)
    out = paged_attention(
        q, kp_p, vp_p, tables, positions, kv_len=kv_len, impl=impl, **kw
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-4, rtol=2e-4
    )
    # sanity: the cap actually excluded something a causal-only mask sees
    causal = paged_attention(q, kp, vp, tables, positions, impl=impl, **kw)
    assert not np.allclose(np.asarray(out), np.asarray(causal), atol=1e-3)


@pytest.mark.parametrize("impl,kw", [("xla", {}), ("kernel", {"interpret": True})])
def test_multiquery_partial_merge_equals_full(impl, kw):
    """Sharded-pool composition for the multi-query path: two pool
    'shards' with partial_out merge to the full-pool answer — the exact
    shard_map math fused prefill/verify run under dp/fsdp meshes."""
    q, kp, vp, tables, positions = _setup_mq()
    full = paged_attention(q, kp, vp, tables, positions, impl=impl, **kw)
    half = kp.shape[0] // 2
    accs, ms, ls = [], [], []
    for sh in range(2):
        lo = sh * half
        local = jnp.where(
            (tables > 0) & (tables >= lo) & (tables < lo + half),
            tables - lo, -1,
        )
        a, m, l = paged_attention(
            q, kp[lo:lo + half], vp[lo:lo + half], local, positions,
            impl=impl, signed_tables=True, partial_out=True, **kw,
        )
        accs.append(a), ms.append(m), ls.append(l)
    merged = merge_partials(jnp.stack(accs), jnp.stack(ms), jnp.stack(ls))
    np.testing.assert_allclose(
        np.asarray(merged), np.asarray(full), atol=2e-5, rtol=2e-5
    )


def test_multiquery_int8_both_impls_agree():
    """int8 dequant-in-kernel on the multi-query path: xla and interpret
    kernel agree tightly with each other and within quantization
    tolerance of the fp reference."""
    q, kp, vp, tables, positions = _setup_mq()
    ref = _dense_reference_mq(q, kp, vp, tables, positions)
    k8, ks = _quantize_pool(kp)
    v8, vs = _quantize_pool(vp)
    outs = {}
    for impl, kw in (("xla", {}), ("kernel", {"interpret": True})):
        outs[impl] = paged_attention(
            q, k8, v8, tables, positions, k_scale=ks, v_scale=vs,
            impl=impl, **kw,
        )
        np.testing.assert_allclose(
            np.asarray(outs[impl]), np.asarray(ref), atol=0.05, rtol=0.05,
            err_msg=impl,
        )
    np.testing.assert_allclose(
        np.asarray(outs["xla"]), np.asarray(outs["kernel"]),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("impl,kw", [("xla", {}), ("kernel", {"interpret": True})])
@pytest.mark.parametrize("int8", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("partial_out", [False, True], ids=["out", "partial"])
@pytest.mark.parametrize("Q", [1, 5])
def test_stacked_pool_layer_equals_per_layer_call(impl, kw, int8, partial_out, Q):
    """The paged programs carry the stacked [L, N, bt, KV, D] pool through
    their layer loop and hand it over whole with a (traced) `layer` index:
    that must read exactly what the per-layer call reads from pool[l]."""
    L = 3
    q, _, _, tables, positions = _setup_mq(Q=Q)
    rng = np.random.default_rng(7)
    shape = (L, 12, 8, 2, 16)
    kp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    vp = jnp.asarray(rng.normal(size=shape), jnp.float32)
    scales = [{}] * L
    stacked = {}
    if int8:
        k8, ks = zip(*(_quantize_pool(kp[l]) for l in range(L)))
        v8, vs = zip(*(_quantize_pool(vp[l]) for l in range(L)))
        kp, vp = jnp.stack(k8), jnp.stack(v8)
        scales = [dict(k_scale=ks[l], v_scale=vs[l]) for l in range(L)]
        stacked = dict(k_scale=jnp.stack(ks), v_scale=jnp.stack(vs))
    common = dict(impl=impl, partial_out=partial_out, **kw)

    @jax.jit
    def at_layer(l):  # traced, as under the layer scan
        return paged_attention(
            q, kp, vp, tables, positions, layer=l, **stacked, **common)

    for l in (0, L - 1):
        got = at_layer(jnp.int32(l))
        assert pa_mod._LAST_IMPL == impl
        want = paged_attention(
            q, kp[l], vp[l], tables, positions, **scales[l], **common)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---- the walk follows what lives (ISSUE 40) -------------------------------
#
# The kernel's grid is bounded by the call's own scalars: live slots x the
# longest live slot's blocks. Each case is one occupancy; `lens` is the keys
# a slot holds once its queries are written (0 = a released slot: position
# 0 and a table of null entries).


def _mixed_lens():
    lens = np.random.default_rng(40).integers(1, 4 * 8 + 1, size=32)
    lens[[0, 3, 4, 11, 17, 30, 31]] = 0
    return [int(n) for n in lens]


_OCCUPANCIES = {
    "all-dead": dict(lens=[0, 0, 0]),
    "one-token": dict(lens=[0, 1, 0]),
    # 8 and 16 end exactly on a block boundary, 9 and 17 one past it
    "block-boundary": dict(lens=[8, 9, 0, 16, 17]),
    "32-mixed": dict(lens=_mixed_lens()),
    # signed tables, an out-of-shard block INSIDE two live ranges
    "hole-partial": dict(lens=[20, 0, 30], holes=[(0, 1), (2, 0), (2, 2)]),
    "hole-only": dict(lens=[7, 0, 12], holes=[(0, 0)]),
    # q = k + 1, the window ends strictly before the first query
    "verify": dict(lens=[11, 0, 27, 3], Q=3, verify=True),
    # 6 queries in tiles of 4 from position 5: tile 0 straddles blocks 0/1
    "prefill-tile": dict(lens=[11, 0], Q=6, block_q=4),
    "int8": dict(lens=[9, 0, 25], int8=True),
    "mha": dict(lens=[9, 0, 25, 32], h=2, kv=2),
    "gqa4": dict(lens=[9, 0, 25, 32], h=8, kv=2),
}


@pytest.mark.parametrize("case", list(_OCCUPANCIES))
def test_kernel_walks_what_lives(case):
    spec = dict(_OCCUPANCIES[case])
    lens = np.asarray(spec.pop("lens"))
    Q, h, kv = spec.pop("Q", 1), spec.pop("h", 4), spec.pop("kv", 2)
    holes, verify = spec.pop("holes", None), spec.pop("verify", False)
    int8 = spec.pop("int8", False)
    d, bt, n_max, n_pool = 16, 8, 4, 40
    b = len(lens)
    rng = np.random.default_rng(b + Q)
    q = jnp.asarray(rng.normal(size=(b, Q, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pool, bt, kv, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pool, bt, kv, d)), jnp.float32)
    tables = np.zeros((b, n_max), np.int32)
    for s, n in enumerate(lens):
        nb = -(-int(n) // bt)
        tables[s, :nb] = rng.integers(1, n_pool, size=nb)
    positions = np.maximum(lens - Q, 0).astype(np.int32)
    kw = dict(spec)
    dead = lens == 0
    if holes is not None:
        tables = np.where(tables > 0, tables, -1)
        for s, j in holes:
            tables[s, j] = -1
        kw.update(signed_tables=True, partial_out=True)
        dead |= (tables < 0).all(axis=1)
    if verify:
        kw["kv_len"] = jnp.asarray(positions)
        dead |= positions == 0
    if int8:
        (kp, ks), (vp, vs) = _quantize_pool(kp), _quantize_pool(vp)
        kw.update(k_scale=ks, v_scale=vs)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(positions))
    want = paged_attention(*args, impl="xla", **kw)
    got = paged_attention(*args, impl="kernel", interpret=True, **kw)
    assert pa_mod._LAST_IMPL == "kernel"
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=2e-5, rtol=2e-5)
    # a dead slot's rows are exactly zero: the output, or with partial_out
    # the accumulator and the denominator (its running max stays NEG_INF)
    out, *stats = jax.tree.leaves(got)
    assert dead.any() and not np.asarray(out)[dead].any()
    if stats:
        m, l = (np.asarray(x)[dead] for x in stats)
        assert not l.any() and (m == pa_mod.NEG_INF).all()
    assert np.asarray(out)[~dead].any() or dead.all()


def test_validation_errors():
    q, kp, vp, tables, positions = _setup()
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, kp[None], vp[None], tables, positions)
    with pytest.raises(ValueError, match="layer"):
        paged_attention(q, kp, vp, tables, positions, layer=0)
    with pytest.raises(ValueError, match="together"):
        paged_attention(q, kp, vp, tables, positions,
                        k_scale=jnp.zeros((12, 2)))
    with pytest.raises(ValueError, match="impl"):
        paged_attention(q, kp, vp, tables, positions, impl="nope")
    with pytest.raises(ValueError, match="heads"):
        paged_attention(q[:, :3], kp, vp, tables, positions)


def test_interpret_wall_clock_budget():
    """Runs last: the whole module (every interpret-mode kernel above)
    must fit the tier-1 budget. A pathological interpret regression fails
    HERE with a number, instead of silently dragging the suite."""
    elapsed = time.perf_counter() - _t0
    assert elapsed < INTERPRET_BUDGET_S, (
        f"paged-attention interpret suite took {elapsed:.1f}s "
        f"(budget {INTERPRET_BUDGET_S}s) — shrink the kernel test shapes"
    )
