"""The Xing4.0 / DeepSeek-V3 layer family behind the paged engine, at a small
size on the CPU with seeded random weights, held to the benchmark's plain
float32 reference (benchmark/blocks/xing4_reference.py, which shares nothing
with the program but the parameter tree):

  (a) latent attention (MLA): materialised prefill, absorbed decode through
      the latent pool and a prefix-cache hit on latent blocks give the
      reference's logits; the Pallas kernel (interpret mode) equals its XLA
      twin
  (b) YaRN's inv_freq and the score scale against values worked by hand
  (c) the sigmoid router: the bias moves the choice and never the weight,
      the weights sum to the scaling factor, the shared expert is added once
  (d) hyper-connections: H_res doubly stochastic, streams copied in and
      summed out (the block against the reference is (a))
  (e) first_k_dense: a dense stack without router leaves, scanned first
  (f) the whole model through ContinuousBatcher(PagedDecodeEngine),
      "gather" against "fused"
  (g) negative controls: a program that drops the rope term of the score,
      the shared expert, or mixes with H_res = I FAILS the cell's
      logit_tolerance comparison
  (h) what a latent pool does not support raises by name
plus the weights a seed gives (held tree = cast tree, leaf for leaf) and the
transfer of a latent sequence between two engines."""

from __future__ import annotations

import dataclasses
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from ray_tpu.models import transformer as tfm
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.transformer import (
    CONFIGS, TransformerConfig, init_paged_kv_cache, init_params,
    make_forward, make_paged_decoder, pack_decode_inputs, pack_prefill_inputs,
    serving_params,
)

BT = 8  # block tokens
CONF = dict(
    name="tiny-xing4", block="xing4", model_type="xing4_0", hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False, vocab_size=256,
    hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
    num_key_value_heads=4, intermediate_size=96, moe_intermediate_size=32,
    first_k_dense_replace=1, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=2,
    scoring_func="sigmoid", topk_method="noaux_tc", n_group=1, topk_group=1,
    moe_layer_freq=1, ep_size=1, num_nextn_predict_layers=0, q_lora_rank=48,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=4, beta_fast=32, beta_slow=1,
                      original_max_position_embeddings=64, mscale=1,
                      mscale_all_dim=1),
    rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
    run=dict(max_seq_len=256),
)
BLOCK = common.load_block(CONF)
REF = BLOCK._reference()
CFG = TransformerConfig(**BLOCK.transformer_kwargs(CONF), dtype=jnp.float32)
# float32 program against a float32 reference: what is left is the order of
# the sums (absorbed against materialised products, online softmax against
# whole rows), a few 1e-6 of logits of size ~3
TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), CFG)


# the cell's rule: the reference follows the router's near-ties (its file's
# margin) at the positions it is asked for
CONF_TIES = dict(CONF, reference=dict(router_tie_margin=0.01))


def _ref(params, seq, positions, conf=CONF):
    return np.asarray(REF.ref_logits(params, seq, conf, positions=positions,
                                     chunk=16, pad_to=32))


def _programs(impl, cfg=CFG):
    return make_paged_decoder(cfg, block_tokens=BT, attention_impl=impl)


def _serve(params, prompt, n_new, impl, ctx=0, cfg=CFG):
    """Logits of the last prompt position and of `n_new - 1` decode steps,
    by the paged programs alone: the prompt's first `ctx` tokens prefilled
    in a call of their own (what a prefix-cache hit leaves in the pool),
    the rest behind them, then greedy decode. -> (logits [n_new, V],
    tokens [n_new])."""
    prefill, decode, _, _ = _programs(impl, cfg)
    nmax = 16
    pool = init_paged_kv_cache(cfg, 1 + 2 * nmax, BT)
    table = np.zeros(nmax, np.int32)
    table[:] = 1 + np.arange(nmax)  # slot 0's blocks; slot 1 stays dead
    key = jax.random.PRNGKey(0)
    if ctx:
        pad = np.zeros((1, 32), np.int32)
        pad[0, :ctx] = prompt[:ctx]
        _, _, pool = prefill(
            params, pool, pack_prefill_inputs(table, pad, ctx, 0), key, 0, 32)
    rest = prompt[ctx:]
    pad = np.zeros((1, 64), np.int32)
    pad[0, :len(rest)] = rest
    tok, logits, pool = prefill(
        params, pool, pack_prefill_inputs(table, pad, len(rest), ctx), key,
        -(-ctx // BT), 64)
    out_logits, toks = [np.asarray(logits[0])], [int(tok[0])]
    tables = np.stack([table, np.zeros(nmax, np.int32)])
    for i in range(n_new - 1):
        pos = len(prompt) + i
        toks_in = np.array([toks[-1], 0], np.int32)
        nxt, logits, pool = decode(
            params, pool, pack_decode_inputs(
                tables, toks_in, np.array([pos, 0], np.int32),
                np.array([table[pos // BT], 0], np.int32),
                np.array([pos % BT, 0], np.int32)), key)
        out_logits.append(np.asarray(logits[0]))
        toks.append(int(nxt[0]))
    return np.stack(out_logits), toks


PROMPT = (np.random.default_rng(1).integers(1, 256, size=43)).astype(np.int32)


# ----------------------------------------------------------- (a) MLA


@pytest.mark.parametrize("impl,ctx", [
    ("gather", 0), ("fused", 0), ("gather", 24), ("fused", 24),
    ("fused", 21),
], ids=["gather-cold", "fused-cold", "gather-hit", "fused-hit",
        "fused-midblock"])
def test_paged_latent_programs_give_the_reference_logits(params, impl, ctx):
    """"gather" prefill is MATERIALISED (K and V expanded from the cached
    rows), every other path ABSORBED; `ctx` tokens already in the pool are
    a prefix-cache hit (block multiple) or an earlier chunk (mid-block)."""
    logits, toks = _serve(params, PROMPT, 5, impl, ctx=ctx)
    seq = np.concatenate([PROMPT, toks[:-1]])
    want = _ref(params, seq, list(range(len(PROMPT) - 1, len(seq))))
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert toks == want.argmax(-1).tolist()


def test_forward_gives_the_reference_logits(params):
    with jax.default_matmul_precision("highest"):
        got = make_forward(CFG)(params, PROMPT[None])[0]
    np.testing.assert_allclose(
        np.asarray(got), _ref(params, PROMPT, None), atol=TOL, rtol=0)


# The latent kernel's grid is the flat list of the call's live (slot, step)
# pairs (ISSUE 43). Each case is one occupancy of a [B, 7] table of 8-token
# blocks: `positions` a slot's first query (None = a released slot: position
# 0 and a table of null entries), `q_len` queries a slot, in tiles of
# `block_q`, `per_step` table entries a grid step.
_MLA_CASES = {
    "decode": dict(positions=[13, 40, None], block_q=16, per_step=3),
    "prefill-tiles": dict(positions=[17], q_len=12, block_q=4, per_step=8),
    "one-block-a-step": dict(positions=[0, 30], q_len=5, block_q=2,
                             per_step=1),
    "all-dead": dict(positions=[None, None, None], per_step=2),
    "dead-between-live": dict(positions=[None, 13, None, None, 40, 5, None],
                              per_step=2),
    # one block beside the whole table (7 blocks = 56 tokens)
    "unequal-lengths": dict(positions=[3, 55, 0, 55, 9], per_step=2),
    # 38 keys = 5 blocks: two steps of 4 entries, the last a quarter full
    "last-step-partly-full": dict(positions=[37, None, 20], per_step=4),
    # 7 entries in steps of 3: the table is padded to 9 with dead entries
    "table-not-a-multiple": dict(positions=[55, 17, None], per_step=3),
    # three tiles of 4 queries behind 17 cached keys, the window capped at
    # 22 keys: kv_len < positions + Q, the last tile's late queries see less
    "prefill-behind-cache-short-kv": dict(
        positions=[17, None], q_len=12, block_q=4, per_step=2, kv_short=7),
    # the verify shape: the window ends strictly before the first query
    "kv-len-before-the-queries": dict(
        positions=[11, None, 27], q_len=3, per_step=2, kv_short=3),
    # blocks reserved ahead of what is visible: live entries the walk skips
    "reserved-ahead": dict(positions=[10, 0, None], per_step=2, reserve=3),
}


@pytest.mark.parametrize("case", list(_MLA_CASES))
def test_mla_kernel_equals_its_xla_twin(case):
    spec = _MLA_CASES[case]
    q_len, block_q = spec.get("q_len", 1), spec.get("block_q", 16)
    pa = importlib.import_module("ray_tpu.ops.paged_attention")
    rng = np.random.default_rng(0)
    n_blocks, width, rank, heads, nmax = 20, 128, 32, 4, 7
    pool = jnp.asarray(rng.normal(size=(2, n_blocks, BT, 1, width)), jnp.float32)
    dead = np.array([p is None for p in spec["positions"]])
    positions = np.array([p or 0 for p in spec["positions"]], np.int32)
    batch = len(positions)
    tables = np.zeros((batch, nmax), np.int32)
    for b, pos in enumerate(positions):
        if not dead[b]:
            need = min(-(-(pos + q_len) // BT) + spec.get("reserve", 0), nmax)
            tables[b, :need] = rng.permutation(np.arange(1, n_blocks))[:need]
    kv_len = np.where(dead, 0, positions + q_len - spec.get("kv_short", 0))
    dead |= kv_len == 0
    q = jnp.asarray(rng.normal(size=(batch, q_len, heads, width)), jnp.float32)
    kw = dict(layer=1, rank=rank, scale=0.2, kv_len=jnp.asarray(kv_len))
    got = pa.mla_paged_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(positions), impl="kernel",
        interpret=True, block_q=block_q, blocks_per_step=spec["per_step"],
        **kw)
    want = pa.mla_paged_attention(
        q, pool, jnp.asarray(tables), jnp.asarray(positions), impl="xla", **kw)
    assert got.shape == (batch, q_len, heads, rank)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # a slot with no visible key is never visited: its rows are exactly zero
    assert not np.asarray(got)[dead].any()
    assert np.asarray(got)[~dead].any() or dead.all()


def test_live_steps_lists_the_pairs_that_hold_a_visible_key():
    """The work list by hand: bt 8, 2 entries a step, a [4, 6] table."""
    pa = importlib.import_module("ray_tpu.ops.paged_attention")
    dead = [-1] * 6
    ptable = jnp.asarray([
        [5, 6, 7, -1, -1, -1],   # 21 keys seen = 3 blocks = 2 steps
        dead,                    # released: no step
        [1, 2, 3, 4, 8, 9],      # kv_len caps the walk at 10 keys = 1 step
        [11, -1, -1, -1, -1, -1],  # one key = 1 step
    ], jnp.int32)
    positions = jnp.asarray([20, 0, 30, 0], jnp.int32)
    kv_len = jnp.asarray([21, 0, 10, 1], jnp.int32)
    slot, j, blocks, n, keys = pa._live_steps(
        ptable, positions, kv_len, 1, 8, 2)
    assert slot.shape == j.shape == (4 * 3,) and slot.dtype == jnp.int32
    assert int(n) == 2 + 0 + 1 + 1  # sum of ceil(visible blocks / 2)
    assert np.asarray(slot)[:4].tolist() == [0, 0, 2, 3]  # slot order
    assert np.asarray(j)[:4].tolist() == [0, 1, 0, 0]
    # pair i's two pool blocks sit at 2 * i; a dead entry is block 0
    assert np.asarray(blocks)[:8].tolist() == [5, 6, 7, 0, 1, 2, 11, 0]
    assert np.asarray(keys).tolist() == [21, 0, 10, 1]
    # a slot's last step is where (j + 1) * 16 reaches its keys
    last = (np.asarray(j)[:4] + 1) * 16 >= np.asarray(keys)[np.asarray(slot)[:4]]
    assert last.tolist() == [False, True, True, True]
    # 12 queries a slot: a walk ends with the last query's block (slot 2:
    # 21 keys = 3 blocks) and with the slot's last live entry (slots 0, 3)
    *_, n, keys = pa._live_steps(ptable, positions, kv_len + 11, 12, 8, 2)
    assert int(n) == 2 + 0 + 2 + 1
    assert np.asarray(keys).tolist() == [24, 0, 21, 8]
    # nothing live: pair 0 is the LAST slot's step 0, and it holds no key
    slot, j, blocks, n, keys = pa._live_steps(
        jnp.asarray([dead] * 4, jnp.int32), positions, kv_len, 1, 8, 2)
    assert int(n) == 0 and int(slot[0]) == 3 and int(j[0]) == 0
    assert not np.asarray(keys).any() and not np.asarray(blocks).any()


def test_one_compiled_program_serves_every_live_set():
    """The list's length is a traced grid bound: three occupancies of one
    (B, Nmax), nothing live among them, run ONE compiled program."""
    pa = importlib.import_module("ray_tpu.ops.paged_attention")
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(1, 12, BT, 1, 128)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, 1, 2, 128)), jnp.float32)

    @jax.jit
    def both(tables, positions):
        kw = dict(layer=0, rank=32, scale=0.3, blocks_per_step=2)
        return tuple(pa.mla_paged_attention(
            q, pool, tables, positions, impl=impl, **kw)
            for impl in ("kernel", "xla"))

    for lens in ([9, 0, 30], [0, 0, 0], [1, 40, 17]):
        tables = np.zeros((3, 5), np.int32)
        for b, n in enumerate(lens):
            tables[b, :-(-n // BT)] = 1 + rng.permutation(11)[:-(-n // BT)]
        got, want = both(jnp.asarray(tables),
                         jnp.asarray(np.maximum(np.array(lens) - 1, 0)))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
        assert not np.asarray(got)[np.array(lens) == 0].any()
    assert both._cache_size() == 1


def test_latent_pool_is_one_leaf_of_padded_rows():
    pool = init_paged_kv_cache(CFG, 5, BT)
    assert set(pool) == {"kv"}
    assert pool["kv"].shape == (3, 5, BT, 1, 128)  # 32 + 8 -> 128 lanes
    assert CFG.latent_width == 40 and CFG.latent_row == 128
    real = dataclasses.replace(CFG, kv_lora_rank=512, qk_rope_head_dim=64)
    assert (real.latent_width, real.latent_row) == (576, 640)
    assert tfm.paged_kv_block_bytes(CFG, BT) == 3 * BT * 128 * 4
    # per-head pools count as they always have
    tiny = CONFIGS["tiny"]
    assert tfm.paged_kv_block_bytes(tiny, BT) == (
        2 * tiny.n_layers * BT * tiny.n_kv_heads * tiny.d_head * 2)
    assert tfm.paged_kv_block_bytes(tiny, BT, jnp.int8) == (
        2 * tiny.n_layers * (BT * tiny.n_kv_heads * tiny.d_head
                             + tiny.n_kv_heads * 4))


# ---------------------------------------------------------- (b) YaRN


def test_yarn_inv_freq_and_scale_by_hand():
    """The published row: rope dim 64, theta 1e4, factor 64 over 4,096
    positions, beta_fast 32, beta_slow 1. The correction dims are
    64 ln(4096 / (32 . 2 pi)) / (2 ln 1e4) = 10.47 -> 10 and
    64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23: pairs 0..10 keep
    theta^(-2i/64), pairs 23.. take it over 64, pair 16 is 6/13 of the way."""
    inv = np.asarray(tfm.yarn_inv_freq(64, 1e4, 64.0, 4096, 32.0, 1.0))
    plain = 1e4 ** -(np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-6)
    ramp = 6 / 13
    np.testing.assert_allclose(
        inv[16], plain[16] / 64 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    real = dataclasses.replace(
        CFG, qk_nope_head_dim=128, qk_rope_head_dim=64, rope_factor=64.0,
        rope_mscale_all_dim=1.0)
    assert tfm.attention_scale(real) == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert tfm.attention_scale(real) == pytest.approx(0.14468, rel=1e-4)
    # mscale / mscale_all_dim = 1: cos and sin are not scaled
    cos, sin = tfm._rope_tables(CFG)
    assert cos.shape == (256, 4) and float(cos[0, 0]) == 1.0
    np.testing.assert_allclose(np.asarray(REF.yarn_inv_freq(CONF)), np.asarray(
        tfm.yarn_inv_freq(8, 1e4, 4.0, 64, 32.0, 1.0)), rtol=1e-6)
    # an unscaled model keeps the tables it always had
    plain_cos, _ = tfm._rope_tables(CONFIGS["tiny"])
    want, _ = tfm.rope_frequencies(16, 128, 10000.0)
    assert np.array_equal(np.asarray(plain_cos), np.asarray(want))


# -------------------------------------------------------- (c) router


def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"])


def test_router_bias_moves_the_choice_never_the_weight(params):
    lp = _layer(params)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    w, idx = tfm._moe_route(x, lp, CFG)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.0, rtol=1e-5)
    scores = jax.nn.sigmoid(x @ lp["router"])
    # a bias that lifts expert 5 above everything: always chosen, and its
    # weight is still its own unbiased score over the chosen pair's sum
    lifted = dict(lp, router_bias=lp["router_bias"].at[5].add(10.0))
    w2, idx2 = tfm._moe_route(x, lifted, CFG)
    assert (np.asarray(idx2) == 5).any(axis=-1).all()
    assert not (np.asarray(idx) == 5).any(axis=-1).all()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(idx2), -1)
    np.testing.assert_allclose(
        np.asarray(w2), 2.0 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
    # without renormalisation the weights are the scores, scaled
    raw = dataclasses.replace(CFG, moe_renormalize=False)
    w3, idx3 = tfm._moe_route(x, lp, raw)
    assert np.array_equal(np.asarray(idx3), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(w3), 2.0 * np.take_along_axis(
        np.asarray(scores), np.asarray(idx), -1), rtol=1e-5)


def test_shared_expert_is_added_once_unweighted(params):
    lp = _layer(params)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 64), jnp.float32)
    out, _ = tfm._moe(h, lp, CFG, lambda x, *a: x)
    routed, _ = tfm._moe(h, lp, dataclasses.replace(CFG, n_shared_experts=0),
                         lambda x, *a: x)
    x = h[0]
    shared = (jax.nn.silu(x @ lp["ws_gate"]) * (x @ lp["ws_up"])) @ lp["ws_down"]
    np.testing.assert_allclose(
        np.asarray(out[0] - routed[0]), np.asarray(shared), atol=1e-5)
    # and the dropless path equals the every-expert oracle
    dense, _ = tfm._moe(h, lp, dataclasses.replace(CFG, moe_impl="dense"),
                        lambda x, *a: x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense), atol=1e-5)


# ------------------------------------------------- (d) hyper-connections


def test_h_res_is_doubly_stochastic_and_maps_match_the_reference(params):
    lp = jax.tree.map(lambda a: a[0], params["dense_layers"])
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (2, 7, 4, 64), jnp.float32)
    h_pre, h_post, h_res = tfm.hc_maps(x, lp, "attn", CFG)
    np.testing.assert_allclose(np.asarray(h_res.sum(-1)), 1.0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_res.sum(-2)), 1.0, atol=1e-4)
    assert float(h_res.min()) > 0 and 0 < float(h_pre.min()) < float(h_pre.max()) < 1
    assert 0 < float(h_post.min()) and float(h_post.max()) < 2
    # not the identity: the seeded gains and biases are not degenerate
    assert float(jnp.abs(h_res - jnp.eye(4)).max()) > 0.3
    want = REF.hc_maps(x[0], lp["hc_attn_phi"], lp["hc_attn_alpha"],
                       lp["hc_attn_bias"], dict(REF._settings(CONF)))
    for got, ref in zip((h_pre[0], h_post[0], h_res[0]), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    # a logit past the clamp is clipped before the exp: finite, still stochastic
    big = dict(lp, hc_attn_bias=lp["hc_attn_bias"].at[8].set(1e4))
    _, _, clamped = tfm.hc_maps(x, big, "attn", CFG)
    assert np.isfinite(np.asarray(clamped)).all()


def test_streams_are_copied_in_and_summed_out():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64), jnp.float32)
    streams = tfm._hc_expand(x, CFG)
    assert streams.shape == (2, 3, 4, 64)
    assert all(np.array_equal(np.asarray(streams[:, :, i]), np.asarray(x))
               for i in range(4))
    np.testing.assert_allclose(
        np.asarray(tfm._hc_collapse(streams, CFG)), 4 * np.asarray(x), rtol=1e-6)
    plain = CONFIGS["tiny"]
    assert tfm._hc_expand(x, plain) is x and tfm._hc_collapse(x, plain) is x
    y = jnp.ones_like(x)
    assert np.array_equal(np.asarray(tfm._residual(x, y)), np.asarray(x + y))


# ------------------------------------------------- (e) first_k_dense


def test_dense_prefix_is_a_stack_of_its_own(params):
    dense, experts = params["dense_layers"], params["layers"]
    assert "router" not in dense and "router_bias" not in dense
    assert not any(k.startswith("ws_") for k in dense)
    assert dense["w_gate"].shape == (1, 64, 96)      # intermediate_size
    assert experts["w_gate"].shape == (2, 8, 64, 32)  # experts x moe width
    assert experts["router"].shape == (2, 64, 8)
    assert CFG.n_expert_layers == 2
    specs = tfm.param_specs(CFG)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
    ids = tfm._layer_ids(CFG)
    assert ids["dense_layers"].tolist() == [0]
    assert ids["layers"].tolist() == [1, 2]
    with pytest.raises(ValueError, match="first_k_dense"):
        TransformerConfig(n_layers=2, first_k_dense=1)


def test_dense_prefix_works_before_per_head_attention_too():
    """`first_k_dense` is not tied to latent attention: a per-head model
    with one dense layer before its expert layers serves, through the pool
    indexed by ONE running layer id, what its forward gives."""
    cfg = dataclasses.replace(
        CONFIGS["tiny_moe"], n_layers=3, first_k_dense=1, d_ff_dense=96,
        moe_capacity_factor=None, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(1), cfg)
    assert params["dense_layers"]["wq"].shape == (1, 64, 4, 16)
    assert params["layers"]["wq"].shape == (2, 64, 4, 16)
    eng = PagedDecodeEngine(cfg, params, max_batch_size=2, block_tokens=BT,
                            attention_impl="fused")
    prompt = np.arange(3, 30).tolist()
    out = _generate(eng, [prompt], max_new=4)[0]
    logits = make_forward(cfg)(params, np.asarray(prompt + out[:-1])[None])[0]
    assert out == np.asarray(logits[len(prompt) - 1:].argmax(-1)).tolist()


@pytest.mark.parametrize("name", ["tiny", "tiny_moe", "latent"])
def test_held_tree_is_the_cast_tree_leaf_for_leaf(name):
    """`init_params(held=True)` (each leaf cast as it is drawn) is, bit for
    bit, `serving_params` of the float32 tree; and the float32 tree of the
    configurations that were there is the one their seed has always given
    (a draw of its first matrix, with the key it has always had)."""
    cfg = (dataclasses.replace(CFG, dtype=jnp.bfloat16) if name == "latent"
           else CONFIGS[name])
    key = jax.random.PRNGKey(11)
    full = init_params(key, cfg)
    held = init_params(key, cfg, held=True)
    cast = serving_params(cfg, full)
    assert jax.tree.structure(held) == jax.tree.structure(cast)
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(cast)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert held["embed"].dtype == jnp.bfloat16
    assert held["layers"]["attn_norm"].dtype == jnp.float32
    if name != "latent":
        first = jax.random.split(key, 16)[0]
        shape = full["layers"]["wq"].shape
        want = jax.random.normal(first, shape, jnp.float32) / math.sqrt(shape[1])
        assert np.array_equal(np.asarray(full["layers"]["wq"]), np.asarray(want))


# ------------------------------------------------- (f) the whole model


def _generate(eng, prompts, max_new=6):
    from ray_tpu.serve.batching import ContinuousBatcher

    b = ContinuousBatcher(eng, max_batch_size=eng.max_batch_size,
                          batch_wait_timeout_s=0.0)
    try:
        streams = [b.submit(tokens=p, max_new_tokens=max_new) for p in prompts]
        return [[int(t) for t in s] for s in streams]
    finally:
        b.close()


def test_whole_model_through_the_batcher_gather_against_fused(params):
    rng = np.random.default_rng(5)
    doc = rng.integers(1, 256, size=40).tolist()
    prompts = [doc + rng.integers(1, 256, size=n).tolist() for n in (5, 11, 3)]
    outs = {}
    for impl in ("gather", "fused"):
        eng = PagedDecodeEngine(
            CFG, params, max_batch_size=2, block_tokens=BT,
            attention_impl=impl, prefill_chunk_tokens=16)
        outs[impl] = _generate(eng, prompts)
        stats = eng.stats()
        assert stats["prefix_hits"] >= 1 and stats["chunked_prefills"] >= 1
        assert stats["kv_bytes_per_token"] == 3 * 128 * 4
        assert stats["kv_pool_bytes"] == sum(
            a.nbytes for a in jax.tree.leaves(eng.pool))
        # 3 requests x 5 decode steps, over the 2 EXPERT layers (of 3 layers)
        assert stats["moe_pairs"] == 15 * CFG.top_k * 2
    assert outs["gather"] == outs["fused"]
    for p, out in zip(prompts, outs["fused"]):
        seq = np.asarray(p + out[:-1])
        want = _ref(params, seq, list(range(len(p) - 1, len(seq))))
        assert out == want.argmax(-1).tolist()


# ------------------------------------------------ (g) negative controls


def _near_argmax(params, prompt, out, tolerance=0.0625, conf=CONF_TIES):
    """benchmark/server.py reference_check's rule: at every generated
    position the reference logit of the SERVED token lies within
    tolerance x |largest| of the largest."""
    seq = np.asarray(list(prompt) + out[:-1])
    logits = _ref(params, seq, list(range(len(prompt) - 1, len(seq))), conf)
    top = logits.max(-1)
    served = logits[np.arange(len(out)), np.asarray(out)]
    return bool(np.all(top - served <= tolerance * np.abs(top)))


def _break_rope_term(monkeypatch, params):
    real = tfm._qkv_latent

    def no_rope(x, lp, cfg, cos, sin, positions=None):
        q, latent, wkv_b = real(x, lp, cfg, cos, sin, positions)
        return q.at[..., cfg.qk_nope_head_dim:].set(0.0), latent, wkv_b

    monkeypatch.setattr(tfm, "_qkv_latent", no_rope)
    return params


def _break_shared_expert(monkeypatch, params):
    layers = dict(params["layers"])
    layers["ws_down"] = jnp.zeros_like(layers["ws_down"])
    return {**params, "layers": layers}


def _break_routed_experts(monkeypatch, params):
    layers = dict(params["layers"])
    layers["w_down"] = jnp.zeros_like(layers["w_down"])
    return {**params, "layers": layers}


def _break_routing_weight(monkeypatch, params):
    """The chosen experts weighted 1.0 in all, not routed_scaling_factor."""
    real = tfm._moe_route

    def unscaled(x, lp, cfg):
        w, idx = real(x, lp, cfg)
        return w / cfg.moe_route_scale, idx

    monkeypatch.setattr(tfm, "_moe_route", unscaled)
    return params


def _break_h_res(monkeypatch, params):
    monkeypatch.setattr(
        tfm, "_sinkhorn",
        lambda logits, iters, eps: jnp.broadcast_to(
            jnp.eye(logits.shape[-1]), logits.shape))
    return params


@pytest.mark.parametrize("fault", [
    None, _break_rope_term, _break_shared_expert, _break_h_res,
    _break_routed_experts, _break_routing_weight,
], ids=["sound", "no-rope-term", "no-shared-expert", "h-res-identity",
        "no-routed-experts", "routing-weight-unscaled"])
def test_a_dropped_mechanism_fails_the_cells_comparison(monkeypatch, params,
                                                        fault):
    """Under the cell's own rule — tolerance 2^-4, the reference following
    the router's near-ties — at the plain 1/sqrt(fan_in) draw."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 256, size=n).tolist() for n in (37, 52, 45)]
    served_tree = fault(monkeypatch, params) if fault else params
    eng = PagedDecodeEngine(CFG, served_tree, max_batch_size=2,
                            block_tokens=BT, attention_impl="fused")
    outs = _generate(eng, prompts, max_new=8)
    ok = all(_near_argmax(params, p, o) for p, o in zip(prompts, outs))
    assert ok == (fault is None)


def _noisy_router(monkeypatch, eps):
    """A replica whose router sees its scores through arithmetic of its
    own: every score moved by up to `eps` (a fixed function of the token),
    for the CHOICE only. Near-ties closer than 2 eps fall either way."""
    real = tfm._moe_route

    def noisy(x, lp, cfg):
        wobble = eps * jnp.sin(997.0 * (x.astype(jnp.float32) @ lp["router"]))
        return real(x, {**lp, "router_bias": lp["router_bias"] + wobble}, cfg)

    monkeypatch.setattr(tfm, "_moe_route", noisy)


@pytest.mark.parametrize("eps,margin,passes", [
    (0.02, 0.0, False),   # the plain rows: a flipped near-tie is a miss
    (0.02, 0.05, True),   # the margin admits both sides of the tie
    (0.05, 0.05, False),  # noise past the margin is a fault again
], ids=["no-margin", "within-margin", "past-margin"])
def test_reference_follows_router_near_ties(monkeypatch, params, eps, margin,
                                            passes):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, size=n).tolist()
               for n in (37, 52, 45, 41, 33, 48)]
    _noisy_router(monkeypatch, eps)
    eng = PagedDecodeEngine(CFG, params, max_batch_size=2, block_tokens=BT,
                            attention_impl="fused")
    outs = _generate(eng, prompts, max_new=8)
    conf = dict(CONF, reference=dict(router_tie_margin=margin))
    ok = all(_near_argmax(params, p, o, conf=conf)
             for p, o in zip(prompts, outs))
    assert ok == passes


def test_tie_choices_by_hand():
    s = np.array([0.90, 0.50, 0.80, 0.795, 0.10, 0.78, 0.60])
    # top-3 = {0, 2, 3}; the cut lies between 0.795 and 0.78
    assert REF.tie_choices(s, 3, 0.0) == [(0.0, [0, 2, 3])]
    got = REF.tie_choices(s, 3, 0.016)   # 3 and 5 tie; 2 is 0.02 above 0.78
    assert [sorted(e) for _, e in got] == [[0, 2, 3], [0, 2, 5]]
    assert got[0][0] == 0.0 and got[1][0] == pytest.approx(0.015)
    got = REF.tie_choices(s, 3, 0.021)   # now 2 is open too: 2 of {2, 3, 5}
    assert [sorted(e) for _, e in got] == [[0, 2, 3], [0, 2, 5], [0, 3, 5]]
    assert [c for c, _ in got] == pytest.approx([0.0, 0.015, 0.02])


def test_branch_zero_is_the_plain_reference_and_the_fold_keeps_its_top(params):
    seq = np.asarray(PROMPT)
    at = [len(seq) - 3, len(seq) - 1]
    plain = _ref(params, seq, at)
    conf = dict(CONF, reference=dict(router_tie_margin=0.05))
    branches = REF.ref_branch_logits(params, seq, conf, at, chunk=16, pad_to=32)
    folded = _ref(params, seq, at, conf)
    assert max(len(b["cost"]) for b in branches) > 1  # some tie within 0.05
    for row, b, f in zip(plain, branches, folded):
        assert b["cost"][0] == 0.0 and all(c > 0 for c in b["cost"][1:])
        np.testing.assert_allclose(b["logits"][0], row, atol=TOL)
        assert f.max() == pytest.approx(row.max(), abs=TOL)
        assert np.all(f >= row - TOL)  # a token is never further from the top
        # every other branch is another model's answer, not a copy
        assert all(np.abs(l - row).max() > 100 * TOL for l in b["logits"][1:])


# ------------------------------------------- (h) refused by name


@pytest.mark.parametrize("kw,word", [
    (dict(kv_cache_dtype="int8"), "kv_dtype"),
    (dict(speculative_k=2, drafter="ngram"), "speculative_k"),
    (dict(mesh=object(), rules=object()), "mesh"),
], ids=["int8", "speculative", "sharded"])
def test_latent_pool_refuses_by_name_at_construction(params, kw, word):
    with pytest.raises(NotImplementedError, match=f"latent.*{word}"):
        PagedDecodeEngine(CFG, params, max_batch_size=2, block_tokens=BT, **kw)


def test_latent_programs_refuse_by_name_too():
    with pytest.raises(NotImplementedError, match="kv_dtype"):
        make_paged_decoder(CFG, block_tokens=BT, kv_dtype=jnp.int8)
    with pytest.raises(NotImplementedError, match="mesh"):
        init_paged_kv_cache(CFG, 4, BT, mesh=object(), rules=object())
    _, _, verify, _ = _programs("fused")
    z = np.zeros((2, 3), np.int32)
    with pytest.raises(NotImplementedError, match="speculative_k"):
        verify(init_params(jax.random.PRNGKey(0), CFG),
               init_paged_kv_cache(CFG, 4, BT), np.zeros((2, 4), np.int32), z,
               z[:, 0], z[:, 0], z, z, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="dense"):
        make_forward(dataclasses.replace(CFG, attention="flash"))
    # a per-head pool is refused nothing
    tfm.refuse_on_latent_pool(CONFIGS["tiny"], kv_dtype=jnp.int8, mesh=object(),
                              speculative_k=4)


# ------------------------------------------------ transfer between engines


def test_latent_sequence_round_trips_between_two_engines(params):
    rng = np.random.default_rng(2)
    doc = rng.integers(1, 256, size=5 * BT).tolist()
    kw = dict(max_batch_size=2, block_tokens=BT, attention_impl="fused",
              model_id="m")
    a = PagedDecodeEngine(CFG, params, **kw)
    b = PagedDecodeEngine(CFG, params, **kw)
    per_head = PagedDecodeEngine(
        dataclasses.replace(CONFIGS["tiny"], max_seq_len=128), seed=0, **kw)
    assert a.transfer_sig == b.transfer_sig != per_head.transfer_sig
    first = _generate(a, [doc + [7, 8, 9]])[0]
    payload = a.export_prefix(doc + [7, 8, 9])
    assert set(payload["blocks"]) == {"kv"}
    assert payload["blocks"]["kv"].shape == (3, 5, BT, 1, 128)
    # over the wire (serve/kv_transfer.py): one buffer, leaves by name
    from ray_tpu.serve.kv_transfer import pack_payload, unpack_payload

    meta, buf = pack_payload(payload)
    assert [leaf["name"] for leaf in meta["leaves"]] == ["kv"]
    payload = unpack_payload(meta, buf)
    assert per_head.import_prefix(payload) == 0       # refused: another key space
    assert per_head.stats()["kv_import_rejects"] == 1
    assert b.import_prefix(payload) == 5 * BT
    assert _generate(b, [doc + [7, 8, 9]])[0] == first
    assert b.stats()["prefix_hits"] == 1
    assert b.stats()["prefix_tokens_reused"] == 5 * BT
