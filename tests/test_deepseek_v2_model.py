"""The DeepSeek-V2 layer behind the paged engine AS ONE CHIP'S SHARE of an
expert-parallel deployment, at a small size on the CPU with seeded random
weights, held to the benchmark's plain float32 reference
(benchmark/blocks/deepseek_v2_reference.py, which shares nothing with the
program but the parameter tree): d 64, 8 heads, 16 routed experts in 4
groups of which 2 are kept, top-3, 4 experts held (rank 1 of 4: experts
4-7), a vocabulary of 128.

  (a) prefill + decode through the latent pool under a PLAIN residual give
      the reference's logits ("gather" materialised, "fused" absorbed,
      cold and behind a prefix-cache hit); the trainer's forward too
  (b) the group-limited router against the reference's, ties excluded by
      construction; one group is today's softmax branch bit for bit; the
      softmax branch applies `moe_route_scale`
  (c) the share adds up: the four shares' routed parts plus the shared
      experts counted ONCE are the uncut layer, in the reference and
      through `_moe` with the held-range fields
  (d) pairs on absent experts get no group: `_moe_dropless`' sizes, the
      expert-load counts, a poisoned row behind the groups' sum
  (e) the whole model through ContinuousBatcher(PagedDecodeEngine): the
      engine's counts of the share
  (f) negative controls under the cell's rule, and float32 where the file
      says float32: a bfloat16 router fails the float32 comparison
  (g) the reference follows near-ties at both cuts (groups, experts)
  (h) what the configuration refuses by name."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from ray_tpu.models import transformer as tfm
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.transformer import (
    TransformerConfig, init_paged_kv_cache, init_params, make_forward,
    make_paged_decoder, pack_decode_inputs, pack_prefill_inputs,
    serving_params,
)

BT = 8  # block tokens
WHOLE = dict(
    name="tiny-deepseek-v2", block="deepseek_v2", model_type="deepseek_v2",
    hidden_act="silu", attention_bias=False, tie_word_embeddings=False,
    vocab_size=128, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=8, num_key_value_heads=8, intermediate_size=96,
    moe_intermediate_size=32, first_k_dense_replace=1, n_routed_experts=16,
    n_shared_experts=2, num_experts_per_tok=3, norm_topk_prob=False,
    routed_scaling_factor=16, scoring_func="softmax",
    topk_method="group_limited_greedy", n_group=4, topk_group=2,
    moe_layer_freq=1, seq_aux=True, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=4, beta_fast=32, beta_slow=1,
                      original_max_position_embeddings=64, mscale=0.707,
                      mscale_all_dim=0.707),
    rms_norm_eps=1e-6, max_position_embeddings=1024,
    run=dict(max_seq_len=256),
)


def share_conf(rank: int, **more) -> dict:
    """The file of chip `rank` of 4: 4 of the 16 experts held."""
    return dict(
        WHOLE, n_routed_experts=4, reduced=["n_routed_experts"],
        published=dict(n_routed_experts=16),
        stands_for=dict(expert_parallel=4, expert_rank=rank), **more)


CONF = share_conf(1)
BLOCK = common.load_block(CONF)
REF = BLOCK._reference()
CFG = TransformerConfig(**BLOCK.transformer_kwargs(CONF), dtype=jnp.float32)
CFG_WHOLE = TransformerConfig(
    **BLOCK.transformer_kwargs(WHOLE), dtype=jnp.float32)
# float32 program against a float32 reference: what is left is the order of
# the sums (absorbed against materialised products, online softmax against
# whole rows, the grouped matmul against one expert at a time), a few 1e-6
# of logits of size ~3
TOL = 2e-4
# the cell's rule: near the argmax within 2^-4 of the largest logit, the
# reference following the router's near-ties (its file's margin)
CONF_TIES = dict(CONF, reference=dict(router_tie_margin=0.02))


def share_of(params, rank: int, held: int = 4):
    """A whole tree's layers cut to one chip's experts."""
    layers = dict(params["layers"])
    for k in ("w_gate", "w_up", "w_down"):
        layers[k] = layers[k][:, rank * held:(rank + 1) * held]
    return {**params, "layers": layers}


@pytest.fixture(scope="module")
def whole():
    return init_params(jax.random.PRNGKey(3), CFG_WHOLE)


@pytest.fixture(scope="module")
def params(whole):
    return share_of(whole, 1)


def _ref(params, seq, positions, conf=CONF, **kw):
    return np.asarray(REF.ref_logits(params, seq, conf, positions=positions,
                                     chunk=16, pad_to=32, **kw))


def _serve(params, prompt, n_new, impl, ctx=0, cfg=CFG):
    """Logits of the last prompt position and of `n_new - 1` decode steps,
    by the paged programs alone (tests/test_latent_model.py: _serve)."""
    prefill, decode, _, _ = make_paged_decoder(
        cfg, block_tokens=BT, attention_impl=impl)
    nmax = 16
    pool = init_paged_kv_cache(cfg, 1 + 2 * nmax, BT)
    table = (1 + np.arange(nmax)).astype(np.int32)
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
        nxt, logits, pool = decode(
            params, pool, pack_decode_inputs(
                tables, np.array([toks[-1], 0], np.int32),
                np.array([pos, 0], np.int32),
                np.array([table[pos // BT], 0], np.int32),
                np.array([pos % BT, 0], np.int32)), key)
        out_logits.append(np.asarray(logits[0]))
        toks.append(int(nxt[0]))
    return np.stack(out_logits), toks


PROMPT = (np.random.default_rng(1).integers(1, 128, size=43)).astype(np.int32)


# ------------------------------------------- (a) the programs, the share


@pytest.mark.parametrize("impl,ctx", [
    ("gather", 0), ("fused", 0), ("gather", 24), ("fused", 24),
], ids=["gather-cold", "fused-cold", "gather-hit", "fused-hit"])
def test_paged_programs_give_the_share_references_logits(params, impl, ctx):
    logits, toks = _serve(params, PROMPT, 5, impl, ctx=ctx)
    seq = np.concatenate([PROMPT, toks[:-1]])
    want = _ref(params, seq, list(range(len(PROMPT) - 1, len(seq))))
    np.testing.assert_allclose(logits, want, atol=TOL, rtol=0)
    assert toks == want.argmax(-1).tolist()


def test_forward_gives_the_reference_logits_share_and_whole(params, whole):
    for cfg, conf, tree in ((CFG, CONF, params), (CFG_WHOLE, WHOLE, whole)):
        with jax.default_matmul_precision("highest"):
            got = make_forward(cfg)(tree, PROMPT[None])[0]
        np.testing.assert_allclose(
            np.asarray(got), _ref(tree, PROMPT, None, conf), atol=TOL, rtol=0)


def test_a_share_is_not_the_whole_model(params, whole):
    """What experts 0-3 and 8-15 would have added is LEFT OUT: the share's
    logits are another model's than the uncut one's."""
    at = [len(PROMPT) - 1]
    assert np.abs(_ref(params, PROMPT, at) - _ref(whole, PROMPT, at, WHOLE)
                  ).max() > 100 * TOL


def test_the_tree_holds_the_share_under_the_whole_router(params):
    drawn = init_params(jax.random.PRNGKey(3), CFG)
    assert drawn["layers"]["router"].shape == (2, 64, 16)
    assert drawn["layers"]["w_gate"].shape == (2, 4, 64, 32)
    assert drawn["layers"]["w_down"].shape == (2, 4, 32, 64)
    assert drawn["layers"]["ws_gate"].shape == (2, 64, 64)  # 2 x 32 wide
    assert "router_bias" not in drawn["layers"]
    assert drawn["dense_layers"]["w_gate"].shape == (1, 64, 96)
    assert drawn["unembed"].shape == (64, 128)
    specs = tfm.param_specs(CFG)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, drawn)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, specs, is_leaf=lambda x: isinstance(x, tuple)))
    assert (CFG.router_width, CFG.n_experts, CFG.expert_offset) == (16, 4, 4)
    assert CFG.expert_share and not CFG_WHOLE.expert_share
    bf16 = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    held = init_params(jax.random.PRNGKey(3), bf16, held=True)
    cast = serving_params(bf16, init_params(jax.random.PRNGKey(3), bf16))
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(cast)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_yarn_scale_of_the_published_row_by_hand():
    """factor 40, mscale = mscale_all_dim = 0.707: the tables' factor is 1
    and s = 192^-1/2 (0.1 x 0.707 x ln 40 + 1)^2 = 0.07217 x 1.5896."""
    real = dataclasses.replace(
        CFG, qk_nope_head_dim=128, qk_rope_head_dim=64, rope_factor=40.0,
        rope_mscale=0.707, rope_mscale_all_dim=0.707)
    assert tfm.attention_scale(real) == pytest.approx(0.11472, rel=1e-4)
    cos, _ = tfm._rope_tables(CFG)
    assert float(cos[0, 0]) == 1.0
    assert REF.score_scale(CONF) == pytest.approx(tfm.attention_scale(CFG))


# --------------------------------------------------------- (b) the router


def _distinct_logits(routed: int, tokens: int = 64):
    """x = I and a router whose columns are shuffles of one evenly spaced
    ladder: token n's logits are row n of the router, no two within
    4 / (routed - 1) of each other — no cut can tie."""
    rng = np.random.default_rng(7)
    ladder = np.linspace(-2.0, 2.0, routed)
    router = np.stack([rng.permutation(ladder) for _ in range(tokens)])
    return jnp.eye(tokens, dtype=jnp.float32), jnp.asarray(router, jnp.float32)


@pytest.mark.parametrize("groups,kept,k", [
    (1, 1, 3), (4, 2, 3), (4, 1, 3), (4, 4, 3), (2, 1, 6), (8, 3, 2),
], ids=["one-group", "4-keep-2", "4-keep-1", "4-keep-4", "2-keep-1", "8-keep-3"])
def test_group_limited_router_against_the_references(groups, kept, k):
    x, router = _distinct_logits(16)
    cfg = dataclasses.replace(
        CFG_WHOLE, moe_n_group=groups, moe_topk_group=kept, top_k=k)
    w, idx = tfm._moe_route(x, {"router": router}, cfg)
    st = dict(n_group=groups, topk_group=kept, top_k=k, renorm=False,
              route_scale=16.0)
    w_ref, idx_ref = REF.route(x, {"router": router}, st)
    assert np.array_equal(np.sort(np.asarray(idx)), np.sort(np.asarray(idx_ref)))
    np.testing.assert_allclose(
        np.sort(np.asarray(w)), np.sort(np.asarray(w_ref)), rtol=1e-6)
    # by hand: every chosen expert lies in one of the `kept` best groups
    logits = np.asarray(router)
    best = logits.reshape(64, groups, -1).max(-1)
    allowed = np.argsort(-best, axis=1)[:, :kept]
    assert all(set(np.asarray(idx)[n] // (16 // groups)) <= set(allowed[n])
               for n in range(64))
    if groups == 1:
        # today's softmax branch, bit for bit (the scale is the one new step)
        probs = jax.nn.softmax(x @ router, axis=-1)
        w0, idx0 = jax.lax.top_k(probs, k)
        assert np.array_equal(np.asarray(idx), np.asarray(idx0))
        assert np.array_equal(np.asarray(w), np.asarray(w0 * 16.0))
        plain = dataclasses.replace(cfg, moe_route_scale=1.0)
        assert np.array_equal(
            np.asarray(tfm._moe_route(x, {"router": router}, plain)[0]),
            np.asarray(w0))


def test_softmax_weights_are_scaled_and_not_renormalised(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 64), jnp.float32)
    w, idx = tfm._moe_route(x, lp, CFG)
    probs = jax.nn.softmax(x @ lp["router"], axis=-1)
    np.testing.assert_allclose(np.asarray(w), 16.0 * np.take_along_axis(
        np.asarray(probs), np.asarray(idx), -1), rtol=1e-6)
    assert np.asarray(idx).max() > 7  # chosen over all 16, held here or not


# ------------------------------------------------- (c) the share adds up


def test_the_four_shares_add_up_to_the_uncut_layer(whole):
    """Routed parts of experts 0-3, 4-7, 8-11, 12-15 + the shared experts
    ONCE = the uncut layer: in the reference (ranges of one tree) and
    through `_moe` with the held-range fields (each chip's own tree)."""
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64), jnp.float32)
    st = dict(REF._settings(WHOLE))
    lp_ref = REF._Layer(whole["layers"], 0)
    uncut_ref = REF._experts(h[0], lp_ref, st)
    parts_ref = [REF._experts(h[0], lp_ref, {
        **st, "lo": 4 * r, "hi": 4 * r + 4, "shared": False}) for r in range(4)]
    shared = REF._gated(h[0], lp_ref["ws_gate"], lp_ref["ws_up"],
                        lp_ref["ws_down"])
    np.testing.assert_allclose(
        np.asarray(sum(parts_ref) + shared), np.asarray(uncut_ref), atol=1e-5)
    same = lambda x, *a: x
    uncut, idx = tfm._moe(
        h, jax.tree.map(lambda a: a[0], whole["layers"]), CFG_WHOLE, same)
    np.testing.assert_allclose(
        np.asarray(uncut[0]), np.asarray(uncut_ref), atol=1e-5)
    total = 0.0
    for r in range(4):
        cfg = dataclasses.replace(CFG, expert_offset=4 * r, n_shared_experts=0)
        lp = jax.tree.map(lambda a: a[0], share_of(whole, r)["layers"])
        part, idx_r = tfm._moe(h, lp, cfg, same)
        assert np.array_equal(np.asarray(idx_r), np.asarray(idx))
        np.testing.assert_allclose(
            np.asarray(part[0]), np.asarray(parts_ref[r]), atol=1e-5)
        assert float(jnp.abs(part).max()) > 0.01  # every chip adds a part
        total = total + part
    np.testing.assert_allclose(
        np.asarray(total[0] + shared), np.asarray(uncut[0]), atol=1e-5)


# ----------------------------------- (d) absent experts' pairs get no group


def test_pairs_on_absent_experts_get_no_group(params, monkeypatch):
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (20, 64), jnp.float32)
    w, idx = tfm._moe_route(x, lp, CFG)
    held = np.logical_and(np.asarray(idx) >= 4, np.asarray(idx) < 8)
    assert 0 < held.sum() < held.size
    seen = {}
    real = jax.lax.ragged_dot

    def spy(rows, wt, sizes, **kw):
        seen["sizes"], seen["rows"] = np.asarray(sizes), rows.shape[0]
        out = real(rows, wt, sizes, **kw)
        # what lies behind the groups' sum is not specified: poison it
        return jnp.where(
            jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes), out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", spy)
    out = tfm._moe_dropless(x, w, idx, lp, CFG)
    assert seen["rows"] == 20 * 3 and seen["sizes"].shape == (4,)
    assert seen["sizes"].tolist() == [
        int((np.asarray(idx) == e).sum()) for e in range(4, 8)]
    assert seen["sizes"].sum() == held.sum() < 60
    assert np.isfinite(np.asarray(out)).all()
    monkeypatch.undo()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(tfm._moe_dropless(x, w, idx, lp, CFG)),
        atol=1e-6)
    # the counts the decode step hands the host: hottest, touched, held pairs
    live = jnp.arange(20) < 15
    load = np.asarray(tfm._expert_load(idx, live, CFG))
    per = [(np.asarray(idx)[:15] == e).sum() for e in range(4, 8)]
    assert load.tolist() == [max(per), sum(p > 0 for p in per), sum(per)]
    # every expert held: the two counts they have always been
    whole_load = tfm._expert_load(idx, live, CFG_WHOLE)
    assert whole_load.shape == (2,)


def test_the_stack_read_in_place_takes_the_share_too(params):
    """The paged programs hand `_moe_dropless` the whole [L, X, ...] stacks
    and the layer's index: the held pairs' sizes land at that layer's X
    groups."""
    x = jax.random.normal(jax.random.PRNGKey(4), (9, 64), jnp.float32)
    for l in (1, 2):  # model layers; the stack's rows 0 and 1
        lp = jax.tree.map(lambda a: a[l - 1], params["layers"])
        w, idx = tfm._moe_route(x, lp, CFG)
        sliced = tfm._moe_dropless(x, w, idx, lp, CFG)
        stack = {**lp, **{k: params["layers"][k]
                          for k in ("w_gate", "w_up", "w_down")}}
        whole = tfm._moe_dropless(x, w, idx, stack, CFG, layer=jnp.int32(l))
        np.testing.assert_allclose(
            np.asarray(whole), np.asarray(sliced), atol=1e-6)


# ------------------------------------------------- (e) the whole model


def _generate(eng, prompts, max_new=6):
    from ray_tpu.serve.batching import ContinuousBatcher

    b = ContinuousBatcher(eng, max_batch_size=eng.max_batch_size,
                          batch_wait_timeout_s=0.0)
    try:
        streams = [b.submit(tokens=p, max_new_tokens=max_new) for p in prompts]
        return [[int(t) for t in s] for s in streams]
    finally:
        b.close()


def test_whole_model_through_the_batcher_and_the_shares_counts(params):
    rng = np.random.default_rng(5)
    doc = rng.integers(1, 128, size=40).tolist()
    prompts = [doc + rng.integers(1, 128, size=n).tolist() for n in (5, 11, 3)]
    outs = {}
    for impl in ("gather", "fused"):
        eng = PagedDecodeEngine(
            CFG, params, max_batch_size=2, block_tokens=BT,
            attention_impl=impl, prefill_chunk_tokens=16)
        outs[impl] = _generate(eng, prompts)
        stats = eng.stats()
        assert stats["prefix_hits"] >= 1 and stats["chunked_prefills"] >= 1
        # 3 requests x 5 decode steps x top-3, over the 2 EXPERT layers
        assert stats["moe_pairs"] == 15 * 3 * 2
        assert 0 < stats["moe_pairs_held"] < stats["moe_pairs"]
        assert stats["moe_hottest"] <= stats["moe_pairs_held"]
        assert 0 < stats["moe_touched"] <= stats["decode_steps"] * 2 * 4
        assert (stats["experts_held"], stats["experts_routed"]) == (4, 16)
        assert 0 < stats["kv_blocks_walked"] < stats["kv_table_blocks"]
    assert outs["gather"] == outs["fused"]
    for p, out in zip(prompts, outs["fused"]):
        seq = np.asarray(p + out[:-1])
        want = _ref(params, seq, list(range(len(p) - 1, len(seq))))
        assert out == want.argmax(-1).tolist()


# ------------------------------------------------ (f) negative controls


def _near_argmax(params, prompt, out, tolerance=0.0625, conf=CONF_TIES):
    """benchmark/server.py reference_check's rule."""
    seq = np.asarray(list(prompt) + out[:-1])
    logits = _ref(params, seq, list(range(len(prompt) - 1, len(seq))), conf)
    top = logits.max(-1)
    served = logits[np.arange(len(out)), np.asarray(out)]
    return bool(np.all(top - served <= tolerance * np.abs(top)))


def _break_group_limit(monkeypatch, params, cfg):
    return params, dataclasses.replace(cfg, moe_n_group=1, moe_topk_group=1)


def _break_routing_weight(monkeypatch, params, cfg):
    """The chosen probabilities as they are, not x routed_scaling_factor."""
    return params, dataclasses.replace(cfg, moe_route_scale=1.0)


def _break_renormalise(monkeypatch, params, cfg):
    return params, dataclasses.replace(cfg, moe_renormalize=True,
                                       moe_route_scale=1.0)


def _break_shared_experts(monkeypatch, params, cfg):
    layers = dict(params["layers"])
    layers["ws_down"] = jnp.zeros_like(layers["ws_down"])
    return {**params, "layers": layers}, cfg


def _break_held_range(monkeypatch, params, cfg):
    """This chip's weights under another chip's rank."""
    return params, dataclasses.replace(cfg, expert_offset=8)


def _break_rope_term(monkeypatch, params, cfg):
    real = tfm._qkv_latent

    def no_rope(x, lp, cfg, cos, sin, positions=None):
        q, latent, wkv_b = real(x, lp, cfg, cos, sin, positions)
        return q.at[..., cfg.qk_nope_head_dim:].set(0.0), latent, wkv_b

    monkeypatch.setattr(tfm, "_qkv_latent", no_rope)
    return params, cfg


@pytest.mark.parametrize("fault", [
    None, _break_group_limit, _break_routing_weight, _break_renormalise,
    _break_shared_experts, _break_held_range, _break_rope_term,
], ids=["sound", "no-group-limit", "routing-weight-unscaled", "renormalised",
        "no-shared-experts", "another-chips-rank", "no-rope-term"])
def test_a_dropped_mechanism_fails_the_cells_comparison(monkeypatch, params,
                                                        fault):
    """Under the cell's own rule — tolerance 2^-4, the reference following
    the router's near-ties — at the plain 1/sqrt(fan_in) draw."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 128, size=n).tolist() for n in (37, 52, 45)]
    tree, cfg = fault(monkeypatch, params, CFG) if fault else (params, CFG)
    eng = PagedDecodeEngine(cfg, tree, max_batch_size=2, block_tokens=BT,
                            attention_impl="fused")
    outs = _generate(eng, prompts, max_new=8)
    ok = all(_near_argmax(params, p, o) for p, o in zip(prompts, outs))
    assert ok == (fault is None)


@pytest.mark.parametrize("where", ["router-logits", "router-softmax"])
def test_bfloat16_where_the_file_says_float32_fails_the_comparison(
        monkeypatch, params, where):
    """The router's logits leave their matmul in float32 and its softmax is
    taken in float32 (`_moe_route`). Either in bfloat16 moves a weight by up
    to 2^-9 of itself and reorders near-equal experts: the float32 program
    then misses the float32 reference's logits by far more than TOL."""
    real = jax.nn.softmax

    def rounded(logits, axis=-1):
        if where == "router-logits":
            return real(logits.astype(jnp.bfloat16).astype(jnp.float32), axis)
        return real(logits.astype(jnp.bfloat16), axis).astype(jnp.float32)

    orig = tfm._moe_route

    def route(x, lp, cfg):
        monkeypatch.setattr(jax.nn, "softmax", rounded)
        try:
            return orig(x, lp, cfg)
        finally:
            monkeypatch.setattr(jax.nn, "softmax", real)

    monkeypatch.setattr(tfm, "_moe_route", route)
    logits, toks = _serve(params, PROMPT, 5, "fused")
    seq = np.concatenate([PROMPT, toks[:-1]])
    want = _ref(params, seq, list(range(len(PROMPT) - 1, len(seq))))
    assert np.abs(logits - want).max() > 10 * TOL


# ------------------------------------------------- (g) near-ties, both cuts


def test_route_choices_by_hand():
    # 2 groups of 3, keep 1, top-2. group bests 0.90 and 0.89: a tie at 0.02
    z = np.array([0.90, 0.50, 0.10, 0.89, 0.885, 0.30])
    assert REF.route_choices(z, 2, 2, 1, 0.0) == [(0.0, [0, 1])]
    got = REF.route_choices(z, 2, 2, 1, 0.02)
    # under group 0: {0, 1} (0.5 and 0.1 are far apart); under group 1 (cost
    # 0.01): {3, 4} (0.885 and 0.3 are far apart)
    assert [sorted(e) for _, e in got] == [[0, 1], [3, 4]]
    assert [c for c, _ in got] == pytest.approx([0.0, 0.01])
    # one group kept of one: only the expert cut can tie
    z = np.array([0.9, 0.5, 0.49, 0.1])
    got = REF.route_choices(z, 2, 1, 1, 0.02)
    assert [sorted(e) for _, e in got] == [[0, 1], [0, 2]]
    # every group kept: no group cut to tie at
    assert len(REF.route_choices(z, 2, 2, 2, 0.0)) == 1


def _noisy_router(monkeypatch, eps):
    """A replica whose router sees its logits through arithmetic of its
    own: every logit moved by up to `eps` (a fixed function of the token).
    Near-ties closer than 2 eps fall either way, at either cut."""
    real = jnp.einsum

    def noisy(spec, x, w, **kw):
        out = real(spec, x, w, **kw)
        if spec == "ne,ex->nx":
            out = out + eps * jnp.sin(997.0 * out)
        return out

    orig = tfm._moe_route

    def route(x, lp, cfg):
        monkeypatch.setattr(jnp, "einsum", noisy)
        try:
            return orig(x, lp, cfg)
        finally:
            monkeypatch.setattr(jnp, "einsum", real)

    monkeypatch.setattr(tfm, "_moe_route", route)


@pytest.mark.parametrize("eps,margin,passes", [
    (0.03, 0.0, False),   # the plain rows: a flipped near-tie is a miss
    (0.03, 0.08, True),   # the margin admits both sides of the tie
    (0.3, 0.08, False),   # noise past the margin is a fault again
], ids=["no-margin", "within-margin", "past-margin"])
def test_reference_follows_router_near_ties(monkeypatch, params, eps, margin,
                                            passes):
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 128, size=n).tolist()
               for n in (37, 52, 45, 41, 33, 48)]
    _noisy_router(monkeypatch, eps)
    eng = PagedDecodeEngine(CFG, params, max_batch_size=2, block_tokens=BT,
                            attention_impl="fused")
    outs = _generate(eng, prompts, max_new=8)
    conf = dict(CONF, reference=dict(router_tie_margin=margin))
    ok = all(_near_argmax(params, p, o, conf=conf)
             for p, o in zip(prompts, outs))
    assert ok == passes


def test_branch_zero_is_the_plain_reference(params):
    seq = np.asarray(PROMPT)
    at = [len(seq) - 3, len(seq) - 1]
    plain = _ref(params, seq, at)
    conf = dict(CONF, reference=dict(router_tie_margin=0.2))
    branches = REF.ref_branch_logits(params, seq, conf, at, chunk=16, pad_to=32)
    folded = _ref(params, seq, at, conf)
    assert max(len(b["cost"]) for b in branches) > 1  # some tie within 0.2
    for row, b, f in zip(plain, branches, folded):
        assert b["cost"][0] == 0.0 and all(c > 0 for c in b["cost"][1:])
        np.testing.assert_allclose(b["logits"][0], row, atol=TOL)
        assert f.max() == pytest.approx(row.max(), abs=TOL)
        assert np.all(f >= row - TOL)  # a token is never further from the top


# ------------------------------------------------- (h) refused by name


@pytest.mark.parametrize("change,error,match", [
    (dict(n_routed_experts=16, n_experts=5), ValueError, "equal parts"),
    (dict(expert_offset=6), ValueError, "equal parts"),
    (dict(expert_offset=16), ValueError, "equal parts"),
    (dict(n_routed_experts=16, n_experts=0), ValueError, "share held"),
    (dict(moe_capacity_factor=1.25), NotImplementedError, "dropless"),
    (dict(moe_impl="dense"), NotImplementedError, "dropless"),
    (dict(moe_n_group=3), ValueError, "equal groups"),
    (dict(moe_topk_group=5), ValueError, "equal groups"),
    (dict(moe_scoring="sigmoid"), NotImplementedError, "sigmoid"),
    (dict(top_k=9), ValueError, "exceeds"),
], ids=["held-does-not-divide", "offset-off-a-share", "offset-past-the-end",
        "share-of-nothing", "capacity-buffer", "dense-oracle",
        "groups-do-not-divide", "more-kept-than-groups", "sigmoid-groups",
        "top-k-past-the-kept-groups"])
def test_what_the_share_and_the_groups_refuse(change, error, match):
    with pytest.raises(error, match=match):
        dataclasses.replace(CFG, **change)


@pytest.mark.parametrize("asked", [
    dict(kv_dtype=jnp.int8), dict(mesh=object()), dict(speculative_k=2),
], ids=["int8", "mesh", "speculation"])
def test_the_latent_pool_still_refuses_by_name(asked):
    with pytest.raises(NotImplementedError, match="latent"):
        tfm.refuse_on_latent_pool(CFG, **asked)
