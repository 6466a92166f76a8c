"""The OLMoE block (QK-norm over the whole projection, dropless top-k
experts without renormalisation, the published rms_norm_eps) against its
plain float32 reference, benchmark/blocks/olmoe.py, on seeded random
weights at a tiny OLMoE-shaped size: d 64, 4 MHA heads, 8 experts top-2,
eps 1e-5, float32. Logits are compared, never tokens.

TOL. Program and reference both compute in float32 here and differ only in
the order of their sums (sorted grouped matmuls against every expert on
every token, fused against plain attention): measured 3e-6 on logits whose
largest is 4. 1e-4 is thirty times that, and five hundred times below what
one bfloat16 pass moves (5e-2): the last test holds it to that."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _held_tree import capture as _capture, check_held_tree, serve as _serve
from benchmark import common
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.transformer import (
    TransformerConfig, init_params, make_forward,
)

TOL = 1e-4

CONF = {
    "name": "tiny-olmoe", "block": "olmoe", "model_type": "olmoe",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
    "vocab_size": 256, "rope_theta": 10000, "rope_scaling": None,
    "rms_norm_eps": 1e-5, "clip_qkv": None, "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False,
    "run": {"max_seq_len": 128},
}
BLOCK = common.load_block(CONF)


def _model(conf=CONF, dtype=jnp.float32, seed=0, **over):
    """(cfg, params): weights from the seed, norm scales drawn around 1 so
    that QK-norm's learned scale and every eps show in the logits."""
    cfg = TransformerConfig(
        **{**BLOCK.transformer_kwargs(conf), **over}, dtype=dtype)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        key, sub = jax.random.split(key)
        leaf = params["layers"][name]
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(sub, leaf.shape)
    return cfg, params


def _tokens(n, seed=7):
    return np.asarray(
        jax.random.randint(jax.random.PRNGKey(seed), (n,), 0, 256), np.int32)


def _forward(cfg, params, tokens):
    return np.asarray(
        jax.jit(make_forward(cfg))(params, tokens[None])[0], np.float32)


def _ref(params, tokens, conf=CONF):
    return np.asarray(BLOCK.ref_logits(params, tokens, conf))


def _gap(a, b):
    return float(np.max(np.abs(a - b)))


# (a), (d): the training forward, any N down to one token
@pytest.mark.parametrize("n", [24, 1], ids=["n24", "n1"])
def test_forward_matches_reference(n):
    cfg, params = _model()
    assert cfg.qk_norm and cfg.moe_capacity_factor is None
    assert not cfg.moe_renormalize and cfg.rms_norm_eps == 1e-5
    tokens = _tokens(n)
    assert _gap(_forward(cfg, params, tokens), _ref(params, tokens)) < TOL


def test_dense_oracle_matches_reference():
    # moe_impl="dense" honours the renormalisation setting: it stays the
    # oracle of both routed paths
    cfg, params = _model(moe_impl="dense")
    tokens = _tokens(24)
    assert _gap(_forward(cfg, params, tokens), _ref(params, tokens)) < TOL


# (b): the paged engine, prefill then decode through the cache
@pytest.mark.parametrize("impl", ["gather", "fused"])
def test_engine_prefill_and_decode_match_reference(impl):
    cfg, params = _model()
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, max_seq_len=64,
        prefix_cache=True, attention_impl=impl)
    rows = _capture(eng)
    shared = _tokens(19, seed=3)          # two whole blocks and a tail
    for slot, turn_seed in ((0, 11), (1, 12)):
        prompt = np.concatenate([shared[:16], _tokens(7, seed=turn_seed)])
        out, got = _serve(eng, rows, slot, prompt, new_tokens=6)
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        want = _ref(params, seq)[len(prompt) - 1:]
        assert _gap(got, want) < TOL, (impl, slot)
    stats = eng.stats()
    # the second prompt found the first one's two blocks in the cache
    assert stats["prefix_hits"] == 1 and stats["prefix_tokens_reused"] == 16
    # 5 decode steps a request, one live slot, top-2, 2 layers
    assert stats["moe_pairs"] == 2 * 5 * 1 * 2 * 2
    # one token's two experts differ: each layer's fullest expert holds one
    assert stats["moe_hottest"] == 2 * 5 * 2


# the held tree: expert stacks, router and QK-norm scales included
@pytest.mark.parametrize("case", ["fresh", "swap", "float32"])
def test_engine_holds_its_weights_in_the_compute_dtype(case):
    cfg, params = _model(dtype=jnp.bfloat16)
    _, other = _model(dtype=jnp.bfloat16, seed=5)
    assert params["layers"]["q_norm"].dtype == jnp.float32
    shared = _tokens(16, seed=3)
    prompts = [np.concatenate([shared, _tokens(n, seed=n)]) for n in (7, 5)]
    check_held_tree(
        case, cfg, params, other, prompts, max_batch_size=2, block_tokens=8,
        max_seq_len=64, prefix_cache=True)


# (c): a router rigged so that every token picks the same two experts
def _rigged():
    cfg, params = _model()
    # every token carries a large positive first coordinate, and the router
    # reads that coordinate alone: expert 7 and 6 win for every token
    params["embed"] = params["embed"].at[:, 0].set(1.0)
    router = jnp.zeros_like(params["layers"]["router"])
    router = router.at[:, 0, :].set(jnp.arange(8, dtype=jnp.float32))
    params["layers"]["router"] = router
    params["layers"]["mlp_norm"] = jnp.abs(params["layers"]["mlp_norm"])
    return cfg, params


def test_rigged_router_dropless_keeps_every_token():
    cfg, params = _rigged()
    tokens = _tokens(16)
    want = _ref(params, tokens)
    assert _gap(_forward(cfg, params, tokens), want) < TOL


def test_rigged_router_capacity_path_drops_and_is_seen():
    # the control: at capacity 1.25 an expert holds ceil(2*16/8*1.25) = 5
    # of the 16 tokens routed to it, and the comparison must see the rest
    # missing — else the test above could not see a dropped token either
    cfg, params = _rigged()
    tokens = _tokens(16)
    dropping = TransformerConfig(
        **{**BLOCK.transformer_kwargs(CONF), "moe_capacity_factor": 1.25},
        dtype=jnp.float32)
    assert _gap(_forward(dropping, params, tokens), _ref(params, tokens)) > 100 * TOL


# (e), (f): each setting matches ITS reference, and the two differ
@pytest.mark.parametrize("key,other", [
    ("norm_topk_prob", True), ("rms_norm_eps", 1e-6)])
def test_setting_reaches_program_and_reference(key, other):
    tokens = _tokens(24)
    refs = []
    for value in (CONF[key], other):
        conf = {**CONF, key: value}
        cfg, params = _model(conf)
        want = _ref(params, tokens, conf)
        assert _gap(_forward(cfg, params, tokens), want) < TOL, (key, value)
        refs.append(want)
    assert _gap(*refs) > 100 * TOL


def test_eps_reaches_every_norm_of_the_engine():
    # decode through the cache at eps 1e-6 against the 1e-5 reference: off
    cfg, params = _model({**CONF, "rms_norm_eps": 1e-6})
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, max_seq_len=64,
        prefix_cache=False)
    rows = _capture(eng)
    prompt = _tokens(12)
    out, got = _serve(eng, rows, 0, prompt, new_tokens=4)
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    right = _ref(params, seq, {**CONF, "rms_norm_eps": 1e-6})[len(prompt) - 1:]
    wrong = _ref(params, seq)[len(prompt) - 1:]
    assert _gap(got, right) < TOL < _gap(got, wrong)


# (g): the tolerance sees a lower precision
def test_bfloat16_fails_the_float32_tolerance():
    cfg, params = _model(dtype=jnp.bfloat16)
    tokens = _tokens(24)
    assert _gap(_forward(cfg, params, tokens), _ref(params, tokens)) > 100 * TOL


def test_num_params_counts_every_leaf():
    cfg, params = _model()
    leaves = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert cfg.num_params() == leaves
