"""The paged programs hand the dropless experts' [L, X, ...] stacks to the
layer body WHOLE, and `_moe_dropless` reads a layer's experts in place:
one grouped matmul over the stack viewed as L*X groups, the other layers'
group sizes zero (transformer.py: `_scan_stacks`, `_moe_dropless`). The
parent sliced the layer's [X, ...] leaves out of the stack first. Same
rows, same experts, same three matmuls: held here on the CPU.

Bit for bit, and what stands in its way on the CPU. XLA:CPU lowers
`lax.ragged_dot` to ONE dense contraction over (group, d): every row times
every group's weights, zeros outside the row's own group. Its blocked sum
associates a row's products differently when there are L*X groups than
when there are X, so float32 results differ in the last bits (measured
1e-5 of values of size 10) though the very same products are added — the
chip's kernel multiplies a tile of rows by ONE group's weights and has no
such sum (held bit for bit on the chip: PERF.md, PR 34). So:
  - where every sum is exact whatever its order — integer-valued rows and
    gate / up weights, a down projection that SELECTS one hidden unit per
    output (a signed power of two, another unit for every expert and
    layer) — the two paths must agree BIT FOR BIT: a row that met another
    layer's or another expert's weights would be wrong by whole units;
  - on drawn weights they agree to the order of float32 sums (TOL)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.transformer import (
    CONFIGS, TransformerConfig, init_paged_kv_cache, init_params,
    make_paged_decoder,
    pack_decode_inputs,
    pack_prefill_inputs,
)

L, X, E, F = 3, 8, 64, 32
SOFTMAX = TransformerConfig(
    vocab_size=256, d_model=E, n_layers=L, n_heads=4, n_kv_heads=2,
    d_head=16, d_ff=F, max_seq_len=128, n_experts=X, top_k=2,
    moe_capacity_factor=None, moe_renormalize=False, dtype=jnp.float32)
# Xing4.0's expert layer: sigmoid scores, the bias picks, a shared expert,
# behind ONE leading dense layer (so layer l's experts are stack entry l-1)
SIGMOID = dataclasses.replace(
    SOFTMAX, n_layers=L + 1, first_k_dense=1, d_ff_dense=96,
    moe_scoring="sigmoid", moe_renormalize=True, moe_route_scale=2.0,
    n_shared_experts=1)
ROUTERS = pytest.mark.parametrize(
    "cfg", [SOFTMAX, SIGMOID], ids=["softmax", "sigmoid-bias-shared"])
ROWS = pytest.mark.parametrize("n", [1, 8, 128])
# drawn float32 weights, outputs of size ~1: the order of a row's sum
TOL = dict(rtol=2e-5, atol=2e-5)


def _stack(cfg, exact, seed=0):
    """The expert stack's leaves [L, ...]. `exact`: integer gate / up
    weights in [-2, 2] and a down projection with one entry a column, a
    signed power of two at a hidden unit drawn per (layer, expert, column):
    no sum over it rounds."""
    layers = dict(init_params(jax.random.PRNGKey(seed), cfg)["layers"])
    if "router_bias" in layers:
        layers["router_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), layers["router_bias"].shape)
    if not exact:
        return layers
    rng = np.random.default_rng(seed)
    for name in ("w_gate", "w_up"):
        layers[name] = jnp.asarray(
            rng.integers(-2, 3, size=(L, X, E, F)), jnp.float32)
    unit = rng.integers(0, F, size=(L, X, E))
    value = rng.choice([-2.0, -1.0, -0.5, 0.5, 1.0, 2.0], size=(L, X, E))
    down = np.zeros((L, X, F, E), np.float32)
    lx, xx, ee = np.meshgrid(*map(np.arange, (L, X, E)), indexing="ij")
    down[lx, xx, unit, ee] = value
    layers["w_down"] = jnp.asarray(down)
    return layers


def _rows(n, exact, seed=0):
    rng = np.random.default_rng(100 + seed)
    if exact:
        return jnp.asarray(rng.integers(-3, 4, size=(n, E)), jnp.float32)
    return jnp.asarray(rng.standard_normal((n, E)), jnp.float32)


def _both_paths(fn, layers, cfg):
    """fn(lp, layer) for every layer of the stack, each way: `lp` with the
    layer's own [X, ...] slices (the parent's), and `lp` with the three
    expert stacks whole beside the layer's other leaves."""
    sliced = jax.jit(lambda lp: fn(lp, None))
    whole = jax.jit(lambda lp, layer: fn(lp, layer))
    for i in range(L):
        lp = jax.tree.map(lambda a: a[i], layers)
        stacks = {k: layers[k] for k in tfm._EXPERT_KEYS}
        yield (i, np.asarray(sliced(lp)),
               np.asarray(whole({**lp, **stacks},
                                jnp.int32(i + cfg.first_k_dense))))


def _routing(kind, n, k, seed=0):
    """Hand-made choices idx [n, k] and weights w [n, k]."""
    rng = np.random.default_rng(seed)
    if kind == "one-expert":          # every pair on expert 5
        idx = np.full((n, k), 5)
    elif kind == "one-empty":         # expert 2 gets no row
        idx = rng.choice([e for e in range(X) if e != 2], size=(n, k))
    else:
        idx = rng.integers(0, X, size=(n, k))
    w = rng.choice([0.25, 0.5, 1.0], size=(n, k))
    return jnp.asarray(idx, jnp.int32), jnp.asarray(w, jnp.float32)


@ROWS
@pytest.mark.parametrize("kind", ["drawn", "one-expert", "one-empty"])
def test_every_layers_experts_are_read_in_place_exactly(n, kind):
    """`_moe_dropless` on a hand-made routing, every layer index of a
    3-layer stack, arithmetic exact: bit for bit the sliced path's."""
    cfg = SOFTMAX
    layers, x = _stack(cfg, exact=True), _rows(n, exact=True)
    idx, w = _routing(kind, n, cfg.top_k)
    seen = []
    for i, want, got in _both_paths(
            lambda lp, layer: tfm._moe_dropless(x, w, idx, lp, cfg, layer),
            layers, cfg):
        assert want.any()
        np.testing.assert_array_equal(got, want, err_msg=f"layer {i}")
        seen.append(want)
    # the control: the layers' experts differ, so a wrong index would show
    assert not np.array_equal(seen[0], seen[1])
    assert not np.array_equal(seen[1], seen[2])


@ROWS
@ROUTERS
@pytest.mark.parametrize("exact", [True, False], ids=["exact", "drawn"])
def test_expert_layer_equals_the_sliced_path(cfg, n, exact):
    """The whole expert layer (`_moe`: router, routed experts, shared
    expert) under both routers: bit for bit where the routed experts'
    arithmetic is exact, to the order of float32 sums on drawn weights;
    the router's choices are the same either way."""
    layers, x = _stack(cfg, exact), _rows(n, exact)

    def layer_out(lp, layer):
        out, idx = tfm._moe(x[None], lp, cfg, lambda a, *axes: a, layer)
        return jnp.concatenate([out[0], idx.astype(out.dtype)], axis=1)

    for i, want, got in _both_paths(layer_out, layers, cfg):
        np.testing.assert_array_equal(got[:, E:], want[:, E:])  # choices
        if exact:
            np.testing.assert_array_equal(got, want, err_msg=f"layer {i}")
        else:
            np.testing.assert_allclose(got, want, **TOL, err_msg=f"layer {i}")


# ---- the three paged programs, against themselves with the parent's scan --

BT = 8
NMAX = 8


def _tiny_moe():
    return dataclasses.replace(
        CONFIGS["tiny_moe"], n_layers=3, n_experts=X, dtype=jnp.float32,
        moe_capacity_factor=None)


def _tiny_xing4():
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_head=24, d_ff=32, max_seq_len=128, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_factor=4.0, rope_original_max=64, rope_mscale_all_dim=1.0,
        first_k_dense=1, d_ff_dense=96, n_experts=X, top_k=2,
        moe_scoring="sigmoid", moe_route_scale=2.0, n_shared_experts=1,
        moe_capacity_factor=None, hc_mult=4, dtype=jnp.float32)


def _run_programs(cfg, params, verify):
    """A 19-token prompt prefilled behind nothing, then 11 more tokens
    behind its two whole blocks, four decode steps of two slots (one of
    them dead), and, where the model has one, a verify step: every logit
    and token the programs give."""
    prefill, decode, verify_step, _ = make_paged_decoder(
        cfg, block_tokens=BT, attention_impl="fused")
    pool = init_paged_kv_cache(cfg, 1 + 2 * NMAX, BT)
    table = 1 + np.arange(NMAX, dtype=np.int32)
    tables = np.stack([table, np.zeros(NMAX, np.int32)])
    key = jax.random.PRNGKey(0)
    prompt = np.random.default_rng(1).integers(1, 256, size=30)
    got = []

    def padded(tokens, width=32):
        out = np.zeros(width, np.int32)
        out[:len(tokens)] = tokens
        return out

    tok, logits, pool = prefill(
        params, pool, pack_prefill_inputs(table, padded(prompt[:19]), 19, 0),
        key, 0, 32)
    got += [tok, logits]
    tok, logits, pool = prefill(
        params, pool, pack_prefill_inputs(table, padded(prompt[16:]), 14, 16),
        key, 2, 32)
    got += [tok, logits]
    pos = len(prompt)
    for _ in range(4):
        # the tokens and the two expert-load counts ride one vector
        out, logits, pool = decode(
            params, pool, pack_decode_inputs(
                tables, np.array([int(tok[0]), 0], np.int32),
                np.array([pos, 0], np.int32),
                np.array([table[pos // BT], 0], np.int32),
                np.array([pos % BT, 0], np.int32)), key)
        tok, load = out[:2], out[2:]
        assert load.shape == (2,)
        got += [tok[:1], logits[:1], load]
        pos += 1
    if verify:
        draft = np.array([[int(tok[0]), 7, 9], [0, 0, 0]], np.int32)
        qpos = pos + np.arange(3)
        out, accepted, pool = verify_step(
            params, pool, tables, draft, np.array([pos, 0], np.int32),
            np.array([2, 0], np.int32),
            np.stack([table[qpos // BT], np.zeros(3, np.int32)]),
            np.stack([qpos % BT, np.zeros(3, np.int32)]).astype(np.int32),
            key)
        got += [out[:1], accepted[:1]]
    return [np.asarray(a) for a in got], pool


@pytest.mark.parametrize("model", ["tiny_moe", "xing4"])
def test_paged_programs_give_the_sliced_scans_answers(monkeypatch, model):
    """`paged_prefill` (cold and behind cached blocks), `paged_decode` and
    `paged_verify` with the expert stacks whole against the same programs
    with the parent's scan (every leaf sliced a layer): the same tokens,
    the same expert-load counts, logits and pool to the order of float32
    sums. (Xing4.0's shape has no verify program: it refuses by name.)"""
    cfg = _tiny_moe() if model == "tiny_moe" else _tiny_xing4()
    params = init_params(jax.random.PRNGKey(2), cfg)
    assert tfm._experts_in_place(cfg)
    got, pool = _run_programs(cfg, params, verify=model == "tiny_moe")
    monkeypatch.setattr(tfm, "_experts_in_place", lambda cfg: False)
    want, want_pool = _run_programs(cfg, params, verify=model == "tiny_moe")
    assert len(got) == len(want)
    for a, b in zip(got, want):
        if a.dtype.kind == "i":
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, **TOL)
    for a, b in zip(jax.tree.leaves(pool), jax.tree.leaves(want_pool)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


def _lowered(program, cfg):
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    prefill, decode, verify, _ = make_paged_decoder(cfg, block_tokens=BT)
    pool = jax.eval_shape(lambda: init_paged_kv_cache(cfg, 9, BT))
    key = jax.random.PRNGKey(0)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if program == "paged_decode":
        return decode.lower(params, pool, i32(2, NMAX + 4), key).as_text()
    if program == "paged_verify":
        return verify.lower(params, pool, i32(2, NMAX), i32(2, 3), i32(2),
                            i32(2), i32(2, 3), i32(2, 3), key).as_text()
    return jax.jit(lambda *a: prefill(*a, 0, 16)).lower(
        params, pool, i32(3 + 16 + NMAX), key).as_text()


@pytest.mark.parametrize("program", ["paged_prefill", "paged_decode",
                                     "paged_verify"])
def test_the_scan_slices_no_expert_leaf(monkeypatch, program):
    """In the lowered program nothing yields one layer's [X, ...] experts:
    the grouped matmul's weights are the stack with L*X groups. With the
    parent's scan (the control) each layer's three are sliced out."""
    cfg = _tiny_moe()
    d, f = cfg.d_model, cfg.d_ff
    one_layer = (f"-> tensor<{X}x{d}x{f}xf32>", f"-> tensor<{X}x{f}x{d}xf32>")
    text = _lowered(program, cfg)
    assert f"-> tensor<{cfg.n_layers * X}x{d}x{f}xf32>" in text
    assert f"-> tensor<{cfg.n_layers * X}x{f}x{d}xf32>" in text
    assert not any(shape in text for shape in one_layer)
    monkeypatch.setattr(tfm, "_experts_in_place", lambda cfg: False)
    text = _lowered(program, cfg)
    assert all(shape in text for shape in one_layer)
