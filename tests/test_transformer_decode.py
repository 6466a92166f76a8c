"""KV-cache decode correctness: paged prefill + cached single-token decode
(the exact "gather" programs) must reproduce the full-context forward (same
prefix -> same logits), solo and under a sharded mesh dryrun — the contract
the serving fast path rests on — and the engine that drives them keeps its
slots isolated.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (
    CONFIGS,
    PagedDecodeEngine,
    init_paged_kv_cache,
    init_params,
    make_forward,
    make_paged_decoder,
    pack_decode_inputs,
    pack_prefill_inputs,
)
from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh

BT = 8  # block_tokens: block boundaries land inside prefix and decode


def _f32(name):
    return dataclasses.replace(CONFIGS[name], dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = _f32("tiny")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _tokens(cfg, b, t, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=(b, t)).astype(np.int32)


def _assert_decode_matches(cfg, params, rules=None, mesh=None,
                           b=2, prefix=8, total=20, tol=1e-3):
    """Prefill `prefix` tokens of each sequence into its own blocks, then
    teacher-force batched decode steps; every step's logits must match the
    full forward at the same position."""
    tokens = _tokens(cfg, b, total)
    full = np.asarray(make_forward(cfg)(params, jnp.asarray(tokens)))

    prefill, decode_step, _, _ = make_paged_decoder(
        cfg, rules, mesh, block_tokens=BT, attention_impl="gather")
    per_slot = -(-total // BT)
    # a pool of 1 + b * per_slot blocks, rounded up to whole (dp, fsdp) shards
    num_blocks = -(-(1 + b * per_slot) // 4) * 4
    pool = init_paged_kv_cache(cfg, num_blocks, BT, mesh=mesh, rules=rules)
    # slot i owns blocks 1 + i*per_slot ...; block 0 is the null block
    tables = 1 + np.arange(b * per_slot, dtype=np.int32).reshape(b, per_slot)
    key = jax.random.PRNGKey(1)
    for i in range(b):
        _, logits, pool = prefill(
            params, pool,
            pack_prefill_inputs(tables[i], tokens[i, :prefix], prefix, 0),
            key, 0, prefix,
        )
        np.testing.assert_allclose(
            np.asarray(logits)[0], full[i, prefix - 1], rtol=tol, atol=tol
        )
    positions = np.full(b, prefix, np.int32)
    rows = np.arange(b)
    for t in range(prefix, total - 1):
        _, logits, pool = decode_step(
            params, pool, pack_decode_inputs(
                tables, tokens[:, t], positions,
                tables[rows, positions // BT], positions % BT), key,
        )
        np.testing.assert_allclose(
            np.asarray(logits), full[:, t], rtol=tol, atol=tol
        )
        positions += 1


def test_decode_matches_forward(tiny_f32):
    cfg, params = tiny_f32
    _assert_decode_matches(cfg, params)


def test_decode_matches_forward_bf16(tiny_f32):
    """bf16 compute (the serving dtype): same prefix -> same logits within
    bf16 rounding (logits are O(2), bf16 ulp there is ~0.016 and the two
    paths reassociate sums differently)."""
    cfg = CONFIGS["tiny"]
    params = tiny_f32[1]
    _assert_decode_matches(cfg, params, tol=1.5e-1)


def test_decode_matches_under_sharded_mesh(tiny_f32):
    """The acceptance dryrun: decode under a dp x fsdp x tp mesh matches
    the unsharded forward, and the pool carries the activation sharding
    (blocks on dp/fsdp, kv_heads on tp)."""
    cfg, params = tiny_f32
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    pool = init_paged_kv_cache(cfg, 8, BT, mesh=mesh, rules=rules)
    spec = pool["k"].sharding.spec
    assert spec[1] == ("dp", "fsdp") and spec[3] == "tp", spec
    _assert_decode_matches(cfg, params, rules=rules, mesh=mesh, b=4)


def _engine(cfg, params, **kw):
    return PagedDecodeEngine(
        cfg, params, block_tokens=BT, attention_impl="gather", **kw)


def test_engine_batched_equals_solo_greedy(tiny_f32):
    """Greedy generation from a multi-slot engine must be identical to a
    fresh single-slot engine: slots are fully isolated."""
    cfg, params = tiny_f32
    tokens = _tokens(cfg, 2, 12)
    eng = _engine(cfg, params, max_batch_size=4)
    t0, _ = eng.admit(0, {"tokens": tokens[0, :5], "max_new_tokens": 6})
    t1, _ = eng.admit(2, {"tokens": tokens[1, :9], "max_new_tokens": 4})
    outs = {0: [t0], 2: [t1]}
    active = [0, 2]
    while active:
        for slot, (tok, done) in eng.step(list(active)).items():
            outs[slot].append(tok)
            if done:
                active.remove(slot)
                eng.release(slot)
    assert len(outs[0]) == 6 and len(outs[2]) == 4

    solo = _engine(cfg, params, max_batch_size=1)
    tok, done = solo.admit(0, {"tokens": tokens[0, :5], "max_new_tokens": 6})
    got = [tok]
    while not done:
        tok, done = solo.step([0])[0]
        got.append(tok)
    assert got == outs[0], (got, outs[0])


def test_engine_slot_reuse_is_clean(tiny_f32):
    """A retired slot's cache residue (its blocks go back to the pool
    unwiped, and the prefix cache may keep some) must not leak into the
    next sequence admitted to the same slot."""
    cfg, params = tiny_f32
    tokens = _tokens(cfg, 2, 12)

    def _gen(eng, slot, prompt, n):
        tok, done = eng.admit(slot, {"tokens": prompt, "max_new_tokens": n})
        out = [tok]
        while not done:
            tok, done = eng.step([slot])[slot]
            out.append(tok)
        eng.release(slot)
        return out

    eng = _engine(cfg, params, max_batch_size=2)
    first = _gen(eng, 0, tokens[0, :7], 5)
    second = _gen(eng, 0, tokens[1, :4], 5)  # same slot, new sequence
    fresh = _engine(cfg, params, max_batch_size=2)
    assert _gen(fresh, 0, tokens[1, :4], 5) == second
    assert _gen(fresh, 1, tokens[0, :7], 5) == first


def test_engine_eos_and_cap(tiny_f32):
    cfg, params = tiny_f32
    prompt = _tokens(cfg, 1, 6)[0]
    eng = _engine(cfg, params, max_batch_size=1)
    tok, done = eng.admit(0, {"tokens": prompt, "max_new_tokens": 3})
    n = 1
    while not done:
        tok, done = eng.step([0])[0]
        n += 1
    assert n == 3  # max_new_tokens cap honored

    # eos cut: make the first generated token the eos
    solo = _engine(cfg, params, max_batch_size=1, eos_id=None)
    first, _ = solo.admit(0, {"tokens": prompt, "max_new_tokens": 50})
    eng2 = _engine(cfg, params, max_batch_size=1, eos_id=first)
    _, done2 = eng2.admit(0, {"tokens": prompt, "max_new_tokens": 50})
    assert done2  # stopped at eos immediately


def test_moe_decode_matches_forward():
    """MoE decode through the dispatch path. capacity_factor=4 makes
    capacity non-binding: with the default 1.25, prefill (N=B*prefix
    tokens) and the full forward (N=B*total) compute DIFFERENT capacities
    and drop different overflow tokens — inherent capacity semantics, not
    a decode bug — so the equality contract only holds drop-free."""
    cfg = dataclasses.replace(_f32("tiny_moe"), moe_capacity_factor=4.0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    _assert_decode_matches(cfg, params, b=2, prefix=6, total=14, tol=2e-3)


def test_moe_decode_matches_forward_dropless():
    """The same contract with no capacity set: the dropless path computes
    every routed pair at any N, so prefill (N = B*prefix), each decode step
    (N = B) and the full forward agree without a factor chosen to fit."""
    cfg = dataclasses.replace(_f32("tiny_moe"), moe_capacity_factor=None)
    params = init_params(jax.random.PRNGKey(0), cfg)
    _assert_decode_matches(cfg, params, b=2, prefix=6, total=14, tol=2e-3)
