"""Helpers of the tests that hold PagedDecodeEngine to its held tree
(transformer.serving_params): the logits behind every token an engine
emits, and the comparison of an engine that holds its weights in the
compute dtype with one whose programs still get the float32 tree (the
behaviour before the engine cast what it takes)."""

import dataclasses

import jax
import numpy as np

from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.transformer import _MATMUL_KEYS

NORM_KEYS = ("attn_norm", "mlp_norm", "q_norm", "k_norm")


def capture(eng):
    """Keep every logits row the engine's two programs produce."""
    rows = {"prefill": [], "decode": []}
    prefill, decode = eng._prefill, eng._decode_step

    def prefill_spy(*a, **kw):
        out = prefill(*a, **kw)
        rows["prefill"].append(np.asarray(out[1], np.float32))
        return out

    def decode_spy(*a, **kw):
        out = decode(*a, **kw)
        rows["decode"].append(np.asarray(out[1], np.float32))
        return out

    eng._prefill, eng._decode_step = prefill_spy, decode_spy
    return rows


def serve(eng, rows, slot, prompt, new_tokens):
    """Admit, decode greedily; -> (tokens, the logits row behind each)."""
    n_prefill, n_decode = len(rows["prefill"]), len(rows["decode"])
    tok, done = eng.admit(slot, {"tokens": prompt, "max_new_tokens": new_tokens})
    out = [int(tok)]
    while not done:
        (tok, done), = eng.step([slot]).values()
        out.append(int(tok))
    logits = [rows["prefill"][-1][0]]
    logits += [r[slot] for r in rows["decode"][n_decode:]]
    assert len(rows["prefill"]) == n_prefill + 1 and len(logits) == len(out)
    return out, np.stack(logits)


def served(eng, prompts, new_tokens=6):
    """Each prompt through its own slot, the second behind the first one's
    cached blocks: -> (tokens, logits) of all of them."""
    rows = capture(eng)
    toks, logits = [], []
    for slot, prompt in enumerate(prompts):
        out, got = serve(eng, rows, slot, prompt, new_tokens)
        toks.append(out)
        logits.append(got)
    return toks, np.concatenate(logits)


def assert_holds(eng, params):
    """eng.params is serving_params of `params`: matmul leaves, embed and
    unembed in cfg.dtype, the norm scales float32, and stats() says so."""
    dtype = np.dtype(eng.cfg.dtype)
    held = eng.params
    for key, leaf in held["layers"].items():
        want = dtype if key in _MATMUL_KEYS else np.dtype(np.float32)
        assert key in _MATMUL_KEYS or key in NORM_KEYS, key
        assert leaf.dtype == want, (key, leaf.dtype)
    assert held["embed"].dtype == dtype and held["unembed"].dtype == dtype
    assert held["final_norm"].dtype == np.float32
    assert jax.tree.structure(held) == jax.tree.structure(params)
    stats = eng.stats()
    assert stats["param_bytes"] == sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(held))
    assert stats["param_dtype"] == dtype.name
    # the caller's tree is the caller's: still there, still float32
    for leaf in jax.tree.leaves(params):
        assert leaf.dtype == np.float32 and not leaf.is_deleted()


def check_held_tree(case, cfg, params, other, prompts, **engine_kw):
    """`cfg` computes in bfloat16, `params` and `other` are float32 trees
    of it. The engine's tokens and logits must be, bit for bit, those of
    the same compiled programs run on the float32 tree itself."""

    def engine(tree):
        return PagedDecodeEngine(cfg, tree, **engine_kw)

    if case == "float32":
        # compute dtype = the tree's: nothing is cast and nothing copied
        eng = PagedDecodeEngine(
            dataclasses.replace(cfg, dtype=np.float32), params, **engine_kw)
        for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(params)):
            assert a is b
        assert eng.stats()["param_dtype"] == "float32"
        return
    before = engine(params)
    before.params = params  # what every program was handed before
    want_tokens, want_logits = served(before, prompts)
    if case == "fresh":
        eng = engine(params)
    else:  # a replica hot-swapped to the learner's float32 tree
        eng = engine(other)
        assert eng.set_params(params) == 1
    assert_holds(eng, params)
    tokens, logits = served(eng, prompts)
    assert tokens == want_tokens
    assert np.array_equal(logits, want_logits), float(
        np.max(np.abs(logits - want_logits)))
    assert before.stats()["prefix_hits"] == eng.stats()["prefix_hits"] == 1
