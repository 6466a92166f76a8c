"""A model with a layer PERIOD — three gated-delta-rule (linear-attention)
layers and one full-attention layer without rope, both with their norms on
the sublayers' outputs — at a tiny size on the CPU, float32, seeded weights,
against the plain reference of benchmark/blocks/olmo_hybrid_reference.py
(per-token recurrence, `highest` matmuls):

  (b) `make_forward`, and prefill-then-decode through
      ContinuousBatcher(PagedDecodeEngine), "gather" and "fused";
  (c) the hybrid cache: a request behind a cached prefix, a hit deeper
      than the deepest snapshot, preempt-and-resume, fork and snapshot
      eviction each give the logits of a cold run;
  (d) negative controls, each judged by the cell's own comparison
      (near-argmax within `logit_tolerance` of the largest logit);
  (e) what a hybrid cache does not support, refused by name;
  (f) parameter counts at the published widths, from shapes alone.

Tolerances. Program and reference are both float32 here and differ in the
order of their sums (chunked scan against per-token recurrence, blocked
softmax, the pool's head padding): 2e-3 absolute on logits of magnitude ~5
is 4e-4 of the largest — a hundred times under what any control below
moves. Warm against cold runs of the SAME program differ only by where the
chunks fall: 5e-4."""

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
    make_paged_decoder,
)
from ray_tpu.ops import gated_delta as gd

BT = 16
VOCAB = 128
LOGIT_TOLERANCE = 0.035  # the cell's (benchmark/workloads/*.sessions.json)
ATOL = 2e-3
ATOL_WARM = 5e-4

# the block's file at a tiny size: 10 heads, so that the pool pads its KV
# heads (10 -> 16) as the published 30 pad to 32
CONF = {
    "name": "tiny-hybrid", "block": "olmo_hybrid", "model_type": "olmo_hybrid",
    "vocab_size": VOCAB, "hidden_size": 80, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 10,
    "num_key_value_heads": 10, "hidden_act": "silu", "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 3, "linear_num_value_heads": 3,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "run": {"max_seq_len": 256},
}
BLOCK = common.load_block(CONF)
CFG = TransformerConfig(**BLOCK.transformer_kwargs(CONF), dtype=jnp.float32,
                        remat=False)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(1, VOCAB, size=n).tolist()


def _near_argmax(ref_logits, served, tol=LOGIT_TOLERANCE):
    """The serving cells' check (benchmark/server.py: reference_check): at
    every position the reference logit of the SERVED token lies within
    tol x |largest reference logit| of that largest logit."""
    ref_logits = np.asarray(ref_logits)
    top = ref_logits.max(-1)
    got = ref_logits[np.arange(len(served)), np.asarray(served)]
    return bool(np.all(top - got <= tol * np.abs(top)))


# ----------------------------------------------------------- (b) the model


def test_period_parameters_are_stacked_per_kind(params):
    assert set(params) == {"embed", "layers", "linear_layers", "final_norm",
                           "unembed"}
    assert params["layers"]["wq"].shape == (2, 80, 10, 8)
    assert params["linear_layers"]["wq"].shape == (6, 80, 3, 8)
    assert params["linear_layers"]["wv"].shape == (6, 80, 3, 16)
    assert params["linear_layers"]["conv_w"].shape == (6, 3 * 32, 4)
    assert CFG.num_params() == sum(a.size for a in jax.tree.leaves(params))
    specs = tfm.param_specs(CFG)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(params)
    # decays between ~0.2 and ~0.9999 a token: A in (0, 16), dt in (1e-3, 1e-1)
    lin = params["linear_layers"]
    rate = np.exp(lin["a_log"]) * np.log1p(np.exp(lin["dt_bias"]))
    assert 0.0 < rate.min() and rate.max() < 1.61


def test_forward_equals_the_reference(params):
    toks = _tokens(1, 150)
    got = jax.jit(make_forward(CFG))(params, jnp.asarray([toks]))[0]
    want = BLOCK.ref_logits(params, toks, CONF)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    assert float(np.abs(want).max()) > 2.0  # not a flat nothing


def test_forward_with_remat_and_gradients(params):
    """The trainer's forward: autodiff through the XLA chunked form, one
    remat'ed scan step a period."""
    cfg = dataclasses.replace(CFG, remat=True)
    toks = jnp.asarray([_tokens(2, 40)])
    loss, grads = jax.value_and_grad(tfm.make_loss_fn(cfg))(
        params, {"tokens": toks})
    assert np.isfinite(float(loss))
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    assert all(v > 0 and np.isfinite(v) for v in jax.tree.leaves(norms)), norms


def _record(eng):
    """Wrap the engine's two model programs: every logits row they return
    lands in the log — ("prefill", [V]) / ("decode", [B, V])."""
    log = []
    prefill, decode = eng._prefill, eng._decode_step

    def rec_prefill(*a, **k):
        out = prefill(*a, **k)
        log.append(("prefill", np.asarray(out[1][0])))
        return out

    def rec_decode(*a, **k):
        out = decode(*a, **k)
        log.append(("decode", np.asarray(out[1])))
        return out

    eng._prefill, eng._decode_step = rec_prefill, rec_decode
    return log


def _engine(params, **kw):
    kw = {"max_batch_size": 4, "block_tokens": BT, "num_blocks": 96,
          "attention_impl": "gather", "prefill_chunk_tokens": 32,
          "n_snapshots": 8, **kw}
    eng = PagedDecodeEngine(CFG, params, **kw)
    eng.log = _record(eng)
    return eng


def _admit(eng, slot, request):
    """Admit and, where the prompt streams in chunks, step until its first
    token -> (token, done, the logits row that token was drawn from)."""
    del eng.log[:]
    tok, done = eng.admit(slot, request)
    while tok is None:
        toks, done = eng.step([slot])[slot]
        tok = toks[0] if toks else None
    return tok, done, next(
        r for kind, r in reversed(eng.log) if kind == "prefill")


def _run(eng, slot, prompt, n):
    """Admit `prompt` into `slot`, decode to `n` tokens, release ->
    (tokens, logits [n, V] of the slot's positions)."""
    tok, done, row = _admit(eng, slot, {"tokens": prompt, "max_new_tokens": n})
    out, rows = [tok], [row]
    while not done:
        tok, done = eng.step([slot])[slot]
        out.append(tok)
        rows.append(eng.log[-1][1][slot])
    eng.release(slot)
    return out, np.stack(rows)


@pytest.fixture(scope="module")
def cold(params):
    """logits of a cold run: no prefix cache, nothing restored."""
    eng = _engine(params, prefix_cache=False, n_snapshots=1)
    memo = {}

    def run(prompt, n):
        key = (tuple(prompt), n)
        if key not in memo:
            memo[key] = _run(eng, 0, prompt, n)
        return memo[key]

    return run


@pytest.mark.parametrize("impl", ["gather", "fused"])
def test_batcher_prefill_then_decode_equals_the_reference(params, impl):
    from ray_tpu.serve.batching import ContinuousBatcher

    eng = PagedDecodeEngine(
        CFG, params, max_batch_size=2, block_tokens=BT,
        attention_impl=impl, prefill_chunk_tokens=32, n_snapshots=6)
    hist = _tokens(3, 64)
    prompts = [hist + _tokens(4, 9), hist + _tokens(5, 21), _tokens(6, 5)]
    b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.0)
    try:
        streams = [b.submit(tokens=p, max_new_tokens=6) for p in prompts]
        outs = [[int(t) for t in s] for s in streams]
    finally:
        b.close()
    for p, out in zip(prompts, outs):
        assert len(out) == 6
        pos = list(range(len(p) - 1, len(p) + 5))
        ref = np.asarray(BLOCK.ref_logits(params, p + out[:-1], CONF, pos))
        # float32 on both sides: the served token IS the reference's argmax,
        # or a tie the reference breaks within ATOL
        top = ref.max(-1)
        assert np.all(top - ref[np.arange(6), out] <= ATOL), (impl, p[-3:])
    st = eng.stats()
    assert st["attention_kernel"] == ("gather" if impl == "gather" else "xla")
    # the second prompt is admitted while the first still prefills: it finds
    # the snapshot at 32, or the one at 64
    assert st["state_restores"] >= 1 and st["prefix_tokens_reused"] >= 32
    assert st["state_bytes_per_seq"] == 6 * (8 * 48 * 4 + 3 * 96 * 4)
    assert st["kv_bytes_per_token"] == 2 * 2 * 16 * 8 * 4  # 10 heads -> 16


def test_engine_logits_equal_the_reference(params, cold):
    prompt = _tokens(7, 70)
    out, logits = cold(prompt, 5)
    pos = list(range(69, 74))
    ref = BLOCK.ref_logits(params, prompt + out[:-1], CONF, pos)
    np.testing.assert_allclose(logits, ref, atol=ATOL, rtol=0)


# ------------------------------------------------------ (c) the hybrid cache


@pytest.fixture(scope="module")
def warm(params):
    return _engine(params)


def test_a_request_behind_a_cached_prefix_gives_cold_logits(warm, cold):
    hist = _tokens(10, 64)
    first, second = hist + _tokens(11, 9), hist + _tokens(12, 13)
    _run(warm, 0, first, 3)  # leaves snapshots at 32 and 64
    before = warm.stats()
    out, logits = _run(warm, 1, second, 5)
    after = warm.stats()
    want_out, want = cold(second, 5)
    assert out == want_out
    np.testing.assert_allclose(logits, want, atol=ATOL_WARM, rtol=0)
    assert after["state_restores"] == before["state_restores"] + 1
    assert after["prefix_tokens_reused"] == before["prefix_tokens_reused"] + 64
    # only the 13 new tokens were computed
    assert after["prefill_tokens"] == before["prefill_tokens"] + 13


def test_a_hit_deeper_than_the_deepest_snapshot_falls_back_to_it(warm, cold):
    """90 tokens in chunks of 32 leave snapshots at 32 and 64 and KV blocks
    up to 80: a prompt that shares 85 tokens matches 5 blocks of keys and
    values but resumes from the snapshot at 64 — the 16 tokens between are
    computed again, into blocks of the slot's own — and says so."""
    base = _tokens(13, 90)
    _run(warm, 0, base, 2)
    cache = warm.prefix_cache
    follow = base[:85] + _tokens(14, 6)
    assert len(cache.match_blocks(np.asarray(follow, np.int32), 5)) == 5
    before = warm.stats()
    out, logits = _run(warm, 2, follow, 4)
    after = warm.stats()
    assert after["prefix_tokens_reused"] - before["prefix_tokens_reused"] == 64
    assert after["prefill_tokens"] - before["prefill_tokens"] == 91 - 64
    want_out, want = cold(follow, 4)
    assert out == want_out
    np.testing.assert_allclose(logits, want, atol=ATOL_WARM, rtol=0)


def test_a_hit_without_any_snapshot_is_a_cold_start(params, cold):
    """Blocks in the cache and no state behind them: nothing is reused."""
    eng = _engine(params, n_snapshots=1)
    base = _tokens(15, 40)
    _run(eng, 0, base, 2)
    for node in eng.prefix_cache._nodes.values():  # drop every snapshot
        eng.prefix_cache._drop_snapshot(node)
    follow = base[:36] + _tokens(16, 5)
    before = eng.stats()["prefix_tokens_reused"]
    out, logits = _run(eng, 1, follow, 3)
    assert eng.stats()["prefix_tokens_reused"] == before
    np.testing.assert_allclose(logits, cold(follow, 3)[1], atol=ATOL_WARM, rtol=0)


def test_preempt_and_resume_gives_cold_logits(warm, cold):
    prompt = _tokens(17, 50)
    want_out, want = cold(prompt, 24)
    tok, done, _ = _admit(warm, 0, {"tokens": prompt, "max_new_tokens": 24})
    out = [tok]
    for _ in range(15):  # crosses the block boundary at 64: a tail snapshot
        tok, done = warm.step([0])[0]
        out.append(tok)
    restores = warm.stats()["state_restores"]
    warm._preempt(0)
    (slot, parked), = warm.take_preempted()
    assert parked["tokens"] == prompt + out
    tok, done, row = _admit(warm, 3, parked)
    rows = [row]
    out.append(tok)
    while not done:
        tok, done = warm.step([3])[3]
        out.append(tok)
        rows.append(warm.log[-1][1][3])
    warm.release(3)
    assert out == want_out
    # re-admission restored the state kept at the last whole block (64)
    assert warm.stats()["state_restores"] == restores + 1
    np.testing.assert_allclose(
        np.stack(rows), want[16:], atol=ATOL_WARM, rtol=0)


def test_fork_copies_the_state(warm, cold):
    prompt = _tokens(18, 37)
    want_out, want = cold(prompt, 10)
    tok, done, _ = _admit(warm, 0, {"tokens": prompt, "max_new_tokens": 10})
    out = [tok]
    for _ in range(4):
        tok, _ = warm.step([0])[0]
        out.append(tok)
    warm.fork(0, 1)
    both = {0: list(out), 1: list(out)}
    rows = {0: [], 1: []}
    done = False
    while not done:
        res = warm.step([0, 1])
        for s in (0, 1):
            both[s].append(res[s][0])
            rows[s].append(warm.log[-1][1][s])
        done = res[0][1]
    warm.release(0)
    warm.release(1)
    assert both[0] == both[1] == want_out
    for s in (0, 1):
        np.testing.assert_allclose(
            np.stack(rows[s]), want[5:], atol=ATOL_WARM, rtol=0)


def test_snapshot_eviction_under_a_full_state_pool(params, cold):
    """Two snapshot rows for five histories: every request still gives a
    cold run's logits, whether its history's snapshot survived or not, and
    a restored snapshot outlives the ones no admission came back to."""
    eng = _engine(params, n_snapshots=2)
    hists = [_tokens(20 + i, 32) for i in range(5)]
    for i, h in enumerate(hists):
        _run(eng, i % 4, h + _tokens(30 + i, 4), 2)
    st = eng.stats()
    assert st["state_snapshot_evictions"] >= 3 and st["state_rows_free"] == 0
    assert eng.prefix_cache.snapshots() == 2
    for i in (4, 0, 4, 2, 4):
        prompt = hists[i] + _tokens(40 + i, 7)
        out, logits = _run(eng, 1, prompt, 3)
        np.testing.assert_allclose(
            logits, cold(prompt, 3)[1], atol=ATOL_WARM, rtol=0)
    # history 4 was restored, again and again, while others came and went
    assert eng.stats()["state_restores"] >= 2
    assert eng.stats()["state_rows_free"] == 0


def test_decode_keeps_the_rows_of_slots_it_does_not_step(warm, cold):
    """A slot mid-way through a chunked prefill keeps its state while the
    others decode around it."""
    long_prompt, short = _tokens(50, 100), _tokens(51, 20)
    want_long, want_short = cold(long_prompt, 4), cold(short, 12)
    del warm.log[:]
    tok_s, _ = warm.admit(0, {"tokens": short, "max_new_tokens": 12})
    tok_l, _ = warm.admit(1, {"tokens": long_prompt, "max_new_tokens": 4})
    assert tok_l is None  # chunked: 32 of 100 tokens so far
    outs = {0: [tok_s], 1: []}
    live = {0, 1}
    while live:
        for s, (toks, done) in warm.step(sorted(live)).items():
            outs[s] += toks if isinstance(toks, list) else [toks]
            if done:
                warm.release(s)
                live.discard(s)
    assert outs[0] == want_short[0] and outs[1] == want_long[0]


# -------------------------------------------------- (d) negative controls


def _served_by(forward_cfg, params, toks):
    """argmax tokens of a program variant at every position of `toks`."""
    logits = jax.jit(make_forward(forward_cfg))(params, jnp.asarray([toks]))
    return np.asarray(jnp.argmax(logits[0], axis=-1))


@pytest.fixture(scope="module")
def control(params):
    toks = _tokens(60, 96)
    return toks, np.asarray(BLOCK.ref_logits(params, toks, CONF))


def test_the_right_program_passes_the_cells_comparison(params, control):
    toks, ref = control
    assert _near_argmax(ref, _served_by(CFG, params, toks))


def _patched(monkeypatch, name):
    if name == "beta_without_its_factor_2":
        real = gd.gate_and_beta
        monkeypatch.setattr(gd, "gate_and_beta", lambda *a: (
            real(*a)[0], 0.5 * real(*a)[1]))
    elif name == "no_decay":
        real = gd.gate_and_beta
        monkeypatch.setattr(gd, "gate_and_beta", lambda *a: (
            jnp.zeros_like(real(*a)[0]), real(*a)[1]))
    elif name == "no_conv":
        monkeypatch.setattr(
            gd, "causal_conv",
            lambda u, w, tail: jax.nn.silu(u.astype(jnp.float32)))
    return CFG


@pytest.mark.parametrize("name", [
    "beta_without_its_factor_2", "no_decay", "no_conv", "pre_norm",
    "rope_on_the_full_layers"])
def test_a_wrong_model_fails_the_cells_comparison(monkeypatch, params,
                                                  control, name):
    toks, ref = control
    cfg = {"pre_norm": dataclasses.replace(CFG, norm_placement="pre"),
           "rope_on_the_full_layers": dataclasses.replace(CFG, use_rope=True),
           }.get(name) or _patched(monkeypatch, name)
    assert not _near_argmax(ref, _served_by(cfg, params, toks)), name


def test_a_prefix_hit_that_restores_no_state_fails(params, cold):
    """The bug this design invites: the hit's blocks under the slot, the
    state left at zero. The engine's own restore is replaced by one that
    zeroes the row; the served tokens then miss the reference."""
    eng = _engine(params)
    hist = _tokens(70, 64)
    _run(eng, 0, hist + _tokens(71, 5), 2)
    real = eng._copy_rows

    def no_state(src, dst, name, tokens):
        if name != "engine.state_restore":
            return real(src, dst, name, tokens)
        eng.pool = {k: a.at[:, dst].set(0) if k in tfm.STATE_LEAVES else a
                    for k, a in eng.pool.items()}

    eng._copy_rows = no_state
    prompt = hist + _tokens(72, 20)
    out, logits = _run(eng, 1, prompt, 8)
    assert eng.stats()["prefix_tokens_reused"] >= 64  # the blocks WERE reused
    pos = list(range(len(prompt) - 1, len(prompt) + 7))
    ref = BLOCK.ref_logits(params, prompt + out[:-1], CONF, pos)
    assert not _near_argmax(ref, out)
    assert float(np.abs(logits - cold(prompt, 8)[1]).max()) > 0.1


def test_a_bfloat16_state_against_the_float32_reference(params):
    """The configuration states a float32 state. A bfloat16 one through the
    same engine moves the logits by ~1e-2 of the largest: outside ATOL by an
    order of magnitude, so THIS file's comparison of logits fails it. The
    cell's near-argmax comparison does not, here or on the chip over 4k-32k
    histories at the published widths (0.0-0.8 % against the sound runs'
    <= 1.4 %: PERF.md section 6, PR 35) - it holds structure, not precision.
    So the state's dtype is no option: the pool's leaf is float32, the
    programs follow the leaf, and the control casts the leaf of a built
    engine."""
    prompt = _tokens(80, 70)
    eng = _engine(params, prefix_cache=False, n_snapshots=1)
    assert eng.pool["state"].dtype == jnp.float32
    eng.pool = {**eng.pool, "state": eng.pool["state"].astype(jnp.bfloat16)}
    out, logits = _run(eng, 0, prompt, 8)
    pos = list(range(69, 77))
    ref = np.asarray(BLOCK.ref_logits(params, prompt + out[:-1], CONF, pos))
    worst = float(np.abs(logits - ref).max())
    assert worst > 5 * ATOL, worst
    assert eng.pool["state"].dtype == jnp.bfloat16  # the programs kept it


# ------------------------------------------------------------ (e) refusals


@pytest.mark.parametrize("kw,word", [
    ({"kv_cache_dtype": "int8"}, "kv_dtype"),
    ({"speculative_k": 2}, "speculative_k"),
    ({"mesh": object(), "rules": object()}, "mesh"),
])
def test_a_hybrid_cache_refuses_by_name_at_construction(params, kw, word):
    with pytest.raises(NotImplementedError, match=f"hybrid cache.*{word}"):
        PagedDecodeEngine(CFG, params, max_batch_size=1, block_tokens=BT, **kw)


def test_a_hybrid_cache_refuses_transfer_by_name(warm):
    toks = _tokens(90, 40)
    with pytest.raises(NotImplementedError, match="export_prefix"):
        warm.export_prefix(toks)
    with pytest.raises(NotImplementedError, match="import_prefix"):
        warm.import_prefix({})
    with pytest.raises(NotImplementedError, match="import_prefix"):
        warm.admit(0, {"tokens": toks, "max_new_tokens": 1, "kv_import": {}})
    assert warm.transfer_sig != PagedDecodeEngine(
        dataclasses.replace(CFG, layer_period=(), norm_placement="pre"),
        max_batch_size=1, block_tokens=BT).transfer_sig


def test_the_transfer_manager_stays_off_on_a_hybrid_cache(warm):
    from ray_tpu.serve.kv_transfer import KVTransferManager

    class _Batcher:
        engine = warm

    mgr = KVTransferManager(_Batcher(), enabled=True)
    assert not mgr.enabled and mgr.export_serve(_tokens(91, 40)) is None


def test_the_programs_refuse_what_is_not_written(params):
    with pytest.raises(NotImplementedError, match="kv_dtype"):
        make_paged_decoder(CFG, block_tokens=BT, kv_dtype=jnp.int8)
    with pytest.raises(NotImplementedError, match="mesh"):
        init_paged_kv_cache(CFG, 4, BT, mesh=object(), rules=object())
    _, _, verify, _ = make_paged_decoder(CFG, block_tokens=BT)
    z = np.zeros((2, 3), np.int32)
    with pytest.raises(NotImplementedError, match="speculative_k"):
        verify(params, init_paged_kv_cache(CFG, 4, BT, state_rows=2),
               np.zeros((2, 4), np.int32), z, z[:, 0], z[:, 0], z, z,
               jax.random.PRNGKey(0))
    tfm.refuse_on_state_pool(  # a no-op without linear layers
        tfm.CONFIGS["tiny"], kv_dtype=jnp.int8, mesh=object(), speculative_k=2)
    with pytest.raises(ValueError, match="n_snapshots"):
        PagedDecodeEngine(tfm.CONFIGS["tiny"], max_batch_size=1, n_snapshots=4)


@pytest.mark.parametrize("change,word", [
    ({"pp_stages": 2}, "pp_stages"),
    ({"n_experts": 4}, "n_experts"),
    ({"hc_mult": 2}, "hc_mult"),
    ({"layer_period": ("linear", "window")}, "layer_period"),
    ({"n_layers": 6}, "whole periods"),
    ({"linear_d_k": 0}, "linear_d_k"),
    ({"norm_placement": "sandwich"}, "norm_placement"),
])
def test_the_config_refuses_by_name(change, word):
    with pytest.raises((NotImplementedError, ValueError), match=word):
        dataclasses.replace(CFG, **change)


# ----------------------------------------------------- (f) published widths


def test_parameter_counts_at_the_published_widths():
    """ISSUE 35, section 3, from shapes alone: a linear layer 215.57 M, a
    full layer 185.81 M, a period 832.52 M, embedding + head 770.70 M, two
    periods 2.436 B, the whole model 7.43 B."""
    conf = common.load_config("olmo-hybrid-7b-l8")
    cfg = TransformerConfig(**common.load_block(conf).transformer_kwargs(conf))
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert count(shapes["linear_layers"]) == 6 * 215_570_172
    assert count(shapes["layers"]) == 2 * 185_809_920
    assert count(shapes["embed"]) + count(shapes["unembed"]) == 770_703_360
    assert count(shapes) == cfg.num_params() == 2_435_748_072
    whole = dataclasses.replace(cfg, n_layers=32)
    assert whole.num_params() == 8 * 832_520_436 + 770_703_360 + 3840
    assert round(whole.num_params() / 1e9, 2) == 7.43
    held = jax.eval_shape(lambda: tfm.serving_params(
        cfg, init_params(jax.random.PRNGKey(0), cfg)))
    small = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(held)
                if a.dtype == jnp.float32)
    assert small < 400_000  # norm scales, conv, A_log, dt_bias stay float32
    # the caches, by their own leaves
    pool = jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, 2561, 64, state_rows=160))
    assert pool["k"].shape == (2, 2561, 64, 32, 128)      # 30 heads -> 32
    assert pool["state"].shape == (6, 160, 96, 5760)
    assert pool["state"].dtype == jnp.float32
    assert pool["conv"].shape == (6, 160, 3 * 11520)
    assert tfm.paged_kv_block_bytes(cfg, 64) == 64 * 32768
    assert tfm.paged_state_row_bytes(cfg) == 13_685_760
