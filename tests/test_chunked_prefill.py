"""Chunked prefill + multi-query fused attention (ISSUE 13 acceptance).

Two contracts certified here:

  1. Tokens are INVARIANT to scheduling: splitting a long prompt into
     chunks (any chunk size, aligned or straddling physical block
     boundaries, with or without prefix hits, fp or int8, gather or
     fused attention, solo or sharded) produces exactly the tokens a
     whole-prompt admission produces.

  2. Scheduling is INTERLEAVED: while one slot streams its prompt in
     chunk-per-step, every other slot decodes in the SAME engine steps —
     a long prompt never stalls in-flight decode streams (the
     head-of-line latency fix). The fused multi-query path (prefill
     q=chunk, speculative verify q=k+1) must match the gather reference
     token-for-token at long context.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import CONFIGS, init_params
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.speculative import ReplayDrafter
from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = dataclasses.replace(
        CONFIGS["tiny"], dtype=jnp.float32, max_seq_len=512
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, size=n)


def _gen(eng, slot, prompt, n):
    """Generate n tokens through the engine contract — tolerates chunked
    admission (None first token, [] step results) and speculative bursts.
    Releases the slot at the end."""
    tok, done = eng.admit(slot, {"tokens": prompt, "max_new_tokens": n})
    out = [] if tok is None else [tok]
    while not done:
        toks, done = eng.step([slot])[slot]
        out.extend(toks if isinstance(toks, (list, tuple)) else [toks])
    eng.release(slot)
    return out


def _build(cfg, params, chunk=0, impl="gather", dtype="fp", B=2, **kw):
    return PagedDecodeEngine(
        cfg, params, max_batch_size=B, block_tokens=8,
        prefill_chunk_tokens=chunk, attention_impl=impl,
        kv_cache_dtype=dtype, seed=0, **kw,
    )


# --------------------------------------------------- scheduling invariance


def test_chunked_equals_whole_prompt_token_for_token(tiny_f32):
    """The acceptance contract: any chunk size — block-aligned, straddling
    a physical block boundary (bt=8, chunk=12: the 2nd chunk spans
    positions 12..23, cutting blocks 1/2 mid-block), or pathological
    (chunk=1) — is invisible to the tokens, for both attention impls."""
    cfg, params = tiny_f32
    prompt = _prompt(cfg, 90)
    ref = _gen(_build(cfg, params), 0, prompt, 10)
    for impl in ("gather", "fused"):
        for chunk in (16, 12, 1):
            eng = _build(cfg, params, chunk=chunk, impl=impl)
            got = _gen(eng, 0, prompt, 10)
            assert got == ref, (impl, chunk)
            assert eng.chunked_prefills == 1
            assert eng.prefill_chunks == -(-90 // chunk)


def test_chunked_int8_matches_whole_prompt_int8(tiny_f32):
    """int8 pools requantize the straddled (slot-owned) block per chunk —
    the committed bytes must still serve the same tokens as a whole-prompt
    int8 admission, under both attention impls."""
    cfg, params = tiny_f32
    prompt = _prompt(cfg, 70, seed=3)
    ref = _gen(_build(cfg, params, dtype="int8"), 0, prompt, 10)
    for impl in ("gather", "fused"):
        got = _gen(
            _build(cfg, params, chunk=12, impl=impl, dtype="int8"),
            0, prompt, 10,
        )
        assert got == ref, impl


def test_chunked_fused_matches_under_sharded_mesh(tiny_f32):
    """dp x fsdp x tp dryrun: chunked prefill through the fused
    multi-query shard_map path (blocks sharded on dp/fsdp with the
    log-sum-exp merge, kv_heads on tp) == the unsharded gather engine."""
    cfg, params = tiny_f32
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    prompt = _prompt(cfg, 60, seed=4)
    ref = _gen(_build(cfg, params), 0, prompt, 8)
    for dtype in ("fp", "int8"):
        sharded = PagedDecodeEngine(
            cfg, params, max_batch_size=4, block_tokens=8, rules=rules,
            mesh=mesh, attention_impl="fused", prefill_chunk_tokens=12,
            kv_cache_dtype=dtype, seed=0,
        )
        got = _gen(sharded, 0, prompt, 8)
        if dtype == "fp":
            assert got == ref
        else:  # int8 vs its own solo int8 engine
            solo = _gen(
                _build(cfg, params, chunk=12, impl="fused",
                       dtype="int8"),
                0, prompt, 8,
            )
            assert got == solo


def test_chunked_prefill_prefix_cache_interaction(tiny_f32):
    """A prefix hit shrinks what streams in chunks: the second admit of
    the same prompt reuses the cached full blocks (ctx = hit span) and
    only the remainder chunks in — tokens identical, prefill work cut."""
    cfg, params = tiny_f32
    prompt = _prompt(cfg, 50, seed=5)
    eng = _build(cfg, params, chunk=12, impl="fused")
    cold = _gen(eng, 0, prompt, 6)
    cold_tokens = eng.prefill_tokens
    hit = _gen(eng, 0, prompt, 6)
    assert hit == cold
    assert eng.prefix_hits == 1
    # the hit admission prefilled only the uncached tail
    assert eng.prefill_tokens - cold_tokens < len(prompt) // 2


# ------------------------------------------------ fused multi-query verify


def test_fused_verify_matches_gather_long_context(tiny_f32):
    """Speculative verify at long context (200-token prompt, 25+ blocks):
    the fused multi-query verify (window walk + in-flight log-sum-exp
    merge) must be token-for-token the gather-window formulation, fp and
    int8, with real accepted bursts (replay drafter)."""
    cfg, params = tiny_f32
    prompt = _prompt(cfg, 200, seed=6)
    for dtype in ("fp", "int8"):
        base = _gen(_build(cfg, params, dtype=dtype), 0, prompt, 24)
        outs = {}
        for impl in ("gather", "fused"):
            eng = _build(
                cfg, params, impl=impl, dtype=dtype, speculative_k=4,
                drafter=ReplayDrafter([list(prompt) + base]),
            )
            outs[impl] = _gen(eng, 0, prompt, 24)
            assert eng.spec_steps > 0, (impl, dtype)  # verify path ran
            assert outs[impl] == base, (impl, dtype)
        assert outs["gather"] == outs["fused"], dtype


def test_fused_verify_matches_gather_under_sharded_mesh(tiny_f32):
    """dp x fsdp x tp dryrun of the fused VERIFY path: the k+1-query
    window partial merges across pool shards, then the in-flight tail
    folds in — tokens must match the solo gather spec engine."""
    cfg, params = tiny_f32
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    prompt = _prompt(cfg, 100, seed=7)
    base = _gen(_build(cfg, params), 0, prompt, 16)
    sharded = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, rules=rules,
        mesh=mesh, attention_impl="fused", speculative_k=4,
        drafter=ReplayDrafter([list(prompt) + base]), seed=0,
    )
    got = _gen(sharded, 0, prompt, 16)
    assert sharded.spec_steps > 0
    assert got == base


# --------------------------------------------------- interleaved scheduling


def test_decode_never_stalls_during_chunked_prefill(tiny_f32):
    """THE head-of-line property, deterministically at the engine level:
    slot 0 decodes while slot 1's 120-token prompt streams in 12-token
    chunks. EVERY shared step must advance slot 0 by a token — zero
    stalled steps — and slot 1 reports [] until its prompt is consumed."""
    cfg, params = tiny_f32
    eng = _build(cfg, params, chunk=12, impl="fused", B=2)
    short = _prompt(cfg, 10, seed=8)
    long = _prompt(cfg, 120, seed=9)
    ref_short = _gen(_build(cfg, params), 0, short, 40)

    tok, done = eng.admit(0, {"tokens": short, "max_new_tokens": 40})
    out0 = [tok]
    tok1, done1 = eng.admit(1, {"tokens": long, "max_new_tokens": 4})
    assert tok1 is None and not done1
    out1 = []
    prefill_steps = 0
    while not done:
        res = eng.step([0] + ([1] if not done1 else []))
        toks, done = res[0]
        toks = toks if isinstance(toks, (list, tuple)) else [toks]
        if 1 in res:
            t1, done1 = res[1]
            out1.extend(t1 if isinstance(t1, (list, tuple)) else [t1])
            if eng.stats()["prefilling"] or (t1 == [] and not out1):
                prefill_steps += 1
                # the no-stall assertion: slot 0 advanced THIS step too
                assert len(toks) == 1, "decode stalled during a chunk step"
        out0.extend(toks)
    # slot 1's prompt is 120 tokens, first chunk at admit, 12/step after:
    # its prefill overlapped ~9 of slot 0's decode steps
    assert prefill_steps >= 8, prefill_steps
    assert out0 == ref_short
    # slot 1 sampled its first token mid-run and decoded to completion
    while not done1:
        t1, done1 = eng.step([1])[1]
        out1.extend(t1 if isinstance(t1, (list, tuple)) else [t1])
    assert len(out1) == 4
    ref_long = _gen(_build(cfg, params), 0, long, 4)
    assert out1 == ref_long


def test_batcher_streams_complete_with_chunked_prefill(tiny_f32):
    """End-to-end through ContinuousBatcher: a decode stream and a
    chunked long-prompt stream share the batch; both deliver exactly the
    whole-prompt reference tokens, and the chunked-prefill stats surface
    through batcher.stats()."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    short = _prompt(cfg, 8, seed=10)
    long = _prompt(cfg, 100, seed=11)
    ref_short = _gen(_build(cfg, params), 0, short, 30)
    ref_long = _gen(_build(cfg, params), 0, long, 10)

    eng = _build(cfg, params, chunk=12, impl="fused", B=2)
    b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.0)
    try:
        s1 = b.submit(tokens=short, max_new_tokens=30)
        s2 = b.submit(tokens=long, max_new_tokens=10)
        o1, o2 = [], []
        t1 = threading.Thread(target=lambda: o1.extend(s1))
        t2 = threading.Thread(target=lambda: o2.extend(s2))
        t1.start(); t2.start()
        t1.join(timeout=120); t2.join(timeout=120)
        assert not t1.is_alive() and not t2.is_alive()
        assert o1 == ref_short
        assert o2 == ref_long
        stats = b.stats()
        assert stats["prefill_chunk_tokens"] == 12
        assert stats["chunked_prefills"] >= 1
        assert stats["prefilling"] == 0  # everything completed
    finally:
        b.close()


def test_chunked_prefill_composes_with_speculation(tiny_f32):
    """A speculating engine admits a chunked prompt: chunk steps route
    around the propose/verify machinery (nothing to draft mid-prefill),
    then speculation kicks in — tokens still match the plain reference."""
    cfg, params = tiny_f32
    prompt = _prompt(cfg, 80, seed=12)
    ref = _gen(_build(cfg, params), 0, prompt, 16)
    eng = _build(
        cfg, params, chunk=12, impl="fused", speculative_k=4,
        drafter=ReplayDrafter([list(prompt) + ref]),
    )
    got = _gen(eng, 0, prompt, 16)
    assert got == ref
    assert eng.chunked_prefills == 1
    assert eng.spec_steps > 0


def test_prefilling_slot_is_newest_first_preemption_victim(tiny_f32):
    """Newest-first preemption stays GLOBAL: when an older decode stream
    needs a block the pool cannot supply, the newest admission — a slot
    still streaming its chunked prefill — is the victim, NOT the older
    decoder. The parked prompt then readmits and completes exactly."""
    cfg, params = tiny_f32
    # 6 usable blocks: A(prompt 8 tokens, max_new 30) grows to 4 blocks;
    # B(24-token prompt, chunked by 8) pins 3 at admission
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=7,
        prefix_cache=False, prefill_chunk_tokens=8, seed=0,
    )
    a_prompt = _prompt(cfg, 8, seed=20)
    b_prompt = _prompt(cfg, 24, seed=21)
    ref_a = _gen(_build(cfg, params, B=1), 0, a_prompt, 30)
    ref_b = _gen(_build(cfg, params, B=1), 0, b_prompt, 2)

    tok, done = eng.admit(0, {"tokens": a_prompt, "max_new_tokens": 30})
    out_a = [tok]
    # grow A to position 23 (3 blocks full) while B is not yet admitted —
    # its NEXT write (position 24) will need a 4th block
    for _ in range(16):
        t, done = eng.step([0])[0]
        out_a.append(t)
    tok_b, _ = eng.admit(1, {"tokens": b_prompt, "max_new_tokens": 2})
    assert tok_b is None  # chunked: 3 blocks pinned, free = 0
    assert eng.stats()["prefilling"] == 1
    # the very next step: B advances a chunk (still mid-prefill) AND A's
    # block-boundary write forces a preemption — the victim must be B
    # (newest, mid-prefill), never the older decoder
    while not done:
        res = eng.step([0, 1])
        t, done = res[0]
        out_a.append(t)
        if 1 in res:  # B must never emit before its preemption
            assert res[1] == ([], False), res[1]
    assert eng.preemptions >= 1
    parked = eng.take_preempted()
    assert [s for s, _ in parked] == [1], parked
    assert out_a == ref_a  # the old stream never paid for B's prompt
    eng.release(0)
    # the parked request readmits through the normal path and completes
    slot, req = parked[0]
    tok, done = eng.admit(slot, req)
    out_b = [] if tok is None else [tok]
    while not done:
        t, done = eng.step([slot])[slot]
        out_b.extend(t if isinstance(t, (list, tuple)) else [t])
    assert out_b == ref_b


def test_sampling_tokens_invariant_to_chunking(tiny_f32):
    """temperature > 0: intermediate chunk dispatches use a fixed
    throwaway key, so the engine consumes ONE RNG key per admission
    regardless of chunk config — same seed, same sampled tokens whether
    the prompt admits whole or in chunks."""
    cfg, params = tiny_f32
    prompt = _prompt(cfg, 60, seed=22)

    def run(chunk):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=1, block_tokens=8,
            prefill_chunk_tokens=chunk, temperature=1.0, seed=7,
        )
        return _gen(eng, 0, prompt, 12)

    whole = run(0)
    assert run(12) == whole
    assert run(7) == whole


# ------------------------------------------------------------ API contract


def test_admit_contract_and_guards(tiny_f32):
    """admit() returns (None, False) only for chunked admissions; prompts
    at or under one chunk admit whole; fork/force_token refuse a
    still-prefilling slot; stats expose the chunk state."""
    cfg, params = tiny_f32
    eng = _build(cfg, params, chunk=16, B=2)
    tok, done = eng.admit(0, {"tokens": _prompt(cfg, 16), "max_new_tokens": 4})
    assert tok is not None  # fits one chunk: whole-prompt admission
    eng.release(0)

    tok, done = eng.admit(0, {"tokens": _prompt(cfg, 40), "max_new_tokens": 4})
    assert tok is None and not done
    st = eng.stats()
    assert st["prefilling"] == 1 and st["prefill_chunk_tokens"] == 16
    with pytest.raises(ValueError, match="prefilling"):
        eng.fork(0, 1)
    with pytest.raises(ValueError, match="prefilling"):
        eng.force_token(0, 1)
    # stepping resolves the pending chunks and the guards lift
    while eng.stats()["prefilling"]:
        eng.step([0])
    eng.force_token(0, 1)  # no raise
    eng.release(0)

    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        _build(cfg, params, chunk=-1)
