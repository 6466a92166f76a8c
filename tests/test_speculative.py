"""Speculative decoding on the paged engine: propose-k drafting + one
batched verify step (models/speculative.py drafters, transformer.py
paged_verify_step, kv_paging.PagedDecodeEngine speculative_k plumbing,
ContinuousBatcher multi-token retirement).

The acceptance contract everywhere: greedy output with speculation enabled
is TOKEN-FOR-TOKEN identical to non-speculative paged decode — the drafter
only changes how many engine steps the tokens take, never the tokens."""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import CONFIGS, init_params
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.speculative import (
    NGramDrafter,
    ReplayDrafter,
    resolve_drafter,
)
from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh


@pytest.fixture(scope="module")
def tiny_f32():
    cfg = dataclasses.replace(CONFIGS["tiny"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=n) for n in lengths]


def _gen(eng, slot, prompt, n):
    """Greedy-generate n tokens through the engine contract, flattening
    speculative bursts; releases the slot at the end."""
    tok, done = eng.admit(slot, {"tokens": prompt, "max_new_tokens": n})
    out = [tok]
    while not done:
        toks, done = eng.step([slot])[slot]
        out.extend(toks if isinstance(toks, (list, tuple)) else [toks])
    eng.release(slot)
    return out


class _WrongDrafter:
    """Proposes k confidently wrong tokens: every draft rejects, so every
    verify step exercises the full rollback path.

    Given the true `sequences` (prompt + reference continuation) the first
    draft is wrong BY CONSTRUCTION — one past the token the model will
    pick — and acceptance stops at the first mismatch, so not a single
    draft can be accepted. Without them the drafts are merely arbitrary,
    and one may match the model by chance."""

    def __init__(self, vocab, sequences=()):
        self.vocab = vocab
        self.sequences = [[int(t) for t in s] for s in sequences]

    def propose(self, tokens, k):
        hist = [int(t) for t in tokens]
        draft = [(hist[-1] + 7 + i) % self.vocab for i in range(k)]
        for seq in self.sequences:
            if len(seq) > len(hist) and seq[:len(hist)] == hist:
                draft[0] = (seq[len(hist)] + 1) % self.vocab
        return draft


@pytest.fixture(scope="module")
def baselines(tiny_f32):
    """Non-speculative greedy references for the module's shared prompts."""
    cfg, params = tiny_f32
    prompts = _prompts(cfg, (5, 9, 17, 30))
    eng = PagedDecodeEngine(cfg, params, max_batch_size=1, block_tokens=8)
    return prompts, [_gen(eng, 0, p, 24) for p in prompts]


# --------------------------------------------------------------- drafters


def test_ngram_drafter_suffix_lookup():
    d = NGramDrafter(max_n=3, min_n=1)
    #          0  1  2  3  4  5  6  7  8
    history = [1, 2, 3, 9, 1, 2, 3, 5, 6]
    # longest suffix n-gram with an earlier occurrence... suffix [5, 6]
    # never repeats, suffix [6] never repeats -> no proposal
    assert d.propose(history, 4) == []
    history = [1, 2, 3, 9, 7, 1, 2, 3]
    # suffix [1, 2, 3] matched at position 0 -> continuation [9, 7, 1, 2]
    assert d.propose(history, 4) == [9, 7, 1, 2]
    assert d.propose(history, 2) == [9, 7]
    # most RECENT occurrence wins
    history = [1, 2, 8, 1, 2, 9, 1, 2]
    assert d.propose(history, 1) == [9]
    # shorter n-grams back off
    assert NGramDrafter(max_n=3).propose([4, 4], 2) == [4]


def test_replay_drafter_and_resolve():
    r = ReplayDrafter([[1, 2, 3, 4, 5]])
    assert r.propose([1, 2], 2) == [3, 4]
    assert r.propose([1, 2, 3, 4, 5], 2) == []  # nothing left to replay
    assert r.propose([9], 2) == []              # prefix mismatch
    assert isinstance(resolve_drafter("ngram"), NGramDrafter)
    assert resolve_drafter("ngram:5").max_n == 5
    assert resolve_drafter("off") is None and resolve_drafter("") is None
    assert resolve_drafter(r) is r
    fn = resolve_drafter(lambda toks, k: [0] * k)
    assert fn.propose([1], 3) == [0, 0, 0]
    with pytest.raises(ValueError):
        resolve_drafter("markov")
    with pytest.raises(ValueError):
        resolve_drafter(object())


def test_speculation_requires_greedy_and_a_drafter(tiny_f32):
    cfg, params = tiny_f32
    with pytest.raises(ValueError, match="greedy"):
        PagedDecodeEngine(cfg, params, speculative_k=4, temperature=0.7)
    with pytest.raises(ValueError, match="drafter"):
        PagedDecodeEngine(cfg, params, speculative_k=4, drafter="off")
    with pytest.raises(ValueError):
        PagedDecodeEngine(cfg, params, speculative_k=-1)
    # a drafter that can never run is a misconfiguration, not a noop
    with pytest.raises(ValueError, match="speculative_k"):
        PagedDecodeEngine(cfg, params, drafter=NGramDrafter())


# ------------------------------------------------------- greedy identity


def test_spec_greedy_identical_multislot(tiny_f32, baselines):
    """Interleaved multi-slot decode with perfect, wrong and self-drafting
    proposers: every variant emits exactly the non-speculative tokens.
    Block boundaries land mid-burst (block_tokens=8, k=4)."""
    cfg, params = tiny_f32
    prompts, refs = baselines
    drafters = {
        "replay": ReplayDrafter(
            [list(p) + r for p, r in zip(prompts, refs)]
        ),
        "wrong": _WrongDrafter(
            cfg.vocab_size, [list(p) + r for p, r in zip(prompts, refs)]
        ),
        "ngram": NGramDrafter(),
    }
    for name, drafter in drafters.items():
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=4, block_tokens=8,
            speculative_k=4, drafter=drafter,
        )
        outs = {}
        active = []
        for s, p in enumerate(prompts):
            tok, done = eng.admit(s, {"tokens": p, "max_new_tokens": 24})
            outs[s] = [tok]
            if not done:
                active.append(s)
        while active:
            for s, (toks, done) in eng.step(list(active)).items():
                outs[s].extend(
                    toks if isinstance(toks, (list, tuple)) else [toks]
                )
                if done:
                    active.remove(s)
                    eng.release(s)
        for s in range(len(prompts)):
            assert outs[s] == refs[s], (name, s)
        st = eng.stats()
        if name == "replay":
            assert st["spec_accept_rate"] > 0.9, st
            assert st["spec_tokens_per_step"] > 3.0, st
        if name == "wrong":
            # every verify step proposed, rejected at the first draft and
            # rolled back: nothing accepted, one token emitted per slot-step
            assert st["spec_proposed_tokens"] > 0, st
            assert st["spec_accepted_tokens"] == 0, st
            assert st["spec_emitted_tokens"] == st["spec_slot_steps"], st


def test_spec_greedy_identical_int8(tiny_f32):
    """int8 pool: spec-int8 must match plain-int8 token-for-token across
    accept bursts AND reject-heavy rollbacks (the verify commit replays
    the sequential RMW history, so the quantized cache state is what
    single-token decode would have written)."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (17,), seed=3)[0]
    plain = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, kv_cache_dtype="int8"
    )
    ref = _gen(plain, 0, prompt, 24)
    for drafter in (
        ReplayDrafter([list(prompt) + ref]),
        _WrongDrafter(cfg.vocab_size),
    ):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=1, block_tokens=8,
            kv_cache_dtype="int8", speculative_k=4, drafter=drafter,
        )
        assert _gen(eng, 0, prompt, 24) == ref, type(drafter).__name__


def test_spec_sharded_dryrun(tiny_f32, baselines):
    """dp x fsdp x tp dryrun: the verify step runs under the sharded pool
    (fp and int8) and still matches the unsharded non-speculative output."""
    cfg, params = tiny_f32
    prompts, refs = baselines
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    drafter = ReplayDrafter([list(prompts[2]) + refs[2]])
    for dtype in ("fp", "int8"):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=2, block_tokens=8, rules=rules,
            mesh=mesh, kv_cache_dtype=dtype, speculative_k=4,
            drafter=drafter,
        )
        assert _gen(eng, 0, prompts[2], 24) == refs[2], dtype
        assert eng.stats()["spec_accept_rate"] > 0.9


# -------------------------------------------------- rollback bookkeeping


def test_spec_rollback_returns_blocks(tiny_f32, baselines):
    """Reject-heavy speculation must not leak pool blocks: after every
    step the engine holds exactly the blocks the live span needs (the
    worst-case prealloc for the rejected tail went back), and release
    drains the slot to a fully free pool."""
    cfg, params = tiny_f32
    prompts, refs = baselines
    prompt = prompts[3]  # len 30
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False,
        speculative_k=4, drafter=_WrongDrafter(cfg.vocab_size),
    )
    tok, done = eng.admit(0, {"tokens": prompt, "max_new_tokens": 24})
    out = [tok]
    while not done:
        toks, done = eng.step([0])[0]
        # the last step falls back to a scalar plain step (remaining-token
        # cap leaves no room to draft)
        out.extend(toks if isinstance(toks, (list, tuple)) else [toks])
        used = eng.allocator.num_usable - eng.allocator.num_free
        want = -(-int(eng._positions[0]) // eng.block_tokens)
        # the next write position's block may already be held (partial
        # tail) but never more than one block beyond the live span
        assert used in (want, want + 1), (used, want)
    assert out == refs[3]
    eng.release(0)
    assert eng.allocator.num_free == eng.allocator.num_usable


def test_spec_cow_under_rejected_span(tiny_f32):
    """A fork-shared partial tail block sits under the verify span: the
    speculative writer must CoW before committing — and when every draft
    rejects, the fork's view of the shared block stays byte-identical
    (its continuation matches a solo teacher-forced engine exactly)."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (13,), seed=5)[0]

    def solo_ref(forced):
        solo = PagedDecodeEngine(
            cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
        )
        solo.admit(0, {"tokens": prompt, "max_new_tokens": 30})
        for _ in range(2):
            solo.step([0])
        solo.force_token(0, forced)
        return [solo.step([0])[0][0] for _ in range(5)]

    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, prefix_cache=False,
        speculative_k=4, drafter=_WrongDrafter(cfg.vocab_size),
    )
    eng.admit(0, {"tokens": prompt, "max_new_tokens": 30})
    for _ in range(2):
        eng.step([0])  # position 15: the tail block is partial
    eng.fork(0, 1)
    eng.force_token(0, 5)
    eng.force_token(1, 9)
    # speculate on the SOURCE first: its verify span covers the shared
    # partial block; every draft rejects, so the span is pure rollback
    src_out = []
    while len(src_out) < 5:
        toks, _ = eng.step([0])[0]
        src_out.extend(toks)
    assert eng.cow_copies >= 1
    dst_out = []
    while len(dst_out) < 5:
        toks, _ = eng.step([1])[1]
        dst_out.extend(toks)
    assert src_out[:5] == solo_ref(5)
    assert dst_out[:5] == solo_ref(9)


def test_spec_prefix_cache_blocks_survive_speculation(tiny_f32):
    """Prefix-cache-shared full blocks sit directly below the verify
    span: speculation (with rollbacks) must leave them byte-identical —
    a later admit of the same prompt still hits the cache and still
    produces identical tokens."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (17,), seed=6)[0]  # 2 full blocks cacheable
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8,
        speculative_k=4, drafter=_WrongDrafter(cfg.vocab_size),
    )
    first = _gen(eng, 0, prompt, 12)
    hits0 = eng.prefix_hits
    second = _gen(eng, 0, prompt, 12)  # hit: shares the cached blocks
    assert eng.prefix_hits == hits0 + 1
    third = _gen(eng, 0, prompt, 12)   # cache must still be intact
    assert eng.prefix_hits == hits0 + 2
    assert first == second == third


# ----------------------------------------------------- serving integration


def test_spec_preemption_storm_all_streams_complete(tiny_f32):
    """Preemption storm WITH speculation: 2x the pool's worth of
    generations, drafts verifying k+1-token spans under block pressure.
    Every stream completes with exactly the non-speculative tokens."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    prompts = _prompts(cfg, (9, 10, 11, 12, 13, 14), seed=7)
    big = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
    )
    refs = [_gen(big, 0, p, 25) for p in prompts]

    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, num_blocks=13,
        prefix_cache=False, speculative_k=4,
        drafter=ReplayDrafter([list(p) + r for p, r in zip(prompts, refs)]),
    )
    b = ContinuousBatcher(eng, max_batch_size=4, batch_wait_timeout_s=0.01)
    try:
        streams = [b.submit(tokens=p, max_new_tokens=25) for p in prompts]
        outs = [list(s) for s in streams]
        assert eng.preemptions >= 1, eng.stats()
        assert eng.spec_steps >= 1, eng.stats()
        for i, (o, r) in enumerate(zip(outs, refs)):
            assert o == r, (i, o, r)
    finally:
        b.close()


def test_batcher_streams_spec_bursts_in_order(tiny_f32, baselines):
    """Multi-token retirement: a verify step's accepted burst reaches the
    stream as individual tokens, in order, interleaved with another
    stream's — and the batcher's stats surface the spec counters."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    prompts, refs = baselines
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, speculative_k=4,
        drafter=ReplayDrafter([list(p) + r for p, r in zip(prompts, refs)]),
    )
    b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.05)
    try:
        s0 = b.submit(tokens=prompts[0], max_new_tokens=24)
        s1 = b.submit(tokens=prompts[1], max_new_tokens=24)
        o0, o1 = [], []
        t0 = threading.Thread(target=lambda: o0.extend(s0))
        t1 = threading.Thread(target=lambda: o1.extend(s1))
        t0.start(); t1.start()
        t0.join(timeout=120); t1.join(timeout=120)
        assert not t0.is_alive() and not t1.is_alive()
        assert o0 == refs[0] and o1 == refs[1]
        st = b.stats()
        assert st["spec_k"] == 4
        assert st["spec_accept_rate"] > 0.9, st
        assert st["spec_tokens_per_step"] > 2.0, st
    finally:
        b.close()


# ------------------------------------------------------------- robustness


def test_spec_bucketed_verify_shapes(tiny_f32, baselines):
    """Draft-length jitter must not churn the verify jit cache: lengths
    bucket to powers of two (plus k), so a drafter oscillating 1..k
    compiles O(log k) shapes."""
    cfg, params = tiny_f32
    prompts, refs = baselines

    class Jitter:
        def __init__(self, seq):
            self.replay = ReplayDrafter([seq])
            self.n = 0

        def propose(self, tokens, k):
            self.n += 1
            want = (self.n % 6) + 1  # 1..6, above and below every bucket
            return self.replay.propose(tokens, min(k, want))

    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, speculative_k=6,
        drafter=Jitter(list(prompts[2]) + refs[2]),
    )
    assert eng._k_buckets == (1, 2, 4, 6)
    assert _gen(eng, 0, prompts[2], 24) == refs[2]
    # verify widths stay on bucket boundaries: K1 in {2, 3, 5, 7}
    assert eng.spec_shapes <= {2, 3, 5, 7}, eng.spec_shapes


def test_spec_drafter_fault_degrades_to_plain_decode(tiny_f32, baselines):
    """A drafter that raises (or returns garbage) must cost nothing but
    speed: generation falls back to plain steps, tokens stay identical."""
    cfg, params = tiny_f32
    prompts, refs = baselines

    class Broken:
        def propose(self, tokens, k):
            raise RuntimeError("draft model fell over")

    class Garbage:
        def propose(self, tokens, k):
            return [10**9, -3, "x"]  # out-of-vocab / junk

    for drafter in (Broken(), Garbage()):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=1, block_tokens=8,
            speculative_k=4, drafter=drafter,
        )
        assert _gen(eng, 0, prompts[1], 24) == refs[1], type(drafter).__name__
        assert eng.spec_steps == 0  # every step fell back to plain decode


def test_spec_pressure_drops_drafts_before_preempting(tiny_f32):
    """Speculation must never cost a preemption that plain decode would
    not have paid: when the k+1-token spans cannot fit the pool, the
    step drops the drafts and proceeds single-token instead of evicting
    a generation."""
    cfg, params = tiny_f32
    p0, p1 = _prompts(cfg, (13, 13), seed=10)
    # 5 usable blocks; two 13-token prompts take 2 each -> 1 free. Each
    # slot's 5-token verify span (pos 13..17) crosses into block 2, so
    # the spec spans need 2 > 1 free — but the plain write (pos 13,
    # block 1, already owned) needs 0.
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=6,
        prefix_cache=False, speculative_k=4,
        drafter=_WrongDrafter(cfg.vocab_size),
    )
    plain = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=6,
        prefix_cache=False,
    )
    for e in (eng, plain):
        e.admit(0, {"tokens": p0, "max_new_tokens": 20})
        e.admit(1, {"tokens": p1, "max_new_tokens": 20})
        assert e.allocator.num_free == 1
    res = eng.step([0, 1])
    ref = plain.step([0, 1])
    assert set(res) == {0, 1}          # nobody was preempted
    assert eng.preemptions == 0
    assert eng.spec_steps == 0          # the step fell back to plain
    for s in (0, 1):
        toks = res[s][0]
        toks = list(toks) if isinstance(toks, (list, tuple)) else [toks]
        assert toks == [ref[s][0]]


def test_warmup_verify_precompiles_buckets(tiny_f32, baselines):
    """warmup_verify compiles every verify bucket out-of-band (bench /
    replica start), is idempotent, and its null-block probe writes leave
    generation untouched — greedy identity still holds afterwards."""
    cfg, params = tiny_f32
    prompts, refs = baselines
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, speculative_k=4,
        drafter=ReplayDrafter([list(prompts[0]) + refs[0]]),
    )
    assert eng.warmup_verify() == len(eng._k_buckets)
    assert eng.warmup_verify() == 0  # idempotent
    assert _gen(eng, 0, prompts[0], 24) == refs[0]
    # spec-off engines no-op
    assert PagedDecodeEngine(cfg, params, max_batch_size=1).warmup_verify() == 0


def test_spec_respects_max_new_and_seq_len(tiny_f32):
    """Caps: a burst must stop exactly at max_new_tokens, and a slot near
    max_seq_len must not verify past the rope tables."""
    cfg, params = tiny_f32  # max_seq_len 128
    prompt = _prompts(cfg, (17,), seed=8)[0]
    plain = PagedDecodeEngine(cfg, params, max_batch_size=1, block_tokens=8)
    ref = _gen(plain, 0, prompt, 7)
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, speculative_k=4,
        drafter=ReplayDrafter([list(prompt) + ref + [0] * 8]),
    )
    out = _gen(eng, 0, prompt, 7)
    assert out == ref and len(out) == 7

    # near the end of the context window: 126-token prompt, 2 writable
    # positions left — speculation must cap the span, finish cleanly, and
    # match the plain engine
    long_p = _prompts(cfg, (126,), seed=9)[0]
    ref2 = _gen(plain, 0, long_p, 10)
    eng2 = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, speculative_k=4,
        drafter=ReplayDrafter([list(long_p) + ref2 + [0] * 8]),
    )
    assert _gen(eng2, 0, long_p, 10) == ref2
