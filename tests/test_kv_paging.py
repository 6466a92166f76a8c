"""Paged KV-cache correctness + chaos (ISSUE 5 acceptance).

The paged subsystem must be INVISIBLE to the tokens: paged decode == a
greedy roll-out of the plain forward, token for token (solo and under the
dp x fsdp x tp dryrun), the fused block walk == the gather programs,
prefix hits skip prefill without changing output, copy-on-write isolates
forked generations, and a preemption storm — admitting past the block
pool's capacity — never crashes and every generation still completes
exactly as an unconstrained run would (recompute-on-readmit, greedy).
"""

import dataclasses
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _held_tree import check_held_tree
from ray_tpu.models import CONFIGS, init_params, make_forward
from ray_tpu.models.kv_paging import (
    BlockAllocator,
    InsufficientBlocksError,
    PagedDecodeEngine,
    PrefixCache,
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
    """Greedy-generate n tokens through the engine contract; releases the
    slot at the end."""
    tok, done = eng.admit(slot, {"tokens": prompt, "max_new_tokens": n})
    out = [tok]
    while not done:
        tok, done = eng.step([slot])[slot]
        out.append(tok)
    eng.release(slot)
    return out


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(make_forward(cfg))


def _assert_follows_forward(cfg, params, prompt, out, tol=1e-3, width=64):
    """`out` is the greedy roll-out of the plain forward, the reference every
    engine is held to: the WHOLE sequence is re-run through `make_forward`
    for each token (padded to one width so it compiles once; causal
    attention never sees the padding). Tokens are exact wherever the
    forward's top-two gap exceeds `tol`; past a nearer tie the streams may
    part."""
    forward = _forward(cfg)
    seq = np.zeros(width, np.int32)
    seq[:len(prompt)] = prompt
    for i, have in enumerate(out):
        at = len(prompt) + i
        logits = np.asarray(forward(params, seq[None]))[0, at - 1]
        want = int(np.argmax(logits))
        if want != have:
            top2 = np.sort(logits.astype(np.float32))[-2:]
            assert top2[1] - top2[0] < tol, (i, want, have, top2)
            return
        seq[at] = want


# ------------------------------------------------------------- allocator


def test_allocator_refcount_and_null_block():
    a = BlockAllocator(8)
    assert a.num_usable == 7 and a.num_free == 7
    blocks = a.alloc(3)
    assert 0 not in blocks and a.num_free == 4
    a.incref(blocks[0])
    a.decref(blocks[0])
    assert a.num_free == 4  # still held
    for b in blocks:
        a.decref(b)
    assert a.num_free == 7
    with pytest.raises(InsufficientBlocksError):
        a.alloc(8)
    with pytest.raises(ValueError):
        a.decref(blocks[0])  # double free


def test_prefix_cache_eviction_is_leaf_first():
    a = BlockAllocator(8)
    cache = PrefixCache(a, block_tokens=4)
    prompt = np.arange(12, dtype=np.int32)
    blocks = a.alloc(3)
    cache.register(prompt, blocks)
    for b in blocks:
        a.decref(b)  # only the cache holds them now
    assert cache.evictable() == 3
    # a one-block eviction takes the LEAF (deepest LRU), so the remaining
    # chain still matches a 2-block prefix
    assert cache.evict(1) == 1
    assert cache.match_count(prompt, 3) == 2


# ----------------------------------------------- paged == forward parity


def _interleaved(eng, prompts, lens):
    """Greedy generation of every prompt at once, one slot each, through
    the engine contract: {slot: tokens}."""
    outs, active = {}, []
    for s, p in enumerate(prompts):
        tok, done = eng.admit(s, {"tokens": p, "max_new_tokens": lens[s]})
        outs[s] = [tok]
        if not done:
            active.append(s)
    while active:
        for s, (tok, done) in eng.step(list(active)).items():
            outs[s].append(tok)
            if done:
                active.remove(s)
                eng.release(s)
    return outs


def test_paged_equals_forward_token_for_token(tiny_f32):
    """The acceptance contract: the paged engine's greedy output is the
    plain forward's greedy roll-out, across interleaved multi-slot decode
    with different prompt lengths (block boundaries land mid-generation)."""
    cfg, params = tiny_f32
    prompts = _prompts(cfg, (5, 9, 17, 30))
    paged = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, attention_impl="gather"
    )
    lens = {0: 12, 1: 9, 2: 20, 3: 5}
    outs = _interleaved(paged, prompts, lens)
    for s, p in enumerate(prompts):
        assert len(outs[s]) == lens[s]
        _assert_follows_forward(cfg, params, p, outs[s])


def test_paged_matches_forward_under_sharded_mesh(tiny_f32):
    """dp x fsdp x tp dryrun: the pool shards by KV_CACHE_AXES (blocks on
    the batch axes, kv_heads on tp) and the tokens still match the
    unsharded forward's roll-out."""
    cfg, params = tiny_f32
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    paged = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, rules=rules, mesh=mesh,
        attention_impl="gather",
    )
    spec = paged.pool["k"].sharding.spec
    assert spec[1] == ("dp", "fsdp") and spec[3] == "tp", spec
    assert paged.num_blocks % 4 == 0  # whole shards on dp x fsdp

    for i, p in enumerate(_prompts(cfg, (7, 19))):
        _assert_follows_forward(cfg, params, p, _gen(paged, i, p, 8))


@pytest.mark.parametrize("length,buckets", [(11, ((16,), (64,))),
                                            (30, ((32,), (128,)))])
def test_paged_prefill_buckets_do_not_change_output(tiny_f32, length, buckets):
    """Prompt padding to a larger bucket must be invisible: only positions
    < length are ever attended."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (length,))[0]

    def run(buckets):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=1, block_tokens=8,
            prefill_buckets=buckets,
        )
        return _gen(eng, 0, prompt, 6)

    assert run(buckets[0]) == run(buckets[1])


# ------------------------------------------------------------ prefix reuse


def test_prefix_hit_skips_prefill(tiny_f32):
    """Admitting a prompt whose prefix blocks are cached prefills ONLY the
    tail (asserted via the engine's prefill_tokens counter) and produces
    the exact same tokens as the cold admit."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (21,))[0]  # bt=8: 2 full blocks <= len-1
    eng = PagedDecodeEngine(cfg, params, max_batch_size=2, block_tokens=8)

    cold = _gen(eng, 0, prompt, 6)
    assert eng.prefix_hits == 0 and eng.prefill_tokens == 21
    hit = _gen(eng, 1, prompt, 6)
    assert hit == cold
    assert eng.prefix_hits == 1
    assert eng.prefix_tokens_reused == 16
    # only the 5 tokens past the shared 16-token span were prefilled
    assert eng.prefill_tokens == 21 + 5

    # divergent tail off the same prefix: shares the blocks, prefills its
    # own tail, and matches a fresh engine exactly (no contamination)
    other = prompt.copy()
    other[18:] = (other[18:] + 1) % cfg.vocab_size
    got = _gen(eng, 0, other, 6)
    fresh = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
    )
    assert got == _gen(fresh, 0, other, 6)
    assert eng.prefix_hits == 2


def test_prefix_cache_survives_release_and_evicts_under_pressure(tiny_f32):
    cfg, params = tiny_f32
    # pool of 5 usable blocks; each 17-token prompt takes 3 (2 cacheable)
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, num_blocks=6
    )
    prompts = _prompts(cfg, (17, 17, 17), seed=3)
    for p in prompts:
        _gen(eng, 0, p, 2)
    # three prompts x 2 cached blocks > pool: the LRU entries were evicted
    # to make room, never a crash, and the latest prompt still hits
    before = eng.prefill_tokens
    _gen(eng, 0, prompts[-1], 2)
    assert eng.prefill_tokens - before == 1
    assert eng.prefix_cache.evictions > 0


# ------------------------------------------------------------ copy-on-write


def test_fork_cow_isolation(tiny_f32):
    """Two generations forked off one cache (shared partial tail block)
    must diverge without contaminating each other: the first divergent
    write triggers copy-on-write, and both forks match solo engines
    teacher-forced the same way."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (13,))[0]
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, prefix_cache=False
    )
    eng.admit(0, {"tokens": prompt, "max_new_tokens": 30})
    for _ in range(2):
        eng.step([0])  # position 15: mid-block, the tail block is partial
    eng.fork(0, 1)
    eng.force_token(0, 5)
    eng.force_token(1, 9)
    outs = {0: [], 1: []}
    for _ in range(5):
        r = eng.step([0, 1])
        for s in (0, 1):
            outs[s].append(r[s][0])
    assert eng.cow_copies >= 1  # the shared tail block was un-shared

    for s, forced in ((0, 5), (1, 9)):
        solo = PagedDecodeEngine(
            cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
        )
        solo.admit(0, {"tokens": prompt, "max_new_tokens": 30})
        for _ in range(2):
            solo.step([0])
        solo.force_token(0, forced)
        ref = [solo.step([0])[0][0] for _ in range(5)]
        assert ref == outs[s], (s, ref, outs[s])


# ---------------------------------------------------- preemption + admission


def test_can_admit_budget_and_insufficient_blocks(tiny_f32):
    cfg, params = tiny_f32
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=7,
        prefix_cache=False,
    )  # 6 usable blocks
    big = {"tokens": _prompts(cfg, (30,))[0], "max_new_tokens": 30}
    small = {"tokens": _prompts(cfg, (9,), seed=1)[0], "max_new_tokens": 6}
    # a never-fits request reports ADMISSIBLE so the batcher routes it to
    # admit()'s hard ValueError instead of parking it at the head of the
    # line (where it would wedge all later admissions)
    assert eng.can_admit(big)      # ceil(60/8) = 8 > 6: route to hard fail
    assert eng.can_admit(small)    # ceil(15/8) = 2 <= 6
    eng.admit(0, small)            # takes 2 blocks
    # a prompt that would fit an EMPTY pool but not the current one raises
    # the retryable error (blocks free as generations retire)
    with pytest.raises(InsufficientBlocksError):
        eng.admit(1, {"tokens": _prompts(cfg, (33,), seed=2)[0],
                      "max_new_tokens": 4})  # needs 5, only 4 free
    # a prompt the pool can NEVER hold is a hard error, not a retry loop
    with pytest.raises(ValueError):
        eng.admit(1, {"tokens": _prompts(cfg, (60,), seed=2)[0],
                      "max_new_tokens": 4})  # needs 8 > 6 usable
    # slot 0 unharmed by the failed admissions
    tok, _ = eng.step([0])[0]
    assert isinstance(tok, int)


def test_idle_pool_impossible_admission_fails_hard(tiny_f32):
    """A request the idle pool can never satisfy — its own prefix hits pin
    cache blocks reclaim cannot touch — must fail with ValueError, not the
    retryable error (nothing is running, so parking would retry forever)."""
    cfg, params = tiny_f32
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=7
    )  # 6 usable
    base = _prompts(cfg, (41,), seed=11)[0]  # 6 blocks, 5 cacheable
    _gen(eng, 0, base, 2)
    # cache pins 5 blocks (the request's own hits — reclaim cannot touch
    # them once pinned); the extended prompt needs 7 total > 6 usable
    extended = np.concatenate([base, _prompts(cfg, (9,), seed=12)[0]])
    with pytest.raises(ValueError):
        eng.admit(0, {"tokens": extended, "max_new_tokens": 2})


def test_preempted_at_last_position_readmits(tiny_f32):
    """A generation preempted at position max_seq_len-1 parks a history of
    exactly max_seq_len tokens; readmission must still work — it emits the
    one remaining token (identical to the uninterrupted run) and finishes."""
    cfg, params = tiny_f32  # max_seq_len 128
    prompt = _prompts(cfg, (127,), seed=13)[0]
    ref_eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
    )
    t0, d0 = ref_eng.admit(0, {"tokens": prompt, "max_new_tokens": 5})
    assert not d0
    (t1, d1) = ref_eng.step([0])[0]
    assert d1  # position hit max_seq_len: uninterrupted run ends here

    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, prefix_cache=False
    )
    tok, done = eng.admit(0, {"tokens": prompt, "max_new_tokens": 5})
    assert tok == t0 and not done
    eng._preempt(0)  # park at position 127: history is 128 tokens
    [(_, parked)] = eng.take_preempted()
    assert len(parked["tokens"]) == cfg.max_seq_len
    rtok, rdone = eng.admit(1, parked)
    assert rdone and rtok == t1  # final token matches, stream completes


def test_never_fits_request_fails_fast_without_wedging(tiny_f32):
    """A request whose worst-case budget exceeds the whole pool must fail
    with a clear error even while the replica is busy — NOT park at the
    head of the line where it would block all later admissions."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=7,
        prefix_cache=False,
    )  # 6 usable
    b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.0)
    try:
        running = b.submit(tokens=_prompts(cfg, (9,), seed=20)[0],
                           max_new_tokens=30)  # worst ceil(39/8)=5 <= 6
        time.sleep(0.05)
        # worst case ceil((30+60)/8) = 12 > 6 usable: never fits
        doomed = b.submit(tokens=_prompts(cfg, (30,), seed=21)[0],
                          max_new_tokens=60)
        with pytest.raises(ValueError):
            list(doomed)
        # the line is NOT wedged: a normal request behind it completes
        ok = b.submit(tokens=_prompts(cfg, (9,), seed=22)[0],
                      max_new_tokens=3)
        assert len(list(ok)) == 3
        assert len(list(running)) == 30
    finally:
        b.close()


def test_preemption_storm_all_generations_complete(tiny_f32):
    """Chaos acceptance: submit 2x the pool's worth of generations through
    the ContinuousBatcher. The engine preempts (never crashes), preempted
    streams stay open, and every stream delivers EXACTLY the tokens an
    unconstrained engine produces."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    prompts = _prompts(cfg, (9, 10, 11, 12, 13, 14), seed=5)

    big = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
    )
    refs = [_gen(big, 0, p, 25) for p in prompts]

    # 12 usable blocks; each request worst-case ceil((14+25)/8) = 5 blocks
    # -> ~2 resident generations for 6 submitted (2x+ oversubscription,
    # counting the 4 slots the batcher is happy to fill)
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, num_blocks=13,
        prefix_cache=False,
    )
    b = ContinuousBatcher(eng, max_batch_size=4, batch_wait_timeout_s=0.01)
    try:
        streams = [b.submit(tokens=p, max_new_tokens=25) for p in prompts]
        outs = [list(s) for s in streams]
        assert eng.preemptions >= 1, eng.stats()
        for i, (o, r) in enumerate(zip(outs, refs)):
            assert o == r, (i, o, r)
        stats = b.stats()
        assert stats["kv_blocks_total"] == 12
        assert stats["preemptions"] == eng.preemptions
    finally:
        b.close()


def test_preempted_stream_survives_and_resumes(tiny_f32):
    """A single preempted generation, observed mid-flight: its stream is
    never errored/closed — tokens pause during the park and resume after
    readmission with no gap and no duplicates."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    p_long, p_short = _prompts(cfg, (9, 12), seed=7)
    big = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False
    )
    ref_long = _gen(big, 0, p_long, 40)
    ref_short = _gen(big, 0, p_short, 30)

    # 8 usable blocks: long alone fits (ceil(49/8)=7), adding short
    # (ceil(42/8)=6) forces a preemption while both run
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=2, block_tokens=8, num_blocks=9,
        prefix_cache=False,
    )
    b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.0)
    try:
        s1 = b.submit(tokens=p_long, max_new_tokens=40)
        time.sleep(0.05)
        s2 = b.submit(tokens=p_short, max_new_tokens=30)
        o1, o2 = [], []
        t1 = threading.Thread(target=lambda: o1.extend(s1))
        t2 = threading.Thread(target=lambda: o2.extend(s2))
        t1.start(); t2.start()
        t1.join(timeout=120); t2.join(timeout=120)
        assert not t1.is_alive() and not t2.is_alive()
        assert eng.preemptions >= 1, eng.stats()
        assert o1 == ref_long
        assert o2 == ref_short
        assert not s1.cut and not s2.cut
    finally:
        b.close()


# ------------------------------------------------------- jit-churn satellite


def test_paged_prefill_reuses_bucketed_compilations(tiny_f32):
    """Prefix hits of different block counts must land on the same
    bucketed (ctx_blocks, suffix_blocks) prefill key — compiles are
    bounded by the bucket table, not by observed block counts."""
    cfg, params = tiny_f32
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8,
        prefill_buckets=(16, 32, 64, 128),
    )
    base = _prompts(cfg, (17,), seed=9)[0]
    _gen(eng, 0, base, 2)        # cold: registers blocks 0,1
    _gen(eng, 0, base, 2)        # hit: ctx 16 tokens -> bucket 16 -> 2 blocks
    shorter = base.copy()
    shorter[9:] = (shorter[9:] + 1) % cfg.vocab_size
    _gen(eng, 0, shorter, 2)     # hit: ctx 8 tokens -> bucket 16 -> 2 blocks
    hit_keys = {k for k in eng.prefill_shapes if k[0] > 0}
    assert len(hit_keys) == 1, eng.prefill_shapes


def test_paged_engine_stats_surface(tiny_f32):
    cfg, params = tiny_f32
    eng = PagedDecodeEngine(cfg, params, max_batch_size=2, block_tokens=8)
    s = eng.stats()
    for key in ("kv_blocks_total", "kv_blocks_free", "kv_block_utilization",
                "preemptions", "prefix_hits", "cow_copies", "block_tokens"):
        assert key in s, key
    assert s["kv_blocks_total"] == s["kv_blocks_free"] == eng.num_blocks - 1


# ------------------------------------------------- the tree a replica holds


@pytest.mark.parametrize("impl", ["gather", "fused"])
@pytest.mark.parametrize("case", ["fresh", "swap", "float32"])
def test_engine_holds_its_weights_in_the_compute_dtype(case, impl):
    """A float32 tree is cast once, when the engine takes it (constructor
    and set_params alike), and tokens and logits are bit for bit those of
    the same programs handed the float32 tree, as they were before."""
    cfg = dataclasses.replace(CONFIGS["tiny"], dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    other = init_params(jax.random.PRNGKey(1), cfg)
    shared, = _prompts(cfg, (16,), seed=3)
    prompts = [np.concatenate([shared, p]) for p in _prompts(cfg, (7, 5))]
    check_held_tree(
        case, cfg, params, other, prompts, max_batch_size=2, block_tokens=8,
        max_seq_len=64, prefix_cache=True, attention_impl=impl)


def test_engine_owned_tree_is_held_cast_and_sharding_is_kept(tiny_f32):
    """A tree the engine builds itself is the one init_params would give,
    cast; a sharded tree keeps each leaf's sharding through the cast."""
    from ray_tpu.models.transformer import param_specs, serving_params
    from ray_tpu.parallel.sharding import tree_shardings

    cfg = dataclasses.replace(tiny_f32[0], dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(3), cfg)
    own = PagedDecodeEngine(cfg, seed=3, max_batch_size=1, block_tokens=8)
    given = PagedDecodeEngine(cfg, params, max_batch_size=1, block_tokens=8)
    for a, b in zip(jax.tree.leaves(own.params), jax.tree.leaves(given.params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    assert own.stats()["param_bytes"] == given.stats()["param_bytes"]
    assert own.stats()["param_dtype"] == "bfloat16"

    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    sharded = jax.device_put(params, tree_shardings(
        mesh, PRESET_RULES["fsdp_tp"], param_specs(cfg)))
    held = serving_params(cfg, sharded)
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(sharded)):
        assert a.sharding == b.sharding, (a.sharding, b.sharding)
    assert held["layers"]["wq"].dtype == jnp.bfloat16
    assert len(held["layers"]["wq"].sharding.device_set) == 8
    # the caller's tree is never touched: what is not cast is shared, and a
    # held tree goes through again as it is
    assert not sharded["layers"]["wq"].is_deleted()
    assert held["final_norm"] is sharded["final_norm"]
    again = serving_params(cfg, held)
    assert again["layers"]["wq"] is held["layers"]["wq"]


def test_preemption_sse_streams_survive():
    """End-to-end chaos: 4 SSE clients against a replica whose block pool
    holds ~2 generations. Preemptions fire mid-stream; every client's SSE
    socket still receives its full token count + [DONE] — the stream
    pauses during the park and resumes after readmission."""
    import json as _json
    import socket

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.batching import ContinuousBatcher

    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    try:
        @serve.deployment
        class Gen:
            def __init__(self):
                import dataclasses as _dc

                import jax as _jax
                import jax.numpy as _jnp

                from ray_tpu.models import CONFIGS as _CONFIGS
                from ray_tpu.models import init_params as _init_params
                from ray_tpu.models.kv_paging import (
                    PagedDecodeEngine as _Paged,
                )

                _cfg = _dc.replace(_CONFIGS["tiny"], dtype=_jnp.float32)
                self.engine = _Paged(
                    _cfg, _init_params(_jax.random.PRNGKey(0), _cfg),
                    max_batch_size=4, block_tokens=8, num_blocks=13,
                    prefix_cache=False, prefill_buckets=(16,),
                )
                self.batcher = ContinuousBatcher(
                    self.engine, max_batch_size=4, batch_wait_timeout_s=0.2
                )

            def __call__(self, body):
                stream = self.batcher.submit(
                    tokens=body["tokens"],
                    max_new_tokens=body.get("max_new_tokens"),
                )
                return serve.sse_stream(stream)

            def chaos_stats(self):
                return self.engine.stats()

        h = serve.run(Gen.bind(), name="paged_gen", route_prefix="/generate")
        host, port = serve.proxy_address().split(":")

        def client(i, out):
            body = _json.dumps({
                "tokens": [1 + i] * (9 + i), "max_new_tokens": 25,
            }).encode()
            s = socket.create_connection((host, int(port)), timeout=120)
            s.sendall(
                b"POST /generate HTTP/1.1\r\nHost: x\r\n"
                b"Content-Type: application/json\r\n"
                + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
            )
            buf = b""
            while b"0\r\n\r\n" not in buf:
                data = s.recv(65536)
                if not data:
                    break
                buf += data
            s.close()
            out[i] = buf

        outs = {}
        threads = [
            threading.Thread(target=client, args=(i, outs)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert set(outs) == {0, 1, 2, 3}, f"clients missing: {set(outs)}"
        for i, buf in outs.items():
            events = [ln for ln in buf.split(b"\n")
                      if ln.startswith(b"data: ")]
            # full generation on the wire despite preemption: 25 tokens +
            # the [DONE] terminator, never an early cut
            assert len(events) == 26, (i, len(events), buf[-200:])
            assert events[-1] == b"data: [DONE]"
        stats = h.chaos_stats.remote().result(timeout_s=10)
        assert stats["preemptions"] >= 1, stats
    finally:
        from ray_tpu import serve as _serve

        _serve.shutdown()
        ray_tpu.shutdown()


# ------------------------------------------- int8 KV + fused attention


def test_paged_int8_greedy_matches_fp(tiny_f32):
    """ISSUE 6 acceptance, held under teacher forcing: fed the fp engine's
    own tokens, the int8-pool engine picks the same next token at every
    position — except where the fp logits are themselves within the int8
    logit tolerance of a tie (test_int8_logits_within_tolerance bounds
    each logit's error by 0.1, so a top-2 gap under 0.2 may legitimately
    flip). Free-running identity on a random tiny model hinges on exactly
    those near-ties: one flip re-seeds everything downstream. The two int8
    implementations — gather and the fused block walk — agree exactly."""
    cfg, params = tiny_f32
    n_new = 12
    prompts = _prompts(cfg, (5, 9, 17, 30))
    fp = PagedDecodeEngine(cfg, params, max_batch_size=2, block_tokens=8)
    ref = [_gen(fp, i % 2, p, n_new) for i, p in enumerate(prompts)]
    got = {}
    for impl in ("gather", "fused"):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=2, block_tokens=8,
            kv_cache_dtype="int8", attention_impl=impl,
        )
        got[impl] = []
        for i, (p, r) in enumerate(zip(prompts, ref)):
            slot = i % 2
            tok, _ = eng.admit(slot, {"tokens": p, "max_new_tokens": n_new})
            out = [tok]
            for forced in r[:-1]:
                eng.force_token(slot, forced)
                out.append(eng.step([slot])[slot][0])
            eng.release(slot)
            got[impl].append(out)
        assert eng.stats()["kv_cache_dtype"] == "int8"
    assert got["fused"] == got["gather"]

    forward = _forward(cfg)
    for p, r, out in zip(prompts, ref, got["gather"]):
        seq = np.concatenate([p, r[:-1]]).astype(np.int32)
        logits = np.asarray(forward(params, seq[None]))[0, len(p) - 1:]
        top2 = np.sort(logits.astype(np.float32), axis=-1)[:, -2:]
        gap = top2[:, 1] - top2[:, 0]
        assert [int(t) for t in np.argmax(logits, -1)] == r  # fp == dense
        for i, (want, have) in enumerate(zip(r, out)):
            assert want == have or gap[i] < 0.2, (i, want, have, gap[i])


def test_fused_paged_matches_gather(tiny_f32, monkeypatch):
    """The fused decode step (block-in-place attention, no [B, W] gather)
    against the gather engine — the exact reference, itself held to the
    plain forward above — interleaved multi-slot; then the interpret-mode
    Pallas kernel for a couple of steps, so tier-1 proves the kernel
    inside the real decode loop, not just standalone."""
    import importlib

    cfg, params = tiny_f32
    prompts = _prompts(cfg, (5, 9, 17, 30))
    lens = dict.fromkeys(range(4), 10)
    gather = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, attention_impl="gather"
    )
    fused = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, attention_impl="fused"
    )
    expect = _interleaved(gather, prompts, lens)
    assert _interleaved(fused, prompts, lens) == expect
    assert fused.stats()["attention_impl"] == "fused"
    assert fused.stats()["attention_kernel"] == "xla"  # no chip here

    # the Pallas kernel (interpret mode) through the engine contract: off
    # the chip the op picks its XLA twin, so the op the programs call is
    # swapped for the kernel here, in the test
    op = importlib.import_module("ray_tpu.ops.paged_attention")
    monkeypatch.setattr(
        op, "paged_attention",
        functools.partial(op.paged_attention, impl="kernel"),
    )
    kern = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, attention_impl="fused"
    )
    assert _gen(kern, 0, prompts[0], 4) == expect[0][:4]
    assert op._LAST_IMPL == "kernel"


def test_attention_path_is_picked_once_from_the_platform(tiny_f32):
    """One decision, made at construction: `attention_impl=None` is "fused"
    on a TPU and "gather" anywhere else, by the platform the engine
    reports; "gather" and "fused" are the two seams the tests hold to each
    other, and anything else — the old `auto`, a backend's name — is
    refused before a request is admitted."""
    cfg, params = tiny_f32
    eng = PagedDecodeEngine(cfg, params, max_batch_size=1, block_tokens=8)
    stats = eng.stats()
    assert stats["platform"] == jax.devices()[0].platform
    want = "fused" if stats["platform"] == "tpu" else "gather"
    assert stats["attention_impl"] == want
    assert stats["attention_kernel"] == {"fused": "pallas",
                                         "gather": "gather"}[want]
    for bad in ("auto", "kernel", "xla", "fused:pallas", ""):
        with pytest.raises(ValueError, match="attention_impl"):
            PagedDecodeEngine(
                cfg, params, max_batch_size=1, block_tokens=8,
                attention_impl=bad,
            )


def test_fused_matches_gather_under_sharded_mesh(tiny_f32):
    """dp x fsdp x tp dryrun of the FUSED path: blocks sharded across
    dp/fsdp mean each shard sees a slice of the pool — the shard_map
    wrapper remaps global block ids, attends locally, and log-sum-exp
    merges the partial softmax. Tokens must still match the unsharded
    gather engine exactly (fp) and the int8 run must agree with solo
    int8."""
    cfg, params = tiny_f32
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    rules = PRESET_RULES["fsdp_tp"]
    gather = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, attention_impl="gather"
    )
    fused = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, rules=rules,
        mesh=mesh, attention_impl="fused",
    )
    spec = fused.pool["k"].sharding.spec
    assert spec[1] == ("dp", "fsdp") and spec[3] == "tp", spec
    for i, p in enumerate(_prompts(cfg, (7, 19))):
        assert _gen(fused, i, p, 8) == _gen(gather, i, p, 8), i

    solo8 = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8,
        kv_cache_dtype="int8", attention_impl="fused",
    )
    shard8 = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, rules=rules,
        mesh=mesh, kv_cache_dtype="int8", attention_impl="fused",
    )
    assert shard8.pool["k"].dtype == jnp.int8
    assert shard8.pool["k_scale"].sharding.spec[1] == ("dp", "fsdp")
    p = _prompts(cfg, (13,), seed=21)[0]
    assert _gen(shard8, 0, p, 8) == _gen(solo8, 0, p, 8)


def test_int8_logits_within_tolerance(tiny_f32):
    """fp-vs-int8 logit bound: prefill + one decode step through
    make_paged_decoder directly, comparing raw logits. Guards against the
    quantizer silently degrading past argmax robustness (the greedy
    parity test would then flip somewhere downstream)."""
    import jax as _jax

    from ray_tpu.models.transformer import (
        init_paged_kv_cache,
        make_paged_decoder,
        pack_decode_inputs,
        pack_prefill_inputs,
    )

    cfg, params = tiny_f32
    bt = 8
    prompt = _prompts(cfg, (21,))[0]
    padded = np.zeros(24, np.int32)
    padded[:21] = prompt
    table = np.zeros(8, np.int32)
    table[:4] = [1, 2, 3, 4]
    results = {}
    for name, kv_dtype in (("fp", None), ("int8", jnp.int8)):
        pool = init_paged_kv_cache(cfg, 8, bt, dtype=kv_dtype)
        prefill, step, _verify, _copy = make_paged_decoder(
            cfg, block_tokens=bt, kv_dtype=kv_dtype
        )
        _, lg_p, pool = prefill(
            params, pool, pack_prefill_inputs(table, padded, 21, 0),
            _jax.random.PRNGKey(0), 0, 24,
        )
        toks, _, positions = (
            np.array([int(prompt[0])], np.int32),
            None,
            np.array([21], np.int32),
        )
        wp = np.array([table[21 // bt]], np.int32)
        wo = np.array([21 % bt], np.int32)
        _, lg_d, pool = step(
            params, pool,
            pack_decode_inputs(table[None], toks, positions, wp, wo),
            _jax.random.PRNGKey(1),
        )
        results[name] = (np.asarray(lg_p), np.asarray(lg_d))
    for i in range(2):
        fp, i8 = results["fp"][i], results["int8"][i]
        err = np.abs(fp - i8).max()
        assert err < 0.1, (i, err)  # quantization noise, far below argmax gaps
        assert err > 0.0  # int8 actually engaged (not silently fp)


def test_fork_cow_isolation_int8(tiny_f32):
    """Copy-on-write under the int8 pool: the CoW copy must carry the
    per-block scales with the blocks — forks match solo int8 engines
    teacher-forced the same way."""
    cfg, params = tiny_f32
    prompt = _prompts(cfg, (13,))[0]

    def mk():
        return PagedDecodeEngine(
            cfg, params, max_batch_size=2, block_tokens=8,
            prefix_cache=False, kv_cache_dtype="int8",
        )

    eng = mk()
    eng.admit(0, {"tokens": prompt, "max_new_tokens": 30})
    for _ in range(2):
        eng.step([0])
    eng.fork(0, 1)
    eng.force_token(0, 5)
    eng.force_token(1, 9)
    outs = {0: [], 1: []}
    for _ in range(5):
        r = eng.step([0, 1])
        for s in (0, 1):
            outs[s].append(r[s][0])
    assert eng.cow_copies >= 1

    for s, forced in ((0, 5), (1, 9)):
        solo = mk()
        solo.admit(0, {"tokens": prompt, "max_new_tokens": 30})
        for _ in range(2):
            solo.step([0])
        solo.force_token(0, forced)
        ref = [solo.step([0])[0][0] for _ in range(5)]
        assert ref == outs[s], (s, ref, outs[s])


def test_preemption_storm_int8_all_streams_complete(tiny_f32):
    """The preemption/readmit chaos test re-run with the int8 pool:
    oversubscribed admissions preempt and recompute-on-readmit, and every
    stream still delivers exactly what an unconstrained int8 engine
    produces (readmission prefill re-quantizes whole blocks; parked
    history teacher-forces the already-emitted tokens, so the stream
    cannot fork from itself)."""
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg, params = tiny_f32
    prompts = _prompts(cfg, (9, 10, 11, 12, 13, 14), seed=5)
    big = PagedDecodeEngine(
        cfg, params, max_batch_size=1, block_tokens=8, prefix_cache=False,
        kv_cache_dtype="int8",
    )
    refs = [_gen(big, 0, p, 25) for p in prompts]
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=4, block_tokens=8, num_blocks=13,
        prefix_cache=False, kv_cache_dtype="int8",
    )
    b = ContinuousBatcher(eng, max_batch_size=4, batch_wait_timeout_s=0.01)
    try:
        streams = [b.submit(tokens=p, max_new_tokens=25) for p in prompts]
        outs = [list(s) for s in streams]
        assert eng.preemptions >= 1, eng.stats()
        for i, (o, r) in enumerate(zip(outs, refs)):
            assert o == r, (i, o, r)
    finally:
        b.close()


def test_pool_bytes_sizing_doubles_blocks(tiny_f32):
    """Byte-budget pool sizing: for the same HBM budget an int8 pool must
    report ~2x the kv_blocks_total of a bf16 pool — the capacity doubling
    admission and block-saturation autoscaling see directly."""
    import dataclasses as _dc

    from ray_tpu.models.transformer import paged_kv_block_bytes

    cfg, _ = tiny_f32
    bf16 = _dc.replace(cfg, dtype=jnp.bfloat16, max_seq_len=32)
    budget = 48 * paged_kv_block_bytes(bf16, 8)
    blocks = {}
    for dtype in ("fp", "int8"):
        eng = PagedDecodeEngine(
            bf16, max_batch_size=1, block_tokens=8, pool_bytes=budget,
            kv_cache_dtype=dtype,
        )
        s = eng.stats()
        blocks[dtype] = s["kv_blocks_total"]
        assert s["kv_block_bytes"] == paged_kv_block_bytes(
            bf16, 8, jnp.int8 if dtype == "int8" else bf16.dtype
        )
    # the budget is a CEILING: 48 blocks of bytes = 48 total = 47 usable
    # (the null block counts against the budget, never on top of it)
    assert blocks["fp"] == 47
    ratio = blocks["int8"] / blocks["fp"]
    assert 1.8 <= ratio <= 2.2, blocks


def test_autoscaling_block_saturation_signal():
    """Satellite: block saturation is a third scale-up signal — saturated
    pools demand more replicas even with idle slots and an empty queue."""
    from ray_tpu.serve.autoscaling import calculate_desired_num_replicas
    from ray_tpu.serve.deployment import AutoscalingConfig

    ac = AutoscalingConfig(min_replicas=1, max_replicas=8,
                           target_ongoing_requests=100.0,
                           target_kv_utilization=0.8)
    # queue shallow, slots quiet, but 96% of blocks in use -> scale up
    assert calculate_desired_num_replicas(
        ac, 1, 2, batch_slots=16, batch_load=2,
        kv_blocks_total=200, kv_blocks_free=8,
    ) == 3
    # headroom: block signal stays quiet
    assert calculate_desired_num_replicas(
        ac, 1, 2, batch_slots=16, batch_load=2,
        kv_blocks_total=200, kv_blocks_free=150,
    ) == 1
    # no paged engine: signal off entirely
    assert calculate_desired_num_replicas(ac, 1, 2) == 1
