"""What a model program costs the host (ISSUE 36): one upload, one program
launch and one fetch per decode step and per admission, no RNG dispatch at
temperature 0 — and the same tokens as before."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import CONFIGS, init_params
from ray_tpu.models.kv_paging import InsufficientBlocksError, PagedDecodeEngine
from ray_tpu.models.transformer import (
    TransformerConfig, make_forward, pack_decode_inputs, pack_prefill_inputs,
    split_host_row,
)
from ray_tpu.serve import telemetry


@functools.lru_cache(maxsize=None)
def _tiny():
    cfg = dataclasses.replace(CONFIGS["tiny"], dtype=jnp.float32,
                              max_seq_len=128)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _script(cfg, params, temperature, watch=None, **engine_kw):
    """A fixed script over one small pool: two admissions, a fork, a
    chunked prefill, a preemption and its re-admission, every stream run
    to its end -> ({stream: [tokens]}, engine). Stream "b" forks off "a"
    after five decode steps (inside a block: its first
    write copies the shared block); "c" prefills in 16-token chunks while the
    others decode; the pool (11 usable blocks of 8 tokens) cannot hold all
    three, so the newest stream is preempted and comes back when one of
    the others has finished. `watch(eng)` sees the engine before its first
    dispatch."""
    eng = PagedDecodeEngine(
        cfg, params, max_batch_size=3, block_tokens=8, num_blocks=12,
        prefill_chunk_tokens=16, temperature=temperature, seed=7, **engine_kw)
    if watch is not None:
        watch(eng)
    rng = np.random.default_rng(36)
    prompts = {"a": rng.integers(1, cfg.vocab_size, size=12),
               "c": rng.integers(1, cfg.vocab_size, size=40)}
    out = {"a": [], "b": [], "c": []}
    live = {}  # slot -> stream
    parked = []  # (stream, request)

    def collect(res):
        for slot, (toks, done) in res.items():
            toks = toks if isinstance(toks, list) else [toks]
            out[live[slot]].extend(int(t) for t in toks)
            if done:
                eng.release(slot)
                del live[slot]
        for slot, req in eng.take_preempted():
            parked.append((live.pop(slot), req))

    def admit(slot, stream, req):
        tok, done = eng.admit(slot, req)
        live[slot] = stream
        if tok is not None:
            collect({slot: (tok, done)})

    admit(0, "a", {"tokens": prompts["a"], "max_new_tokens": 30})
    for _ in range(5):
        collect(eng.step(sorted(live)))
    eng.fork(0, 1)
    live[1] = "b"
    for _ in range(3):
        collect(eng.step(sorted(live)))
    admit(2, "c", {"tokens": prompts["c"], "max_new_tokens": 20})
    while live or parked:
        if parked and len(live) < 2:
            stream, req = parked[0]
            slot = min(set(range(3)) - set(live))
            try:
                admit(slot, stream, req)
                parked.pop(0)
            except InsufficientBlocksError:
                pass
        collect(eng.step(sorted(live)))
    return out, eng


def _logprob_pairs(cfg, params):
    """(token, logprob) pairs of one short sampled generation."""
    eng = PagedDecodeEngine(cfg, params, max_batch_size=2, block_tokens=8,
                            temperature=0.7, seed=3, logprobs=True)
    prompt = np.random.default_rng(5).integers(1, cfg.vocab_size, size=10)
    pair, done = eng.admit(1, {"tokens": prompt, "max_new_tokens": 6})
    pairs = [pair]
    while not done:
        pair, done = eng.step([1])[1]
        pairs.append(pair)
    return pairs, eng


# ------------------------------------------- one upload, one fetch a dispatch

# the four pools the serving cells run, at a tiny width
_KINDS = {
    "dense": lambda: dataclasses.replace(
        CONFIGS["tiny"], dtype=jnp.float32, max_seq_len=128),
    "experts": lambda: dataclasses.replace(
        CONFIGS["tiny_moe"], n_layers=3, n_experts=8, top_k=3,
        max_seq_len=128, dtype=jnp.float32, moe_capacity_factor=None),
    "latent": lambda: TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_head=24, d_ff=32, max_seq_len=128, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_factor=4.0, rope_original_max=64, rope_mscale_all_dim=1.0,
        first_k_dense=1, d_ff_dense=96, n_experts=8, top_k=2,
        moe_scoring="sigmoid", moe_route_scale=2.0, n_shared_experts=1,
        moe_capacity_factor=None, hc_mult=4, dtype=jnp.float32),
    "hybrid": lambda: TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, max_seq_len=128, qk_norm=True, use_rope=False,
        norm_placement="post", layer_period=("linear",) * 3 + ("full",),
        linear_n_heads=2, linear_d_k=8, linear_d_v=16, dtype=jnp.float32),
}
# today's pairs of `_logprob_pairs` (recorded at the parent commit, where a
# second program scored the returned logits and a second fetch read them)
_PARENT_PAIRS = [
    (87, -6.7826151847839355), (236, -4.442203521728516),
    (53, -5.8857574462890625), (233, -4.36126708984375),
    (117, -3.7553701400756836), (121, -3.824838161468506)]


class _Answer:
    """A program's output, counting the device -> host copies taken of it."""

    def __init__(self, dev, log):
        self.dev, self.log = dev, log

    def __array__(self, *a, **k):
        self.log.append(self.dev.shape)
        return np.asarray(self.dev)


def _watch(eng):
    """Wrap the engine's two model programs -> a list of (uploads, fetches)
    of each dispatch, as the launch itself sees them: an argument leaf that
    is not a device array is a host -> device copy the launch makes (the
    only place the caller's transfer guard lets one through), a conversion
    of an output a device -> host copy."""
    seen = []

    def watched(program):
        def call(*args):
            host = [a for a in jax.tree_util.tree_leaves(args[:4])
                    if not isinstance(a, jax.Array)]
            assert all(isinstance(a, np.ndarray) and a.dtype == np.int32
                       for a in host), host
            fetched = []
            with jax.transfer_guard_host_to_device("allow"):
                out, logits, pool = program(*args)
            seen.append((len(host), fetched))
            return _Answer(out, fetched), _Answer(logits, fetched), pool
        return call

    eng._prefill, eng._decode_step = (
        watched(eng._prefill), watched(eng._decode_step))
    return seen


def _record_uploads(eng):
    """-> the list every later decode step appends its uploaded array to."""
    uploads, decode = [], eng._decode_step

    def call(params, pool, inputs, key):
        uploads.append(np.array(inputs))
        return decode(params, pool, inputs, key)

    call._cache_size = decode._cache_size
    eng._decode_step = call
    return uploads


@pytest.mark.parametrize("kind", [*_KINDS, "logprobs"])
def test_one_upload_one_program_one_fetch(kind):
    """A decode step and an admission at temperature 0: each launch takes
    exactly one host array up and exactly one answer is read back, with
    every other implicit transfer forbidden; no RNG split over a whole
    generation; the
    programs under their names; with experts the two counts still reach
    the span and the counters; in logprob mode the pairs are the parent's,
    out of the same one fetch."""
    if kind == "logprobs":
        cfg, params = _tiny()
        pairs, eng = _logprob_pairs(cfg, params)
        assert [int(t) for t, _ in pairs] == [t for t, _ in _PARENT_PAIRS]
        np.testing.assert_allclose(
            [lp for _, lp in pairs], [lp for _, lp in _PARENT_PAIRS],
            rtol=0, atol=1e-6)
        # sampled: one split a dispatch whose token is kept, nothing else
        st = eng.stats()
        assert st["rng_dispatches"] == len(pairs)
        assert st["host_transfers"] == {
            "dispatches": len(pairs), "uploads": len(pairs),
            "fetches": len(pairs)}
        eng.release(1)
    else:
        cfg = _KINDS[kind]()
        eng = None
    tel = telemetry.ServeTelemetry(recorder_capacity=64)
    if eng is None:
        eng = PagedDecodeEngine(
            cfg, max_batch_size=3, seed=0, block_tokens=8, telemetry=tel,
            prefill_buckets=(16,), **({"n_snapshots": 3}
                                      if kind == "hybrid" else {}))
    else:  # the same programs, greedy
        eng = PagedDecodeEngine(cfg, params, max_batch_size=3, block_tokens=8,
                                logprobs=True, telemetry=tel,
                                prefill_buckets=(16,))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 11, 5)]
    # compile outside the guard
    eng.admit(0, {"tokens": prompts[0], "max_new_tokens": 8})
    eng.step([0])
    programs = eng._prefill.programs, eng._decode_step
    seen = _watch(eng)
    before = eng.stats()["host_transfers"]
    # no implicit transfer anywhere in an admission or a step but the one
    # array each launch takes up (the CPU backend honours the guard host ->
    # device; device -> host it is the answers' conversions that count)
    with jax.transfer_guard("disallow"):
        eng.admit(1, {"tokens": prompts[1], "max_new_tokens": 8})
        eng.step([0, 1])
    assert [(ups, len(got)) for ups, got in seen] == [(1, 1), (1, 1)]
    B = eng.max_batch_size
    want = B + (2 if cfg.n_experts else 0) + (B if kind == "logprobs" else 0)
    assert seen[1][1] == [(want,)]  # the [B, V] logits stay on the device
    after = eng.stats()["host_transfers"]
    assert {k: after[k] - before[k] for k in after} == {
        "dispatches": 2, "uploads": 2, "fetches": 2}
    # a whole generation: no key is split, every dispatch is 1 + 1
    eng.admit(2, {"tokens": prompts[2], "max_new_tokens": 6})
    live = [0, 1, 2]
    while live:
        for s, (_, done) in eng.step(live).items():
            if done:
                eng.release(s)
                live.remove(s)
    st = eng.stats()
    assert st["rng_dispatches"] == 0
    ht = st["host_transfers"]
    assert ht["dispatches"] == ht["uploads"] == ht["fetches"] == (
        st["prefill_chunks"] + st["decode_steps"])
    decodes = [e["args"] for e in tel.recorder.snapshot()
               if e["name"] == "decode"]
    assert decodes and all(
        (a["uploads"], a["fetches"]) == (1, 1) for a in decodes)
    if cfg.n_experts:
        assert all(a["moe_touched"] >= a["moe_hottest"] > 0 for a in decodes)
        assert st["moe_touched"] == sum(a["moe_touched"] for a in decodes)
        assert st["moe_hottest"] == sum(a["moe_hottest"] for a in decodes)
    else:
        assert "moe_touched" not in decodes[0] and st["moe_touched"] == 0
    # the trace finds the programs by these names
    prefills, decode = programs
    nmax = eng.blocks_per_slot
    key = jax.random.PRNGKey(0)
    text = decode.lower(
        eng.params, eng.pool, np.zeros((B, nmax + 4), np.int32), key).as_text()
    assert "module @jit_paged_decode " in text[:200]
    for fn in prefills.values():
        text = fn.lower(eng.params, eng.pool,
                        np.zeros(3 + 16 + nmax, np.int32), key).as_text()
        assert "module @jit_paged_prefill " in text[:200]


@pytest.mark.parametrize("how", ["released", "preempted"])
@pytest.mark.parametrize("kind", [*_KINDS])
def test_a_finished_stream_leaves_nothing_in_its_row(kind, how):
    """The decode program runs every row, so a row that kept its last token
    and length would make the next steps' cost depend on what finished
    streams held (with experts: the groups a stale token routes to). A
    released or preempted slot goes up as a never-used one: all zeros."""
    cfg = _KINDS[kind]()
    eng = PagedDecodeEngine(
        cfg, max_batch_size=3, seed=0, block_tokens=8, prefill_buckets=(16,),
        **({"n_snapshots": 3} if kind == "hybrid" else {}))
    rng = np.random.default_rng(2)
    eng.admit(0, {"tokens": rng.integers(1, cfg.vocab_size, size=13),
                  "max_new_tokens": 8})
    eng.admit(2, {"tokens": rng.integers(1, cfg.vocab_size, size=6),
                  "max_new_tokens": 8})
    for _ in range(3):
        eng.step([0, 2])
    if how == "released":
        eng.release(0)
    else:
        eng._preempt(0)
    uploads = _record_uploads(eng)
    eng.step([2])
    (up,) = uploads
    assert not up[0].any() and not up[1].any()  # finished; never used
    assert up[2].any()


def test_the_packed_layouts_round_trip():
    """What the programs slice is what the host packed, column for column."""
    rng = np.random.default_rng(0)
    tables = rng.integers(0, 99, size=(3, 5)).astype(np.int32)
    cols = [rng.integers(0, 99, size=3) for _ in range(4)]
    packed = pack_decode_inputs(tables, *cols)
    assert packed.dtype == np.int32 and packed.shape == (3, 9)
    np.testing.assert_array_equal(packed[:, :5], tables)
    for i, col in enumerate(cols):
        np.testing.assert_array_equal(packed[:, 5 + i], col)
    one = pack_prefill_inputs(tables[1], np.arange(16).reshape(1, 16), 9, 24,
                              row=2)
    assert one.dtype == np.int32 and one.tolist() == (
        [9, 24, 2] + list(range(16)) + tables[1].tolist())
    lps = np.array([-1.5, -0.25, -3.0], np.float32)
    row = np.concatenate([[4, 5, 6], [7, 8], lps.view(np.int32)]).astype(
        np.int32)
    toks, load, got = split_host_row(row, 3, experts=True, logprobs=True)
    assert (toks.tolist(), load.tolist()) == ([4, 5, 6], [7, 8])
    np.testing.assert_array_equal(got, lps)
    toks, load, got = split_host_row(row[:3], 3)
    assert toks.tolist() == [4, 5, 6] and load is None and got is None


# ------------------------------------------------------- the same tokens

# what `_script` sampled at the parent commit (temperature 1.0, seed 7)
_PARENT_SAMPLED = {
    "a": [145, 115, 193, 88, 11, 152, 65, 140, 47, 184, 63, 99, 144, 226, 245,
          185, 101, 106, 39, 31, 99, 116, 33, 135, 25, 248, 213, 192, 153, 73],
    "b": [237, 140, 16, 77, 151, 58, 73, 122, 85, 238, 247, 95, 67, 121, 210,
          95, 36, 35, 195, 202, 51, 122, 159, 118],
    "c": [20, 246, 102, 92, 207, 79, 72, 65, 89, 225, 43, 117, 224, 237, 157,
          219, 40, 249, 203, 23],
}


def _greedy_rollout(cfg, params, prompt, n, width=64):
    """n greedy tokens of the plain float32 forward behind `prompt`."""
    forward = jax.jit(make_forward(cfg))
    seq = np.zeros(width, np.int32)
    seq[:len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n):
        logits = np.asarray(forward(params, seq[None]))[0, at - 1]
        seq[at] = int(np.argmax(logits))
    return seq[len(prompt):len(prompt) + n].tolist()


@pytest.mark.parametrize("temperature", [0.0, 1.0], ids=["greedy", "sampled"])
def test_the_script_gives_the_parents_tokens(temperature):
    """Admissions, a fork with its copy-on-write, a chunked prefill, a
    preemption and the re-admission: greedy tokens are the float32
    forward's; sampled tokens (temperature 1.0, seed 7) are the ones the
    parent commit drew, so the key stream is what it was — one split per
    completing admission and per decode step, none for a chunk in between."""
    cfg, params = _tiny()
    out, eng = _script(cfg, params, temperature)
    st = eng.stats()
    assert (st["preemptions"], st["chunked_prefills"], st["cow_copies"],
            st["prefix_hits"]) == (1, 2, 1, 1)
    if temperature:
        assert out == _PARENT_SAMPLED
        # a, c and c's re-admission complete; every decode step draws
        assert st["rng_dispatches"] == 3 + st["decode_steps"]
        return
    assert st["rng_dispatches"] == 0
    rng = np.random.default_rng(36)
    a = rng.integers(1, cfg.vocab_size, size=12)
    c = rng.integers(1, cfg.vocab_size, size=40)
    assert out["a"] == _greedy_rollout(cfg, params, a, 30)
    assert out["b"] == out["a"][6:]  # the fork carries on where "a" was
    assert out["c"] == _greedy_rollout(cfg, params, c, 20)


@pytest.mark.parametrize("temperature", [0.0, 1.0], ids=["greedy", "sampled"])
def test_kv_blocks_walked_is_the_live_slots_live_blocks(temperature):
    """What a paged kernel has to visit in a decode step, beside what it
    could: `kv_blocks_walked` = the sum over the step's slots of
    ceil((pos + 1) / block_tokens), read here off the positions the step
    UPLOADED, and `kv_table_blocks` = B x Nmax; both on the `engine.decode`
    span and summed in stats(). Counting them changes no token and adds no
    program: the script compiles what it compiled at the parent."""
    cfg, params = _tiny()
    tel = telemetry.ServeTelemetry(recorder_capacity=1024)
    uploads = []
    out, eng = _script(
        cfg, params, temperature, telemetry=tel,
        watch=lambda eng: uploads.append(_record_uploads(eng)))
    (uploads,) = uploads
    if temperature:
        assert out == _PARENT_SAMPLED
    st = eng.stats()
    decodes = [e["args"] for e in tel.recorder.snapshot()
               if e["name"] == "decode"]
    assert len(decodes) == len(uploads) == st["decode_steps"] > 40
    bt, nmax, B = eng.block_tokens, eng.blocks_per_slot, eng.max_batch_size
    for up, args in zip(uploads, decodes):
        positions = up[:, nmax + 1]
        assert args["kv_blocks_walked"] == sum(
            -(-(int(positions[s]) + 1) // bt) for s in args["slots"])
        assert args["kv_tokens"] == sum(
            int(positions[s]) + 1 for s in args["slots"])
        assert args["kv_table_blocks"] == B * nmax
    assert st["kv_blocks_walked"] == sum(a["kv_blocks_walked"] for a in decodes)
    assert st["kv_table_blocks"] == B * nmax * len(decodes)
    assert 0 < st["kv_blocks_walked"] < st["kv_table_blocks"]
    # one decode program whatever the occupancy, the parent's prefill shapes
    assert eng._decode_step._cache_size() == 1
    assert sorted(eng.prefill_shapes) == [(0, 2), (2, 2), (4, 2), (8, 2)]
    assert len(eng._prefill.programs) == 4
