"""Core-runtime microbenchmarks, tracked per round like bench.py.

Reference parity: python/ray/_private/ray_perf.py (the microbenchmark
definitions behind release/microbenchmark). Prints one JSON line with the
headline rates; the targets (VERDICT r1 item 4) are >=5k tasks/s submit,
>=2.5k sync actor calls/s, >=10 GB/s 100MB put, plus an anti-regression
floor on cross-node 256MB transfer (VERDICT weak #3).
"""

from __future__ import annotations

import json
import os
import sys
import time


def bench_task_submit(n: int = 2000) -> float:
    import ray_tpu

    @ray_tpu.remote
    def noop():
        return None

    # warm the worker pool
    ray_tpu.get([noop.remote() for _ in range(8)])
    t0 = time.perf_counter()
    refs = [noop.remote() for _ in range(n)]
    submit_dt = time.perf_counter() - t0
    ray_tpu.get(refs)
    return n / submit_dt


def bench_task_roundtrip(n: int = 500) -> float:
    import ray_tpu

    @ray_tpu.remote
    def noop():
        return None

    ray_tpu.get(noop.remote())
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(noop.remote())
    return n / (time.perf_counter() - t0)


def bench_actor_sync(n: int = 2000) -> float:
    import ray_tpu

    @ray_tpu.remote
    class A:
        def m(self):
            return None

    a = A.remote()
    ray_tpu.get(a.m.remote())
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(a.m.remote())
    return n / (time.perf_counter() - t0)


def bench_actor_async(n: int = 5000) -> float:
    import ray_tpu

    @ray_tpu.remote
    class A:
        def m(self):
            return None

    a = A.remote()
    ray_tpu.get(a.m.remote())
    t0 = time.perf_counter()
    ray_tpu.get([a.m.remote() for _ in range(n)])
    return n / (time.perf_counter() - t0)


def host_memcpy_gbps(mb: int = 100, iters: int = 5) -> float:
    """This host's single-copy floor: put() necessarily pays ONE copy into
    the shm slab, so its ceiling is this number (the 10 GB/s absolute
    target assumes a multicore host where the slab's parallel copy engages;
    on small hosts the honest target is relative to this floor)."""
    import numpy as np

    src = np.frombuffer(np.random.default_rng(0).bytes(mb * 1024 * 1024), dtype=np.uint8)
    dst = bytearray(len(src))
    memoryview(dst)[:] = src.data  # warm dst pages
    t0 = time.perf_counter()
    for _ in range(iters):
        memoryview(dst)[:] = src.data
    return mb * iters / 1024 / (time.perf_counter() - t0)


def bench_put_gbps(mb: int = 100, iters: int = 5) -> float:
    import numpy as np

    import ray_tpu
    from ray_tpu._private.worker import global_worker

    data = np.random.default_rng(0).bytes(mb * 1024 * 1024)
    arr = np.frombuffer(data, dtype=np.uint8)
    # each ref is dropped before the next put (ray_perf semantics): the
    # slab allocator then reuses warm pages instead of first-touch faulting.
    # The sync round-trip per warmup iteration makes the head PROCESS the
    # deletes before the timed loop — otherwise the timed puts allocate
    # cold pages and measure page faults, not the store.
    for _ in range(5):
        ref = ray_tpu.put(arr)
        del ref
        global_worker.request({"t": "nodes"})
    t0 = time.perf_counter()
    for _ in range(iters):
        ref = ray_tpu.put(arr)
        del ref
    dt = time.perf_counter() - t0
    return mb * iters / 1024 / dt


def bench_get_gbps(mb: int = 100, iters: int = 5) -> float:
    import numpy as np

    import ray_tpu

    arr = np.frombuffer(np.random.default_rng(0).bytes(mb * 1024 * 1024), dtype=np.uint8)
    ref = ray_tpu.put(arr)
    ray_tpu.get(ref)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = ray_tpu.get(ref)
    dt = time.perf_counter() - t0
    del out
    return mb * iters / 1024 / dt


def bench_weight_broadcast_ms(mb: int = 10, n_actors: int = 16) -> float:
    """IMPALA-shaped: learner weights -> rollout fleet. put() once (into
    shm), every actor maps the same buffer zero-copy; the measured number
    is the full driver-side latency until every actor holds the weights
    (VERDICT r2 item 5: 10MB to 16 actors, target <50ms localhost)."""
    import numpy as np

    import ray_tpu

    @ray_tpu.remote
    class Rollout:
        def set_weights(self, w):
            self._w = w
            return w.shape[0]

    actors = [Rollout.remote() for _ in range(n_actors)]
    w = np.frombuffer(np.random.default_rng(0).bytes(mb * 1024 * 1024), dtype=np.float32)
    ref = ray_tpu.put(w)
    ray_tpu.get([a.set_weights.remote(ref) for a in actors])  # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        ref = ray_tpu.put(w)
        ray_tpu.get([a.set_weights.remote(ref) for a in actors])
        best = min(best, time.perf_counter() - t0)
    for a in actors:
        ray_tpu.kill(a)
    return best * 1000.0


def bench_decode_speedup(new_tokens: int = 48) -> dict:
    """Continuous-batching win, gated: ONE engine stepping 8 KV-cache
    decode slots together vs serial single-slot decode on the same host.
    Batched decode amortizes the per-step dispatch + weight reads over the
    whole batch, so the tokens/s ratio must clear 2x (the anti-regression
    floor; the measured ratio is usually far higher). Runs on CPU (tiny
    model) — this gates the BATCHING mechanics, not the chip. Both engines
    run PAGED (block-table gather in the decode step), so the gate also
    proves paging did not regress the batched-decode win."""
    import dataclasses

    import numpy as np

    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.models import CONFIGS

    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=256)
    B = 8
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(B, 16)
    )
    never = {"max_new_tokens": 10**9}

    batched = PagedDecodeEngine(cfg, max_batch_size=B, seed=0)
    slots = list(range(B))
    for s in slots:
        batched.admit(s, {"tokens": prompts[s], **never})
    batched.step(slots)  # decode compile + warm
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        batched.step(slots)
    batched_tps = B * new_tokens / (time.perf_counter() - t0)

    serial = PagedDecodeEngine(cfg, max_batch_size=1, seed=0)
    serial.admit(0, {"tokens": prompts[0], **never})
    serial.step([0])
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        serial.step([0])
    serial_tps = new_tokens / (time.perf_counter() - t0)
    return {
        "decode_batched_tokens_per_s": round(batched_tps, 1),
        "decode_serial_tokens_per_s": round(serial_tps, 1),
        "decode_batched_speedup_x": round(batched_tps / serial_tps, 2),
    }


def bench_decode_long_context(
    prefix_tokens: int = 0, batch: int = 2, new_tokens: int = 12,
) -> dict:
    """Long-context decode: the HBM-bound regime where paged attention's
    cost actually lives (a 4k-token prefix means every decode step reads
    ~4k tokens of K/V per layer — bandwidth, not compute). Three engines
    decode the same prompts:

      gather/fp   the block-table gather step (pre-fused reference path)
      fused/fp    ops/paged_attention block-in-place walk, same bytes read
      fused/int8  + int8 blocks: half the bytes per resident token

    Gated: fused/fp must BEAT gather/fp at the same dtype (the kernel win,
    isolated from quantization), and the int8 pool must hold ~2x the
    blocks of the fp pool for the same byte budget (the capacity win
    admission/autoscaling sees). The prefix admits in chunks through the
    prefix cache — each admit reuses the prior chunks' blocks — so setup
    stays ~linear instead of one quadratic 4k prefill."""
    import dataclasses

    import numpy as np

    from ray_tpu.models import CONFIGS, init_params
    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.models.transformer import paged_kv_block_bytes

    import jax
    import jax.numpy as jnp

    prefix_tokens = prefix_tokens or int(
        os.environ.get("RAY_TPU_MICROBENCH_LONGCTX_TOKENS", "4096")
    )
    chunk = 1024
    bt = 64
    cfg = dataclasses.replace(
        CONFIGS["tiny"], dtype=jnp.float32, max_seq_len=prefix_tokens + 2 * bt
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, prefix_tokens)
    )

    def build(impl, dtype):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=batch, block_tokens=bt,
            attention_impl=impl, kv_cache_dtype=dtype, seed=0,
            prefill_buckets=(chunk,),
        )
        for s in range(batch):
            for end in range(chunk, prefix_tokens + 1, chunk):
                eng.admit(s, {"tokens": prompts[s][:end],
                              "max_new_tokens": 10**9})
                if end < prefix_tokens:
                    eng.release(s)
        eng.step(list(range(batch)))  # compile + warm
        return eng

    # a 12-token timed window on a shared host is one scheduler hiccup
    # away from inverting the comparison, and timing the engines
    # back-to-back lets slow drift (thermal, co-tenant load) bias one
    # side. So: build + warm all three, then INTERLEAVE timed repeats
    # round-robin and keep each engine's best — best-of-repeats is the
    # noise-free estimate, interleaving makes drift hit all three alike.
    engines = {
        "gather_fp": build("gather", "fp"),
        "fused_fp": build("fused", "fp"),
        "fused_int8": build("fused", "int8"),
    }
    slots = list(range(batch))
    best = {name: 0.0 for name in engines}
    for _ in range(3):
        for name, eng in engines.items():
            t0 = time.perf_counter()
            for _ in range(new_tokens):
                eng.step(slots)
            r = batch * new_tokens / (time.perf_counter() - t0)
            best[name] = max(best[name], r)
    gather_fp = best["gather_fp"]
    fused_fp = best["fused_fp"]
    fused_int8 = best["fused_int8"]

    # capacity: same byte budget, blocks counted by the engine's own
    # byte-budget sizing — int8 should land ~2x fp. The probe config uses
    # bf16 (the serving dtype) so the ratio states the production claim;
    # this engine above runs f32 only because CPU timing wants it
    small = dataclasses.replace(
        cfg, dtype=jnp.bfloat16, max_seq_len=4 * bt
    )
    budget = 64 * paged_kv_block_bytes(small, bt)
    blocks = {}
    for dtype in ("fp", "int8"):
        e = PagedDecodeEngine(
            small, params=None, max_batch_size=1, block_tokens=bt,
            pool_bytes=budget, kv_cache_dtype=dtype, seed=0,
        )
        blocks[dtype] = e.stats()["kv_blocks_total"]
    return {
        "decode_long_context_tokens_per_s": round(fused_int8, 1),
        "decode_long_context_fused_fp_tokens_per_s": round(fused_fp, 1),
        "decode_long_context_gather_tokens_per_s": round(gather_fp, 1),
        "decode_long_context_fused_speedup_x": round(fused_fp / gather_fp, 2),
        "decode_long_context_int8_speedup_x": round(fused_int8 / gather_fp, 2),
        "kv_int8_blocks_ratio": round(blocks["int8"] / blocks["fp"], 2),
    }


def bench_decode_speculative(new_tokens: int = 96, k: int = 4) -> dict:
    """Speculative-decoding win at LOW batch (B=1 — the lone-stream
    latency regime where batching can't help), gated: propose-k drafting
    + one batched k+1-token verify step must beat per-token decode by >=
    1.5x tokens/s. The drafter is a perfect-draft REPLAY of the
    non-speculative engine's own greedy output (the pluggable
    small-draft-model hook), so the gate certifies the
    propose/verify/commit MECHANICS — one verify step must genuinely
    outrun the k+1 single-token steps it replaces; drafter QUALITY is a
    model/workload property this CPU tiny-model row cannot measure.
    In-row identity assertion: the speculative engine's greedy output
    must equal the non-speculative engine's token-for-token, else the
    speedup is forced to 0 (fails the gate loudly).

    Same discipline as the long-context row: both engines build + warm
    first (the warm run is also the identity check), then timed repeats
    INTERLEAVE round-robin and each side keeps its best — host drift hits
    both alike, best-of-repeats drops scheduler hiccups."""
    import dataclasses

    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.models.speculative import ReplayDrafter

    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=256)
    prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, size=24)

    base = PagedDecodeEngine(cfg, max_batch_size=1, seed=0)

    def run(eng):
        tok, done = eng.admit(0, {"tokens": prompt,
                                  "max_new_tokens": new_tokens})
        out = [tok]
        while not done:
            toks, done = eng.step([0])[0]
            out.extend(toks if isinstance(toks, (list, tuple)) else [toks])
        eng.release(0)
        return out

    recorded = run(base)  # greedy reference + prefill/decode warmup
    spec = PagedDecodeEngine(
        cfg, max_batch_size=1, seed=0, speculative_k=k,
        drafter=ReplayDrafter([list(prompt) + recorded]),
    )
    identical = run(spec) == recorded  # verify-step warmup + identity gate

    def timed(eng):
        """tokens/s over the STEP loop (prefill excluded: the gate is the
        per-token decode rate, and both sides prefill identically)."""
        tok, done = eng.admit(0, {"tokens": prompt,
                                  "max_new_tokens": new_tokens})
        n = 1
        t0 = time.perf_counter()
        while not done:
            toks, done = eng.step([0])[0]
            n += len(toks) if isinstance(toks, (list, tuple)) else 1
        dt = time.perf_counter() - t0
        eng.release(0)
        return (n - 1) / dt

    best_off = best_on = 0.0
    for _ in range(3):
        best_off = max(best_off, timed(base))
        best_on = max(best_on, timed(spec))
    speedup = best_on / best_off if identical else 0.0
    out = {
        "spec_off_tokens_per_s": round(best_off, 1),
        "spec_on_tokens_per_s": round(best_on, 1),
        "spec_decode_speedup_x": round(speedup, 2),
        "spec_accept_rate": spec.stats()["spec_accept_rate"],
        "spec_greedy_identical": int(identical),
    }
    out.update(_spec_verify_longctx())
    return out


def _spec_verify_longctx(
    prefix_tokens: int = 0, batch: int = 2, new_tokens: int = 24, k: int = 4,
) -> dict:
    """Long-context half of the speculative row (ISSUE 13), gated: the
    fused multi-query verify step (q = k+1 through the block-in-place
    walk + in-flight log-sum-exp merge) must at least MATCH the
    gather-window verify at long context — before the multi-query
    kernel, speculation re-paid the gather cost the fused decode path
    had eliminated, so long-context streams LOST part of the fused win
    the moment they drafted. Perfect-draft replay (mechanics, not
    drafter quality), in-row greedy-identity assertion zeroes the
    speedup on divergence, warm-then-interleaved best-of-repeats."""
    import dataclasses

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import CONFIGS, init_params
    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.models.speculative import ReplayDrafter

    prefix_tokens = prefix_tokens or int(
        os.environ.get("RAY_TPU_MICROBENCH_LONGCTX_TOKENS", "4096")
    )
    chunk = min(1024, prefix_tokens)
    bt = 64
    cfg = dataclasses.replace(
        CONFIGS["tiny"], dtype=jnp.float32, max_seq_len=prefix_tokens + 2 * bt
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, prefix_tokens)
    )
    slots = list(range(batch))

    def admit_chunked(eng):
        # the long prefix admits in chunks through the prefix cache (setup
        # stays ~linear); re-admission after release hits the cache whole.
        # Returns each slot's FIRST sampled token (from the full-prompt
        # admission) — it is part of the slot's history, so the replay
        # drafter's recorded sequences must include it or they never
        # prefix-match and speculation silently never runs
        first = {}
        for s in slots:
            for end in range(chunk, prefix_tokens + 1, chunk):
                t, _ = eng.admit(s, {"tokens": prompts[s][:end],
                                     "max_new_tokens": 10**9})
                if end < prefix_tokens:
                    eng.release(s)
            first[s] = int(t)
        return first

    plain = PagedDecodeEngine(
        cfg, params, max_batch_size=batch, block_tokens=bt, seed=0,
        prefill_buckets=(chunk,),
    )
    refs = {s: [t] for s, t in admit_chunked(plain).items()}
    for _ in range(new_tokens - 1):
        r = plain.step(slots)
        for s in slots:
            refs[s].append(r[s][0])

    def build(impl):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=batch, block_tokens=bt, seed=0,
            prefill_buckets=(chunk,), attention_impl=impl, speculative_k=k,
            drafter=ReplayDrafter(
                [list(prompts[s]) + refs[s] for s in slots]
            ),
        )
        return eng, admit_chunked(eng)

    def run(eng, first):
        outs = {s: [first[s]] for s in slots}
        while min(len(o) for o in outs.values()) < new_tokens:
            for s, (toks, _) in eng.step(slots).items():
                outs[s].extend(
                    toks if isinstance(toks, (list, tuple)) else [toks]
                )
        return outs

    engines = {"gather": build("gather"), "fused": build("fused")}
    identical = True
    for eng, first in engines.values():  # warm + identity
        o = run(eng, first)
        identical = identical and all(
            o[s][:new_tokens] == refs[s] for s in slots
        )
    # the gate certifies the VERIFY path: if the drafter never engaged
    # (spec_steps == 0) the timed loop would measure plain decode and the
    # comparison would be vacuous — zero the metric so the gate fails loud
    engaged = all(e.spec_steps > 0 for e, _ in engines.values())

    def timed(eng):
        for s in slots:
            eng.release(s)
            # re-admit whole: the prefix cache serves every full block, so
            # only the tail re-prefills — setup off the timed path
            eng.admit(s, {"tokens": prompts[s], "max_new_tokens": 10**9})
        n = 0
        t0 = time.perf_counter()
        while n < batch * new_tokens:
            for toks, _ in eng.step(slots).values():
                n += len(toks) if isinstance(toks, (list, tuple)) else 1
        return n / (time.perf_counter() - t0)

    best = {name: 0.0 for name in engines}
    for _ in range(3):
        for name, (eng, _) in engines.items():
            best[name] = max(best[name], timed(eng))
    ok = identical and engaged
    speedup = best["fused"] / best["gather"] if ok else 0.0
    return {
        "spec_verify_ctx_tokens": prefix_tokens,
        "spec_verify_engaged": int(engaged),
        "spec_verify_gather_tokens_per_s": round(best["gather"], 1),
        "spec_verify_fused_tokens_per_s": round(best["fused"], 1),
        "spec_verify_fused_speedup_x": round(speedup, 2),
    }


def bench_decode_mixed_traffic(
    prefix_tokens: int = 0, chunk: int = 256, decode_slots: int = 2,
    base_steps: int = 32,
) -> dict:
    """Mixed-traffic tail latency (ISSUE 13's scheduling gate): decode
    p99 inter-token latency measured WHILE a long prompt streams into the
    same running batch as prefill chunks (`prefill_chunk_tokens`), gated
    two ways against the decode-only baseline on the same engine:

      decode_mixed_p99_ratio_x <= bound   chunk steps interleave with
        decode steps, so the worst inter-token gap a decode stream sees
        is ~one chunk's compute — BOUNDED, load-independent of prompt
        length. A scheduler regression (multiple chunks coalescing into
        one step, or a silent whole-prefill fallback) blows this by an
        order of magnitude.
      decode_chunk_stall_reduction_x >= bound   the same prompt admitted
        WHOLE stalls every decode stream for its entire prefill; chunked
        admission must cut that head-of-line spike by >= 4x (measured
        ~12-20x: the ratio grows with prompt length — that is the point).

    The engine runs the production shape: fused attention, chunked
    prefill ON, prefix cache OFF (a cache hit would skip the very
    prefill being measured). All chunk-prefill compile keys are warmed by
    a full throwaway admission first."""
    import dataclasses

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import CONFIGS, init_params
    from ray_tpu.models.kv_paging import PagedDecodeEngine

    prefix_tokens = prefix_tokens or int(
        os.environ.get("RAY_TPU_MICROBENCH_LONGCTX_TOKENS", "4096")
    )
    bt = 64
    cfg = dataclasses.replace(
        CONFIGS["tiny"], dtype=jnp.float32, max_seq_len=prefix_tokens + 4 * bt
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    dec_prompts = rng.integers(0, cfg.vocab_size, size=(decode_slots, 128))
    long_warm = rng.integers(0, cfg.vocab_size, size=prefix_tokens)
    long_timed = rng.integers(0, cfg.vocab_size, size=prefix_tokens)
    B = decode_slots + 1
    dslots = list(range(decode_slots))
    lslot = decode_slots

    def build(chunk_tokens, buckets):
        eng = PagedDecodeEngine(
            cfg, params, max_batch_size=B, block_tokens=bt,
            attention_impl="fused", prefill_chunk_tokens=chunk_tokens,
            prefix_cache=False, seed=0, prefill_buckets=buckets,
        )
        for s in dslots:
            eng.admit(s, {"tokens": dec_prompts[s],
                          "max_new_tokens": 10**9})
        eng.step(dslots)  # decode compile + warm
        return eng

    eng = build(chunk, (128, chunk))
    # warm EVERY chunk-prefill compile key (ctx buckets double up the
    # prompt, so a 4k prompt walks ~log2 distinct (ctx, chunk) shapes)
    eng.admit(lslot, {"tokens": long_warm, "max_new_tokens": 1})
    while eng.stats()["prefilling"]:
        eng.step(dslots + [lslot])
    eng.release(lslot)

    base = []
    for _ in range(base_steps):
        t0 = time.perf_counter()
        eng.step(dslots)
        base.append(time.perf_counter() - t0)

    eng.admit(lslot, {"tokens": long_timed, "max_new_tokens": 1})
    mixed = []
    while eng.stats()["prefilling"]:
        t0 = time.perf_counter()
        eng.step(dslots + [lslot])
        mixed.append(time.perf_counter() - t0)
    eng.release(lslot)

    # the head-of-line spike chunking removes: the same prompt admitted
    # whole (chunking OFF) blocks the loop for its entire prefill
    whole = build(0, (128, prefix_tokens))
    whole.admit(lslot, {"tokens": long_warm, "max_new_tokens": 1})
    whole.release(lslot)  # prefill compile
    t0 = time.perf_counter()
    whole.admit(lslot, {"tokens": long_timed, "max_new_tokens": 1})
    stall = time.perf_counter() - t0

    p99_base = float(np.percentile(base, 99))
    p99_mixed = float(np.percentile(mixed, 99))
    return {
        "mixed_traffic_prompt_tokens": prefix_tokens,
        "mixed_traffic_chunk_tokens": chunk,
        "decode_only_p99_ms": round(p99_base * 1000, 2),
        "decode_mixed_p99_ms": round(p99_mixed * 1000, 2),
        "decode_mixed_p99_ratio_x": round(p99_mixed / p99_base, 2),
        "whole_prompt_stall_ms": round(stall * 1000, 1),
        "decode_chunk_stall_reduction_x": round(stall / p99_mixed, 2),
    }


def bench_prefix_hit(trials: int = 3) -> dict:
    """Prefix-reuse win, gated: admitting a prompt whose prefix blocks are
    already in the PagedDecodeEngine's hash-trie must beat the cold admit
    of the same prompt by >= 2x — the hit prefills only the (one-token)
    tail while the cold path recomputes the whole prompt. Both compile
    paths are warmed on a throwaway prompt first; each trial uses a FRESH
    prompt so its first admit is a true cold miss."""
    import dataclasses
    import statistics

    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.models.kv_paging import PagedDecodeEngine

    bt = 32
    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=512)
    eng = PagedDecodeEngine(
        cfg, max_batch_size=2, seed=0, block_tokens=bt, num_blocks=128,
    )
    rng = np.random.default_rng(0)
    # 15 full blocks + 1 tail token: the hit path prefills ONE token while
    # the cold path recomputes all 481 (the realistic shared-system-prompt
    # shape — the shared span dwarfs the per-request tail)
    plen = 15 * bt + 1
    one = {"max_new_tokens": 1}

    def admit_ms(prompt):
        t0 = time.perf_counter()
        eng.admit(0, {"tokens": prompt, **one})
        dt = (time.perf_counter() - t0) * 1000
        eng.release(0)
        return dt

    warm = rng.integers(0, cfg.vocab_size, size=plen)
    admit_ms(warm)  # cold-path compile
    admit_ms(warm)  # hit-path compile
    cold, hit = [], []
    for _ in range(trials):
        prompt = rng.integers(0, cfg.vocab_size, size=plen)
        cold.append(admit_ms(prompt))
        hit.append(admit_ms(prompt))
    cold_ms = statistics.median(cold)
    hit_ms = statistics.median(hit)
    return {
        "prefix_hit_cold_ms": round(cold_ms, 2),
        "prefix_hit_ms": round(hit_ms, 2),
        "prefix_hit_speedup_x": round(cold_ms / max(hit_ms, 1e-9), 2),
    }


def bench_serve_cross_replica(trials: int = 3) -> dict:
    """Cross-replica prefix transfer win, gated (--only row): serving a
    prompt whose prefix blocks arrive from a PEER engine over the
    transfer path (export -> pack -> wire-check -> unpack -> import ->
    admit) must beat the cold full prefill of the same prompt by >= 1.5x
    — the import pays numpy copies plus a pool scatter instead of
    recomputing attention over the whole shared span. The speedup only
    counts if the importing engine's greedy continuation is TOKEN-
    IDENTICAL to the cold engine's: any divergence zeroes the metric
    (and so fails the gate) — a fast wrong answer is worthless."""
    import dataclasses
    import statistics

    import jax
    import numpy as np

    from ray_tpu.models import CONFIGS, init_params
    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.serve.kv_transfer import pack_payload, unpack_payload

    bt = 32
    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=1152)
    params = init_params(jax.random.PRNGKey(0), cfg)

    def mk():
        return PagedDecodeEngine(
            cfg, params, max_batch_size=2, seed=0, block_tokens=bt,
            num_blocks=192, model_id="bench",
        )

    def gen(eng, prompt, payload=None):
        """(time-to-first-token ms, greedy tokens) for one generation."""
        req = {"tokens": prompt, "max_new_tokens": 8}
        if payload is not None:
            req["kv_import"] = payload
        t0 = time.perf_counter()
        tok, done = eng.admit(0, req)
        ttft = (time.perf_counter() - t0) * 1000
        out = [tok]
        while not done:
            tok, done = eng.step([0])[0]
            out.append(tok)
        eng.release(0)
        return ttft, out

    rng = np.random.default_rng(0)
    plen = 31 * bt + 1  # a ~1k shared span dwarfs the per-request tail
    # three long-lived engines, as in a real fleet: the peer that computed
    # the prefix, the replica that imports it, the replica that recomputes
    # it cold. Each is warmed on a throwaway prompt first (per-engine jit
    # closures: a fresh engine's first admit pays ~40x in compile) — every
    # trial's prompt is fresh, so the cold engine's admit stays a true miss
    src, dst, cold = mk(), mk(), mk()
    warm = rng.integers(0, cfg.vocab_size, size=plen)
    gen(src, warm)
    gen(cold, warm)
    gen(dst, warm, unpack_payload(*pack_payload(
        src.export_prefix(np.asarray(warm, np.int32))
    )))
    cold_ts, imp_ts, identical, payload_bytes = [], [], True, 0
    for _ in range(trials):
        prompt = rng.integers(0, cfg.vocab_size, size=plen)
        _, out_src = gen(src, prompt)  # peer computes + caches the chain
        cold_ms, out_cold = gen(cold, prompt)
        # the import path pays: export gather + pack + wire check + unpack
        # + pool scatter + tail-only admit (the decode tail is identical
        # on both paths and counted in neither — gen times admit only)
        t0 = time.perf_counter()
        meta, buf = pack_payload(
            src.export_prefix(np.asarray(prompt, np.int32))
        )
        payload = unpack_payload(meta, buf)
        transfer_ms = (time.perf_counter() - t0) * 1000
        imp_ttft, out_imp = gen(dst, prompt, payload)
        cold_ts.append(cold_ms)
        imp_ts.append(transfer_ms + imp_ttft)
        payload_bytes = int(buf.size)
        identical = identical and (out_src == out_cold == out_imp)
    cold_ms = statistics.median(cold_ts)
    imp_ms = statistics.median(imp_ts)
    speedup = cold_ms / max(imp_ms, 1e-9) if identical else 0.0
    return {
        "cross_replica_cold_ttft_ms": round(cold_ms, 2),
        "cross_replica_import_ms": round(imp_ms, 2),
        "cross_replica_payload_mb": round(payload_bytes / 2**20, 3),
        "cross_replica_greedy_identical": identical,
        "cross_replica_prefix_hit_speedup_x": round(speedup, 2),
    }


def bench_serve_weight_swap(new_tokens: int = 48, n_streams: int = 4) -> dict:
    """Live weight hot-swap latency cost, gated (--only row, needs a
    cluster for the bulk plane + pubsub): decode p99 inter-token latency
    measured while a WeightPublisher -> WeightSubscriber swap lands
    mid-generation must stay within 10x the quiescent p99 on the same
    batcher. The swap preempts every live slot and recomputes their
    histories under the new weights (see kv_paging.set_params), so the
    stall IS the product — n_streams/total gaps sit above the 99th
    percentile by construction, which makes p99 land inside the stall:
    the gate bounds the stall itself, not the steady state around it.
    Any stream that drops or comes back short zeroes the row (ratio 999):
    a fast swap that loses streams is worthless. weight_swap_publish_s
    (flatten + chunked puts + manifest push) ships informational."""
    import dataclasses
    import threading

    import jax
    import numpy as np

    from ray_tpu.models import CONFIGS, init_params
    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.serve.batching import ContinuousBatcher
    from ray_tpu.serve.weight_swap import WeightPublisher, WeightSubscriber

    cfg = CONFIGS["tiny"]
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    versions = [init_params(k, cfg) for k in keys]
    engine = PagedDecodeEngine(
        cfg, versions[0], max_batch_size=n_streams, temperature=0.0,
        num_blocks=128, seed=0, telemetry=False,
    )
    batcher = ContinuousBatcher(engine, telemetry=False)
    sub = WeightSubscriber(engine, "bench_swap", batcher=batcher).start()
    pub = WeightPublisher("bench_swap")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=8) for _ in range(n_streams)]

    def drain(stream, gaps, toks):
        last = None
        while True:
            items, done = stream.next_batch(max_items=1, wait_s=30.0)
            now = time.perf_counter()
            if items:
                if last is not None:
                    gaps.append(now - last)
                last = now
                toks.extend(items)
            if done:
                return

    def phase(swap_params=None, swap_version=None):
        """Run n_streams concurrent generations to completion; returns
        (all inter-token gaps, per-stream token counts, publish seconds)."""
        streams = [
            batcher.submit(tokens=np.asarray(p, np.int32),
                           max_new_tokens=new_tokens)
            for p in prompts
        ]
        gaps = [[] for _ in streams]
        toks = [[] for _ in streams]
        threads = [
            threading.Thread(target=drain, args=(s, g, t), daemon=True)
            for s, g, t in zip(streams, gaps, toks)
        ]
        for t in threads:
            t.start()
        publish_s = 0.0
        if swap_params is not None:
            # let the streams reach steady-state decode, then land the
            # swap mid-generation through the live plane
            while min(len(t) for t in toks) < new_tokens // 3:
                time.sleep(0.005)
            t0 = time.perf_counter()
            pub.publish(swap_params, version=swap_version)
            publish_s = time.perf_counter() - t0
            deadline = time.time() + 30.0
            while engine.weight_version != swap_version and time.time() < deadline:
                time.sleep(0.005)
        for t in threads:
            t.join(timeout=60.0)
        return (
            [g for gs in gaps for g in gs],
            [len(t) for t in toks],
            publish_s,
        )

    # warmup pays every one-time jit: prefill + decode buckets AND the
    # swap path's readmit prefill (preempted histories land in a longer
    # prefill bucket the plain path never compiles) — the measured phase
    # then times the swap itself, not a first-touch compile
    phase(swap_params=versions[1], swap_version=1)
    q_gaps, q_counts, _ = phase()
    s_gaps, s_counts, publish_s = phase(swap_params=versions[2], swap_version=2)
    survived = (
        all(c == new_tokens for c in q_counts + s_counts)
        and engine.weight_version == 2
        and engine.weight_swaps == 2
    )
    q_p99 = float(np.percentile(q_gaps, 99)) if q_gaps else 0.0
    s_p99 = float(np.percentile(s_gaps, 99)) if s_gaps else 0.0
    ratio = (s_p99 / max(q_p99, 1e-9)) if survived else 999.0
    sub.stop()
    batcher.close()
    return {
        "weight_swap_quiescent_p99_ms": round(q_p99 * 1000, 2),
        "weight_swap_during_p99_ms": round(s_p99 * 1000, 2),
        "weight_swap_publish_s": round(publish_s, 3),
        "weight_swap_streams_survived": survived,
        "weight_swap_p99_ratio_x": round(ratio, 2),
    }


def bench_decode_telemetry_overhead(
    new_tokens: int = 128, batch: int = 8,
) -> dict:
    """Telemetry-plane cost, gated: the full serving loop (ContinuousBatcher
    over a PagedDecodeEngine — per-token TTFT/inter-token observes, per-step
    gauges, flight-recorder events) with telemetry + recorder ON must hold
    >= 0.95x the tokens/s of the identical loop with telemetry OFF. The
    plane is supposed to be lock-cheap (deque appends, histogram observes)
    next to a jax dispatch; this row is the anti-regression tripwire that
    keeps it so. Same discipline as the other decode rows: build + warm
    both sides, then INTERLEAVE timed repeats and keep each side's best."""
    import dataclasses

    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.models.kv_paging import PagedDecodeEngine
    from ray_tpu.serve import telemetry
    from ray_tpu.serve.batching import ContinuousBatcher

    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=256)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, 16)
    )
    # force=True: the row must measure the plane even if the host exports
    # RAY_TPU_SERVE_TELEMETRY=0; 'off' passes telemetry=False explicitly
    tel = telemetry.get_telemetry(force=True)

    def build(tel_arg):
        eng = PagedDecodeEngine(
            cfg, max_batch_size=batch, seed=0, telemetry=tel_arg,
        )
        b = ContinuousBatcher(
            eng, max_batch_size=batch, batch_wait_timeout_s=0.05,
            telemetry=tel_arg,
        )
        return b

    def run(b):
        streams = [
            b.submit(tokens=list(prompts[s]), max_new_tokens=new_tokens)
            for s in range(batch)
        ]
        t0 = time.perf_counter()
        n = 0
        for s in streams:
            for _ in s:
                n += 1
        return n / (time.perf_counter() - t0)

    sides = {"on": build(tel), "off": build(False)}
    for b in sides.values():
        run(b)  # compile + warm (prefill/decode jits shared via cache)
    best = {name: 0.0 for name in sides}
    # 5 repeats, ALTERNATING order per round: the batcher loop thread +
    # consumer thread make this row noisier than the engine-direct rows
    # on small hosts, and a fixed on-then-off order would let slow drift
    # (GC, thermal) bias one side; best-of-5 with both orders keeps the
    # ~1-2% true telemetry cost measurable under ~5% scheduler noise
    for i in range(5):
        order = ("on", "off") if i % 2 == 0 else ("off", "on")
        for name in order:
            best[name] = max(best[name], run(sides[name]))
    for b in sides.values():
        b.close()
    return {
        "decode_telemetry_on_tokens_per_s": round(best["on"], 1),
        "decode_telemetry_off_tokens_per_s": round(best["off"], 1),
        "decode_telemetry_overhead_ratio_x": round(
            best["on"] / max(best["off"], 1e-9), 3
        ),
    }


def bench_decode_spec_realtext(new_tokens: int = 48, k: int = 4) -> dict:
    """MEASURED (not gated): the n-gram drafter's accept rate on REAL
    text — tokenizer-encoded English prompts through the model-hub
    fixture checkpoint (tests/fixtures/hub_gpt2_tiny: real byte-level BPE
    vocab, real safetensors weights path). PR 7 gated the speculative
    MECHANICS with a perfect-draft replay; what it could not measure was
    what self-drafting actually earns on real token streams — this row
    closes that question on CPU and records the answer next to the gated
    rows. Accept rate here is a property of the drafter x this tiny
    fixture model's output distribution, so it is recorded, never
    asserted; the gated spec row above stays the mechanics certificate."""
    out = {
        "spec_realtext_available": 0,
        "spec_accept_rate_realtext": 0.0,
        "spec_tokens_per_step_realtext": 0.0,
    }
    fixture = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tests", "fixtures", "hub_gpt2_tiny",
    )
    try:
        from ray_tpu.models.hub import measure_realtext_spec

        m = measure_realtext_spec(fixture, k=k, new_tokens=new_tokens)
        out.update(
            spec_realtext_available=1,
            spec_accept_rate_realtext=m["spec_accept_rate"],
            spec_tokens_per_step_realtext=m["spec_tokens_per_step"],
        )
    except Exception as e:  # fixture missing/unreadable: recorded, not fatal
        print(f"[microbench] realtext spec row unavailable: {e!r}",
              file=sys.stderr)
    return out


def bench_train_dcn_plane() -> dict:
    """Training DCN-plane wins, gated (--only row): the interleaved-1F1B
    pipeline schedule and the int8+error-feedback DCN gradient exchange,
    measured in a child process holding 8 virtual CPU devices (a 2-slice x
    4-device mesh — the parent process's jax backend is already claimed at
    its own device count, so the topology needs a fresh interpreter).

      pipeline_bubble_reduction_x >= 1.3   GPipe bubble over interleaved
        bubble at the measured shape (pp=4, n_mb=4, v=2: (3/7)/(3/11) =
        11/7 ~ 1.57). The ratio only counts if the interleaved schedule's
        outputs AND gradients match the sequential oracle and the compiled
        HLO ships the same dcn-crossing hop list as GPipe (same count,
        same one-copy payload per hop) — a faster wrong schedule, or one
        that pays for its ICI hop multiplier with DCN traffic, zeroes the
        metric and fails the gate loudly.
      dcn_grad_bytes_ratio_x >= 3.5   fp32 gradient all-reduce bytes over
        the int8 exchange's bytes on the dcn tier (measured ~3.93 @
        block=256: s8 payload + per-block f32 shared scales). Zeroed
        unless the int8 run's ICI bytes are EXACTLY the fp32 run's (the
        compression must be dcn-only) and its loss trajectory stays within
        5e-3 of fp32 over the measured steps (error feedback working).
    """
    import subprocess

    zeros = {
        "pipeline_interleave_parity": 0,
        "pipeline_dcn_hops_invariant": 0,
        "pipeline_bubble_gpipe": 0.0,
        "pipeline_bubble_interleaved": 0.0,
        "pipeline_bubble_reduction_x": 0.0,
        "dcn_grad_bytes_fp32": 0,
        "dcn_grad_bytes_int8": 0,
        "dcn_grad_ici_bytes_delta": -1,
        "dcn_grad_loss_delta": -1.0,
        "dcn_grad_bytes_ratio_x": 0.0,
    }
    env = dict(
        os.environ,
        RAY_TPU_MICROBENCH_CHILD="train_dcn_plane",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    )
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], env=env,
            capture_output=True, text=True,
            timeout=int(os.environ.get(
                "RAY_TPU_MICROBENCH_TRIAL_TIMEOUT_S", "900"
            )),
        )
    except subprocess.TimeoutExpired:
        print("[microbench] train_dcn_plane child timed out", file=sys.stderr)
        return zeros
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and set(zeros) <= set(obj):
            return obj
        break
    print(f"[microbench] train_dcn_plane child produced no JSON: "
          f"{proc.stderr[-800:]}", file=sys.stderr)
    return zeros


def _train_dcn_plane_child() -> dict:
    """Runs in the 8-device child: measure, self-check, print one JSON."""
    import dataclasses

    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import MeshSpec, build_multislice_mesh, dp_outer
    from ray_tpu.parallel.pipeline import (
        bubble_fraction, interleaved_stage_order, pipeline_apply,
    )
    from ray_tpu.train.step import (
        default_optimizer, make_sharded_init, make_train_step,
    )
    from ray_tpu.util.collective import (
        assert_no_cross_slice, mesh_collective_report,
    )
    from jax.sharding import Mesh

    out = {}

    # ---- interleaved-1F1B: parity + DCN-hop invariance + bubble ----
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("dcn", "pp", "dp"))
    pp, v, n_mb, rows = 4, 2, 4, 8
    ws = jax.random.normal(jax.random.PRNGKey(0), (rows, 16, 16)) / 4.0
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    def stage_fn(w, xs):
        return jnp.tanh(xs @ w)

    def pipe(vv, w, xv):
        return pipeline_apply(
            stage_fn, w, xv, mesh=mesh, n_microbatches=n_mb,
            axis_name=("dcn", "pp"), virtual_stages_per_device=vv,
            stage_order="schedule",
        )

    def seq(w):
        r = x
        for i in range(rows):
            r = jnp.tanh(r @ w[i])
        return r

    order = interleaved_stage_order(rows, pp, v)
    ws_sched = jnp.take(ws, jnp.asarray(order), axis=0)
    out_v = jax.jit(lambda w, xv: pipe(v, w, xv))(ws_sched, x)
    g_v = jax.jit(
        jax.grad(lambda w: jnp.sum(pipe(v, w, x) ** 2))
    )(ws_sched)
    g_ref = jax.grad(lambda w: jnp.sum(seq(w) ** 2))(ws)
    parity = bool(
        np.allclose(np.asarray(out_v), np.asarray(seq(ws)), atol=1e-5)
        and np.allclose(
            np.asarray(g_v), np.asarray(g_ref)[np.asarray(order)], atol=1e-4
        )
    )

    def dcn_hops(vv, w):
        hlo = jax.jit(
            jax.value_and_grad(lambda wv: jnp.sum(pipe(vv, wv, x) ** 2))
        ).lower(w).compile().as_text()
        rep = mesh_collective_report(hlo, mesh)
        assert_no_cross_slice(rep)
        return sorted(
            op.payload_bytes for op in rep["ops"]
            if op.crosses_dcn and op.kind == "collective-permute"
        )

    invariant = dcn_hops(1, ws) == dcn_hops(v, ws_sched) != []
    b1 = bubble_fraction(n_mb, pp, 1)
    bv = bubble_fraction(n_mb, pp, v)
    out.update(
        pipeline_interleave_parity=int(parity),
        pipeline_dcn_hops_invariant=int(invariant),
        pipeline_bubble_gpipe=round(b1, 4),
        pipeline_bubble_interleaved=round(bv, 4),
        pipeline_bubble_reduction_x=round(
            b1 / bv if parity and invariant else 0.0, 2
        ),
    )

    # ---- int8 + EF gradient exchange: dcn-only byte drop ----
    # scan_layers=False so every gradient collective is a top-level HLO op:
    # the static counter counts while-body ops once, which would undercount
    # the fp32 baseline and understate the ratio
    cfg = dataclasses.replace(
        CONFIGS["tiny"], n_layers=2, dtype=jnp.float32, scan_layers=False
    )
    topo, rules = dp_outer(
        2, MeshSpec(dp=4), fsdp_params=False, tensor_parallel=False
    )
    tmesh = build_multislice_mesh(topo)

    def batch(i):
        return {
            "tokens": jnp.asarray(
                np.random.default_rng(100 + i).integers(
                    0, cfg.vocab_size, size=(16, 33)
                ),
                jnp.int32,
            ),
            "mask": jnp.ones((16, 33), jnp.int32),
        }

    def run(compression, n_steps=5):
        opt = default_optimizer(lr=1e-3, warmup=1)
        init_fn, shardings = make_sharded_init(
            cfg, tmesh, rules, opt, dcn_grad_compression=compression
        )
        state = init_fn(jax.random.PRNGKey(0))
        step = make_train_step(
            cfg, tmesh, rules, opt, shardings, dcn_grad_compression=compression
        )
        hlo = step.lower(state, batch(0)).compile().as_text()
        losses = []
        for i in range(n_steps):
            state, m = step(state, batch(i))
            losses.append(float(m["loss"]))
        return losses, mesh_collective_report(hlo, tmesh)

    l_off, rep_off = run("off")
    l_i8, rep_i8 = run("int8")
    assert_no_cross_slice(rep_i8)
    loss_delta = max(abs(a - b) for a, b in zip(l_off, l_i8))
    ici_delta = rep_i8["ici_bytes"] - rep_off["ici_bytes"]
    ok = ici_delta == 0 and loss_delta < 5e-3 and rep_i8["dcn_bytes"] > 0
    out.update(
        dcn_grad_bytes_fp32=rep_off["dcn_bytes"],
        dcn_grad_bytes_int8=rep_i8["dcn_bytes"],
        dcn_grad_ici_bytes_delta=ici_delta,
        dcn_grad_loss_delta=round(loss_delta, 6),
        dcn_grad_bytes_ratio_x=round(
            rep_off["dcn_bytes"] / rep_i8["dcn_bytes"] if ok else 0.0, 2
        ),
    )
    print(json.dumps(out))
    return out


def bench_cross_node(mb: int = 256, repeats: int = 3) -> dict:
    """2-node broadcast over the direct bulk plane: produce mb on one agent
    node, pull it on another (zero-copy node-to-node; the head serves only
    locations). Reference row: BASELINE.md multi-node broadcast.

    The timer covers ONLY the consumer-side pull (submit + pull + reply):
    producing the array and sealing it into the source slab happen before
    t0 (a `settle` task on the producer node returns once the object is
    resolvable there). Each repeat produces a FRESH object — pulled
    buffers cache on the consumer node, so re-pulling would time a local
    shm hit, not the plane."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    out = {}
    n = mb * 1024 * 1024
    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 0})
    try:
        cluster.add_node(num_cpus=2, resources={"src": 1})
        cluster.add_node(num_cpus=2, resources={"dst": 1})

        @ray_tpu.remote(resources={"src": 0.1})
        def produce(i):
            return np.ones(n, dtype=np.uint8)

        @ray_tpu.remote(resources={"src": 0.1})
        def settle(x):
            # materializes on the PRODUCING node (local shm, no wire):
            # returns only once the object is sealed and resolvable there
            return len(x)

        @ray_tpu.remote(resources={"dst": 0.1})
        def consume(x):
            return int(x[0]) + len(x)

        # warm: placement + worker spawn on both nodes + peer resolution
        ray_tpu.get(consume.remote(produce.remote(-1)), timeout=180)

        best = 0.0
        for i in range(repeats):
            ref = produce.remote(i)
            ray_tpu.get(settle.remote(ref), timeout=180)
            t0 = time.perf_counter()
            assert ray_tpu.get(consume.remote(ref), timeout=180) == 1 + n
            dt = time.perf_counter() - t0
            best = max(best, mb / 1024 / dt)
        out["cross_node_256mb_gbps"] = round(best, 2)

        # striping sub-metric, wire-only: the DRIVER pulls over real bulk
        # sockets (same-host slab attach off) with 1 socket vs the stripe
        # fan-out. Informational, ungated: on a single-core host both
        # stripes contend for the same CPU so ~1.0x is expected; the
        # fan-out pays off with a NIC per host.
        try:
            speedup, wire_gbps = _cross_node_striped_speedup(
                mb, produce, settle
            )
            out["cross_node_striped_speedup_x"] = round(speedup, 2)
            out["cross_node_wire_gbps"] = round(wire_gbps, 2)
        except Exception as e:
            print(f"[microbench] striped sub-metric unavailable: {e!r}",
                  file=sys.stderr)
    finally:
        cluster.shutdown()
    return out


def _cross_node_striped_speedup(mb, produce, settle):
    import ray_tpu
    from ray_tpu._private import serialization
    from ray_tpu._private.config import GLOBAL_CONFIG as cfg
    from ray_tpu._private.worker import global_worker

    def wire_pull_gbps(ref, stripe_sockets):
        env = global_worker.request(
            {"t": "get_objects", "object_ids": [ref.id]}
        )[0]
        refs = serialization.shm_buffer_refs(env)
        cfg.apply({
            "bulk_same_host": False,
            "bulk_stripe_sockets": stripe_sockets,
            "bulk_stripe_min_bytes": 32 * 1024 * 1024,
        })
        t0 = time.perf_counter()
        got = global_worker.fetch_buffers_direct(refs[0].node, refs)
        dt = time.perf_counter() - t0
        if got is None or any(v is None for v in got.values()):
            raise RuntimeError("direct wire pull failed")
        return mb / 1024 / dt

    try:
        r1 = produce.remote(1001)
        ray_tpu.get(settle.remote(r1), timeout=180)
        rn = produce.remote(1002)
        ray_tpu.get(settle.remote(rn), timeout=180)
        single = wire_pull_gbps(r1, 1)
        striped = wire_pull_gbps(rn, 4)
        return striped / single, single
    finally:
        cfg.apply({
            "bulk_same_host": True,
            "bulk_stripe_sockets": 4,
            "bulk_stripe_min_bytes": 64 * 1024 * 1024,
        })


def bench_cross_node_gbps(mb: int = 256) -> float:
    return bench_cross_node(mb)["cross_node_256mb_gbps"]


def bench_head_stress(n_tasks: int = 0, n_actors: int = 0) -> dict:
    """Head scale envelope (reference: release/benchmarks many_tasks /
    many_actors): ingest n_tasks QUEUED tasks + n_actors pending actors
    through one head; report ingest rates and control-loop latency under
    the backlog. Runs in its own cluster with the direct task path off so
    every submit lands in the head's queue.

    Default sizes scale with the host: the full 100k/1k envelope on >=8
    cores, proportionally smaller on tiny hosts (a 1-core box takes ~15
    min for the full envelope — rates are what matter, and they are
    per-core properties; tests/test_stress.py pins the absolute envelope)."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker

    cpus = os.cpu_count() or 1
    scale = min(1.0, max(0.2, cpus / 8))
    n_tasks = n_tasks or int(100_000 * scale)
    n_actors = n_actors or int(1_000 * scale)
    ray_tpu.init(num_cpus=2, _system_config={"direct_task_calls": False})
    try:
        @ray_tpu.remote(resources={"never": 1.0})
        def blocked():
            return 1

        @ray_tpu.remote(resources={"never": 1.0})
        class Pending:
            pass

        def ping_ms(n=20):
            t0 = time.perf_counter()
            for _ in range(n):
                global_worker.request({"t": "ping"})
            return (time.perf_counter() - t0) / n * 1000

        base_ms = ping_ms()
        t0 = time.perf_counter()
        refs = [blocked.remote() for _ in range(n_tasks)]
        submit_s = time.perf_counter() - t0
        deadline = time.time() + 300
        while time.time() < deadline:
            if global_worker.request({"t": "task_count"}) >= n_tasks:
                break
            time.sleep(1.0)
        ingest_s = time.perf_counter() - t0
        under_ms = ping_ms()
        t0 = time.perf_counter()
        actors = [Pending.remote() for _ in range(n_actors)]
        actors_s = time.perf_counter() - t0
        out = {
            "stress_tasks_submitted": n_tasks,
            "stress_submit_per_s": round(n_tasks / submit_s, 1),
            "stress_ingest_per_s": round(n_tasks / ingest_s, 1),
            "stress_ping_ms_baseline": round(base_ms, 2),
            "stress_ping_ms_under_load": round(under_ms, 2),
            "stress_ping_ms_under_load_and_actors": round(ping_ms(), 2),
            "stress_actor_creates_per_s": round(n_actors / actors_s, 1),
        }
        del refs, actors
        return out
    finally:
        ray_tpu.shutdown()


# every gate in one table: metric -> (op, target). Targets may be
# callables of the results dict (floor-relative: put/cross-node derive
# from the host's measured memcpy floor). Both the full supervisor and
# the --only selector judge from HERE, so a bound cannot drift between
# the sweep and the targeted CI step.
GATES = {
    "task_submit_per_s": (">=", 5000.0),
    "actor_calls_sync_per_s": (">=", 2500.0),
    # put pays exactly one copy: on hosts whose single-core memcpy floor
    # is below 12.5 GB/s the absolute 10 GB/s is unreachable by
    # construction — the honest target is ~75% of the floor, capped
    "put_100mb_gbps": (">=", lambda r: min(10.0, 0.75 * r["host_memcpy_gbps"])),
    # cross-node pull pays at most ONE host copy on the zero-copy bulk
    # plane (slab-attach or recv-into-slab), so half the single-thread
    # memcpy floor is the honest bound — copy time plus an equal budget
    # for dispatch/seal/teardown (ROADMAP item 3 landed: was an
    # anti-regression floor of min(0.15, 0.02x) while pulls were
    # chunk-copied through the head relay)
    "cross_node_256mb_gbps": (">=", lambda r: 0.5 * r["host_memcpy_gbps"]),
    # batched KV-cache decode must beat serial per-request decode: the
    # continuous-batching serving fast path (both engines run PAGED)
    "decode_batched_speedup_x": (">=", 2.0),
    # a prefix-cache hit must beat the cold prefill of the same prompt
    "prefix_hit_speedup_x": (">=", 2.0),
    # a CROSS-REPLICA prefix hit (export -> pack -> wire-check -> unpack
    # -> import on a peer engine) must still beat recomputing the prefill
    # locally; greedy identity is asserted in-row — divergence zeroes the
    # metric. --only row, not part of the full-sweep trials (see `gated`)
    "cross_replica_prefix_hit_speedup_x": (">=", 1.5),
    # block-in-place paged attention must beat the block-table gather at
    # the same dtype in the long-context (bandwidth-bound) decode regime
    "decode_long_context_fused_speedup_x": (">=", 1.1),
    # int8 KV blocks must ~double pool capacity per byte
    "kv_int8_blocks_ratio": (">=", 1.8),
    # one k+1-token speculative verify step must beat the k+1
    # single-token steps it replaces at low batch (perfect-draft harness)
    "spec_decode_speedup_x": (">=", 1.5),
    # the multi-query fused verify must AT LEAST match the gather-window
    # verify at long context (measured ~1.9x on CPU at 4k ctx) — before
    # ISSUE 13, speculation re-paid the gather cost fused decode saved
    "spec_verify_fused_speedup_x": (">=", 1.0),
    # chunked prefill: decode p99 inter-token latency while a 4k prompt
    # streams in chunks stays BOUNDED vs the decode-only baseline (one
    # chunk's compute, ~25x a tiny-batch CPU decode step; a scheduler
    # regression — chunks coalescing, whole-prefill fallback — is 10x+)
    "decode_mixed_p99_ratio_x": ("<=", 50.0),
    # ... and must cut the whole-prompt head-of-line spike by >= 4x
    "decode_chunk_stall_reduction_x": (">=", 4.0),
    # the telemetry plane (per-token request metrics + flight recorder)
    # must cost at most a few percent of decode throughput — telemetry-on
    # tokens/s over telemetry-off on the identical batcher loop
    "decode_telemetry_overhead_ratio_x": (">=", 0.95),
    # interleaved-1F1B (--only train_dcn_plane row, 8-device child): the
    # pipeline bubble must shrink >= 1.3x vs GPipe at the measured shape,
    # and the ratio is zeroed unless the schedule matches the sequential
    # oracle AND adds zero dcn-crossing hops (the v multiplier rides ICI)
    "pipeline_bubble_reduction_x": (">=", 1.3),
    # int8+error-feedback dcn gradient exchange: >= 3.5x fewer
    # slice-boundary bytes than the fp32 all-reduce (~3.93 @ block=256),
    # zeroed unless ICI bytes are untouched and the loss tracks fp32
    "dcn_grad_bytes_ratio_x": (">=", 3.5),
    # live weight hot-swap (--only serve_weight_swap row): decode p99
    # inter-token latency with a publish->pull->preempt->recompute swap
    # landing mid-generation stays within 10x the quiescent p99; zeroed
    # to 999 if any stream drops or comes back short of its token budget
    "weight_swap_p99_ratio_x": ("<=", 10.0),
}


def _gate_ok(metric: str, value: float, target: float) -> bool:
    op = GATES[metric][0]
    return value <= target if op == "<=" else value >= target


def _run_trial() -> dict:
    """One fresh-process trial of the GATED metrics + this trial's own
    environment noise floor (memcpy) — so every rate ships with the host
    condition it was measured under."""
    import ray_tpu

    out = {"host_memcpy_gbps": round(host_memcpy_gbps(), 2)}
    # decode runs BEFORE ray init: jax (CPU) claims its arena in a clean
    # process, and the cluster's workers never contend with the jit warmup
    out.update(bench_decode_speedup())
    out.update(bench_decode_long_context())
    out.update(bench_decode_speculative())
    out.update(bench_decode_mixed_traffic())
    out.update(bench_decode_telemetry_overhead())
    out.update(bench_decode_spec_realtext())
    out.update(bench_prefix_hit())
    ray_tpu.init()
    out["task_submit_per_s"] = round(bench_task_submit(), 1)
    out["actor_calls_sync_per_s"] = round(bench_actor_sync(), 1)
    out["put_100mb_gbps"] = round(bench_put_gbps(), 2)
    ray_tpu.shutdown()
    print(json.dumps(out))
    return out


def main():
    """Self-certifying supervisor (VERDICT r4 #5): the gated metrics run as
    N FRESH child processes (one cluster each); targets_met is computed
    from the per-metric MEDIANS, so a single host-throttled trial cannot
    fail — or pass — the artifact on its own. Each trial records its own
    memcpy noise floor; the put target derives from the median floor."""
    import gc
    import statistics
    import subprocess

    n_trials = int(os.environ.get("RAY_TPU_MICROBENCH_TRIALS", "5"))
    # every GATES entry is trial-gated except cross-node (needs its own
    # 2-node cluster, measured once in THIS process), the cross-replica
    # transfer row, and the train DCN-plane row (dedicated --only CI
    # steps; the latter spawns its own 8-device jax child) — derived, not
    # hand-listed, so a new gate cannot be silently dropped from the
    # sweep's judgment
    gated = tuple(
        k for k in GATES
        if k not in ("cross_node_256mb_gbps",
                     "cross_replica_prefix_hit_speedup_x",
                     "pipeline_bubble_reduction_x",
                     "dcn_grad_bytes_ratio_x",
                     "weight_swap_p99_ratio_x")
    )
    expected = set(gated) | {"host_memcpy_gbps"}
    trials = []
    # trial 0 is a WARMUP, discarded: it faults in the interpreter/page
    # cache and brings the CPU governor up, which is where most of the
    # historical put_100mb_gbps spread (2.49-7.25 GB/s across trials) came
    # from. Between trials the parent quiesces — gc + a short settle — so
    # one trial's teardown (worker reaping, slab unmap) doesn't bleed into
    # the next trial's timed loops.
    for i in range(n_trials + 1):
        if i:
            gc.collect()
            time.sleep(0.75)
        # the decode metric needs a jax backend; microbench is a CORE
        # runtime artifact, so a trial child must never claim a TPU — force
        # CPU even when the operator's shell exports JAX_PLATFORMS=tpu
        env = dict(os.environ, RAY_TPU_MICROBENCH_CHILD="trial",
                   JAX_PLATFORMS="cpu")
        try:
            proc = subprocess.run(
                [sys.executable, sys.argv[0]], env=env, capture_output=True,
                text=True,
                # ISSUE 13 grew each trial by the verify-longctx + mixed-
                # traffic phases (~2 min extra on a 1-core host)
                timeout=int(os.environ.get(
                    "RAY_TPU_MICROBENCH_TRIAL_TIMEOUT_S", "900"
                )),
            )
        except subprocess.TimeoutExpired:
            # one hung (host-throttled) trial must not sink the artifact —
            # the medians over the remaining trials still certify it
            print(f"[microbench] trial {i} timed out; skipping", file=sys.stderr)
            continue
        if i == 0:
            continue  # warmup: result discarded
        for line in reversed(proc.stdout.strip().splitlines()):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(obj, dict) and expected <= set(obj):
                trials.append(obj)
            break
        else:
            print(f"[microbench] trial {i} produced no JSON: "
                  f"{proc.stderr[-500:]}", file=sys.stderr)
    if not trials:
        print(json.dumps({"targets_met": False, "error": "no trials completed"}))
        return {"targets_met": False}

    results = {"host_cpus": os.cpu_count(), "n_trials": len(trials)}
    for k in gated + ("host_memcpy_gbps", "decode_batched_tokens_per_s",
                      "decode_serial_tokens_per_s", "prefix_hit_cold_ms",
                      "prefix_hit_ms", "decode_long_context_tokens_per_s",
                      "decode_long_context_gather_tokens_per_s",
                      "decode_long_context_fused_fp_tokens_per_s",
                      "decode_long_context_int8_speedup_x",
                      "spec_off_tokens_per_s", "spec_on_tokens_per_s",
                      "spec_accept_rate", "spec_greedy_identical",
                      "spec_verify_ctx_tokens", "spec_verify_engaged",
                      "spec_verify_gather_tokens_per_s",
                      "spec_verify_fused_tokens_per_s",
                      "mixed_traffic_prompt_tokens",
                      "mixed_traffic_chunk_tokens",
                      "decode_only_p99_ms", "decode_mixed_p99_ms",
                      "whole_prompt_stall_ms",
                      "decode_telemetry_on_tokens_per_s",
                      "decode_telemetry_off_tokens_per_s",
                      "spec_realtext_available",
                      "spec_accept_rate_realtext",
                      "spec_tokens_per_step_realtext"):
        vals = [t[k] for t in trials]
        results[k] = round(statistics.median(vals), 2)
        results[k + "_spread"] = round(
            statistics.pstdev(vals) if len(vals) > 1 else 0.0, 2
        )
    results["trials"] = trials

    # one pass of the informational (non-gated) metrics in THIS process
    import ray_tpu

    ray_tpu.init()
    results["task_roundtrip_per_s"] = round(bench_task_roundtrip(), 1)
    results["actor_calls_async_per_s"] = round(bench_actor_async(), 1)
    results["get_100mb_gbps"] = round(bench_get_gbps(), 2)
    results["broadcast_10mb_16actors_ms"] = round(bench_weight_broadcast_ms(), 1)
    ray_tpu.shutdown()
    results.update(bench_cross_node())
    results.update(bench_head_stress())

    # targets resolve from the shared GATES table (floor-relative ones —
    # put, cross-node — derive from the MEDIAN memcpy floor: floor and
    # rate come from the same trials, so no minutes-apart drift; the gate
    # rationale lives next to each entry in GATES)
    targets = {
        k: (v(results) if callable(v) else v)
        for k, (_, v) in GATES.items()
        if k in gated or k == "cross_node_256mb_gbps"
    }
    results["put_target_gbps"] = round(targets["put_100mb_gbps"], 2)
    results["cross_node_target_gbps"] = round(
        targets["cross_node_256mb_gbps"], 3
    )
    results["targets"] = {k: round(v, 2) for k, v in targets.items()}
    results["targets_met"] = all(
        _gate_ok(k, results[k], v) for k, v in targets.items()
    )
    print(json.dumps(results))
    return results


# --------------------------------------------------------------------------
# --only: a named row as a targeted CI step
# --------------------------------------------------------------------------

# row name -> (metrics fn, needs a ray cluster, GATES entries the row's
# metrics are judged by). Derived targets pull the memcpy floor in
# automatically. One in-process pass — the fresh-process median-of-N
# discipline belongs to the full supervisor; a targeted CI step wants one
# honest measurement and a hard exit code.
ROWS = {
    "decode_speedup": (bench_decode_speedup, False,
                       ("decode_batched_speedup_x",)),
    "decode_long_context": (bench_decode_long_context, False,
                            ("decode_long_context_fused_speedup_x",
                             "kv_int8_blocks_ratio")),
    "decode_speculative": (bench_decode_speculative, False,
                           ("spec_decode_speedup_x",
                            "spec_verify_fused_speedup_x")),
    "decode_mixed_traffic": (bench_decode_mixed_traffic, False,
                             ("decode_mixed_p99_ratio_x",
                              "decode_chunk_stall_reduction_x")),
    "decode_spec_realtext": (bench_decode_spec_realtext, False, ()),
    "decode_telemetry_overhead": (bench_decode_telemetry_overhead, False,
                                  ("decode_telemetry_overhead_ratio_x",)),
    "prefix_hit": (bench_prefix_hit, False, ("prefix_hit_speedup_x",)),
    "serve_cross_replica": (bench_serve_cross_replica, False,
                            ("cross_replica_prefix_hit_speedup_x",)),
    "serve_weight_swap": (bench_serve_weight_swap, True,
                          ("weight_swap_p99_ratio_x",)),
    "train_dcn_plane": (bench_train_dcn_plane, False,
                        ("pipeline_bubble_reduction_x",
                         "dcn_grad_bytes_ratio_x")),
    "task_submit": (lambda: {"task_submit_per_s": round(bench_task_submit(), 1)},
                    True, ("task_submit_per_s",)),
    "actor_sync": (lambda: {"actor_calls_sync_per_s": round(bench_actor_sync(), 1)},
                   True, ("actor_calls_sync_per_s",)),
    "put": (lambda: {"put_100mb_gbps": round(bench_put_gbps(), 2)},
            True, ("put_100mb_gbps",)),
    # needs_ray=None: the row manages its OWN ray lifecycle (head_stress
    # calls init with a custom system config; cross_node builds a
    # Cluster) — run_only must release any shared cluster first, or the
    # row's init raises "called twice"
    "cross_node": (bench_cross_node, None, ("cross_node_256mb_gbps",)),
    "head_stress": (bench_head_stress, None, ()),
}


def run_only(names) -> bool:
    """Run the named row(s) in THIS process, judge exactly their gates,
    print one JSON object, return pass/fail (the exit code)."""
    unknown = [n for n in names if n not in ROWS]
    if unknown:
        print(f"[microbench] unknown row(s) {unknown}; "
              f"available: {sorted(ROWS)}", file=sys.stderr)
        return False
    results = {"host_cpus": os.cpu_count(), "rows": list(names)}
    needs_floor = any(
        callable(GATES[g][1])
        for n in names for g in ROWS[n][2]
    )
    if needs_floor:
        results["host_memcpy_gbps"] = round(host_memcpy_gbps(), 2)
    inited = False
    import ray_tpu

    try:
        for n in names:
            fn, needs_ray, _ = ROWS[n]
            if needs_ray and not inited:
                ray_tpu.init()
                inited = True
            elif needs_ray is None and inited:
                # row manages its own cluster: hand the runtime back
                ray_tpu.shutdown()
                inited = False
            results.update(fn())
    finally:
        if inited:
            ray_tpu.shutdown()
    checked, ok = {}, True
    for n in names:
        for g in ROWS[n][2]:
            if g not in results:
                # a row that stopped emitting its gated metric must FAIL
                # the targeted step, not silently pass with no judgment
                checked[g] = {"missing": True, "passed": False}
                ok = False
                continue
            op, tgt = GATES[g]
            tgt = tgt(results) if callable(tgt) else tgt
            passed = _gate_ok(g, results[g], tgt)
            checked[g] = {"value": results[g], "op": op,
                          "target": round(tgt, 3), "passed": passed}
            ok = ok and passed
    results["gates"] = checked
    results["targets_met"] = ok
    print(json.dumps(results))
    return ok


if __name__ == "__main__":
    if os.environ.get("RAY_TPU_MICROBENCH_CHILD") == "trial":
        _run_trial()
        sys.exit(0)
    if os.environ.get("RAY_TPU_MICROBENCH_CHILD") == "train_dcn_plane":
        _train_dcn_plane_child()
        sys.exit(0)
    if "--only" in sys.argv:
        # targeted CI step: `microbench.py --only decode_mixed_traffic`
        # (comma-separate for several rows) runs just those rows, judges
        # just their gates, and exits nonzero on any failure. Defaults to
        # CPU like the trial children (set before any row imports jax);
        # an explicit JAX_PLATFORMS export wins.
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        idx = sys.argv.index("--only")
        if idx + 1 >= len(sys.argv):
            print(f"usage: {sys.argv[0]} --only <row>[,<row>...]; "
                  f"rows: {sorted(ROWS)}", file=sys.stderr)
            sys.exit(2)
        names = [n for n in sys.argv[idx + 1].split(",") if n]
        sys.exit(0 if run_only(names) else 1)
    sys.exit(0 if main()["targets_met"] else 1)
