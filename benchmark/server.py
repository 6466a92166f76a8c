"""The deployment the serving cells run: the program's KVGenerationServer,
unchanged, plus the few methods the benchmark calls over the handle —
facts(), start_trace/stop_trace, reference_check — and a thread that notes
how full the KV pool gets. It is deployed with the
same three lines as serve.deploy_generation, so the controller's
`runs_paged_engine` placement puts the replica on the chip."""

from __future__ import annotations

import os
import threading
import time

from ray_tpu.serve.kv_transfer import KVGenerationServer


def _hist_totals(hist, **tags) -> dict:
    """sum and count of a telemetry histogram over the series whose tags
    include `tags` (histograms are bucketed: the benchmark reads only the
    exact sum and count, and takes tails from its own client)."""
    want = set(tags.items())
    total, count = 0.0, 0
    for key, ent in hist._snapshot()["values"].items():
        if want <= set(key) and isinstance(ent, dict):
            total += ent["sum"]
            count += ent["count"]
    return {"sum": total, "count": count}


class BenchServer(KVGenerationServer):
    def __init__(self, cfg_kwargs, *, conf=None, **kw):
        import jax

        from ray_tpu.models.transformer import TransformerConfig

        self._compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, dur, **_: self._compiles.append(time.time())
            if name == "/jax/core/compile/backend_compile_duration" else None
        )
        self._conf = conf
        t0 = time.time()
        super().__init__(TransformerConfig(**cfg_kwargs), **kw)
        self._construct_s = time.time() - t0
        self._kv_peak = {"used": 0, "live": 0}
        threading.Thread(target=self._watch_pool, daemon=True).start()

    def _watch_pool(self, period_s: float = 0.2):
        """The fullest the pool has been since facts() was last called:
        blocks not free (`used`: live sequences and what the prefix cache
        keeps of finished ones) and of those the ones a live sequence
        holds (`live`). A count and a scan of the cache's node table, five
        times a second; both are safe off the batcher's thread
        (kv_paging.py: evictable)."""
        alloc, cache = self.engine.allocator, self.engine.prefix_cache
        while True:
            used = alloc.num_usable - alloc.num_free
            live = used - (cache.evictable() if cache else 0)
            peak = self._kv_peak
            peak["used"] = max(peak["used"], used)
            peak["live"] = max(peak["live"], live)
            time.sleep(period_s)

    # ------------------------------------------------------------- facts

    def facts(self) -> dict:
        """Counters and histogram totals as they stand now; the benchmark
        takes deltas of two calls. `kv_blocks_peak` is the pool's fullest
        since the call before this one."""
        from ray_tpu.serve import telemetry

        tel = telemetry.get_telemetry()
        kv_peak, self._kv_peak = self._kv_peak, {"used": 0, "live": 0}
        es = self.engine.stats()
        dev = self.engine._device
        return {
            "wall": time.time(),
            "pid": os.getpid(),
            "construct_s": self._construct_s,
            "compiles": len(self._compiles),
            "kv_blocks_peak": kv_peak,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "n_devices": 1,
            "engine": {k: es.get(k) for k in (
                "decode_steps", "tokens_generated", "prefills",
                "prefill_tokens", "prefix_hits", "prefix_tokens_reused",
                "preemptions", "device_peak_bytes", "device_bytes_in_use",
                "device_bytes_limit", "kv_pool_bytes", "kv_blocks_total",
                "kv_blocks_free", "attention_impl", "attention_kernel",
                "kv_cache_dtype", "max_batch_size",
            )},
            "prefill_shapes": sorted(self.engine.prefill_shapes),
            "batcher_steps": self.batcher.stats().get("steps"),
            "hist": {} if tel is None else {
                "queue_wait": _hist_totals(tel.queue_wait),
                "decode_step": _hist_totals(tel.engine_step, phase="decode"),
                "prefill_step": _hist_totals(tel.engine_step, phase="prefill"),
                "ttft": _hist_totals(tel.ttft),
                "inter_token": _hist_totals(tel.inter_token),
            },
        }

    def shape_keys(self, pairs):
        """For (cached_prefix_len, remaining_prompt_len) pairs, the key of
        the prefill program each would run — so the warm-up sends one
        request per program and no more. Asks the engine's own bucketing;
        an engine without it gets powers of two, and a miss shows as a
        compilation inside the window."""
        eng = self.engine
        ctx_fn = getattr(eng, "_ctx_bucket_blocks", None)
        len_fn = getattr(eng, "_bucket", None)

        def pow2(n):
            return 1 << max(0, int(n) - 1).bit_length()

        return [
            [ctx_fn(c) if ctx_fn else pow2(c), len_fn(t) if len_fn else pow2(t)]
            for c, t in pairs
        ]

    # ------------------------------------------------------------- trace

    def start_trace(self, trace_dir: str) -> float:
        import shutil

        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        return time.time()

    def stop_trace(self) -> float:
        import jax

        jax.profiler.stop_trace()
        return time.time()

    def trace_facts(self, trace_dir: str, describe: bool = False):
        from benchmark import trace_reduce

        out = trace_reduce.reduce_dir(trace_dir)
        if out is not None and describe:
            out["trace_lines"] = trace_reduce.describe(trace_dir)
        return out

    # ------------------------------------------------------- correctness

    def reference_check(self, prompts, new_tokens: int, tolerance: float):
        """Each prompt goes through this replica's own batcher for
        `new_tokens` greedy tokens; the plain float32 reference then reads
        prompt + answer in one forward pass. At every generated position the
        reference logit of the SERVED token must lie within tolerance x
        |largest reference logit| of that largest logit.

        Why near-argmax and not token equality: with random weights the
        logits are nearly flat, and the served path (bf16, paged kernel,
        prefill over cached blocks) differs from float32 by 1.5-2.2 % of
        the largest logit (PERF.md, PR 21), so the argmax itself flips.
        2^-4 is about three times that; a path that dropped a layer, a
        head group or the cached prefix moves logits by their whole
        magnitude and misses it at once."""
        import numpy as np

        from benchmark import common

        ref_logits = common.load_block(self._conf).ref_logits
        rows = []
        for p in prompts:
            p = [int(t) for t in p]
            out = [int(t) for t in self.batcher.submit(
                tokens=p, max_new_tokens=int(new_tokens))]
            seq = p + out[:-1]
            pos = list(range(len(p) - 1, len(p) - 1 + len(out)))
            logits = np.asarray(ref_logits(
                self.engine.params, seq, self._conf, positions=pos))
            top = logits.max(axis=-1)
            served = logits[np.arange(len(out)), np.asarray(out)]
            rows.append({
                "tokens": len(out),
                "worst_gap": float(np.max(top - served)),
                "largest_logit": float(np.max(np.abs(top))),
                "argmax_agree": int(np.sum(logits.argmax(-1) == np.asarray(out))),
                "ok": bool(len(out) == int(new_tokens) and np.all(
                    top - served <= tolerance * np.abs(top))),
            })
        return rows
