"""Benchmark: flagship-model training throughput on the local chip(s).

Four rows, run as separate child processes (the chip claim is exclusive
per process, so each phase gets a fresh claim):
  raw     — model/step/sharding stack driven directly (round-3 number)
  trainer — the SAME config through the real framework: JaxTrainer actor
            gang, session.report every step, Dataset.iter_device_batches
            feeding the step (reference parity: BASELINE.json config #1
            "GPT-2 125M single-host JaxTrainer")
  hbm     — a ~1.15B-param config sized to fill one v5e's 16G HBM with
            remat + flash (BASELINE.md 7B north star, scaled to one chip)
  rl      — PPO learner samples/sec/chip + end-to-end rollout pipeline +
            weight-broadcast latency (BASELINE.json metric #2)

Prints ONE JSON line; the trainer row is the headline metric, the others
ride along as fields:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "raw": {...},
   "hbm": {...}, "rl": {...}, "trainer_overhead_vs_raw_pct": N}

vs_baseline is measured MFU / 0.45 — the BASELINE.json north-star target
(the reference publishes no tokens/sec numbers; see BASELINE.md notes).
"""

from __future__ import annotations

import json
import os
import sys
import time


PEAK_BF16_FLOPS = {
    # per-chip dense bf16 peak
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,
}


def _peak_flops_kind(kind: str) -> float:
    """Longest matching prefix wins ("TPU v5 lite" before "TPU v5"); a
    device that is not in the table is an error, never a default."""
    for k in sorted(PEAK_BF16_FLOPS, key=len, reverse=True):
        if kind.startswith(k):
            return PEAK_BF16_FLOPS[k]
    raise SystemExit(
        f"bench: no bf16 peak on record for device kind {kind!r}; add it to "
        "PEAK_BF16_FLOPS with its source"
    )


def _require_tpu(device) -> None:
    """Every row is a device measurement: there is no CPU branch."""
    if device.platform != "tpu":
        raise SystemExit(
            f"bench: this phase measures the TPU, jax gave {device.platform!r} "
            f"({device.device_kind}); nothing is reported from another device"
        )


# --------------------------------------------------------------------------
# shared direct step loop (raw + hbm phases)
# --------------------------------------------------------------------------


def _mesh_and_rules(n_chips: int):
    """Single chip: trivial dp mesh. Multi chip: shard params/opt-state over
    the fsdp axis (ZeRO-3) — the batch rules spec is ('dp','fsdp') so the
    batch shards there too. MeshSpec(dp=n) with fsdp rules would leave the
    fsdp axis at size 1 and silently replicate everything."""
    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh

    if n_chips == 1:
        return build_mesh(MeshSpec(dp=1)), PRESET_RULES["dp"]
    return build_mesh(MeshSpec(fsdp=n_chips)), PRESET_RULES["fsdp"]


def _run_step_bench(tag, cfg, batch, seq, steps, opt):
    """Compile + warm + time `steps` chained train steps; returns the stats
    dict shared by the raw and hbm rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.train.step import make_sharded_init, make_train_step

    dev = jax.devices()[0]
    n_chips = len(jax.devices())
    mesh, rules = _mesh_and_rules(n_chips)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh, rules, opt, shardings)

    rng = np.random.default_rng(0)
    batch_data = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(batch, seq + 1)), jnp.int32
        ),
        "mask": jnp.ones((batch, seq + 1), jnp.int32),
    }

    t0 = time.perf_counter()
    state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])
    compile_s = time.perf_counter() - t0
    state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec_per_chip = batch * seq * steps / dt / n_chips
    flops_per_token = cfg.flops_per_token() + cfg.attention_flops_per_token(seq)
    mfu = tokens_per_sec_per_chip * flops_per_token / _peak_flops_kind(dev.device_kind)
    kind = getattr(dev, "device_kind", dev.platform)
    print(
        f"[bench:{tag}] dev={kind} chips={n_chips} "
        f"model={cfg.d_model}x{cfg.n_layers} batch={batch} seq={seq} "
        f"compile={compile_s:.1f}s step={dt / steps * 1000:.1f}ms "
        f"loss={float(metrics['loss']):.3f} mfu={mfu:.3f}",
        file=sys.stderr,
    )
    return {
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "mfu": round(mfu, 4),
        "device": kind,
        "step_ms": round(dt / steps * 1000, 2),
    }


# --------------------------------------------------------------------------
# raw mode — direct step loop (identical to the round-3 bench)
# --------------------------------------------------------------------------


def main_raw():
    import dataclasses

    import jax

    from ray_tpu.models import CONFIGS
    from ray_tpu.train.step import default_optimizer

    _require_tpu(jax.devices()[0])
    # Pallas flash attention (head-major layout, fused single-block
    # backward), remat that saves EXACTLY the residuals backward reads
    # (flash_min), and unrolled layers (drops scan stack traffic):
    # measured 0.47 MFU vs 0.27 for dense+full-remat on v5e (b16 is the
    # largest batch whose saved residuals fit 16G HBM at compile time).
    cfg = dataclasses.replace(
        CONFIGS["gpt2_125m"],
        attention="flash",
        remat_policy="flash_min",
        scan_layers=False,
    )
    batch, seq, steps = 16, 1024, 30  # window matched to the trainer phase: overhead must compare equal-length timed windows

    row = _run_step_bench(
        "raw", cfg, batch, seq, steps, default_optimizer(lr=1e-3, warmup=10)
    )
    row["metric"] = "gpt2_125m_train_tokens_per_sec_per_chip"
    row["vs_baseline"] = round(row["mfu"] / 0.45, 4)
    print(json.dumps(row))


# --------------------------------------------------------------------------
# hbm mode — HBM-limit single-chip config (~1.15B params, fp32 adam v)
# --------------------------------------------------------------------------


def main_hbm():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import CONFIGS
    from ray_tpu.train.step import default_optimizer

    _require_tpu(jax.devices()[0])
    n_chips = len(jax.devices())

    cfg = dataclasses.replace(
        CONFIGS["gpt_1b"],
        attention="flash",
        remat_policy="flash_qkv",
        scan_layers=False,
        loss_chunk=128,
    )
    # 6/chip is the largest per-chip batch that fits 16G (15.9G static
    # allocation at 8); multi-chip scales it so dim 0 stays divisible
    batch, seq, steps = 6 * n_chips, 1024, 8

    # bf16 momentum: the ~1.15B fp32 params + fp32 adam v alone are ~9G;
    # halving mu is what leaves room for grads + activations on 16G
    opt = default_optimizer(lr=1e-4, warmup=10, mu_dtype=jnp.bfloat16)
    row = _run_step_bench("hbm", cfg, batch, seq, steps, opt)
    row["metric"] = "gpt_1b_hbm_limit_tokens_per_sec_per_chip"
    row["vs_baseline"] = round(row["mfu"] / 0.40, 4)
    row["params_b"] = round(cfg.num_params() / 1e9, 3)
    print(json.dumps(row))


# --------------------------------------------------------------------------
# decode mode — KV-cache serving fast path (tokens/s/chip at the decode step)
# --------------------------------------------------------------------------


def _decode_realtext_spec(k: int = 4, new_tokens: int = 48) -> dict:
    """Real-text drafter measurement riding the decode row: load a hub
    model (RAY_TPU_BENCH_MODEL_PATH, else the checked-in fixture), run
    the n-gram drafter over tokenizer-encoded English prompts, and record
    the measured accept rate + the model's identity. Measured, never
    asserted — drafter yield on real text is a model/workload property,
    and the row exists precisely to OBSERVE it (PR 7's open question).
    Absent model files degrade to the synthetic identity, never a fault."""
    out = {"model_id": None, "params_source": "synthetic",
           "spec_accept_rate_realtext": None}
    path = os.environ.get("RAY_TPU_BENCH_MODEL_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "tests", "fixtures", "hub_gpt2_tiny",
    )
    try:
        from ray_tpu.models.hub import measure_realtext_spec

        m = measure_realtext_spec(path, k=k, new_tokens=new_tokens)
        out.update(
            model_id=m["model_id"],
            params_source=m["params_source"],
            spec_accept_rate_realtext=m["spec_accept_rate"],
        )
    except Exception as e:
        print(f"[bench:decode] realtext spec measurement unavailable: {e!r}",
              file=sys.stderr)
    return out


def _decode_latency_distribution(engine, prompts, new_tokens: int) -> dict:
    """TTFT/inter-token latency distribution for the decode row, pulled
    from the telemetry plane's histograms (serve/telemetry.py): the
    prompts run through a ContinuousBatcher (the production consumer of
    the engine) and the row reads p50/p99 off serve_ttft_s /
    serve_inter_token_latency_s — so TPU certification rounds bank real
    latency distributions next to tokens/s, not just means. Callers must
    pass prompts the engine has NOT seen: a prefix-cache hit would turn
    the banked TTFT into cache-hit admission latency, an order of
    magnitude under what a cold client waits. None fields when telemetry
    is off."""
    out = {"ttft_p50_ms": None, "ttft_p99_ms": None,
           "inter_token_p99_ms": None}
    try:
        from ray_tpu.serve import telemetry
        from ray_tpu.serve.batching import ContinuousBatcher
        from ray_tpu.util.metrics import local_histogram_quantiles

        if telemetry.get_telemetry() is None:
            return out
        batcher = ContinuousBatcher(
            engine, max_batch_size=len(prompts), batch_wait_timeout_s=0.05
        )
        try:
            streams = [
                batcher.submit(tokens=list(p), max_new_tokens=new_tokens)
                for p in prompts
            ]
            for s in streams:
                for _ in s:
                    pass
        finally:
            batcher.close()
        ttft = local_histogram_quantiles("serve_ttft_s", (0.5, 0.99))
        inter = local_histogram_quantiles(
            "serve_inter_token_latency_s", (0.99,))
        if ttft and ttft[0] is not None:
            out["ttft_p50_ms"] = round(ttft[0] * 1000, 2)
            out["ttft_p99_ms"] = round(ttft[1] * 1000, 2)
        if inter and inter[0] is not None:
            out["inter_token_p99_ms"] = round(inter[0] * 1000, 2)
    except Exception as e:
        print(f"[bench:decode] latency distribution unavailable: {e!r}",
              file=sys.stderr)
    return out


def main_decode():
    """Batched KV-cache decode throughput: the serving-side counterpart of
    the training rows. Prefills `batch` slots, then times `new_tokens`
    continuous decode steps through the PAGED engine (the same loop the
    serve replica drives — block-table gather attention, so the row also
    tracks the paging overhead), reporting tokens/s/chip plus block-pool
    utilization and preemptions. The batched-vs-serial and prefix-hit
    gates live in microbench.py; this row is the absolute rate. The row
    also carries the real-text drafter measurement (model-hub weights +
    tokenizer-encoded English prompts) so decode trajectories name which
    weights they speak for."""
    import jax
    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.models.kv_paging import PagedDecodeEngine

    dev = jax.devices()[0]
    _require_tpu(dev)
    n_chips = len(jax.devices())

    cfg = CONFIGS["gpt2_125m"]
    batch, prompt_len, new_tokens = 8, 128, 128

    # telemetry=False: the timed loop's tokens/s must stay comparable to
    # pre-telemetry bench rounds (engine-pure, no per-step observes); the
    # latency-distribution pass below gets its TTFT/inter-token numbers
    # from the BATCHER-side telemetry, which the engine doesn't carry
    engine = PagedDecodeEngine(cfg, max_batch_size=batch, seed=0,
                               telemetry=False)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, size=(batch, prompt_len))
    slots = list(range(batch))

    t0 = time.perf_counter()
    for s in slots:
        engine.admit(s, {"tokens": prompts[s], "max_new_tokens": 10**9})
    prefill_s = time.perf_counter() - t0
    engine.step(slots)  # decode compile + warm
    # spec verify buckets compile OUT of the timed loop: a drafter's
    # first mid-window proposal would otherwise bill a trace+compile
    # to dt and sink the spec-on row
    engine.warmup_verify()
    gen0 = engine.tokens_generated
    t0 = time.perf_counter()
    for _ in range(new_tokens):
        engine.step(slots)
    dt = time.perf_counter() - t0
    # count tokens EMITTED, not steps: with speculation on a step emits
    # 1..k+1 per slot, and a steps-based rate would report a spec-on run
    # as slower while the spec stats next to it say otherwise
    emitted = engine.tokens_generated - gen0

    tokens_per_sec_per_chip = emitted / dt / n_chips
    estats = engine.stats()
    # latency distribution AFTER the timed loop (separate batcher-driven
    # pass over freed slots; the decode rate above stays engine-pure).
    # FRESH prompts: the decoded ones now sit in the prefix cache, and a
    # hit would bank cache-hit TTFT instead of a cold client's wait
    for s in slots:
        engine.release(s)
    latency = _decode_latency_distribution(
        engine, rng.integers(0, cfg.vocab_size, size=(batch, prompt_len)),
        new_tokens,
    )
    kind = getattr(dev, "device_kind", dev.platform)
    print(
        f"[bench:decode] dev={kind} chips={n_chips} batch={batch} "
        f"prompt={prompt_len} new={new_tokens} "
        f"attn={estats['attention_impl']} kv_dtype={estats['kv_cache_dtype']} "
        f"prefill={prefill_s * 1000:.0f}ms step={dt / new_tokens * 1000:.2f}ms "
        f"tok/s/chip={tokens_per_sec_per_chip:.1f} "
        f"kv_util={estats['kv_block_utilization']}",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "gpt2_125m_decode_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec_per_chip, 1),
                "unit": "tokens/s/chip",
                "device": kind,
                "batch": batch,
                "prompt_len": prompt_len,
                "new_tokens": new_tokens,
                "emitted_tokens": int(emitted),
                "prefill_ms": round(prefill_s * 1000, 1),
                "decode_step_ms": round(dt / new_tokens * 1000, 3),
                # which decode fast path produced this number — BENCH_r*
                # trajectories stay comparable across the fused/int8 change
                # ("gather"+"fp" rows are the pre-fused lineage)
                "attention_variant": estats["attention_impl"],
                "kv_dtype": estats["kv_cache_dtype"],
                # latency distribution from the telemetry histograms
                # (serve_ttft_s / serve_inter_token_latency_s): what a
                # client actually waits, not the step-time mean
                **latency,
                # ISSUE 13: the attention the VERIFY step ran (one fused
                # multi-query impl serves decode/verify/prefill, so it
                # equals attention_variant — recorded separately so TPU
                # certification rounds can name the fused-verify config
                # even if the impls ever diverge again) + the chunked-
                # prefill granularity (0 = whole-prompt admission)
                "verify_attention_variant": estats["attention_impl"],
                "prefill_chunk_tokens": estats["prefill_chunk_tokens"],
                # paged-KV observability: live fraction of the block pool
                # at the end of the timed run + preemptions (nonzero means
                # the pool was undersized for this batch/length mix)
                "kv_block_utilization": estats["kv_block_utilization"],
                "preemptions": estats["preemptions"],
                # speculative decoding (serve_speculative_k; 0 = off):
                # rows stay comparable across spec-on/spec-off rounds —
                # tokens/s/chip plus which k and what the drafter earned
                "spec_k": estats["spec_k"],
                "spec_accept_rate": estats["spec_accept_rate"],
                "spec_tokens_per_step": estats["spec_tokens_per_step"],
                # which weights/tokenizer this round can speak for + what
                # the n-gram drafter measured on real-text prompts (hub
                # model; "synthetic" when no checkpoint was loadable)
                **_decode_realtext_spec(),
            }
        )
    )


# --------------------------------------------------------------------------
# trainer mode — the framework in the measured loop
# --------------------------------------------------------------------------


def _trainer_train_fn(config):
    """Runs INSIDE the TrainWorker actor: this process — not the driver —
    holds the chip. Pulls device batches from the Dataset shard, reports every step
    through session.report, and reports the measured throughput at the end."""
    import dataclasses
    import time as _time

    import jax
    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh
    from ray_tpu.train import session
    from ray_tpu.train.step import default_optimizer, make_sharded_init, make_train_step

    dev = jax.devices()[0]
    _require_tpu(dev)
    cfg = dataclasses.replace(
        CONFIGS[config["model"]],
        attention="flash", remat_policy="flash_min", scan_layers=False,
    )
    batch, seq = config["batch"], config["seq"]
    steps, warmup = config["steps"], config["warmup"]

    mesh = build_mesh(MeshSpec(dp=len(jax.devices())))
    rules = PRESET_RULES["dp"]
    opt = default_optimizer(lr=1e-3, warmup=10)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh, rules, opt, shardings)

    ds = session.get_dataset_shard("train")
    it = ds.iter_device_batches(batch_size=batch, mesh=mesh, rules=rules, prefetch=2)

    t_start = _time.perf_counter()
    n_timed = 0
    t0 = None
    compile_s = None
    for i, b in enumerate(it):
        if i >= warmup + steps:
            break
        state, metrics = step(state, b)
        if i < warmup:
            # compile + cache-warm steps: sync so the timed window below
            # contains ONLY steady-state step+feed work
            jax.block_until_ready(metrics["loss"])
            if i == 0:
                compile_s = _time.perf_counter() - t_start
            if i == warmup - 1:
                t0 = _time.perf_counter()
            continue
        n_timed += 1
        # per-step report through the real session plumbing — but nothing
        # here touches device values (a float(loss) would sync the pipe)
        session.report({"step": i})
    jax.block_until_ready(metrics["loss"])
    dt = _time.perf_counter() - t0
    it.close()  # settle the feed pipeline so its stats finalize

    tokens_per_sec = batch * seq * n_timed / dt
    session.report(
        {
            "final": True,
            "tokens_per_sec": tokens_per_sec,
            "steps_timed": n_timed,
            "step_ms": dt / max(1, n_timed) * 1000.0,
            "compile_s": compile_s,
            "loss": float(metrics["loss"]),
            "device_kind": dev.device_kind,
            "n_devices": len(jax.devices()),
            # input-pipeline evidence (VERDICT r4 #2): per-operator stats
            # of the Dataset feed that just sustained the chip
            "dataset_stats": ds.stats_dict(),
        }
    )
    return "done"


def main_trainer():
    """Driver: builds the token Dataset, runs JaxTrainer over one TPU worker
    actor, and computes MFU from the worker's reported throughput. The
    driver itself never initializes a jax backend."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu.models import CONFIGS
    from ray_tpu.train import JaxTrainer, ScalingConfig

    model, batch, seq, steps, warmup = "gpt2_125m", 16, 1024, 30, 3
    vocab = CONFIGS[model].vocab_size

    # chips are detected by the head; a cluster without one fails the
    # {"TPU": 1} request below with a message instead of waiting
    ray_tpu.init(num_cpus=4)

    n_rows = (steps + warmup + 6) * batch

    def gen_tokens(blk):
        n = len(blk["id"])
        rng = np.random.default_rng(int(blk["id"][0]) + 1)
        return {
            "tokens": rng.integers(0, vocab, size=(n, seq + 1)).astype(np.int32),
            "mask": np.ones((n, seq + 1), np.int32),
        }

    ds = rdata.range(n_rows, override_num_blocks=8).map_batches(
        gen_tokens, batch_size=batch
    )

    trainer = JaxTrainer(
        _trainer_train_fn,
        train_loop_config={
            "model": model, "batch": batch, "seq": seq,
            "steps": steps, "warmup": warmup,
        },
        scaling_config=ScalingConfig(
            num_workers=1,
            resources_per_worker={"CPU": 1, "TPU": 1},
        ),
        datasets={"train": ds},
    )
    result = trainer.fit()
    ray_tpu.shutdown()
    if result.error is not None:
        raise SystemExit(f"trainer bench failed: {result.error!r}")

    final = next(
        (m for m in reversed(result.metrics_history) if m.get("final")), None
    )
    if final is None:
        raise SystemExit("trainer bench: no final report")
    per_step_reports = sum(1 for m in result.metrics_history if "step" in m)

    cfg = CONFIGS[model]
    flops_per_token = cfg.flops_per_token() + cfg.attention_flops_per_token(seq)
    tokens_per_sec_per_chip = final["tokens_per_sec"] / final["n_devices"]
    mfu = tokens_per_sec_per_chip * flops_per_token / _peak_flops_kind(
        final["device_kind"]
    )

    print(
        f"[bench:trainer] dev={final['device_kind']} model={model} "
        f"batch={batch} seq={seq} compile={final['compile_s']:.1f}s "
        f"step={final['step_ms']:.1f}ms loss={final['loss']:.3f} "
        f"mfu={mfu:.3f} reports={per_step_reports}",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "gpt2_125m_jaxtrainer_tokens_per_sec_per_chip",
                "value": round(tokens_per_sec_per_chip, 1),
                "unit": "tokens/s/chip",
                "vs_baseline": round(mfu / 0.45, 4),
                "mfu": round(mfu, 4),
                "device": final["device_kind"],
                "step_ms": round(final["step_ms"], 2),
                "session_reports": per_step_reports,
                "dataset_stats": final.get("dataset_stats"),
            }
        )
    )


# --------------------------------------------------------------------------
# rl mode — the second north star: PPO learner samples/sec/chip
# --------------------------------------------------------------------------


def main_rl():
    """Three RL numbers (BASELINE.json metric #2; reference intent:
    rllib/core/learner/learner_group.py:61):
      - learner-only: PPOLearner.update on the chip over a large synthetic
        batch — samples/sec/chip through the jitted epochs-x-minibatches
        program, H2D included (it is part of real learner feed cost)
      - pipeline: PPO end-to-end on CartPole — CPU rollout actors feeding
        the learner through Algorithm.training_step
      - weight-broadcast latency learner -> rollout workers
    The learner runs IN THIS child process (it holds the chip); rollout
    actors are CPU-pinned workers."""
    import jax
    import numpy as np

    from ray_tpu.rl.learner import PPOLearner
    from ray_tpu.rl.sample_batch import (
        ACTIONS, ADVANTAGES, LOGP, OBS, TARGETS, VALUES, SampleBatch,
    )

    dev = jax.devices()[0]
    _require_tpu(dev)
    kind = dev.device_kind

    obs_dim, n_act = 64, 8
    B, mb, iters = 65536, 8192, 5
    learner = PPOLearner(
        obs_dim, n_act, hidden=(256, 256), minibatch_size=mb, num_epochs=4
    )
    rng = np.random.default_rng(0)
    batch = SampleBatch(
        {
            OBS: rng.normal(size=(B, obs_dim)).astype(np.float32),
            ACTIONS: rng.integers(0, n_act, B).astype(np.int64),
            LOGP: np.full(B, -np.log(n_act), np.float32),
            ADVANTAGES: rng.normal(size=B).astype(np.float32),
            TARGETS: rng.normal(size=B).astype(np.float32),
            VALUES: rng.normal(size=B).astype(np.float32),
        }
    )
    learner.update(batch)  # compile
    # update() trains on the mesh-aligned truncation, not B — credit only
    # what was actually processed (guards a future B/mb retune)
    used = learner._built_used
    assert used == B, (used, B)
    t0 = time.perf_counter()
    for _ in range(iters):
        learner.update(batch)
    dt = time.perf_counter() - t0
    feed_sps = used * iters / dt  # includes fresh H2D per update

    # device-resident batch: the learner PROGRAM's throughput (epochs x
    # minibatches on-chip), beside the feed-included number above, which
    # pays a fresh H2D copy of the batch per update.
    import jax.numpy as jnp

    cols = {
        k: jnp.asarray(batch[k][:used])
        for k in (OBS, ACTIONS, LOGP, ADVANTAGES, TARGETS, VALUES)
    }
    from ray_tpu.rl.sample_batch import LOSS_MASK

    cols[LOSS_MASK] = jnp.ones(used, jnp.float32)
    state, m = learner._update_fn(learner.state, cols)
    jax.block_until_ready(m["total_loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, m = learner._update_fn(state, cols)
    jax.block_until_ready(m["total_loss"])
    dt = time.perf_counter() - t0
    learner.state = state
    learner_sps = used * iters / dt

    # -- end-to-end PPO pipeline on CartPole + weight broadcast --
    import ray_tpu
    from ray_tpu.rl.ppo import PPOConfig

    ray_tpu.init(num_cpus=10)  # logical slots: the scaling sweep peaks at 8 actors + learner
    algo = (
        PPOConfig()
        .environment("CartPole-v1")
        .rollouts(num_rollout_workers=2, rollout_fragment_length=250)
        .training(train_batch_size=2000, minibatch_size=256, num_epochs=4)
        .build()
    )
    algo.train()  # warm: rollout-actor spawn + learner compile at this size
    t0 = time.perf_counter()
    n = 0
    for _ in range(2):
        res = algo.train()
        n += res["num_env_steps_sampled_this_iter"]
    pipeline_sps = n / (time.perf_counter() - t0)

    w = algo.learner_group.get_weights()
    t0 = time.perf_counter()
    algo.workers.set_weights(w)
    broadcast_ms = (time.perf_counter() - t0) * 1000.0
    algo.stop()

    # -- rollout-actor scaling curves (VERDICT r4 #9): the SAME pipeline at
    # 1/2/4/8 rollout actors, two env regimes:
    #   cpu_bound     — CartPole as-is: rollouts saturate host cores, so on
    #                   an N-core host the curve tops out at ~N (on this
    #                   1-core rig it INVERTS from scheduler contention —
    #                   recorded as-is, host_cpus rides along)
    #   latency_bound — CartPole with 1ms step latency (simulator/IO-wait
    #                   shaped, the regime distributed rollouts exist for):
    #                   actors overlap their waits, so the curve shows the
    #                   framework's actual fan-out scaling even on 1 core
    def _slow_cartpole():
        import gymnasium

        class _SlowStep(gymnasium.Wrapper):
            def step(self, action):
                time.sleep(0.001)
                return self.env.step(action)

        return _SlowStep(gymnasium.make("CartPole-v1"))

    def _curve(env_spec, train_batch, frag):
        pts = []
        for n_workers in (1, 2, 4, 8):
            a = (
                PPOConfig()
                .environment(env_spec)
                .rollouts(num_rollout_workers=n_workers,
                          rollout_fragment_length=frag)
                .training(train_batch_size=train_batch, minibatch_size=256,
                          num_epochs=4)
                .build()
            )
            a.train()  # warm (actor spawn; learner jit is size-cached)
            t0 = time.perf_counter()
            n = 0
            for _ in range(2):
                res = a.train()
                n += res["num_env_steps_sampled_this_iter"]
            pts.append(
                {"rollout_actors": n_workers,
                 "samples_per_sec": round(n / (time.perf_counter() - t0), 1)}
            )
            a.stop()
        return pts

    scaling = {
        "cpu_bound": _curve("CartPole-v1", 2000, 250),
        "latency_bound": _curve(_slow_cartpole, 2000, 250),
    }
    ray_tpu.shutdown()

    print(
        f"[bench:rl] dev={kind} learner={learner_sps:,.0f} samples/s "
        f"(feed-included {feed_sps:,.0f}; B={B} epochs=4) "
        f"pipeline={pipeline_sps:,.0f} samples/s broadcast={broadcast_ms:.1f}ms",
        file=sys.stderr,
    )
    print(
        json.dumps(
            {
                "metric": "ppo_learner_samples_per_sec_per_chip",
                "value": round(learner_sps, 1),
                "unit": "samples/s/chip",
                "device": kind,
                "feed_included_samples_per_sec": round(feed_sps, 1),
                "pipeline_samples_per_sec": round(pipeline_sps, 1),
                "weight_broadcast_ms": round(broadcast_ms, 2),
                "update_ms": round(dt / iters * 1000, 2),
                "batch_size": B,
                "rollout_scaling": scaling,
                "host_cpus": os.cpu_count(),
            }
        )
    )


# --------------------------------------------------------------------------
# supervisor
# --------------------------------------------------------------------------


def _install_stack_dumper():
    """Child-side half of the hang watchdog: register a faulthandler that
    dumps EVERY thread's stack to $RAY_TPU_BENCH_STACKDUMP on SIGUSR2. The
    supervisor fires the signal right before group-killing a hung phase, so
    the dump lands in the phase row and a TPU hang shows WHERE the child
    was wedged — inside a collective, opening the chip, the feed pipeline —
    instead of evaporating with the process."""
    path = os.environ.get("RAY_TPU_BENCH_STACKDUMP")
    if not path:
        return
    import faulthandler
    import signal

    try:
        f = open(path, "w")
        faulthandler.register(signal.SIGUSR2, file=f, all_threads=True)
    except Exception as e:  # never let observability break the phase
        print(f"[bench] stack dumper not installed: {e}", file=sys.stderr)


def _collect_stack_dump(pid, dump_path, wait_s=3.0):
    """Supervisor-side half: SIGUSR2 the hung child and wait for its
    faulthandler to finish writing dump_path (the caller reads the file).
    A child that never installed the handler dies to SIGUSR2's default
    disposition — detected via signal-0 probe so the wait ends early
    instead of burning the full wait_s (the group SIGKILL was coming
    anyway)."""
    import signal

    try:
        os.kill(pid, signal.SIGUSR2)
    except OSError:
        return
    deadline = time.monotonic() + wait_s
    last = -1
    while time.monotonic() < deadline:
        try:
            size = os.path.getsize(dump_path)
        except OSError:
            size = 0
        if size > 0 and size == last:
            return  # dump finished growing
        last = size
        if size == 0:
            try:
                os.kill(pid, 0)  # still alive?
            except OSError:
                return  # died without a handler: no dump is coming
        time.sleep(0.15)


def _run_child(cmd, child_env, timeout, stack_dump_path=None):
    """Returns (rc|None, stdout, stderr); rc None = hung/timed out.

    Own session + group-kill on timeout: a wedged child may have forked
    helpers (the trainer phase's workers) that inherit the pipes — killing
    only the child would leave communicate() blocked short of EOF forever.

    stack_dump_path: when set, a timed-out child gets SIGUSR2 first so its
    faulthandler (see _install_stack_dumper) can write thread stacks there
    before the SIGKILL lands; the caller reads the file afterwards."""
    import signal
    import subprocess

    p = subprocess.Popen(
        cmd, env=child_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
    )
    try:
        out, err = p.communicate(timeout=timeout)
        return p.returncode, out or "", err or ""
    except subprocess.TimeoutExpired:
        if stack_dump_path:
            _collect_stack_dump(p.pid, stack_dump_path)
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except OSError:
            p.kill()
        try:
            out, err = p.communicate(timeout=10)
        except Exception:
            out, err = "", ""
        return None, out or "", err or ""
    except BaseException:
        # SIGTERM/budget abort mid-communicate: the child must not outlive
        # the supervisor (it would hold the chip claim hostage)
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except OSError:
            p.kill()
        raise


_MIN_PHASE_WINDOW_S = 5.0  # a smaller budget slice can't fit any phase


def _budget_left(deadline):
    """Seconds left in the global budget (None = unlimited)."""
    return None if deadline is None else deadline - time.monotonic()


def _emit_row(results_path: str, mode: str, row: dict) -> None:
    """Append one completed phase row to the results file IMMEDIATELY
    (VERDICT weak #1b: a later hung phase must degrade to partial results,
    never lose finished work). __graft_entry__._emit_result_row mirrors
    this jsonl contract for the MULTICHIP two_slice row — keep in lockstep."""
    if not results_path:
        return
    try:
        with open(results_path, "a") as f:
            f.write(json.dumps({"phase": mode, "row": row}) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError as e:
        print(f"[bench] could not emit {mode} row: {e}", file=sys.stderr)


def _phase(mode: str, timeout: float, attempts: int,
           deadline=None, results_path: str = ""):
    """Run one bench phase in child processes until a JSON line lands.
    Returns the parsed row (dict) or None — there is no fallback to another
    device. The child-with-timeout contains a phase that hangs reaching
    the chip. Every child timeout is clamped to the global budget
    (`deadline`, monotonic); a completed row is appended to `results_path`
    the moment it lands."""
    # test hook: RAY_TPU_BENCH_CHILD_SCRIPT swaps the child for a fake
    # (e.g. one that sleeps forever) without patching this module
    me = os.environ.get("RAY_TPU_BENCH_CHILD_SCRIPT") or os.path.abspath(__file__)
    backoffs = [15.0, 30.0]
    env = dict(os.environ, RAY_TPU_BENCH_CHILD=mode)
    for i in range(attempts):
        left = _budget_left(deadline)
        if left is not None and left < _MIN_PHASE_WINDOW_S:
            print(f"[bench] {mode}: global budget exhausted "
                  f"({left:.0f}s left); skipping", file=sys.stderr)
            return None
        child_timeout = timeout if left is None else min(timeout, left)
        # hang watchdog: the child registers a SIGUSR2 faulthandler on this
        # path; a timed-out child dumps its thread stacks here before dying
        import tempfile

        fd, dump_path = tempfile.mkstemp(prefix=f"bench_{mode}_stacks_")
        os.close(fd)
        env["RAY_TPU_BENCH_STACKDUMP"] = dump_path
        t0 = time.perf_counter()
        try:
            rc, out, err = _run_child(
                [sys.executable, me], env, child_timeout,
                stack_dump_path=dump_path,
            )
            dt = time.perf_counter() - t0
            stacks = ""
            if rc is None:
                try:
                    with open(dump_path) as f:
                        stacks = f.read()
                except OSError:
                    pass
        finally:
            try:
                os.unlink(dump_path)
            except OSError:
                pass
        row = _last_json(out)
        if rc == 0 and row is not None:
            sys.stderr.write(err)
            _emit_row(results_path, mode, row)
            return row
        why = "hung (timeout)" if rc is None else f"rc={rc}"
        tail = "\n".join(err.strip().splitlines()[-6:])
        print(f"[bench] {mode} attempt {i + 1}/{attempts} failed ({why}, "
              f"{dt:.0f}s){': ' + tail if tail else ''}", file=sys.stderr)
        if stacks:
            # the whole point of the watchdog: the hang site rides the
            # incremental results file as a phase row, so a wedged trainer
            # phase can finally be root-caused from the round artifacts
            print(f"[bench] {mode} hung-child thread stacks:\n{stacks}",
                  file=sys.stderr)
            _emit_row(results_path, mode, {
                "hung": True,
                "attempt": i + 1,
                "timeout_s": child_timeout,
                "stack_dump": stacks,
            })
        if i < attempts - 1:
            pause = backoffs[min(i, len(backoffs) - 1)]
            left = _budget_left(deadline)
            if left is not None:
                pause = max(0.0, min(pause, left - _MIN_PHASE_WINDOW_S))
            time.sleep(pause)
    return None


def _last_json(out: str):
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


class _BenchAborted(Exception):
    """SIGTERM landed: stop launching work, emit best-so-far."""


def _supervise() -> int:
    # INTERLEAVED raw/trainer reps (VERDICT r4 #5): alternating the two
    # phases puts both under the same slow host drift, so the overhead
    # claim is a mean ± spread over paired runs instead of one pair of
    # single-run numbers minutes apart (which once produced a nonsense
    # negative overhead).
    #
    # Global wall-clock budget (VERDICT weak #1b): the worst-case phase
    # schedule exceeds any sane driver kill-timeout by construction, so the
    # supervisor clamps itself — phases that don't fit the remaining budget
    # are SKIPPED and the best-so-far JSON still prints. SIGTERM gets the
    # same degradation instead of losing finished rows.
    import signal

    reps = max(1, int(os.environ.get("RAY_TPU_BENCH_OVERHEAD_REPS", "2")))
    raw_timeout = float(os.environ.get("RAY_TPU_BENCH_TPU_TIMEOUT_S", "300"))
    budget_s = float(os.environ.get("RAY_TPU_BENCH_TOTAL_BUDGET_S", "3300"))
    deadline = time.monotonic() + budget_s if budget_s > 0 else None
    results_path = os.environ.get("RAY_TPU_BENCH_RESULTS", "")

    def _on_term(signum, frame):
        raise _BenchAborted()

    old_term = signal.signal(signal.SIGTERM, _on_term)
    raws, trainers, rep_pairs = [], [], []
    hbm = rl = decode = None
    try:
        for _ in range(reps):
            r = _phase("raw", raw_timeout, 3,
                       deadline=deadline, results_path=results_path)
            if r is not None:
                raws.append(r)
            t = _phase("trainer", 600, 2,
                       deadline=deadline, results_path=results_path)
            if t is not None:
                trainers.append(t)
            if r is not None and t is not None:
                # overhead pairs only from reps where BOTH phases ran — a
                # failed rep must not pair measurements minutes apart
                rep_pairs.append((r, t))
        # decode rides early among the satellite rows: it is the cheapest
        # TPU phase, so a later trainer/hbm hang still leaves the serving
        # row in the incremental results file
        decode = _phase("decode", 600, 2,
                        deadline=deadline, results_path=results_path)
        hbm = _phase("hbm", 600, 2,
                     deadline=deadline, results_path=results_path)
        rl = _phase("rl", 600, 2,
                    deadline=deadline, results_path=results_path)
    except _BenchAborted:
        print("[bench] SIGTERM: emitting best-so-far results", file=sys.stderr)
    finally:
        signal.signal(signal.SIGTERM, old_term)
    raw = raws[-1] if raws else None
    trainer = trainers[-1] if trainers else None

    if trainer is not None:
        primary = dict(trainer)
        if raw is not None:
            primary["raw"] = raw
            # only comparable when both phases ran on the same device
            pairs = [
                (r, t) for r, t in rep_pairs
                if r.get("mfu") and r.get("device") == t.get("device")
            ]
            if pairs:
                ovh = [
                    (r["mfu"] - t.get("mfu", 0.0)) / r["mfu"] * 100
                    for r, t in pairs
                ]
                mean = sum(ovh) / len(ovh)
                spread = (max(ovh) - min(ovh)) / 2 if len(ovh) > 1 else None
                primary["trainer_overhead_vs_raw_pct"] = round(mean, 2)
                if spread is not None:
                    primary["trainer_overhead_spread_pct"] = round(spread, 2)
                primary["overhead_pairs"] = [
                    {"raw_mfu": r["mfu"], "trainer_mfu": t.get("mfu")}
                    for r, t in pairs
                ]
    elif raw is not None:
        primary = dict(raw)
        primary["trainer_row_missing"] = True
    else:
        print("[bench] no phase produced a result", file=sys.stderr)
        return 1
    failed = [
        name for name, row in (
            ("raw", raw), ("trainer", trainer), ("decode", decode),
            ("hbm", hbm), ("rl", rl),
        ) if row is None
    ]
    if hbm is not None:
        primary["hbm"] = hbm
    if rl is not None:
        primary["rl"] = rl
    if decode is not None:
        primary["decode"] = decode
    print(json.dumps(primary))
    if failed:
        # best-so-far still prints, but a run with a failed, hung or
        # skipped phase is not a passing run
        print(f"[bench] phases without a result: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    mode = os.environ.get("RAY_TPU_BENCH_CHILD")
    if mode:
        _install_stack_dumper()
    if mode == "raw" or mode == "1":  # "1" = old envvar spelling
        main_raw()
    elif mode == "trainer":
        main_trainer()
    elif mode == "hbm":
        main_hbm()
    elif mode == "rl":
        main_rl()
    elif mode == "decode":
        main_decode()
    else:
        sys.exit(_supervise())
