#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python3 chip_smoke.py            one TPU chip: train, serve, shutdown
    python3 chip_smoke.py --chips 4  one host, four chips: the sharded
                                     trainer and its one-device comparison,
                                     and nothing else

Drives the two accelerator paths users run, through the entry points they
call (`ray_tpu.init` -> head -> TPU worker actor), at the full width of
CONFIGS["gpt_1b"] with weights made from a seed:

  train  a seeded token Dataset -> JaxTrainer, one worker holding the
         chip(s) -> make_sharded_init / make_train_step (flash attention),
         a handful of steps fed by iter_device_batches, session.report each
  serve  serve.deploy_generation -> four concurrent HTTP/SSE clients through
         the proxy -> ContinuousBatcher(PagedDecodeEngine), bf16 and int8 KV;
         then the fused kernel against the gather engine on the same weights
         and prompts

This process starts the cluster and never initialises a JAX backend: a chip
belongs to one process, and that process is the TPU worker. Every verdict is
taken here, by check(); the workers only report facts. There is no CPU path:
without a chip the script fails. One JSON line per phase; the LAST line is
{"ok": true, "device": {"platform", "kind", "count"}} as JAX reported it in
the worker, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time

SEED = 0
# the configuration bench run r04 took on this chip: flash attention, remat
# that keeps q/k/v, unrolled layers, chunked loss, bf16 momentum
TRAIN = {
    "model": "gpt_1b", "seq": 1024, "steps": 5, "loss_chunk": 128,
    "batch_per_chips": {1: 6, 4: 4},
}
SERVE = {
    "model": "gpt_1b", "clients": 4, "new_tokens": 64, "prefix_blocks": 3,
    "tail_tokens": 24,
    # 3.8 GB of bf16 KV beside 4.6 GB of f32 parameters — four times what
    # the engine's eight slots can fill: the size the decode step was
    # compiled at before the first chip run, not tuned
    "engine_kwargs": {"num_blocks": 513},
}
# fused kernel vs gather engine, first-step logits: both compute in bf16
# (8 mantissa bits) through 14 layers; 2^-4 of the largest logit is 16 ulps
LOGIT_TOL = 2.0 ** -4
PARITY_TOL = 5e-2  # sharded vs one-device first-step loss


def emit(phase: str, **row) -> None:
    print(json.dumps({"phase": phase, **row}), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED — {what}")


# ----------------------------------------------------------------- train


def train_loop(config):
    """Runs inside the TrainWorker actor — the process that owns the chips.
    Reports facts; the driver judges them."""
    import dataclasses
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import CONFIGS
    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh
    from ray_tpu.train import session
    from ray_tpu.train.step import (
        default_optimizer, make_sharded_init, make_train_step,
    )

    # init values must not depend on the output sharding, or the one-device
    # comparison trains different parameters
    jax.config.update("jax_threefry_partitionable", True)
    devs = jax.devices()
    cfg = dataclasses.replace(
        CONFIGS[config["model"]], attention="flash", remat_policy="flash_qkv",
        scan_layers=False, loss_chunk=config["loss_chunk"],
    )
    opt = default_optimizer(lr=1e-4, warmup=10, mu_dtype=jnp.bfloat16)

    def build(devices, spec, rules):
        mesh = build_mesh(spec, devices=devices)
        init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
        state = init_fn(jax.random.PRNGKey(config["seed"]))
        step = make_train_step(cfg, mesh, rules, opt, shardings)
        return mesh, state, step

    def probe(params):
        # a fixed slice of every leaf, on the host: the step donates its
        # state, so "did the parameters move" is asked of copies
        return [np.asarray(x.ravel()[:256]) for x in jax.tree.leaves(params)]

    n = len(devs)
    if n == 1:
        spec, rules = MeshSpec(dp=1), PRESET_RULES["dp"]
    else:
        spec, rules = MeshSpec(fsdp=n), PRESET_RULES["fsdp"]
    mesh, state, step = build(devs, spec, rules)
    before = probe(state.params)
    total_param_bytes = sum(x.nbytes for x in jax.tree.leaves(state.params))
    param_bytes = {d.id: 0 for d in devs}
    for leaf in jax.tree.leaves(state.params):
        for sh in leaf.addressable_shards:
            param_bytes[sh.device.id] += sh.data.nbytes

    ds = session.get_dataset_shard("train")
    it = ds.iter_device_batches(
        batch_size=config["batch"], mesh=mesh, rules=rules, prefetch=2
    )
    compiled = None
    losses, step_ms = [], []
    first_batch = None
    for i, batch in enumerate(it):
        if i >= config["steps"]:
            break
        if compiled is None:
            first_batch = {k: np.asarray(v) for k, v in batch.items()}
            t0 = _time.perf_counter()
            compiled = step.lower(state, batch).compile()
            compile_s = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])  # blocks until the step is done
        step_ms.append((_time.perf_counter() - t0) * 1e3)
        losses.append(loss)
        session.report({"step": int(metrics["step"]), "loss": loss})
    it.close()

    after = probe(state.params)
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    final = {
        "final": True,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": n,
        "pid": os.getpid(),
        "jax_platforms": os.environ.get("JAX_PLATFORMS"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1},
        "losses": losses,
        "final_step": int(state.step),
        "compile_s": round(compile_s, 2),
        "step_ms": [round(x, 2) for x in step_ms],
        "leaves": len(before),
        "leaves_moved": sum(
            int(not np.array_equal(a, b)) for a, b in zip(before, after)
        ),
        "pallas_calls_in_step": hlo.count("tpu_custom_call"),
        "collectives_in_step": sum(
            hlo.count(op) for op in
            ("all-gather", "all-reduce", "reduce-scatter", "all-to-all")
        ),
        "total_param_bytes": int(total_param_bytes),
        "param_bytes_per_device": [int(param_bytes[d.id]) for d in devs],
        "step_argument_bytes": int(mem.argument_size_in_bytes),
        "step_temp_bytes": int(mem.temp_size_in_bytes),
        "peak_bytes_per_device": [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devs
        ],
    }

    if n > 1:
        # what the sharded run is compared with: the same seed and the same
        # first batch on one device of this process. A one-device gpt_1b
        # state will not sit beside device 0's share of the sharded one:
        # drop that first.
        del state, compiled, metrics, batch, it
        _, state1, step1 = build(devs[:1], MeshSpec(dp=1), PRESET_RULES["dp"])
        _, metrics1 = step1(state1, first_batch)
        final["one_device_first_loss"] = float(metrics1["loss"])
    session.report(final)
    return "done"


def run_train(chips: int) -> dict:
    import numpy as np

    from ray_tpu import data as rdata
    from ray_tpu.models import CONFIGS
    from ray_tpu.train import JaxTrainer, ScalingConfig

    batch, seq = TRAIN["batch_per_chips"][chips], TRAIN["seq"]
    vocab = CONFIGS[TRAIN["model"]].vocab_size

    def gen_tokens(blk):
        rows = len(blk["id"])
        rng = np.random.default_rng(SEED + int(blk["id"][0]) + 1)
        return {
            "tokens": rng.integers(0, vocab, size=(rows, seq + 1)).astype(np.int32),
            "mask": np.ones((rows, seq + 1), np.int32),
        }

    ds = rdata.range(
        (TRAIN["steps"] + 3) * batch, override_num_blocks=4
    ).map_batches(gen_tokens, batch_size=batch)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "model": TRAIN["model"], "batch": batch, "seed": SEED,
            "steps": TRAIN["steps"], "loss_chunk": TRAIN["loss_chunk"],
        },
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"CPU": 1, "TPU": chips}
        ),
        datasets={"train": ds},
    )
    result = trainer.fit()
    check(result.error is None, f"JaxTrainer.fit: {result.error!r}")
    reports = [m for m in result.metrics_history if "step" in m]
    final = next((m for m in result.metrics_history if m.get("final")), None)
    check(final is not None, "the train loop sent no final report")
    emit("train", chips=chips, batch=batch, seq=seq, **final)

    check(final["platform"] == "tpu",
          f"the TrainWorker computed on {final['platform']!r}, not the TPU")
    check(final["n_devices"] == chips,
          f"the TrainWorker saw {final['n_devices']} devices, wanted {chips}")
    check(len(reports) == TRAIN["steps"] == len(final["losses"]),
          f"{len(reports)} step reports for {TRAIN['steps']} steps")
    check(all(np.isfinite(final["losses"])), f"losses {final['losses']}")
    check(final["final_step"] == TRAIN["steps"],
          f"state.step is {final['final_step']} after {TRAIN['steps']} steps")
    # a donated state that comes back unchanged is the compile-cache-hit
    # aliasing fault (ROADMAP, "Signatures")
    check(final["leaves_moved"] == final["leaves"],
          f"only {final['leaves_moved']} of {final['leaves']} parameter "
          "leaves changed over the steps")
    check(final["pallas_calls_in_step"] > 0,
          "no Pallas call in the compiled step: flash attention fell to dense")
    if chips > 1:
        per_dev = final["param_bytes_per_device"]
        check(len(per_dev) == chips and min(per_dev) > 0
              and max(per_dev) < final["total_param_bytes"],
              f"parameters are not spread over the chips: {per_dev} of "
              f"{final['total_param_bytes']} bytes")
        check(final["collectives_in_step"] > 0,
              "no collective in the compiled sharded step")
        delta = abs(final["losses"][0] - final["one_device_first_loss"])
        emit("parity", sharded_first_loss=final["losses"][0],
             one_device_first_loss=final["one_device_first_loss"],
             delta=delta, tolerance=PARITY_TOL)
        check(delta < PARITY_TOL,
              f"sharded first-step loss differs from one device by {delta}")
    return final


# ----------------------------------------------------------------- serve


def make_prompts():
    import numpy as np

    from ray_tpu._private.config import GLOBAL_CONFIG as gcfg
    from ray_tpu.models import CONFIGS

    vocab = CONFIGS[SERVE["model"]].vocab_size
    rng = np.random.default_rng(SEED)
    prefix = rng.integers(
        1, vocab, size=SERVE["prefix_blocks"] * int(gcfg.serve_kv_block_tokens)
    )
    return [
        [int(t) for t in prefix]
        + [int(t) for t in rng.integers(1, vocab, size=SERVE["tail_tokens"])]
        for _ in range(SERVE["clients"])
    ]


def sse_generate(address: str, path: str, tokens, out: dict, key) -> None:
    """One raw-socket HTTP client: POST the prompt, read the chunked
    text/event-stream to its end, keep the tokens and whether [DONE] came."""
    host, port = address.split(":")
    body = json.dumps({
        "tokens": tokens, "max_new_tokens": SERVE["new_tokens"], "stream": True,
    }).encode()
    t0 = time.monotonic()
    with socket.create_connection((host, int(port)), timeout=900) as s:
        s.sendall(
            f"POST {path} HTTP/1.1\r\nHost: x\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n".encode() + body
        )
        buf, first_s = b"", None
        while b"0\r\n\r\n" not in buf:
            data = s.recv(65536)
            if not data:
                break
            if first_s is None and b"data: " in data:
                first_s = time.monotonic() - t0
            buf += data
    events = [ln[6:] for ln in buf.split(b"\n") if ln.startswith(b"data: ")]
    done = bool(events) and events[-1].strip() == b"[DONE]"
    out[key] = {
        "status": buf.split(b"\r\n", 1)[0].decode(errors="replace"),
        "tokens": [int(e) for e in events[: -1 if done else None]],
        "done": done,
        "first_token_s": first_s,
        "total_s": time.monotonic() - t0,
    }


def agree(a, b) -> int:
    """Length of the token prefix two generations share."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                min(len(a), len(b)))


def worker_pids() -> set:
    from ray_tpu.experimental.state.api import list_workers

    return {w["pid"] for w in list_workers() if w.get("pid")}


def run_serve(kv_dtype: str, prompts) -> dict:
    """Deploy through serve.deploy_generation, send the traffic through the
    HTTP proxy, judge the streams and the replica's stats, delete."""
    from ray_tpu import serve
    from ray_tpu.models import CONFIGS

    name, path = f"smoke-{kv_dtype}", f"/smoke-{kv_dtype}"
    t0 = time.monotonic()
    handle = serve.deploy_generation(
        name, CONFIGS[SERVE["model"]], weights_seed=SEED, route_prefix=path,
        engine_kwargs={**SERVE["engine_kwargs"], "kv_cache_dtype": kv_dtype},
    )
    deploy_s = time.monotonic() - t0
    address = serve.proxy_address()
    outs: dict = {}
    # alone, into an empty cache: the whole prompt prefills, and its blocks
    # become the prefix every later request hits
    sse_generate(address, path, prompts[0], outs, "first")
    threads = [
        threading.Thread(target=sse_generate, args=(address, path, p, outs, i))
        for i, p in enumerate(prompts)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # the same request as client 0, by the same path (full prefix hit): a
    # different answer would mean a result depends on its batch-mates
    sse_generate(address, path, prompts[0], outs, "again")
    stats = handle.engine_stats.remote().result(timeout_s=300)
    serve.delete(name)

    emit(
        "serve", kv_cache_dtype=kv_dtype, deploy_s=round(deploy_s, 2),
        streams={str(k): {kk: vv for kk, vv in v.items() if kk != "tokens"}
                 | {"n_tokens": len(v["tokens"])} for k, v in outs.items()},
        first_tokens=[outs[i]["tokens"][:1] for i in range(len(prompts))],
        # prefill over cached blocks (paged kernel) vs the whole prompt at
        # once: same keys, another summation order, in bf16
        whole_vs_cached_prefix_agree=agree(
            outs["first"]["tokens"], outs["again"]["tokens"]),
        stats={k: stats.get(k) for k in (
            "platform", "device_kind", "attention_impl", "attention_kernel",
            "kv_cache_dtype", "kv_pool_bytes", "kv_blocks_total",
            "device_bytes_in_use", "device_peak_bytes", "device_bytes_limit",
            "max_batch_size", "steps", "prefill_tokens", "prefix_hits",
            "prefix_tokens_reused", "preemptions",
        )},
    )
    check(len(outs) == len(prompts) + 2, f"clients missing: {list(outs)}")
    for k, o in outs.items():
        check(o["done"] and len(o["tokens"]) == SERVE["new_tokens"],
              f"stream {k} ended with {len(o['tokens'])} tokens, "
              f"done={o['done']} ({o['status']})")
    check(stats["platform"] == "tpu",
          f"the replica computed on {stats['platform']!r}, not the TPU")
    check(stats["attention_impl"] == "fused"
          and stats["attention_kernel"] == "pallas",
          "paged attention resolved to "
          f"{stats['attention_impl']}/{stats['attention_kernel']}, "
          "not the fused Pallas kernel")
    check(stats["kv_cache_dtype"] == kv_dtype, "wrong KV dtype in the replica")
    check(stats["prefix_hits"] >= 1, "no prefix hit on a shared prompt prefix")
    check(outs["again"]["tokens"] == outs[0]["tokens"],
          "the same request sent twice returned different tokens "
          f"(agree on {agree(outs['again']['tokens'], outs[0]['tokens'])})")
    check(outs["first"]["tokens"][0] == outs["again"]["tokens"][0],
          "first token differs between whole-prompt and cached-prefix prefill")
    return {"first": outs["first"]["tokens"],
            "tokens": [outs[i]["tokens"] for i in range(len(prompts))]}


class EngineComparison:
    """Runs where the chip is free again, after the deployments are gone:
    the fused-kernel engine and the gather engine on the deployment's weights
    and prompts, one after the other, through the engine contract the
    batcher drives (admit / step / release)."""

    def run(self, model: str, seed: int, kv_dtype: str, prompts, new_tokens: int):
        import jax
        import numpy as np

        from ray_tpu.models import CONFIGS
        from ray_tpu.models.kv_paging import PagedDecodeEngine
        from ray_tpu.models.transformer import init_params

        cfg = CONFIGS[model]
        params = init_params(jax.random.PRNGKey(seed), cfg)
        out = {"pid": os.getpid()}
        for impl in ("fused", "gather"):
            eng = PagedDecodeEngine(
                cfg, params, max_batch_size=len(prompts), attention_impl=impl,
                kv_cache_dtype=kv_dtype, prefix_cache=False,
            )
            first_logits = []
            prefill = eng._prefill

            def spy(*a, _prefill=prefill, **kw):
                res = _prefill(*a, **kw)
                first_logits.append(np.asarray(res[1], np.float32)[0])
                return res

            eng._prefill = spy
            toks = {s: [] for s in range(len(prompts))}
            live = []
            for s, p in enumerate(prompts):
                tok, done = eng.admit(s, {"tokens": p, "max_new_tokens": new_tokens})
                toks[s].append(int(tok))
                if not done:
                    live.append(s)
            while live:
                for s, (tok, done) in eng.step(list(live)).items():
                    toks[s].append(int(tok))
                    if done:
                        live.remove(s)
                        eng.release(s)
            st = eng.stats()
            out[impl] = {
                "tokens": [toks[s] for s in range(len(prompts))],
                "logits": first_logits,
                "kernel": st["attention_kernel"], "platform": st["platform"],
            }
            del eng
        return out


def compare_engines(kv_dtype: str, prompts, served: dict) -> None:
    import numpy as np

    import ray_tpu

    actor = ray_tpu.remote(EngineComparison).options(num_tpus=1).remote()
    res = ray_tpu.get(
        actor.run.remote(
            SERVE["model"], SEED, kv_dtype, prompts, SERVE["new_tokens"]
        ),
        timeout=900,
    )
    ray_tpu.kill(actor)
    fused, gather = res["fused"], res["gather"]
    diffs, scales, agreeing = [], [], []
    for lf, lg, tf, tg in zip(
        fused["logits"], gather["logits"], fused["tokens"], gather["tokens"]
    ):
        diffs.append(float(np.max(np.abs(lf - lg))))
        scales.append(float(np.max(np.abs(lg))))
        agreeing.append(agree(tf, tg))
    emit(
        "compare", kv_cache_dtype=kv_dtype,
        kernels=[fused["kernel"], gather["kernel"]],
        first_logits_max_abs_diff=diffs, first_logits_max_abs=scales,
        tolerance=f"{LOGIT_TOL} x max(1, max|gather logits|)",
        first_token_equal=[
            tf[0] == tg[0] for tf, tg in zip(fused["tokens"], gather["tokens"])
        ],
        agreeing_token_prefix=agreeing, of=SERVE["new_tokens"],
        # the deployment's own answers beside the bare engine's: its first
        # request prefilled the whole prompt, as the engine here does; its
        # concurrent ones prefilled over cached prefix blocks
        served_first_agrees=agree(served["first"], fused["tokens"][0]),
        served_concurrent_agree=[
            agree(a, b) for a, b in zip(served["tokens"], fused["tokens"])
        ],
    )
    check(fused["platform"] == gather["platform"] == "tpu",
          "the comparison engines did not run on the TPU")
    check(fused["kernel"] == "pallas" and gather["kernel"] == "gather",
          f"comparison ran {fused['kernel']} against {gather['kernel']}")
    for i, (d, s) in enumerate(zip(diffs, scales)):
        check(np.isfinite(d) and d <= LOGIT_TOL * max(1.0, s),
              f"prompt {i}: first-step logits differ by {d} (largest {s})")
        check(fused["tokens"][i][0] == gather["tokens"][i][0],
              f"prompt {i}: first token differs between kernel and gather")
    check(served["first"][0] == fused["tokens"][0][0],
          "the deployment's first token differs from the bare engine's on "
          "the same weights, prompt and prefill path")


# ------------------------------------------------------------------ main


def alive(pid: int) -> bool:
    """False once the process has exited — a zombie the head has yet to
    reap holds no chip."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def wait_gone(pids, what: str, timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    left = {p for p in pids if alive(p)}
    while left and time.monotonic() < deadline:
        time.sleep(0.1)
        left = {p for p in left if alive(p)}
    check(not left, f"{what}: worker processes {sorted(left)} are still alive")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips

    import jax
    import jaxlib

    import ray_tpu
    from ray_tpu import serve

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    emit("versions", python=sys.version.split()[0], jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu_version,
         jax_platforms=os.environ.get("JAX_PLATFORMS"),
         jax_compilation_cache_dir=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    platforms = os.environ.get("JAX_PLATFORMS", "")
    check(not platforms or platforms.split(",")[0] == "tpu",
          f"JAX_PLATFORMS={platforms!r} pins this run off the TPU")

    ray_tpu.init()  # chips are detected, not passed in
    seen: set = set()
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        emit("cluster", tpu_chips_detected=have)
        check(have >= chips,
              f"ray_tpu.init() detected {have:g} TPU chips, this run needs "
              f"{chips}")
        final = run_train(chips)
        seen.add(final["pid"])
        if chips == 1:
            prompts = make_prompts()
            for kv_dtype in ("fp", "int8"):
                served = run_serve(kv_dtype, prompts)
                seen |= worker_pids()
                compare_engines(kv_dtype, prompts, served)
            serve.shutdown()
    finally:
        seen |= worker_pids()
        ray_tpu.shutdown()
    wait_gone(seen, "after shutdown")
    from jax._src import xla_bridge

    check(not xla_bridge.backends_are_initialized(),
          "the driver process initialised a JAX backend")
    emit("shutdown", worker_processes_gone=len(seen))
    print(json.dumps({"ok": True, "device": {
        "platform": final["platform"], "kind": final["device_kind"],
        "count": final["n_devices"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
