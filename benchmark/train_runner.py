"""The training runner: chip_smoke.py's run_train / train_loop, turned from
"five steps" into "warm up, check against the plain reference, then a timed
window". Every parameter comes from the cell's file.

run_train runs in the driver process and never touches JAX. train_loop runs
inside the TrainWorker actor, the process that owns the chip(s): it takes
every time and the trace there and reports facts; the driver judges them."""

from __future__ import annotations

import math
import os
import time


def train_loop(config):
    import shutil

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common, trace_reduce
    from ray_tpu.models.transformer import TransformerConfig
    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh
    from ray_tpu.train import session
    from ray_tpu.train.step import (
        default_optimizer, make_sharded_init, make_train_step,
    )

    cell, conf = config["cell"], config["conf"]
    seconds, trace = float(config["seconds"]), bool(config["trace"])

    compiles = []  # every backend compilation this process makes, by time
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **kw: compiles.append(time.perf_counter())
        if name == "/jax/core/compile/backend_compile_duration" else None
    )
    # init values must not depend on the output sharding
    jax.config.update("jax_threefry_partitionable", True)
    devs = jax.devices()
    block = common.load_block(conf)
    cfg = TransformerConfig(**block.transformer_kwargs(conf), **cell["model"])
    o = cell["optimizer"]
    opt = default_optimizer(lr=o["lr"], warmup=o["warmup"],
                            mu_dtype=getattr(jnp, o["mu_dtype"]))
    mesh = build_mesh(MeshSpec(**cell["mesh"]), devices=devs)
    rules = PRESET_RULES[cell["rules"]]
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(common.jax_seed(config["seed"])))
    step = make_train_step(cfg, mesh, rules, opt, shardings)

    def probe(params):
        # the step donates its state, so "did the parameters move" is
        # asked of host copies of a fixed slice of every leaf
        return [np.asarray(x.ravel()[:256]) for x in jax.tree.leaves(params)]

    before = probe(state.params)
    ds = session.get_dataset_shard("train")
    it = ds.iter_device_batches(
        batch_size=config["batch"], mesh=mesh, rules=rules,
        prefetch=cell["prefetch"],
    )
    batch = next(it)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0

    # correctness, outside the window: the plain float32 reference on the
    # system's own parameters and first batch, before the step donates them
    t0 = time.perf_counter()
    ref_loss = block.ref_loss(
        state.params, np.asarray(batch["tokens"]), conf)
    reference_s = time.perf_counter() - t0

    losses = []
    for i in range(cell["warmup_steps"]):
        if i:
            batch = next(it)
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))

    trace_dir = os.path.join(common.ROOT, "chiprun_out", "trace",
                             config["cell"]["name"])
    trace_steps = int(cell["trace_steps"]) if trace else 0
    trace_from = 3  # steps into the window
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---------------------------------------------------------- the window
    step_ms, wait_ms, done_at = [], [], []
    compiles_before = len(compiles)
    setup_done_wall = time.time()
    w0 = time.perf_counter()
    i = 0
    while True:
        if trace and i == trace_from:
            jax.profiler.start_trace(trace_dir)
        t_a = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            batch = next(it)
        t_b = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step_dispatch"):
            state, metrics = compiled(state, batch)
        with jax.profiler.TraceAnnotation("bench.loss_to_host"):
            loss = float(metrics["loss"])  # the step is complete here
        t_c = time.perf_counter()
        wait_ms.append((t_b - t_a) * 1e3)
        step_ms.append((t_c - t_b) * 1e3)
        losses.append(loss)
        done_at.append(t_c - w0)
        i += 1
        if trace and i == trace_from + trace_steps:
            jax.profiler.stop_trace()
        # the window closes with the first step that completes at or after
        # --seconds: every step in it is whole, and the rate is taken over
        # all of its time (with a traced run, after the trace is written)
        if t_c - w0 >= seconds and not (trace and i < trace_from + trace_steps):
            break
    window_s = done_at[-1]
    compiles_in_window = len(compiles) - compiles_before
    it.close()

    after = probe(state.params)
    facts = {
        "final": True,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
        "pid": os.getpid(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "setup_done_wall": setup_done_wall,
        "compile_s": compile_s,
        "reference_s": reference_s,
        "reference_loss": ref_loss,
        "first_loss": losses[0],
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "last_loss": losses[-1],
        "steps": len(step_ms),
        "window_s": window_s,
        "step_ms": step_ms,
        "input_wait_ms": wait_ms,
        "compiles_in_window": compiles_in_window,
        "compiles_total": len(compiles),
        "leaves": len(before),
        "leaves_moved": sum(
            int(not np.array_equal(a, b)) for a, b in zip(before, after)),
        "final_step": int(state.step),
        "pallas_calls_in_step": compiled.as_text().count("tpu_custom_call"),
        "peak_bytes_per_device": [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devs
        ],
    }
    if trace:
        facts["trace"] = trace_reduce.reduce_dir(trace_dir)
        if facts["trace"] is not None and config.get("describe_trace"):
            facts["trace"]["trace_lines"] = trace_reduce.describe(trace_dir)
    session.report(facts)
    return "done"


def run_train(cell: dict, conf: dict, args) -> dict:
    """-> the facts the worker reported, plus the driver's own."""
    import numpy as np

    from ray_tpu import data as rdata
    from ray_tpu.train import JaxTrainer, ScalingConfig

    chips = int(cell["chips"])
    batch = int(cell["batch_per_chip"]) * chips
    seq, vocab, seed = int(cell["seq_len"]), int(conf["vocab_size"]), int(args.seed)

    def gen_tokens(blk):
        rows = len(blk["id"])
        rng = np.random.default_rng([seed, int(blk["id"][0])])
        return {
            "tokens": rng.integers(0, vocab, size=(rows, seq + 1)).astype(np.int32),
            "mask": np.ones((rows, seq + 1), np.int32),
        }

    # sized from --seconds, not from a step count: more than the window can
    # consume at the fastest step this cell could plausibly reach
    steps = (math.ceil(args.seconds * float(cell["max_steps_per_s"]))
             + int(cell["warmup_steps"]) + int(cell["trace_steps"]) + 8)
    rows = steps * batch
    rpb = int(cell["rows_per_block"])
    ds = rdata.range(rows, override_num_blocks=max(1, rows // rpb)).map_batches(
        gen_tokens, batch_size=batch)
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "cell": cell, "conf": conf, "batch": batch, "seed": seed,
            "seconds": args.seconds, "trace": bool(args.trace),
            "describe_trace": bool(getattr(args, "describe_trace", False)),
        },
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"CPU": 1, "TPU": chips}),
        datasets={"train": ds},
    )
    result = trainer.fit()
    if result.error is not None:
        raise RuntimeError(f"JaxTrainer.fit: {result.error!r}")
    facts = next((m for m in result.metrics_history if m.get("final")), None)
    if facts is None:
        raise RuntimeError("the train loop sent no final report")
    facts["batch"], facts["tokens_per_step"] = batch, batch * seq
    return facts
