#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on: sets the
system up through its normal entry points (ray_tpu.init -> head -> TPU
worker), warms every shape, measures for --seconds, checks the outputs
against the plain float32 reference, and prints ONE JSON object as the last
line of its output. With --trace 0 the metrics are the cell's end-to-end
metrics; with --trace 1 its per-layer metrics, read by the small readers
under benchmark/layer_metrics/ from the run's facts.

    --sweep 4,6,8,...   serving cells only: one short window per rate on one
                        deployment, to find the knee. Prints a table, not a
                        result line.

There is no CPU path: without the chips the cell asks for this exits 2 and
prints no result. This process never opens a JAX backend."""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import uuid  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common  # noqa: E402
from benchmark.common import ROOT  # noqa: E402


PLATFORM = "tpu"  # there is no other: a run elsewhere fails


def fail(msg: str, code: int = 2):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def descendants() -> set:
    """pids of every process below this one, from /proc."""
    parent = {}
    for d in os.listdir("/proc"):
        if d.isdigit():
            try:
                with open(f"/proc/{d}/stat") as f:
                    parent[int(d)] = int(f.read().rsplit(")", 1)[1].split()[1])
            except OSError:
                pass
    out, frontier = set(), {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier} - out
        out |= frontier
    return out


def alive(pid: int) -> bool:
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except OSError:
        return False


def reap(pids: set, grace_s: float = 30.0) -> int:
    """Wait until every process this run started has ended; end what is
    still there after `grace_s`. -> how many had to be ended."""
    import signal

    deadline = time.time() + grace_s
    left = {p for p in pids if alive(p)}
    while left and time.time() < deadline:
        time.sleep(0.1)
        left = {p for p in left if alive(p)}
    for p in left:
        try:
            os.kill(p, signal.SIGKILL)
        except OSError:
            pass
    deadline = time.time() + 10.0
    while any(alive(p) for p in left) and time.time() < deadline:
        time.sleep(0.1)
    return len(left)


SOCKET_ROOM = 50  # a unix socket path holds 107 bytes; the session's own part takes 56


def scratch_root(links: list) -> str:
    """Where the cluster keeps its session directory and head storage:
    `ray_tpu_bench` under TMPDIR, never the program's default /tmp/ray_tpu,
    where the runs of two checkouts would meet. The session directory holds
    unix sockets, whose paths are short; under a long TMPDIR it is reached
    through a symbolic link of this run's own, made in the first place that
    is short enough (checkout, HOME, XDG_CACHE_HOME, last /tmp) and removed
    at the end. The link is appended to `links`."""
    real = os.path.join(tempfile.gettempdir(), "ray_tpu_bench")
    os.makedirs(real, exist_ok=True)
    if len(real) <= SOCKET_ROOM:
        return real
    for base in (ROOT, os.path.expanduser("~"),
                 os.environ.get("XDG_CACHE_HOME", ""), "/tmp"):
        link = os.path.join(base, ".rtb" + uuid.uuid4().hex[:8])
        if os.path.isdir(base) and len(link) <= SOCKET_ROOM:
            os.symlink(real, link)
            links.append(link)
            return link
    fail(f"no place for a session directory of at most {SOCKET_ROOM} "
         f"characters (TMPDIR gives {real!r})")


def metric_defs() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {kind: {m["name"]: m for m in bench[kind]}
            for kind in ("end_to_end", "per_layer")}


def wanted(defs: dict, cell_name: str) -> list:
    return [m for m in defs.values()
            if "workloads" not in m or cell_name in m["workloads"]]


def train_result(cell, conf, facts):
    peaks = common.peaks_for(facts["device_kind"])
    tokens = facts["steps"] * facts["tokens_per_step"]
    rate = tokens / facts["window_s"] / facts["n_devices"]
    flops = common.load_block(conf).required_train_flops_per_token(
        conf, cell["seq_len"])
    delta = abs(facts["first_loss"] - facts["reference_loss"])
    checks = {
        # bf16 compute against a float32 reference, averaged over every
        # token of the first batch: see README, "tolerances"
        "loss_matches_reference": delta <= float(cell["loss_tolerance"]),
        "losses_finite": facts["losses_finite"],
        "every_leaf_moved": facts["leaves_moved"] == facts["leaves"],
        "no_compile_in_window": facts["compiles_in_window"] == 0,
        "flash_kernel_in_step": facts["pallas_calls_in_step"] > 0,
        "step_count": facts["final_step"] == facts["steps"] + cell["warmup_steps"],
    }
    e2e = {
        "train_tokens_per_s_per_chip": rate,
        "setup_s": facts["setup_done_wall"] - T_START,
    }
    derived = {
        "mfu": rate * flops / peaks["bf16_flops_per_s"],
        "mfu_base": f"{flops / 1e9:.4f} GFLOP/token required x tokens/s/chip "
                    f"over {peaks['bf16_flops_per_s'] / 1e12:g} TFLOP/s bf16",
        "loss_delta_vs_reference": delta,
        "first_loss": facts["first_loss"],
        "reference_loss": facts["reference_loss"],
        "steps": facts["steps"], "window_s": facts["window_s"],
        "compile_s": facts["compile_s"], "reference_s": facts["reference_s"],
        "checks": checks,
    }
    return e2e, checks, facts["steps"], 0, derived


def serve_window(win: dict, seconds: float) -> dict:
    """The end-to-end metrics of one window, from the client's records."""
    cl = win["client"]
    return {
        "serve_tokens_per_s": cl["tokens_in_window"] / seconds,
        "ttft_p50_ms": common.median(cl["ttft_ms"]),
        "itl_p95_ms": common.percentile(cl["itl_ms"], 95),
        "setup_s": win["setup_done_wall"] - T_START,
    }


def serve_result(facts, seconds: float):
    win = facts["windows"][0]
    cl = win["client"]
    ref = facts["reference"]
    compiles = win["after"]["compiles"] - win["before"]["compiles"]
    checks = {
        "reference_near_argmax": all(r["ok"] for r in ref),
        "no_compile_in_window": compiles == 0,
        "no_request_failed": cl["failed"] == 0,
        "paged_kernel":
            facts["final"]["engine"]["attention_kernel"] == "pallas",
    }
    eng, peak = win["after"]["engine"], win["after"]["kv_blocks_peak"]
    derived = {
        "rate_per_s": win["rate"], "requests": cl["attempted"],
        "in_flight_at_end": cl["in_flight_at_end"],
        # how full the reserved pool got (window and drain): `used` counts
        # what the prefix cache keeps of finished requests, `live` does not
        "kv_blocks": {"total": eng["kv_blocks_total"],
                      "peak_used": peak["used"], "peak_live": peak["live"],
                      "peak_live_share": peak["live"] / eng["kv_blocks_total"]},
        # the tails beside the end-to-end numbers, in every run
        "ttft_ms": {f"p{q}": common.percentile(cl["ttft_ms"], q)
                    for q in (50, 90, 95, 99)},
        "itl_ms": {f"p{q}": common.percentile(cl["itl_ms"], q)
                   for q in (50, 95, 99)},
        "compiles_in_window": compiles, "reference": ref,
        "construct_s": facts["final"]["construct_s"],
        "warm_up": facts["warm_up"], "drained_s": win["drained_s"],
        "checks": checks,
    }
    return (serve_window(win, seconds), checks, cl["attempted"], cl["failed"],
            derived)


def serve_reader_facts(win: dict) -> dict:
    return {"kind": "serve", "client": win["client"], "before": win["before"],
            "after": win["after"], "trace": win.get("trace")}


def read_layers(defs: dict, cell: dict, rf: dict) -> dict:
    """The cell's per-layer metrics, each by its own reader; a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in wanted(defs["per_layer"], cell["name"]):
        value = common.load_reader(m["name"])(rf)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def sweep(cell, conf, defs, args):
    """One row per rate: the end-to-end metrics and the per-layer metrics
    that need no trace, plus what tells a growing backlog."""
    from benchmark.serve_runner import run_serve

    rates = [float(x) for x in args.sweep.split(",")]
    facts = run_serve(cell, conf, args, rates=rates)
    rows = []
    for win in facts["windows"]:
        cl = win["client"]
        row = {"rate": win["rate"], "attempted": cl["attempted"],
               "failed": cl["failed"],
               "offered_tokens_per_s": cl["tokens_streamed"] / args.seconds,
               "in_flight_at_end": cl["in_flight_at_end"],
               "drained_s": win["drained_s"],
               "compiles": win["after"]["compiles"] - win["before"]["compiles"],
               **serve_window(win, args.seconds)}
        layers = read_layers(defs, cell, serve_reader_facts(win))
        row.update({k: v["value"] for k, v in layers.items()})
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", f"sweep.{cell['name']}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"rows": rows, "reference": facts["reference"]}, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--describe-trace", action="store_true",
                    help="with --trace 1: also write the trace's planes, "
                         "lines and event names to chiprun_out/")
    args = ap.parse_args()

    cell = common.load_workload(args.workload)
    conf = common.load_config(cell["config"])
    defs = metric_defs()

    # caches and scratch stay inside the checkout or under TMPDIR
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    # every program, however quick to compile, is in the cache after a
    # checkout's first run: set-up is then the same work every time
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and platforms.split(",")[0] != PLATFORM:
        fail(f"JAX_PLATFORMS={platforms!r} pins this run off the TPU")

    import ray_tpu

    links: list = []
    try:
        tmp_root = scratch_root(links)
        os.environ["RAY_TPU_SESSION_DIR_ROOT"] = tmp_root
        os.environ["RAY_TPU_HEAD_STORAGE_DIR"] = os.path.join(
            tmp_root, "storage")
        ray_tpu.init()  # chips are detected, not passed in
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < cell["chips"]:
            fail(f"{have:g} TPU chips here, cell {cell['name']} needs "
                 f"{cell['chips']}")
        if args.sweep:
            sweep(cell, conf, defs, args)
            return 0
        if cell["kind"] == "train":
            from benchmark.train_runner import run_train

            facts = run_train(cell, conf, args)
            e2e, checks, attempted, failed, derived = train_result(
                cell, conf, facts)
            dev = {"platform": facts["platform"], "kind": facts["device_kind"],
                   "count": facts["n_devices"],
                   "memory_peak_bytes": max(facts["peak_bytes_per_device"])}
            trace = facts.get("trace")
        elif cell["kind"] == "serve":
            from benchmark.serve_runner import run_serve

            facts = run_serve(cell, conf, args)
            e2e, checks, attempted, failed, derived = serve_result(
                facts, args.seconds)
            fin = facts["final"]
            dev = {"platform": fin["platform"], "kind": fin["device_kind"],
                   "count": fin["n_devices"],
                   "memory_peak_bytes": fin["engine"]["device_peak_bytes"]}
            trace = facts["windows"][0].get("trace")
            from ray_tpu import serve

            serve.shutdown()
        else:
            fail(f"unknown cell kind {cell['kind']!r}")
    finally:
        started = descendants()
        ray_tpu.shutdown()
        killed = reap(started)
        if killed:
            print(f"benchmark: {killed} process(es) outlived shutdown and "
                  "were ended", file=sys.stderr, flush=True)
        for link in links:
            os.unlink(link)

    if dev["platform"] != PLATFORM:
        fail(f"the worker computed on {dev['platform']!r}, not the TPU")
    if dev["count"] != cell["chips"]:
        fail(f"the worker saw {dev['count']} devices, the cell needs "
             f"{cell['chips']}")
    common.peaks_for(dev["kind"])  # a kind without peaks is an error

    line = {"correct": all(checks.values()), "attempted": attempted,
            "failed": failed, "metrics": {}, "device": dev,
            "derived": derived}
    if args.trace:
        if trace is None:
            fail("the traced run recorded no device operation", 3)
        dev["busy_s"], dev["window_s"] = trace["busy_s"], trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
        if cell["kind"] == "train":
            rf = {"kind": "train", "train": facts, "trace": trace}
        else:
            rf = serve_reader_facts(facts["windows"][0])
        line["metrics"] = read_layers(defs, cell, rf)
        if args.describe_trace and trace.get("trace_lines"):
            out = os.path.join(ROOT, "chiprun_out",
                               f"trace_lines.{cell['name']}.json")
            with open(out, "w") as f:
                json.dump(trace["trace_lines"], f, indent=1)
    else:
        for m in wanted(defs["end_to_end"], cell["name"]):
            line["metrics"][m["name"]] = {
                "value": e2e[m["name"]], "unit": m["unit"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
