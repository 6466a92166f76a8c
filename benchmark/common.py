"""What every part of the benchmark shares: where its files are, how a
configuration's block, a cell and a reader are found by name, the
percentile, and the table of peaks.

Nothing here opens a JAX backend: the driver process imports it."""

from __future__ import annotations

import importlib.util
import json
import math
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_workload(name: str) -> dict:
    cell = load_json("workloads", f"{name}.json")
    cell["name"] = name
    return cell


def load_config(name: str) -> dict:
    return load_json("configs", f"{name}.json")


def peaks_for(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json: "
            "add it with its source, there is no default"
        )
    return table[device_kind]


def jax_seed(seed: int) -> int:
    """--seed may pass 2**31; jax.random.PRNGKey takes a signed 32-bit
    value when x64 is off. Fold the high bits in instead of dropping them."""
    seed = int(seed)
    return (seed ^ (seed >> 31) * 0x9E3779B1) & 0x7FFFFFFF


# ------------------------------------------------------------- arithmetic


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest order statistics — numpy's default, written out so the
    yardstick depends on nothing."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def hist_mean_ms(facts: dict, name: str):
    """Mean of a replica histogram over the window, in ms: sum delta over
    count delta of the totals read at both ends. None without samples."""
    a = facts["after"]["hist"].get(name)
    b = facts["before"]["hist"].get(name)
    if not a or not b or a["count"] == b["count"]:
        return None
    return (a["sum"] - b["sum"]) / (a["count"] - b["count"]) * 1e3


def _load_module(folder: str, name: str):
    """benchmark/<folder>/<name>.py as a module of its own, found by its
    file's name: what lets a later PR add one without editing a file."""
    path = os.path.join(BENCH_DIR, folder, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """benchmark/layer_metrics/<metric>.py, by the metric's name."""
    return _load_module("layer_metrics", metric).read


# what a configuration file says about itself, its cut and its deployment,
# whatever its block: read by the runners (`run`, `engine`) or by nobody
BOOKKEEPING = ("name", "source", "architectures", "block", "run", "engine",
               "reduced", "assumed", "departures", "published", "stands_for",
               "max_position_embeddings")
BLOCK_NAMES = ("transformer_kwargs", "required_train_flops_per_token",
               "ref_logits", "ref_loss")


def load_block(conf: dict):
    """benchmark/blocks/<name>.py, by the configuration file's `block` key
    (`llama` without one): the module that maps the file onto the program's
    TransformerConfig, counts its required FLOPs and holds its plain
    float32 reference. A file that lacks one of BLOCK_NAMES fails here,
    by that name."""
    name = conf.get("block", "llama")
    mod = _load_module("blocks", name)
    missing = [n for n in BLOCK_NAMES if not callable(getattr(mod, n, None))]
    if missing:
        raise AttributeError(
            f"benchmark/blocks/{name}.py lacks {', '.join(missing)}")
    return mod
