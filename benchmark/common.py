"""What every part of the benchmark shares: where its files are, how a
published configuration becomes the program's TransformerConfig, the
required-FLOPs count, the percentile, and the table of peaks.

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


def transformer_kwargs(conf: dict) -> dict:
    """The published keys, renamed to the program's TransformerConfig
    fields. Both families here are the llama-style block the program runs
    (RMSNorm, RoPE, GQA, gated SiLU MLP, no bias, untied embeddings);
    anything else in the file is refused, not ignored."""
    if conf.get("hidden_act") != "silu" or conf.get("bias") \
            or conf.get("tie_word_embeddings") or conf.get("sliding_window"):
        raise ValueError(f"{conf.get('name')}: not the block this harness maps")
    return dict(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], rope_theta=float(conf["rope_theta"]),
        max_seq_len=conf["run"]["max_seq_len"], tie_embeddings=False,
    )


def jax_seed(seed: int) -> int:
    """--seed may pass 2**31; jax.random.PRNGKey takes a signed 32-bit
    value when x64 is off. Fold the high bits in instead of dropping them."""
    seed = int(seed)
    return (seed ^ (seed >> 31) * 0x9E3779B1) & 0x7FFFFFFF


# ------------------------------------------------------------- arithmetic


def matmul_params(conf: dict) -> dict:
    """Parameters that sit in matrix multiplications, per layer and in the
    output head. The embedding table is a lookup and the norm scales are
    elementwise: neither is counted."""
    e, h, kv, d = (conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"])
    attn = e * h * d + 2 * e * kv * d + h * d * e
    mlp = 3 * e * conf["intermediate_size"]
    return {"layer": attn + mlp, "head": e * conf["vocab_size"],
            "layers": conf["num_hidden_layers"]}


def required_train_flops_per_token(conf: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE for one token of a
    `seq_len` sequence: 2 per multiply-add, backward = 2 x forward, so
    3 x forward. Attention is counted causal: token i attends to i+1
    keys, (seq_len+1)/2 on average, for QK^T and for PV. Recomputation
    under remat is work the implementation chose, not required work, and is
    not counted; neither are the embedding lookup, norms, rope, softmax."""
    p = matmul_params(conf)
    matmul = 2.0 * (p["layers"] * p["layer"] + p["head"])
    attn = (p["layers"] * 2 * 2.0 * conf["num_attention_heads"]
            * conf["head_dim"] * (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


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


def load_reader(metric: str):
    """benchmark/layer_metrics/<metric>.py, by the metric's name."""
    path = os.path.join(BENCH_DIR, "layer_metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
