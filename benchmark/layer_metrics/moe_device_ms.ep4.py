"""`moe_device_ms` in the saturated long-generation cell of one chip's share of an
expert-parallel deployment (`deepseek-v2-l5-ep4.long-gen-saturated`), where
it is read beside completed tokens per second: the cell is above its knee,
so its tails and steps are per-layer numbers, never end-to-end ones.
The scopes it reads (`moe.route` with `moe.groups` inside it, `moe.experts`
with `moe.shared`) and libtpu's `ragged-dot` kernels cover the router over
all 160 experts, the grouped matmuls over the held experts' pairs and the
shared experts.
The accepted reader's quantity, with executions joined to their spans by
overlap (benchmark/span_join.py says why). A file of its own because the
accepted metric's list of cells is pinned by the benchmark's own tests and
only a `benchmark` PR may edit it."""
from benchmark import common, span_join


def moe_ns_per_run(facts):
    """(ns the expert layer's operations cover, summed over the whole
    decode executions that belong to an `engine.decode` span; how many
    executions), or None: the accepted reader's count over this join."""
    tr = span_join.trace_of(facts)
    if tr is None:
        return None
    if "moe_ns_per_run" not in vars(tr):  # moe_weight_roofline.ep4 asks again
        tr.moe_ns_per_run = common._load_module(
            "layer_metrics", "moe_device_ms")._moe_ns_per_run(tr)
    return tr.moe_ns_per_run


def read(facts):
    got = moe_ns_per_run(facts)
    return None if got is None else got[0] / got[1] / 1e6
