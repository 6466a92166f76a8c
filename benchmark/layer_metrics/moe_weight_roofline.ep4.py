"""Share of the HBM roofline the expert layer reaches in the decode steps of
a chip that holds a SHARE of its experts, in percent; bound by BYTES. Over
the whole `jit_paged_decode` executions that belong to a recorded
`engine.decode` span (joined by overlap: benchmark/span_join.py) that
carries `moe_touched`: the least time the chip could take to read
the held experts the step TOUCHED once — sum(`moe_touched`) x 3 matrices
(gate, up, down) x hidden_size x moe_intermediate_size x 2 bytes
(bfloat16), sizes from the configuration's file (3 x 5120 x 1536 x 2 B =
47.2 MB an expert), over peaks.json's hbm_bytes_per_s — over the time
`moe_device_ms.ep4` reads in the same executions. `moe_touched` is the
program's own count of (layer, held expert) groups with at least one pair
of a live slot: the work the step needs whatever implements it, not every
expert the layers hold (the all-experts count of `moe_weight_roofline`
passes 100 % where a step touches a part of them). A floor: the router, the
shared experts' weights, the activations and the sorted rows are left out.
A trace without the count or without the scopes gives None."""
from benchmark import common, span_join, span_reduce

COMPUTE_BYTES = 2  # the program computes in bfloat16 (TransformerConfig.dtype)


def expert_bytes(conf: dict) -> float:
    """One routed expert's three matrices."""
    return (3.0 * conf["hidden_size"] * conf["moe_intermediate_size"]
            * COMPUTE_BYTES)


def read(facts):
    tr = span_join.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    got = common._load_module(
        "layer_metrics", "moe_device_ms.ep4").moe_ns_per_run(facts)
    _, conf = span_reduce.shapes(tr.cell)
    if got is None or "moe_intermediate_size" not in conf:
        return None
    steps = [r.stats["span"].stats for r in
             tr.executions("jit_paged_decode", inside="engine.decode")]
    if not steps or any("moe_touched" not in s for s in steps):
        return None
    peak = common.peaks_for(facts["after"]["device_kind"])["hbm_bytes_per_s"]
    touched = sum(float(s["moe_touched"]) for s in steps)
    return 100.0 * (touched * expert_bytes(conf) / peak) / (got[0] / 1e9)
