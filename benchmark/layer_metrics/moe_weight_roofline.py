"""Share of the HBM roofline the expert layer reaches in the decode steps,
in percent; bound by BYTES. The least time the chip could take to read one
step's expert weights ONCE in the compute dtype — layers x experts x 3
matrices (gate, up, down) x hidden_size x intermediate_size x 2 bytes
(bfloat16), from the configuration's file, over peaks.json's
hbm_bytes_per_s — over `moe_device_ms`. A floor: a step that routes no
token to an expert need not read it (at 8 of 64 experts a token, a step of
8 or more live slots touches nearly all), and the router, the activations
and the sorted rows are left out."""
from benchmark import common, span_reduce

COMPUTE_BYTES = 2  # the program computes in bfloat16 (TransformerConfig.dtype)


def expert_weight_bytes(conf: dict) -> float:
    return (float(conf["num_hidden_layers"]) * conf["num_experts"] * 3
            * conf["hidden_size"] * conf["intermediate_size"] * COMPUTE_BYTES)


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    got = common._load_module("layer_metrics", "moe_device_ms").moe_ns_per_run(
        facts)
    _, conf = span_reduce.shapes(tr.cell)
    if got is None or "num_experts" not in conf:
        return None
    peak = common.peaks_for(facts["after"]["device_kind"])["hbm_bytes_per_s"]
    least_s = expert_weight_bytes(conf) / peak
    return 100.0 * least_s / (got[0] / got[1] / 1e9)
