"""Share of its roofline the linear layers' scan (`gdn.scan` + `gdn.conv`)
reaches in the prefills, in percent. Over the whole `jit_paged_prefill`
executions inside a recorded `engine.prefill` span: the least time the chip
could take for the tokens the prefill consumed (`tokens` of the span), over
the time `gdn_scan_ms` reads. The count reads the WORK, from the
configuration's file:

  bytes  tokens x linear layers x (q + k + v + gate widths = 2,880 + 2,880
         + 5,760 + 5,760 at the published widths) x 2 B: the projections'
         outputs read once (the state, 2.2 MB a layer, is left out)
  FLOPs  tokens x linear layers x heads x 3 x 2 x key dim x value dim: the
         recurrence's three passes over the state (S^T k, the rank-one
         update, S^T q) — not the chunked form's extra matmuls

least time = max(bytes / hbm_bytes_per_s, FLOPs / bf16_flops_per_s) of
peaks.json: 96 FLOP a byte against a v5e's 240, so BYTES bind. The padded
tokens of a bucket and the chunked form's own matmuls are work the program
does and the count leaves out: no implementation reads over 100 %."""
from benchmark import common, span_reduce


def scan_work(conf: dict, tokens: float) -> tuple:
    """(bytes, FLOPs) of the linear layers' scan over `tokens` tokens."""
    heads = conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    rows = float(tokens) * conf["layer_types"].count("linear_attention")
    return (rows * heads * (2 * dk + 2 * dv) * 2,
            rows * heads * 3 * 2 * dk * dv)


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    got = [(r, ns) for r, ns in common._load_module(
        "layer_metrics", "gdn_scan_ms").runs_of(tr)
        if "tokens" in r.stats["span"].stats]
    total_ns = sum(ns for _, ns in got)
    _, conf = span_reduce.shapes(tr.cell)
    if not total_ns or "linear_key_head_dim" not in conf:
        return None
    peaks = common.peaks_for(facts["after"]["device_kind"])
    least_s = 0.0
    for r, _ in got:
        nbytes, flops = scan_work(conf, r.stats["span"].stats["tokens"])
        least_s += max(nbytes / peaks["hbm_bytes_per_s"],
                       flops / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (total_ns / 1e9)
