"""Share of the HBM roofline the `paged_attention` kernel reaches in the
decode steps, in percent; bound by BYTES. Over the whole `jit_paged_decode`
executions inside a recorded `engine.decode` span: the least time the chip
could take to read the K and V the step attends to (`kv_tokens` of the span
x 2 x layers x kv heads x head_dim x bytes of the pool's dtype, from the
configuration's file, over peaks.json's hbm_bytes_per_s), summed, over the
kernel's summed time."""
from benchmark import common, span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    runs = [r for r in tr.executions("jit_paged_decode", inside="engine.decode")
            if "kv_tokens" in r.stats["span"].stats]
    kernel_ns = sum(k.dur for k in tr.kernel_events("paged_attention", runs))
    if not kernel_ns:
        return None
    _, conf = span_reduce.shapes(tr.cell)
    peak = common.peaks_for(facts["after"]["device_kind"])["hbm_bytes_per_s"]
    least_s = sum(span_reduce.paged_attention_bytes(
        conf, r.stats["span"].stats["kv_tokens"]) for r in runs) / peak
    return 100.0 * least_s / (kernel_ns / 1e9)
