"""`paged_attention_roofline` with the count of a hybrid cache's KV pool:
only the FULL-attention layers keep keys and values (the accepted reader
counts `num_hidden_layers`, every layer). Share of the HBM roofline the
`paged_attention` kernel reaches in the decode steps, in percent; bound by
BYTES. Over the whole `jit_paged_decode` executions inside a recorded
`engine.decode` span: `kv_tokens` of the span x 2 x full layers x kv heads
x head dim (hidden / heads) x bytes of the pool's dtype, over peaks.json's
hbm_bytes_per_s, summed, over the kernel's summed time. The two zero heads
the pool pads 30 to 32 with are read by the kernel and left out of the
count."""
from benchmark import common, span_reduce


def kv_bytes(conf: dict, kv_tokens: float) -> float:
    width = span_reduce.KV_BYTES[conf["engine"]["kv_cache_dtype"]]
    head_dim = conf["hidden_size"] // conf["num_attention_heads"]
    return (float(kv_tokens) * 2 * conf["layer_types"].count("full_attention")
            * conf["num_key_value_heads"] * head_dim * width)


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    runs = [r for r in common._load_module(
        "layer_metrics", "paged_attention_ms.hybrid").decode_runs(tr)
        if "kv_tokens" in r.stats["span"].stats]
    kernel_ns = sum(k.dur for k in tr.kernel_events("paged_attention", runs))
    _, conf = span_reduce.shapes(tr.cell)
    if not kernel_ns or "layer_types" not in conf:
        return None
    peak = common.peaks_for(facts["after"]["device_kind"])["hbm_bytes_per_s"]
    least_s = sum(kv_bytes(conf, r.stats["span"].stats["kv_tokens"])
                  for r in runs) / peak
    return 100.0 * least_s / (kernel_ns / 1e9)
