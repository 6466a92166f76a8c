"""Share of its roofline the `mla_paged_attention` kernel reaches in the
decode steps, in percent. Over the whole `jit_paged_decode` executions
inside a recorded `engine.decode` span: the least time the chip could take
for what the step attends (`kv_tokens` of the span, summed over the batch),
over the kernel's summed time. The count reads the WORK, not the
implementation, from the configuration's file:

  bytes  kv_tokens x layers x (kv_lora_rank + qk_rope_head_dim) x bytes of
         the pool's dtype — a token's cached row read once for all heads
         (1,152 B a layer in bfloat16; the pool pads the row to 640 values,
         which the count leaves out)
  FLOPs  kv_tokens x layers x 2 x heads x ((kv_lora_rank + qk_rope_head_dim)
         for the scores + kv_lora_rank for the values)

least time = max(bytes / hbm_bytes_per_s, FLOPs / bf16_flops_per_s) of
peaks.json: 60 FLOP a byte, so on a v5e (240 FLOP a byte) BYTES bind."""
from benchmark import common, span_reduce


def latent_work(conf: dict, kv_tokens: float) -> tuple:
    """(bytes, FLOPs) of one decode step that attends `kv_tokens`."""
    row = conf["kv_lora_rank"] + conf["qk_rope_head_dim"]
    rows = float(kv_tokens) * conf["num_hidden_layers"]
    width = span_reduce.KV_BYTES[conf["engine"]["kv_cache_dtype"]]
    return (rows * row * width, rows * 2 * conf["num_attention_heads"]
            * (row + conf["kv_lora_rank"]))


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    mla = common._load_module("layer_metrics", "mla_attention_ms")
    runs = mla.decode_runs(tr)
    kernel_ns = mla.kernel_ns(tr, runs)
    _, conf = span_reduce.shapes(tr.cell)
    if not kernel_ns or "kv_lora_rank" not in conf:
        return None
    peaks = common.peaks_for(facts["after"]["device_kind"])
    least_s = 0.0
    for r in runs:
        nbytes, flops = latent_work(conf, r.stats["span"].stats["kv_tokens"])
        least_s += max(nbytes / peaks["hbm_bytes_per_s"],
                       flops / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
