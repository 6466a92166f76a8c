"""`mla_attention_roofline` in the saturated long-generation cell of one
chip's share of an expert-parallel deployment
(`deepseek-v2-l5-ep4.long-gen-saturated`): the share of its roofline the
`mla_paged_attention` kernel reaches in the decode steps, in percent, at
128 heads. The count is the accepted reader's (`latent_work`), from the
configuration's file:

  bytes  kv_tokens x 5 layers x (512 + 64) x 2 B = 1,152 B a token and
         layer, a cached row read once for all heads
  FLOPs  kv_tokens x 5 layers x 2 x 128 heads x ((512 + 64) + 512) = 278,528
         a token and layer

least time = max(bytes / 819 GB/s, FLOPs / 197 TFLOP/s): 242 FLOP a byte
against the v5e's 240, so here FLOPs bind, by a hair (1.407 ns a token and
layer by bytes, 1.414 by FLOPs) — at 32 heads (60 FLOP a byte) bytes did.
The accepted reader's quantity and count, with executions joined to their
spans by overlap (benchmark/span_join.py says why); a file of its own
because the accepted metric's list of cells is pinned by the benchmark's
own tests."""
from benchmark import common, span_join, span_reduce


def read(facts):
    tr = span_join.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    mla = common._load_module("layer_metrics", "mla_attention_ms")
    runs = mla.decode_runs(tr)
    kernel_ns = mla.kernel_ns(tr, runs)
    _, conf = span_reduce.shapes(tr.cell)
    if not kernel_ns or "kv_lora_rank" not in conf:
        return None
    latent_work = common._load_module(
        "layer_metrics", "mla_attention_roofline").latent_work
    peaks = common.peaks_for(facts["after"]["device_kind"])
    least_s = 0.0
    for r in runs:
        nbytes, flops = latent_work(conf, r.stats["span"].stats["kv_tokens"])
        least_s += max(nbytes / peaks["hbm_bytes_per_s"],
                       flops / peaks["bf16_flops_per_s"])
    return 100.0 * least_s / (kernel_ns / 1e9)
