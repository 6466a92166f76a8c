"""Share of the bf16 peak the flash attention kernels reach in the training
step, in percent; bound by FLOPs. Over the kernel events inside the whole
executions of the step program on one device: the causal matmuls each
executed pass requires on this device's shard of the batch (forward 2,
backward 4 — span_reduce.FLASH_MATMULS; shapes from the cell's and the
configuration's files) over peaks.json's bf16_flops_per_s, summed, over the
kernels' summed time."""
from benchmark import common, span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    events = tr.kernel_events("flash_attention", tr.executions("jit_step_fn"))
    if not events:
        return None
    cell, conf = span_reduce.shapes(tr.cell)
    peak = common.peaks_for(facts["train"]["device_kind"])["bf16_flops_per_s"]
    flops = span_reduce.flash_matmul_flops(conf, cell) * sum(
        span_reduce.FLASH_MATMULS[k.name] for k in events)
    return 100.0 * (flops / peak) / (sum(k.dur for k in events) / 1e9)
