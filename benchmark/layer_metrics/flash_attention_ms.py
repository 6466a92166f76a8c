"""Time of the flash attention kernels in one training step, on one device:
the summed duration of every `flash_attention_*` event on "XLA Ops" (forward,
backward dq and dkv, and a forward that remat runs again: it ran) inside the
whole executions of the step program, per execution."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = tr.executions("jit_step_fn")
    events = tr.kernel_events("flash_attention", runs)
    if not events:
        return None
    return sum(k.dur for k in events) / len(runs) / 1e6
