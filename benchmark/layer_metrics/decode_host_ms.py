"""Mean host share of one decode step: over the `engine.decode` spans that
hold a whole `jit_paged_decode` execution, the span's duration less the time
the device is busy inside it (program span against device trace, one clock).
decode_device_ms + decode_host_ms is the mean span, unless another program
(copy-on-write's jit_copy_blocks, the key split) ran inside it."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    spans = [r.stats["span"] for r in
             tr.executions("jit_paged_decode", inside="engine.decode")]
    return span_reduce.mean_ms(
        s.dur - tr.busy_inside(s.start, s.end) for s in spans)
