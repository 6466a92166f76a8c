"""Mean device time of one decode step: the duration of the whole
`jit_paged_decode` executions on the device's "XLA Modules" line that lie
inside a recorded `engine.decode` span (device trace)."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = tr.executions("jit_paged_decode", inside="engine.decode")
    return span_reduce.mean_ms(r.dur for r in runs)
