"""Time of the `paged_attention` kernel in one decode step: the summed
duration of its events on "XLA Ops" inside the whole `jit_paged_decode`
executions that lie in a recorded `engine.decode` span, per execution (one
event per layer)."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = tr.executions("jit_paged_decode", inside="engine.decode")
    events = tr.kernel_events("paged_attention", runs)
    if not events:
        return None
    return sum(k.dur for k in events) / len(runs) / 1e6
