"""Mean device time of one prefill: the duration of the whole
`jit_paged_prefill` executions on "XLA Modules" that lie inside a recorded
`engine.prefill` span (device trace). All window widths together."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = tr.executions("jit_paged_prefill", inside="engine.prefill")
    return span_reduce.mean_ms(r.dur for r in runs)
