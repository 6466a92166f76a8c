"""`decode_host_ms` in the cells of a hybrid cache (linear layers beside full-attention
ones): over the `engine.decode` spans that
hold a `jit_paged_decode` execution, the span's duration less the time the
device is busy inside it. The accepted reader's method but for which executions count
(`gdn_step_ms.runs_inside` says why: a hybrid decode step's operations
follow its live slots), and a twin because the accepted metric's list of
cells is pinned by the benchmark's own test (test_olmoe_block.py) and only a
`benchmark` PR may edit it."""
from benchmark import common, span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = common._load_module("layer_metrics", "gdn_step_ms").runs_inside(
        tr, "jit_paged_decode", "engine.decode")
    return span_reduce.mean_ms(r.stats["span"].dur - tr.busy_inside(
        r.stats["span"].start, r.stats["span"].end) for r in runs)
