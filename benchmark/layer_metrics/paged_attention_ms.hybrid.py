"""`paged_attention_ms` in the cells of a hybrid cache (linear layers beside
full-attention ones): time of the `paged_attention` kernel in one decode
step — the summed duration of its events on "XLA Ops" inside the
`jit_paged_decode` executions that lie in a recorded `engine.decode` span,
per execution (one event per FULL layer). The accepted reader's method but
for which executions count (`gdn_step_ms.runs_inside` says why), and a twin
because the accepted metric's list of cells is pinned by the benchmark's own
test (test_olmoe_block.py) and only a `benchmark` PR may edit it."""
from benchmark import common, span_reduce


def decode_runs(tr):
    return common._load_module("layer_metrics", "gdn_step_ms").runs_inside(
        tr, "jit_paged_decode", "engine.decode")


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = decode_runs(tr)
    events = tr.kernel_events("paged_attention", runs)
    if not events:
        return None
    return sum(k.dur for k in events) / len(runs) / 1e6
