"""`decode_host_ms` in the saturated long-generation cell of one chip's share of an
expert-parallel deployment (`deepseek-v2-l5-ep4.long-gen-saturated`), where
it is read beside completed tokens per second: the cell is above its knee,
so its tails and steps are per-layer numbers, never end-to-end ones.
Mean host share of one decode step: over the `engine.decode` spans to which
a whole `jit_paged_decode` execution belongs, the span's duration less the
time the device is busy from the earlier start to the later end of the two
(the span's own extent, were the two clocks one).
The accepted reader's quantity, with executions joined to their spans by
overlap (benchmark/span_join.py says why). A file of its own because the
accepted metric's list of cells is pinned by the benchmark's own tests and
only a `benchmark` PR may edit it."""
from benchmark import span_join, span_reduce


def read(facts):
    tr = span_join.trace_of(facts)
    if tr is None:
        return None
    runs = tr.executions("jit_paged_decode", inside="engine.decode")
    return span_reduce.mean_ms(
        r.stats["span"].dur - tr.busy_around(r.stats["span"], r) for r in runs)
