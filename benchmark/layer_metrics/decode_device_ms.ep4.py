"""`decode_device_ms` in the saturated long-generation cell of one chip's share of an
expert-parallel deployment (`deepseek-v2-l5-ep4.long-gen-saturated`), where
it is read beside completed tokens per second: the cell is above its knee,
so its tails and steps are per-layer numbers, never end-to-end ones.
Mean duration of the whole `jit_paged_decode` executions that belong to a
recorded `engine.decode` span (device trace).
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
    return span_reduce.mean_ms(r.dur for r in runs)
