"""`mla_attention_ms` in the saturated long-generation cell of one chip's share of an
expert-parallel deployment (`deepseek-v2-l5-ep4.long-gen-saturated`), where
it is read beside completed tokens per second: the cell is above its knee,
so its tails and steps are per-layer numbers, never end-to-end ones.
One `mla_paged_attention` event per layer: 64 slots x 128 heads over each
slot's own cached rows.
The accepted reader's quantity, with executions joined to their spans by
overlap (benchmark/span_join.py says why). A file of its own because the
accepted metric's list of cells is pinned by the benchmark's own tests and
only a `benchmark` PR may edit it."""
from benchmark import common, span_join


def read(facts):
    tr = span_join.trace_of(facts)
    if tr is None:
        return None
    mla = common._load_module("layer_metrics", "mla_attention_ms")
    runs = mla.decode_runs(tr)
    ns = mla.kernel_ns(tr, runs)
    return ns / len(runs) / 1e6 if ns else None
