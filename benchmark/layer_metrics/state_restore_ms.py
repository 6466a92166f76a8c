"""Host time an admission spends moving recurrent state: the summed
duration of the `engine.state_restore` (snapshot -> the slot's row, before
the prefill) and `engine.state_snapshot` (the slot's row -> a snapshot,
behind a prefill chunk that ended on a block boundary) spans inside the
recorded `batcher.admit` spans, per admission, in ms. Each is the dispatch
of one `jit_copy_state` (13.7 MB a row at the published widths); the
snapshots decode leaves at block boundaries lie in `engine.decode` spans
and are not counted here. A trace without either span (every other cell,
the parent) gives None."""
from benchmark import span_reduce

MOVES = ("engine.state_restore", "engine.state_snapshot")


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    admits = tr.named("batcher.admit")
    moves = [s for name in MOVES for s in tr.named(name)]
    if not admits or not moves:
        return None
    inside = sum(m.dur for m in moves if any(a.holds(m) for a in admits))
    return inside / len(admits) / 1e6
