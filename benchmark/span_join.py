"""Device executions joined to host spans by OVERLAP: what the trace readers
of `deepseek-v2-l5-ep4.long-gen-saturated` use where the accepted ones use
`span_reduce.Trace.executions(.., inside=..)`.

Why another join. `Span.holds` asks that an execution lie wholly inside its
span on one clock, and the two clocks are off by a per-session 0-1.5 ms, the
device early (PERF.md section 7). A launch follows its span's start by
0.5-1.5 ms, so in a session at the far end of that offset an execution reads
as starting BEFORE its span and is dropped: in this cell's traced run of
seed 280819910 all 12 prefills started 0.25-1.5 ms before their
`engine.prefill` span (0 held: the line lacked the metric) and 69 of 100
decode steps before their `engine.decode` span. Spans of one name never
overlap each other and the engine waits for each launch's result inside its
span, so the span that an execution overlaps MOST, by more than half of the
execution, is its own whatever the offset; an execution whose span the
window's edge cut (not recorded) overlaps none and is left out, as before.

`trace_of(facts)` gives the run's trace as `span_reduce.trace_of` does, with
that join; every other attribute is the trace's own, so the accepted
readers' helpers (`decode_runs`, `kernel_ns`, `_moe_ns_per_run`) take it as
they take a `Trace`. Nothing of span_reduce.py is changed or patched."""

from __future__ import annotations

from bisect import bisect_right

from benchmark import span_reduce


def overlap(span, run) -> float:
    return max(0.0, min(span.end, run.end) - max(span.start, run.start))


class Joined:
    """A `span_reduce.Trace` whose `executions(.., inside=..)` joins by
    overlap. What a helper caches on it (`mla_kernel_events`) stays here."""

    def __init__(self, tr):
        self.tr = tr

    def __getattr__(self, name):  # only what this object lacks
        return getattr(self.tr, name)

    def executions(self, program: str, inside: str | None = None) -> list:
        runs = self.tr.executions(program)
        if inside is None:
            return runs
        spans = self.tr.named(inside)
        starts = [s.start for s in spans]
        out = []
        for r in runs:
            best = None
            i = max(bisect_right(starts, r.start) - 1, 0)
            while i < len(spans) and spans[i].start < r.end:
                if best is None or overlap(spans[i], r) > overlap(best, r):
                    best = spans[i]
                i += 1
            if best is not None and 2.0 * overlap(best, r) > r.dur:
                r.stats["span"] = best
                out.append(r)
        return out

    def busy_around(self, span, run) -> float:
        """ns the device is busy from the earlier start to the later end of
        a span and its execution: what `busy_inside(span)` reads on one
        clock."""
        return self.tr.busy_inside(min(span.start, run.start),
                                   max(span.end, run.end))


def trace_of(facts: dict):
    """The readers' entry: None where the run was not traced."""
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    if not hasattr(tr, "joined_by_overlap"):
        tr.joined_by_overlap = Joined(tr)
    return tr.joined_by_overlap
