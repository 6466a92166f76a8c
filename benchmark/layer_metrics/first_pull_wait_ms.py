"""How long a request's first token lies in its stream's queue before a
pull takes it: the median `waited_us` of the traced window's
`batcher.first_pull` spans, in ms. The pull (`stream_next`) is a second
actor call that can only be issued once the first has answered with the
stream's id, so a token that is ready before the pull arrives waits here.
None on a trace without the span."""
from benchmark import span_reduce
from benchmark.common import median


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    took = [s.stats["waited_us"] / 1e3
            for s in tr.named("batcher.first_pull")
            if "waited_us" in s.stats]
    return median(took) if took else None
