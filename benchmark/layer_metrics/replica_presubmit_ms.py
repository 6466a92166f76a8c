"""From Replica.handle_request to ContinuousBatcher.submit — the
deployment's own code before the batcher (the request's fields, the SSE
wrapper): the median `replica_us` of the traced window's
`batcher.first_token` spans, in ms. None on a trace without the attribute."""
from benchmark import span_reduce
from benchmark.common import median


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    took = [s.stats["replica_us"] / 1e3
            for s in tr.named("batcher.first_token")
            if "replica_us" in s.stats]
    return median(took) if took else None
