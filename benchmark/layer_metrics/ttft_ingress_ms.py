"""From the proxy having read a request to the replica having the call:
the median, over the traced window's `batcher.first_token` spans, of
`proxy_us` + `ingress_us` (the proxy's asyncio loop -> a pool thread ->
DeploymentHandle.remote -> the actor call into Replica.handle_request), in
ms. Durations the program measured with its own clocks and wrote as span
attributes: nothing here is a position on the trace's axis. A span of a
request that did not come through the proxy has neither attribute and is
left out; a trace without the span (the parent's, a training cell's) gives
None."""
from benchmark import span_reduce
from benchmark.common import median


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    took = [(s.stats["proxy_us"] + s.stats["ingress_us"]) / 1e3
            for s in tr.named("batcher.first_token")
            if "proxy_us" in s.stats and "ingress_us" in s.stats]
    return median(took) if took else None
