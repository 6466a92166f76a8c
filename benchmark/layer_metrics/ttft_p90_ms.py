"""90th percentile of first-token time from the due time, at the client.
Over the hundred-odd requests of a window it swings by 5 % between runs of
one trace (PERF.md, PR 23), so it carries no bound: it stands beside the
end-to-end median, ttft_p50_ms."""
from benchmark.common import percentile


def read(facts):
    if facts["kind"] != "serve" or not facts["client"]["ttft_ms"]:
        return None
    return percentile(facts["client"]["ttft_ms"], 90)
