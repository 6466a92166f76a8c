"""95th percentile of (actual send time - due time) in the benchmark's own
client: a starved generator must not be read as a fast server."""
from benchmark.common import percentile


def read(facts):
    if facts["kind"] != "serve" or not facts["client"]["late_ms"]:
        return None
    return percentile(facts["client"]["late_ms"], 95)
