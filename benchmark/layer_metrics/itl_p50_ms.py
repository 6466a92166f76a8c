"""Median gap between consecutive output tokens of one stream, at the
client: the steadier statistic beside itl_p95_ms."""
from benchmark.common import median


def read(facts):
    if facts["kind"] != "serve" or not facts["client"]["itl_ms"]:
        return None
    return median(facts["client"]["itl_ms"])
