"""95th percentile of the gaps between consecutive output tokens of one
stream, at the client, in the document-QA cell of the latent (MLA) pool:
what `itl_p95_ms` is end to end in the other serving cells, a per-layer
number here because it spread past half its bound in the driver's two sets
of six (PERF.md section 6, PR 33). A gap there is a decode step or a decode
step and an admission behind 4k-16k cached tokens, so the tail follows how
the window's admissions fall."""
from benchmark.common import percentile


def read(facts):
    if facts["kind"] != "serve" or not facts["client"]["itl_ms"]:
        return None
    return percentile(facts["client"]["itl_ms"], 95)
