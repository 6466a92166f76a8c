"""95th percentile of the gaps between consecutive output tokens of one
stream, at the client, in the long-session cell of the hybrid cache: what
`itl_p95_ms` is end to end in the chat cells, a per-layer number here. A
gap there is a decode step, or a decode step and an admission behind 4k-32k
cached tokens (a state restore, a prefill of a few hundred tokens and its
snapshots), so the tail follows how the window's admissions fall between
the steps — as in the latent pool's document cell, where it spread past
half its 2 % bound (PERF.md section 6, PR 33 and PR 35)."""
from benchmark.common import percentile


def read(facts):
    if facts["kind"] != "serve" or not facts["client"]["itl_ms"]:
        return None
    return percentile(facts["client"]["itl_ms"], 95)
