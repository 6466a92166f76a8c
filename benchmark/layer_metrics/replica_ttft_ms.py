"""Mean submit -> first token inside the replica over the run, every
request of the window: serve_ttft_s (observed where GenerationStream pushes
a request's first token), sum delta / count delta. Queue wait, the
admission with its prefill and, where the prompt is chunked, the steps
between the chunks: all of a first token's time the batcher can see."""
from benchmark.common import hist_mean_ms


def read(facts):
    if facts["kind"] != "serve":
        return None
    return hist_mean_ms(facts, "ttft")
