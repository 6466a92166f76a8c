"""Mean submit -> engine-admission wait inside the replica over the run:
serve_queue_wait_s, sum delta / count delta."""
from benchmark.common import hist_mean_ms


def read(facts):
    if facts["kind"] != "serve":
        return None
    return hist_mean_ms(facts, "queue_wait")
