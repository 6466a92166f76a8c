"""Mean engine decode dispatch over the run:
serve_engine_step_s{phase=decode}, sum delta / count delta."""
from benchmark.common import hist_mean_ms


def read(facts):
    if facts["kind"] != "serve":
        return None
    return hist_mean_ms(facts, "decode_step")
