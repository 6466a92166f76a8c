"""What the running streams wait between two decode steps: the mean, over
the `batcher.iteration` spans that stepped the engine (they carry `slots`),
of the summed `batcher.admit` spans inside each — admissions run their
prefill on the loop's thread while every other stream stands still."""
from benchmark import span_reduce


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    admits = tr.named("batcher.admit")
    return span_reduce.mean_ms(
        sum(a.dur for a in admits if it.holds(a))
        for it in tr.named("batcher.iteration") if "slots" in it.stats)
