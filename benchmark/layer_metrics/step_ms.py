"""Median host-clock time of one compiled step + its loss reaching the
host, over the steps of the window (benchmark/train_runner.py)."""
from benchmark.common import median


def read(facts):
    if facts["kind"] != "train":
        return None
    return median(facts["train"]["step_ms"])
