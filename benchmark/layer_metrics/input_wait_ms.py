"""Median host-clock time the train loop waited in next(it) for the next
device batch (Dataset.iter_device_batches), over the steps of the window."""
from benchmark.common import median


def read(facts):
    if facts["kind"] != "train":
        return None
    return median(facts["train"]["input_wait_ms"])
