"""`ttft_p95_ms` in the saturated chat cell, where it is read beside completed
tokens per second (the cell is above its knee: its tails and steps are
per-layer numbers there, never end-to-end ones). Same reader, same facts."""
from benchmark import common

read = common.load_reader("ttft_p95_ms")
