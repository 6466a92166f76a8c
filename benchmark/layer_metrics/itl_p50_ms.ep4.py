"""`itl_p50_ms` in the saturated long-generation cell of one chip's share of an
expert-parallel deployment (`deepseek-v2-l5-ep4.long-gen-saturated`), where
it is read beside completed tokens per second: the cell is above its knee,
so its tails and steps are per-layer numbers, never end-to-end ones. Same
reader, same facts. A twin because the accepted metric's list of cells is
pinned by the benchmark's own tests and only a `benchmark` PR may edit it."""
from benchmark import common

read = common.load_reader("itl_p50_ms")
