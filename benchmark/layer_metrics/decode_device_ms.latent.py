"""`decode_device_ms` in the cells of a latent (MLA) KV pool: same reader, same facts, the
same program names (jit_paged_decode / jit_paged_prefill). A twin because
the accepted metric's list of cells is pinned by the benchmark's own test
(test_olmoe_block.py) and only a `benchmark` PR may edit it."""
from benchmark import common

read = common.load_reader("decode_device_ms")
