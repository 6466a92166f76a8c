"""`device_idle_share.serve` in the long-session cell of the hybrid cache,
where it is read beside completed tokens per second (the cell does not
report `itl_p95_ms`: see `itl_p95_ms.hybrid`). Same reader, same facts."""
from benchmark import common

read = common.load_reader("device_idle_share.serve")
