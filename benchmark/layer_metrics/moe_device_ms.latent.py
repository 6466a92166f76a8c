"""`moe_device_ms` in the document-QA cell of the latent (MLA) pool, where it is read
beside completed tokens per second: `itl_p95_ms` spread past half its bound
there in the driver's two sets of six (PERF.md section 6, PR 33), so the
cell does not report it and no metric of the cell may move it. Same reader,
same facts."""
from benchmark import common

read = common.load_reader("moe_device_ms")
