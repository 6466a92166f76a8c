"""Device time of the linear layers' own work in one prefill: the time
covered by the operations of the program's `gdn.scan` (the chunked delta
rule) and `gdn.conv` (the causal depthwise convolution, the head split and
the L2 norms) scopes, or by a kernel named `gdn_chunk_scan`, inside the
whole `jit_paged_prefill` executions that lie in a recorded `engine.prefill`
span, per execution (all six linear layers). The projections before and
the gated norm and output projection after are the layer's matmuls, not
this. A program without the scopes (every other cell, the parent) gives
None."""
from benchmark import common, span_reduce

SCOPES = ("gdn.scan", "gdn.conv")
KERNELS = ("gdn_chunk_scan",)


def runs_of(tr):
    step = common._load_module("layer_metrics", "gdn_step_ms")
    return step.scoped_runs(
        tr, "jit_paged_prefill", "engine.prefill", SCOPES, KERNELS)


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    got = runs_of(tr)
    total = sum(ns for _, ns in got)
    return total / len(got) / 1e6 if total else None
