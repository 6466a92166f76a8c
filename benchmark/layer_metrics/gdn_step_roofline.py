"""Share of the HBM roofline the gated-delta-rule state step reaches in the
decode steps, in percent; bound by BYTES. Over the whole `jit_paged_decode`
executions inside a recorded `engine.decode` span: the least time the chip
could take to read and write the recurrent states of the step's LIVE slots
(`slots` of the span x linear layers x 2 x heads x key dim x value dim x 4
bytes of float32, from the configuration's file — 2,211,840 B a slot and
layer at the published widths — over peaks.json's hbm_bytes_per_s), summed,
over the time `gdn_step_ms` reads. The count is of the live slots' states
whatever the program moves: a step that also moved the rows of dead slots,
or the expanded keys and queries the XLA form writes out, reads low, and
no implementation reads over 100 %. q, k, v, the gates and the output (a
few hundred KB a slot) are left out: the count is a floor."""
from benchmark import common, span_reduce

STATE_BYTES = 4  # the configuration's stated float32 state


def state_bytes(conf: dict, slots: float) -> float:
    """Bytes one decode step has to move for `slots` live sequences."""
    layers = conf["layer_types"].count("linear_attention")
    return (float(slots) * layers * 2 * conf["linear_num_value_heads"]
            * conf["linear_key_head_dim"] * conf["linear_value_head_dim"]
            * STATE_BYTES)


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None or tr.cell is None:
        return None
    step = common._load_module("layer_metrics", "gdn_step_ms")
    got = [(r, ns) for r, ns in step.scoped_runs(
        tr, "jit_paged_decode", "engine.decode", step.SCOPES, step.KERNELS)
        if "slots" in r.stats["span"].stats]
    total_ns = sum(ns for _, ns in got)
    _, conf = span_reduce.shapes(tr.cell)
    if not total_ns or "linear_key_head_dim" not in conf:
        return None
    peak = common.peaks_for(facts["after"]["device_kind"])["hbm_bytes_per_s"]
    least_s = sum(state_bytes(conf, r.stats["span"].stats["slots"])
                  for r, _ in got) / peak
    return 100.0 * least_s / (total_ns / 1e9)
