"""Device time of the hyper-connections in one decode step: the time covered
by the operations of the program's `hc.mix` scope (models/transformer.py:
_hc_mix and _residual — computing H_pre / H_post / H_res from the streams
and applying them, both sublayers of every layer) inside the whole
`jit_paged_decode` executions that lie in a recorded `engine.decode` span,
per execution. The scope is found as `moe_device_ms` finds its own: in the
`tf_op` stat of the events' metadata. A program without the scope (every
other cell, the parent) gives None."""
from benchmark import common, span_reduce
from benchmark.trace_reduce import DEVICE_PREFIX, OPS_LINE, union_length

SCOPE = "hc.mix"


def scoped_ns_per_run(tr, scope: str):
    """(ns the scope's operations cover, summed over the whole decode
    executions inside `engine.decode` spans; how many executions), or None."""
    runs = tr.executions("jit_paged_decode", inside="engine.decode")
    path = span_reduce.newest_xplane()
    if not runs or path is None:
        return None
    names = common._load_module("layer_metrics", "moe_device_ms").op_names(path)
    from jax.profiler import ProfileData

    device = next((p for p in ProfileData.from_file(path).planes
                   if p.name.startswith(DEVICE_PREFIX)), None)
    if device is None:
        return None
    spans = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
             for line in device.lines if line.name == OPS_LINE
             for ev in line.events if scope in names.get(ev.name, "")]
    total = 0.0
    for r in runs:
        inside = [(s, e) for s, e in spans if r.start <= s and e <= r.end]
        total += union_length(inside)[0] if inside else 0.0
    return (total, len(runs)) if total else None


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    got = scoped_ns_per_run(tr, SCOPE)
    return None if got is None else got[0] / got[1] / 1e6
