"""Device time of the gated-delta-rule state step in one decode step: the
time covered by the operations of the program's `gdn.step` scope
(models/transformer.py: paged_decode's `recur`; ops/gated_delta.py:
state_step) or by a kernel named `gdn_state_step`, inside the whole
`jit_paged_decode` executions that lie in a recorded `engine.decode` span,
per execution (all six linear layers). The scope is found as
`moe_device_ms` finds its own: in the `tf_op` stat of the events' metadata.
A program without the scope or the kernel (every other cell, the parent)
gives None.

`runs_inside` and `scoped_runs` are what the hybrid cell's readers share.
The decode program of a hybrid cache loops over the LIVE rows of the state
pool, so the number of operations in an execution follows the live slots,
and span_reduce's rule for a whole execution (as many operations as the
program's fullest) keeps only the fullest steps: 2 of 97 in this PR's first
trace. An execution is taken here when it lies inside a recorded span —
the profiler records a span only if it began and ended inside the session,
so an execution the window's edge cut has none."""
from benchmark import common, span_reduce
from benchmark.trace_reduce import DEVICE_PREFIX, OPS_LINE, union_length

SCOPES = ("gdn.step",)
KERNELS = ("gdn_state_step",)


def runs_inside(tr, program: str, inside: str) -> list:
    """the executions of `program` that lie inside a recorded `inside`
    span, each with its span as `.stats["span"]`."""
    out = []
    for r in tr.runs:
        if r.name != program:
            continue
        sp = next((s for s in tr.named(inside) if s.holds(r)), None)
        if sp is not None:
            r.stats["span"] = sp
            out.append(r)
    return out


def scoped_runs(tr, program: str, inside: str, scopes, kernels):
    """[(execution, ns covered by the operations under one of `scopes` or
    named after one of `kernels`)] over the executions of `program` inside
    a recorded `inside` span; [] where the trace holds none."""
    runs = runs_inside(tr, program, inside)
    path = span_reduce.newest_xplane()
    if not runs or path is None:
        return []
    key = ("scoped", scopes, kernels)
    cache = tr.__dict__.setdefault("gdn_events", {})
    if key not in cache:
        names = common._load_module(
            "layer_metrics", "moe_device_ms").op_names(path)
        from jax.profiler import ProfileData

        device = next((p for p in ProfileData.from_file(path).planes
                       if p.name.startswith(DEVICE_PREFIX)), None)
        cache[key] = [
            (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for line in (device.lines if device is not None else ())
            if line.name == OPS_LINE for ev in line.events
            if any(s in names.get(ev.name, "") for s in scopes)
            or any(k in ev.name.split(" = ", 1)[0] for k in kernels)]
    out = []
    for r in runs:
        inside_run = [(s, e) for s, e in cache[key]
                      if r.start <= s and e <= r.end]
        out.append((r, union_length(inside_run)[0] if inside_run else 0.0))
    return out


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    got = scoped_runs(tr, "jit_paged_decode", "engine.decode", SCOPES, KERNELS)
    total = sum(ns for _, ns in got)
    return total / len(got) / 1e6 if total else None
