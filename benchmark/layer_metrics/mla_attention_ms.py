"""Time of the `mla_paged_attention` kernel (ops/paged_attention.py: the
latent pool's walk) in one decode step: the summed duration of its events
on "XLA Ops" inside the whole `jit_paged_decode` executions that lie in a
recorded `engine.decode` span, per execution (one event per layer).

span_reduce finds a kernel by a name that appears IN the instruction's, so
it files `%mla_paged_attention.3` under `paged_attention`; this file reads
the device's operations itself and keeps those named after the latent
kernel. A trace without one (any other cell, the parent) gives None."""
from benchmark import span_reduce
from benchmark.trace_reduce import DEVICE_PREFIX, OPS_LINE

KERNEL = "mla_paged_attention"


def decode_runs(tr):
    """whole decode executions inside a recorded span that says how many
    cached tokens the step attends."""
    return [r for r in tr.executions("jit_paged_decode", inside="engine.decode")
            if "kv_tokens" in r.stats["span"].stats]


def kernel_ns(tr, runs) -> float:
    """ns the latent kernel's events cover inside `runs`; 0.0 without any."""
    path = span_reduce.newest_xplane()
    if path is None or not runs:
        return 0.0
    if not hasattr(tr, "mla_kernel_events"):
        from jax.profiler import ProfileData

        device = next((p for p in ProfileData.from_file(path).planes
                       if p.name.startswith(DEVICE_PREFIX)), None)
        tr.mla_kernel_events = [
            (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
            for line in (device.lines if device is not None else ())
            if line.name == OPS_LINE for ev in line.events
            if KERNEL in ev.name.split(" = ", 1)[0]]
    return sum(e - s for r in runs for s, e in tr.mla_kernel_events
               if r.start <= s and e <= r.end)


def read(facts):
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    runs = decode_runs(tr)
    ns = kernel_ns(tr, runs)
    return ns / len(runs) / 1e6 if ns else None
