"""From a profiler trace to the numbers that need the PROGRAM'S OWN NAMES:
its spans on the host (ray_tpu/util/profiling.py: span), its jitted
programs on the device's "XLA Modules" line, its Pallas kernels on "XLA Ops".
The new readers under layer_metrics/ are a few lines each on top of this.

A sibling of trace_reduce.py, which it imports and does not change. Where
trace_reduce.py reduces the trace inside the process that wrote it and hands
`facts["trace"]` to the readers, this file is read by the readers themselves,
in the benchmark's driver process, from the trace file on disk: the newest
`chiprun_out/trace/<cell>/plugins/profile/*/*.xplane.pb`. The directory
names the cell, and the cell's and the configuration's files give the shapes
that the two roofline counts need. ProfileData parses the file; no JAX
backend is opened.

What is where in a v5e trace (read by hand, PERF.md section 7):

- host spans are events of the `/host:CPU` plane, on the line of the thread
  that opened them, named `batcher.iteration`, `engine.decode`, ... with
  their attributes (`slots`, `kv_tokens`, `rid`, ...) as event stats. They
  lie on the same clock as the device's events.
- a jitted program is one event per execution on the device's
  `XLA Modules` line, named `jit_paged_decode(<hash>)`; the name kept here
  is the part before the bracket.
- a Pallas kernel is an event of `XLA Ops` whose HLO instruction is named
  after the kernel: `%paged_attention.3`, `%flash_attention_bwd_dq.7`, and
  under autodiff `%jvp_flash_attention_fwd_.2`. A kernel is found by its
  name appearing in the instruction's own name.

The window's edge. A span still open when the trace stops is not recorded,
and neither is one that was open when it started; an execution that the
stop cut is recorded SHORT. So an execution counts only if it is whole —
it holds as many operations as the fullest execution of its program — and
a number that joins executions to spans counts only executions that lie
inside a recorded span. Both sides of every ratio use the same set."""

from __future__ import annotations

import glob
import os
import re
import sys
from bisect import bisect_left, bisect_right

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import common
from benchmark.trace_reduce import DEVICE_PREFIX, OPS_LINE, union_length

MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("batcher.", "engine.")
# longest first: flash_attention_bwd is a prefix of the two-kernel backward
KERNELS = ("flash_attention_bwd_dkv", "flash_attention_bwd_dq",
           "flash_attention_bwd", "flash_attention_fwd", "paged_attention")
KV_BYTES = {"fp": 2, "int8": 1}  # PagedDecodeEngine kv_cache_dtype -> bytes

_CACHE: dict = {}  # path -> Trace, one parse per process


class Span:
    __slots__ = ("name", "start", "end", "stats")

    def __init__(self, name, start, end, stats=None):
        self.name, self.start, self.end = name, float(start), float(end)
        self.stats = stats or {}

    @property
    def dur(self):
        return self.end - self.start

    def holds(self, other) -> bool:
        return self.start <= other.start and other.end <= self.end


def kernel_of(hlo: str):
    """'%jvp_flash_attention_fwd_.2 = bf16[..] custom-call(..)' ->
    'flash_attention_fwd'; None for an operation that is no named kernel."""
    own = hlo.split(" = ", 1)[0]
    for k in KERNELS:
        if k in own:
            return k
    return None


class Trace:
    """One parsed trace: host spans by name, the executions of the first
    device's programs with the kernel events inside each, and that
    device's busy intervals."""

    def __init__(self, data, cell: str | None = None):
        self.cell = cell
        self.spans: dict = {}
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        self.spans.setdefault(ev.name, []).append(Span(
                            ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            dict(ev.stats)))
        for group in self.spans.values():
            group.sort(key=lambda s: s.start)
        self.runs: list = []   # executions: Span(program, ..), stats n_ops
        self.kernels: list = []  # Span(kernel, start, end)
        self.busy: list = []   # merged [start, end] of device operations
        self._busy_ends: list = []
        device = next((p for p in data.planes
                       if p.name.startswith(DEVICE_PREFIX)), None)
        if device is None:
            return
        ops = []
        for line in device.lines:
            if line.name == MODULES_LINE:
                # "jit_paged_prefill(<hash>)": the hash tells the shapes of
                # one program apart, which the rule for `whole` needs
                self.runs = sorted(
                    (Span(ev.name.split("(", 1)[0], ev.start_ns,
                          ev.start_ns + ev.duration_ns, {"program": ev.name})
                     for ev in line.events), key=lambda r: r.start)
            elif line.name == OPS_LINE:
                ops = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                       for ev in line.events]
        _, self.busy = union_length([(s, s + d) for _, s, d in ops])
        self._busy_ends = [e for _, e in self.busy]
        # a kernel event is a leaf of "XLA Ops" (a custom call encloses
        # nothing), so its duration is its self time
        for n, s, d in ops:
            kernel = kernel_of(n)
            if kernel:
                self.kernels.append(Span(kernel, s, s + d))
        self.kernels.sort(key=lambda k: k.start)
        starts = sorted(s for _, s, _ in ops)
        for r in self.runs:
            r.stats["n_ops"] = (bisect_left(starts, r.end)
                                - bisect_left(starts, r.start))
        fullest: dict = {}
        for r in self.runs:
            key = r.stats["program"]
            fullest[key] = max(fullest.get(key, 0), r.stats["n_ops"])
        for r in self.runs:
            r.stats["whole"] = r.stats["n_ops"] == fullest[r.stats["program"]]

    # ------------------------------------------------------------ helpers

    def named(self, name: str) -> list:
        """the recorded host spans of that name, in time order."""
        return self.spans.get(name, [])

    def executions(self, program: str, inside: str | None = None) -> list:
        """whole executions of a program (`jit_paged_decode`); with
        `inside`, only those that lie in a recorded span of that name,
        each with its span as `.stats["span"]`."""
        runs = [r for r in self.runs if r.name == program and r.stats["whole"]]
        if inside is None:
            return runs
        out = []
        for r in runs:
            sp = next((s for s in self.named(inside) if s.holds(r)), None)
            if sp is not None:
                r.stats["span"] = sp
                out.append(r)
        return out

    def kernel_events(self, kernel_prefix: str, runs) -> list:
        """the kernel events (by name prefix) inside the given executions."""
        return [k for r in runs for k in self.kernels
                if k.name.startswith(kernel_prefix)
                and r.start <= k.start and k.end <= r.end]

    def busy_inside(self, start: float, end: float) -> float:
        """ns the device is busy inside [start, end]."""
        total = 0.0
        for i in range(bisect_right(self._busy_ends, start), len(self.busy)):
            s, e = self.busy[i]
            if s >= end:
                break
            total += min(e, end) - max(s, start)
        return total

    def idle_by_span(self) -> dict:
        """Where the device's idle time inside the window lies among the
        program's spans: each idle nanosecond goes to the INNERMOST span
        that covers it (`engine.decode` keeps what its five leaves do not
        cover), `(no span)` what no span covers. -> name -> seconds, plus
        `idle_s` and `covered_share`."""
        if not self.busy:
            return {}
        lo, hi = self.busy[0][0], self.busy[-1][1]
        spans = sorted((s for g in self.spans.values() for s in g),
                       key=lambda s: (s.start, -s.end))
        idle_in = [max(0.0, min(s.end, hi) - max(s.start, lo))
                   - self.busy_inside(max(s.start, lo), min(s.end, hi))
                   for s in spans]
        own = list(idle_in)
        stack = []  # indices of the spans still open
        top = 0.0
        for i, s in enumerate(spans):
            while stack and spans[stack[-1]].end <= s.start:
                stack.pop()
            if stack:
                own[stack[-1]] -= idle_in[i]
            else:
                top += idle_in[i]
            stack.append(i)
        idle = (hi - lo) - self.busy_inside(lo, hi)
        out: dict = {}
        for s, t in zip(spans, own):
            out[s.name] = out.get(s.name, 0.0) + t / 1e9
        out["(no span)"] = (idle - top) / 1e9
        out["idle_s"] = idle / 1e9
        out["covered_share"] = top / idle if idle else 0.0
        return out


def mean_ms(values_ns):
    """mean of nanosecond values in ms; None of nothing."""
    values_ns = list(values_ns)
    return sum(values_ns) / len(values_ns) / 1e6 if values_ns else None


def newest_xplane():
    files = glob.glob(os.path.join(
        common.ROOT, "chiprun_out", "trace", "*", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> Trace:
    if path not in _CACHE:
        from jax.profiler import ProfileData

        cell = None
        m = re.search(r"trace/([^/]+)/plugins/profile/", path.replace(os.sep, "/"))
        if m:
            cell = m.group(1)
        _CACHE[path] = Trace(ProfileData.from_file(path), cell)
    return _CACHE[path]


def trace_of(facts: dict):
    """The readers' entry: the trace this run wrote, or None when the run
    was not traced (the untraced sweep also calls the readers)."""
    if facts.get("trace") is None:
        return None
    path = newest_xplane()
    return None if path is None else load(path)


# ------------------------------------- what a kernel call has to move or do


def shapes(cell_name: str):
    cell = common.load_workload(cell_name)
    return cell, common.load_config(cell["config"])


def paged_attention_bytes(conf: dict, kv_tokens: int) -> float:
    """Bytes the paged kernel has to read in one decode step that attends
    to `kv_tokens` cached tokens (summed over the batch): K and V, every
    layer, every KV head, in the pool's dtype. Queries, outputs and block
    tables are left out (a few hundred KB): the count is a floor."""
    width = KV_BYTES[conf["engine"]["kv_cache_dtype"]]
    return (float(kv_tokens) * 2 * conf["num_hidden_layers"]
            * conf["num_key_value_heads"] * conf["head_dim"] * width)


def flash_matmul_flops(conf: dict, cell: dict) -> float:
    """FLOPs of ONE causal attention matmul (QK^T, or PV) of one layer over
    this device's shard of the batch: 2 per multiply-add, token i against
    i + 1 keys — the count required_train_flops_per_token uses, per layer
    and per sequence instead of per token."""
    seq = cell["seq_len"]
    per_token = (2.0 * conf["num_attention_heads"] * conf["head_dim"]
                 * (seq + 1) / 2.0)
    return per_token * seq * cell["batch_per_chip"]


# matmuls a pass REQUIRES: forward QK^T and PV; backward dV, dP, dQ, dK (two
# in each of its kernels, four in the fused one). The QK^T each backward
# kernel recomputes is the implementation's choice and is not counted; a
# forward that remat runs again is a pass that ran, and is.
FLASH_MATMULS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 2,
                 "flash_attention_bwd_dkv": 2, "flash_attention_bwd": 4}


def report(tr: Trace) -> dict:
    """What one reads by hand, by command: spans, programs, kernels, and
    where the device's idle time lies."""
    def stat(spans):
        return {"count": len(spans), "mean_ms": mean_ms(s.dur for s in spans)}

    programs: dict = {}
    for r in tr.runs:
        programs.setdefault(r.name, []).append(r)
    kernels: dict = {}
    for k in tr.kernels:
        kernels.setdefault(k.name, []).append(k)
    return {
        "cell": tr.cell,
        "spans": {n: stat(g) for n, g in sorted(tr.spans.items())},
        "programs": {n: {**stat([r for r in g if r.stats["whole"]]),
                         "cut": sum(not r.stats["whole"] for r in g)}
                     for n, g in sorted(programs.items())},
        "kernels": {n: stat(g) for n, g in sorted(kernels.items())},
        "idle_by_span": tr.idle_by_span(),
    }


if __name__ == "__main__":
    import json

    src = sys.argv[1] if len(sys.argv) > 1 else newest_xplane()
    if src is None:
        sys.exit("no trace under chiprun_out/trace/")
    if os.path.isdir(src):
        from benchmark.trace_reduce import find_xplane

        src = find_xplane(src)
    print(json.dumps(report(load(src)), indent=1))
