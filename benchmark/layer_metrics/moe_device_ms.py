"""Device time of the expert layer in one decode step: the time covered by
the operations of the program's `moe.route` / `moe.experts` scopes
(models/transformer.py: _moe) inside the whole `jit_paged_decode`
executions that lie in a recorded `engine.decode` span, per execution (all
layers). The weight cast is outside the scopes and is not counted.

Where a v5e trace keeps the scope. An event of "XLA Ops" is named by its
whole HLO instruction; the instruction's `op_name`
(`jit(paged_decode)/while/body/.../moe.experts/...`) is not among the
event's own stats but in the `tf_op` stat of the event's METADATA, which
jax.profiler.ProfileData does not hand out. So this file reads that one
table from the .xplane.pb itself (protobuf wire format, two levels deep:
XSpace.planes -> XPlane.event_metadata / stat_metadata) and joins it to the
events by the instruction text. libtpu replaces `lax.ragged_dot` by its own
grouped-matmul kernels and names them anew (`%ragged-dot-*`, op_name
`ragged-dot-*`): those carry no scope, and in this program only the expert
layer has them, so they count by their name."""
from __future__ import annotations

from benchmark import span_reduce
from benchmark.trace_reduce import DEVICE_PREFIX, OPS_LINE, union_length

SCOPE = "moe."
KERNEL = "ragged-dot"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; length-delimited
    values come back as memoryviews, nothing is decoded further."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield num, wire, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def op_names(path: str) -> dict:
    """instruction text -> op_name, for the first device plane of the
    trace file: the `tf_op` stat of each event's metadata."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    table: dict = {}
    for num, wire, plane in _fields(space):
        if num != 1 or wire != 2:          # XSpace.planes
            continue
        name, events, stats = "", [], {}
        for pn, pw, val in _fields(plane):
            if pn == 2 and pw == 2:        # XPlane.name
                name = _text(val)
            elif pn == 4 and pw == 2:      # event_metadata: map entry
                events.append(val)
            elif pn == 5 and pw == 2:      # stat_metadata: map entry
                for en, ew, ev in _fields(val):
                    if en == 2 and ew == 2:
                        sid, sname = None, ""
                        for mn, mw, mv in _fields(ev):
                            if mn == 1 and mw == 0:
                                sid = mv
                            elif mn == 2 and mw == 2:
                                sname = _text(mv)
                        stats[sid] = sname
        if not name.startswith(DEVICE_PREFIX):
            continue
        for entry in events:
            for en, ew, ev in _fields(entry):
                if en != 2 or ew != 2:     # the entry's value: XEventMetadata
                    continue
                inst, op = "", None
                for mn, mw, mv in _fields(ev):
                    if mn == 2 and mw == 2:
                        inst = _text(mv)
                    elif mn == 5 and mw == 2:   # XEventMetadata.stats
                        sid, sval = None, None
                        for sn, sw, sv in _fields(mv):
                            if sn == 1 and sw == 0:
                                sid = sv
                            elif sn == 5 and sw == 2:
                                sval = _text(sv)
                        if stats.get(sid) == "tf_op" and sval is not None:
                            op = sval
                if inst and op is not None:
                    table[inst] = op
        break
    return table


def in_expert_layer(inst: str, op_name: str) -> bool:
    return SCOPE in op_name or KERNEL in inst.split(" = ", 1)[0]


def moe_ns_per_run(facts):
    """(ns the expert layer's operations cover, summed over the whole
    decode executions inside `engine.decode` spans; how many executions),
    or None where the trace holds no such operation."""
    tr = span_reduce.trace_of(facts)
    if tr is None:
        return None
    if not hasattr(tr, "moe_ns_per_run"):  # moe_weight_roofline asks again
        tr.moe_ns_per_run = _moe_ns_per_run(tr)
    return tr.moe_ns_per_run


def _moe_ns_per_run(tr):
    runs = tr.executions("jit_paged_decode", inside="engine.decode")
    path = span_reduce.newest_xplane()
    if not runs or path is None:
        return None
    names = op_names(path)
    from jax.profiler import ProfileData

    device = next((p for p in ProfileData.from_file(path).planes
                   if p.name.startswith(DEVICE_PREFIX)), None)
    if device is None:
        return None
    verdict: dict = {}
    spans = []
    for line in device.lines:
        if line.name != OPS_LINE:
            continue
        for ev in line.events:
            hit = verdict.get(ev.name)
            if hit is None:
                hit = verdict[ev.name] = in_expert_layer(
                    ev.name, names.get(ev.name, ""))
            if hit:
                spans.append((float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns)))
    total = 0.0
    for r in runs:
        inside = [(s, e) for s, e in spans if r.start <= s and e <= r.end]
        total += union_length(inside)[0] if inside else 0.0
    return (total, len(runs)) if total else None


def read(facts):
    got = moe_ns_per_run(facts)
    return None if got is None else got[0] / got[1] / 1e6
