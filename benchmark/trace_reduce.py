"""From a profiler trace (.xplane.pb) to the numbers the benchmark reports:
device busy and idle time, collective time, the device operations that took
most time, and the longest idle gaps with what the host was doing in them.

Reads the file with jax.profiler.ProfileData and nothing else. A device is
a plane whose name starts with "/device:TPU:"; its operations are the events
of the line named "XLA Ops" (one event per executed HLO operation, fusion or
custom call, with start and duration on the trace's clock). Busy time is
the UNION of those intervals, so operations that overlap on the device are
not counted twice. The window runs from the first device operation of the
trace to the end of the last one: what start_trace and stop_trace themselves
cost the host (0.05 s and 0.25 s on the v5e host) lies outside it.

On this chip an event of "XLA Ops" is named by the whole text of its HLO
instruction ("%fusion.12 = bf16[...] fusion(...), kind=kOutput, ..."); the
reduction keeps the instruction's own name less its number ("fusion"). Control-flow
operations (while, conditional) enclose the operations of their bodies on the
same line, so the time of an operation is its SELF time: its duration less
that of the events nested directly inside it."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute",
    re.IGNORECASE)
TOP_N = 10


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_length(intervals) -> tuple[float, list]:
    """Total length of the union of [start, end) intervals, and the merged
    intervals themselves, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def short_name(hlo: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion': the instruction's
    own name less its number, so that the copies of one operation in every
    unrolled layer and every step add up. A custom call keeps its target,
    which is how a Pallas kernel shows ('closed_call[tpu_custom_call]')."""
    name = hlo.split(" = ", 1)[0].strip().lstrip("%")
    name = re.sub(r"\.\d+$", "", name)
    m = re.search(r'custom_call_target="([^"]+)"', hlo)
    return f"{name}[{m.group(1)}]" if m else name


def _device_ops(plane):
    for line in plane.lines:
        if line.name == OPS_LINE:
            return [(short_name(ev.name), float(ev.start_ns),
                     float(ev.duration_ns)) for ev in line.events]
    return []


def self_times(ops) -> dict:
    """name -> summed self time (ns): each event's duration less the
    durations of the events nested directly inside it. An event that only
    overlaps the one before it (ends after it) is its sibling, not its
    child."""
    out: dict = {}
    stack = []  # (end, name, self) of the events still open
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and (stack[-1][0] <= s or stack[-1][0] < s + d):
            _, n, t = stack.pop()
            out[n] = out.get(n, 0.0) + t
        if stack:
            stack[-1][2] -= d
        stack.append([s + d, name, d])
    for _, n, t in stack:
        out[n] = out.get(n, 0.0) + t
    return out


def _host_events(data):
    """Events of the host threads that dispatch device work, as (start,
    end, name). A dispatching thread is one with a jitted call
    ("PjitFunction(...)") or one of the benchmark's own annotations
    ("bench....") on it; event loops and idle pool threads sit in select()
    the whole trace long and would claim every gap. With no such thread,
    every host thread counts."""
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                    ev.name) for ev in line.events if ev.duration_ns > 0]
            dispatches = any(n.startswith(("PjitFunction", "bench."))
                             for _, _, n in evs)
            lines.append((dispatches, evs))
    chosen = [evs for d, evs in lines if d] or [evs for _, evs in lines]
    return [e for evs in chosen for e in evs]


def _attribute(gap, host_events) -> str:
    """The host event that overlaps the gap most; among equals the
    shortest, which is the innermost."""
    best, best_key = "host:nothing-recorded", (0.0, 0.0)
    gs, ge = gap
    for s, e, name in host_events:
        ov = min(e, ge) - max(s, gs)
        if ov > 0:
            key = (ov, -(e - s))
            if key > best_key:
                best, best_key = name, key
    return best


def reduce_xspace(data, max_gaps: int = 64) -> dict | None:
    """ProfileData -> facts. None when no device operation was recorded."""
    devices = [(p.name, _device_ops(p)) for p in data.planes
               if p.name.startswith(DEVICE_PREFIX)]
    devices = [(n, ops) for n, ops in devices if ops]
    if not devices:
        return None
    lo = min(s for _, ops in devices for _, s, _ in ops)
    hi = max(s + d for _, ops in devices for _, s, d in ops)
    window_ns = hi - lo

    busy = []
    for _, ops in devices:
        length, _ = union_length([(s, s + d) for _, s, d in ops])
        busy.append(length)

    # one device (the first) for what is reported per device
    name0, ops0 = devices[0]
    by_name = self_times(ops0)
    coll_ns = sum(t for n, t in by_name.items() if COLLECTIVE.search(n))
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP_N]

    _, merged0 = union_length([(s, s + d) for _, s, d in ops0])
    edges = [lo] + [x for iv in merged0 for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = _host_events(data) if gaps else []
    by_host: dict = {}
    for gap in gaps[:max_gaps]:
        who = _attribute(gap, host)
        by_host[who] = by_host.get(who, 0.0) + (gap[1] - gap[0])
    idle_top = [kv for kv in sorted(by_host.items(), key=lambda kv: -kv[1])
                if kv[1] >= 1000.0][:TOP_N]  # a microsecond or more

    mean_busy = sum(busy) / len(busy)
    return {
        "devices": [n for n, _ in devices],
        "window_s": window_ns / 1e9,
        "busy_s": mean_busy / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy],
        "idle_share_pct": 100.0 * (1.0 - mean_busy / window_ns),
        "collective_s": coll_ns / 1e9,
        "collective_share_pct": 100.0 * coll_ns / window_ns,
        "n_ops": len(ops0),
        "device_ops": [[n, t / 1e9] for n, t in top],
        "idle_gaps": [[n, t / 1e9] for n, t in idle_top],
        "gaps_attributed": min(len(gaps), max_gaps), "gaps_total": len(gaps),
    }


def reduce_dir(trace_dir: str) -> dict | None:
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_xspace(ProfileData.from_file(path))


def describe(trace_dir: str, per_line: int = 12) -> list:
    """Planes, lines and a few event names of a trace: what one reads by
    hand before writing a reduction against it."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return []
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            names: dict = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0.0) + float(ev.duration_ns)
            top = sorted(names.items(), key=lambda kv: -kv[1])[:per_line]
            rows.append({"plane": plane.name, "line": line.name,
                         "events": len(evs),
                         "first_ns": min((e.start_ns for e in evs), default=0),
                         "last_ns": max((e.end_ns for e in evs), default=0),
                         "top": [[n[:400], t / 1e9] for n, t in top]})
    return rows
