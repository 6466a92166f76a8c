"""In-process on-demand profiling: CPU stack sampling + memory snapshots.

Reference parity: dashboard/modules/reporter/profile_manager.py — the
reference shells out to py-spy (CPU flamegraph / stack dump) and memray
(allocation tracking) against an arbitrary pid. Neither tool ships in this
environment, and out-of-process attaches need ptrace scope; instead every
ray_tpu worker can profile ITSELF on request (the worker protocol loop stays
responsive while an executor thread grinds — sampling happens from a
dedicated thread reading sys._current_frames()). The output is the standard
collapsed-stack ("flamegraph.pl") format: `root;child;leaf count` lines,
renderable by any flamegraph tool and cheap to aggregate in the dashboard.

Memory profiling uses stdlib tracemalloc: `memory_profile(duration)` diffs
two snapshots taken `duration` apart and reports the top allocation sites
(memray's core use-case: "where is memory going right now").

`span()` is the serving hot path's one timing primitive: a context manager
that writes a layer boundary ONCE to three sinks — the jax.profiler trace
(so it lies on the device's clock), the serve_engine_step_s histogram and
the flight recorder (serve/telemetry.py). `mark()` is its zero-length
sibling: a point on the trace whose attributes are durations the program
measured with its own clocks (a request's stage durations, once a request).
"""

from __future__ import annotations

import sys
import threading
import time
from collections import Counter
from typing import Any, Dict, List, Optional

_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, resolved on first use


class Span:
    """One timed layer boundary (see `span`). `event` and `slot` may be
    assigned inside the block — an admission learns only at its end
    whether it is a `request` or a `readmit`, or was parked (no event)."""

    __slots__ = ("_ann", "_tel", "_t0", "phase", "event", "slot", "args")

    def __init__(self, name, tel, phase, slot, event, counts):
        global _TRACE_ANNOTATION
        if _TRACE_ANNOTATION is None:
            # importing jax.profiler opens no backend
            from jax.profiler import TraceAnnotation

            _TRACE_ANNOTATION = TraceAnnotation
        attrs = _scalars(counts)
        if slot >= 0:
            attrs["slot"] = slot
        self._ann = _TRACE_ANNOTATION(name, **attrs)
        self._tel = tel
        self.phase, self.event, self.slot = phase, event, slot
        self.args = counts

    def set(self, **counts) -> None:
        """Counts known only inside the block (how many slots survived the
        reservation, how many tokens they attend to)."""
        self._ann.set_metadata(**_scalars(counts))
        if self._tel is not None:
            self.args.update(counts)

    def drop(self) -> None:
        """The block was not the phase it set out to be (every slot was
        preempted, a verify fell back to the plain step): the trace keeps
        the interval, the histogram and the recorder get nothing."""
        self.phase = self.event = None

    def __enter__(self):
        self._ann.__enter__()
        if self._tel is not None:
            self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        tel = self._tel
        if tel is not None:
            dur = time.monotonic() - self._t0  # the one clock read
            if self.phase is not None:
                tel.observe_phase(self.phase, dur)
            if self.event is not None and tel.recorder is not None:
                tel.recorder.record(self.event, slot=self.slot, dur=dur,
                                    args=self.args or None)
        return self._ann.__exit__(*exc)


def _scalars(counts: Dict[str, Any]) -> Dict[str, Any]:
    """Trace attributes are scalars: a sequence (the flight recorder's
    tuple of slot ids) is written to the trace as its length."""
    return {k: len(v) if isinstance(v, (tuple, list)) else v
            for k, v in counts.items()}


def span(name: str, tel=None, phase: Optional[str] = None, slot: int = -1,
         event: Optional[str] = None, **counts) -> Span:
    """`with span("engine.decode", tel, phase="decode", event="decode")`:
    a `jax.profiler.TraceAnnotation(name, **counts)` around the block —
    under two microseconds when no profiler session is active — and,
    when `tel` (a ServeTelemetry) is given, ONE duration taken on exit and
    fed to `tel.observe_phase(phase, dur)` and to the flight recorder as
    `event` with the same counts. The dotted `name` is the trace's; `event`
    is the recorder's documented name (`prefill_chunk`, `decode`, `verify`,
    `request` / `readmit`). With `tel=None` no clock is read and no
    histogram or recorder work is done."""
    return Span(name, tel, phase, slot, event, counts)


def mark(name: str, **counts) -> None:
    """A zero-length span: one `TraceAnnotation(name, **counts)` opened and
    closed at once, trace only — no clock read, no histogram, no recorder.
    What it carries is in its attributes, never in its position."""
    with Span(name, None, None, -1, None, counts):
        pass


def _frame_label(frame) -> str:
    code = frame.f_code
    fname = code.co_filename
    # compact: last two path components are enough to locate a file
    parts = fname.replace("\\", "/").rsplit("/", 2)
    short = "/".join(parts[-2:]) if len(parts) > 1 else fname
    return f"{short}:{code.co_name}"


def _collapse(frame) -> str:
    """Root-first collapsed stack for one thread's current frame."""
    stack: List[str] = []
    while frame is not None:
        stack.append(_frame_label(frame))
        frame = frame.f_back
    stack.reverse()
    return ";".join(stack)


def sample_stacks(
    duration_s: float = 2.0,
    interval_s: float = 0.01,
    include_idle: bool = False,
) -> Dict[str, int]:
    """Sample every thread's Python stack for `duration_s`; returns
    {collapsed_stack: sample_count}. The sampling thread excludes itself.

    `include_idle=False` drops stacks whose leaf is a pure wait (epoll /
    lock.acquire / sleep) — the protocol loop and executor idle-parks would
    otherwise dominate every profile.
    """
    me = threading.get_ident()
    agg: Counter = Counter()
    deadline = time.monotonic() + max(0.05, duration_s)
    idle_leaves = (
        "select.py:select", "selectors.py:select", "threading.py:wait",
        "threading.py:_wait_for_tstate_lock", "queue.py:get",
        "socket.py:accept",
    )
    while time.monotonic() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = _collapse(frame)
            if not include_idle and stack.rsplit(";", 1)[-1].endswith(idle_leaves):
                continue
            agg[stack] += 1
        time.sleep(interval_s)
    return dict(agg)


def collapsed_lines(agg: Dict[str, int], limit: Optional[int] = None) -> List[str]:
    """Render an aggregate as flamegraph-collapsed lines, hottest first."""
    items = sorted(agg.items(), key=lambda kv: -kv[1])
    if limit:
        items = items[:limit]
    return [f"{stack} {n}" for stack, n in items]


def top_functions(agg: Dict[str, int], limit: int = 15) -> List[dict]:
    """Leaf-attributed hot functions (the 'self time' view of a profile)."""
    leaf: Counter = Counter()
    total = 0
    for stack, n in agg.items():
        leaf[stack.rsplit(";", 1)[-1]] += n
        total += n
    return [
        {"fn": fn, "samples": n, "pct": round(100.0 * n / max(1, total), 1)}
        for fn, n in leaf.most_common(limit)
    ]


def cpu_profile(duration_s: float = 2.0, interval_s: float = 0.01) -> dict:
    """The worker-side RPC body: one self-profile, JSON-friendly."""
    t0 = time.monotonic()
    agg = sample_stacks(duration_s, interval_s)
    return {
        "kind": "cpu",
        "duration_s": round(time.monotonic() - t0, 3),
        "samples": sum(agg.values()),
        "collapsed": collapsed_lines(agg, limit=200),
        "top": top_functions(agg),
    }


def memory_profile(duration_s: float = 1.0, top: int = 25) -> dict:
    """Top allocation sites over a window (tracemalloc snapshot diff).
    If tracemalloc was off, turns it on for the window (self-contained)."""
    import tracemalloc

    started_here = not tracemalloc.is_tracing()
    if started_here:
        tracemalloc.start(10)
    try:
        before = tracemalloc.take_snapshot()
        time.sleep(max(0.0, duration_s))
        after = tracemalloc.take_snapshot()
        stats = after.compare_to(before, "lineno")
        cur, peak = tracemalloc.get_traced_memory()
        rows = [
            {
                "site": str(s.traceback[0]) if s.traceback else "?",
                "size_diff_kb": round(s.size_diff / 1024.0, 1),
                "size_kb": round(s.size / 1024.0, 1),
                "count_diff": s.count_diff,
            }
            for s in stats[:top]
        ]
        return {
            "kind": "mem",
            "traced_current_kb": round(cur / 1024.0, 1),
            "traced_peak_kb": round(peak / 1024.0, 1),
            "window_s": duration_s,
            "top": rows,
        }
    finally:
        if started_here:
            tracemalloc.stop()


def stack_dump() -> dict:
    """Instantaneous stack of every thread (py-spy `dump` equivalent)."""
    frames = sys._current_frames()
    me = threading.get_ident()
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for tid, frame in frames.items():
        if tid == me:
            continue
        out[names.get(tid, str(tid))] = _collapse(frame).split(";")
    return {"kind": "dump", "threads": out}
