"""Request batching inside a replica.

Two batching models live here:

  @serve.batch — request-level coalescing (reference parity:
  serve/batching.py _BatchQueue: collect up to max_batch_size requests or
  batch_wait_timeout_s, call the wrapped fn once with the list, scatter
  results). Implemented with a flusher thread because replica methods
  execute on a thread pool (see _private/worker_main.py).

  ContinuousBatcher — TOKEN-level batching for autoregressive generation
  (the Orca/vLLM iteration-level scheduling shape): one loop thread owns an
  engine with `max_batch_size` decode slots, admits queued requests into
  the RUNNING batch between decode steps and retires finished sequences at
  token granularity — no stop-the-world between generations. Emitted
  tokens stream to per-request GenerationStreams (the replica exposes them
  to the proxy via stream_next pulls; see serve/README.md).

Both compose with graceful draining: `drain(deadline_s)` stops admissions,
bounces queued-but-unadmitted work with ReplicaDrainingError (the handle
retries it transparently on a live replica) and lets in-flight work finish
— a running generation keeps decoding until done or the drain deadline, at
which point it is CUT (its stream ends, marked `cut`), never orphaned.
"""

from __future__ import annotations

import functools
import itertools
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu.util.profiling import mark, span

from .telemetry import STAGE_ATTRS, current_request


class _BatchQueue:
    _serve_drainable = True

    def __init__(self, fn: Callable, max_batch_size: int, timeout_s: float):
        self.fn = fn
        self.max_batch_size = max_batch_size
        self.timeout_s = timeout_s
        self.q: "queue.Queue" = queue.Queue()
        self._draining = False
        self._thread = threading.Thread(target=self._flush_loop, daemon=True)
        self._thread.start()

    def submit(self, self_arg, item) -> Future:
        fut: Future = Future()
        if self._draining:
            fut.set_exception(self._drain_error())
            return fut
        self.q.put((self_arg, item, fut))
        if self._draining:
            # raced drain(): make sure nothing lingers in the queue
            self._bounce_queued()
        return fut

    @staticmethod
    def _drain_error():
        from .replica import ReplicaDrainingError

        return ReplicaDrainingError()

    def _bounce_queued(self):
        while True:
            try:
                *_, fut = self.q.get_nowait()
            except queue.Empty:
                return
            if not fut.done():
                fut.set_exception(self._drain_error())

    def drain(self, deadline_s: Optional[float] = None) -> None:
        """Stop batching: queued-but-unadmitted items fail with
        ReplicaDrainingError (no user code ran — the handle re-routes them
        to a live replica); the batch currently executing completes."""
        self._draining = True
        self._bounce_queued()

    def _flush_loop(self):
        while True:
            first = self.q.get()
            batch = [first]
            deadline = self.timeout_s
            t0 = time.monotonic()
            while len(batch) < self.max_batch_size and not self._draining:
                remaining = deadline - (time.monotonic() - t0)
                if remaining <= 0:
                    break
                try:
                    batch.append(self.q.get(timeout=remaining))
                except queue.Empty:
                    break
            if self._draining:
                # collected but user code never ran: bounce for retry
                for *_, f in batch:
                    if not f.done():
                        f.set_exception(self._drain_error())
                continue
            self_arg = batch[0][0]
            items = [b[1] for b in batch]
            futs = [b[2] for b in batch]
            try:
                if self_arg is None:
                    results = self.fn(items)
                else:
                    results = self.fn(self_arg, items)
                if len(results) != len(items):
                    raise ValueError(
                        f"@serve.batch fn returned {len(results)} results for "
                        f"{len(items)} inputs"
                    )
                for f, r in zip(futs, results):
                    f.set_result(r)
            except Exception as e:  # noqa: BLE001
                for f in futs:
                    f.set_exception(e)


def batch(_fn=None, *, max_batch_size: int = 8, batch_wait_timeout_s: float = 0.01):
    """Decorate a method taking List[T] -> List[R]; callers pass single T."""

    def decorator(fn):
        bq_attr = f"__batch_queue_{fn.__name__}"

        @functools.wraps(fn)
        def wrapper(*args):
            if len(args) == 2:  # bound method: (self, item)
                self_arg, item = args
                holder = self_arg
            else:  # plain function: (item,)
                (item,) = args
                self_arg, holder = None, wrapper
            bq = getattr(holder, bq_attr, None)
            if bq is None:
                bq = _BatchQueue(fn, max_batch_size, batch_wait_timeout_s)
                setattr(holder, bq_attr, bq)
            return bq.submit(self_arg, item).result()

        return wrapper

    if _fn is not None:
        return decorator(_fn)
    return decorator


# --------------------------------------------------------------------------
# continuous batching (token-granularity admission/retirement)
# --------------------------------------------------------------------------


class GenerationStream:
    """Per-request token stream: the batcher pushes, one consumer pulls.

    Iterable in-process; `next_batch` is the long-poll pull the replica's
    stream_next uses (block up to wait_s for the first item, then drain
    whatever else is ready)."""

    _END = object()

    def __init__(self, request_id: int, request: Dict[str, Any]):
        self.request_id = request_id
        self.request = request
        self.cut = False        # drain deadline truncated this generation
        self.cancelled = False  # consumer went away
        # cancel(completed=True): an API layer ended the generation as a
        # SUCCESS (stop-sequence match, eos decided mid-burst) — the slot
        # frees like any cancel, but metrics count the request as "ok",
        # not as a client abort
        self.cancel_completed = False
        self.preempted = False  # evicted under KV pressure, parked to resume
        self._q: "queue.Queue" = queue.Queue()
        self._finished = threading.Event()
        self._error: Optional[BaseException] = None
        self._drained = False   # END consumed; only the error (if any) left
        # lifecycle timestamps (monotonic): kept unconditionally (they're
        # one clock read per token) so API layers can report TTFT even
        # with the metrics plane off; _tel is set by the owning batcher
        self.t_submit = time.monotonic()
        self.t_enqueue = self.t_submit  # re-stamped on preemption re-parks
        self.t_first: Optional[float] = None
        self._t_last = self.t_submit
        self.n_tokens = 0
        self._tel = None
        # the request as the rest of the system knows it: the carried
        # telemetry.RequestClock (None for a caller outside any request),
        # where the last admission started and into which slot (set by the
        # batcher), and whether a consumer has taken the first item yet
        self._clock = None
        self.t_admit = self.t_submit
        self._slot = -1
        self._first_pulled = False
        # finalize-once guard is a real lock: close() (caller thread) and
        # the batcher loop can race _finish on the same stream, and a
        # check-then-set would double-count request metrics
        self._finalized = False
        self._final_lock = threading.Lock()

    # -- producer side (batcher loop thread)

    def _push(self, token) -> None:
        now = time.monotonic()
        tel = self._tel
        if tel is not None:
            if self.n_tokens == 0:
                tel.ttft.observe(now - self.t_submit)
                self._mark_first_token(tel, now)
            else:
                tel.inter_token.observe(now - self._t_last)
        if self.n_tokens == 0:
            self.t_first = now
        self._t_last = now
        self.n_tokens += 1
        self._q.put(token)

    def _span_ids(self) -> Dict[str, Any]:
        """`rid` (this batcher's counter) and, inside a carried request,
        `req` (the id every process knows it by)."""
        if self._clock is None:
            return {"rid": self.request_id}
        return {"rid": self.request_id, "req": self._clock.rid}

    def _mark_first_token(self, tel, now: float) -> None:
        """Stage 6, once a request: the way here as one zero-length span
        whose attributes are the stage durations in whole microseconds
        (`queue_us` from the LAST enqueue, as serve_queue_wait_s has it;
        `prefill_us` from the last admission's start, through the chunks
        and the decode steps between them when the prompt was chunked),
        and the stages before `submit` to serve_request_stage_s."""
        us = {"queue_us": int((self.t_admit - self.t_enqueue) * 1e6),
              "prefill_us": int((now - self.t_admit) * 1e6)}
        if self._clock is not None:
            for stage, dur in self._clock.stages_in(self.t_submit).items():
                tel.observe_stage(stage, dur)
                us[STAGE_ATTRS[stage]] = int(dur * 1e6)
        mark("batcher.first_token", slot=self._slot, **self._span_ids(), **us)

    def _mark_first_pull(self, tel) -> None:
        """Stage 7, on the puller's thread: how long the first token lay
        in the queue before a pull took it."""
        waited = time.monotonic() - self.t_first
        tel.observe_stage("first_pull_wait", waited)
        mark("batcher.first_pull", **self._span_ids(),
             waited_us=int(waited * 1e6))

    def _outcome(self) -> str:
        if self._error is not None:
            from .replica import ReplicaDrainingError

            return ("draining" if isinstance(self._error, ReplicaDrainingError)
                    else "error")
        if self.cut:
            return "cut"
        if self.cancelled and not self.cancel_completed:
            return "cancelled"
        return "ok"

    def _finish(self, error: Optional[BaseException] = None,
                cut: bool = False) -> None:
        # FIRST finish wins the terminal state — close()/drain racing the
        # loop thread's own _finish must neither clear a recorded engine
        # fault (self._error = None would turn it into a silent clean
        # cut) nor double-count the request's metrics. State is published
        # INSIDE the lock and losers return before touching the queue, so
        # a loser's END can never release the consumer ahead of the
        # winner's error write.
        with self._final_lock:
            if self._finalized:
                return
            self._finalized = True
            self._error = error
            self.cut = cut or self.cut
        tel = self._tel
        if tel is not None:
            tel.request_latency.observe(time.monotonic() - self.t_submit)
            tel.requests.inc(tags={"outcome": self._outcome()})
            if self.n_tokens:
                # counted at retirement, not per token: one Counter.inc
                # per request keeps the per-token hot path to exactly
                # one histogram observe
                tel.tokens.inc(self.n_tokens)
        self._finished.set()
        self._q.put(self._END)

    # -- consumer side

    def cancel(self, completed: bool = False) -> None:
        """Consumer gone (or, with completed=True, the API layer closed a
        SUCCESSFUL generation early — stop match): the batcher retires
        the slot at the next step."""
        if completed:
            self.cancel_completed = True
        self.cancelled = True

    @property
    def finished(self) -> bool:
        return self._finished.is_set()

    def next_batch(self, max_items: int = 64,
                   wait_s: float = 0.25) -> Tuple[List[Any], bool]:
        """Pull up to max_items; returns (items, done). Blocks up to wait_s
        for the first item; raises the stream's error (e.g.
        ReplicaDrainingError for a never-admitted request, an engine fault
        mid-generation) once all produced items have been delivered — a
        faulted stream must never end looking like a clean completion, so
        when tokens and the END marker land in one pull the items go out
        with done=False and the NEXT pull raises."""
        if self._drained:
            if self._error is not None:
                raise self._error
            return [], True
        items: List[Any] = []
        try:
            first = self._q.get(timeout=max(0.0, wait_s))
        except queue.Empty:
            return items, False
        ended = first is self._END
        if not ended:
            items.append(first)
            while len(items) < max_items:
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    break
                if nxt is self._END:
                    ended = True
                    break
                items.append(nxt)
        if not self._first_pulled and items:
            self._first_pulled = True
            if self._tel is not None:
                self._mark_first_pull(self._tel)
        if ended:
            self._drained = True
            if self._error is not None:
                if items:
                    return items, False  # error surfaces on the next pull
                raise self._error
        return items, ended

    def __iter__(self):
        while True:
            items, done = self.next_batch(max_items=64, wait_s=5.0)
            yield from items
            if done:
                return


class ContinuousBatcher:
    """Token-granularity continuous batching over a slot-based engine.

    engine contract (ray_tpu.models.kv_paging.PagedDecodeEngine is the
    implementation; a test may hand any object with these):
      admit(slot, request) -> (token, done)   prefill `request["tokens"]`
        into the free slot; the first sampled token, and whether the
        generation is already over (eos, or `max_new_tokens` of 1)
      step(slots)          -> {slot: (token, done)}   ONE decode step for
        every listed slot together, whatever their sequence lengths
      release(slot)          optional: the slot's generation has ended or
        been cut; free what it holds
      stats() -> dict        optional: merged into the batcher's stats()

    A step result may also carry a token LIST per slot (speculative
    decoding: PagedDecodeEngine with speculative_k > 0 emits 1..k+1
    accepted tokens per verify step). Every token is pushed to the
    stream individually, so SSE consumers see the whole accepted burst
    and deadlines/drain/preemption still cut at token granularity.

    Chunked prefill (PagedDecodeEngine with prefill_chunk_tokens > 0)
    stretches the contract the other way: admit() may return
    (None, False) — nothing is pushed — and subsequent steps return
    ([], False) for that slot while its prompt streams in chunk-per-step,
    INTERLEAVED with everyone else's decode in the same engine step. The
    first sampled token arrives through step() once the prompt is
    consumed. The batcher needs no scheduling changes for this: the
    engine owns the chunk/decode interleave; empty token lists simply
    push nothing.

    One loop thread owns the engine. Requests submitted while the batch is
    full wait in a queue and are admitted the moment a slot retires —
    mid-generation of everyone else (that is the whole point). The
    per-step occupancy log (`occupancy_log()`) records which requests
    shared each engine step; tests use it to prove interleaving.

    Paging-aware engines (ray_tpu.models.kv_paging.PagedDecodeEngine) are
    driven through two optional duck-typed hooks:

      can_admit(request) -> bool   block-budget admission: a request whose
        worst-case KV-block need exceeds the pool's current headroom waits
        at the head of the line (order preserved) instead of thrashing —
        unless NOTHING is running, in which case it is admitted
        best-effort so a lone oversized request still gets a clear error
        rather than queueing forever.
      take_preempted() -> [(slot, parked_request)]   generations the
        engine evicted under pool exhaustion: their stream stays OPEN and
        the parked request (prompt + tokens generated so far) re-enters at
        the head of the admission line — on readmit the engine recomputes
        the cache and the stream resumes exactly where it stopped, so the
        consumer (an SSE socket, an iter_stream caller) never notices
        beyond latency.
    """

    _serve_drainable = True

    def __init__(
        self,
        engine,
        max_batch_size: Optional[int] = None,
        batch_wait_timeout_s: Optional[float] = None,
        telemetry=None,
    ):
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        from .telemetry import resolve as _tel_resolve

        # request-lifecycle metrics + flight recorder (serve/telemetry.py):
        # None = process singleton per the serve_telemetry flag, False =
        # off for this batcher (zero per-token work)
        self._tel = _tel_resolve(telemetry)
        self._rec = self._tel.recorder if self._tel is not None else None
        self.engine = engine
        engine_cap = getattr(engine, "max_batch_size", None)
        self.max_batch_size = int(
            max_batch_size
            or engine_cap
            or cfg.serve_generation_max_batch_size
        )
        if engine_cap is not None and self.max_batch_size > engine_cap:
            raise ValueError(
                f"max_batch_size {self.max_batch_size} exceeds the engine's "
                f"{engine_cap} slots"
            )
        self.batch_wait_timeout_s = float(
            cfg.serve_generation_batch_wait_timeout_s
            if batch_wait_timeout_s is None else batch_wait_timeout_s
        )
        self._pending: "queue.Queue[GenerationStream]" = queue.Queue()
        # head-of-line parking: preempted generations awaiting readmission
        # and requests the engine's block budget cannot cover yet — checked
        # before the pending queue so admission order is preserved
        self._holdback: "deque" = deque()
        # items popped from holdback/pending but not yet admitted ("in
        # hand"): counted as ongoing so a drain poll sampling mid-gather
        # never sees a momentarily-empty replica and reaps an open stream
        self._in_hand = 0
        # memoized verdict for the parked head-of-line request: pool
        # headroom only changes on retire/preempt/admit, so the per-step
        # can_admit recheck (prompt hashing + cache scan) is skipped until
        # one of those happens
        self._admission_verdict: Optional[Tuple[int, bool]] = None
        self._admission_dirty = True
        self._free = list(range(self.max_batch_size))
        self._active: Dict[int, GenerationStream] = {}
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._draining = False
        self._drain_deadline: Optional[float] = None
        self._shutdown = False
        self._steps = 0
        # bounded: observability for tests/operators, not a flight recorder
        self._occupancy: "deque" = deque(maxlen=65536)
        # cross-thread calls serviced by the loop thread (run_on_loop):
        # (fn, result box, done event) triples, drained every iteration
        self._loop_calls: "deque" = deque()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="continuous-batcher"
        )
        self._thread.start()

    # ------------------------------------------------------------ public API

    def submit(self, **request) -> GenerationStream:
        """Queue a generation request; returns its token stream. Raises
        ReplicaDrainingError while draining (nothing ran — retryable)."""
        from .replica import ReplicaDrainingError

        with self._lock:
            if self._draining or self._shutdown:
                raise ReplicaDrainingError()
            stream = GenerationStream(next(self._ids), request)
            stream._tel = self._tel
            stream._clock = current_request()
            self._pending.put(stream)
        return stream

    def drain(self, deadline_s: Optional[float] = None) -> None:
        """Stop admissions; bounce queued-but-unadmitted requests for
        handle-side retry; let running generations finish until
        `deadline_s` from now, then cut them."""
        with self._lock:
            self._draining = True
            # explicit None check: deadline_s=0 means cut NOW, not never
            self._drain_deadline = (
                None if deadline_s is None else time.monotonic() + deadline_s
            )
        self._bounce_pending()
        if self._tel is not None:
            # drain precedes a reap: persist the post-mortem window now
            self._tel.flush_events(force=True)

    def close(self) -> None:
        """Terminal stop: bounce queued requests AND cut active streams so
        no consumer is left blocking on a loop thread that exited."""
        self._shutdown = True
        self._bounce_pending()
        self._cut_parked()
        with self._lock:
            active = list(self._active.values())
            self._active.clear()
        for stream in active:
            stream._finish(cut=True)
        if self._tel is not None:
            self._tel.flush_events(force=True)

    def occupancy_log(self) -> List[Tuple[int, int, Tuple[int, ...]]]:
        """[(step, n_active, request_ids active that step), ...]"""
        return list(self._occupancy)

    def run_on_loop(self, fn, timeout_s: float = 10.0):
        """Run `fn()` on the batcher's loop thread and return its result.

        The loop thread owns the engine (admit/step/release are not
        thread-safe), so anything that must see one consistent engine
        state — cross-replica prefix exports reading the pool, ad-hoc
        engine surgery in tests — goes through here instead of touching
        the engine from a request thread. Calls are drained at the top of
        every loop iteration (the idle loop wakes at least every ~50ms).
        Raises TimeoutError when the loop cannot service the call in
        `timeout_s` and RuntimeError after close()."""
        if threading.current_thread() is self._thread:
            return fn()
        if self._shutdown:
            raise RuntimeError("batcher is closed")
        box: Dict[str, Any] = {}
        done = threading.Event()
        self._loop_calls.append((fn, box, done))
        if not done.wait(timeout_s):
            raise TimeoutError(
                f"batcher loop did not service the call in {timeout_s}s"
            )
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {
                "active": len(self._active),
                "free_slots": len(self._free),
                "queued": self._pending.qsize() + len(self._holdback),
                "steps": self._steps,
                "draining": self._draining,
                "max_batch_size": self.max_batch_size,
            }
        # free-block headroom from paging-aware engines: the autoscaler's
        # third scale signal and the admission gate's observability
        get_stats = getattr(self.engine, "stats", None)
        if get_stats is not None:
            try:
                es = get_stats()
            except Exception:
                es = None
            if isinstance(es, dict):
                for k in ("flight_events", "flight_events_total",
                          "kv_blocks_total", "kv_blocks_free",
                          "kv_blocks_cached", "preemptions", "prefix_hits",
                          "kv_block_bytes", "kv_pool_bytes",
                          "kv_cache_dtype", "attention_impl",
                          "attention_kernel", "platform", "device_kind",
                          "device_bytes_in_use", "device_peak_bytes",
                          "device_bytes_limit",
                          "prefill_chunk_tokens", "prefill_chunks",
                          "chunked_prefills", "prefilling",
                          "prefill_tokens", "prefix_tokens_reused",
                          "kv_exports", "kv_blocks_exported",
                          "kv_imports", "kv_blocks_imported",
                          "kv_tokens_imported", "kv_import_rejects",
                          "spec_k", "spec_steps", "spec_slot_steps",
                          "spec_proposed_tokens", "spec_accepted_tokens",
                          "spec_emitted_tokens", "spec_accept_rate",
                          "spec_tokens_per_step",
                          "weight_version", "weight_swaps",
                          # a hybrid cache's state pool (kv_paging.StatePool)
                          "state_bytes_per_seq", "state_rows_total",
                          "state_rows_free", "state_snapshots",
                          "state_restores", "state_snapshot_evictions",
                          # what the model programs cost the host
                          "decode_steps", "rng_dispatches",
                          "host_transfers"):
                    if k in es:
                        out[k] = es[k]
        return out

    def num_ongoing(self) -> int:
        with self._lock:
            return (len(self._active) + self._pending.qsize()
                    + len(self._holdback) + self._in_hand)

    # -------------------------------------------------------------- internals

    def _bounce_pending(self) -> None:
        """Fail queued-but-unadmitted requests with the retryable drain
        error. Preempted holdback streams already emitted tokens through
        THIS replica, so they cannot be re-routed — they stay parked for
        readmission until the drain deadline cuts them."""
        from .replica import ReplicaDrainingError

        keep = []
        with self._lock:
            while self._holdback:
                item = self._holdback.popleft()
                if item[0].preempted:
                    keep.append(item)
                else:
                    item[0]._finish(error=ReplicaDrainingError())
            self._holdback.extend(keep)
        while True:
            try:
                stream = self._pending.get_nowait()
            except queue.Empty:
                return
            stream._finish(error=ReplicaDrainingError())

    def _cut_parked(self) -> None:
        """Terminal: cut preempted streams still parked (drain deadline or
        close — they can never resume here)."""
        with self._lock:
            parked = list(self._holdback)
            self._holdback.clear()
        for stream, _ in parked:
            stream._finish(cut=True)

    def _admissible(self, stream: GenerationStream,
                    request: Dict[str, Any]) -> bool:
        can = getattr(self.engine, "can_admit", None)
        if can is None:
            return True
        # the verdict for the parked head item is stable until a retire /
        # preemption / admission changes the pool — skip the recheck
        # (prompt hashing + cache scan) on the per-step hot path until then
        rid = stream.request_id
        if (not self._admission_dirty
                and self._admission_verdict is not None
                and self._admission_verdict[0] == rid):
            return self._admission_verdict[1]
        try:
            verdict = bool(can(request))
        except Exception:
            return True  # a broken budget check must not wedge admission
        self._admission_verdict = (rid, verdict)
        self._admission_dirty = False
        return verdict

    def _admit_one(self, stream: GenerationStream,
                   request: Optional[Dict[str, Any]] = None) -> bool:
        """Admit into a free slot; returns False when the request was
        PARKED for lack of KV blocks (the caller must stop gathering this
        round or it would spin on the same head-of-line item)."""
        if request is None:
            request = stream.request
        if stream.cancelled or stream.finished:
            if not stream.finished:
                stream._finish()
            return True
        with self._lock:
            slot = self._free.pop()
            self._active[slot] = stream
        # queue wait ends where ADMISSION STARTS: admit() runs the prefill
        # (possibly a whole long prompt), which must not read as queue time
        t_admit = stream.t_admit = time.monotonic()
        stream._slot = slot
        # rid<->slot correlation for the trace and the timeline: the
        # engine's own spans and "admit" event know the slot, not the
        # request id (`req`: the id the proxy and the handle know it by).
        # The recorder event is named once admission succeeded
        with span("batcher.admit", self._tel, slot=slot,
                  **stream._span_ids()) as admit_span:
            return self._admit_into(slot, stream, request, t_admit,
                                    admit_span)

    def _admit_into(self, slot: int, stream: GenerationStream,
                    request: Dict[str, Any], t_admit: float,
                    admit_span) -> bool:
        try:
            tok, done = self.engine.admit(slot, request)
        except Exception as e:  # noqa: BLE001 — bad request must not kill the loop
            import sys

            kvmod = sys.modules.get("ray_tpu.models.kv_paging")
            if kvmod is not None and isinstance(
                    e, kvmod.InsufficientBlocksError):
                # pool can't cover the prompt right now: park for retry —
                # blocks free as running generations retire (a prompt that
                # can NEVER fit raises ValueError instead and fails here)
                with self._lock:
                    self._active.pop(slot, None)
                    self._free.append(slot)
                    self._holdback.appendleft((stream, request))
                return False
            stream._finish(error=e)
            self._retire(slot)
            return True
        if self._tel is not None:
            self._tel.queue_wait.observe(t_admit - stream.t_enqueue)
            admit_span.event = "readmit" if stream.preempted else "request"
        # a chunked-prefill admission (PagedDecodeEngine with
        # prefill_chunk_tokens) returns no token yet — the prompt streams
        # in chunk-per-step and the first sampled token arrives via step()
        if tok is not None:
            stream._push(tok)
        if done:
            stream._finish()
            self._retire(slot)
        return True

    def _retire(self, slot: int) -> None:
        with self._lock:
            self._active.pop(slot, None)
            self._free.append(slot)
            self._admission_dirty = True  # freed blocks: recheck parked head
        release = getattr(self.engine, "release", None)
        if release is not None:
            release(slot)

    def _gather(self, first_timeout: float) -> None:
        """Admit queued work into free slots: holdback (preempted /
        budget-parked, order preserved) first, then the pending queue —
        blocking up to first_timeout for the first pending item (idle
        parking / coalescing), then taking whatever else is ready."""
        block = first_timeout
        while self._free and not self._shutdown:
            with self._lock:
                item = self._holdback.popleft() if self._holdback else None
                if item is not None:
                    self._in_hand += 1
            if item is None:
                try:
                    stream = self._pending.get(timeout=block)
                except queue.Empty:
                    return
                # counted the instant the pop returns (before the lengthy
                # admissibility check) so a drain poll never sees the
                # stream in neither queue nor batch; counting BEFORE the
                # blocking get would instead report a phantom ongoing
                # request on every idle batcher
                with self._lock:
                    self._in_hand += 1
                item = (stream, stream.request)
            try:
                block = 0.0
                stream, request = item
                if not self._admissible(stream, request):
                    with self._lock:
                        busy = bool(self._active)
                        if busy:
                            # head-of-line wait: blocks free as the running
                            # batch retires; admitting past budget would
                            # only force preemption churn
                            self._holdback.appendleft(item)
                    if busy:
                        return
                    # nothing running to free blocks: admit best-effort so
                    # the request either squeezes in (cache eviction) or
                    # fails with the engine's real error instead of
                    # parking forever
                if not self._admit_one(stream, request):
                    return
                with self._lock:
                    self._admission_dirty = True  # pool changed: recheck
            finally:
                with self._lock:
                    self._in_hand -= 1

    def _absorb_preempted(self) -> None:
        """Park engine-evicted generations (stream stays open) at the head
        of the admission line for recompute-on-readmit."""
        take = getattr(self.engine, "take_preempted", None)
        if take is None:
            return
        try:
            evicted = take() or ()
        except Exception:
            return
        for slot, parked in reversed(list(evicted)):
            with self._lock:
                stream = self._active.pop(slot, None)
                if slot not in self._free:
                    self._free.append(slot)
            if stream is None:
                continue
            if stream.cancelled:
                stream._finish()
                continue
            stream.preempted = True
            # queue wait for the READMISSION measures from this re-park,
            # not the original submit (that span is request latency's job)
            stream.t_enqueue = time.monotonic()
            if self._tel is not None:
                self._tel.preemptions.inc()
            with self._lock:
                self._holdback.appendleft((stream, parked))
                self._admission_dirty = True  # blocks freed by the eviction

    def _run_loop_calls(self) -> None:
        while self._loop_calls:
            try:
                fn, box, done = self._loop_calls.popleft()
            except IndexError:
                return
            try:
                box["result"] = fn()
            except Exception as e:  # noqa: BLE001 — caller re-raises
                box["error"] = e
            done.set()

    def _loop(self) -> None:
        while not self._shutdown:
            # one pass = one span; a pass that steps the engine carries
            # `slots`, an idle pass (parked on the queue) does not
            with span("batcher.iteration") as it_span:
                self._iteration(it_span)
        # loop exit (close()): fail parked cross-thread calls, or their
        # callers would block until their timeout
        while self._loop_calls:
            try:
                _, box, done = self._loop_calls.popleft()
            except IndexError:
                break
            box["error"] = RuntimeError("batcher loop exited")
            done.set()

    def _iteration(self, it_span) -> None:
        self._run_loop_calls()
        if not self._active:
            if self._draining:
                self._bounce_pending()
                # preempted generations parked in holdback are
                # in-flight work: keep readmitting them until done or
                # the drain deadline cuts them
                with self._lock:
                    has_parked = bool(self._holdback)
                if has_parked:
                    self._gather(first_timeout=0.0)
                if (self._draining and self._drain_deadline is not None
                        and time.monotonic() >= self._drain_deadline):
                    self._cut_parked()
                if not self._active:
                    time.sleep(0.01)
                    return
            # idle: park on the queue; once the first request lands,
            # hold the batch open for the coalescing window so
            # near-simultaneous requests share the first step
            self._gather(first_timeout=0.05)
            if self._active and self.batch_wait_timeout_s > 0:
                deadline = time.monotonic() + self.batch_wait_timeout_s
                while (len(self._free) > 0
                       and time.monotonic() < deadline):
                    self._gather(
                        first_timeout=max(0.0, deadline - time.monotonic())
                    )
                    if not self._free:
                        break
            if not self._active:
                return
        else:
            # running batch: admit whatever is queued, no waiting
            self._gather(first_timeout=0.0)

        with self._lock:
            slots = sorted(self._active)
            ids = tuple(self._active[s].request_id for s in slots)
        if not slots:
            return
        it_span.set(slots=len(slots))
        try:
            results = self.engine.step(slots)
        except Exception as e:  # noqa: BLE001 — engine fault fails the batch
            if self._tel is not None:
                if self._rec is not None:
                    self._rec.record(
                        "engine_fault",
                        args={"error": repr(e)[:200],
                              "slots": tuple(slots)})
                # a faulting engine is exactly when the post-mortem
                # window matters: get it off this process NOW
                self._tel.flush_events(force=True)
            # discard any preemptions staged before the fault: their
            # streams are errored with everyone else's below, and a
            # stale parked entry must never hijack the slot's NEXT
            # stream on a later successful step
            take = getattr(self.engine, "take_preempted", None)
            if take is not None:
                try:
                    take()
                except Exception:
                    pass
            for slot in slots:
                stream = self._active.get(slot)
                if stream is not None:
                    stream._finish(error=e)
                self._retire(slot)
            return
        # slots the engine preempted mid-step are absent from results:
        # park their streams (still open) for recompute-on-readmit
        self._absorb_preempted()
        self._steps += 1
        self._occupancy.append((self._steps, len(slots), ids))
        if self._tel is not None and self._steps % 8 == 1:
            # cheap occupancy/pool gauges (attribute reads, no
            # engine.stats() call — that walks the prefix-cache trie),
            # refreshed every 8th step: gauge freshness at sub-step
            # granularity buys nothing, the hot loop's budget does
            self._tel.occupancy.set(len(slots))
            alloc = getattr(self.engine, "allocator", None)
            if alloc is not None:
                self._tel.kv_util.set(
                    (alloc.num_usable - alloc.num_free)
                    / max(1, alloc.num_usable))
            if getattr(self.engine, "speculative_k", 0):
                self._tel.spec_accept.set(
                    self.engine.spec_accepted
                    / max(1, self.engine.spec_proposed))
            self._tel.flush_events()
        with span("batcher.emit"):
            for slot, (tok, done) in results.items():
                stream = self._active.get(slot)
                if stream is None:
                    continue
                if stream.cancelled:
                    stream._finish()
                    self._retire(slot)
                    continue
                # multi-token retirement: a speculative verify step may
                # emit a burst of accepted tokens — push each one so the
                # stream (and its SSE consumer) sees them all in order.
                # Only LISTS fan out: a tuple is one atomic item — the
                # (token, logprob) pair a logprobs=True engine emits
                for t in (tok if isinstance(tok, list) else (tok,)):
                    stream._push(t)
                if done:
                    stream._finish()
                    self._retire(slot)
        # drain deadline: cut whatever is still running or parked
        if (self._draining and self._drain_deadline is not None
                and time.monotonic() >= self._drain_deadline):
            with self._lock:
                leftover = dict(self._active)
            for slot, stream in leftover.items():
                stream._finish(cut=True)
                self._retire(slot)
            self._cut_parked()
