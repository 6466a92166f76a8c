"""Replica actor: hosts one copy of a deployment's callable.

Reference parity: serve/_private/replica.py:382 (RayServeReplica — wraps the
user callable, tracks ongoing requests for autoscaling stats) plus the
graceful-drain protocol (reference: replica.py perform_graceful_shutdown —
a replica slated for removal stops ACCEPTING requests but finishes the ones
already in flight; the controller only reaps it once it reports idle or the
drain deadline passes).

Token streaming: a handler that returns a NON-buffered StreamingResponse
(chunks still being produced — e.g. a ContinuousBatcher generation) cannot
ship the chunks in the actor result (results are single pickled messages).
Instead the replica registers the live stream and returns a
ReplicaStreamHandle; the proxy (or a handle caller via
DeploymentResponse.iter_stream) pulls chunks with stream_next() as they are
produced. Open streams count as ongoing work for drain/autoscaling.
"""

from __future__ import annotations

import inspect
import itertools
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from . import telemetry


class ReplicaDrainingError(RuntimeError):
    """Raised by a draining replica for NEW requests. No user code ran, so
    the handle retries it transparently against a refreshed replica set
    (the drained replica has already been dropped
    from the published set; this error only hits requests that raced the
    drain broadcast)."""

    def __init__(self, deployment_name: str = ""):
        super().__init__(
            f"replica of {deployment_name!r} is draining and accepts no new "
            "requests"
        )
        self.deployment_name = deployment_name


@dataclass
class ReplicaStreamHandle:
    """Marker a replica returns in place of a live (non-buffered) stream:
    the consumer pulls the chunks from the SAME replica via stream_next."""

    stream_id: int
    content_type: str = "text/plain; charset=utf-8"


class _IterStream:
    """Adapter giving plain iterables the GenerationStream pull surface.
    next() can block arbitrarily (generators have no timeout), so generic
    lazy streams pull ONE chunk per call — queue-backed GenerationStreams
    use their native batched long-poll instead."""

    def __init__(self, it):
        self._it = iter(it)

    def next_batch(self, max_items: int, wait_s: float):
        try:
            return [next(self._it)], False
        except StopIteration:
            return [], True

    def cancel(self):
        close = getattr(self._it, "close", None)
        if close is not None:
            close()


class Replica:
    def __init__(self, deployment_name: str, func_or_class, init_args, init_kwargs):
        self.deployment_name = deployment_name
        self._ongoing = 0
        self._total = 0
        self._draining = False
        self._lock = threading.Lock()
        self._streams: Dict[int, Any] = {}
        self._stream_ids = itertools.count(1)
        # monotonic fold of per-batcher cumulative counters across batcher
        # replacement (see _mono_sum): retired batchers' last-seen values
        # accumulate in _mono_base instead of vanishing from stats()
        self._mono_base: Dict[str, int] = {}
        self._mono_seen: Dict[str, Dict[int, int]] = {}
        # sid -> why it was closed early (reaped/cancelled): a later pull
        # must surface the truncation, not fake a clean completion
        self._closed_early: Dict[int, str] = {}
        # telemetry context BEFORE user __init__: engines/batchers built
        # there pick up deployment/replica default tags on their metrics
        # (one replica actor per worker process, so process scope is right)
        try:
            from . import telemetry

            telemetry.set_context(
                deployment=deployment_name, replica=f"pid-{os.getpid()}"
            )
        except Exception:
            pass
        if inspect.isclass(func_or_class):
            self.callable = func_or_class(*init_args, **init_kwargs)
            self.is_function = False
        else:
            self.callable = func_or_class
            self.is_function = True

    def ready(self):
        return True

    def pid(self) -> int:
        """This replica's worker process id (chaos tests SIGKILL it)."""
        return os.getpid()

    def handle_request(self, method_name: str, args, kwargs,
                       model_id: str = "", ctx=None):
        """`ctx` is the caller's telemetry.RequestClock (the request's id
        and the proxy's stamps), carried beside `model_id`; a caller that
        sends none (kv_transfer's peer calls, a direct caller) is served
        the same."""
        if ctx is not None:
            ctx.received()  # stage 3, before anything else
        with self._lock:
            if self._draining:
                raise ReplicaDrainingError(self.deployment_name)
            self._ongoing += 1
            self._total += 1
        if model_id:
            from .multiplex import _set_model_id

            _set_model_id(model_id)
        try:
            with telemetry.request_scope(ctx):
                if self.is_function or method_name == "__call__":
                    result = self.callable(*args, **kwargs)
                else:
                    result = getattr(self.callable, method_name)(
                        *args, **kwargs)
            return self._maybe_register_stream(result)
        finally:
            if model_id:
                from .multiplex import _set_model_id

                _set_model_id("")
            with self._lock:
                self._ongoing -= 1

    # ------------------------------------------------------------- streaming

    def _maybe_register_stream(self, result):
        from .http_proxy import StreamingResponse

        if not (isinstance(result, StreamingResponse) and not result.buffered):
            return result
        chunks = result.chunks
        if not hasattr(chunks, "next_batch"):
            chunks = _IterStream(chunks)
        self._reap_idle_streams()
        with self._lock:
            sid = next(self._stream_ids)
            self._streams[sid] = [chunks, time.monotonic()]
        return ReplicaStreamHandle(sid, result.content_type)

    def _reap_idle_streams(self) -> None:
        """Drop streams nobody has pulled for serve_stream_idle_reap_s: an
        abandoned consumer (handle caller that never iterated, proxy that
        errored without cancelling) must not count as ongoing work forever.
        Runs on every registry touch — including num_ongoing/stats, which
        the drain loop and autoscaler poll."""
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        ttl = float(cfg.serve_stream_idle_reap_s)
        now = time.monotonic()
        with self._lock:
            dead = [sid for sid, (_, ts) in self._streams.items()
                    if now - ts > ttl]
            victims = [(sid, self._streams.pop(sid)[0]) for sid in dead]
            for sid in dead:
                self._mark_closed_early(sid, "idle-reaped")
        for _, stream in victims:
            cancel = getattr(stream, "cancel", None)
            if cancel is not None:
                try:
                    cancel()
                except Exception:
                    pass

    def stream_next(self, stream_id: int, max_items: int = 64,
                    wait_s: float = 0.25) -> Tuple[List[Any], bool]:
        """Long-poll pull: up to max_items chunks from a registered stream,
        waiting up to wait_s for the first. Returns (chunks, done); the
        stream unregisters itself on done. Unknown ids are already-finished
        streams: ([], True)."""
        self._reap_idle_streams()
        with self._lock:
            entry = self._streams.get(stream_id)
            if entry is not None:
                entry[1] = time.monotonic()
            reason = self._closed_early.get(stream_id)
        if entry is None:
            if reason is not None:
                # a truncated stream must never read as a clean completion
                raise RuntimeError(
                    f"stream {stream_id} was {reason} before its consumer "
                    "finished pulling"
                )
            return [], True
        stream = entry[0]
        try:
            items, done = stream.next_batch(max_items, wait_s)
        except Exception:
            with self._lock:
                self._streams.pop(stream_id, None)
            raise
        if done:
            with self._lock:
                self._streams.pop(stream_id, None)
        else:
            with self._lock:
                if stream_id in self._streams:
                    self._streams[stream_id][1] = time.monotonic()
        return items, done

    def _mark_closed_early(self, sid: int, reason: str) -> None:
        """Record why a stream went away (bounded; caller holds the lock)."""
        self._closed_early[sid] = reason
        while len(self._closed_early) > 512:
            self._closed_early.pop(next(iter(self._closed_early)))

    def stream_cancel(self, stream_id: int) -> bool:
        """Consumer disconnected: drop the stream and tell its producer."""
        with self._lock:
            entry = self._streams.pop(stream_id, None)
            if entry is not None:
                self._mark_closed_early(stream_id, "cancelled")
        if entry is None:
            return False
        stream = entry[0]
        cancel = getattr(stream, "cancel", None)
        if cancel is not None:
            try:
                cancel()
            except Exception:
                pass
        return True

    # ------------------------------------------------------------- draining

    def _drainables(self) -> List[Any]:
        """Drainable batchers hanging off the user callable (@serve.batch
        queues, ContinuousBatchers) — the single discovery point shared by
        the drain path and the autoscaling stats."""
        attrs = getattr(self.callable, "__dict__", None) or {}
        return [v for v in list(attrs.values())
                if getattr(v, "_serve_drainable", False)]

    def prepare_to_drain(self, deadline_s: Optional[float] = None) -> int:
        """Stop accepting new requests; returns the in-flight count at the
        moment the gate closed (controller sequencing: drain -> reap).

        deadline_s (the deployment's graceful_shutdown_timeout_s) is
        propagated to any drainable batchers hanging off the user callable:
        they bounce queued-but-unadmitted work for handle-side retry and
        cut still-running generations at the deadline."""
        with self._lock:
            self._draining = True
            ongoing = self._ongoing + len(self._streams)
        for v in self._drainables():
            try:
                v.drain(deadline_s)
            except Exception:
                pass
        # a draining replica is about to be reaped: persist its flight
        # recorder on the head while the process still exists
        self.flush_telemetry()
        return ongoing

    def num_ongoing(self) -> int:
        self._reap_idle_streams()
        with self._lock:
            return self._ongoing + len(self._streams)

    def _mono_sum(self, key: str, values: Dict[int, int]) -> int:
        """Monotonic sum of a per-batcher CUMULATIVE counter across batcher
        replacement. A user callable that rebuilds its batcher (engine
        swap, recovery) would otherwise make the replica-level sum drop to
        the new batcher's fresh count — losing attribution mid-diff for
        anything comparing before/after (the multi-replica prefix-hit
        test diffs prefill_tokens exactly that way). A batcher that
        vanishes — or whose id is reused by a NEW batcher, detectable as
        the counter going backwards — folds its last-seen value into a
        retained base."""
        base = self._mono_base.get(key, 0)
        seen = self._mono_seen.setdefault(key, {})
        for bid, last in list(seen.items()):
            cur = values.get(bid)
            if cur is None or cur < last:
                base += last
                del seen[bid]
        seen.update(values)
        self._mono_base[key] = base
        return base + sum(values.values())

    _MONO_KEYS = ("prefill_tokens", "prefix_tokens_reused",
                  "kv_blocks_exported", "kv_blocks_imported",
                  "kv_tokens_imported", "kv_import_rejects")

    def _batcher_stats(self) -> Dict[str, int]:
        """Aggregate generation-slot occupancy over any drainable batchers
        hanging off the user callable (serve.ContinuousBatcher instances) —
        the decode-aware autoscaling signal: a generation-bound replica is
        saturated when its SLOTS are, long before queued-call counts say so."""
        slots = active = queued = 0
        kv_total = kv_free = preempt = kv_bytes = 0
        spec_k = spec_slot_steps = spec_proposed = 0
        spec_accepted = spec_emitted = 0
        chunk_tokens = prefilling = chunked_prefills = prefill_chunks = 0
        mono_cur: Dict[str, Dict[int, int]] = {k: {} for k in self._MONO_KEYS}
        for v in self._drainables():
            get_stats = getattr(v, "stats", None)
            if get_stats is None:
                continue
            try:
                s = get_stats()
            except Exception:
                continue
            if not isinstance(s, dict) or "max_batch_size" not in s:
                continue
            for k in self._MONO_KEYS:
                if k in s:
                    mono_cur[k][id(v)] = int(s[k])
            slots += int(s.get("max_batch_size", 0))
            active += int(s.get("active", 0))
            queued += int(s.get("queued", 0))
            # paged-KV headroom (ContinuousBatchers over a
            # PagedDecodeEngine): block saturation is the third scale-up
            # signal — a replica can have free SLOTS yet no blocks left
            # for long prompts, which queue depth never shows
            kv_total += int(s.get("kv_blocks_total", 0))
            # prefix-cache-held blocks are HEADROOM, not load: they evict
            # on demand, so counting them as used would ratchet a warm
            # idle deployment up to max_replicas and block downscaling
            kv_free += (int(s.get("kv_blocks_free", 0))
                        + int(s.get("kv_blocks_cached", 0)))
            preempt += int(s.get("preemptions", 0))
            # capacity in BYTES too: an int8 pool reports ~2x the blocks
            # of a bf16 pool for the same HBM, and this is what makes
            # that doubling auditable from the controller side — the
            # engine's figure includes the null block, so it reconciles
            # exactly with a serve_kv_pool_mb budget
            kv_bytes += int(s.get("kv_pool_bytes", 0))
            # speculative decoding: aggregate the raw counters and derive
            # the replica-level rates from their sums, so a fleet of
            # batchers reports one honest accept rate instead of an
            # average of per-batcher averages
            spec_k = max(spec_k, int(s.get("spec_k", 0)))
            spec_slot_steps += int(s.get("spec_slot_steps", 0))
            spec_proposed += int(s.get("spec_proposed_tokens", 0))
            spec_accepted += int(s.get("spec_accepted_tokens", 0))
            spec_emitted += int(s.get("spec_emitted_tokens", 0))
            # chunked prefill: slots mid-prompt right now (load the
            # controller can see next to slot/block saturation), how many
            # admissions streamed chunked, and total chunk dispatches
            chunk_tokens = max(chunk_tokens,
                               int(s.get("prefill_chunk_tokens", 0)))
            prefilling += int(s.get("prefilling", 0))
            chunked_prefills += int(s.get("chunked_prefills", 0))
            prefill_chunks += int(s.get("prefill_chunks", 0))
        out = {"batch_slots": slots, "batch_active": active,
               "batch_queued": queued, "kv_blocks_total": kv_total,
               "kv_blocks_free": kv_free, "kv_preemptions": preempt,
               "kv_pool_bytes": kv_bytes,
               "prefill_chunk_tokens": chunk_tokens,
               "prefilling": prefilling,
               "chunked_prefills": chunked_prefills,
               "prefill_chunks": prefill_chunks,
               "spec_k": spec_k,
               "spec_accept_rate": round(
                   spec_accepted / max(1, spec_proposed), 4),
               "spec_tokens_per_step": round(
                   spec_emitted / max(1, spec_slot_steps), 2)}
        # monotonic across batcher replacement — see _mono_sum
        for k in self._MONO_KEYS:
            out[k] = self._mono_sum(k, mono_cur[k])
        return out

    def stats(self) -> Dict[str, Any]:
        self._reap_idle_streams()
        out = {
            "ongoing": self._ongoing + len(self._streams),
            "streams": len(self._streams),
            "total": self._total,
            "draining": self._draining,
            "ts": time.time(),
        }
        out.update(self._batcher_stats())
        try:
            # bulk-plane transfer health in THIS replica process (weight
            # pulls, big args/returns): pulls/bytes by path — fleet work
            # reads it off replica stats without a metrics scrape
            from ray_tpu.util import metrics as _bm

            pulls = _bm.local_counter_by_tag("bulk_plane_pulls_total", "path")
            if pulls:
                out["bulk_pulls_by_path"] = pulls
                out["bulk_bytes_by_path"] = _bm.local_counter_by_tag(
                    "bulk_plane_bytes_total", "path"
                )
            # cluster-wide KV plane: recompute fallbacks + wire bytes by
            # direction in THIS replica process (serve/kv_transfer.py)
            kvfb = _bm.local_counter_by_tag(
                "kv_transfer_fallbacks_total", "path"
            )
            if kvfb:
                out["kv_transfer_fallbacks_total"] = int(sum(kvfb.values()))
            kvb = _bm.local_counter_by_tag(
                "serve_kv_transfer_bytes_total", "direction"
            )
            if kvb:
                out["kv_transfer_bytes_by_direction"] = kvb
        except Exception:
            pass
        # transfer managers hanging off the user callable advertise their
        # remote-pull figures and the prefix digest affinity routing feeds
        # on (controller harvests "prefix_digest" from these stats)
        attrs = getattr(self.callable, "__dict__", None) or {}
        digest: Dict[str, int] = {}
        for v in list(attrs.values()):
            if not getattr(v, "_serve_kv_transfer", False):
                continue
            try:
                out.update(v.stats())
                digest.update(v.digest())
            except Exception:
                pass
        if digest:
            out["prefix_digest"] = digest
        try:
            from . import telemetry

            tel = telemetry.get_telemetry()
            if tel is not None and tel.recorder is not None:
                # fallback only: an engine's own figures (forwarded via
                # the batcher passthrough) stay authoritative — e.g. an
                # engine built with telemetry=False must report 0 even
                # while the process singleton records for others
                out.setdefault("flight_events", len(tel.recorder))
                out.setdefault("flight_events_total", tel.recorder.total)
        except Exception:
            pass
        return out

    # ------------------------------------------------------------ telemetry

    def flush_telemetry(self) -> bool:
        """Force-push this replica's flight recorder (and metrics) to the
        head — dump_timeline()'s fan-out target, also called on drain."""
        return telemetry.flush_to_head()

    def check_health(self) -> bool:
        user_check = getattr(self.callable, "check_health", None)
        if user_check is not None and not self.is_function:
            user_check()
        # piggyback the throttled telemetry pushes on the controller's
        # periodic health probe: an idle replica's final observations (a
        # finished request's counters) and its last N recorder events
        # reach the head without a dedicated poller
        try:
            from ray_tpu.util import metrics

            from . import telemetry

            telemetry.flush_events()
            metrics.pump()
        except Exception:
            pass
        return True
