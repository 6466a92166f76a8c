"""DeploymentHandle: the Python-native ingress to a deployment.

Reference parity: serve/handle.py (DeploymentHandle/DeploymentResponse) with
the router's power-of-two-choices replica selection (serve/_private/router.py:370)
done handle-side over locally-tracked in-flight counts.

Robustness layer (request-lifecycle hardening):
  - replica-death / replica-draining retries re-route with CAPPED
    EXPONENTIAL BACKOFF + JITTER instead of hot-looping against a replica
    set the controller is still rebuilding
  - a per-deployment CIRCUIT BREAKER trips after consecutive failures and
    fails calls fast with DeploymentUnavailableError (the HTTP proxy maps
    it to 503 + Retry-After) while the controller restarts replicas; a
    half-open probe closes it again once a call succeeds
"""

from __future__ import annotations

import random
import threading
import time
from collections import deque
from typing import Any, Optional

from .telemetry import outgoing_request, request_scope

CONTROLLER_NAME = "SERVE_CONTROLLER"


class DeploymentUnavailableError(RuntimeError):
    """The deployment cannot take requests right now (no live replicas,
    draining for removal, or its circuit breaker is open). Transient by
    design: callers should retry after `retry_after_s`; the HTTP proxy
    translates it to 503 + Retry-After."""

    def __init__(self, deployment_name: str, reason: str,
                 retry_after_s: float = 1.0):
        super().__init__(
            f"deployment {deployment_name!r} unavailable: {reason}"
        )
        self.deployment_name = deployment_name
        self.reason = reason
        self.retry_after_s = retry_after_s


class _CircuitBreaker:
    """Per-deployment failure gate (reference intent: the router's backoff
    on UNAVAILABLE replicas; shape follows the classic closed -> open ->
    half-open machine). Thread-safe: proxy pool threads share one breaker
    per deployment."""

    def __init__(self, failure_threshold: int, reset_s: float):
        self.failure_threshold = max(1, int(failure_threshold))
        self.reset_s = float(reset_s)
        self._lock = threading.Lock()
        self._consecutive = 0
        self._opened_at: Optional[float] = None
        self._probing_since: Optional[float] = None

    def allow(self) -> bool:
        """True if a call may proceed (closed, or half-open probe slot)."""
        with self._lock:
            if self._opened_at is None:
                return True
            now = time.time()
            if now - self._opened_at < self.reset_s:
                return False
            # half-open: one probe at a time — but a probe slot EXPIRES
            # after reset_s so a caller that never reports back (fire-and-
            # forget .remote() with no .result()) can't brick the breaker
            if (self._probing_since is None
                    or now - self._probing_since >= self.reset_s):
                self._probing_since = now
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            self._opened_at = None
            self._probing_since = None

    def record_failure(self) -> None:
        with self._lock:
            self._consecutive += 1
            if self._probing_since is not None:
                # failed probe re-opens a fresh window
                self._opened_at = time.time()
                self._probing_since = None
            elif (self._opened_at is None
                  and self._consecutive >= self.failure_threshold):
                self._opened_at = time.time()

    def release_probe(self) -> None:
        """Give back a probe slot without judging the deployment either way
        (e.g. the probe call timed out caller-side): the next allow() may
        probe again immediately."""
        with self._lock:
            if self._probing_since is not None:
                self._probing_since = None
                if self._opened_at is not None:
                    # make the next probe eligible now, not reset_s from now
                    self._opened_at = time.time() - self.reset_s

    @property
    def is_open(self) -> bool:
        with self._lock:
            return self._opened_at is not None

    def seconds_until_probe(self) -> float:
        with self._lock:
            if self._opened_at is None:
                return 0.0
            return max(0.0, self.reset_s - (time.time() - self._opened_at))


_breakers: dict = {}
_breakers_lock = threading.Lock()


def get_breaker(deployment_name: str) -> _CircuitBreaker:
    """One breaker per (process, deployment) — handles are minted freely
    (attribute access, options(), unpickling), so breaker state must not
    live on the handle itself."""
    with _breakers_lock:
        b = _breakers.get(deployment_name)
        if b is None:
            from ray_tpu._private.config import GLOBAL_CONFIG as cfg

            b = _breakers[deployment_name] = _CircuitBreaker(
                cfg.serve_breaker_failure_threshold, cfg.serve_breaker_reset_s
            )
        return b


def _reset_breakers() -> None:
    """Test/shutdown hook: forget breaker state between serve sessions."""
    with _breakers_lock:
        _breakers.clear()


def _backoff_s(attempt: int) -> float:
    """Capped exponential backoff with full jitter (attempt is 0-based)."""
    from ray_tpu._private.config import GLOBAL_CONFIG as cfg

    cap = min(
        float(cfg.serve_handle_backoff_max_s),
        float(cfg.serve_handle_backoff_base_s) * (2 ** attempt),
    )
    return random.uniform(cap / 2, cap)


def _retryable_errors() -> tuple:
    from ray_tpu.exceptions import (
        ActorDiedError,
        ActorUnavailableError,
        WorkerCrashedError,
    )

    from .replica import ReplicaDrainingError

    return (ActorDiedError, ActorUnavailableError, WorkerCrashedError,
            ReplicaDrainingError)


class DeploymentResponse:
    def __init__(self, ref, handle=None, call=None, ctx=None):
        self._ref = ref
        self._handle = handle
        self._call = call  # (args, kwargs) for replica-death retry
        # the telemetry.RequestClock the call went out with: a retry of
        # the same logical call keeps the request's id
        self._ctx = ctx
        self.retries = 0   # re-route attempts this response consumed
        # the replica actor that served this call: streaming results
        # (ReplicaStreamHandle) must be pulled from the replica that holds
        # the live stream, not re-routed
        self.replica = None

    def result(self, timeout_s: Optional[float] = None):
        import ray_tpu

        from ray_tpu.exceptions import GetTimeoutError, PlaneRequestTimeout

        breaker = (
            get_breaker(self._handle.deployment_name)
            if self._handle is not None else None
        )
        # timeout_s bounds the WHOLE logical call — backoff sleeps and
        # every retry's get() draw down one shared deadline, so a caller
        # asking for 5s never blocks (attempts+1) x 5s
        deadline = (
            None if timeout_s is None else time.monotonic() + timeout_s
        )

        def _remaining():
            return (
                None if deadline is None else deadline - time.monotonic()
            )

        try:
            out = ray_tpu.get(self._ref, timeout=timeout_s)
            if breaker is not None:
                breaker.record_success()
            return out
        except GetTimeoutError:
            # no verdict on the deployment — give any probe slot back so
            # the breaker can't wedge half-open
            if breaker is not None:
                breaker.release_probe()
            raise
        except PlaneRequestTimeout:
            # a plane blip, NOT a replica verdict: the data plane lost the
            # request/reply pair (black-holed link, wedged head handler) —
            # the replica may well have computed the answer. Retry the SAME
            # replica once (idempotent re-execution / head-side rid dedup
            # make the duplicate safe), then fall into the re-route path.
            # Never feeds the breaker: an unresponsive plane says nothing
            # about the deployment's health.
            if breaker is not None:
                breaker.release_probe()
            if (self._handle is None or self._call is None
                    or self.replica is None):
                raise
            args, kwargs = self._call
            try:
                self.retries += 1
                retry = self.replica.handle_request.remote(
                    self._handle.method_name, args, kwargs,
                    model_id=self._handle.multiplexed_model_id,
                    ctx=self._ctx,
                )
                out = ray_tpu.get(retry, timeout=_remaining())
                if breaker is not None:
                    breaker.record_success()
                return out
            except (PlaneRequestTimeout,) + _retryable_errors() as e:
                # same replica unreachable twice (or genuinely dead): now
                # re-route like a replica failure
                return self._reroute(e, breaker, _remaining)
        except _retryable_errors() as first_exc:
            # the chosen replica died mid-call or was draining (e.g. torn
            # down by a redeploy that raced this request): re-route
            # immediately — death is a verdict, unlike a plane blip above
            if self._handle is None or self._call is None:
                raise
            return self._reroute(first_exc, breaker, _remaining)
        except Exception:
            # the replica answered with a user-code error: the deployment
            # is SERVING — close/feed the breaker as a success so an open
            # breaker's probe that reaches user code recovers the circuit
            if breaker is not None:
                breaker.record_success()
            raise

    def _reroute(self, first_exc, breaker, _remaining):
        """Re-route the logical call against a refreshed replica set with
        spaced, bounded attempts (reference: the router retries system
        failures transparently, serve/_private/router.py — plus backoff so
        a crash-looping deployment isn't hammered). The breaker samples the
        LOGICAL call once at the end — a transient drain race retried to
        success must not march the breaker toward open, and a final failure
        that is merely a plane timeout releases the probe instead of
        recording a failure (plane blips never trip the circuit)."""
        import ray_tpu

        from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        from ray_tpu.exceptions import GetTimeoutError, PlaneRequestTimeout

        args, kwargs = self._call
        attempts = max(0, int(cfg.serve_handle_retry_attempts))
        last_exc = first_exc
        for attempt in range(attempts):
            left = _remaining()
            if left is not None and left <= 0:
                break
            pause = _backoff_s(attempt)
            time.sleep(pause if left is None else min(pause, left))
            self.retries += 1
            try:
                self._handle._refresh(force=True)
                with request_scope(self._ctx):
                    retry = self._handle.remote(*args, **kwargs)
                out = ray_tpu.get(retry.ref, timeout=_remaining())
                self.replica = retry.replica
                breaker.record_success()
                return out
            except GetTimeoutError:
                breaker.release_probe()
                raise
            except (PlaneRequestTimeout,) + _retryable_errors() as e:
                last_exc = e
            except DeploymentUnavailableError:
                # breaker opened (or replicas gone) while we retried:
                # fail fast — the proxy turns this into 503
                raise
        if isinstance(last_exc, PlaneRequestTimeout):
            breaker.release_probe()
        else:
            breaker.record_failure()
        raise last_exc

    @property
    def ref(self):
        return self._ref

    def iter_stream(self, timeout_s: Optional[float] = None,
                    pull_max_chunks: Optional[int] = None,
                    pull_wait_s: Optional[float] = None):
        """Iterate a streaming result without going through HTTP: resolves
        the call to its ReplicaStreamHandle, then pulls chunks from the
        serving replica as they are produced. Raises TypeError if the
        deployment returned a non-streaming result."""
        import ray_tpu

        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        from .replica import ReplicaStreamHandle

        sh = self.result(timeout_s=timeout_s)
        if not isinstance(sh, ReplicaStreamHandle):
            raise TypeError(
                f"deployment returned {type(sh).__name__}, not a stream — "
                "iter_stream needs a non-buffered StreamingResponse"
            )
        n = int(cfg.serve_stream_pull_max_chunks
                if pull_max_chunks is None else pull_max_chunks)
        wait = float(cfg.serve_stream_pull_wait_s
                     if pull_wait_s is None else pull_wait_s)
        done = False
        try:
            # timeout_s bounds PROGRESS, not just one pull: a request
            # parked behind a full batch yields empty pulls forever — the
            # idle deadline turns that into GetTimeoutError like any other
            # stalled call
            idle_deadline = (
                None if timeout_s is None
                else time.monotonic() + float(timeout_s)
            )
            while True:
                chunks, done = ray_tpu.get(
                    self.replica.stream_next.remote(sh.stream_id, n, wait),
                    timeout=timeout_s,
                )
                yield from chunks
                if done:
                    return
                if chunks:
                    idle_deadline = (
                        None if timeout_s is None
                        else time.monotonic() + float(timeout_s)
                    )
                elif (idle_deadline is not None
                      and time.monotonic() >= idle_deadline):
                    from ray_tpu.exceptions import GetTimeoutError

                    raise GetTimeoutError(
                        f"stream produced nothing for {timeout_s}s"
                    )
        finally:
            if not done:
                # consumer stopped early (break / GC): free the replica's
                # decode slot instead of generating into the void
                try:
                    self.replica.stream_cancel.remote(sh.stream_id)
                except Exception:
                    pass


class DeploymentHandle:
    def __init__(
        self,
        deployment_name: str,
        method_name: str = "__call__",
        multiplexed_model_id: str = "",
    ):
        self.deployment_name = deployment_name
        self.method_name = method_name
        self.multiplexed_model_id = multiplexed_model_id
        self._replicas = []
        self._refreshed = 0.0
        self._inflight: deque = deque()  # (replica_index, ref)
        self._counts: dict = {}
        self._seen_version = -1  # last adopted ReplicaWatcher.version
        self._deployment_draining = False
        # model affinity: id -> replica actor_id last used (keeps a loaded
        # model's traffic on the replica that holds it — serve/multiplex.py)
        self._model_affinity: dict = {}

    # -- pickling: drop live state; reconnect lazily on the other side
    def __reduce__(self):
        return (
            DeploymentHandle,
            (self.deployment_name, self.method_name, self.multiplexed_model_id),
        )

    def options(
        self,
        *,
        method_name: Optional[str] = None,
        multiplexed_model_id: Optional[str] = None,
    ) -> "DeploymentHandle":
        h = DeploymentHandle(
            self.deployment_name,
            method_name or self.method_name,
            multiplexed_model_id
            if multiplexed_model_id is not None
            else self.multiplexed_model_id,
        )
        h._model_affinity = self._model_affinity  # shared map across options()
        return h

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        # method handles keep the multiplexed model id and SHARE the
        # affinity map — h.options(multiplexed_model_id=...).generate must
        # route/identify exactly like h itself
        h = DeploymentHandle(self.deployment_name, name, self.multiplexed_model_id)
        h._model_affinity = self._model_affinity
        return h

    # ------------------------------------------------------------- routing

    def _controller(self):
        import ray_tpu

        return ray_tpu.get_actor(CONTROLLER_NAME)

    def _adopt(self, replicas):
        self._replicas = list(replicas)
        self._refreshed = time.time()
        self._counts = {i: self._counts.get(i, 0) for i in range(len(self._replicas))}

    def _refresh(self, force: bool = False):
        """Adopt the shared long-poll watcher's replica snapshot when it has
        a newer one (reference: handle-side LongPollClient updating the
        router, serve/_private/long_poll.py:68); only fall back to pulling
        from the controller when the push pipeline isn't delivering."""
        from .long_poll import get_watcher

        watcher = get_watcher(self.deployment_name)
        if watcher.version != self._seen_version and watcher.replicas is not None:
            self._seen_version = watcher.version
            self._deployment_draining = watcher.draining
            self._adopt(watcher.replicas)
            # a just-landed push is at least as fresh as a pull started
            # after it — even on the force (error-retry) path
            return
        if watcher.replicas is not None:
            self._deployment_draining = watcher.draining
        # push healthy -> the long TTL is safe; push broken/unproven -> the
        # 1s pull keeps routing at most one interval stale
        ttl = 30.0 if watcher.healthy() else 1.0
        if not force and time.time() - self._refreshed < ttl and self._replicas:
            return
        import ray_tpu

        try:
            self._adopt(
                ray_tpu.get(
                    self._controller().get_replicas.remote(self.deployment_name)
                )
            )
        except ValueError:
            # the controller no longer knows this deployment (retired, or
            # this pull raced its removal broadcast): treat as drained-to-
            # nothing so callers get DeploymentUnavailableError, never a
            # raw controller error
            self._deployment_draining = True
            self._adopt([])

    def _prune(self):
        import ray_tpu

        still = deque()
        while self._inflight:
            idx, ref = self._inflight.popleft()
            ready, _ = ray_tpu.wait([ref], timeout=0)
            if ready:
                self._counts[idx] = max(0, self._counts.get(idx, 1) - 1)
            else:
                still.append((idx, ref))
        self._inflight = still

    def _prefix_idx(self, hint: str) -> Optional[int]:
        """Index of the replica the prefix digest advertises for `hint`,
        or None (no digest entry, or that replica left the set)."""
        from .long_poll import get_prefix_watcher

        entry = get_prefix_watcher(self.deployment_name).digest.get(hint)
        if not entry:
            return None
        aid = entry[0]
        for i, r in enumerate(self._replicas):
            if getattr(r, "_actor_id", None) == aid:
                return i
        return None

    def _pick_replica(self, hint: str = "") -> int:
        n = len(self._replicas)
        if n == 1:
            return 0
        model_id = self.multiplexed_model_id
        if model_id:
            # affinity first: keep a loaded model's traffic on its replica
            want = self._model_affinity.get(model_id)
            for i, r in enumerate(self._replicas):
                if getattr(r, "_actor_id", None) == want:
                    return i
        a, b = random.sample(range(n), 2)
        pick = a if self._counts.get(a, 0) <= self._counts.get(b, 0) else b
        if hint:
            # prefix affinity: ties break toward the replica advertising
            # the longest cached chain for this prompt's hint — but only
            # while its queue stays within max_skew of the two-choices
            # floor. Load wins when depths diverge: a hot prefix cannot
            # pin a replica (the hint is a bounded-weight tie-break, not
            # a hard route).
            idx = self._prefix_idx(hint)
            if idx is not None:
                from ray_tpu._private.config import GLOBAL_CONFIG as cfg

                floor = min(self._counts.get(a, 0), self._counts.get(b, 0))
                skew = int(cfg.serve_prefix_affinity_max_skew)
                if self._counts.get(idx, 0) <= floor + skew:
                    return idx
        return pick

    def remote(self, *args, **kwargs) -> DeploymentResponse:
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        breaker = get_breaker(self.deployment_name)
        if not breaker.allow():
            # fail FAST while the controller restarts replicas — no routing,
            # no remote call, no hot loop
            raise DeploymentUnavailableError(
                self.deployment_name, "circuit breaker open",
                retry_after_s=max(
                    breaker.seconds_until_probe(), cfg.serve_http_retry_after_s
                ),
            )
        self._refresh()
        self._prune()
        hint = ""
        if cfg.serve_prefix_affinity:
            # one hint per call, shared by both attempts: proxy traffic
            # arrives as a body dict in args[0], handle traffic as
            # tokens= kwargs — request_hint covers both shapes
            from .kv_transfer import request_hint

            hint = request_hint(args, kwargs)
        ctx = outgoing_request()
        for attempt in range(2):
            # re-checked every attempt: a force-refresh after a failed
            # submit may have adopted an empty/draining set. Failing here is
            # a breaker FAILURE (not just a fast error): it re-opens the
            # window cleanly when this call held the half-open probe slot,
            # so the slot can never leak.
            if self._deployment_draining:
                breaker.record_failure()
                raise DeploymentUnavailableError(
                    self.deployment_name, "deployment is draining",
                    retry_after_s=cfg.serve_http_retry_after_s,
                )
            if not self._replicas:
                breaker.record_failure()
                raise DeploymentUnavailableError(
                    self.deployment_name, "no live replicas",
                    retry_after_s=cfg.serve_http_retry_after_s,
                )
            idx = self._pick_replica(hint)
            try:
                ref = self._replicas[idx].handle_request.remote(
                    self.method_name, args, kwargs,
                    model_id=self.multiplexed_model_id, ctx=ctx,
                )
                break
            except Exception:
                if attempt == 1:
                    raise
                self._refresh(force=True)  # replica set changed under us
        if self.multiplexed_model_id:
            self._model_affinity[self.multiplexed_model_id] = getattr(
                self._replicas[idx], "_actor_id", None
            )
        self._counts[idx] = self._counts.get(idx, 0) + 1
        self._inflight.append((idx, ref))
        resp = DeploymentResponse(ref, handle=self, call=(args, kwargs),
                                  ctx=ctx)
        resp.replica = self._replicas[idx]
        return resp
