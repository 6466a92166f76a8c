"""HTTP ingress: asyncio HTTP/1.1 server over longest-prefix routes ->
ingress DeploymentHandles.

Reference parity: serve/_private/http_proxy.py:320 (HTTPProxy /
HTTPProxyActor, uvicorn+starlette). Rebuilt on an asyncio server (VERDICT
r2 item 8 — the previous stdlib ThreadingHTTPServer held one THREAD per
in-flight request, so 100 slow streaming consumers pinned 100 threads):
  - persistent connections (HTTP/1.1 keep-alive): one coroutine per
    connection loops over requests, bounded by a connection cap (excess
    connections get 503 + Retry-After)
  - request-lifecycle deadlines (slow-loris defense): the request head must
    arrive within `keep_alive_timeout_s` (covers idle keep-alive waits AND
    header trickle), the body within `read_timeout_s`; expiry sends 408 and
    reaps the connection — well-behaved neighbors are untouched because a
    slow client only ever parks its own coroutine
  - hard size limits: head > max_header_bytes -> 431, body >
    max_body_bytes -> 413 (both content-length and chunked)
  - chunked request bodies are decoded (uvicorn parity); chunked responses
    unchanged
  - replica calls run on a BOUNDED thread pool (they block on the handle),
    with 503 + Retry-After backpressure once the queued-call cap is hit or
    the deployment is unavailable (draining, no replicas, circuit breaker
    open); response STREAMING happens on the event loop with backpressure
    (`await writer.drain()`)
  - longest-prefix route match (an app at "/app" serves "/app/anything");
    the matched remainder + query string ride along for handlers that want
    them (pass_request=True deployments receive a Request object)
  - JSON bodies parse to Python values; other content types pass through as
    raw bytes
  - responses: bytes -> application/octet-stream, str -> text/plain,
    StreamingResponse -> chunked transfer, anything else -> {"result": ...}
    JSON (the v1 wire shape, kept stable)
  - per-proxy configurable request timeout -> 504 on expiry

All limits/deadlines default from the `serve_http_*` config flags
(_private/config.py, RAY_TPU_* env-overridable) and can be set per proxy via
constructor kwargs or the set_limits() actor method.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, Iterable, Optional
from urllib.parse import parse_qs, urlsplit

from . import telemetry

# Replica-call threads; streaming holds none. KNOWN LIMIT: the pool bounds
# concurrent REPLICA CALLS, so >pool-size slow calls queue (and their
# wait_for clocks include queue time) — overload degrades to 503s/504s,
# which is deliberate backpressure where the old thread-per-request server
# grew unboundedly instead.
_CALL_POOL_SIZE = 16

_REASONS = {
    200: "OK", 400: "Bad Request", 404: "Not Found",
    408: "Request Timeout", 411: "Length Required",
    413: "Payload Too Large", 431: "Request Header Fields Too Large",
    500: "Internal Server Error", 503: "Service Unavailable",
    504: "Gateway Timeout",
}


@dataclass
class Request:
    """What a deployment sees when it asks for the raw request."""

    method: str
    path: str            # full request path
    route: str           # matched route prefix
    subpath: str         # path remainder after the route
    query: Dict[str, Any]
    headers: Dict[str, str]
    body: Any            # parsed JSON or raw bytes


@dataclass
class StreamingResponse:
    """Chunked-transfer response: iterable of str/bytes chunks.

    buffered=True (default): the iterable is materialized at construction
    (generators included) so the response pickles across the replica->proxy
    actor boundary — actor results are single messages; the streaming
    happens proxy->client.

    buffered=False: the chunks are still being PRODUCED (e.g. a
    ContinuousBatcher generation). The replica registers the live stream
    and hands the proxy a ReplicaStreamHandle; the proxy pulls chunks with
    stream_next() and forwards each to the client as it arrives — true
    incremental delivery, one chunked frame per chunk."""

    chunks: Iterable[Any]
    content_type: str = "text/plain; charset=utf-8"
    buffered: bool = True

    def __post_init__(self):
        if self.buffered:
            self.chunks = list(self.chunks)


def _sse_encode(item) -> str:
    """Default SSE payload encoding: strings pass through, everything else
    is JSON — str() of a dict/list would emit python repr (single quotes),
    which standard SSE consumers (OpenAI clients included) cannot parse."""
    return item if isinstance(item, str) else json.dumps(item)


class _SSEStream:
    """Format a pull-style token stream (GenerationStream) as server-sent
    events while PRESERVING its long-poll next_batch surface, so replica
    stream_next pulls stay batched and timeout-bounded. The terminal event
    is `data: [DONE]` — preceded by `event: cut` when the generation was
    truncated at a drain deadline."""

    def __init__(self, inner, encode=_sse_encode):
        self._inner = inner
        self._encode = encode

    def next_batch(self, max_items: int, wait_s: float):
        items, done = self._inner.next_batch(max_items, wait_s)
        out = [f"data: {self._encode(i)}\n\n" for i in items]
        if done:
            if getattr(self._inner, "cut", False):
                out.append("event: cut\ndata: [DONE]\n\n")
            else:
                out.append("data: [DONE]\n\n")
        return out, done

    def cancel(self):
        cancel = getattr(self._inner, "cancel", None)
        if cancel is not None:
            cancel()


def sse_stream(stream, encode=_sse_encode) -> StreamingResponse:
    """Wrap a token stream as a non-buffered text/event-stream response:
    every token becomes its own SSE `data:` event delivered per-token over
    chunked transfer — `data: <payload>\\n\\n` frames ending with the
    `data: [DONE]\\n\\n` sentinel (the OpenAI wire shape; dict/list items
    are JSON-encoded by default). `stream` is ideally pull-style (has
    next_batch, e.g. ContinuousBatcher.submit()'s GenerationStream); plain
    iterables work but pull one chunk per stream_next round-trip."""
    if hasattr(stream, "next_batch"):
        chunks: Any = _SSEStream(stream, encode)
    else:
        def _gen():
            for item in stream:
                yield f"data: {encode(item)}\n\n"
            yield "data: [DONE]\n\n"

        chunks = _gen()
    return StreamingResponse(
        chunks, content_type="text/event-stream", buffered=False
    )


@dataclass
class Response:
    """Explicit-status response from a handler (ingress handlers use it for
    201/4xx etc.). body follows the normal result contract: str -> text,
    bytes -> octet-stream, anything else -> JSON."""

    status: int
    body: Any = None
    content_type: Optional[str] = None


@dataclass
class _Route:
    prefix: str
    handle: Any
    pass_request: bool = False


class _HttpReject(Exception):
    """Internal: abort request processing with this status; the connection
    closes after the reply (its stream state is unknown/hostile)."""

    def __init__(self, status: int, message: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.status = status
        self.message = message
        self.retry_after_s = retry_after_s


def _parse_body(raw: bytes, ctype: str):
    ctype = (ctype or "").split(";")[0].strip()
    if not raw:
        return None
    if ctype in ("application/json", "", "text/json"):
        try:
            return json.loads(raw)
        except json.JSONDecodeError:
            pass
    if ctype.startswith("text/"):
        return raw.decode(errors="replace")
    return raw  # binary passthrough


class HTTPProxyActor:
    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 8000,
        request_timeout_s: float = 60.0,
        keep_alive_timeout_s: Optional[float] = None,
        read_timeout_s: Optional[float] = None,
        max_header_bytes: Optional[int] = None,
        max_body_bytes: Optional[int] = None,
        max_connections: Optional[int] = None,
        max_queued_calls: Optional[int] = None,
        retry_after_s: Optional[float] = None,
    ):
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        def _knob(value, flag):
            return cfg.get(flag) if value is None else value

        self.host = host
        self.port = port
        self.request_timeout_s = request_timeout_s
        self.keep_alive_timeout_s = float(
            _knob(keep_alive_timeout_s, "serve_http_keep_alive_timeout_s"))
        self.read_timeout_s = float(
            _knob(read_timeout_s, "serve_http_read_timeout_s"))
        self.max_header_bytes = int(
            _knob(max_header_bytes, "serve_http_max_header_bytes"))
        self.max_body_bytes = int(
            _knob(max_body_bytes, "serve_http_max_body_bytes"))
        self.max_connections = int(
            _knob(max_connections, "serve_http_max_connections"))
        self.max_queued_calls = int(
            _knob(max_queued_calls, "serve_http_max_queued_calls"))
        self.retry_after_s = float(
            _knob(retry_after_s, "serve_http_retry_after_s"))
        self.routes: Dict[str, _Route] = {}
        # per the serve_telemetry flag in THIS process: None = a request
        # still gets its id, but no clock is read for it here
        self._tel = telemetry.get_telemetry()
        self._nconn = 0
        self._ncalls = 0  # replica calls submitted but not yet finished
        # replica calls block a pool thread; the loop never blocks
        self._pool = ThreadPoolExecutor(
            max_workers=_CALL_POOL_SIZE, thread_name_prefix="ingress-call"
        )
        # /metrics gets its OWN single thread: a saturated call pool (the
        # incident) must not make the proxy unobservable — scrapes never
        # compete with replica calls, and the export's bounded head
        # round-trip bounds this thread
        self._scrape_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="metrics-scrape"
        )
        self._loop = asyncio.new_event_loop()
        started = threading.Event()

        # stream limit gates readuntil/readline and is FIXED at server
        # construction; keep it above the header cap so the explicit 431
        # check fires first (set_limits clamps later raises against it)
        self._stream_limit = max(2 * self.max_header_bytes, 256 * 1024)

        def _run():
            asyncio.set_event_loop(self._loop)
            self._server = self._loop.run_until_complete(
                asyncio.start_server(
                    self._on_client, host=host, port=port,
                    limit=self._stream_limit,
                )
            )
            self.port = self._server.sockets[0].getsockname()[1]
            started.set()
            self._loop.run_forever()

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()
        if not started.wait(10):
            raise RuntimeError("ingress server failed to start")

    # ---------------------------------------------------------- http plane

    def _match(self, path: str) -> Optional[_Route]:
        """Longest-prefix routing (reference: route_prefix semantics)."""
        best = None
        for prefix, route in self.routes.items():
            if path == prefix or path.startswith(
                prefix if prefix.endswith("/") else prefix + "/"
            ) or prefix == "/":
                if best is None or len(prefix) > len(best.prefix):
                    best = route
        return best

    async def _read_body(self, reader, headers: Dict[str, str]) -> bytes:
        """Request body under the read deadline and size cap. Raises
        _HttpReject (408 slow body / 413 oversized / 400 malformed)."""
        deadline = self._loop.time() + self.read_timeout_s

        async def _timed(coro):
            remaining = deadline - self._loop.time()
            if remaining <= 0:
                raise _HttpReject(408, "request body read timed out")
            try:
                return await asyncio.wait_for(coro, timeout=remaining)
            except asyncio.TimeoutError:
                raise _HttpReject(408, "request body read timed out")

        if "chunked" in headers.get("transfer-encoding", "").lower():
            # chunked request decoding (uvicorn/h11 parity): size-line,
            # data+CRLF, ... , 0-size line, optional trailers, blank line
            raw = bytearray()
            while True:
                line = await _timed(reader.readline())
                try:
                    size = int(line.split(b";")[0].strip() or b"0", 16)
                except ValueError:
                    raise _HttpReject(400, "malformed chunk size")
                if size == 0:
                    while True:  # drain trailers up to the blank line
                        tl = await _timed(reader.readline())
                        if tl in (b"\r\n", b"\n", b""):
                            break
                    return bytes(raw)
                if len(raw) + size > self.max_body_bytes:
                    raise _HttpReject(413, "request body too large")
                chunk = await _timed(reader.readexactly(size + 2))
                if chunk[-2:] != b"\r\n":
                    raise _HttpReject(400, "malformed chunk terminator")
                raw += chunk[:-2]
        try:
            n = int(headers.get("content-length", 0) or 0)
        except ValueError:
            raise _HttpReject(400, "malformed content-length")
        if n > self.max_body_bytes:
            raise _HttpReject(413, "request body too large")
        return await _timed(reader.readexactly(n)) if n else b""

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter):
        """One coroutine per connection; loops over keep-alive requests.
        Every read is under a deadline, so hostile clients (slow-loris,
        half-open sockets) cost one bounded coroutine, never a thread."""
        if self._nconn >= self.max_connections:
            try:
                await self._reply(
                    writer, 503, "application/json",
                    b'{"error": "connection limit reached"}',
                    extra_headers=self._retry_after(), close=True,
                )
            except Exception:
                pass
            finally:
                try:
                    writer.close()
                except Exception:
                    pass
            return
        self._nconn += 1
        try:
            while True:
                try:
                    head = await asyncio.wait_for(
                        reader.readuntil(b"\r\n\r\n"),
                        timeout=self.keep_alive_timeout_s,
                    )
                except asyncio.TimeoutError:
                    # idle keep-alive OR trickling headers (slow-loris):
                    # 408 best-effort, then reap the connection
                    try:
                        await self._reply(writer, 408, "application/json",
                                          b'{"error": "request timed out"}',
                                          close=True)
                    except Exception:
                        pass
                    return
                except (asyncio.IncompleteReadError, ConnectionError):
                    return
                except asyncio.LimitOverrunError:
                    await self._reply(writer, 431, "application/json",
                                      b'{"error": "headers too large"}',
                                      close=True)
                    return
                if len(head) > self.max_header_bytes:
                    await self._reply(writer, 431, "application/json",
                                      b'{"error": "headers too large"}',
                                      close=True)
                    return
                lines = head.decode("latin1").split("\r\n")
                try:
                    method, target, version = lines[0].split(" ", 2)
                except ValueError:
                    await self._reply(writer, 400, "application/json",
                                      b'{"error": "bad request line"}',
                                      close=True)
                    return
                headers = {}
                for ln in lines[1:]:
                    if not ln:
                        continue
                    k, _, v = ln.partition(":")
                    headers[k.strip().lower()] = v.strip()
                try:
                    raw = await self._read_body(reader, headers)
                except _HttpReject as rej:
                    await self._reply(
                        writer, rej.status, "application/json",
                        json.dumps({"error": rej.message}).encode(),
                        extra_headers=self._retry_after(rej.retry_after_s),
                        close=True,
                    )
                    return
                except (asyncio.IncompleteReadError, ConnectionError):
                    return  # client hung up mid-body: nothing to answer
                except ValueError:
                    # stream-limit overrun inside a chunked body (readline
                    # raises ValueError on LimitOverrunError)
                    await self._reply(writer, 400, "application/json",
                                      b'{"error": "malformed request body"}',
                                      close=True)
                    return
                keep_alive = (
                    headers.get("connection", "").lower() != "close"
                    and version.upper() != "HTTP/1.0"
                )
                # stage 1, `proxy.recv`: the request is read whole. Its id
                # is the client's `x-request-id` or minted here
                ctx = telemetry.new_request(headers.get("x-request-id", ""))
                if self._tel is not None:
                    ctx.t_recv = time.time()
                await self._dispatch(writer, method, target, headers, raw,
                                     ctx)
                if not keep_alive:
                    return
        except (ConnectionError, asyncio.CancelledError):
            pass
        finally:
            self._nconn -= 1
            try:
                writer.close()
            except Exception:
                pass

    def _retry_after(self, retry_after_s: Optional[float] = None):
        secs = self.retry_after_s if retry_after_s is None else retry_after_s
        return {"Retry-After": str(max(1, int(round(secs))))}

    async def _reply(self, writer, status: int, ctype: str, payload: bytes,
                     extra_headers: Optional[Dict[str, str]] = None,
                     close: bool = False):
        reason = _REASONS.get(status, "")
        lines = [
            f"HTTP/1.1 {status} {reason}",
            f"Content-Type: {ctype}",
            f"Content-Length: {len(payload)}",
        ]
        if status in (503,) and extra_headers is None:
            extra_headers = self._retry_after()
        for k, v in (extra_headers or {}).items():
            lines.append(f"{k}: {v}")
        if close:
            lines.append("Connection: close")
        writer.write(("\r\n".join(lines) + "\r\n\r\n").encode("latin1"))
        writer.write(payload)
        await writer.drain()
        self._record_request(status)

    def _record_request(self, status: int) -> None:
        """The proxy's ONE flight-recorder event of a routed request,
        `proxy.request`, at the first bytes it writes back — stage 8
        (`proxy.first_write`) of a live stream, else the reply, an error's
        included: `dur` from stage 1, args `req`, `status` and, as far as
        the request got, `dispatch_us` (1 -> 2), `answer_us` (2 -> the
        handle's reply at the pool thread), `pull_us` (the round trip of
        the first `stream_next` that delivered). `return_to_client`, from
        that pull's reply to here, is the proxy's own share of the way
        back on one clock: serve_request_stage_s. Later writes of the
        same request record nothing."""
        ctx = telemetry.current_request()
        if ctx is None or ctx.status is not None:
            return
        ctx.status = status
        tel = self._tel
        if tel is None or ctx.t_recv is None:
            return
        now = time.time()
        if ctx.t_pulled is not None:
            tel.observe_stage("return_to_client", now - ctx.t_pulled)
        if tel.recorder is None:
            return
        args = {"req": ctx.rid, "status": status}
        if ctx.t_call is not None:
            args["dispatch_us"] = int((ctx.t_call - ctx.t_recv) * 1e6)
        if ctx.answer_s is not None:
            args["answer_us"] = int(ctx.answer_s * 1e6)
        if ctx.pull_s is not None:
            args["pull_us"] = int(ctx.pull_s * 1e6)
        tel.recorder.record("proxy.request", dur=now - ctx.t_recv, args=args)
        tel.flush_events()  # throttled delta push to the head

    async def _reply_chunked(self, writer, resp: StreamingResponse):
        writer.write(
            f"HTTP/1.1 200 OK\r\nContent-Type: {resp.content_type}\r\n"
            "Transfer-Encoding: chunked\r\n\r\n".encode("latin1")
        )
        self._record_request(200)
        for chunk in resp.chunks:
            data = chunk.encode() if isinstance(chunk, str) else bytes(chunk)
            if not data:
                continue
            writer.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
            # backpressure: a slow client parks THIS coroutine only
            await writer.drain()
        writer.write(b"0\r\n\r\n")
        await writer.drain()

    def _call_route(self, route: _Route, args: tuple, ctx):
        """Blocking replica call; runs on the bounded pool. Returns the
        DeploymentResponse too: a streaming result must be pulled from the
        exact replica that holds the live stream (replica affinity). `ctx`
        (the request's clock) is this thread's current request for the
        call, which is how the handle finds it: no handle signature knows
        of it."""
        timed = ctx.t_recv is not None
        with telemetry.request_scope(ctx):
            if timed:
                ctx.t_call = time.time()  # stage 2, `proxy.call`
            resp = route.handle.remote(*args)
            result = resp.result(timeout_s=self.request_timeout_s)
        if timed:
            ctx.answer_s = time.time() - ctx.t_call
        return resp, result

    async def _pool_call(self, fn, timeout: float):
        """Submit a blocking callable to the call pool with the shared
        occupancy accounting: _ncalls mirrors POOL-THREAD occupancy, so
        the slot is released only by the future's done callback — never
        by the timeout path (a timed-out call's thread keeps blocking,
        and the saturation cap must keep counting it). The shield means
        wait_for abandons the WAIT on timeout, not the thread. One
        helper, so the invariant cannot drift between dispatch sites."""
        self._ncalls += 1
        fut = self._loop.run_in_executor(self._pool, fn)

        def _done(f):
            self._ncalls -= 1
            if not f.cancelled():
                f.exception()  # retrieved: a post-timeout error must not warn

        fut.add_done_callback(_done)
        return await asyncio.wait_for(asyncio.shield(fut), timeout=timeout)

    def _export_metrics(self) -> bytes:
        """Cluster-wide Prometheus text (runs on the call pool: the merge
        pulls every process's snapshot from the head over the worker
        socket). The head round-trip is BOUNDED — a wedged head must cost
        one failed scrape, never a permanently parked pool thread."""
        from ray_tpu.util.metrics import export_prometheus

        return export_prometheus(timeout=20.0).encode()

    async def _dispatch(self, writer, method: str, target: str,
                        headers: Dict[str, str], raw: bytes, ctx):
        parts = urlsplit(target)
        path = parts.path.rstrip("/") or "/"
        if method == "GET" and path == "/metrics":
            # Prometheus scrape endpoint (reference: the per-node metrics
            # agent's exposition port). Reserved ahead of route matching —
            # an app mounted at "/" cannot shadow the scrape — and served
            # off a DEDICATED thread, outside the call pool and its
            # saturation gate: the scrape must keep answering during the
            # very incidents (pool saturation, SSE floods) the metrics
            # exist to explain. Bounded by the export's own head timeout.
            fut = self._loop.run_in_executor(
                self._scrape_pool, self._export_metrics)
            try:
                payload = await asyncio.wait_for(fut, timeout=30.0)
            except Exception as e:  # noqa: BLE001
                await self._reply(writer, 500, "application/json",
                                  json.dumps({"error": repr(e)}).encode())
                return
            await self._reply(
                writer, 200,
                "text/plain; version=0.0.4; charset=utf-8", payload,
            )
            return
        route = self._match(path)
        if route is None:
            await self._reply(writer, 404, "application/json",
                              b'{"error": "no app at this route"}')
            return
        body = _parse_body(raw, headers.get("content-type", "")) if method not in (
            "GET", "DELETE") else None
        if route.pass_request:
            arg = Request(
                method=method,
                path=parts.path,
                route=route.prefix,
                subpath=path[len(route.prefix):].lstrip("/"),
                query={k: v[0] if len(v) == 1 else v
                       for k, v in parse_qs(parts.query).items()},
                headers=headers,
                body=body,
            )
            args = (arg,)
        else:
            args = () if body is None else (body,)
        # a routed request: `ctx` is this task's current request from here
        # to its last byte (what everything below reads it by)
        with telemetry.request_scope(ctx):
            await self._serve_route(writer, route, args)

    async def _serve_route(self, writer, route: _Route, args: tuple):
        from .handle import DeploymentUnavailableError
        from .replica import ReplicaDrainingError

        ctx = telemetry.current_request()

        if self._ncalls >= self.max_queued_calls:
            # saturation backpressure AHEAD of the pool: queueing more work
            # would only grow tail latency past the 504 deadline anyway
            await self._reply(
                writer, 503, "application/json",
                b'{"error": "proxy saturated"}',
                extra_headers=self._retry_after(),
            )
            return
        try:
            dresp, result = await self._pool_call(
                lambda: self._call_route(route, args, ctx),
                self.request_timeout_s + 5.0,
            )
        except asyncio.TimeoutError:
            await self._reply(writer, 504, "application/json",
                              b'{"error": "request timed out"}')
            return
        except DeploymentUnavailableError as e:
            # draining / no replicas / circuit breaker open: transient by
            # construction — tell the client when to come back
            await self._reply(
                writer, 503, "application/json",
                json.dumps({"error": str(e)}).encode(),
                extra_headers=self._retry_after(
                    getattr(e, "retry_after_s", None)),
            )
            return
        except ReplicaDrainingError as e:
            # handle retries exhausted against a still-draining set
            await self._reply(
                writer, 503, "application/json",
                json.dumps({"error": str(e)}).encode(),
                extra_headers=self._retry_after(),
            )
            return
        except Exception as e:  # noqa: BLE001
            await self._reply(writer, 500, "application/json",
                              json.dumps({"error": repr(e)}).encode())
            return
        from .replica import ReplicaStreamHandle

        if isinstance(result, ReplicaStreamHandle):
            await self._stream_replica_pull(writer, route, args, dresp, result)
            return
        await self._write_result(writer, result)

    async def _write_result(self, writer, result):
        status = 200
        bare = isinstance(result, Response)  # Response bodies serialize bare
        ctype_override = None
        if bare:
            status = result.status
            ctype_override = result.content_type
            result = result.body
        try:
            if ctype_override is not None:
                data = (
                    result.encode() if isinstance(result, str)
                    else bytes(result) if isinstance(result, (bytes, bytearray, memoryview))
                    else json.dumps(result).encode()
                )
                await self._reply(writer, status, ctype_override, data)
                return
            if isinstance(result, StreamingResponse):
                await self._reply_chunked(writer, result)
                return
            if isinstance(result, (bytes, bytearray, memoryview)):
                await self._reply(writer, status, "application/octet-stream",
                                  bytes(result))
                return
            if isinstance(result, str):
                await self._reply(writer, status, "text/plain; charset=utf-8",
                                  result.encode())
                return
            # Response bodies serialize bare; plain results keep the stable
            # v1 {"result": ...} wire shape
            payload = json.dumps(result if bare else {"result": result}).encode()
        except ConnectionError:
            raise
        except Exception as e:  # a non-JSON-able result must 500, not drop
            await self._reply(writer, 500, "application/json",
                              json.dumps({"error": repr(e)}).encode())
            return
        await self._reply(writer, status, "application/json", payload)

    # ------------------------------------------------------ live streaming

    def _stream_cancel(self, replica, stream_id: int) -> None:
        """Fire-and-forget: tell the replica its consumer went away so the
        batcher can retire the slot instead of generating into the void."""
        try:
            replica.stream_cancel.remote(stream_id)
        except Exception:
            pass

    async def _stream_replica_pull(self, writer, route: _Route, args: tuple,
                                   dresp, sh) -> None:
        """Forward a live replica stream: pull chunk batches with
        stream_next (long-poll on the replica) and write each chunk as its
        own chunked frame with backpressure.

        The response head is written only after the FIRST successful pull:
        a generation that was never admitted (its submit raced a drain —
        stream_next raises ReplicaDrainingError) is re-dispatched ONCE
        against the refreshed replica set, or answered 503 — never a dead
        200. Once streaming has started, errors can only end the
        connection (chunked truncation); the replica-side drain cut keeps
        that path bounded."""
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        from ray_tpu.exceptions import (
            ActorDiedError,
            ActorUnavailableError,
            GetTimeoutError,
            WorkerCrashedError,
        )

        from .handle import DeploymentUnavailableError
        from .replica import ReplicaDrainingError, ReplicaStreamHandle

        max_chunks = int(cfg.serve_stream_pull_max_chunks)
        pull_wait = float(cfg.serve_stream_pull_wait_s)
        replica = getattr(dresp, "replica", None)
        head_written = False
        retried = False
        idle_deadline = self._loop.time() + self.request_timeout_s

        # the way back is timed until the first chunk is written (stage
        # 8), and not after: nothing is added per token or per later pull
        ctx = telemetry.current_request()
        timed = ctx.t_recv is not None

        def _pull(rep, sid, timed):
            import ray_tpu

            t0 = time.time() if timed else 0.0
            out = ray_tpu.get(
                rep.stream_next.remote(sid, max_chunks, pull_wait),
                timeout=self.request_timeout_s,
            )
            if timed:  # overwritten until a pull delivers
                ctx.t_pulled = time.time()
                ctx.pull_s = ctx.t_pulled - t0
            return out

        while True:
            if replica is None:
                if not head_written:
                    await self._reply(
                        writer, 500, "application/json",
                        b'{"error": "stream lost its serving replica"}')
                return
            try:
                rep, sid = replica, sh.stream_id
                chunks, done = await self._pool_call(
                    lambda: _pull(rep, sid, timed),
                    self.request_timeout_s + 5.0
                )
            except (asyncio.TimeoutError, GetTimeoutError):
                # GetTimeoutError is the common spelling (the blocking
                # ray_tpu.get inside _pull times out first); the asyncio
                # guard only fires if the pool thread itself wedges
                if not head_written:
                    await self._reply(writer, 504, "application/json",
                                      b'{"error": "stream pull timed out"}')
                self._stream_cancel(replica, sh.stream_id)
                writer.close()
                return
            except (ReplicaDrainingError, ActorDiedError,
                    ActorUnavailableError, WorkerCrashedError) as e:
                # the generation was never admitted (drain raced the call)
                # or the replica died before the first token
                if head_written:
                    writer.close()  # mid-stream: truncate, client retries
                    return
                if retried:
                    await self._reply(
                        writer, 503, "application/json",
                        json.dumps({"error": str(e)}).encode(),
                        extra_headers=self._retry_after())
                    return
                retried = True
                try:
                    # same occupancy accounting as every other pool
                    # submission: the retry call can block a pool thread
                    # for up to request_timeout_s and must be visible to
                    # the saturation gate
                    dresp, result = await self._pool_call(
                        lambda: self._call_route(route, args, ctx),
                        self.request_timeout_s + 5.0,
                    )
                except asyncio.TimeoutError:
                    await self._reply(writer, 504, "application/json",
                                      b'{"error": "request timed out"}')
                    return
                except (DeploymentUnavailableError, ReplicaDrainingError) as e2:
                    await self._reply(
                        writer, 503, "application/json",
                        json.dumps({"error": str(e2)}).encode(),
                        extra_headers=self._retry_after(
                            getattr(e2, "retry_after_s", None)))
                    return
                except Exception as e2:  # noqa: BLE001
                    await self._reply(writer, 500, "application/json",
                                      json.dumps({"error": repr(e2)}).encode())
                    return
                if not isinstance(result, ReplicaStreamHandle):
                    await self._write_result(writer, result)
                    return
                replica = getattr(dresp, "replica", None)
                sh = result
                idle_deadline = self._loop.time() + self.request_timeout_s
                continue
            except Exception as e:  # noqa: BLE001 — producer raised
                if not head_written:
                    await self._reply(writer, 500, "application/json",
                                      json.dumps({"error": repr(e)}).encode())
                else:
                    writer.close()
                return
            try:
                if not head_written:
                    writer.write(
                        f"HTTP/1.1 200 OK\r\nContent-Type: {sh.content_type}"
                        "\r\nTransfer-Encoding: chunked\r\n\r\n".encode("latin1")
                    )
                    head_written = True
                for chunk in chunks:
                    data = (chunk.encode() if isinstance(chunk, str)
                            else bytes(chunk))
                    if not data:
                        continue
                    writer.write(f"{len(data):x}\r\n".encode() + data + b"\r\n")
                # backpressure: a slow client parks THIS coroutine only
                await writer.drain()
                if timed and chunks:
                    timed = False
                    self._record_request(200)  # stage 8, `proxy.first_write`
                if done:
                    writer.write(b"0\r\n\r\n")
                    await writer.drain()
                    return
            except (ConnectionError, asyncio.CancelledError):
                self._stream_cancel(replica, sh.stream_id)
                raise
            now = self._loop.time()
            if chunks:
                idle_deadline = now + self.request_timeout_s
            elif now >= idle_deadline:
                self._stream_cancel(replica, sh.stream_id)
                if not head_written:
                    # nothing sent yet (e.g. parked behind a full batch
                    # past the deadline): a proper 504, not a dead socket
                    await self._reply(writer, 504, "application/json",
                                      b'{"error": "stream timed out"}')
                    return
                # mid-stream there is no status code left — cut the
                # connection (chunked truncation tells the client)
                writer.close()
                return

    # ---------------------------------------------------------- actor API

    def flush_telemetry(self) -> bool:
        """Force-push this proxy's flight recorder (its `proxy.request`
        events) and metrics to the head: dump_timeline()'s fan-out."""
        return telemetry.flush_to_head()

    def ready(self):
        host = self.host
        if host in ("0.0.0.0", ""):
            # advertise a ROUTABLE address, not the wildcard bind (fleet
            # proxies feed proxy_addresses() -> load balancers off-box)
            from .._private.head import _advertise_host

            host = _advertise_host(host)
        return {"host": host, "port": self.port}

    def set_route(
        self, route_prefix: str, deployment_name: str, pass_request: bool = False
    ):
        from .handle import DeploymentHandle

        prefix = route_prefix.rstrip("/") or "/"
        self.routes[prefix] = _Route(
            prefix=prefix,
            handle=DeploymentHandle(deployment_name),
            pass_request=pass_request,
        )
        return True

    def remove_route(self, route_prefix: str):
        self.routes.pop(route_prefix.rstrip("/") or "/", None)
        return True

    def set_request_timeout(self, timeout_s: float):
        self.request_timeout_s = float(timeout_s)
        return True

    def set_limits(self, **limits):
        """Tune the hardening knobs on a live proxy (tests, operators).
        Accepts any of: keep_alive_timeout_s, read_timeout_s,
        max_header_bytes, max_body_bytes, max_connections,
        max_queued_calls, retry_after_s, request_timeout_s."""
        allowed = {
            "keep_alive_timeout_s": float, "read_timeout_s": float,
            "max_header_bytes": int, "max_body_bytes": int,
            "max_connections": int, "max_queued_calls": int,
            "retry_after_s": float, "request_timeout_s": float,
        }
        for k, v in limits.items():
            if k not in allowed:
                raise ValueError(f"unknown proxy limit {k!r}")
            v = allowed[k](v)
            if k == "max_header_bytes":
                # the asyncio stream limit is fixed at construction:
                # readuntil would LimitOverrunError below a larger cap, so
                # clamp instead of silently advertising headroom that the
                # transport can't deliver (raising it for real needs a new
                # proxy constructed with the bigger cap)
                v = min(v, self._stream_limit // 2)
            setattr(self, k, v)
        return True

    def limits(self) -> Dict[str, Any]:
        return {
            "keep_alive_timeout_s": self.keep_alive_timeout_s,
            "read_timeout_s": self.read_timeout_s,
            "max_header_bytes": self.max_header_bytes,
            "max_body_bytes": self.max_body_bytes,
            "max_connections": self.max_connections,
            "max_queued_calls": self.max_queued_calls,
            "retry_after_s": self.retry_after_s,
            "request_timeout_s": self.request_timeout_s,
        }

    def stop(self):
        def _stop():
            try:
                self._server.close()
            except Exception:
                pass
            self._loop.stop()

        try:
            self._loop.call_soon_threadsafe(_stop)
        except RuntimeError:
            pass
        return True
