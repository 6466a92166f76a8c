"""OpenAI-compatible completions API over the paged serving stack.

`OpenAICompletions` is a serve deployment that loads a model-hub bundle
(models/hub: safetensors checkpoint + byte-level BPE tokenizer) into a
`PagedDecodeEngine` + `ContinuousBatcher` and speaks the OpenAI HTTP
surface, so standard client libraries and load generators drive the
fleet unmodified:

    POST {route}/completions     text completions, stream and non-stream
    GET  {route}/models          the one-model list

Request shape (the OpenAI `/v1/completions` contract, greedy decoding):
    prompt       str | [str, ...] | [token_id, ...]
    max_tokens   int (default 16)
    stream       bool — SSE chunks `data: {json}\n\n`, terminated by the
                 `data: [DONE]\n\n` sentinel (Content-Type:
                 text/event-stream); non-stream returns one JSON body
    stop         str | [str, ...] (<= 4): generation cut BEFORE the first
                 match; streaming holds back any text that could still
                 become a stop match, so no post-stop text ever escapes
    echo         bool — prepend the prompt text to the output
    temperature  accepted and IGNORED (the serving engine is greedy;
                 OpenAI clients default to 1.0, rejecting it would break
                 every stock client). n > 1, logprobs, best_of are
                 rejected with an OpenAI-shaped error.

finish_reason: "stop" (eos token or stop sequence) or "length"
(max_tokens, context-window cut, drain cut). The eos token itself is
never surfaced as text. Token ids flow through
`IncrementalDetokenizer`, so a multi-byte character split across tokens
streams as ONE complete character (never mojibake), and the drafter
behind `serve_speculative_k` now proposes over real token ids.

Deploy with:

    from ray_tpu import serve
    from ray_tpu.serve.openai_api import openai_app
    serve.run(openai_app(model_path), name="llm", route_prefix="/v1")

`model_path` defaults from the `serve_model_path` config flag; the
advertised model id from `serve_model_id` (else the checkpoint dir name).
"""

from __future__ import annotations

import json
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

from .batching import ContinuousBatcher
from .http_proxy import Request, Response, StreamingResponse


class _OpenAIError(Exception):
    def __init__(self, status: int, message: str,
                 err_type: str = "invalid_request_error"):
        super().__init__(message)
        self.status = status
        self.message = message
        self.err_type = err_type

    def response(self) -> Response:
        return Response(
            status=self.status,
            body={"error": {"message": self.message, "type": self.err_type,
                            "param": None, "code": None}},
        )


class _StopBuffer:
    """Hold back any text tail that could still grow into a stop match, so
    a streaming response never emits characters past a stop sequence that
    only completes in a later token."""

    def __init__(self, stops: List[str]):
        self._stops = stops
        self._buf = ""
        self.matched = False

    def push(self, text: str) -> str:
        if self.matched or not self._stops:
            return "" if self.matched else text
        self._buf += text
        cut = None
        for s in self._stops:
            i = self._buf.find(s)
            if i != -1 and (cut is None or i < cut):
                cut = i
        if cut is not None:
            self.matched = True
            out, self._buf = self._buf[:cut], ""
            return out
        # longest suffix that is a proper prefix of some stop string stays
        hold = 0
        for s in self._stops:
            for j in range(min(len(s) - 1, len(self._buf)), 0, -1):
                if self._buf.endswith(s[:j]):
                    hold = max(hold, j)
                    break
        if hold:
            out, self._buf = self._buf[:-hold], self._buf[-hold:]
            return out
        out, self._buf = self._buf, ""
        return out

    def flush(self) -> str:
        """End of stream: whatever was held back was never a stop."""
        if self.matched:
            return ""
        out, self._buf = self._buf, ""
        return out


def _chunk_frame(cid: str, created: int, model: str, text: str,
                 finish_reason: Optional[str],
                 extra: Optional[Dict[str, Any]] = None) -> str:
    frame = {
        "id": cid,
        "object": "text_completion",
        "created": created,
        "model": model,
        "choices": [{"text": text, "index": 0, "logprobs": None,
                     "finish_reason": finish_reason}],
    }
    if extra:
        frame.update(extra)
    return "data: " + json.dumps(frame, ensure_ascii=False) + "\n\n"


def _timing_block(stream) -> Optional[Dict[str, float]]:
    """TTFT + total latency off the GenerationStream's lifecycle
    timestamps (kept even with the metrics plane off) — the per-response
    twin of the serve_ttft_s histogram, so one request's latency is
    auditable without a scrape. Extension field, absent from the OpenAI
    schema; stock clients ignore unknown keys."""
    t_first = getattr(stream, "t_first", None)
    t_submit = getattr(stream, "t_submit", None)
    if t_first is None or t_submit is None:
        return None
    return {
        "ttft_ms": round((t_first - t_submit) * 1000, 2),
        "latency_ms": round((time.monotonic() - t_submit) * 1000, 2),
    }


class _CompletionSSE:
    """Adapt a GenerationStream of token ids into OpenAI SSE frames while
    PRESERVING the batched long-poll pull surface (next_batch), so the
    replica->proxy stream_next path stays timeout-bounded and batched.

    Detokenization is incremental (incomplete UTF-8 tails held back) and
    stop sequences are enforced here — once a stop matches, the inner
    generation is cancelled and the stream ends with finish_reason
    "stop" and the [DONE] sentinel."""

    def __init__(self, stream, tokenizer, eos_id: Optional[int],
                 model_id: str, cid: str, created: int,
                 stops: List[str], echo_text: str = "",
                 n_prompt: int = 0):
        self._stream = stream
        self._detok = tokenizer.detokenizer()
        self._eos_id = eos_id
        self._model = model_id
        self._cid = cid
        self._created = created
        self._stop = _StopBuffer(stops)
        self._echo_text = echo_text
        self._done_sent = False
        self._n_prompt = n_prompt
        self._n_completion = 0

    def _frame(self, text: str, finish: Optional[str] = None,
               extra: Optional[Dict[str, Any]] = None) -> str:
        return _chunk_frame(self._cid, self._created, self._model, text,
                            finish, extra)

    def next_batch(self, max_items: int, wait_s: float) -> Tuple[List[str], bool]:
        if self._done_sent:
            return [], True
        # stream faults PROPAGATE: a never-admitted request's
        # ReplicaDrainingError must reach the proxy before the response
        # head so it re-dispatches to a live replica ("never a dead
        # 200"), and a mid-stream engine fault must truncate the chunked
        # response, not fabricate a clean [DONE]
        items, done = self._stream.next_batch(max_items, wait_s)
        out: List[str] = []
        if self._echo_text:
            out.append(self._frame(self._echo_text))
            self._echo_text = ""
        finish: Optional[str] = None
        emit = ""
        # per-token stop matching: counting must STOP at the token that
        # completes a stop match (a burst pull — e.g. a speculative
        # accept — may deliver tokens past it), or the streamed usage
        # would diverge from the non-stream path's count for the same
        # request
        for tok in items:
            if self._eos_id is not None and tok == self._eos_id:
                finish = "stop"
                break
            self._n_completion += 1
            emit += self._stop.push(self._detok.push(tok))
            if self._stop.matched:
                finish = "stop"
                break
        if emit:
            out.append(self._frame(emit))
        if finish == "stop" and not done:
            # eos/stop decided the end before the engine did (stop match,
            # or eos arrived mid-burst): stop pulling and free the slot —
            # a SUCCESSFUL completion, so metrics must not count it as a
            # client abort
            self._cancel_inner(completed=True)
            done = True
        if done:
            tail = "" if self._stop.matched else (
                self._stop.push(self._detok.flush()) + self._stop.flush()
            )
            if finish is None:
                finish = ("stop" if self._stop.matched else "length")
            # the finishing frame carries usage + timing (telemetry in the
            # response itself): prompt/completion token accounting and the
            # stream's measured TTFT/total latency
            extra: Dict[str, Any] = {"usage": {
                "prompt_tokens": self._n_prompt,
                "completion_tokens": self._n_completion,
                "total_tokens": self._n_prompt + self._n_completion,
            }}
            timing = _timing_block(self._stream)
            if timing is not None:
                extra["timing"] = timing
            out.append(self._frame(tail, finish, extra))
            out.append("data: [DONE]\n\n")
            self._done_sent = True
        return out, done

    def cancel(self) -> None:
        self._cancel_inner()

    def _cancel_inner(self, completed: bool = False) -> None:
        cancel = getattr(self._stream, "cancel", None)
        if cancel is None:
            return
        try:
            cancel(completed=completed)
        except TypeError:  # plain iterables' cancel() takes no kwargs
            cancel()


class OpenAICompletions:
    """The deployment callable behind `/v1`: loads the hub bundle in the
    replica process, owns engine + batcher, routes OpenAI requests."""

    _serve_ingress = True  # serve.run hands us the raw http_proxy.Request
    runs_paged_engine = True  # controller._spawn_replica: one chip per replica

    def __init__(
        self,
        model_path: Optional[str] = None,
        model_id: Optional[str] = None,
        engine_kwargs: Optional[Dict[str, Any]] = None,
        batcher_kwargs: Optional[Dict[str, Any]] = None,
        mesh=None,
        rules=None,
    ):
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        from ray_tpu.models.hub import load_model
        from ray_tpu.models.kv_paging import PagedDecodeEngine

        model_path = model_path or str(cfg.serve_model_path)
        if not model_path:
            raise ValueError(
                "OpenAICompletions needs a checkpoint directory: pass "
                "model_path or set the serve_model_path config flag"
            )
        # mesh + rules flow into BOTH the loader (per-leaf sharded
        # device_put + vocab padding — the host never replicates the full
        # model) and the engine (sharded KV pool). jax meshes do not
        # pickle across the deployment boundary: pass them when
        # constructing in-process, or build them inside a subclass's
        # __init__ for fleet deployments.
        self.bundle = load_model(
            model_path, mesh=mesh, rules=rules,
            model_id=model_id or str(cfg.serve_model_id) or None,
        )
        engine_kwargs = dict(engine_kwargs or {})
        engine_kwargs.setdefault("mesh", mesh)
        engine_kwargs.setdefault("rules", rules)
        self.engine = PagedDecodeEngine(
            self.bundle.cfg, self.bundle.params,
            eos_id=self.bundle.eos_id,
            **engine_kwargs,
        )
        self.batcher = ContinuousBatcher(self.engine, **(batcher_kwargs or {}))
        self.created = int(time.time())

    # ------------------------------------------------------------- routing

    def __call__(self, request: Request):
        try:
            sub = (request.subpath or "").strip("/")
            if request.method == "GET" and sub in ("models", "v1/models"):
                return self._models()
            if request.method == "POST" and sub in (
                "completions", "v1/completions"
            ):
                return self._completions(request.body)
            raise _OpenAIError(
                404, f"no route for {request.method} {request.path!r}",
                "not_found_error",
            )
        except _OpenAIError as e:
            return e.response()

    def _models(self):
        # explicit Response: plain dict results get the {"result": ...} v1
        # wrapper, but OpenAI clients need the bare object
        return Response(200, {
            "object": "list",
            "data": [{
                "id": self.bundle.model_id,
                "object": "model",
                "created": self.created,
                "owned_by": "ray_tpu",
            }],
        })

    # --------------------------------------------------------- completions

    def _encode_prompt(self, prompt) -> List[List[int]]:
        tok = self.bundle.tokenizer
        if isinstance(prompt, str):
            return [tok.encode(prompt)]
        if isinstance(prompt, list) and prompt:
            # bool is an int subclass: JSON true/false must not pass as ids
            if all(isinstance(p, int) and not isinstance(p, bool)
                   for p in prompt):
                # bound by the REAL vocab: cfg.vocab_size includes
                # alignment-only padded entries (cfg.vocab_pad) whose
                # embeddings are zero rows, not tokens
                real_vocab = (self.bundle.cfg.vocab_size
                              - self.bundle.cfg.vocab_pad)
                bad = [p for p in prompt if not 0 <= p < real_vocab]
                if bad:
                    raise _OpenAIError(
                        400, f"prompt token ids out of vocab: {bad[:4]}")
                return [list(prompt)]
            if all(isinstance(p, str) for p in prompt):
                return [tok.encode(p) for p in prompt]
        raise _OpenAIError(
            400, "prompt must be a string, a list of strings, or a list "
            "of token ids")

    def _completions(self, body):
        if not isinstance(body, dict):
            raise _OpenAIError(400, "request body must be a JSON object")
        try:
            n, best_of = int(body.get("n", 1)), int(body.get("best_of", 1))
        except (TypeError, ValueError):
            raise _OpenAIError(400, "n and best_of must be integers")
        if n != 1:
            raise _OpenAIError(400, "n > 1 is not supported")
        if body.get("logprobs") not in (None, 0):
            raise _OpenAIError(400, "logprobs are not supported")
        if best_of != 1:
            raise _OpenAIError(400, "best_of > 1 is not supported")
        if "prompt" not in body:
            raise _OpenAIError(400, "missing required field: prompt")
        prompts = self._encode_prompt(body["prompt"])
        try:
            max_tokens = int(body.get("max_tokens", 16))
        except (TypeError, ValueError):
            raise _OpenAIError(400, "max_tokens must be an integer")
        if max_tokens < 1:
            raise _OpenAIError(400, "max_tokens must be >= 1")
        stop = body.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list) or len(stop) > 4 or not all(
            isinstance(s, str) and s for s in stop
        ):
            raise _OpenAIError(
                400, "stop must be a non-empty string or up to 4 of them")
        echo = bool(body.get("echo", False))
        stream = bool(body.get("stream", False))
        max_ctx = self.engine.max_seq_len
        for ids in prompts:
            if not ids:
                raise _OpenAIError(400, "prompt encoded to zero tokens")
            if len(ids) >= max_ctx:
                raise _OpenAIError(
                    400,
                    f"prompt of {len(ids)} tokens exceeds the context "
                    f"window of {max_ctx}",
                    "context_length_exceeded",
                )
        cid = "cmpl-" + uuid.uuid4().hex[:24]
        created = int(time.time())
        model_id = self.bundle.model_id
        if stream:
            if len(prompts) != 1:
                raise _OpenAIError(
                    400, "stream=true supports a single prompt")
            return self._stream_one(prompts[0], max_tokens, stop, echo,
                                    cid, created, model_id)
        return self._complete(prompts, max_tokens, stop, echo, cid,
                              created, model_id)

    def _submit(self, ids: List[int], max_tokens: int):
        # submit() only ENQUEUES — engine.admit's validation runs later on
        # the batcher loop thread and surfaces through the stream. The one
        # admit-time hard failure a request can cause by itself (worst-case
        # KV span larger than the whole pool) is checked HERE so the
        # client gets an OpenAI-shaped 400, not a mid-generation fault.
        worst_fn = getattr(self.engine, "worst_case_blocks", None)
        if worst_fn is not None:
            worst = worst_fn(len(ids), max_tokens)
            usable = self.engine.allocator.num_usable
            if worst > usable:
                raise _OpenAIError(
                    400,
                    f"prompt + max_tokens spans {worst} KV blocks; this "
                    f"deployment's pool holds {usable}",
                )
        return self.batcher.submit(tokens=ids, max_new_tokens=max_tokens)

    def _stream_one(self, ids, max_tokens, stop, echo, cid, created,
                    model_id):
        echo_text = self.bundle.tokenizer.decode(ids) if echo else ""
        sse = _CompletionSSE(
            self._submit(ids, max_tokens), self.bundle.tokenizer,
            self.bundle.eos_id, model_id, cid, created, stop, echo_text,
            n_prompt=len(ids),
        )
        return StreamingResponse(
            sse, content_type="text/event-stream", buffered=False
        )

    def _complete(self, prompts, max_tokens, stop, echo, cid, created,
                  model_id):
        streams = [self._submit(ids, max_tokens) for ids in prompts]
        try:
            return self._collect(prompts, streams, stop, echo, cid,
                                 created, model_id)
        except ValueError as e:
            # an engine-side validation fault surfacing through a stream
            # (bad request by construction) answers as an OpenAI 400
            raise _OpenAIError(400, str(e))
        finally:
            # a fault on one stream must not orphan its siblings: an
            # unconsumed generation would keep its slot + KV blocks
            # decoding to max_tokens with no reader
            for s in streams:
                if not s.finished:
                    s.cancel()

    def _collect(self, prompts, streams, stop, echo, cid, created,
                 model_id):
        tok = self.bundle.tokenizer
        eos = self.bundle.eos_id
        choices = []
        n_completion = 0
        for i, (ids, stream) in enumerate(zip(prompts, streams)):
            # incremental stop enforcement, same as the streaming path: a
            # stop match CANCELS the generation so the decode slot and its
            # KV blocks free at the match, not after max_tokens more steps
            detok = tok.detokenizer()
            sb = _StopBuffer(stop)
            finish = "length"
            text = ""
            n_toks = 0
            for t in stream:
                if eos is not None and t == eos:
                    finish = "stop"
                    break
                n_toks += 1
                text += sb.push(detok.push(t))
                if sb.matched:
                    finish = "stop"
                    stream.cancel(completed=True)
                    break
            if not sb.matched:
                text += sb.push(detok.flush()) + sb.flush()
            n_completion += n_toks
            if echo:
                text = tok.decode(ids) + text
            choices.append({
                "text": text,
                "index": i,
                "logprobs": None,
                "finish_reason": finish,
            })
        n_prompt = sum(len(p) for p in prompts)
        body = {
            "id": cid,
            "object": "text_completion",
            "created": created,
            "model": model_id,
            "choices": choices,
            "usage": {
                "prompt_tokens": n_prompt,
                "completion_tokens": n_completion,
                "total_tokens": n_prompt + n_completion,
            },
        }
        # measured per-request latency next to usage (extension field;
        # multi-prompt requests report the first stream's TTFT — the
        # moment the response started producing)
        timing = _timing_block(streams[0]) if streams else None
        if timing is not None:
            body["timing"] = timing
        return Response(200, body)

    # ------------------------------------------------------------- serving

    def stats(self) -> Dict[str, Any]:
        out = self.batcher.stats()
        out["model_id"] = self.bundle.model_id
        out["params_source"] = self.bundle.params_source
        return out

    def check_health(self) -> bool:
        if not self.batcher._thread.is_alive():
            raise RuntimeError("continuous batcher loop thread died")
        return True


def openai_app(
    model_path: Optional[str] = None,
    model_id: Optional[str] = None,
    *,
    deployment_name: Optional[str] = None,
    num_replicas: int = 1,
    engine_kwargs: Optional[Dict[str, Any]] = None,
    batcher_kwargs: Optional[Dict[str, Any]] = None,
    **deployment_kwargs,
):
    """Bind OpenAICompletions as a serve Application:

        serve.run(openai_app("/path/to/ckpt"), name="llm",
                  route_prefix="/v1")

    Each call mints a UNIQUELY-NAMED deployment by default (the
    controller keys deployments globally by name — the same trap the
    DAGDriver factory solves): two models deployed at two routes must
    not silently redeploy each other's replicas. Pass `deployment_name`
    to pin a stable name (single-model fleets, targeted redeploys).
    """
    from . import deployment

    name = deployment_name or f"OpenAICompletions_{uuid.uuid4().hex[:8]}"
    dep = deployment(
        OpenAICompletions, name=name,
        num_replicas=num_replicas, **deployment_kwargs,
    )
    return dep.bind(
        model_path, model_id,
        engine_kwargs=engine_kwargs, batcher_kwargs=batcher_kwargs,
    )
