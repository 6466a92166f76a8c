"""Cluster-wide KV plane: cross-replica prefix transfer + disaggregation.

ROADMAP item 3's serving half: each replica's `PrefixCache` turns a
repeated prompt prefix into an admission-time block reuse — but only for
prompts that land on THAT replica. This module makes the hit rate
cluster-wide by shipping cached KV blocks between replicas over the bulk
object plane, and layers two fleet capabilities on the same transfer
path:

  payload plumbing     `pack_payload`/`unpack_payload` flatten an engine
                       export (kv_paging.PagedDecodeEngine.export_prefix:
                       content-addressed chain keys + k/v block contents,
                       int8 scales included) into ONE contiguous uint8
                       buffer + a small meta dict. The buffer rides
                       `ray_tpu.put`/`get` — the PR 12 bulk plane:
                       recv-into-slab on the consumer, striping for
                       multi-MB spans, relay fallback on stream fault,
                       zero-copy shm attach on the same host. A CRC +
                       length check rejects anything truncated or
                       corrupted mid-flight.
  KVTransferManager    per-replica glue: serves peers' export requests
                       (engine reads routed through the batcher loop
                       thread — the pool's owner), pulls remote prefixes
                       before admission, verifies, and accounts every
                       outcome. ANY failure — peer gone, payload
                       truncated, signature mismatch, local pool
                       pressure — degrades to local recompute and bumps
                       `kv_transfer_fallbacks_total`; a transfer can cost
                       latency, never correctness.
  prefix hints         `prefix_hint` hashes the prompt's leading
                       `serve_prefix_hint_tokens` tokens — the routing
                       currency shared by proxy, handle, controller and
                       replicas (see handle._pick_replica / the
                       controller's prefix digest).
  KVGenerationServer   a deployment-ready paged generation server with
                       the whole plane wired in, and the building block
                       of `deploy_disaggregated`: prefill-tagged replicas
                       run chunked prefill to completion and hand the
                       committed blocks to decode replicas over the
                       transfer path; decode resumes token-for-token
                       identically (greedy parity vs a monolithic
                       replica — the tail past the last FULL block is
                       recomputed locally, so the first sampled token is
                       derived from the same hidden state either way).

Flag matrix: `serve_kv_transfer` (the transfer path itself),
`serve_prefix_affinity` (hint-based routing), `serve_disaggregate`
(deploy_disaggregated's default) — see serve/README.md for the fallback
matrix.
"""

from __future__ import annotations

import hashlib
import threading
import zlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

try:  # np.dtype("bfloat16") resolves only once ml_dtypes registered it
    import ml_dtypes  # noqa: F401
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    pass


class KVTransferError(RuntimeError):
    """A transfer payload failed the wire-integrity check (short read,
    truncation, corruption). Callers fall back to local recompute."""


# ----------------------------------------------------------- prefix hints


def prefix_hint(tokens, hint_tokens: Optional[int] = None) -> str:
    """Stable short hash over the prompt's leading tokens — the routing
    currency of prefix affinity. Proxy, handle and replicas must agree on
    the window, so it comes from config (`serve_prefix_hint_tokens`), not
    engine geometry; prompts shorter than the window hash what they have
    (their hint simply never matches a longer prompt's)."""
    from ray_tpu._private.config import GLOBAL_CONFIG as cfg

    n = int(cfg.serve_prefix_hint_tokens if hint_tokens is None
            else hint_tokens)
    arr = np.asarray(tokens, np.int32)
    if arr.ndim != 1 or n <= 0:
        return ""
    take = min(int(arr.size), n)
    if take <= 0:
        return ""
    h = hashlib.sha1(b"ray_tpu.prefix_hint.v1")
    h.update(np.ascontiguousarray(arr[:take], np.int32).tobytes())
    return h.hexdigest()[:16]


def request_hint(args, kwargs) -> str:
    """Best-effort prefix hint for a handle/proxy call: looks for a token
    sequence under the conventional request keys (`tokens`, or an int
    `prompt` list in an OpenAI-shaped body). Returns "" when the call
    shape is not a generation request — routing then falls through to
    plain power-of-two-choices."""
    candidates: List[Any] = []
    if isinstance(kwargs, dict):
        candidates.append(kwargs)
    for a in args or ():
        if isinstance(a, dict):
            candidates.append(a)
    for body in candidates:
        for key in ("tokens", "prompt"):
            toks = body.get(key)
            if (isinstance(toks, (list, tuple)) and toks
                    and all(isinstance(t, (int, np.integer)) for t in toks)):
                try:
                    return prefix_hint(toks)
                except Exception:
                    return ""
    return ""


# ------------------------------------------------------- payload plumbing


def pack_payload(payload: Dict[str, Any]) -> Tuple[Dict[str, Any], np.ndarray]:
    """Flatten an engine export into (meta, one contiguous uint8 buffer).
    The buffer is what rides the bulk plane; meta is a small dict carried
    in the actor reply (sig, chain keys, token span, leaf layout, length
    + CRC for wire integrity)."""
    parts: List[np.ndarray] = []
    leaves: List[Dict[str, Any]] = []
    off = 0
    for name in sorted(payload["blocks"]):
        arr = np.ascontiguousarray(payload["blocks"][name])
        raw = arr.view(np.uint8).reshape(-1)
        leaves.append({
            "name": name,
            "dtype": str(arr.dtype),
            "shape": tuple(int(d) for d in arr.shape),
            "offset": off,
            "nbytes": int(raw.size),
        })
        parts.append(raw)
        off += int(raw.size)
    buf = np.concatenate(parts) if parts else np.zeros(0, np.uint8)
    meta = {
        "sig": payload["sig"],
        "keys": list(payload["keys"]),
        "tokens": np.ascontiguousarray(payload["tokens"], np.int32),
        "block_tokens": int(payload["block_tokens"]),
        "kv_cache_dtype": payload["kv_cache_dtype"],
        "leaves": leaves,
        "total_bytes": int(buf.size),
        "crc": zlib.crc32(buf),
    }
    return meta, buf


def unpack_payload(meta: Dict[str, Any], buf) -> Dict[str, Any]:
    """Rebuild the engine-import payload from (meta, buffer). Raises
    KVTransferError when the buffer does not match meta's length/CRC —
    a transfer that died or was corrupted mid-flight must be detected
    HERE, before any byte could reach a pool."""
    if isinstance(buf, (bytes, bytearray, memoryview)):
        buf = np.frombuffer(buf, np.uint8)
    buf = np.asarray(buf)
    if buf.dtype != np.uint8:
        buf = buf.view(np.uint8)
    buf = buf.reshape(-1)
    if int(buf.size) != int(meta.get("total_bytes", -1)):
        raise KVTransferError(
            f"KV transfer payload length mismatch: got {buf.size} bytes, "
            f"expected {meta.get('total_bytes')}"
        )
    if zlib.crc32(np.ascontiguousarray(buf)) != meta.get("crc"):
        raise KVTransferError("KV transfer payload failed its CRC check")
    blocks: Dict[str, np.ndarray] = {}
    for leaf in meta["leaves"]:
        raw = buf[leaf["offset"]:leaf["offset"] + leaf["nbytes"]]
        blocks[leaf["name"]] = np.ascontiguousarray(raw).view(
            np.dtype(leaf["dtype"])
        ).reshape(leaf["shape"])
    return {
        "sig": meta["sig"],
        "keys": list(meta["keys"]),
        "tokens": np.asarray(meta["tokens"], np.int32),
        "block_tokens": int(meta["block_tokens"]),
        "kv_cache_dtype": meta["kv_cache_dtype"],
        "blocks": blocks,
    }


# ------------------------------------------------------- transfer manager


class KVTransferManager:
    """Per-replica glue between the engine's export/import primitives and
    the fleet: serves peers' export requests, pulls remote prefixes
    before admission, advertises this replica's cached chains (the prefix
    digest affinity routing feeds on), and accounts every byte/outcome.

    Replica.stats discovers instances by the `_serve_kv_transfer` marker
    (the same duck-typed scan as `_serve_drainable`)."""

    _serve_kv_transfer = True

    def __init__(self, batcher, engine=None, *, enabled: Optional[bool] = None,
                 deployment: str = "", digest_size: Optional[int] = None,
                 telemetry=None):
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        from ray_tpu.util import metrics as _metrics

        from .telemetry import resolve as _tel_resolve

        self.batcher = batcher
        self.engine = engine if engine is not None else batcher.engine
        self.enabled = bool(
            cfg.serve_kv_transfer if enabled is None else enabled
        )
        if getattr(self.engine, "hybrid", False):
            # a hybrid cache (recurrent state beside KV) refuses
            # export_prefix / import_prefix by name: blocks without the
            # state snapshot behind them cannot be resumed from. Its
            # transfer signature carries the state's geometry all the
            # same, so a peer that asks is answered with a miss
            self.enabled = False
        self.deployment = deployment
        self.min_blocks = max(1, int(cfg.serve_kv_transfer_min_blocks))
        self._tel = _tel_resolve(telemetry)
        self._fallbacks = _metrics.kv_transfer_fallbacks_counter()
        self._lock = threading.Lock()
        # hint -> cached chain depth (full blocks); bounded LRU — the
        # slice of this replica's PrefixCache the controller aggregates
        self._digest: "OrderedDict[str, int]" = OrderedDict()
        self._digest_size = int(
            cfg.serve_prefix_digest_size if digest_size is None
            else digest_size
        )
        self.pulls = 0          # remote pulls attempted
        self.pull_hits = 0      # pulls that yielded a verified payload
        self.fallbacks = 0      # pulls abandoned for local recompute
        self.exports_served = 0
        self.bytes_in = 0
        self.bytes_out = 0

    # -- export side (peer-facing; runs on replica request threads) ------

    def export_serve(self, tokens) -> Optional[Tuple[Dict[str, Any], Any]]:
        """Serve a peer's export request: (meta, bulk-plane ref to the
        packed buffer), or None on a local cache miss. The engine read
        runs on the batcher loop thread — the pool's single owner — so
        the chain match and the block gather see one consistent pool."""
        if not self.enabled:
            return None
        import ray_tpu
        from ray_tpu._private import faults

        toks = np.asarray(tokens, np.int32)
        payload = self.batcher.run_on_loop(
            lambda: self.engine.export_prefix(toks)
        )
        if payload is None:
            return None
        meta, buf = pack_payload(payload)
        if faults.ACTIVE and faults.kv_transfer_action() == "drop":
            # chaos: the transfer dies mid-flight — ship a truncated
            # buffer so the importer's length/CRC check fires (the
            # fallback path the chaos suite pins)
            buf = np.ascontiguousarray(buf[:max(1, buf.size // 2)])
        ref = ray_tpu.put(buf)
        self.exports_served += 1
        self.bytes_out += int(buf.size)
        if self._tel is not None:
            self._tel.kv_transfer_bytes.inc(
                int(buf.size), tags={"direction": "export"})
        return meta, ref

    # -- import side (before admission) ----------------------------------

    def try_import(self, tokens, peers=()) -> Optional[Dict[str, Any]]:
        """Pull this prompt's prefix from a peer replica. Returns a
        verified engine payload to ride the request (`kv_import=...`), or
        None — already cached locally, no peer has it, or the transfer
        failed (fallback counted). Peers are actor handles tried in
        order; the first verified payload wins."""
        if not self.enabled or not peers:
            return None
        arr = np.asarray(tokens, np.int32)
        bt = self.engine.block_tokens
        # same cap as admission's lookup: at least one real token must
        # remain to prefill, so a full final block is never worth pulling
        want = (int(arr.size) - 1) // bt
        if want < self.min_blocks:
            return None
        cache = self.engine.prefix_cache
        if cache is None:
            return None
        # match_blocks off-thread: dict lookups against the trie (no LRU
        # touch, no iteration) — same read-safety class as stats()
        if len(cache.match_blocks(arr, want)) >= want:
            return None  # the whole span is already local
        self.pulls += 1
        payload = self._pull(arr, peers)
        if payload is None:
            self._note_fallback()
            return None
        self.pull_hits += 1
        if self._tel is not None:
            self._tel.kv_transfer_hits.inc()
            self._update_hit_rate()
        return payload

    def _pull(self, arr: np.ndarray, peers) -> Optional[Dict[str, Any]]:
        import ray_tpu

        toks_list = [int(t) for t in arr]
        for peer in peers:
            try:
                res = ray_tpu.get(
                    peer.handle_request.remote("kv_export", (toks_list,), {}),
                    timeout=30,
                )
                if res is None:
                    continue
                meta, ref = res
                buf = ray_tpu.get(ref, timeout=30)
                payload = unpack_payload(meta, buf)
                # the peer must have answered for OUR prompt: its token
                # span has to be a prefix of ours, or the payload would
                # pollute the local cache with an unrelated chain
                span = payload["tokens"]
                if (span.size > arr.size
                        or not np.array_equal(span, arr[:span.size])):
                    continue
                self.bytes_in += int(np.asarray(buf).size)
                if self._tel is not None:
                    self._tel.kv_transfer_bytes.inc(
                        int(np.asarray(buf).size),
                        tags={"direction": "import"})
                return payload
            except Exception:
                continue
        return None

    def _note_fallback(self) -> None:
        self.fallbacks += 1
        self._fallbacks.inc()
        self._update_hit_rate()

    def _update_hit_rate(self) -> None:
        if self._tel is not None:
            self._tel.prefix_remote_hit_rate.set(
                self.pull_hits / max(1, self.pulls))

    # -- digest (affinity advertisement) ---------------------------------

    def note_prompt(self, tokens) -> None:
        """Advertise this replica's cached chain depth for the prompt's
        hint. Called after a generation completes (the chain is
        registered by then); the controller harvests digest() from
        Replica.stats and publishes the per-deployment aggregate."""
        cache = self.engine.prefix_cache
        if cache is None:
            return
        hint = prefix_hint(tokens)
        if not hint:
            return
        arr = np.asarray(tokens, np.int32)
        depth = len(cache.match_blocks(
            arr, int(arr.size) // self.engine.block_tokens))
        with self._lock:
            self._digest[hint] = depth
            self._digest.move_to_end(hint)
            while len(self._digest) > self._digest_size:
                self._digest.popitem(last=False)

    def digest(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._digest)

    # -- observability ----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        return {
            "kv_transfer_enabled": self.enabled,
            "kv_transfer_pulls": self.pulls,
            "kv_transfer_hits": self.pull_hits,
            "kv_transfer_fallbacks": self.fallbacks,
            "kv_transfer_exports_served": self.exports_served,
            "kv_transfer_bytes_in": self.bytes_in,
            "kv_transfer_bytes_out": self.bytes_out,
            "prefix_remote_hit_rate": round(
                self.pull_hits / max(1, self.pulls), 4),
        }


# --------------------------------------------------- generation deployment


class KVGenerationServer:
    """Deployment-ready paged generation server with the cluster-wide KV
    plane wired in. Builds a PagedDecodeEngine (weights re-derived from
    `weights_seed`, so every replica holds identical parameters: the f32
    draws of `init_params`, each cast as it is drawn (`held=True`) to what
    the engine holds of any tree — matmul weights, `embed`, `unembed` in
    `cfg.dtype`, norm scales float32 — so no float32 tree ever exists)
    + a ContinuousBatcher + a KVTransferManager, and exposes:

      generate(tokens, max_new_tokens)  greedy generation; pulls the
          prompt's prefix from a peer (monolithic role) or from the
          prefill pool (decode role) before admission — any transfer
          failure falls back to local prefill
      kv_export(tokens)                 the peer-facing export endpoint
      prefill(tokens)                   prefill role: run chunked prefill
          to completion (one sampled token) and export the committed
          chain for a decode replica
      engine_stats()                    the batcher/engine stats dict

    Roles: "monolithic" (default — peer pulls within one deployment),
    "prefill" / "decode" (the two pools of deploy_disaggregated)."""

    runs_paged_engine = True  # controller._spawn_replica: one chip per replica

    def __init__(self, cfg, *, weights_seed: int = 0,
                 engine_kwargs: Optional[Dict[str, Any]] = None,
                 deployment: str = "", role: str = "monolithic",
                 prefill=None, transfer: Optional[bool] = None):
        import jax

        from ray_tpu.models.kv_paging import PagedDecodeEngine
        from ray_tpu.models.transformer import init_params

        from .batching import ContinuousBatcher

        if role not in ("monolithic", "prefill", "decode"):
            raise ValueError(f"unknown role {role!r}")
        self.role = role
        self.deployment = deployment
        # each f32 leaf from the seed is cast as it is drawn and then freed:
        # the whole float32 tree never exists (it need not fit the chip),
        # and nothing of it is left when the engine allocates its pool
        params = init_params(
            jax.random.PRNGKey(int(weights_seed)), cfg, held=True)
        kw = dict(engine_kwargs or {})
        self.engine = PagedDecodeEngine(cfg, params, **kw)
        self.batcher = ContinuousBatcher(self.engine)
        self.kv = KVTransferManager(
            self.batcher, deployment=deployment, enabled=transfer
        )
        self._prefill_handle = prefill

    # -- peer discovery ---------------------------------------------------

    def _peers(self) -> List[Any]:
        """Sibling replica actor handles, self excluded. Empty outside a
        serve deployment (bare construction in tests/benches)."""
        if not self.deployment:
            return []
        try:
            import ray_tpu

            from .handle import CONTROLLER_NAME

            me = ray_tpu.get_runtime_context().get_actor_id()
            ctl = ray_tpu.get_actor(CONTROLLER_NAME)
            reps = ray_tpu.get(
                ctl.get_replicas.remote(self.deployment), timeout=5
            )
            return [r for r in reps
                    if getattr(r, "_actor_id", None) != me]
        except Exception:
            return []

    # -- serving surface --------------------------------------------------

    def kv_export(self, tokens):
        return self.kv.export_serve(tokens)

    def prefill(self, tokens):
        """Prefill-pool endpoint: run the prompt's prefill to completion
        (chunked per the engine's prefill_chunk_tokens; exactly one
        sampled token, discarded) and export the committed chain. Returns
        (meta, bulk-plane ref) or None when nothing exportable."""
        toks = [int(t) for t in tokens]
        stream = self.batcher.submit(tokens=toks, max_new_tokens=1)
        for _ in stream:
            pass
        self.kv.note_prompt(toks)
        return self.kv.export_serve(toks)

    def _pull_from_prefill(self, toks: List[int]) -> Optional[Dict[str, Any]]:
        """Decode-pool import: the prefill handle runs the prefill and
        hands back the committed blocks over the transfer path."""
        import ray_tpu

        self.kv.pulls += 1
        try:
            res = self._prefill_handle.prefill.remote(toks).result(
                timeout_s=120
            )
            if res is None:
                raise KVTransferError("prefill pool exported nothing")
            meta, ref = res
            buf = ray_tpu.get(ref, timeout=30)
            payload = unpack_payload(meta, buf)
            span = payload["tokens"]
            arr = np.asarray(toks, np.int32)
            if (span.size > arr.size
                    or not np.array_equal(span, arr[:span.size])):
                raise KVTransferError("prefill pool answered for another prompt")
        except Exception:
            self.kv._note_fallback()
            return None
        self.kv.pull_hits += 1
        self.kv.bytes_in += int(np.asarray(buf).size)
        if self.kv._tel is not None:
            self.kv._tel.kv_transfer_hits.inc()
            self.kv._tel.kv_transfer_bytes.inc(
                int(np.asarray(buf).size), tags={"direction": "import"})
            self.kv._update_hit_rate()
        return payload

    def _submit(self, toks: List[int], max_new_tokens: int):
        """Admit one generation (pulling its prefix over the transfer
        path first, where this role does) and return its token stream."""
        payload = None
        if self.role == "decode" and self._prefill_handle is not None:
            payload = self._pull_from_prefill(toks)
        elif self.role != "prefill" and self.kv.enabled:
            payload = self.kv.try_import(toks, self._peers())
        kw: Dict[str, Any] = {}
        if payload is not None:
            kw["kv_import"] = payload
        return self.batcher.submit(
            tokens=toks, max_new_tokens=int(max_new_tokens), **kw
        )

    def generate(self, tokens, max_new_tokens: int = 16) -> Dict[str, Any]:
        toks = [int(t) for t in tokens]
        out = [int(t) for t in self._submit(toks, max_new_tokens)]
        self.kv.note_prompt(toks)
        return {"tokens": out}

    def __call__(self, body):
        """HTTP ingress: `{"tokens": [...], "max_new_tokens": n}` answers
        with the whole generation as JSON; with `"stream": true` each token
        is its own server-sent event as the batcher emits it."""
        req = body if isinstance(body, dict) else {}
        toks = [int(t) for t in req.get("tokens") or ()]
        max_new = int(req.get("max_new_tokens") or 16)
        if req.get("stream"):
            from .http_proxy import sse_stream

            return sse_stream(self._submit(toks, max_new))
        return self.generate(toks, max_new)

    def engine_stats(self) -> Dict[str, Any]:
        return self.batcher.stats()

    def transfer_stats(self) -> Dict[str, Any]:
        return self.kv.stats()


# ------------------------------------------------ disaggregated deployment


def deploy_generation(
    name: str,
    cfg,
    *,
    num_replicas: int = 1,
    disaggregate: Optional[bool] = None,
    weights_seed: int = 0,
    engine_kwargs: Optional[Dict[str, Any]] = None,
    route_prefix: Optional[str] = None,
    **disagg_kwargs,
):
    """Deploy a KVGenerationServer fleet. Topology comes from
    `disaggregate` (default: the `serve_disaggregate` flag): off — one
    monolithic pool of `num_replicas` peers sharing prefixes over the
    transfer path; on — deploy_disaggregated's prefill/decode split with
    `num_replicas` decode replicas. Returns the serving handle."""
    from ray_tpu._private.config import GLOBAL_CONFIG as gcfg

    if disaggregate is None:
        disaggregate = bool(gcfg.serve_disaggregate)
    if disaggregate:
        return deploy_disaggregated(
            name, cfg, weights_seed=weights_seed,
            engine_kwargs=engine_kwargs, decode_replicas=num_replicas,
            route_prefix=route_prefix, **disagg_kwargs,
        )
    from ray_tpu.serve import deployment as serve_deployment
    from ray_tpu.serve import run as serve_run

    Dep = serve_deployment(
        name=name, num_replicas=int(num_replicas)
    )(KVGenerationServer)
    app = Dep.bind(
        cfg, weights_seed=weights_seed,
        engine_kwargs=dict(engine_kwargs or {}), deployment=name,
    )
    # route_prefix=None -> handle-only (no HTTP proxy spun up)
    return serve_run(app, name=name, route_prefix=route_prefix)


def deploy_disaggregated(
    name: str,
    cfg,
    *,
    weights_seed: int = 0,
    engine_kwargs: Optional[Dict[str, Any]] = None,
    prefill_replicas: int = 1,
    decode_replicas: int = 1,
    prefill_autoscaling=None,
    decode_autoscaling=None,
    autoscale: Optional[bool] = None,
    route_prefix: Optional[str] = None,
):
    """Deploy the disaggregated prefill/decode topology: a prefill pool
    (`<name>-prefill`) running chunked prefill to completion and a decode
    pool (`<name>`, the ingress) resuming each stream from the handed-off
    blocks — token-for-token identical to a monolithic replica (greedy).

    With `autoscale` (default: the `serve_disaggregate` flag being on
    does NOT autoscale by itself — pass autoscale=True or explicit
    configs), the two pools scale on the EXISTING autoscaling signals,
    each on the one that binds it: block saturation for prefill (long
    prompts exhaust the pool first) and batch occupancy for decode
    (slots saturate first). Returns the decode pool's handle."""
    # serve.deployment here means the decorator in serve/__init__ (which
    # wins the name over the .deployment submodule), not the submodule
    from ray_tpu.serve import deployment as serve_deployment
    from ray_tpu.serve import run as serve_run

    from .deployment import AutoscalingConfig

    if autoscale:
        if prefill_autoscaling is None:
            prefill_autoscaling = AutoscalingConfig(
                min_replicas=1,
                max_replicas=max(1, int(prefill_replicas)),
                target_kv_utilization=0.85,
            )
        if decode_autoscaling is None:
            decode_autoscaling = AutoscalingConfig(
                min_replicas=1,
                max_replicas=max(1, int(decode_replicas)),
                target_batch_occupancy=0.8,
            )
    prefill_name = f"{name}-prefill"
    ek = dict(engine_kwargs or {})
    Prefill = serve_deployment(
        name=prefill_name,
        num_replicas=1 if prefill_autoscaling else int(prefill_replicas),
        autoscaling_config=prefill_autoscaling,
    )(KVGenerationServer)
    Decode = serve_deployment(
        name=name,
        num_replicas=1 if decode_autoscaling else int(decode_replicas),
        autoscaling_config=decode_autoscaling,
    )(KVGenerationServer)
    prefill_app = Prefill.bind(
        cfg, weights_seed=weights_seed, engine_kwargs=ek,
        deployment=prefill_name, role="prefill",
    )
    decode_app = Decode.bind(
        cfg, weights_seed=weights_seed, engine_kwargs=ek,
        deployment=name, role="decode", prefill=prefill_app,
    )
    # route_prefix=None -> handle-only (no HTTP proxy spun up)
    return serve_run(decode_app, name=name, route_prefix=route_prefix)
