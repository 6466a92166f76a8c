"""Flag/config system.

Reference parity: src/ray/common/ray_config_def.h — a table of typed,
env-overridable flags (RAY_<name>). Here: one dataclass-like registry,
overridable via RAY_TPU_<NAME> env vars and `init(_system_config=...)`.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict


class _Flag:
    __slots__ = ("name", "type", "default", "doc")

    def __init__(self, name: str, type_: Callable, default: Any, doc: str = ""):
        self.name = name
        self.type = type_
        self.default = default
        self.doc = doc


def _parse_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).lower() in ("1", "true", "yes", "on")


class Config:
    """Global config registry. Values resolve in order:
    programmatic override > RAY_TPU_<NAME> env var > default."""

    _FLAGS: Dict[str, _Flag] = {}

    def __init__(self):
        self._overrides: Dict[str, Any] = {}

    @classmethod
    def define(cls, name: str, type_: Callable, default: Any, doc: str = ""):
        cls._FLAGS[name] = _Flag(name, type_, default, doc)

    def get(self, name: str):
        flag = self._FLAGS[name]
        if name in self._overrides:
            return self._overrides[name]
        env_name = "RAY_TPU_" + name.upper()
        if env_name in os.environ:
            raw = os.environ[env_name]
            if flag.type is bool:
                return _parse_bool(raw)
            return flag.type(raw)
        return flag.default

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        try:
            return self.get(name)
        except KeyError:
            raise AttributeError(name) from None

    def apply(self, overrides: Dict[str, Any]):
        for k, v in overrides.items():
            if k not in self._FLAGS:
                raise ValueError(f"Unknown config flag: {k}")
            self._overrides[k] = self._FLAGS[k].type(v) if not isinstance(v, bool) else v

    def snapshot(self) -> Dict[str, Any]:
        return {k: self.get(k) for k in self._FLAGS}

    def to_json(self) -> str:
        return json.dumps(self.snapshot())


D = Config.define
# --- core runtime ---
D("raylet_heartbeat_period_ms", int, 1000, "worker->head heartbeat period")
D("health_check_period_ms", int, 5000, "head-side liveness probe period")
D("health_check_failure_threshold", int, 24,
  "consecutive failed probes before a worker/node is declared dead (~2min "
  "with the default period: long GIL-holding stretches — jax traces and "
  "XLA compiles on loaded hosts — must not look like hangs)")
D("worker_register_timeout_s", float, 30.0, "max wait for a spawned worker to register")
D("task_retry_delay_ms", int, 100, "delay before retrying a failed task")
D("max_pending_lease_requests", int, 1024)
D("object_inline_limit_bytes", int, 128 * 1024, "objects <= this ride the control socket; larger go to shm")
D("fetch_chunk_bytes", int, 16 * 1024 * 1024,
  "chunk size for node-to-node buffer pulls (object_manager.h chunked "
  "transfer analogue); bounds per-message memory on the bulk plane")
D("bulk_stripe_sockets", int, 4,
  "parallel sockets a large bulk pull stripes across (READ_RANGE fan-out); "
  "1 disables striping")
D("bulk_stripe_min_bytes", int, 64 * 1024 * 1024,
  "buffers at or above this size stripe across bulk_stripe_sockets; "
  "smaller buffers ride one socket (pipelined for multi-buffer pulls)")
D("bulk_same_host", bool, True,
  "when a peer node's shm plane lives on THIS machine (colocated test "
  "clusters, multi-agent hosts), attach it directly and copy slab-to-slab "
  "instead of going through TCP")
D("bulk_read_timeout_s", float, 120.0,
  "blocking-socket timeout for bulk-plane pulls; a blackholed/dead peer "
  "surfaces as a timeout and the pull falls back to the head relay")
D("shm_store_bytes", int, 2 * 1024**3, "capacity of the C++ shared-memory object store")
D("shm_store_enabled", bool, True)
D("get_poll_timeout_s", float, 0.2)
D("actor_restart_delay_ms", int, 100)
D("worker_pool_prestart", int, 0, "workers to prestart per node at init")
D("direct_actor_calls", bool, True,
  "push actor calls straight to the actor's worker (head only resolves the "
  "route); falls back to head-mediated dispatch per actor on failure")
D("direct_task_calls", bool, True,
  "push normal tasks straight to head-granted leased workers with lease "
  "reuse (direct_task_transport.cc:588,:191); head path for placement "
  "strategies / runtime envs / TPU tasks and as fallback")
D("direct_task_max_leases", int, 8,
  "max concurrently held worker leases per (caller, resource shape)")
D("task_lease_idle_ms", int, 200,
  "idle time before a held task lease is released back to the cluster")
D("data_plane_request_warn_s", float, 60.0,
  "a driver->head data-plane request (get_objects dep resolution on the "
  "direct task channels) still unanswered after this long logs a loud "
  "repeating error naming its rid and the connection's other outstanding "
  "rids — turns a lost request/reply pair (the standalone "
  "test_repartition_exchange_exact wedge) into a diagnosable log line "
  "next to the test hang-guard's stack dump; 0 disables")
D("data_plane_request_deadline_s", float, 30.0,
  "per-attempt reply deadline for retransmit-armed data-plane requests "
  "(dep-resolution get_objects on the direct task channels): a request "
  "with no reply after this long is RE-SENT with the same rid and a "
  "bumped attempt counter (idempotent handlers re-execute; mutating ones "
  "dedup by rid head-side). Per-attempt waits back off exponentially, "
  "capped at 8x. 0 disables retransmit (legacy wait-forever behaviour)")
D("data_plane_request_retries", int, 4,
  "retransmits allowed per deadline-armed plane request before it "
  "surfaces PlaneRequestTimeout to the caller (total attempts = 1 + "
  "retries); dep pulls that exhaust this fall back to head-side task "
  "routing, which resolves deps on the head instead")
D("scheduler_spread_threshold", float, 0.5, "hybrid policy: prefer local until this utilization")
D("log_to_driver", bool, True)
D("session_dir_root", str, "/tmp/ray_tpu")
D("head_snapshot_period_ms", int, 15000,
  "period for head-state snapshots (KV, actors, jobs, PGs) to disk; 0 disables")
D("head_snapshot_path", str, "",
  "snapshot file (default <session_dir>/head_state.pkl); set a stable path "
  "to survive session-dir cleanup")
D("head_restore_path", str, "",
  "restore head state from this snapshot at startup (reference: GCS "
  "restart reload, gcs_init_data.h)")
D("head_storage_dir", str, "/tmp/ray_tpu/storage",
  "head-hosted object storage root for head:// URIs (checkpoints, "
  "experiment state); stable across sessions so a restarted cluster on "
  "the same head host can restore by URI")
D("head_reconnect_timeout_s", float, 60.0,
  "how long agents/workers/drivers keep retrying the head address after "
  "their connection drops (head crash + restart-from-snapshot window)")
D("head_tcp_host", str, "127.0.0.1",
  "bind host for the multi-host TCP control plane; the wire protocol is "
  "unauthenticated pickle, so bind non-loopback (0.0.0.0) only on trusted "
  "networks (real multi-host deployments)")
D("head_tcp_port", int, 0, "bind port for the TCP control plane (0 = ephemeral)")
D("dashboard_enabled", bool, True, "serve the dashboard-lite HTTP endpoint")
D("dashboard_host", str, "127.0.0.1")
D("dashboard_port", int, 0, "dashboard port (0 = ephemeral)")
D("memory_monitor_refresh_ms", int, 1000,
  "period for node memory-pressure sampling (reference: memory_monitor.h); "
  "0 disables the OOM killer")
D("memory_usage_threshold", float, 0.95,
  "node memory fraction above which the OOM killing policy fires "
  "(reference: ray_config_def.h memory_usage_threshold)")
D("memory_monitor_test_path", str, "",
  "test hook: file holding '<used> <total>' bytes used as the memory sample")
D("resource_report_period_ms", int, 2000,
  "agent->head node load report period (ray_syncer gossip analogue)")
# --- serve ingress hardening ---
# The HTTP proxy reads these at construction in ITS worker process, so set
# them via RAY_TPU_* env vars (inherited by spawned workers) or per-proxy
# through HTTPProxyActor kwargs / set_limits(); handle/breaker knobs are
# read in the calling process, so `init(_system_config=...)` works too.
D("serve_http_keep_alive_timeout_s", float, 30.0,
  "deadline for a complete request head to arrive on a connection — covers "
  "both idle keep-alive waits and slow-loris header trickle; expiry sends "
  "408 and closes")
D("serve_http_read_timeout_s", float, 30.0,
  "deadline for the request BODY (content-length or chunked) to arrive "
  "after the head; expiry sends 408 and closes")
D("serve_http_max_header_bytes", int, 64 * 1024,
  "request head larger than this is rejected with 431")
D("serve_http_max_body_bytes", int, 32 * 1024 * 1024,
  "request body larger than this is rejected with 413")
D("serve_http_max_connections", int, 1024,
  "open connections per proxy; excess connections get 503 + Retry-After")
D("serve_http_max_queued_calls", int, 128,
  "in-flight replica calls per proxy before new requests get 503 + "
  "Retry-After (backpressure ahead of the bounded call pool)")
D("serve_http_retry_after_s", float, 1.0,
  "Retry-After header value on 503 backpressure responses")
D("serve_handle_retry_attempts", int, 3,
  "re-route attempts after a replica died/was draining mid-call")
D("serve_handle_backoff_base_s", float, 0.05,
  "initial backoff before a replica-death re-route; doubles per attempt")
D("serve_handle_backoff_max_s", float, 1.0,
  "cap on the per-attempt re-route backoff (jitter rides below the cap)")
D("serve_breaker_failure_threshold", int, 5,
  "consecutive handle-level failures before a deployment's circuit breaker "
  "opens and calls fail fast with DeploymentUnavailableError")
D("serve_breaker_reset_s", float, 1.0,
  "how long an open circuit breaker waits before letting one probe through")
# --- serve continuous batching / token streaming ---
# Generation knobs are read in the REPLICA process at ContinuousBatcher
# construction (env vars or explicit constructor args); stream-pull knobs
# are read in the proxy process per pull.
D("serve_generation_max_batch_size", int, 8,
  "decode slots per ContinuousBatcher: the running batch admits new "
  "requests and retires finished ones at token granularity up to this size")
D("serve_generation_batch_wait_timeout_s", float, 0.01,
  "coalescing window when the running batch is EMPTY: wait this long for "
  "more requests before the first decode step (an active batch admits "
  "queued requests between steps without waiting)")
D("serve_stream_pull_max_chunks", int, 64,
  "max chunks the proxy pulls from a replica stream per stream_next call")
D("serve_stream_pull_wait_s", float, 0.25,
  "long-poll wait inside stream_next: block up to this long for the first "
  "chunk before returning an empty pull (bounds pull-call latency)")
D("serve_stream_idle_reap_s", float, 120.0,
  "a registered replica stream nobody has pulled for this long is "
  "cancelled and dropped — an abandoned consumer must not inflate "
  "num_ongoing (wedging drain) or hold a decode slot forever")
# --- paged KV cache (models/kv_paging.py) ---
# Read in the replica process at PagedDecodeEngine construction (env vars
# or explicit constructor args).
D("serve_kv_block_tokens", int, 64,
  "tokens per physical KV-cache block: the paging granularity — smaller "
  "blocks waste less tail memory and share finer prefixes but grow the "
  "block tables; 64 keeps the minor gather dim MXU/lane aligned")
D("serve_kv_cache_blocks", int, 0,
  "total physical blocks in a PagedDecodeEngine's pool (0 = dense "
  "equivalent: max_batch_size * ceil(max_seq_len/block_tokens), + the "
  "reserved null block); set below dense to oversubscribe HBM — prefix "
  "reuse and preemption keep oversubscription safe")
D("serve_kv_cache_dtype", str, "fp",
  "paged KV-pool storage: 'fp' stores model dtype (the exact reference "
  "path, held to the plain forward); 'int8' stores int8 blocks with "
  "per-block per-kv-head f32 scales — half the HBM per resident token, "
  "~2x concurrent sequences per chip, quantize at cache write / dequant "
  "at the attention read (greedy decode stays token-identical on the "
  "parity suite; logits drift within the quantization tolerance)")
D("serve_kv_pool_mb", int, 0,
  "size the paged KV pool by HBM budget instead of block count: "
  "num_blocks = budget // block_bytes, so int8 pools hold ~2x the blocks "
  "of bf16 for the same bytes; 0 = use serve_kv_cache_blocks / the "
  "dense-equivalent default (explicit constructor args win over both)")
D("serve_prefill_chunk_tokens", int, 0,
  "chunked prefill: admit long prompts into the RUNNING batch in chunks "
  "of this many tokens — each engine step advances one chunk while every "
  "other slot decodes, so a 4k-token prompt never stalls in-flight "
  "streams for its whole prefill (the head-of-line tail-latency fix for "
  "mixed traffic). 0 = whole-prompt prefill at admission (the "
  "lowest-latency path for a lone request); prompts at or under the "
  "chunk size admit whole either way")
D("serve_speculative_k", int, 0,
  "speculative decoding on the paged engine: a drafter proposes up to k "
  "tokens per slot per step and the target model verifies all k+1 "
  "positions in ONE batched decode step — accepted tokens commit through "
  "the block-table append, the rejected tail rolls back (table truncated, "
  "blocks freed). Greedy output stays token-for-token identical to "
  "non-speculative decode; greedy/temperature-0 only. 0 = off; the "
  "single-stream latency win scales with the drafter's accept rate")
D("serve_speculative_drafter", str, "ngram",
  "drafter when serve_speculative_k > 0: 'ngram' (self-drafting suffix "
  "lookup over the slot's own history — no extra model) or "
  "'ngram:<max_n>'; PagedDecodeEngine(drafter=...) also accepts any "
  "object with propose(tokens, k) -> tokens, the small-draft-model hook")
D("serve_model_path", str, "",
  "default checkpoint DIRECTORY for serve.openai_api.OpenAICompletions "
  "(model.safetensors + config.json + vocab.json + merges.txt — the "
  "model-hub layout, models/hub); explicit constructor args win")
D("serve_model_id", str, "",
  "model id the OpenAI-compatible endpoint advertises in /v1/models and "
  "completion responses; empty = the checkpoint directory's name")
D("serve_telemetry", bool, True,
  "serving telemetry plane (serve/telemetry.py): request-lifecycle "
  "histograms/counters/gauges (TTFT, inter-token latency, queue wait, "
  "request/error/preemption counters, KV-pool utilization, batch "
  "occupancy, spec accept rate — tagged by deployment/replica) plus the "
  "engine flight recorder. Read at engine/batcher construction in the "
  "replica process; off = zero per-token/per-step telemetry work")
D("serve_telemetry_recorder_events", int, 4096,
  "flight-recorder ring capacity: step-level engine events (admit, "
  "prefill_chunk, decode, verify, rollback, preempt, readmit, retire, "
  "eos) kept per process, oldest dropped first — the post-mortem window "
  "behind serve.telemetry.dump_timeline() / `ray_tpu timeline`; 0 "
  "disables the recorder while keeping the metrics")
D("serve_telemetry_push_s", float, 5.0,
  "min interval between a process's flight-recorder pushes to the head "
  "(piggybacked on replica stats/health polls; drain, engine faults and "
  "dump_timeline() force an immediate push)")
D("serve_kv_prefix_cache", bool, True,
  "keep full prompt blocks in a hash-trie after release so identical "
  "prompt prefixes (system prompts, few-shot headers) share physical "
  "blocks and skip prefill for the shared span; cache-held blocks are "
  "evicted LRU under pool pressure")
D("serve_kv_transfer", bool, True,
  "cluster-wide KV plane (serve/kv_transfer.py): replicas export cached "
  "prefix blocks on request and import peers' blocks before prefill, so "
  "a prefix computed anywhere in the deployment is a hit everywhere; "
  "any transfer failure falls back to local recompute — never wrong "
  "tokens. Off = every replica's PrefixCache stays private")
D("serve_kv_transfer_min_blocks", int, 1,
  "minimum full prompt blocks below which a replica does not attempt a "
  "remote prefix pull (the transfer round-trip must be worth more than "
  "the prefill it saves)")
D("serve_prefix_affinity", bool, False,
  "prefix-affinity routing: the controller aggregates a bounded LRU "
  "prefix->replica digest from replica stats and publishes it over "
  "long-poll; handles break power-of-two-choices ties toward the "
  "replica advertising the longest cached chain for the request's "
  "prefix hint. Plain load wins when queue depth diverges (see "
  "serve_prefix_affinity_max_skew) so affinity cannot create hotspots")
D("serve_prefix_affinity_max_skew", int, 2,
  "max in-flight-request excess the affinity replica may carry over the "
  "two-choices winner and still take the request; beyond it the load "
  "pick wins — the hotspot cap")
D("serve_prefix_hint_tokens", int, 64,
  "leading prompt tokens hashed into the prefix hint used by affinity "
  "routing and the replica-side digest; proxy, handle and replicas must "
  "agree, so this is config (not engine geometry)")
D("serve_prefix_digest_size", int, 512,
  "per-deployment cap on the controller's prefix->replica digest "
  "(bounded LRU: oldest hint evicted first)")
D("serve_weight_swap", bool, True,
  "live weight plane (serve/weight_swap.py): learners publish versioned "
  "param trees as bulk-plane objects, replicas subscribe over long-poll, "
  "pull + device_put by their own partition rules and hot-swap between "
  "engine steps — in-flight streams survive (recompute-on-readmit), the "
  "prefix cache flushes, and the transfer-sig version bumps so stale "
  "chain keys can never serve new-weight traffic. Off = subscribers "
  "never attach; publish() still works for manual pulls")
D("serve_weight_chunk_mb", int, 64,
  "per-leaf chunk size for published weights: leaves larger than this "
  "ship as multiple bulk-plane objects so pulls stripe across senders "
  "and a single giant leaf cannot serialize the swap; 0 = never chunk")
D("serve_weight_poll_timeout_s", float, 10.0,
  "long-poll timeout of the replica-side weight watcher (how long one "
  "poll parks on the weights channel before re-arming)")
D("serve_disaggregate", bool, False,
  "disaggregated prefill/decode default for kv_transfer.deploy_"
  "disaggregated(): prefill-tagged replicas run chunked prefill to "
  "completion and hand committed blocks to a decode replica over the "
  "transfer path; decode resumes token-for-token identically (greedy). "
  "The two pools scale on the existing autoscaling signals — block "
  "saturation (prefill) and batch occupancy (decode)")
D("train_dist_heartbeat_timeout_s", int, 30,
  "upper bound on detecting a dead jax.distributed gang peer: the "
  "coordination-service heartbeat interval/missing-count are derived "
  "from this, so a hard-killed rank parks the surviving ranks' shutdown "
  "barrier ~this long instead of jax's ~100s default — the gang-restart "
  "latency floor (train/trainer.py). 0 = keep jax's defaults")
D("train_dcn_grad_compression", str, "off",
  "gradient compression over the slow `dcn` axis of a multi-slice mesh "
  "(train/step.py): 'off' = fp32 all-reduce spanning (dcn, dp) as today; "
  "'int8' = full-precision reduce INSIDE the slice (ICI), then an int8 "
  "block-quantized exchange with error feedback across slices — ~4x "
  "fewer DCN bytes per step (util/collective/compress.py). Adds an "
  "error-feedback residual buffer to the optimizer state (checkpointed; "
  "restoring a pre-compression checkpoint zero-initializes it)")
D("train_dcn_grad_compression_block", int, 256,
  "quantization block size for train_dcn_grad_compression=int8: one "
  "shared fp32 scale per block crosses DCN alongside the int8 payload")
# --- TPU ---
D("tpu_chips_per_host", int, 4, "default TPU chips advertised per host when detected")
D("mesh_dryrun_platform", str, "cpu")

GLOBAL_CONFIG = Config()
