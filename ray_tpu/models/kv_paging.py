"""Paged KV-cache subsystem: block allocator, prefix reuse, preemption.

A max-length cache slab per slot would let HBM — not compute — cap
concurrency, every request paying for its worst-case length up front. The
decode engine here keeps a POOL of fixed-size token blocks instead (the
vLLM PagedAttention memory model) plus the host-side machinery that makes
the pool safe to oversubscribe:

  BlockAllocator    refcounted free-list over the physical blocks; block 0
                    is the reserved null block (padding writes and padded
                    table entries route there, never into live data).
  PrefixCache       hash-trie over FULL prompt blocks with chained keys:
                    identical system-prompt prefixes map to the same
                    physical blocks, so a prefix hit admits by increfing
                    blocks instead of recomputing prefill for the shared
                    span. Cache-held blocks are evicted LRU-leaf-first
                    under pool pressure.
  StatePool         the rows of a hybrid cache's recurrent-state pool (a
                    model with linear-attention layers keeps ONE state a
                    sequence beside the KV blocks of its attention layers):
                    a row per decode slot plus a free list of SNAPSHOT
                    rows. A snapshot hangs on the prefix-cache node of the
                    block boundary it was taken at; a prefix hit is usable
                    only up to the deepest node that holds one.
  PagedDecodeEngine the `ContinuousBatcher` engine contract (admit / step /
                    release) over the pool, plus:
                      can_admit(request)  worst-case block-budget admission
                      fork(src, dst)      share ALL blocks (copy-on-write
                                          isolates the forks on first
                                          divergent write)
                      take_preempted()    generations evicted under pool
                                          exhaustion, parked as
                                          recompute-on-readmit requests

Preemption contract: when a decode step needs blocks the pool cannot
supply (even after cache eviction), the NEWEST generations are preempted —
their blocks freed, their full token history parked — until the rest fit.
A parked generation readmits as a plain prefill of prompt + generated
tokens; with greedy sampling the resumed stream is token-for-token what
the uninterrupted run would have produced. The engine therefore never
OOMs the replica: admission past capacity degrades to recompute, not to a
crash.

Speculative decoding (`speculative_k > 0`) layers propose/verify/commit on
top of the same machinery: a drafter (models/speculative.py; self-drafting
n-gram lookup by default, any propose(tokens, k) object as the
small-draft-model hook) guesses up to k tokens per slot between steps, and
ONE batched verify step scores all k+1 positions (transformer.py
`paged_verify_step`). Accepted tokens commit through the normal block-table
append; the rejected tail is rolled back by truncating the slot's table —
freed blocks return to the allocator, and on int8 pools the partial last
block is requantized by the verify commit itself (it replays the
single-token RMW history for accepted tokens only). A step may therefore
emit 1..k+1 tokens per slot: step() returns token LISTS when speculation
is enabled. Greedy output is token-for-token identical to non-speculative
decode by construction (acceptance compares drafts against the model's own
argmax); speculation is greedy-only.

Host traffic: a dispatch of a model program (an admission's prefill chunk,
a decode step) costs the host ONE upload — everything the program needs
from the host, packed into one int32 array (transformer.py
`pack_prefill_inputs` / `pack_decode_inputs`) — ONE launch and ONE fetch of
one int32 vector (tokens, with experts their load counts, in logprob mode
the log-probabilities). The RNG key is split on the host only at
temperature > 0; greedy engines pass one device array made at construction.
stats()["host_transfers"] and ["rng_dispatches"] count all of it.

Not thread-safe: one loop thread (the batcher's) owns admit/step/release;
stats() reads are safe from other threads (plain int reads).
"""

from __future__ import annotations

import hashlib
import logging
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..util.profiling import span
from .transformer import (
    TransformerConfig,
    init_paged_kv_cache,
    init_params,
    make_copy_state,
    make_paged_decoder,
    pack_decode_inputs,
    pack_prefill_inputs,
    paged_kv_block_bytes,
    paged_state_row_bytes,
    refuse_on_latent_pool,
    refuse_on_state_pool,
    serving_params,
    split_host_row,
)

logger = logging.getLogger(__name__)


def default_prefill_buckets(max_seq_len: int) -> Tuple[int, ...]:
    """Powers of two up to max_seq_len (always including it): each bucket
    costs one prefill compile, padding within a bucket costs only FLOPs."""
    buckets = []
    b = 16
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq_len)
    return tuple(buckets)


class InsufficientBlocksError(RuntimeError):
    """The pool cannot cover an admission's block need even after cache
    eviction. Raised by admit(); ContinuousBatcher parks the request for
    retry instead of failing it (blocks free as generations retire)."""


class BlockAllocator:
    """Refcounted fixed pool of KV blocks. Block 0 is the permanently-held
    null block: padded block-table entries and masked token writes target
    it, so it is never handed out."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(f"need >= 2 blocks (1 is the null block), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._ref = np.zeros(self.num_blocks, np.int32)
        self._ref[0] = 1  # null block: never allocated, never freed
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_usable(self) -> int:
        return self.num_blocks - 1

    def alloc(self, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise InsufficientBlocksError(
                f"need {n} KV blocks, {len(self._free)} free"
            )
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise ValueError(f"incref of free block {block}")
        self._ref[block] += 1

    def decref(self, block: int) -> None:
        if self._ref[block] <= 0:
            raise ValueError(f"decref of free block {block}")
        self._ref[block] -= 1
        if self._ref[block] == 0:
            self._free.append(block)

    def refcount(self, block: int) -> int:
        return int(self._ref[block])


class StatePool:
    """Rows of the recurrent-state pool (transformer.init_paged_kv_cache,
    `STATE_LEAVES`): rows 0..n_slots-1 belong to the decode slots, row for
    slot; the `n_snapshots` rows behind them are handed out as snapshots —
    copies of a slot's row at a block boundary, kept on a prefix-cache node
    (`PrefixCache.attach_snapshot`) until that node is dropped or the pool
    needs the row for a newer one. This is the seam a cache that is not a
    chain of blocks plugs into: rows are reserved (`take`), committed to a
    node, copied for a fork or a restore (the engine's `copy_state`
    program), and released (`give`); what a row costs — one sequence,
    whatever its length — is the engine's `state_row_bytes`."""

    def __init__(self, n_slots: int, n_snapshots: int):
        if n_snapshots < 1:
            raise ValueError(
                f"a state pool needs >= 1 snapshot row, got {n_snapshots}")
        self.n_slots = int(n_slots)
        self.rows_total = self.n_slots + int(n_snapshots)
        self._free: List[int] = list(
            range(self.rows_total - 1, self.n_slots - 1, -1))

    @property
    def num_free(self) -> int:
        return len(self._free)

    def take(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def give(self, row: int) -> None:
        self._free.append(int(row))


class PrefixCache:
    """Hash-trie over full prompt blocks.

    A node's key is sha1(parent_key || block tokens), so a chain of keys
    identifies a prompt prefix by content AND position — two prompts share
    a node iff they share every token up to and including that block. The
    cache holds its own reference on every registered block; a block whose
    only reference is the cache's (refcount 1) is evictable, leaf-first in
    LRU order so chains never dangle.

    On a hybrid cache (`state_pool` given) a node may also hold a SNAPSHOT:
    the row of the state pool with the recurrent state after the node's
    last token ("snap"). A snapshot lives and dies with its node — node
    eviction and `flush` hand the row back — and when the state pool has no
    free row the oldest snapshot that no admission ever restored is dropped
    first, then the least recently used of the restored ones: the few
    histories every session returns to outlive the tails of finished
    requests, however many of those pass."""

    def __init__(self, allocator: BlockAllocator, block_tokens: int,
                 state_pool: Optional[StatePool] = None):
        self._alloc = allocator
        self.block_tokens = int(block_tokens)
        self._state = state_pool
        # key -> {"block": int, "parent": key, "ts": int} (+ "snap": row,
        # "used": restores, on a hybrid cache)
        self._nodes: Dict[bytes, Dict[str, Any]] = {}
        self._children: Dict[bytes, set] = {}
        self._clock = 0
        self.hits = 0
        self.evictions = 0
        self.flushes = 0
        self.snapshot_evictions = 0

    def __len__(self) -> int:
        return len(self._nodes)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _child_key(self, parent: bytes, block_tokens: np.ndarray) -> bytes:
        h = hashlib.sha1()
        h.update(parent or b"root")
        h.update(np.ascontiguousarray(block_tokens, np.int32).tobytes())
        return h.digest()

    def _chain(self, prompt: np.ndarray, max_blocks: int):
        bt = self.block_tokens
        key = b""
        for bi in range(max_blocks):
            key = self._child_key(key, prompt[bi * bt:(bi + 1) * bt])
            node = self._nodes.get(key)
            if node is None:
                return
            yield key, node

    def lookup(self, prompt: np.ndarray, max_blocks: int) -> List[int]:
        """Longest cached chain of full blocks matching the prompt prefix;
        returns the physical block ids (LRU-touched, NOT increfed — the
        caller takes its references)."""
        out = []
        for _, node in self._chain(prompt, max_blocks):
            node["ts"] = self._tick()
            out.append(node["block"])
        if out:
            self.hits += 1
        return out

    def lookup_snapshot(self, prompt: np.ndarray, max_blocks: int,
                        touch: bool = True) -> Tuple[List[int], Optional[int]]:
        """The hybrid cache's lookup: the matching chain cut at its DEEPEST
        node that holds a snapshot -> (its blocks, that snapshot's row), or
        ([], None) when no node of the chain has one — keys and values
        without the state after them cannot be resumed from. `touch`
        LRU-touches the whole matching chain and counts the hit (off for
        admission budgeting)."""
        blocks, depth, found = [], 0, None
        for _, node in self._chain(prompt, max_blocks):
            if touch:
                node["ts"] = self._tick()
            blocks.append(node["block"])
            if node.get("snap") is not None:
                depth, found = len(blocks), node
        if found is None:
            return [], None
        if touch:
            found["used"] = found.get("used", 0) + 1
            self.hits += 1
        return blocks[:depth], found["snap"]

    def _node_at(self, prompt: np.ndarray, n_blocks: int):
        """the node of the prompt's first `n_blocks` blocks, or None."""
        chain = list(self._chain(prompt, n_blocks))
        return chain[-1][1] if len(chain) == n_blocks else None

    def attach_snapshot(self, prompt: np.ndarray, n_blocks: int,
                        row: Optional[int] = None) -> Optional[int]:
        """Hang a snapshot on the node of the prompt's first `n_blocks`
        blocks (registered already): `row` if the caller has filled one,
        else a row reserved here, which the caller must now copy the state
        into -> the row, or None: the node already holds a snapshot, or
        there is no such node. A full state pool drops a snapshot first
        (`steal_snapshot`)."""
        node = self._node_at(prompt, n_blocks) if n_blocks else None
        if node is None or node.get("snap") is not None:
            return None
        if row is None:
            row = self._state.take()
        if row is None:
            row = self.steal_snapshot(keep=node)
        node["snap"] = row
        return row

    def steal_snapshot(self, keep=None) -> Optional[int]:
        """Drop one node's snapshot for its row (the node keeps its block):
        the oldest that no admission restored, else the least recently
        used. None when no node (but `keep`) holds one."""
        victim = min(
            (n for n in self._nodes.values()
             if n.get("snap") is not None and n is not keep),
            key=lambda n: (n.get("used", 0) > 0, n["ts"]), default=None)
        if victim is None:
            return None
        row, victim["snap"] = victim["snap"], None
        self.snapshot_evictions += 1
        return row

    def snapshots(self) -> int:
        return sum(1 for n in list(self._nodes.values())
                   if n.get("snap") is not None)

    def _drop_snapshot(self, node) -> None:
        if node.get("snap") is not None:
            self._state.give(node["snap"])
            node["snap"] = None
            self.snapshot_evictions += 1

    def match_count(self, prompt: np.ndarray, max_blocks: int) -> int:
        """lookup() length without the LRU touch (admission budgeting)."""
        return sum(1 for _ in self._chain(prompt, max_blocks))

    def match_blocks(self, prompt: np.ndarray, max_blocks: int) -> List[int]:
        """lookup() without the LRU touch (admission budgeting)."""
        return [node["block"] for _, node in self._chain(prompt, max_blocks)]

    def register(self, prompt: np.ndarray, blocks: Sequence[int]) -> None:
        """Insert the prompt's first len(blocks) full blocks. New nodes
        incref their block (the cache's own reference); existing nodes are
        only LRU-touched (their block is already the canonical one)."""
        key = b""
        for bi, block in enumerate(blocks):
            parent = key
            key = self._child_key(
                key, prompt[bi * self.block_tokens:(bi + 1) * self.block_tokens]
            )
            node = self._nodes.get(key)
            if node is None:
                self._nodes[key] = {"block": int(block), "parent": parent,
                                    "ts": self._tick()}
                self._children.setdefault(parent, set()).add(key)
                self._alloc.incref(int(block))
            else:
                node["ts"] = self._tick()

    def evictable(self) -> int:
        """Blocks the cache could eventually free: held only by the cache
        (refcount 1). Counts non-leaves too — leaf-first eviction reaches
        them once their children go. Safe to call off the loop thread
        (stats polling): iterates an atomic snapshot of the node table."""
        return sum(
            1 for n in list(self._nodes.values())
            if self._alloc.refcount(n["block"]) == 1
        )

    def evict(self, n: int) -> int:
        """Free up to n blocks, LRU childless-first; returns blocks freed.

        One scan collects every current victim (childless, cache-only) and
        evicts LRU-first from that batch; the outer loop re-scans only when
        a whole batch was consumed and more is needed (evicting leaves can
        expose their parents) — O(passes * nodes), not O(n * nodes)."""
        freed = 0
        while freed < n:
            candidates = sorted(
                (node["ts"], key) for key, node in self._nodes.items()
                if not self._children.get(key)
                and self._alloc.refcount(node["block"]) == 1
            )
            if not candidates:
                break
            for _, key in candidates:
                if freed >= n:
                    break
                node = self._nodes.pop(key)
                self._children.get(node["parent"], set()).discard(key)
                self._children.pop(key, None)
                self._alloc.decref(node["block"])
                self._drop_snapshot(node)
                self.evictions += 1
                freed += 1
        return freed

    def flush(self) -> int:
        """Drop EVERY node unconditionally — the weight-hot-swap path:
        cached KV was computed under the OLD weights and must never be
        spliced under a new-weight admission. Only the cache's own
        references are released; a block shared with a live slot simply
        loses the cache ref and frees when the slot retires. Returns the
        number of nodes dropped."""
        n = len(self._nodes)
        for node in self._nodes.values():
            self._alloc.decref(node["block"])
            self._drop_snapshot(node)
        self._nodes.clear()
        self._children.clear()
        self.flushes += 1
        return n


class PagedDecodeEngine:
    """Block-granular KV-cache decode engine (module docstring has the
    architecture): the decode engine, ContinuousBatcher's admit / step /
    release / stats contract plus the paging APIs the batcher discovers
    by duck-typing: can_admit, take_preempted.

    `attention_impl` is decided here, once: None (every caller but the
    tests) is "fused" on a TPU and "gather" elsewhere — the block walk of
    ops/paged_attention.py (Pallas kernel or XLA twin, by backend) against
    the gather-window step that is the tests' exact reference. `stats()`
    reports the choice (`attention_impl`, `attention_kernel`, `platform`).

    What the engine holds (`self.params`): the matmul weights, `embed` and
    `unembed` in `cfg.dtype`, the norm scales in float32 —
    `transformer.serving_params` of whatever tree it is given, at
    construction and at every `set_params`. A float32 tree (a trainer's
    masters, `init_params`) is cast ONCE on the device when the engine
    takes it, not inside every prefill and decode step; the caller's tree
    is left alone (a tree the engine built itself from `seed` is freed
    leaf by leaf as it is cast). `stats()` reports `param_bytes` and
    `param_dtype` of the held tree."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params=None,
        *,
        max_batch_size: int = 8,
        rules=None,
        mesh=None,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        default_max_new_tokens: int = 64,
        max_seq_len: Optional[int] = None,
        prefill_buckets: Optional[Sequence[int]] = None,
        seed: int = 0,
        block_tokens: Optional[int] = None,
        num_blocks: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        kv_cache_dtype: Optional[str] = None,
        attention_impl: Optional[str] = None,
        pool_bytes: Optional[int] = None,
        speculative_k: Optional[int] = None,
        drafter=None,
        prefill_chunk_tokens: Optional[int] = None,
        telemetry=None,
        model_id: Optional[str] = None,
        logprobs: bool = False,
        n_snapshots: Optional[int] = None,
    ):
        import jax
        import jax.numpy as jnp

        from ray_tpu._private.config import GLOBAL_CONFIG as gcfg

        # telemetry plane (serve/telemetry.py): None resolves the process
        # singleton per the serve_telemetry flag, False disables for this
        # engine (benches compare on-vs-off), an object is used AS-IS.
        # The None case only consults serve telemetry when that module is
        # ALREADY imported (serving processes are — Replica.__init__
        # loads it before user code builds engines): a bare engine in a
        # training/bench process must not pull the whole serve package in
        # at construction, and an injected object can never be dropped by
        # a serve import fault.
        if telemetry is None:
            try:
                import sys as _sys

                tmod = _sys.modules.get("ray_tpu.serve.telemetry")
                telemetry = (
                    tmod.get_telemetry() if tmod is not None else None
                )
            except Exception:
                telemetry = None
        self._tel = telemetry or None
        self._rec = self._tel.recorder if self._tel is not None else None

        self.cfg = cfg
        self.max_batch_size = int(max_batch_size)
        self.eos_id = eos_id
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        if self.max_seq_len > cfg.max_seq_len:
            raise ValueError("max_seq_len exceeds the model's rope tables")
        self.block_tokens = int(block_tokens or gcfg.serve_kv_block_tokens)
        bt = self.block_tokens
        self.blocks_per_slot = -(-self.max_seq_len // bt)

        kv_cache_dtype = kv_cache_dtype or gcfg.serve_kv_cache_dtype
        if kv_cache_dtype not in ("fp", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'fp' or 'int8', got {kv_cache_dtype!r}"
            )
        self.kv_cache_dtype = kv_cache_dtype
        kv_dtype = jnp.int8 if kv_cache_dtype == "int8" else cfg.dtype
        self.kv_block_bytes = paged_kv_block_bytes(cfg, bt, kv_dtype)
        # a hybrid cache: the model's linear layers keep one recurrent
        # state a sequence in a state pool beside the KV pool (StatePool)
        self.hybrid = bool(cfg.layers_of("linear"))
        self.state_row_bytes = paged_state_row_bytes(cfg)
        if n_snapshots is not None and not self.hybrid:
            raise ValueError(
                "n_snapshots given but the model has no linear layers: "
                "there is no state pool to size")

        # cross-replica transfer identity (serve/kv_transfer.py): two
        # engines produce matching export keys iff they agree on every
        # byte-layout-relevant knob — model identity, block geometry,
        # pool storage dtype, layer/head shape, and (once a hot swap has
        # happened) the WEIGHT VERSION. The signature SEEDS the
        # content-addressed key chain, so keys minted under a different
        # model / dtype / geometry / weight version can never collide
        # with this pool's (the int8-into-fp poison case — and the
        # stale-weights-KV poison case — are unrepresentable by key
        # construction, not merely checked at import).
        self.model_id = str(
            model_id if model_id is not None else gcfg.serve_model_id or ""
        )
        self._kv_store_dtype = np.dtype(kv_dtype).name
        self.weight_version = 0
        self.transfer_sig = self._compute_transfer_sig()

        # the device this engine computes on, resolved once and reported in
        # stats(): nothing below may choose a path from the backend again
        self._device = jax.devices()[0]
        self.platform = self._device.platform
        self.device_kind = self._device.device_kind
        if self.platform != "tpu":
            from ray_tpu._private.spawn import detect_tpu_chips

            if detect_tpu_chips():
                # legitimate for CPU rollout workers; for a serving replica
                # it means the deployment was not granted the node's TPU
                logger.warning(
                    "PagedDecodeEngine is computing on %r in a process "
                    "pinned off this host's TPU (stats()['platform'] says "
                    "so); a replica meant for the chip must hold a TPU "
                    "resource", self.platform,
                )
        if attention_impl is None:
            # the fused walk is the TPU fast path; the gather step stays
            # the exact (and cheapest-to-dispatch) path on CPU CI hosts
            attention_impl = "fused" if self.platform == "tpu" else "gather"
        if attention_impl not in ("gather", "fused"):
            # fail at construction, not at the first decode step's trace
            raise ValueError(
                "attention_impl must be None (by platform), 'gather' or "
                f"'fused', got {attention_impl!r}"
            )
        self.attention_impl = attention_impl
        # what actually attends: the gather step, or under "fused" what
        # ops.paged_attention picks from the backend — the Pallas kernel
        # compiled by Mosaic on a TPU, its chunked XLA twin elsewhere
        self.attention_kernel = (
            "gather" if attention_impl == "gather"
            else "pallas" if self.platform == "tpu" else "xla"
        )

        prefill_chunk_tokens = int(
            gcfg.serve_prefill_chunk_tokens if prefill_chunk_tokens is None
            else prefill_chunk_tokens
        )
        if prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0 (0 = whole-prompt "
                f"prefill), got {prefill_chunk_tokens}"
            )
        self.prefill_chunk_tokens = prefill_chunk_tokens

        speculative_k = int(
            gcfg.serve_speculative_k if speculative_k is None
            else speculative_k
        )
        if speculative_k < 0:
            raise ValueError(f"speculative_k must be >= 0, got {speculative_k}")
        self.speculative_k = speculative_k
        # a latent (MLA) pool: what it does not support fails here, by name
        refuse_on_latent_pool(cfg, kv_dtype=kv_dtype, mesh=mesh,
                              speculative_k=speculative_k)
        # ... and so does a hybrid cache
        refuse_on_state_pool(cfg, kv_dtype=kv_dtype, mesh=mesh,
                             speculative_k=speculative_k)
        self.drafter = None
        if speculative_k:
            if temperature > 0.0:
                # acceptance compares drafts against the model's argmax;
                # per-position sampling would not preserve the temperature
                # distribution — refuse at construction, not mid-stream
                raise ValueError(
                    "speculative decoding is greedy-only (temperature 0), "
                    f"got temperature={temperature}"
                )
            from .speculative import resolve_drafter

            self.drafter = resolve_drafter(
                drafter if drafter is not None
                else gcfg.serve_speculative_drafter
            )
            if self.drafter is None:
                raise ValueError(
                    f"speculative_k={speculative_k} needs a drafter, but "
                    "the drafter resolved to 'off'"
                )
            # draft lengths bucket to powers of two (plus k itself) so a
            # jittery drafter compiles O(log k) verify shapes, not O(k)
            buckets, b = [], 1
            while b < speculative_k:
                buckets.append(b)
                b *= 2
            buckets.append(speculative_k)
            self._k_buckets = tuple(buckets)
        elif drafter is not None:
            # same strictness as the other conflicting-knob pairs: a
            # drafter that can never run is a misconfiguration, not a noop
            raise ValueError(
                "drafter given but speculative_k is 0 — pass "
                "speculative_k > 0 (or serve_speculative_k) to enable "
                "speculative decoding"
            )

        # per-token logprobs (generation-based RL, rl/llm): each emitted
        # token becomes a (token, logprob) pair — the logprob of the
        # SAMPLED id under the exact distribution the sampler drew from
        # (same vocab_pad masking, same temperature scaling, fp32), so a
        # dense re-forward reproduces it bit-for-tolerance. The programs
        # compute it themselves and hand it back beside the token (one
        # fetch). Restricted to speculative_k == 0: the verify step commits
        # accepted drafts without returning per-position logits.
        self.logprobs = bool(logprobs)
        if self.logprobs and self.speculative_k:
            raise ValueError(
                "logprobs=True requires speculative_k == 0 — the verify "
                "step returns no per-position logits to score"
            )
        self.temperature = float(temperature)

        if num_blocks is not None and pool_bytes is not None:
            raise ValueError(
                "num_blocks and pool_bytes are conflicting pool sizes — "
                "pass one (the byte budget is a ceiling, the block count "
                "a floor)"
            )
        if num_blocks is None and pool_bytes is None:
            pool_bytes = int(gcfg.serve_kv_pool_mb) * (1 << 20) or None
        from_budget = num_blocks is None and pool_bytes is not None
        if from_budget:
            # byte-budget sizing: int8 pools fit ~2x the blocks of bf16
            # ones — capacity and autoscaling see the doubling directly.
            # The budget is a CEILING (the operator's HBM headroom), so
            # the null block counts against it and a budget that cannot
            # hold it plus one usable block is an error, not a tiny pool
            num_blocks = int(pool_bytes) // self.kv_block_bytes
            if num_blocks < 2:
                raise ValueError(
                    f"pool_bytes={pool_bytes} holds {num_blocks} blocks of "
                    f"{self.kv_block_bytes} bytes; need >= 2 (null + 1 usable)"
                )
        if num_blocks is None:
            num_blocks = int(gcfg.serve_kv_cache_blocks) or 0
        if not num_blocks:
            # dense-equivalent HBM budget (+1 for the null block): paging
            # then wins by oversubscription (admission past this is what
            # prefix reuse + preemption make safe)
            num_blocks = 1 + self.max_batch_size * self.blocks_per_slot
        if mesh is not None and rules is not None:
            # the pool's block dim shards on the "batch" mesh axes: every
            # shard must be whole — round DOWN under a byte budget (the
            # budget is a ceiling) and UP otherwise (counts are a floor)
            axes = rules.mesh_axes("batch") or ()
            if isinstance(axes, str):
                axes = (axes,)
            m = 1
            for a in axes:
                m *= dict(mesh.shape)[a]
            if from_budget:
                num_blocks = (num_blocks // m) * m
                if num_blocks < 2:
                    raise ValueError(
                        f"pool_bytes={pool_bytes} cannot hold a whole "
                        f"{m}-shard block set plus the null block"
                    )
            else:
                num_blocks = -(-num_blocks // m) * m
        self.num_blocks = int(num_blocks)

        # a tree built here is nobody else's: each leaf is cast as it is drawn
        self.params = (
            init_params(jax.random.PRNGKey(seed), cfg, held=True)
            if params is None else serving_params(cfg, params)
        )
        # swap-time device_put (serve/weight_swap.py) re-distributes a
        # pulled host tree by THIS engine's partition rules
        self._rules = rules
        self._mesh = mesh
        self.allocator = BlockAllocator(self.num_blocks)
        if prefix_cache is None:
            prefix_cache = bool(gcfg.serve_kv_prefix_cache)
        self.state_pool = None
        if self.hybrid:
            # twice the slots by default: every slot's running tail and as
            # many snapshots on cached prefixes again
            self.state_pool = StatePool(
                self.max_batch_size,
                2 * self.max_batch_size if n_snapshots is None
                else int(n_snapshots))
            self._copy_state = make_copy_state()
        self.prefix_cache = (
            PrefixCache(self.allocator, bt, self.state_pool)
            if prefix_cache else None
        )
        self.pool = init_paged_kv_cache(
            cfg, self.num_blocks, bt, mesh=mesh, rules=rules, dtype=kv_dtype,
            state_rows=self.state_pool.rows_total if self.hybrid else 0,
        )
        self._prefill, self._decode_step, self._verify_step, self._copy_blocks = (
            make_paged_decoder(
                cfg, rules=rules, mesh=mesh, temperature=temperature,
                block_tokens=bt, kv_dtype=kv_dtype,
                attention_impl=attention_impl, logprobs=self.logprobs,
            )
        )
        buckets = sorted(set(
            prefill_buckets or default_prefill_buckets(self.max_seq_len)
        ))
        # readmission after preemption prefills prompt + generated-so-far,
        # which can be LONGER than any original prompt: extend the caller's
        # bucket table (doubling) until it covers max_seq_len, or a parked
        # generation could never be readmitted
        b = buckets[-1]
        while b < self.max_seq_len:
            b = min(b * 2, self.max_seq_len)
            buckets.append(b)
        self.buckets = tuple(buckets)
        self._key = jax.random.PRNGKey(seed + 1)
        # the key of every dispatch whose sample is greedy or thrown away:
        # one device array, made once (`_sample_key`) — and replicated once
        # over the mesh of a sharded pool, not by every launch
        self._fixed_key = jax.random.PRNGKey(0)
        if mesh is not None and rules is not None:
            self._fixed_key = jax.device_put(
                self._fixed_key,
                jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))

        B = self.max_batch_size
        self._tables = np.zeros((B, self.blocks_per_slot), np.int32)
        self._row_blocks = np.zeros(B, np.int32)  # allocated entries per row
        self._live = np.zeros(B, bool)
        self._positions = np.zeros(B, np.int32)
        self._last_tokens = np.zeros(B, np.int32)
        self._new_counts = np.zeros(B, np.int64)
        self._max_new = np.full(B, self.default_max_new_tokens, np.int64)
        self._history: List[Optional[List[int]]] = [None] * B
        # chunked prefill: the slot's FULL prompt while its prefill is
        # still streaming in chunks (committed span = _positions[slot]);
        # None once the slot is generating
        self._chunk_state: List[Optional[np.ndarray]] = [None] * B
        self._admit_seq = np.zeros(B, np.int64)
        self._seq = 0
        self._preempted: List[Tuple[int, Dict[str, Any]]] = []
        # logprob of the pending first sampled token per slot (set by the
        # completing prefill chunk, read by admit()/step() when emitting)
        self._lp_pending = np.zeros(B, np.float64)
        # hybrid cache: the slot's running TAIL snapshot — (row, tokens):
        # its state as of its last whole block, taken when decode crossed
        # that boundary; it goes onto the prefix cache when the slot's
        # blocks are released
        self._tail_snap: List[Optional[Tuple[int, int]]] = [None] * B
        self.state_snapshots = 0
        self.state_restores = 0

        # counters (bench/observability/tests)
        self.tokens_generated = 0
        self.prefills = 0
        self.prefill_tokens = 0
        self.prefill_chunks = 0     # paged-prefill dispatches (>= prefills)
        self.chunked_prefills = 0   # admissions that streamed in chunks
        self.decode_steps = 0
        # what the model programs cost the host: RNG splits dispatched (none
        # at temperature 0), the dispatches of a model program (prefill,
        # decode, verify), the NumPy arrays handed to them (each one is a
        # host -> device copy the launch makes) and the answers read back
        self.rng_dispatches = 0
        self.model_dispatches = 0
        self.uploads = 0
        self.fetches = 0
        # sparse-expert models: see the decode step
        self.moe_pairs = 0
        self.moe_pairs_held = 0  # of those, the pairs on experts held here
        self.moe_hottest = 0
        self.moe_touched = 0
        # summed over the decode steps: the live slots' live blocks (what a
        # paged kernel has to visit) and the block table's size B x Nmax
        self.kv_blocks_walked = 0
        self.kv_table_blocks = 0
        self.prefix_hits = 0
        self.prefix_tokens_reused = 0
        self.preemptions = 0
        self.cow_copies = 0
        self.prefill_shapes: set = set()  # (ctx_blocks, suffix_blocks) keys
        # speculative decoding counters
        self.spec_steps = 0
        self.spec_slot_steps = 0  # (slot, verify-step) participations
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_shapes: set = set()  # K1 widths the verify step compiled
        # cross-replica KV transfer counters (serve/kv_transfer.py)
        self.kv_exports = 0
        self.kv_blocks_exported = 0
        self.kv_imports = 0
        self.kv_blocks_imported = 0
        self.kv_tokens_imported = 0
        self.kv_import_rejects = 0
        # live weight hot-swap counters (serve/weight_swap.py)
        self.weight_swaps = 0

    # ------------------------------------------------------------- internals

    def _compute_transfer_sig(self) -> bytes:
        sig = hashlib.sha1()
        sig.update(b"ray_tpu.kv_transfer.v1|")
        sig.update(self.model_id.encode())
        sig.update(
            f"|bt={self.block_tokens}|kv={self.kv_cache_dtype}"
            f"|sd={self._kv_store_dtype}"
            f"|L={self.cfg.n_layers}|H={self.cfg.n_kv_heads}"
            f"|D={self.cfg.d_head}".encode()
        )
        if self.cfg.kv_lora_rank:
            # a latent pool: the row's geometry joins the key space, so a
            # per-head and a latent replica of one model_id refuse each other
            sig.update(
                f"|latent={self.cfg.kv_lora_rank}+{self.cfg.qk_rope_head_dim}"
                f"/{self.cfg.latent_row}".encode())
        if self.hybrid:
            # a hybrid cache: the state's geometry joins the key space, so
            # unlike replicas refuse each other
            c = self.cfg
            sig.update(
                f"|state={c.layers_of('linear')}x{c.linear_n_heads}"
                f"x{c.linear_d_k}x{c.linear_d_v}/{self.state_row_bytes}B"
                f"|conv={c.linear_conv_kernel}"
                f"|kvL={c.layers_of('full')}".encode())
        # version 0 (never swapped) keeps the original byte layout, so
        # engines that never hot-swap interoperate with older peers; any
        # swap moves the whole key space
        if self.weight_version:
            sig.update(f"|wv={self.weight_version}".encode())
        return sig.digest()

    def _sample_key(self, sampled: bool = True):
        """The key of one model-program dispatch. At temperature > 0 a
        dispatch whose sample is kept takes the next key of the engine's
        stream (ONE split per completing admission and per decode step);
        a greedy sampler never reads its key, and a throwaway sample must
        not consume the stream: both get the one fixed key."""
        if not (sampled and self.temperature > 0.0):
            return self._fixed_key
        self.rng_dispatches += 1
        self._key, sub = jax.random.split(self._key)
        return sub

    def _fetch(self, out) -> np.ndarray:
        """One device -> host copy of a model program's answer (it waits
        for the program)."""
        self.fetches += 1
        return np.asarray(out)

    def _bucket(self, length: int) -> int:
        for b in self.buckets:
            if b >= length:
                return b
        raise ValueError(
            f"prompt of {length} tokens exceeds max_seq_len {self.max_seq_len}"
        )

    def _ctx_bucket_blocks(self, ctx_len: int) -> int:
        """Pad the context block count to the same bucket boundaries as
        prompt lengths, so a prefix hit of 65 and one of 120 tokens reuse
        ONE paged-prefill compilation instead of compiling per block-count."""
        if ctx_len <= 0:
            return 0
        bucketed = min(self._bucket(ctx_len), self.max_seq_len)
        return min(-(-bucketed // self.block_tokens), self.blocks_per_slot)

    def _done(self, slot: int, token: int) -> bool:
        if self.eos_id is not None and token == self.eos_id:
            return True
        if self._new_counts[slot] >= self._max_new[slot]:
            return True
        return int(self._positions[slot]) >= self.max_seq_len

    # ------------------------------------------------ hybrid cache: state

    def _hang_snapshot(self, slot: int, tokens: np.ndarray, depth: int,
                       row: Optional[int] = None) -> Optional[int]:
        """Register the slot's first `depth` tokens' blocks (a block
        multiple; the slot's table must still hold them) and hang a
        snapshot on their node: `row` if it is filled already, else a row
        reserved for the caller to fill -> the row, or None (the node holds
        a snapshot already)."""
        n = depth // self.block_tokens
        self.prefix_cache.register(
            tokens, [int(b) for b in self._tables[slot, :n]])
        return self.prefix_cache.attach_snapshot(tokens, n, row)

    def _snapshot(self, slot: int, tokens: np.ndarray, depth: int) -> None:
        """Leave a snapshot of the slot's state row, which has consumed
        exactly the first `depth` tokens, on their prefix-cache node."""
        row = self._hang_snapshot(slot, tokens, depth)
        if row is not None:
            self._copy_rows(slot, row, "engine.state_snapshot", depth)
            self.state_snapshots += 1

    def _copy_rows(self, src: int, dst: int, name: str, tokens: int) -> None:
        with span(name, slot=min(src, dst), tokens=int(tokens)):
            self.pool = self._copy_state(
                self.pool, np.asarray([src], np.int32),
                np.asarray([dst], np.int32))

    def _keep_tail(self, slot: int) -> None:
        """Decode has just carried the slot onto a block boundary: copy its
        row into the slot's tail snapshot (one row a slot, overwritten as
        the sequence grows; taken from the free rows, else from the cache's
        oldest snapshot)."""
        held = self._tail_snap[slot]
        row = held[0] if held else self.state_pool.take()
        if row is None and self.prefix_cache is not None:
            row = self.prefix_cache.steal_snapshot()
        if row is None:
            return
        depth = int(self._positions[slot])
        self._copy_rows(slot, row, "engine.state_snapshot", depth)
        self.state_snapshots += 1
        self._tail_snap[slot] = (row, depth)

    def _commit_tail(self, slot: int) -> None:
        """The slot's blocks are about to go: its tail snapshot moves onto
        the prefix-cache node of its depth (a finished sequence leaves its
        state at its last whole block; a preempted one finds it again at
        re-admission), or back to the free rows."""
        held, self._tail_snap[slot] = self._tail_snap[slot], None
        if held is None:
            return
        row, depth = held
        hist = self._history[slot]
        if self.prefix_cache is not None and hist is not None:
            tokens = np.asarray(hist[:depth], np.int32)
            if self._hang_snapshot(slot, tokens, depth, row) is not None:
                return
        self.state_pool.give(row)

    def _release_blocks(self, slot: int) -> None:
        if self.hybrid:
            self._commit_tail(slot)
        for bi in range(int(self._row_blocks[slot])):
            b = int(self._tables[slot, bi])
            if b:
                self.allocator.decref(b)
        self._tables[slot, :] = 0
        self._row_blocks[slot] = 0
        # a released row is a never-used row again: the decode program
        # runs every row, and a row left with its last token and length
        # makes a step's cost (the experts its stale token routes to, the
        # null blocks its stale length walks) depend on what finished
        # streams held
        self._last_tokens[slot] = 0
        self._positions[slot] = 0
        self._live[slot] = False
        self._history[slot] = None
        self._chunk_state[slot] = None

    def _reclaim(self, need: int) -> None:
        """Evict cache-only blocks until `need` blocks are free (best
        effort — callers decide between raising and preempting)."""
        short = need - self.allocator.num_free
        if short > 0 and self.prefix_cache is not None:
            self.prefix_cache.evict(short)

    def _preempt(self, slot: int) -> None:
        remaining = int(self._max_new[slot] - self._new_counts[slot])
        parked = {
            # full history (prompt + generated, incl. the pending last
            # token): readmission prefills it and the NEXT sampled token
            # continues the stream exactly where it stopped (greedy)
            "tokens": list(self._history[slot] or []),
            "max_new_tokens": max(1, remaining),
        }
        self._preempted.append((slot, parked))
        self.preemptions += 1
        if self._rec is not None:
            self._rec.record("preempt", slot=slot,
                             args={"tokens": len(parked["tokens"])})
        self._release_blocks(slot)

    # ----------------------------------------------------------- engine API

    def worst_case_blocks(self, prompt_len: int, max_new: int) -> int:
        """Blocks a request can EVER need: its full prompt + max_new span,
        capped at max_seq_len. The single formula behind admission (both
        the hard-fail and the budget check) and the serving API's
        submit-time validation — one definition, so a doomed request is
        judged identically at every gate."""
        span = min(int(prompt_len) + int(max_new), self.max_seq_len)
        return -(-span // self.block_tokens)

    def can_admit(self, request: Dict[str, Any]) -> bool:
        """Worst-case block-budget admission check: free + cache-evictable
        blocks must cover the request's full prompt + max_new_tokens span,
        minus the blocks a prefix hit would reuse. The batcher calls this
        BEFORE taking a slot, so over-capacity requests queue instead of
        thrashing the pool."""
        prompt = np.asarray(request["tokens"], np.int32)
        length = int(prompt.size)
        if length == 0 or length > self.max_seq_len:
            return True  # let admit() raise the real validation error
        mnt = request.get("max_new_tokens")
        mnt = self.default_max_new_tokens if mnt is None else max(1, int(mnt))
        worst = self.worst_case_blocks(length, mnt)
        if worst > self.allocator.num_usable:
            # can NEVER fit: report admissible so the batcher routes it to
            # admit(), whose worst-case check fails it with the hard
            # ValueError — parking it would wedge the admission line
            return True
        reusable = 0
        evictable = 0
        if self.prefix_cache is not None:
            evictable = self.prefix_cache.evictable()
            if length > 1:
                cap = (length - 1) // self.block_tokens
                # a hybrid cache reuses only up to its deepest snapshot
                hits = (self.prefix_cache.lookup_snapshot(
                            prompt, cap, touch=False)[0] if self.hybrid
                        else self.prefix_cache.match_blocks(prompt, cap))
                reusable = len(hits)
                # a cache-only hit block is counted in evictable() but
                # admission will PIN it (incref), not evict it — counting
                # it in both the reuse discount and the eviction budget
                # would approve admissions that deterministically fail
                evictable -= sum(
                    1 for b in hits if self.allocator.refcount(b) == 1
                )
        budget = self.allocator.num_free + max(0, evictable)
        return budget >= worst - reusable

    def admit(
        self, slot: int, request: Dict[str, Any]
    ) -> Tuple[Optional[int], bool]:
        """Prefill `request` into `slot`, reusing cached prefix blocks.

        With `prefill_chunk_tokens > 0` a prompt longer than one chunk
        admits CHUNKED: only the first chunk prefills here and the call
        returns (None, False) — step() advances one chunk per engine step
        (interleaved with other slots' decode) until the prompt is
        consumed and the first token samples. Shorter prompts (and
        chunking off) prefill whole and return (first_token, done) as
        before.

        Raises InsufficientBlocksError (retryable: the batcher parks the
        request) when the pool cannot cover the prompt itself."""
        bt = self.block_tokens
        prompt = np.asarray(request["tokens"], np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError("request['tokens'] must be a non-empty 1-D seq")
        length = int(prompt.size)
        # length == max_seq_len is admittable: it emits exactly ONE token
        # and finishes without a cache write — which is also what makes a
        # generation preempted at its very last position readmittable (its
        # parked history fills the window)
        if length > self.max_seq_len:
            raise ValueError(
                f"prompt of {length} tokens exceeds max_seq_len "
                f"{self.max_seq_len}"
            )
        mnt = request.get("max_new_tokens")
        mnt = self.default_max_new_tokens if mnt is None else max(1, int(mnt))
        # a request whose WORST-CASE span can never fit the pool is
        # rejected before any token flows (predictability over optimism:
        # admitting it would stream tokens until self-preemption, then die
        # on readmission). length + max_new is invariant across preemption
        # cycles, so passing this check once means readmission can never
        # hard-fail by size.
        worst = self.worst_case_blocks(length, mnt)
        if worst > self.allocator.num_usable:
            raise ValueError(
                f"request worst case of {worst} KV blocks "
                f"({length} prompt + up to {mnt} new tokens) exceeds the "
                f"pool's {self.allocator.num_usable} blocks"
            )
        if self._live[slot]:
            self._release_blocks(slot)

        # cross-replica import: a transfer payload riding the request is
        # applied BEFORE the prefix lookup, so the imported chain is hit
        # by the normal admission path below (refcounted exactly like
        # locally-computed blocks). A payload that fails verification is
        # dropped — the lookup just misses and the span prefills from
        # scratch (the recompute fallback).
        kv_payload = request.get("kv_import")
        if kv_payload is not None:
            self.import_prefix(kv_payload, slot=slot)

        # prefix reuse: longest chain of cached FULL blocks, capped at
        # length-1 so at least one real token remains to prefill (its
        # hidden state produces the first sampled token)
        hit_blocks: List[int] = []
        snap_row = None
        if self.prefix_cache is not None and length > 1:
            if self.hybrid:
                # ... and on a hybrid cache only as deep as the deepest
                # node with a state snapshot: keys and values alone cannot
                # be resumed from. What lies behind it in the cache is
                # computed again, into blocks of the slot's own
                hit_blocks, snap_row = self.prefix_cache.lookup_snapshot(
                    prompt, (length - 1) // bt)
            else:
                hit_blocks = self.prefix_cache.lookup(
                    prompt, (length - 1) // bt)
        p_hit = len(hit_blocks) * bt
        for b in hit_blocks:
            self.allocator.incref(b)

        total_prompt_blocks = -(-length // bt)
        need = total_prompt_blocks - len(hit_blocks)
        self._reclaim(need)
        try:
            new_blocks = self.allocator.alloc(need)
        except InsufficientBlocksError:
            for b in hit_blocks:
                self.allocator.decref(b)
            # retrying only helps if waiting can free blocks: another live
            # generation retiring. Without one, everything evictable was
            # already evicted (reclaim cascades the whole cache), so the
            # failure is PERMANENT — fail the request with a hard error
            # instead of letting the batcher park-and-retry it forever.
            others_live = any(
                self._live[s] for s in range(self.max_batch_size)
                if s != slot
            )
            if total_prompt_blocks > self.allocator.num_usable or not others_live:
                raise ValueError(
                    f"prompt needs {total_prompt_blocks} KV blocks "
                    f"({need} beyond its prefix hits) but only "
                    f"{self.allocator.num_free} of "
                    f"{self.allocator.num_usable} can free up"
                ) from None
            raise
        row = hit_blocks + new_blocks
        self._tables[slot, :] = 0
        self._tables[slot, :len(row)] = row
        self._row_blocks[slot] = len(row)
        self._live[slot] = True

        self._positions[slot] = p_hit  # committed span so far
        self._max_new[slot] = mnt
        self._new_counts[slot] = 0
        self._history[slot] = [int(t) for t in prompt[:length]]
        self._chunk_state[slot] = np.ascontiguousarray(
            prompt[:length], dtype=np.int32
        )
        self._seq += 1
        self._admit_seq[slot] = self._seq
        self.prefills += 1
        if hit_blocks:
            self.prefix_hits += 1
            self.prefix_tokens_reused += p_hit
        if self._rec is not None:
            self._rec.record("admit", slot=slot,
                             args={"prompt": length, "hit_tokens": p_hit})
        if snap_row is not None:
            # the state after the reused tokens, into the slot's row (a
            # cold start needs no copy: prefill starts from zeros at ctx 0)
            self._copy_rows(snap_row, slot, "engine.state_restore", p_hit)
            self.state_restores += 1

        chunk = self.prefill_chunk_tokens
        if chunk and length - p_hit > chunk:
            # chunked admission: run the FIRST chunk now; step() advances
            # one chunk per engine step, interleaved with everyone else's
            # decode, until the prompt is consumed and the first token
            # samples — so a long prompt never stalls in-flight streams
            # for its whole prefill (the head-of-line latency fix)
            self.chunked_prefills += 1
            tok = self._run_prefill_chunk(slot)
        else:
            tok = self._run_prefill_chunk(slot, whole=True)
        if tok is None:
            return None, False
        done = self._done(slot, tok)
        if self.logprobs:
            # (token, logprob) pairs are the emitted unit in logprob mode;
            # the batcher pushes tuples atomically
            return (tok, float(self._lp_pending[slot])), done
        return tok, done

    def _run_prefill_chunk(self, slot: int, whole: bool = False) -> Optional[int]:
        """Consume the next prompt span of the slot's pending prefill
        (one prefill_chunk_tokens chunk, or the whole remainder with
        `whole=True`) through ONE paged-prefill dispatch. Returns the
        first sampled token when this call consumed the prompt's tail,
        else None (still prefilling; intermediate dispatches compute a
        throwaway sample — the B=1 unembed is noise next to the layers).

        The committed span is self._positions[slot]. Mid-prompt chunk
        boundaries need NOT be block-aligned: the prefill window math
        handles a chunk straddling a physical block (the straddled block
        is slot-owned — prefix-hit sharing is whole-block — so the quant
        path's requantize-owned rule keeps the CoW invariant; tests pin
        the straddle edge)."""
        prompt = self._chunk_state[slot]
        ctx = int(self._positions[slot])
        rem = int(prompt.size) - ctx
        take = rem if whole else min(self.prefill_chunk_tokens, rem)
        last = take == rem
        # the span ends after the first-token fetch when the chunk is the
        # last: the histogram and the recorder see the prefill itself, not
        # its enqueue
        with span("engine.prefill", self._tel, phase="prefill", slot=slot,
                  event="prefill_chunk", tokens=take, ctx=ctx,
                  last=bool(last)):
            return self._prefill_chunk(slot, prompt, ctx, take, last)

    def _prefill_chunk(self, slot: int, prompt, ctx: int, take: int,
                       last: bool) -> Optional[int]:
        bt = self.block_tokens
        length = int(prompt.size)
        bucket = self._bucket(take)
        padded = np.zeros(bucket, np.int32)
        padded[:take] = prompt[ctx:ctx + take]
        ctx_blocks = self._ctx_bucket_blocks(ctx)
        self.prefill_shapes.add((ctx_blocks, -(-bucket // bt)))
        # intermediate chunks sample a throwaway token — give them a FIXED
        # key so only the completing dispatch consumes the engine's RNG
        # stream: one key per admission regardless of chunking, which is
        # what keeps temperature > 0 tokens invariant to the chunk config
        # (greedy never reads the key at all)
        key = self._sample_key(sampled=last)
        # one NumPy array: the launch's one upload (the slot's row of the
        # state pool is its own index)
        inputs = pack_prefill_inputs(
            self._tables[slot], padded, take, ctx, row=slot)
        self.model_dispatches += 1
        self.uploads += 1
        out, _logits, self.pool = self._prefill(
            self.params, self.pool, inputs, key, ctx_blocks, bucket)
        self._positions[slot] = ctx + take
        self.prefill_tokens += take
        self.prefill_chunks += 1
        if (self.hybrid and self.prefix_cache is not None
                and (ctx + take) % bt == 0):
            # the chunk ended on a block boundary: the state behind it is
            # worth keeping (a prompt that shares these tokens resumes here)
            self._snapshot(slot, prompt, ctx + take)
        if not last:
            return None
        # one fetch: the first token and, in logprob mode, its logprob
        toks, _, lps = split_host_row(
            self._fetch(out), 1, logprobs=self.logprobs)
        tok = int(toks[0])
        if lps is not None:
            self._lp_pending[slot] = float(lps[0])
        self._chunk_state[slot] = None
        self._last_tokens[slot] = tok
        self._new_counts[slot] = 1
        hist = self._history[slot]
        if hist is not None:
            hist.append(tok)
        self.tokens_generated += 1
        # make this prompt's full blocks (hit + freshly computed) reusable
        if self.prefix_cache is not None:
            reg = (length - 1) // bt
            if reg:
                self.prefix_cache.register(
                    prompt, [int(b) for b in self._tables[slot, :reg]]
                )
        return tok

    def fork(self, src: int, dst: int) -> None:
        """Share ALL of src's blocks (including the partial tail) with dst:
        zero-copy generation fork. The first divergent write into a shared
        block triggers copy-on-write in step()."""
        if not self._live[src]:
            raise ValueError(f"fork source slot {src} is not live")
        if self._chunk_state[src] is not None:
            raise ValueError(
                f"fork source slot {src} is still prefilling (chunked)"
            )
        if self._live[dst]:
            self._release_blocks(dst)
        self._tables[dst] = self._tables[src].copy()
        self._row_blocks[dst] = self._row_blocks[src]
        for bi in range(int(self._row_blocks[src])):
            b = int(self._tables[src, bi])
            if b:
                self.allocator.incref(b)
        self._live[dst] = True
        self._positions[dst] = self._positions[src]
        self._last_tokens[dst] = self._last_tokens[src]
        self._new_counts[dst] = self._new_counts[src]
        self._max_new[dst] = self._max_new[src]
        self._history[dst] = list(self._history[src] or [])
        self._seq += 1
        self._admit_seq[dst] = self._seq
        if self.hybrid:
            # the state cannot be shared by reference: the fork gets a copy
            self._tail_snap[dst] = None
            self._copy_rows(src, dst, "engine.state_restore",
                            int(self._positions[src]))

    def force_token(self, slot: int, token: int) -> None:
        """Teacher-force the next input token for `slot` (replaces the
        pending sampled token — tests and speculative-decode hooks)."""
        if not self._live[slot]:
            raise ValueError(f"slot {slot} is not live")
        if self._chunk_state[slot] is not None:
            raise ValueError(
                f"slot {slot} is still prefilling (chunked) — no pending "
                "sampled token to replace"
            )
        self._last_tokens[slot] = int(token)
        hist = self._history[slot]
        if hist:
            hist[-1] = int(token)

    def step(self, slots: List[int]) -> Dict[int, Tuple[Any, bool]]:
        """One engine step for the live slots in `slots`. Slots the pool
        cannot grow are PREEMPTED (newest first) rather than OOMing; they
        are absent from the result and surface via take_preempted().

        Without speculation each slot's result is (token, done). With
        `speculative_k > 0` a step that verified drafts returns
        ([token, ...], done) — 1..k+1 tokens per slot — and steps where no
        slot drafted fall back to the plain single-token result.

        Slots still streaming a chunked prefill advance by ONE chunk per
        step and report ([], False) until their prompt is consumed (the
        completing chunk reports ([tok], done) with the first sampled
        token); every other slot decodes in the same step — chunk work
        and decode work interleave, so no decode stream ever waits for a
        whole long prompt."""
        surviving = [s for s in sorted(set(slots)) if self._live[s]]
        if not surviving:
            return {}
        out: Dict[int, Tuple[Any, bool]] = {}
        prefilling = [
            s for s in surviving if self._chunk_state[s] is not None
        ]
        for s in prefilling:
            tok = self._run_prefill_chunk(s)
            if tok is None:
                out[s] = ([], False)
            else:
                item = (
                    (tok, float(self._lp_pending[s]))
                    if self.logprobs else tok
                )
                out[s] = ([item], self._done(s, tok))
        decoding = [s for s in surviving if self._chunk_state[s] is None
                    and s not in out]
        if decoding:
            if self.speculative_k:
                drafts = self._propose(decoding)
                if any(drafts.values()):
                    out.update(self._spec_step(decoding, drafts))
                    return out
            out.update(self._plain_step(decoding))
        return out

    def _span_need(self, surviving: List[int], block_span) -> int:
        """Blocks the write spans require right now: unallocated entries
        plus shared blocks that must copy-on-write. Conservative across
        slots (a block shared between two stepping forks counts twice;
        the first CoW un-shares it for the second)."""
        need = 0
        for s in surviving:
            for bi in block_span(s):
                blk = int(self._tables[s, bi])
                if blk == 0 or self.allocator.refcount(blk) > 1:
                    need += 1
        return need

    def _reserve_write_spans(self, surviving: List[int], block_span) -> List[int]:
        """Make every block index in block_span(s) writable for each
        surviving slot — allocated and exclusively owned. ONE reservation
        contract for the plain step (span = the single write block) and
        the speculative step (span = the k+1-token verify window): evict
        cache blocks, preempt newest-first under pressure, then allocate
        + copy-on-write. Returns the surviving list (shrunk by
        preemptions). Note _reclaim cannot change the spans' own need
        (eviction only frees cache-ONLY blocks, refcount 1 — a span
        block is always also held by its slot), so need is computed once
        per pass.

        Newest-first is GLOBAL: slots mid-chunked-prefill are not in
        `surviving` (they allocated at admission and never step here) but
        they ARE preemption candidates — a freshly admitted long prompt
        is the newest work with the least to recompute, and exempting it
        would let one prefill serially evict every older decode stream
        (the exact head-of-line inversion chunking exists to fix). A
        preempted prefilling slot parks its full prompt and readmits like
        any other preemption."""
        prefilling = [
            s for s in range(self.max_batch_size)
            if self._live[s] and self._chunk_state[s] is not None
            and s not in surviving
        ]
        while True:
            need = self._span_need(surviving, block_span)
            self._reclaim(need)
            if need <= self.allocator.num_free:
                break
            victim = max(surviving + prefilling,
                         key=lambda s: self._admit_seq[s])
            self._preempt(victim)
            if victim in prefilling:
                prefilling.remove(victim)
                continue
            surviving.remove(victim)
            if not surviving:
                return surviving

        cow_src: List[int] = []
        cow_dst: List[int] = []
        for s in surviving:
            for bi in block_span(s):
                blk = int(self._tables[s, bi])
                if blk and self.allocator.refcount(blk) == 1:
                    continue  # an earlier CoW this step already un-shared it
                nb = self.allocator.alloc(1)[0]
                if blk:  # shared: copy-on-write before this slot's write
                    cow_src.append(blk)
                    cow_dst.append(nb)
                    self.allocator.decref(blk)
                    self.cow_copies += 1
                self._tables[s, bi] = nb
                self._row_blocks[s] = max(int(self._row_blocks[s]), bi + 1)
        if cow_src:
            self.pool = self._copy_blocks(
                self.pool, np.asarray(cow_src, np.int32),
                np.asarray(cow_dst, np.int32),
            )
        return surviving

    def _plain_step(self, surviving: List[int]) -> Dict[int, Tuple[Any, bool]]:
        with span("engine.decode", self._tel, phase="decode",
                  event="decode") as step_span:
            return self._plain_step_spanned(surviving, step_span)

    def _plain_step_spanned(self, surviving, step_span):
        bt = self.block_tokens

        # resolve this step's block needs (new block at a block boundary,
        # copy-on-write when the write block is shared) under pool pressure
        with span("engine.reserve"):
            surviving = self._reserve_write_spans(
                surviving,
                lambda s: (int(self._positions[s]) // bt,),
            )
        if not surviving:
            step_span.drop()
            return {}

        with span("engine.inputs"):
            B = self.max_batch_size
            write_phys = np.zeros(B, np.int32)  # inactive rows -> null block
            write_off = np.zeros(B, np.int32)
            kv_tokens = kv_blocks = 0
            for s in surviving:
                pos = int(self._positions[s])
                write_phys[s] = self._tables[s, pos // bt]
                write_off[s] = pos % bt
                kv_tokens += pos + 1  # the step attends to 0..pos
                kv_blocks += pos // bt + 1  # in that many of its blocks
            # everything the program needs from the host, one array
            inputs = pack_decode_inputs(
                self._tables, self._last_tokens, self._positions,
                write_phys, write_off)
            key = self._sample_key()
        # slots: the recorder keeps the ids (one timeline lane each), the
        # trace their number; kv_tokens is what the paged kernel must read,
        # kv_blocks_walked the table entries that hold it, of kv_table_blocks
        step_span.set(slots=tuple(surviving), kv_tokens=kv_tokens,
                      kv_blocks_walked=kv_blocks,
                      kv_table_blocks=self._tables.size)
        self.kv_blocks_walked += kv_blocks
        self.kv_table_blocks += self._tables.size
        uploads, fetches = self.uploads, self.fetches
        with span("engine.dispatch"):
            # one launch, and in it one upload: `inputs` is the only
            # argument that is not on the device already
            self.model_dispatches += 1
            self.uploads += 1
            out, _logits, self.pool = self._decode_step(
                self.params, self.pool, inputs, key)
        with span("engine.fetch"):
            # one fetch (the wait for the device is in it): the tokens,
            # with experts their two counts, in logprob mode the logprobs
            toks, moe_load, lps = split_host_row(
                self._fetch(out), self.max_batch_size,
                experts=bool(self.cfg.n_experts), logprobs=self.logprobs,
                share=self.cfg.expert_share)
            step_span.set(uploads=self.uploads - uploads,
                          fetches=self.fetches - fetches)
            if moe_load is not None:
                # a sparse-expert model: the step's routed (token, expert)
                # pairs, the load of its fullest expert and the experts
                # with any pair (the groups the grouped matmul reads), each
                # summed over the layers (pairs * n_experts / hottest = 1:
                # even). Where the replica holds a share of the routed
                # experts, hottest and touched are over the held ones and
                # `moe_pairs_held` are the pairs it computes
                pairs = (len(surviving) * self.cfg.top_k
                         * self.cfg.n_expert_layers)
                hottest, touched, *held = map(int, moe_load)
                held = held[0] if held else pairs
                step_span.set(moe_pairs=pairs, moe_hottest=hottest,
                              moe_touched=touched, moe_pairs_held=held)
                self.moe_pairs += pairs
                self.moe_pairs_held += held
                self.moe_hottest += hottest
                self.moe_touched += touched
        with span("engine.bookkeep"):
            out: Dict[int, Tuple[Any, bool]] = {}
            for s in surviving:
                tok = int(toks[s])
                self._positions[s] += 1
                self._last_tokens[s] = tok
                self._new_counts[s] += 1
                hist = self._history[s]
                if hist is not None:
                    hist.append(tok)
                item = (tok, float(lps[s])) if lps is not None else tok
                out[s] = (item, self._done(s, tok))
                if (self._rec is not None and self.eos_id is not None
                        and tok == self.eos_id):
                    self._rec.record("eos", slot=s)
            self.decode_steps += 1
            self.tokens_generated += len(surviving)
        if self.hybrid:
            for s in surviving:
                if int(self._positions[s]) % bt == 0:
                    self._keep_tail(s)
        return out

    # ----------------------------------------------------- speculative path

    def warmup_verify(self) -> int:
        """Compile every speculative verify bucket against the live pool
        (the probe writes touch only the null block, outputs are
        discarded). Call before a timed window or at replica start so a
        drafter's FIRST proposal mid-traffic does not bill a trace +
        compile to a real request. Returns the number of shapes warmed;
        no-op with speculation off or shapes already compiled."""
        if not self.speculative_k:
            return 0
        B = self.max_batch_size
        warmed = 0
        for k_eff in self._k_buckets:
            K1 = k_eff + 1
            if K1 in self.spec_shapes:
                continue
            zeros = np.zeros((B, K1), np.int32)
            _, _, self.pool = self._verify(
                self._tables, zeros, np.zeros(B, np.int32),
                np.zeros(B, np.int32), zeros, zeros)
            self.spec_shapes.add(K1)
            warmed += 1
        return warmed

    def _verify(self, *host_inputs):
        """One dispatch of the verify program (no cell runs it: its six
        inputs still go up one by one, and are counted as that)."""
        self.model_dispatches += 1
        self.uploads += len(host_inputs)
        return self._verify_step(
            self.params, self.pool, *host_inputs, self._sample_key())

    def _propose(self, surviving: List[int]) -> Dict[int, List[int]]:
        """Ask the drafter for up to k tokens per slot, capped so the
        verify span can neither outrun max_new_tokens (at most
        remaining-1 drafts: the undrafted output is always one token) nor
        write past max_seq_len. Drafter faults and out-of-vocab tokens
        degrade to 'no draft' — a bad drafter may slow a stream down, it
        must never wedge or corrupt it."""
        drafts: Dict[int, List[int]] = {}
        for s in surviving:
            cap = min(
                self.speculative_k,
                int(self._max_new[s] - self._new_counts[s]) - 1,
                self.max_seq_len - 1 - int(self._positions[s]),
            )
            if cap <= 0:
                drafts[s] = []
                continue
            try:
                # the LIVE history list, not a copy — O(seq) boxing per
                # slot per step would erode the latency win speculation
                # exists for; drafters must treat it as read-only
                raw = self.drafter.propose(self._history[s] or (), cap)
            except Exception:
                raw = []
            clean: List[int] = []
            for t in list(raw)[:cap]:
                t = int(t)
                if not 0 <= t < self.cfg.vocab_size:
                    break
                clean.append(t)
            drafts[s] = clean
        return drafts

    def _spec_step(
        self, surviving: List[int], drafts: Dict[int, List[int]]
    ) -> Dict[int, Tuple[List[int], bool]]:
        """Verify each slot's draft in ONE batched forward and commit the
        accepted prefix. Block bookkeeping is the plain step's, widened to
        the k+1-token span: blocks for the whole span are taken up front
        (preempting newest-first under pressure, CoW for shared write
        blocks), and the rejected tail is rolled back afterwards by
        truncating the table — unused blocks go straight back to the
        allocator."""
        with span("engine.verify", self._tel, phase="verify",
                  event="verify") as step_span:
            return self._spec_step_spanned(surviving, drafts, step_span)

    def _spec_step_spanned(self, surviving, drafts, step_span):
        bt = self.block_tokens

        def _span_blocks(s: int):
            p = int(self._positions[s])
            return range(p // bt, (p + len(drafts.get(s, ()))) // bt + 1)

        # speculation must never cost a preemption that non-speculative
        # decode would not have paid: if the k+1-token spans cannot fit
        # the pool without evicting a generation, drop the drafts and
        # take the plain single-token step (which preempts only when even
        # THAT cannot fit). The feasibility probe is SIDE-EFFECT-FREE —
        # evictable() estimates what reclaim could free without actually
        # flushing prefix-cache blocks for a speculation we then abandon.
        need = self._span_need(surviving, _span_blocks)
        evictable = (
            self.prefix_cache.evictable() if self.prefix_cache else 0
        )
        if need > self.allocator.num_free + evictable:
            step_span.drop()
            return self._plain_step(surviving)
        self._reclaim(need)
        if need > self.allocator.num_free:
            # reclaim under-delivered (evictable() counts blocks only a
            # cascade of leaf evictions could reach): still no preemption
            step_span.drop()
            return self._plain_step(surviving)
        surviving = self._reserve_write_spans(surviving, _span_blocks)
        if not surviving:
            step_span.drop()
            return {}

        kmax = max(len(drafts[s]) for s in surviving)
        k_eff = next(b for b in self._k_buckets if b >= kmax)
        K1 = k_eff + 1
        B = self.max_batch_size
        tokens = np.zeros((B, K1), np.int32)
        draft_len = np.zeros(B, np.int32)
        write_phys = np.zeros((B, K1), np.int32)  # dead/padded -> null block
        write_off = np.zeros((B, K1), np.int32)
        for s in surviving:
            p = int(self._positions[s])
            d = drafts.get(s, [])
            tokens[s, 0] = self._last_tokens[s]
            tokens[s, 1:1 + len(d)] = d
            draft_len[s] = len(d)
            for i in range(len(d) + 1):
                write_phys[s, i] = self._tables[s, (p + i) // bt]
                write_off[s, i] = (p + i) % bt
        out, accepted, self.pool = self._verify(
            self._tables, tokens, self._positions, draft_len, write_phys,
            write_off)
        out = self._fetch(out)
        accepted = self._fetch(accepted)

        results: Dict[int, Tuple[List[int], bool]] = {}
        for s in surviving:
            a = int(accepted[s])
            final: List[int] = []
            done = False
            hist = self._history[s]
            for tok in (int(t) for t in out[s, :a + 1]):
                final.append(tok)
                self._positions[s] += 1
                self._new_counts[s] += 1
                if hist is not None:
                    hist.append(tok)
                if self._done(s, tok):
                    done = True
                    break
            self._last_tokens[s] = final[-1]
            # rollback: truncate the table past the last committed token —
            # span blocks the rejected tail reserved return to the pool
            keep = (int(self._positions[s]) - 1) // bt + 1
            for bi in range(keep, int(self._row_blocks[s])):
                blk = int(self._tables[s, bi])
                if blk:
                    self.allocator.decref(blk)
                    self._tables[s, bi] = 0
            self._row_blocks[s] = min(int(self._row_blocks[s]), keep)
            results[s] = (final, done)
            self.tokens_generated += len(final)
            self.spec_emitted += len(final)
            self.spec_slot_steps += 1
            self.spec_proposed += int(draft_len[s])
            self.spec_accepted += a
            if self._rec is not None:
                if a < int(draft_len[s]):
                    self._rec.record(
                        "rollback", slot=s,
                        args={"rejected": int(draft_len[s]) - a})
                if (self.eos_id is not None and final
                        and final[-1] == self.eos_id):
                    self._rec.record("eos", slot=s)
        self.decode_steps += 1
        self.spec_steps += 1
        self.spec_shapes.add(K1)
        step_span.set(slots=tuple(surviving),
                      proposed=int(draft_len[list(surviving)].sum()),
                      accepted=int(accepted[list(surviving)].sum()))
        return results

    def take_preempted(self) -> List[Tuple[int, Dict[str, Any]]]:
        """(slot, parked_request) pairs preempted since the last call. The
        parked request readmits through the normal admit path (prefill of
        prompt + generated so far = recompute-on-readmit)."""
        out, self._preempted = self._preempted, []
        return out

    def release(self, slot: int) -> None:
        """Free a slot's blocks (idempotent; cache-registered blocks stay
        resident under the cache's own reference until evicted)."""
        if self._live[slot]:
            if self._rec is not None:
                self._rec.record(
                    "retire", slot=slot,
                    args={"tokens": int(self._new_counts[slot])})
            self._release_blocks(slot)
        self._new_counts[slot] = 0

    # --------------------------------------------------- live weight hot-swap

    def set_params(self, params, version: Optional[int] = None,
                   bytes_pulled: int = 0) -> int:
        """Swap the engine's weights between steps (live weight update —
        serve/weight_swap.py routes here via ContinuousBatcher.run_on_loop;
        loop thread only, like admit/step). Returns the new version.

        `params` is cast as at construction (`serving_params`: matmul
        weights, `embed`, `unembed` to `cfg.dtype`, once, on the device),
        so a replica swapped to a learner's float32 tree holds — and
        computes — bit for bit what a fresh engine built from that tree
        would. The caller's tree is not touched.

        Swap semantics are RECOMPUTE, not splice: every live slot is
        preempted (full history parked; the batcher readmits it and
        prompt + generated-so-far prefills under the NEW weights) and the
        prefix cache is flushed, so KV computed under the old weights can
        never attend to new-weight queries. That is exactly what makes
        every post-swap token greedy-identical to a fresh engine loaded
        with the new weights — splicing stale KV under new weights would
        emit tokens NEITHER model would produce. In-flight streams stay
        open throughout (recompute-on-readmit, the preemption contract);
        their consumers see added latency, never a drop.

        The transfer signature is re-derived with the new version, so
        cross-replica chain keys minted under the old weights are
        disjoint from the new key space by construction, and the drafter
        is refreshed (refresh(params) hook when it has one, stale state
        cleared) so swap-then-speculate proposes from the new weights."""
        t0 = time.monotonic()
        for s in range(self.max_batch_size):
            if self._live[s]:
                self._preempt(s)
        flushed = (
            self.prefix_cache.flush() if self.prefix_cache is not None else 0
        )
        self.params = serving_params(self.cfg, params)
        self.weight_version = (
            int(version) if version is not None else self.weight_version + 1
        )
        self.transfer_sig = self._compute_transfer_sig()
        self.weight_swaps += 1
        drafter = self.drafter
        if drafter is not None:
            refresh = getattr(drafter, "refresh", None)
            if refresh is not None:
                try:
                    refresh(self.params)
                except Exception:
                    # drafter faults degrade to 'no draft' (the _propose
                    # contract) — they must never fail the swap
                    pass
        if self._tel is not None:
            gauge = getattr(self._tel, "weight_version", None)
            if gauge is not None:
                gauge.set(self.weight_version)
        if self._rec is not None:
            self._rec.record(
                "weight_swap", dur=time.monotonic() - t0,
                args={"version": self.weight_version,
                      "bytes": int(bytes_pulled),
                      "flushed_blocks": flushed},
            )
        return self.weight_version

    # --------------------------------------------- cross-replica KV transfer

    def _refuse_transfer(self, what: str) -> None:
        if self.hybrid:
            raise NotImplementedError(
                f"a hybrid cache (recurrent state beside KV) does not "
                f"support {what}: a payload of blocks without the state "
                "snapshot behind them cannot be resumed from, and shipping "
                "snapshots is not written (ROADMAP, Reach A6)")

    def transfer_keys(self, tokens, n_blocks: int) -> List[bytes]:
        """Content-addressed keys for the prompt's first `n_blocks` FULL
        blocks. The chain is seeded with `transfer_sig` (model_id + block
        geometry + pool dtype + layer/head shape) and extended per block
        with its int32 token bytes — so two replicas of the same
        deployment compute identical keys for identical prefixes, in any
        process, while engines differing in ANY layout knob compute
        disjoint key spaces. This chain is deliberately separate from the
        in-process PrefixCache key chain (which has no cross-engine
        identity to carry)."""
        prompt = np.asarray(tokens, np.int32)
        bt = self.block_tokens
        if prompt.size < n_blocks * bt:
            raise ValueError(
                f"need {n_blocks * bt} tokens for {n_blocks} blocks, "
                f"got {prompt.size}"
            )
        keys: List[bytes] = []
        key = self.transfer_sig
        for bi in range(int(n_blocks)):
            h = hashlib.sha1()
            h.update(key)
            h.update(np.ascontiguousarray(
                prompt[bi * bt:(bi + 1) * bt], np.int32).tobytes())
            key = h.digest()
            keys.append(key)
        return keys

    def export_prefix(
        self, tokens, max_blocks: Optional[int] = None
    ) -> Optional[Dict[str, Any]]:
        """Export the longest cached chain of full blocks matching the
        prompt prefix as a self-verifying payload: chain keys, the token
        span they cover, and the block contents gathered from the pool
        (k/v, plus k_scale/v_scale on int8 pools). Returns None on a
        cache miss. Runs on the LOOP THREAD (same ownership contract as
        admit/step — the match and the pool gather must see one
        consistent pool state); serving code routes here via
        ContinuousBatcher.run_on_loop."""
        self._refuse_transfer("export_prefix")
        if self.prefix_cache is None:
            return None
        prompt = np.asarray(tokens, np.int32)
        if prompt.ndim != 1:
            return None
        bt = self.block_tokens
        cap = int(prompt.size) // bt
        if max_blocks is not None:
            cap = min(cap, int(max_blocks))
        if cap <= 0:
            return None
        blocks = self.prefix_cache.match_blocks(prompt, cap)
        if not blocks:
            return None
        n = len(blocks)
        idx = np.asarray(blocks, np.int32)
        payload = {
            "sig": self.transfer_sig,
            "keys": self.transfer_keys(prompt, n),
            "tokens": np.ascontiguousarray(prompt[:n * bt], np.int32),
            "block_tokens": bt,
            "kv_cache_dtype": self.kv_cache_dtype,
            "blocks": {
                name: np.asarray(self.pool[name][:, idx])
                for name in self.pool
            },
        }
        self.kv_exports += 1
        self.kv_blocks_exported += n
        if self._rec is not None:
            self._rec.record("kv_export",
                             args={"blocks": n, "tokens": n * bt})
        return payload

    def import_prefix(self, payload: Dict[str, Any], slot: int = -1) -> int:
        """Install an exported prefix into the local pool + PrefixCache.
        Returns the number of tokens newly imported (0 = nothing new:
        already cached locally, or the payload failed verification and
        was dropped — callers treat 0-with-reject as the recompute
        fallback). Verification is strict: the engine signature must
        match, the chain keys must recompute from the shipped tokens, and
        every block leaf must match the pool's slice shape and dtype — a
        payload from a different model, kv dtype, or block geometry can
        never be installed. Imported blocks end up held by the cache at
        refcount 1, exactly like locally-computed chain blocks. Loop
        thread only (admit() applies request-borne payloads itself)."""
        import jax.numpy as jnp

        self._refuse_transfer("import_prefix")
        bt = self.block_tokens
        tokens = None
        n = 0
        ok = (
            isinstance(payload, dict)
            and payload.get("sig") == self.transfer_sig
            and int(payload.get("block_tokens") or 0) == bt
            and payload.get("kv_cache_dtype") == self.kv_cache_dtype
        )
        if ok:
            tokens = np.asarray(payload.get("tokens"), np.int32)
            keys = list(payload.get("keys") or ())
            n = len(keys)
            ok = (
                n > 0 and tokens.ndim == 1 and tokens.size == n * bt
                and self.transfer_keys(tokens, n) == keys
            )
        if ok:
            blocks = payload.get("blocks")
            ok = isinstance(blocks, dict) and set(blocks) == set(self.pool)
            if ok:
                for name, arr in blocks.items():
                    ref = self.pool[name]
                    want = (ref.shape[0], n) + tuple(ref.shape[2:])
                    if (tuple(np.shape(arr)) != want
                            or np.dtype(arr.dtype) != np.dtype(ref.dtype)):
                        ok = False
                        break
        if not ok or self.prefix_cache is None:
            self.kv_import_rejects += 1
            if self._rec is not None:
                self._rec.record("kv_import", slot=slot,
                                 args={"rejected": True})
            return 0
        local = self.prefix_cache.match_blocks(tokens, n)
        m = len(local)
        if m >= n:
            return 0  # whole span already cached locally — nothing to do
        need = n - m
        self._reclaim(need)
        try:
            new_blocks = self.allocator.alloc(need)
        except InsufficientBlocksError:
            # pool pressure, not payload fault — still a recompute
            # fallback from the caller's point of view
            self.kv_import_rejects += 1
            if self._rec is not None:
                self._rec.record("kv_import", slot=slot,
                                 args={"rejected": True, "blocks": need})
            return 0
        idx = np.asarray(new_blocks, np.int32)
        pool = dict(self.pool)
        for name, arr in payload["blocks"].items():
            src = jnp.asarray(np.asarray(arr)[:, m:n])
            pool[name] = pool[name].at[:, idx].set(src)
        self.pool = pool
        # register increfs only the NEW nodes; dropping our allocation
        # reference leaves them cache-held at refcount 1 — identical to a
        # retired locally-computed chain
        self.prefix_cache.register(tokens, local + new_blocks)
        for b in new_blocks:
            self.allocator.decref(b)
        self.kv_imports += 1
        self.kv_blocks_imported += need
        self.kv_tokens_imported += need * bt
        if self._rec is not None:
            self._rec.record(
                "kv_import", slot=slot,
                args={"blocks": need, "reused": m, "tokens": need * bt},
            )
        return need * bt

    def stats(self) -> Dict[str, Any]:
        import jax

        used = self.allocator.num_usable - self.allocator.num_free
        mem = self._device.memory_stats() or {}  # None on the CPU backend
        return {
            # flight recorder (serve/telemetry.py): events currently held
            # in the ring + lifetime total (dropped = total - held)
            "flight_events": len(self._rec) if self._rec is not None else 0,
            "flight_events_total": (
                self._rec.total if self._rec is not None else 0
            ),
            "tokens_generated": self.tokens_generated,
            "prefills": self.prefills,
            "prefill_tokens": self.prefill_tokens,
            # chunked prefill: 0 chunk tokens = whole-prompt admission
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "prefill_chunks": self.prefill_chunks,
            "chunked_prefills": self.chunked_prefills,
            "prefilling": sum(
                1 for st in self._chunk_state if st is not None
            ),
            "decode_steps": self.decode_steps,
            # RNG splits dispatched (0 at temperature 0) and what the model
            # programs' dispatches cost in host<->device copies: a decode
            # step and a completing admission are 1 upload + 1 fetch (a
            # prefill chunk that is not the last fetches nothing)
            "rng_dispatches": self.rng_dispatches,
            "host_transfers": {
                "dispatches": self.model_dispatches,
                "uploads": self.uploads,
                "fetches": self.fetches,
            },
            # decode steps of a sparse-expert model: routed (token, expert)
            # pairs, the summed load of each step's fullest expert, and
            # the (layer, expert) groups with a pair: what the steps read
            "moe_pairs": self.moe_pairs,
            "moe_pairs_held": self.moe_pairs_held,
            "moe_hottest": self.moe_hottest,
            "moe_touched": self.moe_touched,
            "kv_blocks_walked": self.kv_blocks_walked,
            "kv_table_blocks": self.kv_table_blocks,
            "max_batch_size": self.max_batch_size,
            "block_tokens": self.block_tokens,
            "kv_cache_dtype": self.kv_cache_dtype,
            "attention_impl": self.attention_impl,
            "attention_kernel": self.attention_kernel,
            "platform": self.platform,
            "device_kind": self.device_kind,
            "device_bytes_in_use": mem.get("bytes_in_use"),
            "device_peak_bytes": mem.get("peak_bytes_in_use"),
            "device_bytes_limit": mem.get("bytes_limit"),
            # the held tree (serving_params): its bytes, and the dtype of
            # its matmul leaves
            "param_bytes": sum(
                int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(self.params)
            ),
            "param_dtype": np.dtype(self.params["embed"].dtype).name,
            # a layer's experts held here, of those its router scores
            "experts_held": self.cfg.n_experts,
            "experts_routed": self.cfg.router_width,
            "kv_block_bytes": self.kv_block_bytes,
            # what one resident token costs over all layers, by the pool's
            # own leaves (a latent pool: one row a layer)
            "kv_bytes_per_token": self.kv_block_bytes // self.block_tokens,
            # true pool HBM: counts the reserved null block too, so this
            # reconciles exactly with a serve_kv_pool_mb budget
            "kv_pool_bytes": self.kv_block_bytes * self.num_blocks,
            "kv_blocks_total": self.allocator.num_usable,
            "kv_blocks_free": self.allocator.num_free,
            "kv_block_utilization": round(
                used / max(1, self.allocator.num_usable), 4
            ),
            "kv_blocks_cached": (
                self.prefix_cache.evictable() if self.prefix_cache else 0
            ),
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_reused": self.prefix_tokens_reused,
            # hybrid cache (0 / None without linear layers): what one
            # sequence's recurrent state costs whatever its length, the
            # state pool's snapshot rows, and the snapshots taken, restored
            # into a slot at admission, and dropped (with their node, or
            # for their row)
            "state_bytes_per_seq": self.state_row_bytes,
            "state_rows_total": (
                self.state_pool.rows_total if self.hybrid else 0),
            "state_rows_free": (
                self.state_pool.num_free if self.hybrid else 0),
            "state_snapshots": self.state_snapshots,
            "state_restores": self.state_restores,
            "state_snapshot_evictions": (
                self.prefix_cache.snapshot_evictions
                if self.prefix_cache is not None else 0),
            # cross-replica KV transfer (serve/kv_transfer.py): rejects
            # count payloads dropped at verification or under pool
            # pressure — each one is a recompute fallback upstream
            "kv_exports": self.kv_exports,
            "kv_blocks_exported": self.kv_blocks_exported,
            "kv_imports": self.kv_imports,
            "kv_blocks_imported": self.kv_blocks_imported,
            "kv_tokens_imported": self.kv_tokens_imported,
            "kv_import_rejects": self.kv_import_rejects,
            # live weight hot-swap (serve/weight_swap.py)
            "weight_version": self.weight_version,
            "weight_swaps": self.weight_swaps,
            "preemptions": self.preemptions,
            "cow_copies": self.cow_copies,
            # speculative decoding: k=0 means off; rates cover spec steps
            # only (a step where nobody drafted is a plain decode step)
            "spec_k": self.speculative_k,
            "spec_steps": self.spec_steps,
            "spec_slot_steps": self.spec_slot_steps,
            "spec_proposed_tokens": self.spec_proposed,
            "spec_accepted_tokens": self.spec_accepted,
            "spec_emitted_tokens": self.spec_emitted,
            "spec_accept_rate": round(
                self.spec_accepted / max(1, self.spec_proposed), 4
            ),
            # average accepted burst length per slot per verify step
            # (1..k+1) — batch-size-independent, unlike tokens per ENGINE
            # step which would just re-measure occupancy
            "spec_tokens_per_step": round(
                self.spec_emitted / max(1, self.spec_slot_steps), 2
            ),
        }
