"""Decoder-only transformer LM (llama-family), TPU-first.

Design (idiomatic JAX, not a port — the reference has no in-repo LM; its
model-parallel story is external Alpa, release/alpa_tests/):
  - params are a plain dict pytree; every leaf has a logical-axis tuple in a
    parallel `param_specs` tree, mapped to mesh axes by ShardingRules —
    DP/FSDP/TP/EP are sharding-table entries, not code paths.
  - layers are STACKED and scanned (lax.scan over a [L, ...] leading dim):
    one compiled layer body regardless of depth — compile time O(1) in
    layers, and XLA pipelines the scan on TPU. What a layer only READS
    (its weights) is the scan's xs; state a layer WRITES a few rows of —
    the paged KV pool — is carried whole and addressed by layer index, so
    it is updated in place instead of sliced out and written back. The
    serving programs hand the dropless experts' three [L, X, ...] leaves
    to the layer body whole as well (`_scan_stacks`): the chip's grouped
    matmul takes a whole buffer as its weight operand, so a layer's slice
    of the stack would be COPIED in front of it; over the stack viewed as
    [L*X, ...], with the other layers' group sizes zero, it reads the
    layer's experts where they lie.
  - each scan step is jax.checkpoint'ed (rematerialization: trade MXU FLOPs
    for HBM, the standard TPU memory trade).
  - attention impl is selectable: dense (small L), ring (sequence-parallel
    over `sp` via ppermute ring), ulysses (all-to-all head scatter).
  - bf16 compute, f32 params/accumulators.

Decode fast path (serving): `init_paged_kv_cache` + `make_paged_decoder`
build prefill, cached single-token decode and speculative verify over a
pool of fixed-size token blocks addressed through per-slot block tables —
one compiled shape regardless of live lengths, and every generated token
pays O(L) attention reads instead of the O(L^2) full-sequence forward. The
pool's leaves stay stacked [L, N, ...] through every program's layer loop:
writes scatter into `leaf[l, block, offset]` of the donated buffer and
reads fetch `leaf[l, block]`, so a step moves the tokens it writes and the
blocks it attends, never the pool. The engine that drives the programs,
with host-side allocation, prefix reuse and preemption, is
`ray_tpu/models/kv_paging.py`.
"""

from __future__ import annotations

import dataclasses
import math
import zlib
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.attention import (
    NEG_INF,
    _repeat_kv,
    causal_attention,
    causal_attention_bhsd,
)
from ..ops.norm import rms_norm
from ..ops.ring_attention import ring_attention
from ..ops.rope import apply_rope, apply_rope_bhsd, rope_frequencies
from ..ops.ulysses import ulysses_attention
from ..ops.losses import (
    blockwise_softmax_cross_entropy,
    softmax_cross_entropy_with_int_labels,
)
from ..parallel.sharding import ShardingRules, constrain


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 12
    d_head: int = 64
    d_ff: int = 3072
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attention: str = "dense"  # dense | flash | ring | ulysses
    remat: bool = True
    # what the per-layer checkpoint saves: "full" recomputes everything
    # (max memory savings), "dots_no_batch" keeps weight-matmul outputs and
    # recomputes only attention + elementwise (the usual best MFU/memory
    # trade), "dots" keeps every dot product, "flash" = dots_no_batch plus
    # the attention-kernel output (backward never re-runs the kernel),
    # "flash_min" = ONLY the named residuals backward actually reads
    # (rope'd q/k, v, attention out+lse, mlp gate/up) — the best measured
    # MFU on the 125M bench
    remat_policy: str = "full"
    # flash attention tile sizes; on v5e big tiles win (grid overhead
    # dominates small blocks — measured 310ms @128 vs 234ms @1024 on the
    # 125M single-chip bench)
    flash_block_q: int = 1024
    flash_block_k: int = 1024
    # True: one lax.scan over stacked layers (O(1) compile in depth; the
    # multi-chip/pp path requires it). False: unrolled python loop —
    # longer compiles but drops the scan's stack dynamic-slice/update
    # traffic (~5% step time at 12 layers on v5e)
    scan_layers: bool = True
    # MoE (expert parallel); n_experts=0 -> dense MLP
    n_experts: int = 0
    top_k: int = 2
    # "dispatch": capacity-based top-k routing (FLOPs scale with top_k) —
    # the real EP path; "dense": every expert computes every token (exact
    # oracle for tests, O(n_experts) FLOPs)
    moe_impl: str = "dispatch"
    # slots per expert = ceil(top_k * tokens / n_experts * factor): pairs
    # past an expert's slots are DROPPED (the `ep`-sharded path needs the
    # fixed buffer). None = dropless: every routed (token, expert) pair is
    # computed, whatever the routing — what published sparse-expert layers
    # (OLMoE, ...) define, and the only exact setting for a decode batch
    moe_capacity_factor: Optional[float] = 1.25
    # divide the top-k router weights by their sum (False: use the softmax
    # probabilities as they are — HF `norm_topk_prob: false`)
    moe_renormalize: bool = True
    tie_embeddings: bool = False
    # "silu_gate": llama-family gated MLP (w_gate/w_up/w_down, silu) —
    # the default everywhere. "gelu": gpt2-family two-matmul MLP
    # (w_up/w_down, tanh-approx gelu, no gate) — what the model hub's
    # gpt2-class checkpoint mapping loads into (models/hub/checkpoint.py);
    # dense MLP only (MoE keeps the gated experts)
    mlp_variant: str = "silu_gate"
    # trailing vocab entries that exist only for sharding alignment (e.g.
    # a checkpoint's 50257-token vocab padded to 50304 so the vocab dim
    # divides a tp mesh): embedding rows are zero, and the samplers mask
    # their logits to -inf so a padded id can never be emitted
    vocab_pad: int = 0
    # pipeline parallelism: >1 splits the layer stack into pp stages
    pp_stages: int = 1
    pp_microbatches: int = 4
    # interleaved-1F1B depth v (parallel/pipeline.py): each pipeline device
    # hosts v of the pp_stages chunks (round-robin: chunk q on device
    # q % (pp_stages/v)), shrinking the bubble toward (pp-1)/(v*n_mb+pp-1).
    # Requires pp_stages % pp_interleave == 0 and pp_microbatches divisible
    # by the per-device stage count pp_stages // pp_interleave.
    pp_interleave: int = 1
    # >0: the training loss never materializes full [tokens, vocab] logits;
    # the unembed matmul + log-softmax run per seq-chunk of this size under
    # jax.checkpoint (ops/losses.py blockwise_softmax_cross_entropy). Frees
    # O(tokens x vocab) residual HBM — worth a batch-size step on 16G chips
    loss_chunk: int = 0
    # eps of every RMSNorm (published configs: `rms_norm_eps`)
    rms_norm_eps: float = 1e-6
    # RMSNorm with a learned scale over a token's WHOLE q and k projection
    # (all heads x d_head), before the split into heads and before RoPE
    qk_norm: bool = False
    # latent attention (MLA, DeepSeek-V2 §2.1): kv_lora_rank > 0 replaces
    # wq/wk/wv by the low-rank pairs wq_a/wq_b and wkv_a/wkv_b. A token
    # caches ONE row per layer — its normed c_kv (kv_lora_rank) and the
    # roped key every head shares (qk_rope_head_dim) — instead of per-head
    # K and V; d_head and n_kv_heads are then unused
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN (rope_factor > 1): inv_freq blended between theta^(-2i/d) and
    # the same over rope_factor, ramped between the correction dims of
    # beta_fast / beta_slow over rope_original_max positions; latent
    # attention also scales its scores by (0.1 mscale_all_dim ln factor + 1)^2
    rope_factor: float = 0.0
    rope_original_max: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # the first `first_k_dense` layers keep a dense gated MLP of width
    # d_ff_dense (params["dense_layers"]); the rest are expert layers
    first_k_dense: int = 0
    d_ff_dense: int = 0
    # router scores: "softmax" over all experts, or "sigmoid" per expert —
    # then the choice is made on score + router_bias, the weights are the
    # UNBIASED scores of the chosen (divided by their sum under
    # moe_renormalize) times moe_route_scale
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    # an expert layer's SHARE of an expert-parallel deployment: the router
    # scores n_routed_experts (0: n_experts, every expert is held) and
    # chooses over all of them; this replica holds the n_experts from
    # expert `expert_offset` on and computes the chosen pairs whose expert
    # it holds — what the absent experts would add is another chip's part
    n_routed_experts: int = 0
    expert_offset: int = 0
    # group-limited choice (DeepSeek-V2 `group_limited_greedy`, softmax
    # scores): the routed experts lie in moe_n_group groups of consecutive
    # ones, a group scores its best expert, and the top_k are taken among
    # the moe_topk_group best groups' experts. 1 group: the plain top_k
    moe_n_group: int = 1
    moe_topk_group: int = 1
    # experts every token goes through, beside the routed ones: ONE gated
    # MLP of width n_shared_experts * d_ff
    n_shared_experts: int = 0
    # hyper-connections (mHC, arXiv:2512.24880): hc_mult residual streams
    # per token, mixed around each sublayer by H_pre / H_post / H_res
    # (`_hc_mix`); 0 is the plain residual add
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # a layer PERIOD: the kinds ("linear" | "full") of the blocks that
    # repeat through the depth, n_layers / len(period) times. "full" is the
    # attention layer of the fields above (params["layers"], stacked over
    # the full layers only); "linear" is a gated-delta-rule layer
    # (ops/gated_delta.py; params["linear_layers"]) of linear_n_heads heads
    # with linear_d_k-wide keys and linear_d_v-wide values behind a causal
    # depthwise convolution of kernel linear_conv_kernel. () = every layer
    # is the attention layer
    layer_period: Tuple[str, ...] = ()
    linear_n_heads: int = 0
    linear_d_k: int = 0
    linear_d_v: int = 0
    linear_conv_kernel: int = 4
    # where a sublayer's RMSNorm sits: "pre" x + F(norm(x)), or "post"
    # x + norm(F(x)) — on the sublayer's OUTPUT, none on its input
    norm_placement: str = "pre"
    # False: q and k are not rotated (positions reach such a layer only
    # through what the layers before it carry)
    use_rope: bool = True

    def __post_init__(self):
        if self.norm_placement not in ("pre", "post"):
            raise ValueError(
                f"norm_placement must be 'pre' or 'post', "
                f"got {self.norm_placement!r}"
            )
        if self.layer_period:
            object.__setattr__(self, "layer_period", tuple(self.layer_period))
            bad = sorted(set(self.layer_period) - {"linear", "full"})
            if bad:
                raise ValueError(
                    f"layer_period kinds must be 'linear' or 'full', got {bad}")
            if self.n_layers % len(self.layer_period):
                raise ValueError(
                    f"n_layers {self.n_layers} is not whole periods of "
                    f"{len(self.layer_period)} layers")
            if "linear" in self.layer_period and not (
                    self.linear_n_heads and self.linear_d_k
                    and self.linear_d_v and self.linear_conv_kernel > 1):
                raise ValueError(
                    "a 'linear' layer needs linear_n_heads, linear_d_k, "
                    "linear_d_v and linear_conv_kernel > 1")
            for name in ("n_experts", "first_k_dense", "kv_lora_rank",
                         "hc_mult"):
                if getattr(self, name):
                    raise NotImplementedError(
                        f"a layer period beside {name}="
                        f"{getattr(self, name)!r} is not written")
            if self.pp_stages > 1:
                raise NotImplementedError(
                    "pp_stages > 1 over a layer period is not written: a "
                    "stage boundary would have to fall between periods")
        if self.mlp_variant not in ("silu_gate", "gelu"):
            raise ValueError(
                f"mlp_variant must be 'silu_gate' or 'gelu', "
                f"got {self.mlp_variant!r}"
            )
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_scoring must be 'softmax' or 'sigmoid', "
                f"got {self.moe_scoring!r}"
            )
        R = self.router_width
        if self.n_routed_experts and not self.n_experts:
            raise ValueError("n_routed_experts needs n_experts, the share held")
        if self.expert_share:
            if R % self.n_experts or self.expert_offset % self.n_experts \
                    or not 0 <= self.expert_offset < R:
                raise ValueError(
                    f"a share of {self.n_experts} experts from expert "
                    f"{self.expert_offset} is not one of "
                    f"n_routed_experts={R} cut into equal parts")
            if self.moe_impl == "dense" or self.moe_capacity_factor is not None:
                raise NotImplementedError(
                    "an expert layer that holds a share of its experts "
                    "(n_routed_experts > n_experts) runs dropless "
                    "(moe_capacity_factor=None) only: the capacity buffer "
                    "and the dense oracle over a share are not written")
        if self.moe_n_group > 1:
            if R % self.moe_n_group or not (
                    0 < self.moe_topk_group <= self.moe_n_group):
                raise ValueError(
                    f"moe_n_group {self.moe_n_group} / moe_topk_group "
                    f"{self.moe_topk_group} do not cut {R} routed experts "
                    "into equal groups of which some are kept")
            if self.moe_scoring != "softmax":
                raise NotImplementedError(
                    "group-limited routing over sigmoid scores is not "
                    "written (moe_n_group > 1 needs moe_scoring='softmax')")
            if self.top_k > self.moe_topk_group * (R // self.moe_n_group):
                raise ValueError(
                    f"top_k {self.top_k} exceeds the experts of "
                    f"{self.moe_topk_group} groups")
        if not 0 <= self.first_k_dense <= self.n_layers:
            raise ValueError(
                f"first_k_dense {self.first_k_dense} outside 0..n_layers "
                f"{self.n_layers}"
            )
        if self.first_k_dense and not (self.n_experts and self.d_ff_dense):
            raise ValueError(
                "first_k_dense needs n_experts (the layers after it) and "
                "d_ff_dense (the width of the dense ones)"
            )
        if self.kv_lora_rank and not (
            self.q_lora_rank and self.qk_nope_head_dim
            and self.qk_rope_head_dim and self.v_head_dim
        ):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        if self.pp_interleave < 1:
            raise ValueError(
                f"pp_interleave must be >= 1, got {self.pp_interleave}"
            )
        if self.pp_stages % self.pp_interleave:
            raise ValueError(
                f"pp_stages {self.pp_stages} not divisible by "
                f"pp_interleave {self.pp_interleave}"
            )

    @property
    def latent_width(self) -> int:
        """Values a token caches per layer under latent attention."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Width of the latent pool's rows: `latent_width` rounded up to
        the TPU's 128 lanes. A 576-wide minor dim gets a device layout with
        the BLOCK dim minor (padding-free, but every block access becomes a
        strided walk and the kernel call a whole-pool relayout); 640 keeps
        rows contiguous. The tail columns stay zero."""
        return -(-self.latent_width // 128) * 128

    @property
    def router_width(self) -> int:
        """Experts the router scores: all of the deployment's."""
        return self.n_routed_experts or self.n_experts

    @property
    def expert_share(self) -> bool:
        """Whether this replica holds only a share of the routed experts."""
        return self.router_width != self.n_experts

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.first_k_dense if self.n_experts else 0

    def layers_of(self, kind: str) -> int:
        """How many of the model's layers are of `kind` ("linear" |
        "full"); without a period every layer is "full"."""
        if not self.layer_period:
            return self.n_layers if kind == "full" else 0
        return (self.layer_period.count(kind)
                * (self.n_layers // len(self.layer_period)))

    @property
    def kv_pool_heads(self) -> int:
        """KV heads of the paged pool's leaves: n_kv_heads, rounded up to
        whole 8-row tiles where it is more than one tile and not whole ones
        (30 -> 32). A [.., block_tokens, 30, 128] leaf is given a device
        layout with the HEAD dim major over the tokens (padding-free), and
        every program would relay the whole pool into the row-major layout
        its scatters and the paged kernel take, and back: two pool-sized
        copies a step. Row-major tiles pad 30 to 32 rows anyway; the two
        extra heads are written as zeros and attended by two zero query
        heads that are dropped."""
        kv = self.n_kv_heads
        return kv if kv <= 8 or kv % 8 == 0 else -(-kv // 8) * 8

    @property
    def linear_channels(self) -> int:
        """Channels of a linear layer's convolution: [q | k | v]."""
        return self.linear_n_heads * (2 * self.linear_d_k + self.linear_d_v)

    def flops_per_token(self) -> float:
        """Approximate training FLOPs/token (fwd+bwd ≈ 6 * params-matmul)."""
        attn = 2 * self.d_model * self.d_head * (self.n_heads + 2 * self.n_kv_heads)
        attn += 2 * self.n_heads * self.d_head * self.d_model
        mlp_mult = self.n_experts if self.n_experts else 1
        n_mats = 2 if (not self.n_experts and self.mlp_variant == "gelu") else 3
        mlp = n_mats * 2 * self.d_model * self.d_ff * (min(self.top_k, mlp_mult) if self.n_experts else 1)
        per_layer = attn + mlp
        # attention scores/values: 2 * 2 * L * d per token (L = seq len, set at call)
        embed = 2 * self.d_model * self.vocab_size
        return 3 * (self.n_layers * per_layer + embed)

    def attention_flops_per_token(self, seq_len: int) -> float:
        return 3 * self.n_layers * (2 * 2 * seq_len * self.n_heads * self.d_head)

    def num_params(self) -> int:
        lp = (
            2 * self.d_model  # norms
            + self.d_model * self.d_head * (self.n_heads + 2 * self.n_kv_heads)
            + self.n_heads * self.d_head * self.d_model
        )
        if self.qk_norm:
            lp += self.d_head * (self.n_heads + self.n_kv_heads)
        if self.n_experts:
            lp += self.d_model * self.router_width  # router
            lp += self.n_experts * 3 * self.d_model * self.d_ff
        else:
            lp += (2 if self.mlp_variant == "gelu" else 3) * self.d_model * self.d_ff
        total = self.layers_of("full") * lp + self.d_model
        if self.layers_of("linear"):
            H, dk, dv = self.linear_n_heads, self.linear_d_k, self.linear_d_v
            total += self.layers_of("linear") * (
                2 * self.d_model                          # norms
                + self.d_model * H * (2 * dk + 2 * dv)    # q, k, v, gate
                + 2 * self.d_model * H + 2 * H            # a, b, A_log, dt_bias
                + self.linear_channels * self.linear_conv_kernel
                + dv + H * dv * self.d_model              # o_norm, wo
                + 3 * self.d_model * self.d_ff)
        total += self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        return total


CONFIGS: Dict[str, TransformerConfig] = {
    "tiny": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=128, max_seq_len=128,
    ),
    "tiny_moe": TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=128, max_seq_len=128, n_experts=4, top_k=2,
    ),
    # GPT-2 small scale (125M) — the single-host integration model
    "gpt2_125m": TransformerConfig(
        vocab_size=50304, d_model=768, n_layers=12, n_heads=12, n_kv_heads=12,
        d_head=64, d_ff=3072, max_seq_len=1024,
    ),
    # ~1.15B params — the single-chip HBM-limit config: fp32 params/adam-v
    # + bf16 momentum fill most of a v5e's 16G; flash attention +
    # flash_qkv remat (mlp gate/up recomputed) + chunked loss keep
    # activations/logits in budget. Measured 0.55 MFU at batch 6 on v5e.
    "gpt_1b": TransformerConfig(
        vocab_size=50304, d_model=2048, n_layers=14, n_heads=16, n_kv_heads=16,
        d_head=128, d_ff=8192, max_seq_len=1024, loss_chunk=256,
    ),
    # Llama-2 7B — the BASELINE.json north-star config
    "llama2_7b": TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=32,
        d_head=128, d_ff=11008, max_seq_len=4096,
    ),
    # Llama-3-8B-style GQA config
    "llama3_8b": TransformerConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_head=128, d_ff=14336, max_seq_len=8192, rope_theta=500000.0,
    ),
}


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def _hc_width(cfg: TransformerConfig) -> int:
    """Outputs of one sublayer's hyper-connection projection: n for H_pre,
    n for H_post, n*n for H_res."""
    n = cfg.hc_mult
    return 2 * n + n * n


def _extra_leaves(cfg: TransformerConfig, stack: str):
    """(name, shape without the layer dim, logical axes, kind) of the
    leaves one layer of `stack` ("layers" | "dense_layers") has BESIDE the
    llama / expert leaves `init_params` has always drawn: latent attention,
    router bias, shared expert, hyper-connections. `kind` is the fan-in of
    a matmul weight, or "ones" / "bias" / "alpha" / "hc_bias". The dense
    stack's own attention and MLP leaves are all here too."""
    E, H = cfg.d_model, cfg.n_heads
    out = []
    if stack == "dense_layers":
        # the main stack's attention and MLP leaves come from `init_params`
        # itself, in the order (and with the keys) they always have
        Fd, KV, D = cfg.d_ff_dense, cfg.n_kv_heads, cfg.d_head
        out += [
            ("w_gate", (E, Fd), ("embed", "mlp"), E),
            ("w_up", (E, Fd), ("embed", "mlp"), E),
            ("w_down", (Fd, E), ("mlp", "embed"), Fd),
        ]
        if not cfg.kv_lora_rank:
            out += [
                ("wq", (E, H, D), ("embed", "heads", "head_dim"), E),
                ("wk", (E, KV, D), ("embed", "kv_heads", "head_dim"), E),
                ("wv", (E, KV, D), ("embed", "kv_heads", "head_dim"), E),
                ("wo", (H, D, E), ("heads", "head_dim", "embed"), H * D),
            ]
            if cfg.qk_norm:
                out += [("q_norm", (H, D), ("heads", "head_dim"), "ones"),
                        ("k_norm", (KV, D), ("kv_heads", "head_dim"), "ones")]
    if cfg.kv_lora_rank:
        R, Q = cfg.kv_lora_rank, cfg.q_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        out += [
            ("wq_a", (E, Q), ("embed", None), E),
            ("q_a_norm", (Q,), (None,), "ones"),
            ("wq_b", (Q, H, dn + dr), (None, "heads", "head_dim"), Q),
            ("wkv_a", (E, R + dr), ("embed", None), E),
            ("kv_a_norm", (R,), (None,), "ones"),
            ("wkv_b", (R, H, dn + dv), (None, "heads", "head_dim"), R),
            ("wo", (H, dv, E), ("heads", "head_dim", "embed"), H * dv),
        ]
    if stack == "layers" and cfg.n_experts:
        if cfg.moe_scoring == "sigmoid":
            out.append(("router_bias", (cfg.router_width,), ("expert",), "bias"))
        if cfg.n_shared_experts:
            Fs = cfg.n_shared_experts * cfg.d_ff
            out += [
                ("ws_gate", (E, Fs), ("embed", "mlp"), E),
                ("ws_up", (E, Fs), ("embed", "mlp"), E),
                ("ws_down", (Fs, E), ("mlp", "embed"), Fs),
            ]
    if cfg.hc_mult:
        nC, W = cfg.hc_mult * E, _hc_width(cfg)
        for sub in ("attn", "mlp"):
            out += [
                (f"hc_{sub}_phi", (nC, W), (None, None), nC),
                (f"hc_{sub}_alpha", (3,), (None,), "alpha"),
                (f"hc_{sub}_bias", (W,), (None,), "hc_bias"),
            ]
    return out


def _linear_leaves(cfg: TransformerConfig):
    """(name, shape without the layer dim, logical axes, kind) of ONE
    linear (gated-delta-rule) layer, `_extra_leaves`' form: the four
    projections q, k, v and the output gate, the two H-wide gates a (decay)
    and b (write strength), the depthwise convolution over [q | k | v],
    A_log and dt_bias of the decay, the dv-wide scale of the gated output
    norm (shared by the heads), the output projection, the MLP and the two
    norm scales. Kinds beside a fan-in / "ones": "a_log" = ln U(0, 16),
    "dt_bias" = softplus^-1 of exp U(ln 1e-3, ln 1e-1) — the published
    implementation's draws, decays between ~0.2 and ~0.9999 a token."""
    E, F = cfg.d_model, cfg.d_ff
    H, dk, dv = cfg.linear_n_heads, cfg.linear_d_k, cfg.linear_d_v
    hd = ("embed", "heads", "head_dim")
    return [
        ("attn_norm", (E,), ("embed",), "ones"),
        ("wq", (E, H, dk), hd, E),
        ("wk", (E, H, dk), hd, E),
        ("wv", (E, H, dv), hd, E),
        ("wg", (E, H, dv), hd, E),
        ("wa", (E, H), ("embed", "heads"), E),
        ("wb", (E, H), ("embed", "heads"), E),
        ("conv_w", (cfg.linear_channels, cfg.linear_conv_kernel),
         (None, None), cfg.linear_conv_kernel),
        ("a_log", (H,), ("heads",), "a_log"),
        ("dt_bias", (H,), ("heads",), "dt_bias"),
        ("o_norm", (dv,), ("head_dim",), "ones"),
        ("wo", (H, dv, E), ("heads", "head_dim", "embed"), H * dv),
        ("mlp_norm", (E,), ("embed",), "ones"),
        ("w_gate", (E, F), ("embed", "mlp"), E),
        ("w_up", (E, F), ("embed", "mlp"), E),
        ("w_down", (F, E), ("mlp", "embed"), F),
    ]


def param_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical-axis tuples mirroring the param pytree. With pp_stages>1 the
    layer leaves carry a leading ("stage",) dim sharded on the pp axis."""
    layer = {
        "attn_norm": ("layers", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if not cfg.kv_lora_rank:
        layer.update(
            wq=("layers", "embed", "heads", "head_dim"),
            wk=("layers", "embed", "kv_heads", "head_dim"),
            wv=("layers", "embed", "kv_heads", "head_dim"),
            wo=("layers", "heads", "head_dim", "embed"),
        )
    if cfg.qk_norm:
        layer.update(
            q_norm=("layers", "heads", "head_dim"),
            k_norm=("layers", "kv_heads", "head_dim"),
        )
    def extras(stack):
        return {n: ("layers",) + ax for n, _, ax, _ in _extra_leaves(cfg, stack)}

    if cfg.n_experts:
        layer.update(
            router=("layers", "embed", "expert"),
            w_gate=("layers", "expert", "embed", "mlp"),
            w_up=("layers", "expert", "embed", "mlp"),
            w_down=("layers", "expert", "mlp", "embed"),
        )
    elif cfg.mlp_variant == "gelu":
        layer.update(
            w_up=("layers", "embed", "mlp"),
            w_down=("layers", "mlp", "embed"),
        )
    else:
        layer.update(
            w_gate=("layers", "embed", "mlp"),
            w_up=("layers", "embed", "mlp"),
            w_down=("layers", "mlp", "embed"),
        )
    layer.update(extras("layers"))
    if cfg.pp_stages > 1:
        layer = {k: ("stage",) + v for k, v in layer.items()}
    specs = {
        "embed": ("vocab", "embed"),
        "layers": layer,
        "final_norm": ("embed",),
    }
    if cfg.first_k_dense:
        specs["dense_layers"] = {
            "attn_norm": ("layers", "embed"), "mlp_norm": ("layers", "embed"),
            **extras("dense_layers")}
    if cfg.layers_of("linear"):
        specs["linear_layers"] = {
            n: ("layers",) + ax for n, _, ax, _ in _linear_leaves(cfg)}
    if not cfg.tie_embeddings:
        specs["unembed"] = ("embed", "vocab")
    return specs


def init_params(rng: jax.Array, cfg: TransformerConfig, *,
                held: bool = False) -> Dict[str, Any]:
    """The model's parameter tree from `rng`, float32.

    `held=True` gives the tree a serving replica holds instead
    (`serving_params` of this one): each matmul leaf is drawn and cast to
    `cfg.dtype` by one program (`_draw_held`), so the device never holds
    more than the cast tree and that program's temporaries — a float32
    tree of a large configuration need not fit the chip at all. The draws
    are the same either way.

    Leaves every llama / expert configuration has take their keys in the
    order they always have (a seed's weights do not change); the leaves of
    `_extra_leaves` and the whole `dense_layers` stack take keys folded from
    their names."""
    L, E, H, KV, D, F = (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_ff,
    )
    Ld = cfg.first_k_dense
    L = cfg.layers_of("full") - Ld  # layers of the main stack
    keys = iter(jax.random.split(rng, 16))
    dtype = jnp.dtype(cfg.dtype)

    def norm_init(*shape):
        return jnp.ones(shape, jnp.float32)

    def dense_init(name, key, shape, fan_in, gain=()):
        """N(0, 1/fan_in) times `gain`; a leaf the replica holds in
        `cfg.dtype` (`name`) is drawn and cast by ONE program, so its
        float32 draw is never a buffer of its own."""
        factors = tuple(np.float32(g) for g in gain)
        if held and name in _HELD_KEYS:
            return _draw_held(key, np.float32(math.sqrt(fan_in)), factors,
                              shape=shape, dtype=dtype)
        leaf = jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
        for g in gain:
            leaf = leaf * g
        return leaf

    def named_key(stack, name):
        return jax.random.fold_in(rng, zlib.crc32(f"{stack}/{name}".encode()))

    def extras(stack, n, leaves=None):
        out = {}
        for name, shape, _, kind in leaves or _extra_leaves(cfg, stack):
            key, shape = named_key(stack, name), (n,) + shape
            if kind == "ones":
                leaf = norm_init(*shape)
            elif kind == "a_log":
                leaf = jnp.log(jax.random.uniform(
                    key, shape, jnp.float32, minval=1e-4, maxval=16.0))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    key, shape, jnp.float32, minval=math.log(1e-3),
                    maxval=math.log(1e-1)))
                leaf = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "bias":
                # moves the choice between near-equal experts, not all of it
                leaf = 0.05 * jax.random.normal(key, shape, jnp.float32)
            elif kind == "alpha":
                # the three gains of (H_pre, H_post, H_res): 0.1 .. 1, never
                # the 0 a trained-from-identity init would start at
                leaf = 0.1 + 0.9 * jax.random.uniform(key, shape, jnp.float32)
            elif kind == "hc_bias":
                # H_pre / H_post biases at std 2 (reads and writes that
                # prefer some streams: the streams drift apart, so H_res,
                # which only re-deals what they hold — their sum is
                # invariant under a doubly stochastic mix — decides what a
                # sublayer reads), H_res logits at std 1
                m = cfg.hc_mult
                leaf = jax.random.normal(key, shape, jnp.float32) * np.repeat(
                    np.float32([2.0, 2.0, 1.0]), [m, m, m * m])
            else:
                leaf = dense_init(name, key, shape, kind)
            out[name] = leaf
        return out

    layer: Dict[str, Any] = {"attn_norm": norm_init(L, E)}
    if not cfg.kv_lora_rank:
        layer.update(
            wq=dense_init("wq", next(keys), (L, E, H, D), E),
            wk=dense_init("wk", next(keys), (L, E, KV, D), E),
            wv=dense_init("wv", next(keys), (L, E, KV, D), E),
            wo=dense_init("wo", next(keys), (L, H, D, E), H * D),
        )
    layer["mlp_norm"] = norm_init(L, E)
    if cfg.qk_norm:
        layer.update(q_norm=norm_init(L, H, D), k_norm=norm_init(L, KV, D))
    if cfg.n_experts:
        X = cfg.n_experts  # held: the stacks are this replica's share
        layer.update(
            router=dense_init(
                "router", next(keys), (L, E, cfg.router_width), E),
            w_gate=dense_init("w_gate", next(keys), (L, X, E, F), E),
            w_up=dense_init("w_up", next(keys), (L, X, E, F), E),
            w_down=dense_init("w_down", next(keys), (L, X, F, E), F),
        )
    elif cfg.mlp_variant == "gelu":
        layer.update(
            w_up=dense_init("w_up", next(keys), (L, E, F), E),
            w_down=dense_init("w_down", next(keys), (L, F, E), F),
        )
    else:
        layer.update(
            w_gate=dense_init("w_gate", next(keys), (L, E, F), E),
            w_up=dense_init("w_up", next(keys), (L, E, F), E),
            w_down=dense_init("w_down", next(keys), (L, F, E), F),
        )
    layer.update(extras("layers", L))
    if cfg.pp_stages > 1:
        if L % cfg.pp_stages:
            raise ValueError(f"n_layers {L} not divisible by pp_stages {cfg.pp_stages}")
        lps = L // cfg.pp_stages
        layer = {
            k: v.reshape((cfg.pp_stages, lps) + v.shape[1:]) for k, v in layer.items()
        }
    params = {
        "embed": dense_init("embed", next(keys), (cfg.vocab_size, E), E,
                            gain=(math.sqrt(E), 0.02)),
        "layers": layer,
        "final_norm": norm_init(E),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(
            "unembed", next(keys), (E, cfg.vocab_size), E)
    if Ld:
        params["dense_layers"] = {
            "attn_norm": norm_init(Ld, E), "mlp_norm": norm_init(Ld, E),
            **extras("dense_layers", Ld)}
    if cfg.layers_of("linear"):
        params["linear_layers"] = extras(
            "linear_layers", cfg.layers_of("linear"), _linear_leaves(cfg))
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _moe_route(x, lp, cfg: TransformerConfig):
    """Router of one expert layer. x [N, E] -> (w [N, k] f32, idx [N, k]),
    idx over ALL `cfg.router_width` routed experts, held here or not.

    "softmax": softmax in f32 over ALL experts, then the k largest — under
    `moe_n_group` > 1 among the experts of the `moe_topk_group` groups whose
    best expert scores highest (the other groups' scores are zeroed first:
    DeepSeek-V2's `group_limited_greedy`); the weights are divided by their
    sum only under `cfg.moe_renormalize`, and multiplied by
    `moe_route_scale`.
    "sigmoid" (DeepSeek-V3's `noaux_tc` with one group): a score per
    expert, the k largest of score + `router_bias` — the bias steers the
    CHOICE only — and the weights are the chosen experts' unbiased scores,
    over their sum under `moe_renormalize`, times `moe_route_scale`.
    The logits leave the matmul in f32: rounded to bf16 they would reorder
    near-equal experts."""
    with jax.named_scope("moe.route"):
        gate_logits = jnp.einsum(
            "ne,ex->nx", x, lp["router"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        if cfg.moe_scoring == "sigmoid":
            scores = jax.nn.sigmoid(gate_logits)
            _, idx = lax.top_k(scores + lp["router_bias"], cfg.top_k)
            w = jnp.take_along_axis(scores, idx, axis=-1)
            if cfg.moe_renormalize:
                w = w / (w.sum(-1, keepdims=True) + 1e-20)
            return w * cfg.moe_route_scale, idx
        probs = jax.nn.softmax(gate_logits, axis=-1)
        if cfg.moe_n_group > 1:
            with jax.named_scope("moe.groups"):
                G = cfg.moe_n_group
                best = jnp.max(probs.reshape(-1, G, probs.shape[-1] // G), -1)
                _, kept = lax.top_k(best, cfg.moe_topk_group)      # [N, g]
                keep = jnp.sum(jax.nn.one_hot(kept, G, dtype=jnp.int32), 1)
                probs = jnp.where(
                    jnp.repeat(keep, probs.shape[-1] // G, axis=-1) > 0,
                    probs, 0.0)
        w, idx = lax.top_k(probs, cfg.top_k)
        if cfg.moe_renormalize:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        if cfg.moe_route_scale != 1.0:
            w = w * cfg.moe_route_scale
    return w, idx


def _moe_dense(x, w, idx, lp, cfg: TransformerConfig):
    """Dense-dispatch oracle: every expert computes every token; the top-k
    router weights zero out non-selected experts. Exact but O(n_experts)
    FLOPs — kept as the correctness reference for the other two paths."""
    gate = jnp.sum(
        jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32) * w[..., None],
        axis=1,
    )  # [N, X]
    g = jnp.einsum("ne,xef->nxf", x, lp["w_gate"].astype(x.dtype))
    u = jnp.einsum("ne,xef->nxf", x, lp["w_up"].astype(x.dtype))
    y = jnp.einsum("nxf,xfe->nxe", jax.nn.silu(g) * u, lp["w_down"].astype(x.dtype))
    return jnp.einsum("nxe,nx->ne", y, gate.astype(x.dtype))


def _moe_dispatch(x, w, idx, lp, cfg: TransformerConfig, constrain_fn):
    """Capacity-based top-k MoE (GShard/Switch family, TPU-first) — the
    path of a set `cfg.moe_capacity_factor`, and the one that DROPS:

    tokens are sorted by destination expert and scattered into a fixed
    [n_experts, capacity, d_model] buffer; the expert FFNs run as ONE
    batched matmul over that buffer; outputs scatter-add back weighted by
    the router weights. FLOPs scale with top_k * N * capacity_factor —
    independent of n_experts. Under an `ep`-sharded mesh the sharding
    constraint on the buffer makes GSPMD insert the token all-to-alls
    (SURVEY §2.4 "mesh expert axis + ragged all-to-all"); a (token,
    expert) pair beyond its expert's capacity is dropped — silently, and
    at a decode batch's handful of tokens the capacity is 1 or 2.
    `moe_capacity_factor=None` (`_moe_dropless`) drops nothing.
    Static shapes throughout: sort + gather/scatter, no ragged compute."""
    N, E = x.shape
    X, k = cfg.n_experts, cfg.top_k
    C = min(N, max(1, math.ceil(k * N / X * cfg.moe_capacity_factor)))

    flat_e = idx.reshape(-1)                       # [N*k] destination expert
    flat_t = jnp.repeat(jnp.arange(N), k)          # [N*k] source token
    flat_w = w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)       # group by expert
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # slot within the expert's capacity window
    group_start = jnp.searchsorted(se, jnp.arange(X))
    pos = jnp.arange(N * k) - group_start[se]
    valid = (pos < C).astype(x.dtype)              # overflow -> dropped
    pos_c = jnp.minimum(pos, C - 1)

    buf = jnp.zeros((X, C, E), x.dtype)
    buf = buf.at[se, pos_c].add(x[st] * valid[:, None])
    buf = constrain_fn(buf, "expert", None, "embed")
    g = jnp.einsum("xce,xef->xcf", buf, lp["w_gate"].astype(x.dtype))
    u = jnp.einsum("xce,xef->xcf", buf, lp["w_up"].astype(x.dtype))
    y = jnp.einsum("xcf,xfe->xce", jax.nn.silu(g) * u, lp["w_down"].astype(x.dtype))
    y = constrain_fn(y, "expert", None, "embed")

    contrib = y[se, pos_c] * (sw.astype(x.dtype) * valid)[:, None]  # [N*k, E]
    return jnp.zeros((N, E), x.dtype).at[st].add(contrib)


def _moe_dropless(x, w, idx, lp, cfg: TransformerConfig, layer=None):
    """Dropless top-k MoE (`moe_capacity_factor=None`): EVERY routed
    (token, expert) pair is computed, at the FLOPs of the N*k pairs.

    The pairs are sorted by expert, so each expert's rows are contiguous
    in one [N*k, d_model] matrix, and `lax.ragged_dot` multiplies each run
    of rows by its own expert's weights with `group_sizes` from the router
    — on TPU one grouped-matmul kernel that reads an expert's weights once
    and skips experts without rows. Shapes are static for any N >= 1 and
    any routing (all pairs on one expert included); the output is gathered
    back per token and the k contributions summed in f32.

    The expert leaves are this layer's [X, ...] slices (the trainer's
    forward) or the WHOLE [L, X, ...] stacks with `layer`, the layer's
    index in the model (the paged programs, `_scan_stacks`). The kernel
    takes a whole buffer as its weight operand: a slice of the stack would
    be materialised in front of each call (three copies of a layer's
    experts a layer, most of a decode step's device time: PERF.md, PR 34).
    A stack is therefore read in place — viewed as L*X groups (merging the
    two leading dims moves nothing), with the router's sizes written at
    this layer's X groups and zero rows for every other layer's. Same
    rows, same experts, same three matmuls.

    Under `cfg.expert_share` idx names experts of the whole deployment and
    the leaves are the X held ones: only the pairs whose expert is held get
    a group, they sort to the front, and the rows behind the groups' sum —
    the pairs another chip computes — are not multiplied."""
    N, E = x.shape
    X, k = cfg.n_experts, cfg.top_k
    flat_e = idx.reshape(-1)                       # [N*k] destination expert
    if cfg.expert_share:
        # the held experts' pairs first, by expert; every other pair sorts
        # behind them under the key X, which no group counts
        local = flat_e - cfg.expert_offset
        held = jnp.logical_and(local >= 0, local < X)
        flat_e = jnp.where(held, local, X)
    order = jnp.argsort(flat_e, stable=True)       # pairs grouped by expert
    sizes = jnp.sum(
        jax.nn.one_hot(flat_e, X, dtype=jnp.int32), axis=0
    )                                              # [X] rows per expert
    in_stack = lp["w_gate"].ndim == 4
    if in_stack:
        groups = lp["w_gate"].shape[0] * X
        sizes = lax.dynamic_update_slice(
            jnp.zeros((groups,), jnp.int32), sizes,
            ((layer - cfg.first_k_dense) * X,))

    def experts(rows, name):
        wt = lp[name].astype(x.dtype)
        if in_stack:
            wt = wt.reshape(groups, *wt.shape[2:])
        return lax.ragged_dot(rows, wt, sizes)

    xs = x[order // k]                             # [N*k, E] sorted rows
    g = experts(xs, "w_gate")
    u = experts(xs, "w_up")
    y = experts(jax.nn.silu(g) * u, "w_down")
    # back to token order: pair j of token n sits at sorted row inv[n*k+j]
    inv = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    y = y[inv].reshape(N, k, E).astype(jnp.float32)
    if cfg.expert_share:
        # a row past the groups' sum is not multiplied, and what the kernel
        # leaves there is not specified: such a pair adds nothing
        y = jnp.where(held.reshape(N, k, 1), y, 0.0)
    return jnp.sum(y * w[..., None], axis=1).astype(x.dtype)


def _moe(h, lp, cfg: TransformerConfig, constrain_fn, layer=None):
    """The sparse-expert MLP: h [B, S, E] -> (out [B, S, E], idx [B*S, k],
    the experts each token was routed to). A shared expert (`ws_*`), where
    the layer has one, is added once, unweighted. `layer`: the layer's
    index in the model, where `lp` holds the expert stacks whole
    (`_moe_dropless`)."""
    B, S, E = h.shape
    x = h.reshape(B * S, E)
    w, idx = _moe_route(x, lp, cfg)
    with jax.named_scope("moe.experts"):
        if cfg.moe_impl == "dense":
            out = _moe_dense(x, w, idx, lp, cfg)
        elif cfg.moe_capacity_factor is None:
            out = _moe_dropless(x, w, idx, lp, cfg, layer)
        else:
            out = _moe_dispatch(x, w, idx, lp, cfg, constrain_fn)
        if cfg.n_shared_experts:
            with jax.named_scope("moe.shared"):
                g = jnp.einsum("ne,ef->nf", x, lp["ws_gate"].astype(x.dtype))
                u = jnp.einsum("ne,ef->nf", x, lp["ws_up"].astype(x.dtype))
                out = out + jnp.einsum(
                    "nf,fe->ne", jax.nn.silu(g) * u,
                    lp["ws_down"].astype(x.dtype))
    return out.reshape(B, S, E), idx


def _expert_load(idx, live, cfg: TransformerConfig):
    """What the tokens marked `live` ([N] bool) ask of one expert layer's
    HELD experts, idx [N, k] -> int32 [2]: the load of the fullest expert
    (the most (token, expert) pairs any one expert got) and the number of
    experts with at least one pair — the groups the grouped matmul reads.
    Under `cfg.expert_share` int32 [3]: behind them the pairs on held
    experts, all the layer computes of the live tokens' k each."""
    hits = jax.nn.one_hot(idx - cfg.expert_offset if cfg.expert_share else idx,
                          cfg.n_experts, dtype=jnp.int32)
    load = jnp.sum(hits * live[:, None, None], axis=(0, 1))
    out = [jnp.max(load), jnp.sum(load > 0, dtype=jnp.int32)]
    if cfg.expert_share:
        out.append(jnp.sum(load))
    return jnp.stack(out)


_MATMUL_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router",
                "wq_a", "wq_b", "wkv_a", "wkv_b", "ws_gate", "ws_up", "ws_down",
                "wg", "wa", "wb")
# what a serving replica holds in cfg.dtype (`serving_params`)
_HELD_KEYS = _MATMUL_KEYS + ("embed", "unembed")
_STACKS = ("dense_layers", "layers")
# the stacks of a layer period, by the kind of their layers
_KIND_STACK = {"linear": "linear_layers", "full": "layers"}
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


# The compiler's constant folding walks the counters of the random bits, an
# array the leaf's size: 13.8 s of a 14.9 s compile for an expert stack
# [4, 64, 3584, 1024] on a v5e, a second without it (PERF.md, PR 33), and it
# has nothing to fold here (the key and the scalars are arguments).
@partial(jax.jit, static_argnames=("shape", "dtype"),
         compiler_options={"xla_disable_hlo_passes": "constant_folding"})
def _draw_held(key, divisor, factors, *, shape, dtype):
    """`init_params`' draw of one matmul leaf — normal / divisor, times each
    of `factors`, the operations and their order as the float32 tree's —
    cast to `dtype` in the same program. The scalars are arguments, not
    constants: a constant divisor may be compiled as a multiplication."""
    leaf = jax.random.normal(key, shape, jnp.float32) / divisor
    for g in factors:
        leaf = leaf * g
    return leaf.astype(dtype)


def _map_matmul_leaves(params, fn):
    """`params` with `fn` applied to the stacked matmul weights of every
    layer stack it has."""
    out = dict(params)
    for stack in _STACKS + ("linear_layers",):
        if stack in params:
            layers = dict(params[stack])
            for key in _MATMUL_KEYS:
                if key in layers:
                    layers[key] = fn(layers[key])
            out[stack] = layers
    return out


def _cast_matmul_params(cfg: TransformerConfig, params):
    """Cast the stacked matmul weights to compute dtype ONCE per program —
    the trainer's case: it keeps f32 masters, and without this XLA
    re-converts them on every scan iteration and again per remat pass (~5%
    of step time on the 125M bench); norm scales stay f32 (rms_norm
    computes in f32 anyway). On a tree that `serving_params` already cast
    (what a serving replica holds) every `astype` here is the identity and
    compiles to nothing."""
    return _map_matmul_leaves(params, lambda a: a.astype(cfg.dtype))


def serving_params(cfg: TransformerConfig, params):
    """The tree a serving replica holds: exactly the leaves the decode
    programs cast — the stacked matmul weights of each layer stack, `embed`
    and `unembed` — in `cfg.dtype`, every norm scale (and the router bias
    and hyper-connection leaves) as it came (float32). The
    cast is the convert the programs would run, done ONCE per tree instead
    of in every prefill and every decode step, on the device and leaf by
    leaf: a leaf keeps its sharding, and one already in `cfg.dtype` is
    returned as it is (no copy), so the call is idempotent and never touches
    the caller's tree. A replica that draws its own weights never makes the
    float32 tree in the first place: `init_params(held=True)`."""
    dtype = jnp.dtype(cfg.dtype)

    def held(leaf):
        return leaf if leaf.dtype == dtype else jnp.asarray(leaf).astype(dtype)

    out = _map_matmul_leaves(params, held)
    out["embed"] = held(params["embed"])
    if "unembed" in params:
        out["unembed"] = held(params["unembed"])
    return out


def _mlp(h, lp, cfg: TransformerConfig, constrain_fn):
    """The dense MLP (gated SiLU, or the gpt2-family two-matmul gelu)."""
    from jax.ad_checkpoint import checkpoint_name

    u = checkpoint_name(
        jnp.einsum("bse,ef->bsf", h, lp["w_up"].astype(h.dtype)), "mlp_up"
    )
    if cfg.mlp_variant == "gelu":
        # gpt2-family two-matmul MLP (tanh-approx gelu, matching gelu_new)
        u = constrain_fn(u, "batch", "seq", "mlp")
        return jnp.einsum(
            "bsf,fe->bse", jax.nn.gelu(u, approximate=True),
            lp["w_down"].astype(h.dtype),
        )
    g = checkpoint_name(
        jnp.einsum("bse,ef->bsf", h, lp["w_gate"].astype(h.dtype)), "mlp_gate"
    )
    g = constrain_fn(g, "batch", "seq", "mlp")
    return jnp.einsum("bsf,fe->bse", jax.nn.silu(g) * u, lp["w_down"].astype(h.dtype))


def _whole_projection_norm(x, scale, eps: float, head_major: bool):
    """RMSNorm over a token's whole projection — every head and every
    head_dim entry together — with the learned `scale` [heads, d_head]
    (QK-norm as OLMoE defines it: before the split into heads matters and
    before RoPE). x is [B, S, H, D], or [B, H, S, D] when `head_major`."""
    x32 = x.astype(jnp.float32)
    axes = (1, 3) if head_major else (2, 3)
    var = jnp.mean(x32 * x32, axis=axes, keepdims=True)
    scale = scale.astype(jnp.float32)
    if head_major:
        scale = scale[:, None, :]
    y = x32 * jnp.reciprocal(jnp.sqrt(var + eps))
    return (y * scale).astype(x.dtype)


def _qkv(x, lp, cfg: TransformerConfig, cos, sin, positions=None,
         head_major: bool = False):
    """(q, k, v) of one layer from the layer's input x [B, S, E] — the
    front half of `_block`: pre-norm with the config's eps, the three
    projections, QK-norm over the whole projection when the config has it,
    RoPE at `positions` ([B, S] or [1, S]; None = 0..S-1) — no input norm
    under `norm_placement="post"`, no rotation without `use_rope`. Layout
    [B, S, H, D], or [B, H, S, D] when `head_major` (the training forward's
    kernel-native layout, which also names q, k, v for the remat
    policies)."""
    h = x if cfg.norm_placement == "post" else rms_norm(
        x, lp["attn_norm"], cfg.rms_norm_eps)
    out = "bhsd" if head_major else "bshd"
    q = jnp.einsum(f"bse,ehd->{out}", h, lp["wq"].astype(h.dtype))
    k = jnp.einsum(f"bse,ehd->{out}", h, lp["wk"].astype(h.dtype))
    v = jnp.einsum(f"bse,ehd->{out}", h, lp["wv"].astype(h.dtype))
    if cfg.qk_norm:
        q = _whole_projection_norm(q, lp["q_norm"], cfg.rms_norm_eps, head_major)
        k = _whole_projection_norm(k, lp["k_norm"], cfg.rms_norm_eps, head_major)
    if not head_major:
        if cfg.use_rope:
            q = apply_rope(q, cos, sin, positions=positions)
            k = apply_rope(k, cos, sin, positions=positions)
        return q, k, v
    assert positions is None, "head-major RoPE runs at positions 0..S-1"
    from jax.ad_checkpoint import checkpoint_name

    if not cfg.use_rope:
        return (checkpoint_name(q, "rope_q"), checkpoint_name(k, "rope_k"),
                checkpoint_name(v, "attn_v"))

    # post-rope q/k and v are named so the flash remat policies can save
    # exactly these — backward then reads them instead of re-deriving
    # qkv-matmul + rope per layer (and the "flash_min" policy saves ONLY
    # named residuals: the pre-rope wq/wk outputs dots_no_batch would keep
    # are redundant next to rope_q/k)
    v = checkpoint_name(v, "attn_v")
    q = checkpoint_name(apply_rope_bhsd(q, cos, sin), "rope_q")
    k = checkpoint_name(apply_rope_bhsd(k, cos, sin), "rope_k")
    return q, k, v


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's inverse frequencies [dim // 2], f32: dimension i keeps
    theta^(-2i/dim) below the correction dim of `beta_fast` rotations over
    `original_max` positions, takes that over `factor` above the one of
    `beta_slow`, and a linear ramp between them (DeepSeek-V2's
    `DeepseekV2YarnRotaryEmbedding`)."""
    def correction_dim(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    extra = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(cfg: TransformerConfig):
    """(cos, sin) [max_seq_len, rope_dim // 2] of the model's RoPE: over
    d_head, or under latent attention over qk_rope_head_dim, YaRN-scaled
    where `rope_factor` says so."""
    if not cfg.use_rope:
        return None, None
    dim = cfg.qk_rope_head_dim if cfg.kv_lora_rank else cfg.d_head
    if cfg.rope_factor <= 1:
        return rope_frequencies(dim, cfg.max_seq_len, cfg.rope_theta)
    inv_freq = yarn_inv_freq(
        dim, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
        cfg.rope_beta_fast, cfg.rope_beta_slow)
    freqs = jnp.outer(jnp.arange(cfg.max_seq_len, dtype=jnp.float32), inv_freq)
    m = (_yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


def attention_scale(cfg: TransformerConfig) -> float:
    """What the scores are multiplied by: 1/sqrt(head dim), times YaRN's
    (0.1 mscale_all_dim ln factor + 1)^2 under latent attention."""
    if not cfg.kv_lora_rank:
        return cfg.d_head**-0.5
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_factor > 1 and cfg.rope_mscale_all_dim:
        scale *= _yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def _qkv_latent(x, lp, cfg: TransformerConfig, cos, sin, positions=None):
    """The latent sibling of `_qkv` (MLA): from the layer's input x
    [B, S, E] ->

      q       [B, S, H, nope + rope]   c_q = RMSNorm(h Wq_a); q = c_q Wq_b,
                                       its last `rope` columns roped
      latent  [B, S, 1, latent_width]  what the token CACHES: the normed
                                       c_kv and the roped key all heads
                                       share, [c_kv | k_rope]
      wkv_b   [R, H, nope + v]         this layer's up-projection of c_kv
                                       to per-head k_nope and v: handed to
                                       the program's `attend`, which expands
                                       K/V with it (materialised) or folds
                                       it into q and the output (absorbed)

    `attend(q, latent, wkv_b)` returns [B, S, H, v_head_dim]."""
    dn, R = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    c_q = rms_norm(
        jnp.einsum("bse,eq->bsq", h, lp["wq_a"].astype(h.dtype)),
        lp["q_a_norm"], cfg.rms_norm_eps)
    q = jnp.einsum("bsq,qhd->bshd", c_q, lp["wq_b"].astype(h.dtype))
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], cos, sin, positions=positions)],
        axis=-1)
    ckv = jnp.einsum("bse,er->bsr", h, lp["wkv_a"].astype(h.dtype))
    c_kv = rms_norm(ckv[..., :R], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_rope = apply_rope(ckv[..., None, R:], cos, sin, positions=positions)
    latent = jnp.concatenate([c_kv[..., None, :], k_rope], axis=-1)
    return q, latent, lp["wkv_b"].astype(h.dtype)


def expand_latent(latent, wkv_b, cfg: TransformerConfig):
    """Materialised MLA: cached rows [B, K, 1, >= latent_width] -> per-head
    (k [B, K, H, nope + rope], v [B, K, H, v_head_dim])."""
    R, dn, dr = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kv = jnp.einsum("bkr,rhd->bkhd", latent[:, :, 0, :R], wkv_b)
    k_rope = jnp.broadcast_to(
        latent[:, :, :, R:R + dr], kv.shape[:3] + (dr,))
    return jnp.concatenate([kv[..., :dn], k_rope], axis=-1), kv[..., dn:]


def _sinkhorn(logits, iters: int, eps: float):
    """exp(logits) [..., n, n] made doubly stochastic: `iters` rounds of
    rows over (their sum + eps), then columns over (theirs + eps)."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_maps(x, lp, sub: str, cfg: TransformerConfig):
    """The three maps of one sublayer's hyper-connection from the streams
    x [..., n, C], all f32: H_pre [..., n] = sigmoid, H_post [..., n] =
    2 sigmoid, H_res [..., n, n] = Sinkhorn(clip(.)) — each an affine map
    (gain alpha, bias) of phi applied to vec(x) under ONE RMS over all n*C
    values, with no learned scale."""
    n = cfg.hc_mult
    flat = x.reshape(x.shape[:-2] + (-1,)).astype(jnp.float32)
    flat = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    proj = jnp.einsum("...c,cw->...w", flat, lp[f"hc_{sub}_phi"],
                      precision=jax.lax.Precision.HIGHEST)
    alpha, bias = lp[f"hc_{sub}_alpha"], lp[f"hc_{sub}_bias"]
    gain = jnp.repeat(alpha, np.array([n, n, n * n]),
                      total_repeat_length=_hc_width(cfg))
    proj = proj * gain + bias
    h_pre = jax.nn.sigmoid(proj[..., :n])
    h_post = 2.0 * jax.nn.sigmoid(proj[..., n:2 * n])
    h_res = _sinkhorn(
        jnp.clip(proj[..., 2 * n:], -cfg.hc_res_clamp, cfg.hc_res_clamp)
        .reshape(proj.shape[:-1] + (n, n)),
        cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return h_pre, h_post, h_res


def _hc_mix(x, lp, sub: str, cfg: TransformerConfig):
    """Entry of a sublayer under hyper-connections: x [B, S, n, C] ->
    (u [B, S, C] = H_pre x, the sublayer's input; (H_res x, H_post), what
    `_residual` needs once the sublayer's output is there)."""
    with jax.named_scope("hc.mix"):
        h_pre, h_post, h_res = hc_maps(x, lp, sub, cfg)
        x32 = x.astype(jnp.float32)
        # n is 4: sums of n scaled streams, which fuse elementwise (a dot
        # with a 4-wide contraction would go to the MXU one token a time)
        u = jnp.sum(h_pre[..., None] * x32, axis=-2).astype(x.dtype)
        kept = jnp.sum(h_res[..., None] * x32[:, :, None], axis=-2)
    return u, (kept, h_post)


def _residual(x, y, mix=None):
    """The residual connection, for both sublayers of every program: the
    plain add, or under hyper-connections (mix from `_hc_mix`)
    H_res x + H_post^T y over the n streams."""
    if mix is None:
        return x + y
    kept, h_post = mix
    with jax.named_scope("hc.mix"):
        out = kept + h_post[..., None] * y.astype(jnp.float32)[:, :, None, :]
        return out.astype(x.dtype)


def _hc_expand(x, cfg: TransformerConfig):
    """Embedding rows [B, S, C] -> the layers' carry: the row copied into
    each of the n streams [B, S, n, C] (itself without hyper-connections)."""
    if not cfg.hc_mult:
        return x
    return jnp.broadcast_to(
        x[:, :, None, :], x.shape[:2] + (cfg.hc_mult, x.shape[-1]))


def _hc_collapse(x, cfg: TransformerConfig):
    """The last layer's carry -> [B, S, C]: the streams summed."""
    if not cfg.hc_mult:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=2).astype(x.dtype)


def _linear_mix(x, lp, cfg: TransformerConfig, recur):
    """The token-mixing half of a linear (gated-delta-rule) layer, the
    sibling of `_qkv` + attend + the output projection: from the sublayer's
    input x [B, S, E] the projections q, k, v (one [B, S, C] row, the
    convolution's input), the output gate z, and the decay and write
    strength (`gate_and_beta`, float32); then the program's own
    `recur(u, g, beta) -> (o [B, S, H, dv] float32, kept)` — convolution
    from its tail, the delta rule from its state, both wherever the
    program keeps them — the gated RMSNorm over each head's dv values and
    the output projection. -> (y [B, S, E], kept)."""
    from ..ops.gated_delta import gate_and_beta

    B, S, _ = x.shape
    if cfg.norm_placement != "post":
        x = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    u = jnp.concatenate([
        jnp.einsum("bse,ehd->bshd", x, lp[w].astype(x.dtype)).reshape(B, S, -1)
        for w in ("wq", "wk", "wv")], axis=-1)
    z = jnp.einsum("bse,ehd->bshd", x, lp["wg"].astype(x.dtype))
    a = jnp.einsum("bse,eh->bsh", x, lp["wa"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    b = jnp.einsum("bse,eh->bsh", x, lp["wb"].astype(x.dtype),
                   preferred_element_type=jnp.float32)
    g, beta = gate_and_beta(a, b, lp["a_log"], lp["dt_bias"])
    o, kept = recur(u, g, beta)
    y = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps) * jax.nn.silu(
        z.astype(jnp.float32))
    return jnp.einsum("bshd,hde->bse", y.astype(x.dtype),
                      lp["wo"].astype(x.dtype)), kept


def _recur_sequence(lp, cfg: TransformerConfig):
    """`_linear_mix`'s `recur` for a sequence that starts at its first
    token and keeps nothing (the trainer's forward): zero tail, zero
    state, the chunked form."""
    from ..ops import gated_delta as gd

    H, dk, dv = cfg.linear_n_heads, cfg.linear_d_k, cfg.linear_d_v

    def recur(u, g, beta):
        B = u.shape[0]
        with jax.named_scope("gdn.conv"):
            tail = jnp.zeros((B, cfg.linear_conv_kernel - 1, u.shape[-1]),
                             u.dtype)
            q, k, v = gd.split_heads(
                gd.causal_conv(u, lp["conv_w"], tail), H, dk, dv)
        with jax.named_scope("gdn.scan"):
            o, _ = gd.chunked(q, k, v, g, beta,
                              jnp.zeros((B, H, dk, dv), jnp.float32))
        return o, None

    return recur


def _block(x, lp, cfg: TransformerConfig, cos, sin, attend, constrain_fn, *,
           positions=None, head_major: bool = False, routed=None,
           dense: bool = False, layer=None, linear: bool = False):
    """One decoder layer, written once for every program: `_qkv` (or its
    latent sibling), the program's own attention, the output projection and
    its residual, then the post-norm MLP (dense or sparse experts) and its
    residual — or, `linear`, the gated-delta-rule sibling of the attention
    half (`_linear_mix`, with `attend` the program's `recur`). Each
    sublayer's RMSNorm sits on its input, or under
    `cfg.norm_placement="post"` on its OUTPUT: x + norm(F(x)).
    Both residuals are `_residual`: the plain add, or the
    hyper-connection's mix over the n streams x then carries
    ([B, S, n, C]). `dense` marks a layer of the leading dense stack of a
    model whose other layers have experts; `layer` is the layer's index in
    the model, needed where `lp` holds the expert stacks whole (`_moe`).

    `attend(q, k, v) -> (attn, kept)` is all that differs between the
    programs: causal / flash over the sequence itself (the trainer's
    forward); write the new K/V into the pool, then gather or fused-walk
    the block window (paged prefill and decode, `kept` = the pool's new
    leaves); the cached window plus the in-flight tail (verify, `kept` =
    this layer's k, v, committed after acceptance). `kept` is handed back
    as it came. Under latent attention it is called with `_qkv_latent`'s
    (q, latent, wkv_b).

    `routed(idx)`, where the layer has experts, gets the router's choices
    idx [B*S, k] as soon as they exist — before the residual add, where
    decode's expert-load count has always been traced — and its result is
    returned. Returns (x, kept, routed's result or None)."""
    post = cfg.norm_placement == "post"
    u, mix = _hc_mix(x, lp, "attn", cfg) if cfg.hc_mult else (x, None)
    if linear:
        # `attend` is the program's `recur` here (`_linear_mix`)
        y, kept = _linear_mix(u, lp, cfg, attend)
    else:
        if cfg.kv_lora_rank:
            q, k, v = _qkv_latent(u, lp, cfg, cos, sin, positions)
        else:
            q, k, v = _qkv(u, lp, cfg, cos, sin, positions, head_major)
        attn, kept = attend(q, k, v)
        wo_eq = "bhsd,hde->bse" if head_major else "bshd,hde->bse"
        y = jnp.einsum(wo_eq, attn, lp["wo"].astype(x.dtype))
    if post:
        y = rms_norm(y, lp["attn_norm"], cfg.rms_norm_eps)
    x = _residual(x, y, mix)
    u, mix = _hc_mix(x, lp, "mlp", cfg) if cfg.hc_mult else (x, None)
    h2 = u if post else rms_norm(u, lp["mlp_norm"], cfg.rms_norm_eps)
    stat = None
    if cfg.n_experts and not dense:
        y, idx = _moe(h2, lp, cfg, constrain_fn, layer)
        if routed is not None:
            stat = routed(idx)
    else:
        y = _mlp(h2, lp, cfg, constrain_fn)
    if post:
        y = rms_norm(y, lp["mlp_norm"], cfg.rms_norm_eps)
    axes = ("batch", "seq", None, "embed") if cfg.hc_mult else (
        "batch", "seq", "embed")
    return constrain_fn(_residual(x, y, mix), *axes), kept, stat


def _experts_in_place(cfg: TransformerConfig) -> bool:
    """Whether `_moe_dropless` runs the expert layers: it reads a layer's
    experts in place in their stack."""
    return bool(cfg.n_experts and cfg.moe_impl != "dense"
                and cfg.moe_capacity_factor is None)


def _scan_period(layer_fn, carry, params, cfg: TransformerConfig,
                 wrap=lambda f: f):
    """The layers of a model with a layer PERIOD: ONE `lax.scan` over the
    periods whose body runs the period's blocks in order,
    `layer_fn(stack)(carry, (layer weights, index))` each. The weights are
    stacked per KIND (`_KIND_STACK`) and the body indexes the stacks whole;
    a layer's index counts the layers of ITS kind — each kind addresses
    its own cache (a full layer the KV pool, a linear one the state pool).
    `wrap` is applied to the scan body (the trainer's remat). -> carry."""
    n_periods = cfg.n_layers // len(cfg.layer_period)
    stacks = sorted({_KIND_STACK[k] for k in cfg.layer_period})
    per = {st: sum(_KIND_STACK[k] == st for k in cfg.layer_period)
           for st in stacks}
    steps = {st: layer_fn(st) for st in stacks}

    def period(carry, p):
        seen = dict.fromkeys(stacks, 0)
        for kind in cfg.layer_period:
            st = _KIND_STACK[kind]
            l = p * per[st] + seen[st]
            seen[st] += 1
            # ONE slice a layer, straight out of the kind's whole stack (as
            # a scan slices its xs): a period's layers sliced out together
            # first would be a copy of every weight, every step
            lp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, l, keepdims=False),
                params[st])
            carry, _ = steps[st](carry, (lp, l))
        return carry, None

    carry, _ = lax.scan(
        wrap(period), carry, jnp.arange(n_periods, dtype=jnp.int32))
    return carry


def _scan_stacks(layer_fn, carry, params, layer_ids, cfg: TransformerConfig):
    """`lax.scan` of `layer_fn(stack)(carry, (layer weights, layer index))`
    over the model's layer stacks in turn — the leading dense stack, where
    the model has one, then the main stack — with ONE running layer index
    (`layer_ids`: stack name -> its layers' indices into the KV pool); a
    model with a layer period goes through `_scan_period` instead.
    Dropless experts' three leaves are not sliced by the scan: every layer
    gets them whole, [L, X, ...], under their own keys (`_moe_dropless`
    says why). -> (carry, the main stack's ys)."""
    if cfg.layer_period:
        return _scan_period(layer_fn, carry, params, cfg), None
    ys = None
    for stack in _STACKS:
        if stack not in params:
            continue
        step, layers = layer_fn(stack), params[stack]
        if stack == "layers" and _experts_in_place(cfg):
            whole = {k: layers[k] for k in _EXPERT_KEYS}
            layers = {k: a for k, a in layers.items() if k not in whole}

            def step(carry, per_layer, step=step, whole=whole):
                lp, l = per_layer
                return step(carry, ({**lp, **whole}, l))

        carry, ys = lax.scan(step, carry, (layers, layer_ids[stack]))
    return carry, ys


def _layer_ids(cfg: TransformerConfig):
    """stack name -> int32 indices of its layers in the whole model."""
    ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    if not cfg.first_k_dense:
        return {"layers": ids}
    return {"dense_layers": ids[:cfg.first_k_dense],
            "layers": ids[cfg.first_k_dense:]}


def make_forward(
    cfg: TransformerConfig,
    rules: Optional[ShardingRules] = None,
    mesh=None,
    _return_backbone: bool = False,
):
    """Build forward(params, tokens) -> logits.

    `rules`+`mesh` enable sharding constraints and (for ring/ulysses
    attention) the shard_map-wrapped sequence-parallel kernels.
    """
    cos, sin = _rope_tables(cfg)

    if cfg.attention == "ring":
        inner_attn = partial(ring_attention, axis_name="sp", causal=True)
    elif cfg.attention == "ulysses":
        inner_attn = partial(ulysses_attention, axis_name="sp", causal=True)
    else:
        inner_attn = None

    # dense/flash run head-major ([B,H,S,D], the kernel/MXU-native layout:
    # relayout transposes around attention cost more than attention itself
    # at small d_head); ring/ulysses keep [B,S,H,D] (seq must be a leading
    # non-minor dim for the sp shard_map)
    # (latent attention: the plain dense oracle, K/V materialised per head)
    head_major = inner_attn is None and not cfg.kv_lora_rank
    if cfg.kv_lora_rank and (cfg.attention != "dense" or cfg.pp_stages > 1):
        raise NotImplementedError(
            "latent attention trains through attention='dense' on one "
            f"pipeline stage only, got attention={cfg.attention!r}, "
            f"pp_stages={cfg.pp_stages}"
        )

    def attend(q, k, v):
        if inner_attn is not None and mesh is not None:
            from jax.sharding import PartitionSpec as P

            from ..parallel.sharding import manual_shard_map

            spec = P(None, "sp", None, None)
            return manual_shard_map(
                inner_attn, mesh, (spec, spec, spec), spec, {"sp"}
            )(q, k, v)
        if head_major:
            if cfg.attention == "flash":
                from ..ops.flash_attention import flash_attention

                flash = partial(
                    flash_attention,
                    block_q=min(cfg.flash_block_q, q.shape[2]),
                    block_k=min(cfg.flash_block_k, k.shape[2]),
                    layout="bhsd",
                )
                if mesh is None or rules is None or mesh.size == 1:
                    return flash(q, k, v)
                # GSPMD cannot partition a Mosaic kernel ("wrap the call
                # in a shard_map"): run it per shard of batch and heads —
                # attention is independent across both. Manual over EVERY
                # mesh axis: the compiler refuses the kernel while any
                # axis, even one of size 1, is left to it
                from ..parallel.sharding import manual_shard_map

                q_spec = rules.spec("batch", "heads", None, None)
                kv_spec = rules.spec("batch", "kv_heads", None, None)
                return manual_shard_map(
                    flash, mesh, (q_spec, kv_spec, kv_spec), q_spec,
                    mesh.axis_names,
                )(q, k, v)
            return causal_attention_bhsd(q, k, v)
        # ring/ulysses without a mesh: dense correctness oracle
        return causal_attention(q, k, v)

    def _constrain(x, *axes):
        if rules is None or mesh is None:
            return x
        return constrain(x, rules, *axes, mesh=mesh)

    q_axes = (("batch", "heads", "seq", "head_dim") if head_major
              else ("batch", "seq", "heads", "head_dim"))

    def attend_seq(q, k, v):
        # the sequence attends itself: nothing is kept for later
        if cfg.kv_lora_rank:
            k, v = expand_latent(k, v, cfg)
            return causal_attention(
                q, k, v, scale=attention_scale(cfg)), None
        return attend(_constrain(q, *q_axes), k, v), None

    def make_step(dense: bool):
        def layer_step(x, lp):
            x, _, _ = _block(x, lp, cfg, cos, sin, attend_seq, _constrain,
                             head_major=head_major, dense=dense)
            return x, None

        return layer_step

    def period_step(stack):
        # a layer of a period: (x, (weights, index)) as `_scan_period` calls
        def layer_step(x, per_layer):
            lp, _ = per_layer
            linear = stack == "linear_layers"
            x, _, _ = _block(
                x, lp, cfg, cos, sin,
                _recur_sequence(lp, cfg) if linear else attend_seq,
                _constrain, head_major=head_major, linear=linear)
            return x, None

        return layer_step

    if cfg.remat:
        cp = jax.checkpoint_policies
        policies = {
            "full": None,
            "dots": cp.checkpoint_dots,
            "dots_no_batch": cp.dots_with_no_batch_dims_saveable,
            "flash": cp.save_from_both_policies(
                cp.dots_with_no_batch_dims_saveable,
                cp.save_only_these_names(
                    "flash_out", "flash_lse", "rope_q", "rope_k"
                ),
            ),
            # exactly the residuals backward reads, nothing else: drops the
            # redundant pre-rope wq/wk, wo-out, and mlp-down-out stacks that
            # dots_no_batch would also save (~100MB/layer of scan-stack
            # write+read traffic on the 125M bench)
            "flash_min": cp.save_only_these_names(
                "flash_out", "flash_lse", "rope_q", "rope_k", "attn_v",
                "mlp_gate", "mlp_up",
            ),
            # flash_min minus the mlp gate/up stacks — backward re-derives
            # them (one matmul each from the saved layer input). At
            # d_ff=8192 those two stacks are the LARGEST saved residuals
            # (2 * B*S*d_ff bf16 per layer); trading ~8% more backward
            # flops for that memory is what fits the ~1B HBM-limit config
            "flash_qkv": cp.save_only_these_names(
                "flash_out", "flash_lse", "rope_q", "rope_k", "attn_v",
            ),
        }
        remat = partial(jax.checkpoint, policy=policies[cfg.remat_policy])
    else:
        remat = lambda f: f  # noqa: E731
    step, dense_step = remat(make_step(False)), remat(make_step(True))

    def _apply_layers(params, x):
        if cfg.pp_stages > 1:
            from ..parallel.pipeline import pipeline_apply

            if mesh is None:
                raise ValueError("pp_stages > 1 requires a mesh")

            def stage_fn(stage_layers, xs):
                ys, _ = lax.scan(step, xs, stage_layers)
                return ys

            # stage placement comes from the rule table: "stage" -> "pp"
            # (flat ICI pipeline) or ("dcn", "pp") (multislice pp-outer:
            # stage-groups mapped one per slice, boundary hops over DCN)
            stage_axes = rules.mesh_axes("stage") if rules is not None else None
            return pipeline_apply(
                stage_fn,
                params["layers"],
                x,
                mesh=mesh,
                n_microbatches=cfg.pp_microbatches,
                axis_name=stage_axes or "pp",
                virtual_stages_per_device=cfg.pp_interleave,
            )
        if cfg.layer_period:
            return _scan_period(period_step, x, params, cfg, wrap=remat)
        if "dense_layers" in params:
            x, _ = lax.scan(dense_step, x, params["dense_layers"])
        if not cfg.scan_layers:
            for i in range(cfg.n_layers - cfg.first_k_dense):
                lp_i = jax.tree.map(lambda a: a[i], params["layers"])
                x, _ = step(x, lp_i)
            return x
        x, _ = lax.scan(step, x, params["layers"])
        return x

    def backbone(params, tokens):
        """Everything up to (and including) the final norm; returns the
        final hidden states plus the compute-dtype unembed matrix so the
        loss can choose how to project them (dense vs blockwise)."""
        x = params["embed"].astype(cfg.dtype)[tokens]
        x = _constrain(x, "batch", "seq", "embed")
        params = _cast_matmul_params(cfg, params)
        x = _hc_collapse(_apply_layers(params, _hc_expand(x, cfg)), cfg)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        unembed = params.get("unembed")
        if unembed is None:
            unembed = params["embed"].T
        return x, unembed.astype(cfg.dtype)

    def forward(params, tokens):
        x, unembed = backbone(params, tokens)
        logits = jnp.einsum("bse,ev->bsv", x, unembed)
        logits = _constrain(logits, "batch", "seq", "vocab")
        return logits

    if _return_backbone:
        return forward, backbone, _constrain
    return forward


# --------------------------------------------------------------------------
# autoregressive decode (KV cache)
# --------------------------------------------------------------------------

# pool leaves are [n_layers, num_blocks, block_tokens, kv_heads, head_dim];
# the logical axes reuse the activation rules, so the pool shards like
# activations under every existing mesh preset (dp/fsdp shard the block dim,
# tp shards kv_heads; kv_seq stays unsharded outside sp presets — decode
# scatters at dynamic positions, which sp sharding would turn into
# collectives per token)
KV_CACHE_AXES = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")


def _make_sampler(temperature: float, vocab_pad: int = 0):
    """Greedy argmax (temperature 0) or categorical sampling, shared by
    the paged programs. `vocab_pad` masks the trailing alignment-only vocab
    entries (see TransformerConfig.vocab_pad) to -inf so a padded id can
    never win the argmax / be sampled."""

    def _sample(logits, key):
        if vocab_pad:
            V = logits.shape[-1]
            pad = jnp.arange(V) >= V - vocab_pad
            logits = jnp.where(pad, NEG_INF, logits)
        if temperature > 0.0:
            return jax.random.categorical(
                key, logits.astype(jnp.float32) / temperature, axis=-1
            ).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return _sample


def _token_logprobs(logits, toks, temperature: float, vocab_pad: int = 0):
    """log p(tok) under the distribution `_make_sampler` draws from (same
    vocab_pad masking, same temperature scaling, float32): what a
    `logprobs` engine emits beside each token. logits [N, V], toks [N]."""
    logits = logits.astype(jnp.float32)
    if vocab_pad:
        V = logits.shape[-1]
        logits = jnp.where(jnp.arange(V) >= V - vocab_pad, NEG_INF, logits)
    if temperature > 0.0:
        logits = logits / temperature
    lp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(
        lp, toks[:, None].astype(jnp.int32), axis=-1)[:, 0]


# The host's side of the paged programs (kv_paging.PagedDecodeEngine): what
# a dispatch needs from the host goes up as ONE int32 array, built here with
# NumPy and sliced inside the program, and what the host needs back comes
# down as ONE int32 vector. A transfer of a few hundred bytes costs the host
# what a launch does, so their NUMBER is the cost, not their size.

DECODE_TAIL = 4  # tokens, positions, write_phys, write_off
PREFILL_HEAD = 3  # length, ctx_len, row


def pack_decode_inputs(tables, tokens, positions, write_phys, write_off):
    """`paged_decode`'s one input, int32 [B, Nmax + 4]: each slot's block
    table, then its pending token, its position and the (physical block,
    offset) its new K/V goes to."""
    tables = np.asarray(tables)
    out = np.empty((tables.shape[0], tables.shape[1] + DECODE_TAIL), np.int32)
    out[:, :-DECODE_TAIL] = tables
    for i, col in enumerate((tokens, positions, write_phys, write_off)):
        out[:, i - DECODE_TAIL] = col
    return out


def pack_prefill_inputs(table, tokens, length, ctx_len, row=0):
    """`paged_prefill`'s one input, int32 [3 + Sb + Nmax]: (length,
    ctx_len, row), the suffix padded to its bucket, the slot's block table.
    `row` is the slot's row of a hybrid cache's state pool (read by no
    other program)."""
    return np.concatenate([
        np.asarray([length, ctx_len, row], np.int32),
        np.asarray(tokens, np.int32).reshape(-1),
        np.asarray(table, np.int32)])


def split_host_row(out, n: int, experts: bool = False, logprobs: bool = False,
                   share: bool = False):
    """The vector a paged program hands back, on the host (NumPy) ->
    (tokens [n], moe_load [2] or None, logprobs float32 [n] or None). The
    programs lay it out as tokens | expert counts (decode, with experts;
    [3] where the layer holds a `share` of its experts) | the tokens'
    log-probabilities bit-cast to int32 (`logprobs`)."""
    at = n + (2 + bool(share) if experts else 0)
    return (out[:n], out[n:at] if experts else None,
            out[at:at + n].view(np.float32) if logprobs else None)


def _unembed_matrix(cfg: TransformerConfig, params):
    u = params.get("unembed")
    if u is None:
        u = params["embed"].T
    return u.astype(cfg.dtype)


def _cached_attend(q, kc, vc, mask, scale, n_rep):
    """Attention over cache-layout K/V — the single softmax formulation
    of the "gather" implementation's prefill, decode and verify.

    q [B,Sq,H,D]; kc/vc [B,W,KV,D]; mask [B,Sq,W] (True = attend)."""
    kr = _repeat_kv(kc, n_rep)
    vr = _repeat_kv(vc, n_rep)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, kr, preferred_element_type=jnp.float32
    ) * scale
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vr.dtype), vr)


# per-block quantization scales are [n_layers, num_blocks, kv_heads]: the
# block dim shards with the pool's block dim, kv_heads with tp
KV_SCALE_AXES = ("layers", "batch", "kv_heads")


def refuse_on_latent_pool(cfg: TransformerConfig, *, kv_dtype=None, mesh=None,
                          speculative_k: int = 0) -> None:
    """What a latent (MLA) pool does not support, refused BY NAME where a
    pool or its programs are built — `kv_dtype` int8 (per-block scales of a
    row that is key and value at once), `mesh` (a sharded pool's
    cross-shard merge), `speculative_k` (verify's in-flight tail) — so that
    none of them can run wrong silently. A no-op for per-head pools."""
    if not cfg.kv_lora_rank:
        return
    asked = {
        "kv_dtype": kv_dtype if kv_dtype is not None
        and jnp.dtype(kv_dtype) == jnp.int8 else None,
        "mesh": mesh, "speculative_k": speculative_k or None,
    }
    for name, value in asked.items():
        if value is not None:
            raise NotImplementedError(
                f"a latent (MLA) KV pool does not support {name}={value!r}: "
                "int8 latent rows, a sharded latent pool and speculative "
                "verify over one are not written (ROADMAP, Reach A5 / A7)"
            )


# the leaves of a paged pool that are NOT chains of token blocks: a linear
# layer's recurrent state and conv tail, one row a slot or snapshot
STATE_LEAVES = ("state", "conv")


def refuse_on_state_pool(cfg: TransformerConfig, *, kv_dtype=None, mesh=None,
                         speculative_k: int = 0) -> None:
    """What a hybrid cache (a recurrent-state pool beside the KV pool: a
    model with linear layers) does not support, refused BY NAME where a
    pool or its programs are built — `kv_dtype` int8, `mesh` (a sharded
    state pool) and `speculative_k` (verify would have to roll a state back
    past its rejected drafts). A no-op without linear layers."""
    if not cfg.layers_of("linear"):
        return
    asked = {
        "kv_dtype": kv_dtype if kv_dtype is not None
        and jnp.dtype(kv_dtype) == jnp.int8 else None,
        "mesh": mesh, "speculative_k": speculative_k or None,
    }
    for name, value in asked.items():
        if value is not None:
            raise NotImplementedError(
                f"a hybrid cache (recurrent state beside KV) does not "
                f"support {name}={value!r}: an int8 KV pool beside a state "
                "pool, a sharded state pool and speculative verify over a "
                "recurrent state are not written (ROADMAP, Reach A6)"
            )


def paged_state_row_bytes(cfg: TransformerConfig) -> int:
    """HBM bytes ONE row of the state pool costs across the linear layers
    (the recurrent state and the conv tail of one sequence, whatever its
    length), read off the pool's own leaves; 0 without linear layers."""
    pool = jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 1, 1, state_rows=1))
    return sum(math.prod(pool[n].shape) * pool[n].dtype.itemsize
               for n in STATE_LEAVES if n in pool)


def paged_kv_block_bytes(
    cfg: TransformerConfig, block_tokens: int, dtype=None
) -> int:
    """HBM bytes ONE physical block costs across all layers — the unit the
    engine's byte-budget pool sizing divides by, which is how int8 pools
    end up with ~2x the blocks of a bf16 pool for the same budget. Read off
    the pool's own leaves (K + V + the per-block scales when quantized, or
    the one latent leaf), not recounted."""
    pool = jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, 1, block_tokens, dtype=dtype))
    return sum(
        math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(pool))


def init_paged_kv_cache(
    cfg: TransformerConfig,
    num_blocks: int,
    block_tokens: int,
    mesh=None,
    rules: Optional[ShardingRules] = None,
    dtype=None,
    state_rows: int = 0,
):
    """Allocate the pooled (paged) per-layer KV cache: `num_blocks` physical
    blocks of `block_tokens` tokens each, shared by every decode slot via
    per-slot block tables. The logical axes are KV_CACHE_AXES — the block
    dim takes the "batch" axis (dp/fsdp), so the pool shards under every
    existing mesh preset. Block 0 is reserved as the null block: padded table
    entries and masked-token writes route there (see kv_paging.py).

    `dtype=jnp.int8` stores the pool quantized with per-block, per-kv-head
    f32 scales (`k_scale`/`v_scale` leaves, x ~= q * scale): half the HBM
    per resident token, dequantized at the attention read.

    Under latent attention the pool is ONE leaf, `kv`
    [L, N, block_tokens, 1, latent_row]: a token's normed c_kv and shared
    roped key in its first `latent_width` columns (`cfg.latent_row` says
    why the row is wider), serving every head as key and as value.

    A model with a layer period keeps K and V for its FULL layers only
    (the leaves' layer dim counts those), and with `state_rows` > 0 the
    pool holds the linear layers' STATE POOL beside them (`STATE_LEAVES`):
    `state` [L_lin, rows, dk, H*dv], always float32, one recurrent
    state a row in the layout of ops/gated_delta.py — 96 x 5760 at the
    published widths, no lane or sublane padding on the chip — and `conv`
    [L_lin, rows, (K-1)*C] in the compute dtype, the conv tail flattened so
    that its minor dim is whole lanes too. A row belongs to a decode slot
    or to a snapshot (kv_paging.py). The K/V leaves hold
    `cfg.kv_pool_heads` heads (30 -> 32)."""
    dtype = dtype or cfg.dtype
    refuse_on_state_pool(cfg, kv_dtype=dtype, mesh=mesh)
    if cfg.kv_lora_rank:
        refuse_on_latent_pool(cfg, kv_dtype=dtype, mesh=mesh)
        return {"kv": jnp.zeros(
            (cfg.n_layers, num_blocks, block_tokens, 1, cfg.latent_row), dtype)}
    kv_layers = cfg.layers_of("full")
    shape = (kv_layers, num_blocks, block_tokens, cfg.kv_pool_heads, cfg.d_head)
    pool = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if state_rows and cfg.layers_of("linear"):
        n_lin = cfg.layers_of("linear")
        pool["state"] = jnp.zeros(
            (n_lin, state_rows, cfg.linear_d_k,
             cfg.linear_n_heads * cfg.linear_d_v), jnp.float32)
        pool["conv"] = jnp.zeros(
            (n_lin, state_rows,
             (cfg.linear_conv_kernel - 1) * cfg.linear_channels), cfg.dtype)
    if dtype == jnp.int8:
        sshape = (kv_layers, num_blocks, cfg.kv_pool_heads)
        pool["k_scale"] = jnp.zeros(sshape, jnp.float32)
        pool["v_scale"] = jnp.zeros(sshape, jnp.float32)
    if mesh is not None and rules is not None:
        from ..parallel.sharding import logical_sharding

        sh = logical_sharding(mesh, rules, *KV_CACHE_AXES)
        ssh = logical_sharding(mesh, rules, *KV_SCALE_AXES)
        pool = {
            name: jax.device_put(a, ssh if name.endswith("_scale") else sh)
            for name, a in pool.items()
        }
    return pool


def make_paged_decoder(
    cfg: TransformerConfig,
    rules: Optional[ShardingRules] = None,
    mesh=None,
    temperature: float = 0.0,
    block_tokens: int = 64,
    kv_dtype=None,
    attention_impl: str = "gather",
    logprobs: bool = False,
):
    """Build the paged fast path: (paged_prefill, paged_decode_step,
    paged_verify_step, copy_blocks) over a block pool from
    `init_paged_kv_cache`.

    One loop shape for all three model programs, every implementation and
    every pool dtype: `lax.scan` over (stacked layer weights, layer index),
    each layer one `_block`, with the pool's stacked [L, N, ...] leaves as
    loop-carried state (prefill, decode) or closed over read-only (verify,
    which commits after the loop). A layer writes `leaf.at[l, block, offset]`
    and reads `leaf[l, block]`. The pool argument is donated, so the
    scatter lands in the caller's buffer: no instruction of a compiled
    program scales with the pool (tests/test_chip_compile.py holds the
    programs to that).

    The two programs a serving step runs take what the host knows as ONE
    int32 array (`pack_prefill_inputs`, `pack_decode_inputs`: built with
    NumPy, sliced in the program) and hand back what the host reads as ONE
    int32 vector (`split_host_row`), so a dispatch costs the host one
    upload, one launch and one fetch. `key` is read at temperature > 0
    only; greedy programs take any key (the engine passes one device array
    it made once).

    paged_prefill(params, pool, inputs[3 + Sb + Nmax], key, ctx_blocks, Sb)
        -> (out[1 (+1)], logits[1,V], pool)
      `inputs` packs (length, ctx_len, row), the suffix tokens[Sb] and the
      slot's table[Nmax]; `out` is the next token, followed with
      `logprobs` by its log-probability (float32 bits in an int32).
      B=1 prefill of a prompt SUFFIX whose first `ctx_len` tokens are
      already in the pool — a prefix-cache hit (block multiple), a prior
      prefill CHUNK of the same prompt (any offset; kv_paging's chunked
      admission calls this once per chunk), or 0 for a cold prompt.
      Suffix K/V is scattered into the slot's table blocks — a chunk
      boundary may land mid-block; the straddled block is slot-owned —
      and attention runs over the block window, so the committed span is
      never recomputed. `ctx_blocks` and `Sb` are STATIC (bucketed by the
      caller — kv_paging pads block counts to the same bucket boundaries as
      prompt lengths) and key the compiled programs. `row` is the slot's
      row of a hybrid cache's state pool, read by no other model.

    paged_decode_step(params, pool, inputs[B, Nmax + 4], key)
        -> (out[B (+2) (+B)], logits[B,V], pool)
      `inputs` packs tables[B,Nmax] and the columns tokens, positions,
      write_phys, write_off; `out` is next_tokens[B], then with experts
      moe_load[2], then with `logprobs` the tokens' log-probabilities.
      One cached decode step for every slot: the new K/V is written at the
      host-resolved (physical block, offset) pair — inactive slots route to
      the null block — and attention reads each slot's logical sequence
      via its block table. ONE compiled shape per (B, Nmax) regardless of
      live sequence lengths or block-table contents. `moe_load` is absent
      without experts; with them int32 [2], both summed over the expert
      layers: `moe_hottest`, the load of the step's fullest expert (pairs
      routed to it by the live slots), and `moe_touched`, the experts with
      at least one such pair — the groups whose weights the step reads;
      both over the HELD experts, and where those are a share of the routed
      ones (`cfg.expert_share`) a third, the pairs on held experts.

    paged_verify_step(params, pool, tables[B,Nmax], tokens[B,K1],
                      positions[B], draft_len[B], write_phys[B,K1],
                      write_off[B,K1], key)
        -> (out_tokens[B,K1], accepted[B], pool)
      Speculative decoding's verify: tokens[:, 0] is each slot's pending
      input token and tokens[:, 1:] its (padded) draft; ONE batched
      forward scores all K1 positions, greedy acceptance is computed
      in-graph (draft i survives iff it matches the model's output at
      position i-1 and every earlier draft survived), and ONLY the
      accepted inputs' K/V commit to the pool — rejected entries route to
      the null block, so there is nothing in the pool to roll back.
      Attention never writes before acceptance: the slot's cached window
      is attended through its table (kv_len = positions keeps the
      unwritten span invisible) and the K1 in-flight K/V join it with a
      causal tail mask — appended past the gathered window under
      "gather", folded in as a second online-softmax partial via the
      log-sum-exp merge under "fused".
      Compiled once per (B, K1, Nmax) — the engine buckets K1 (kv_paging).
      Greedy-only: with temperature > 0 the per-position samples would not
      preserve the sampling distribution (the engine refuses to enable
      speculation off greedy).

      fp pools commit with one masked scatter; int8 pools REPLAY the
      single-token RMW sequence (a K1-step in-graph scan of the same
      dequant -> zero-tail -> insert -> requantize write), so the
      committed bytes and scales are bit-identical to non-speculative
      decode having written the accepted tokens one at a time. The only
      int8 divergence is that verify attends the in-flight K/V at full
      precision (the reference attends them post-quantization) — greedy
      tokens can differ only where quantization noise alone would flip
      the argmax.

    copy_blocks(pool, src[n], dst[n]) -> pool
      Copy-on-write: duplicate physical blocks across all layers (refcount
      divergence handled host-side in kv_paging.BlockAllocator).

    `kv_dtype=jnp.int8` runs the pool quantized (per-block per-kv-head f32
    scales): cache writes quantize, attention reads dequantize, and the
    dequantized cache content is authoritative for prefill too — so the
    int8 engine is self-consistent even though it is not bit-identical to
    the fp reference path (which stays exact under the default dtype).

    `attention_impl` picks the attention for EVERY phase — decode (q=1),
    prefill (q=suffix chunk) and speculative verify (q=k+1):
      "gather"  gather each slot's window [B, Nmax*bt] through its block
                table, then dense masked softmax — the exact reference
                path the tests hold to the plain forward.
      "fused"   ops/paged_attention.py walks the block table and attends
                block-in-place with a q-tile grid axis; the op picks the
                Pallas kernel on a TPU backend and its chunked XLA twin
                elsewhere. Composes with KV_CACHE_AXES sharding via
                shard_map: block-sharded pools run per-shard with a
                log-sum-exp merge across the block axes; tp-sharded
                kv_heads need no merge.
    """
    if cfg.pp_stages > 1:
        raise NotImplementedError("decode does not support pp_stages > 1")
    bt = int(block_tokens)
    if bt <= 0:
        raise ValueError(f"block_tokens must be positive, got {bt}")
    if attention_impl not in ("gather", "fused"):
        raise ValueError(
            f"attention_impl must be 'gather' or 'fused', got {attention_impl!r}"
        )
    kv_dtype = kv_dtype or cfg.dtype
    quant = kv_dtype == jnp.int8
    latent = bool(cfg.kv_lora_rank)
    hybrid = bool(cfg.layers_of("linear"))
    refuse_on_latent_pool(cfg, kv_dtype=kv_dtype, mesh=mesh)
    refuse_on_state_pool(cfg, kv_dtype=kv_dtype, mesh=mesh)
    cos, sin = _rope_tables(cfg)
    scale = attention_scale(cfg)
    n_rep = cfg.n_heads // cfg.n_kv_heads

    def _constrain(x, *axes):
        if rules is None or mesh is None:
            return x
        return constrain(x, rules, *axes, mesh=mesh)

    def _pool_heads(q, k, v):
        """q, k, v [B, S, heads, D] at the head counts of the pool's
        leaves (`cfg.kv_pool_heads`; whole zero groups appended, so a query
        head keeps its KV head) — themselves where the pool has the
        model's own count."""
        def pad(x, n):
            if x.shape[2] == n:
                return x
            return jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))

        kvp = cfg.kv_pool_heads
        return pad(q, kvp * n_rep), pad(k, kvp), pad(v, kvp)

    _sample = _make_sampler(temperature, cfg.vocab_pad)

    def _host_row(toks, logits, moe_load=None):
        """Everything the host reads back from one dispatch, one vector."""
        parts = [toks]
        if moe_load is not None:
            parts.append(moe_load)
        if logprobs:
            parts.append(lax.bitcast_convert_type(
                _token_logprobs(logits, toks, temperature, cfg.vocab_pad),
                jnp.int32))
        return jnp.concatenate(parts)  # of one part: the part itself

    def _pool_leaves(pool):
        """(k, v, k_scale, v_scale) of the STACKED pool, scales None for fp
        pools. The layer loops carry these whole and address them by layer
        (`leaf[l, block]`): handed to `lax.scan` as xs/ys instead, every
        layer's [N, bt, KV, D] slice would be copied out of the stack and
        copied back, whole-pool traffic for a few tokens' write."""
        if latent:  # the one latent leaf rides where K does
            return (pool["kv"], None, None, None)
        if hybrid:  # the state pool's two leaves ride behind
            return (pool["k"], pool["v"], None, None,
                    pool["state"], pool["conv"])
        return (pool["k"], pool["v"], pool.get("k_scale"), pool.get("v_scale"))

    def _pool_dict(kc, vc, ksc, vsc, *state):
        if latent:
            return {"kv": kc}
        if quant:
            return {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
        if hybrid:
            return {"k": kc, "v": vc, "state": state[0], "conv": state[1]}
        return {"k": kc, "v": vc}

    layer_ids = _layer_ids(cfg)

    # ---- latent (MLA) pools: one row per token for every head -----------

    def _latent_row(lat):
        """[..., 1, latent_width] -> a pool row, zero-padded to its width."""
        pad = cfg.latent_row - lat.shape[-1]
        return jnp.pad(lat, [(0, 0)] * (lat.ndim - 1) + [(0, pad)])

    def _latent_attend(q, wkv_b, kc, l, tables, positions, kv_len, mask,
                       materialise=False):
        """q [B, Q, H, nope + rope] against layer `l` of the latent pool
        (this step's rows already written) -> [B, Q, H, v_head_dim].

        ABSORBED (decode under both implementations, prefill under
        "fused"): wkv_b's key half folds into q (nope -> rank columns), the
        heads score the cached rows as they are and sum their first `rank`
        columns, and wkv_b's value half maps that sum to v_head_dim — the
        cache is read once for all heads and no K or V is ever expanded.
        "fused" walks the table in place (ops.mla_paged_attention); "gather"
        gathers the window and runs the dense softmax of the per-head path.
        MATERIALISED (`materialise`, "gather" prefill — the oracle the
        absorbed paths are held to): expand the window's rows to per-head
        K and V and attend as the trainer's forward does."""
        R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
        if attention_impl == "gather":
            win = kc[l, tables].reshape(tables.shape[0], -1, 1, cfg.latent_row)
            if materialise:
                k, v = expand_latent(win, wkv_b, cfg)
                return _cached_attend(q, k, v, mask, scale, 1)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q[..., :dn], wkv_b[..., :dn])
        qx = _latent_row(jnp.concatenate([q_lat, q[..., dn:]], axis=-1))
        if attention_impl == "fused":
            from ..ops.paged_attention import mla_paged_attention

            o_lat = mla_paged_attention(
                qx, kc, tables, positions, layer=l, rank=R, scale=scale,
                kv_len=kv_len)
        else:
            o_lat = _cached_attend(
                qx, win, win[..., :R], mask, scale, cfg.n_heads)
        return jnp.einsum("bqhr,rhd->bqhd", o_lat, wkv_b[..., dn:])

    def _gather_window(kc, ksc, l, tables):
        """[B, Nmax] tables -> each slot's window [B, Nmax*bt, KV, D] of
        layer `l` in compute dtype (the "gather" implementation's read)."""
        kw = kc[l, tables]
        if quant:
            kw = _dequant(kw, ksc[l, tables])
        return kw.reshape(tables.shape[0], -1, *kc.shape[3:])

    def _dequant(blocks, scales):
        """[..., bt, KV, D] int8 x [..., KV] -> compute dtype."""
        return (
            blocks.astype(jnp.float32) * scales[..., None, :, None]
        ).astype(cfg.dtype)

    def _quantize(win):
        """[..., bt, KV, D] f32 -> (int8 blocks, [..., KV] f32 scales).
        Leading dims are free: the prefill path quantizes [G] blocks, the
        decode RMW [B], the speculative commit [L, B]."""
        amax = jnp.max(jnp.abs(win), axis=(-3, -1))
        s = amax / 127.0
        q8 = jnp.clip(
            jnp.round(win / jnp.maximum(s, 1e-20)[..., None, :, None]),
            -127, 127,
        ).astype(jnp.int8)
        return q8, s

    def _rmw_insert_quant(blk, s0, knew, wo):
        """The int8 token write's shared math — dequantize the write
        block, zero the stale tail, insert ONE token, requantize — over
        arbitrary leading dims: blk [..., B, bt, KV, D], s0 [..., B, KV],
        knew [..., B, KV, D], wo [B]. The single-token decode step and
        the speculative verify commit both call THIS, so the commit's
        replayed write history cannot drift from the per-token reference
        (spec-vs-plain int8 bit-identity of the pool depends on it).
        With an unchanged scale the existing tokens round-trip exactly;
        a scale bump re-rounds them once at the new grain."""
        B = wo.shape[0]
        deq = blk.astype(jnp.float32) * s0[..., None, :, None]
        keep = jnp.arange(bt)[:, None, None] < wo[:, None, None, None]
        deq = jnp.where(keep, deq, 0.0)
        deq = deq.at[..., jnp.arange(B), wo, :, :].set(
            knew.astype(jnp.float32)
        )
        return _quantize(deq)

    # ---- fused attention (ops/paged_attention.py), sharding-aware -------

    def _flat_axes(logical):
        if rules is None or mesh is None:
            return ()
        axes = rules.mesh_axes(logical)
        if axes is None:
            return ()
        if isinstance(axes, str):
            axes = (axes,)
        return tuple(a for a in axes if a in mesh.shape)

    def _fused_attend(qx, kc, vc, ksc, vsc, l, tables, positions,
                      kv_len=None, partial=False):
        """qx [B, Q, H, D] against layer `l` of the (possibly sharded)
        stacked pool.

        One fused formulation for every phase: decode (Q=1), prefill
        (Q=chunk) and speculative verify (Q=k+1) — query i of slot b sits
        at positions[b]+i and `kv_len` caps the live cached window (verify
        passes kv_len=positions so the not-yet-written in-flight span
        stays invisible; see ops/paged_attention.py).

        `partial=True` returns the unnormalized (acc, m, l) online-softmax
        triple — already combined across block-sharded pool shards, so the
        caller can log-sum-exp-merge extra non-pool keys (the verify
        step's in-flight K1 tail) before normalizing."""
        from jax.sharding import PartitionSpec as P

        from ..ops.paged_attention import merge_partials, paged_attention
        from ..parallel.sharding import manual_shard_map

        scales = dict(k_scale=ksc, v_scale=vsc) if quant else {}
        block_axes = _flat_axes("batch")
        kv_axes = _flat_axes("kv_heads")
        q_axes = _flat_axes("heads")
        if kv_len is None:
            kv_len = positions + qx.shape[1]
        if not block_axes and not kv_axes:
            return paged_attention(
                qx, kc, vc, tables, positions, layer=l, scale=scale,
                kv_len=kv_len, partial_out=partial, **scales,
            )

        def inner(qx, kc, vc, *rest):
            if quant:
                (ksc, vsc), rest = rest[:2], rest[2:]
                sc = dict(k_scale=ksc, v_scale=vsc)
            else:
                sc = {}
            l, tables, positions, kv_len = rest
            if not block_axes:
                return paged_attention(
                    qx, kc, vc, tables, positions, layer=l, scale=scale,
                    kv_len=kv_len, partial_out=partial, **sc,
                )
            # blocks are sharded: remap global table entries to this
            # shard's local ids (others masked dead), attend locally, and
            # log-sum-exp-merge the partial softmax across the block axes
            nloc = kc.shape[1]
            idx = jnp.int32(0)
            for a in block_axes:
                idx = idx * dict(mesh.shape)[a] + lax.axis_index(a)
            lo = idx * nloc
            live = (tables > 0) & (tables >= lo) & (tables < lo + nloc)
            ptab = jnp.where(live, tables - lo, -1).astype(jnp.int32)
            acc, m, den = paged_attention(
                qx, kc, vc, ptab, positions, layer=l, scale=scale,
                signed_tables=True, partial_out=True, kv_len=kv_len, **sc,
            )
            if partial:
                # fold the shards into ONE globally-valid partial triple
                # (replicated over the block axes): pmax the running max,
                # rescale, psum — the caller still owns normalization
                m_g = lax.pmax(m, block_axes)
                e = jnp.exp(m - m_g)
                num = lax.psum(acc * e[..., None], block_axes)
                den = lax.psum(den * e, block_axes)
                return num, m_g, den
            return merge_partials(
                acc, m, den, axis_names=block_axes, out_dtype=qx.dtype
            )

        bspec = tuple(block_axes) if block_axes else None
        kvspec = tuple(kv_axes) if kv_axes else None
        hspec = tuple(q_axes) if q_axes else None
        qspec = P(None, None, hspec, None)
        # the stacked pool's leading layer dim is never sharded
        in_specs = [qspec] + [P(None, bspec, None, kvspec, None)] * 2
        args = [qx, kc, vc]
        if quant:
            in_specs += [P(None, bspec, kvspec)] * 2
            args += [ksc, vsc]
        in_specs += [P(), P(None, None), P(None), P(None)]
        args += [l, tables, positions, kv_len]
        manual = set(block_axes) | set(kv_axes) | set(q_axes)
        out_specs = (
            (qspec, P(None, None, hspec), P(None, None, hspec))
            if partial else qspec
        )
        return manual_shard_map(
            inner, mesh, tuple(in_specs), out_specs, manual
        )(*args)

    def _merge_inflight(q, acc_w, m_w, l_w, k_infl, v_infl, fmask):
        """Fold the verify step's K1 in-flight keys (appended past the
        cached window, never yet in the pool) into the fused window
        partial: a tiny dense causal pass produces its own (acc, m, l)
        and the log-sum-exp combine yields the exact softmax over
        window + in-flight — no gathered window ever exists.

        q [B,K1,H,D]; k_infl/v_infl [B,K1,KV,D]; fmask [B,K1,K1]."""
        from ..ops.paged_attention import merge_partials

        kr = _repeat_kv(k_infl, n_rep).astype(jnp.float32)
        vr = _repeat_kv(v_infl, n_rep).astype(jnp.float32)
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), kr,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = fmask[:, None, :, :]  # [B,1,K1,K1]
        s = jnp.where(mask, s, NEG_INF)
        m_f = jnp.max(s, axis=-1)                      # [B,H,K1]
        p = jnp.where(mask, jnp.exp(s - m_f[..., None]), 0.0)
        l_f = jnp.sum(p, axis=-1)                      # [B,H,K1]
        acc_f = jnp.einsum(
            "bhqk,bkhd->bqhd", p, vr, preferred_element_type=jnp.float32
        )
        m_f = m_f.transpose(0, 2, 1)                   # [B,K1,H]
        l_f = l_f.transpose(0, 2, 1)
        return merge_partials(
            jnp.stack([acc_w, acc_f]), jnp.stack([m_w, m_f]),
            jnp.stack([l_w, l_f]), out_dtype=cfg.dtype,
        )

    def _prefill_body(G, Sb, params, pool, inputs, key):
        params = _cast_matmul_params(cfg, params)
        length, ctx_len, row = (inputs[i] for i in range(PREFILL_HEAD))
        tokens = inputs[PREFILL_HEAD:PREFILL_HEAD + Sb][None]
        table = inputs[PREFILL_HEAD + Sb:]
        x = params["embed"].astype(cfg.dtype)[tokens]
        x = _hc_expand(_constrain(x, "batch", "seq", "embed"), cfg)
        qpos = ctx_len + jnp.arange(Sb)  # global positions of the suffix
        valid_tok = jnp.arange(Sb) < length
        # padded suffix tokens write into the null block (0), never into a
        # real one; real tokens land at table[pos // bt] offset pos % bt
        w_phys = jnp.where(valid_tok, table[qpos // bt], 0)
        w_off = qpos % bt
        window = table[:G]
        # window position j holds global position j; key j is visible to
        # query at global position p iff j <= p (ctx + causal in one mask)
        kmask = (jnp.arange(G * bt)[None, :] <= qpos[:, None])[None]

        def _write_suffix_quant(kc, ksc, l, knew):
            """Quantized prefill write: rebuild layer `l`'s window in f32
            (dequant + suffix insert + stale-tail zeroing), requantize per
            block, scatter the blocks back. Returns the updated pool leaves
            plus the DEQUANTIZED window — attention reads what the cache
            will serve, so int8 prefill and int8 decode agree on every
            key."""
            raw = kc[l, window]  # [G, bt, KV, D] int8
            s0 = ksc[l, window]  # [G, KV]
            win = raw.astype(jnp.float32) * s0[:, None, :, None]
            flat = win.reshape(G * bt, *win.shape[2:])
            # padded suffix tokens scatter out of bounds and are dropped
            # (the fp path routes them to the null block instead)
            wpos = jnp.where(valid_tok, qpos, G * bt)
            flat = flat.at[wpos].set(
                knew.astype(jnp.float32), mode="drop"
            )
            # recycled blocks carry stale values past the live span; they
            # are masked in attention but would poison the block scales
            total = ctx_len + length
            flat = jnp.where(
                jnp.arange(G * bt)[:, None, None] < total, flat, 0.0
            )
            win = flat.reshape(G, bt, *flat.shape[1:])
            q8, s = _quantize(win)
            # shared context blocks (prefix-cache hits, refcount > 1) must
            # never be rewritten — keep their ORIGINAL bytes/scales, so
            # the allocator's copy-on-write invariant holds even if the
            # quantizer stops being a round-trip identity; the slot only
            # owns the suffix blocks it allocated
            owned = jnp.arange(G) >= ctx_len // bt
            q8 = jnp.where(owned[:, None, None, None], q8, raw)
            s = jnp.where(owned[:, None], s, s0)
            kw = _dequant(q8, s).reshape(1, G * bt, *win.shape[2:])
            return kc.at[l, window].set(q8), ksc.at[l, window].set(s), kw

        def layer_fn(carry, per_layer, stack="layers"):
            x, *leaves = carry
            lp, l = per_layer

            def recur(u, g, beta):
                """A linear layer of the suffix, from the slot's row of the
                state pool: the conv from the row's tail and the chunked
                scan from its state (zeros when nothing is committed yet),
                padded tokens passed through (g = 0, beta = 0 leaves a
                state alone); the row takes the state and the tail after
                `length` tokens."""
                from ..ops import gated_delta as gd

                state, conv = leaves[4:]
                H, dk, dv = cfg.linear_n_heads, cfg.linear_d_k, cfg.linear_d_v
                warm = (ctx_len > 0)
                with jax.named_scope("gdn.conv"):
                    tail = lax.dynamic_slice(
                        conv, (l, row, 0), (1, 1, conv.shape[2]))
                    tail = tail.reshape(1, -1, u.shape[-1]) * warm.astype(
                        conv.dtype)
                    q, k, v = gd.split_heads(
                        gd.causal_conv(u, lp["conv_w"], tail), H, dk, dv)
                    conv = lax.dynamic_update_slice(
                        conv, gd.conv_tail(u, tail, length).reshape(1, 1, -1),
                        (l, row, 0))
                with jax.named_scope("gdn.scan"):
                    live = valid_tok[None, :, None]
                    s0 = lax.dynamic_slice(
                        state, (l, row, 0, 0), (1, 1) + state.shape[2:])[0]
                    s0 = gd.from_pool_layout(
                        s0.astype(jnp.float32), H) * warm.astype(jnp.float32)
                    o, s1 = gd.chunked(
                        q, k, v, jnp.where(live, g, 0.0),
                        jnp.where(live, beta, 0.0), s0)
                    state = lax.dynamic_update_slice(
                        state, gd.to_pool_layout(s1).astype(state.dtype)[None],
                        (l, row, 0, 0))
                return o, (*leaves[:4], state, conv)

            def attend(q, k, v):
                kc, vc, ksc, vsc = leaves[:4]
                q = _constrain(q, "batch", "seq", "heads", "head_dim")
                if latent:
                    # k is the suffix's latent rows, v the layer's wkv_b
                    kc = kc.at[l, w_phys, w_off].set(
                        _latent_row(k[0]).astype(kc.dtype))
                    attn = _latent_attend(
                        q, v, kc, l, window[None],
                        jnp.reshape(jnp.asarray(ctx_len, jnp.int32), (1,)),
                        jnp.reshape(
                            jnp.asarray(ctx_len + length, jnp.int32), (1,)),
                        kmask, materialise=True)
                    return attn, (kc, None, None, None)
                q, k, v = _pool_heads(q, k, v)
                # write the suffix K/V first — suffix keys are then read
                # back from the pool, so cache content is authoritative
                # either way
                if quant:
                    kc, ksc, kw = _write_suffix_quant(kc, ksc, l, k[0])
                    vc, vsc, vw = _write_suffix_quant(vc, vsc, l, v[0])
                else:
                    kc = kc.at[l, w_phys, w_off].set(k[0].astype(kc.dtype))
                    vc = vc.at[l, w_phys, w_off].set(v[0].astype(vc.dtype))
                if attention_impl == "fused":
                    # multi-query fused walk over the window blocks in
                    # place: query i sits at ctx_len + i, kv_len caps
                    # recycled-block positions past the live span (quant
                    # kw/vw are unused — the kernel dequantizes from the
                    # pool itself)
                    attn = _fused_attend(
                        q, kc, vc, ksc, vsc, l, window[None],
                        jnp.reshape(jnp.asarray(ctx_len, jnp.int32), (1,)),
                        kv_len=jnp.reshape(
                            jnp.asarray(ctx_len + length, jnp.int32), (1,)
                        ),
                    )
                else:
                    if not quant:
                        kw = _gather_window(kc, ksc, l, window[None])
                        vw = _gather_window(vc, vsc, l, window[None])
                    attn = _cached_attend(q, kw, vw, kmask, scale, n_rep)
                return attn[:, :, :cfg.n_heads], (kc, vc, ksc, vsc, *leaves[4:])

            linear = stack == "linear_layers"
            x, leaves, _ = _block(
                x, lp, cfg, cos, sin, recur if linear else attend, _constrain,
                positions=qpos[None], dense=stack == "dense_layers", layer=l,
                linear=linear)
            return (x, *leaves), None

        (x, *leaves), _ = _scan_stacks(
            lambda stack: partial(layer_fn, stack=stack),
            (x,) + _pool_leaves(pool), params, layer_ids, cfg)
        x = rms_norm(_hc_collapse(x, cfg), params["final_norm"],
                     cfg.rms_norm_eps)
        x_last = x[0, jnp.maximum(length - 1, 0)][None]
        logits = jnp.einsum("be,ev->bv", x_last, _unembed_matrix(cfg, params))
        logits = _constrain(logits, "batch", "vocab")
        return (_host_row(_sample(logits, key), logits), logits,
                _pool_dict(*leaves))

    # jax.jit names a program after its function, and that name is what the
    # profiler's "XLA Modules" line shows: jit_paged_prefill,
    # jit_paged_decode, jit_paged_verify, jit_copy_blocks. The benchmark's
    # reduction finds the programs by these names (PERF.md, spans table).
    _prefill_jits: Dict[Tuple[int, int], Any] = {}

    def _prefill_program(G: int, Sb: int):
        def paged_prefill(params, pool, inputs, key):
            return _prefill_body(G, Sb, params, pool, inputs, key)

        return jax.jit(paged_prefill, donate_argnums=(1,))

    def prefill_dispatch(params, pool, inputs, key, ctx_blocks: int, Sb: int):
        Nmax = inputs.shape[0] - PREFILL_HEAD - Sb
        G = min(int(ctx_blocks) + -(-Sb // bt), Nmax)
        fn = _prefill_jits.get((G, Sb))
        if fn is None:
            fn = _prefill_jits[G, Sb] = _prefill_program(G, Sb)
        return fn(params, pool, inputs, key)

    # (window width G, suffix width Sb) -> program
    prefill_dispatch.programs = _prefill_jits

    def paged_decode(params, pool, inputs, key):
        params = _cast_matmul_params(cfg, params)
        Nmax = inputs.shape[1] - DECODE_TAIL
        tables = inputs[:, :Nmax]
        tokens, positions, write_phys, write_off = (
            inputs[:, Nmax + i] for i in range(DECODE_TAIL))
        W = tables.shape[1] * bt
        x = params["embed"].astype(cfg.dtype)[tokens][:, None, :]  # [B,1,E]
        x = _hc_expand(_constrain(x, "batch", "seq", "embed"), cfg)
        pos2 = positions[:, None]
        kmask = (jnp.arange(W)[None, :] <= pos2)[:, None, :]  # [B,1,W]

        def _write_token_quant(kc, ksc, l, knew):
            """Quantized decode write: read-modify-write each slot's write
            block of layer `l` (shared math in `_rmw_insert_quant` —
            recycled blocks carry stale values past the live span that
            would poison the scale, hence the zero-tail). knew is
            [B, KV, D]."""
            q8, s1 = _rmw_insert_quant(
                kc[l, write_phys], ksc[l, write_phys], knew, write_off
            )
            return (kc.at[l, write_phys].set(q8),
                    ksc.at[l, write_phys].set(s1))

        def load(idx):
            # what the live slots ask of the layer's experts (an inactive
            # slot writes to the null block, 0)
            return _expert_load(idx, write_phys > 0, cfg)

        if hybrid:
            # the rows of the state pool this step advances: the slots that
            # write a real block, listed first (a slot that is not decoding
            # — free, or mid-way through a chunked prefill — keeps its row)
            active = write_phys > 0
            live_rows = jnp.argsort(~active, stable=True).astype(jnp.int32)
            n_live = jnp.sum(active, dtype=jnp.int32)

        def layer_fn(carry, per_layer, stack="layers"):
            x, *leaves = carry
            lp, l = per_layer

            def recur(u, g, beta):
                """A linear layer's decode step: every slot's conv window
                is its row's tail plus this token (the rows are the pool's
                first B; only an active slot's tail is replaced), and the
                state step runs over the live rows alone, in place."""
                from ..ops import gated_delta as gd

                state, conv = leaves[4:]
                H, dk, dv = cfg.linear_n_heads, cfg.linear_d_k, cfg.linear_d_v
                B = u.shape[0]
                with jax.named_scope("gdn.conv"):
                    tails = lax.dynamic_slice(
                        conv, (l, 0, 0), (1, B, conv.shape[2]))
                    tail = tails.reshape(B, -1, u.shape[-1])
                    q, k, v = gd.split_heads(
                        gd.causal_conv(u, lp["conv_w"], tail)[:, 0], H, dk, dv)
                    new = jnp.concatenate([tail[:, 1:], u], axis=1)
                    conv = lax.dynamic_update_slice(
                        conv, jnp.where(active[:, None, None], new,
                                        tail).reshape(tails.shape), (l, 0, 0))
                with jax.named_scope("gdn.step"):
                    o, state = gd.state_step(
                        state, l, live_rows, n_live, q, k, v, g[:, 0],
                        beta[:, 0])
                return o[:, None], (*leaves[:4], state, conv)

            def attend(q, k, v):
                # q [B,1,H,D]; k, v [B,1,KV,D]
                kc, vc, ksc, vsc = leaves[:4]
                if latent:
                    kc = kc.at[l, write_phys, write_off].set(
                        _latent_row(k[:, 0]).astype(kc.dtype))
                    attn = _latent_attend(
                        q, v, kc, l, tables, positions, positions + 1, kmask)
                    return attn, (kc, None, None, None)
                q, k, v = _pool_heads(q, k, v)
                if quant:
                    kc, ksc = _write_token_quant(kc, ksc, l, k[:, 0])
                    vc, vsc = _write_token_quant(vc, vsc, l, v[:, 0])
                else:
                    kc = kc.at[l, write_phys, write_off].set(
                        k[:, 0].astype(kc.dtype))
                    vc = vc.at[l, write_phys, write_off].set(
                        v[:, 0].astype(vc.dtype))
                if attention_impl == "fused":
                    # block-in-place attention: no [B, W] gather exists.
                    # This token's K/V was just written, so the live
                    # window is positions + 1 keys deep
                    attn = _fused_attend(
                        q, kc, vc, ksc, vsc, l, tables, positions,
                        kv_len=positions + 1,
                    )
                else:
                    kw = _gather_window(kc, ksc, l, tables)
                    vw = _gather_window(vc, vsc, l, tables)
                    attn = _cached_attend(q, kw, vw, kmask, scale, n_rep)
                return attn[:, :, :cfg.n_heads], (kc, vc, ksc, vsc, *leaves[4:])

            linear = stack == "linear_layers"
            x, leaves, stats = _block(
                x, lp, cfg, cos, sin, recur if linear else attend, _constrain,
                positions=pos2, routed=load, dense=stack == "dense_layers",
                layer=l, linear=linear)
            return (x, *leaves), stats

        (x, *leaves), stats = _scan_stacks(
            lambda stack: partial(layer_fn, stack=stack),
            (x,) + _pool_leaves(pool), params, layer_ids, cfg)
        x = rms_norm(_hc_collapse(x, cfg), params["final_norm"],
                     cfg.rms_norm_eps)
        logits = jnp.einsum("be,ev->bv", x[:, 0], _unembed_matrix(cfg, params))
        logits = _constrain(logits, "batch", "vocab")
        moe_load = None if stats is None else jnp.sum(stats, axis=0)
        return (_host_row(_sample(logits, key), logits, moe_load), logits,
                _pool_dict(*leaves))

    def _rmw_commit_quant(kc, ksc, knew, wp_i, wo_i):
        """[L]-batched twin of the decode step's `_write_token_quant`:
        ONE token into each slot's write block across every layer at
        once. The RMW math itself is `_rmw_insert_quant`, shared with the
        per-token decode write — replaying it per accepted token
        reproduces the single-token write history bit-for-bit. knew is
        [L, B, KV, D]."""
        q8, s1 = _rmw_insert_quant(kc[:, wp_i], ksc[:, wp_i], knew, wo_i)
        return kc.at[:, wp_i].set(q8), ksc.at[:, wp_i].set(s1)

    def _verify_commit(pool, ks, vs, wp, wo):
        """Write the accepted inputs' K/V stacks ([L,B,K1,KV,D]) into the
        pool; rejected/dead entries arrive with wp == 0 (null block)."""
        if not quant:
            return {
                "k": pool["k"].at[:, wp, wo].set(ks.astype(pool["k"].dtype)),
                "v": pool["v"].at[:, wp, wo].set(vs.astype(pool["v"].dtype)),
            }

        def one(carry, xs):
            kc, ksc, vc, vsc = carry
            k_i, v_i, wp_i, wo_i = xs
            kc, ksc = _rmw_commit_quant(kc, ksc, k_i, wp_i, wo_i)
            vc, vsc = _rmw_commit_quant(vc, vsc, v_i, wp_i, wo_i)
            return (kc, ksc, vc, vsc), None

        # token order matters: each RMW zeroes past its own offset, so the
        # scan walks positions ascending — exactly the sequential history
        (kc, ksc, vc, vsc), _ = lax.scan(
            one,
            (pool["k"], pool["k_scale"], pool["v"], pool["v_scale"]),
            (jnp.moveaxis(ks, 2, 0), jnp.moveaxis(vs, 2, 0), wp.T, wo.T),
        )
        return {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}

    def paged_verify(params, pool, tables, tokens, positions, draft_len,
                     write_phys, write_off, key):
        refuse_on_latent_pool(cfg, speculative_k=True)
        refuse_on_state_pool(cfg, speculative_k=True)
        if cfg.first_k_dense or cfg.hc_mult:
            raise NotImplementedError(
                "speculative verify walks one layer stack with the plain "
                "residual: first_k_dense / hc_mult models are not written")
        params = _cast_matmul_params(cfg, params)
        B, K1 = tokens.shape
        Nmax = tables.shape[1]
        W = Nmax * bt
        x = params["embed"].astype(cfg.dtype)[tokens]  # [B, K1, E]
        x = _constrain(x, "batch", "seq", "embed")
        qpos = positions[:, None] + jnp.arange(K1)[None, :]  # [B, K1]
        # padded tail positions can run past the rope tables (they are
        # rejected by the draft_len mask; clamp keeps the gather in range)
        rope_pos = jnp.minimum(qpos, cfg.max_seq_len - 1)
        # cached window holds positions 0..p-1 (the pending token's K/V is
        # NOT yet written); everything at or past p in a recycled block is
        # stale. In-flight tokens attend each other causally past the
        # window — appended, never written, so acceptance decides what
        # lands in the pool.
        cmask = jnp.broadcast_to(
            jnp.arange(W)[None, None, :] < positions[:, None, None],
            (B, K1, W),
        )
        fmask = jnp.broadcast_to(
            jnp.tril(jnp.ones((K1, K1), bool))[None], (B, K1, K1)
        )
        mask = jnp.concatenate([cmask, fmask], axis=2)  # [B, K1, W+K1]

        # read-only here: the loop closes over the pool, and the accepted
        # K/V commit after it
        kc, vc, ksc, vsc = _pool_leaves(pool)

        def layer_fn(x, per_layer):
            lp, l = per_layer

            def attend(q, k, v):
                q = _constrain(q, "batch", "seq", "heads", "head_dim")
                q, k, v = _pool_heads(q, k, v)
                if attention_impl == "fused":
                    # multi-query fused walk over the cached window
                    # (kv_len = positions keeps the not-yet-written span
                    # invisible and masks recycled-block staleness), then
                    # the K1 in-flight keys fold in as a second
                    # online-softmax partial — the gather-window concat
                    # never materializes
                    acc_w, m_w, l_w = _fused_attend(
                        q, kc, vc, ksc, vsc, l, tables, positions,
                        kv_len=positions, partial=True,
                    )
                    attn = _merge_inflight(q, acc_w, m_w, l_w, k, v, fmask)
                else:
                    kw = _gather_window(kc, ksc, l, tables)
                    vw = _gather_window(vc, vsc, l, tables)
                    kcat = jnp.concatenate([kw, k.astype(kw.dtype)], axis=1)
                    vcat = jnp.concatenate([vw, v.astype(vw.dtype)], axis=1)
                    attn = _cached_attend(q, kcat, vcat, mask, scale, n_rep)
                return attn[:, :, :cfg.n_heads], (k, v)

            x, kv, _ = _block(x, lp, cfg, cos, sin, attend, _constrain,
                              positions=rope_pos, layer=l)
            return x, kv

        x, (ks, vs) = _scan_stacks(
            lambda stack: layer_fn, x, params, layer_ids, cfg)
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = jnp.einsum("bse,ev->bsv", x, _unembed_matrix(cfg, params))
        logits = _constrain(logits, "batch", "seq", "vocab")
        out = _sample(logits, key)  # [B, K1]
        # greedy acceptance: draft i survives iff it equals the model's
        # output one position earlier AND every prior draft survived
        match = (tokens[:, 1:] == out[:, :-1]) & (
            jnp.arange(1, K1)[None, :] <= draft_len[:, None]
        )
        accepted = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(
            axis=1
        ).astype(jnp.int32)
        commit = jnp.arange(K1)[None, :] <= accepted[:, None]  # [B, K1]
        wp = jnp.where(commit, write_phys, 0)
        pool = _verify_commit(pool, ks, vs, wp, write_off)
        return out, accepted, pool

    def copy_blocks(pool, src, dst):
        # every pool leaf (K/V blocks AND their scales) has the physical
        # block dim at axis 1
        return {
            name: a if name in STATE_LEAVES else a.at[:, dst].set(a[:, src])
            for name, a in pool.items()
        }

    return (
        prefill_dispatch,
        jax.jit(paged_decode, donate_argnums=(1,)),
        jax.jit(paged_verify, donate_argnums=(1,)),
        jax.jit(copy_blocks, donate_argnums=(0,)),
    )


def make_copy_state():
    """copy_state(pool, src[n], dst[n]) -> pool: the state pool's rows
    `src` copied onto rows `dst` in every linear layer (snapshot -> slot at
    admission, slot -> snapshot behind a prefill chunk or a finished
    sequence, slot -> slot for a fork), the KV leaves untouched. The pool
    is donated; the program's name is `jit_copy_state`."""
    def copy_state(pool, src, dst):
        return {
            name: a.at[:, dst].set(a[:, src]) if name in STATE_LEAVES else a
            for name, a in pool.items()
        }

    return jax.jit(copy_state, donate_argnums=(0,))


def make_loss_fn(cfg: TransformerConfig, rules=None, mesh=None):
    forward, backbone, _constrain = make_forward(
        cfg, rules, mesh, _return_backbone=True
    )

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        labels = tokens[:, 1:]
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:].astype(bool)
        if cfg.loss_chunk:
            x, unembed = backbone(params, tokens[:, :-1])
            loss, _ = blockwise_softmax_cross_entropy(
                x, unembed, labels, where=mask, chunk=cfg.loss_chunk,
                constrain_logits=lambda l: _constrain(l, "batch", "seq", "vocab"),
            )
            return loss
        logits = forward(params, tokens[:, :-1])
        loss, _ = softmax_cross_entropy_with_int_labels(logits, labels, where=mask)
        return loss

    return loss_fn
