"""The plain reference of the OLMoE block (blocks/olmoe.py, which imports
this file when its ref_logits / ref_loss are first called): the decoder
layer's forward pass and the loss in straightforward jax.numpy, float32,
matmul precision "highest" — no kernel, no cache, no sorting, no batching,
and nothing imported from ray_tpu. It follows OlmoeDecoderLayer of
transformers' modeling_olmoe.py (pre-norm):

    h  = x + Wo . softmax(causal(q k^T / sqrt(d))) v
         q = rope(RMSNorm_q(Wq . n1(x))),  k = rope(RMSNorm_k(Wk . n1(x))),
         v = Wv . n1(x)
    x' = h + sum_{e in top-k(p)} p_e . Wdown_e(silu(Wgate_e . n2(h)) * Wup_e . n2(h))
         p = softmax_f32(Wr . n2(h)), NOT renormalised unless norm_topk_prob
    logits = Wunembed . RMSNorm(x'_last_layer)

RMSNorm_q / RMSNorm_k normalise the WHOLE projection (all heads x head_dim
together, one learned scale per entry), before the split into heads and
before RoPE. EVERY expert is computed on EVERY token and masked by the
top-k weights (zero for an expert that was not chosen): there is no
capacity and nothing to drop. RoPE rotates the two HALVES of a head
(rotate_half). Experts are visited one at a time, so the largest temporary
is [S, intermediate_size].

It takes the program's parameter tree (embed, layers{attn_norm, wq, wk, wv,
wo, q_norm, k_norm, mlp_norm, router, w_gate, w_up, w_down} stacked over
layers, final_norm, unembed) and nothing else from the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# the pieces both blocks share, from the llama block's reference: RMSNorm
# over the last axis, rotate-half RoPE at positions 0..S-1, the output head
from benchmark.reference import F32, _head, _rmsnorm, _rope


def _experts(h, lp, top_k, renormalize):
    """The sparse-expert MLP on h [S, E]: every expert on every token,
    weighted by its top-k router probability (0 where not chosen)."""
    probs = jax.nn.softmax(jnp.einsum("se,ex->sx", h, lp["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if renormalize:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    n_experts = probs.shape[-1]
    weight = jnp.sum(
        jax.nn.one_hot(idx, n_experts, dtype=F32) * top[..., None], axis=1)

    def one(acc, xs):
        w_gate, w_up, w_down, p = xs                     # p: [S]
        inner = jax.nn.silu(h @ w_gate) * (h @ w_up)
        return acc + p[:, None] * (inner @ w_down), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weight.T))
    return out


@functools.partial(jax.jit, static_argnames=(
    "theta", "eps", "top_k", "renormalize", "q_block"))
def ref_layer(x, lp, *, theta, eps, top_k, renormalize, q_block):
    """One layer on one sequence. x: [S, E] float32."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s = x.shape[0]
        h = _rmsnorm(x, lp["attn_norm"], eps)
        heads, kv_heads = lp["wq"].shape[1], lp["wk"].shape[1]
        # projections flat, [S, heads x head_dim]: QK-norm sees all of it
        q = h @ lp["wq"].reshape(h.shape[-1], -1)
        k = h @ lp["wk"].reshape(h.shape[-1], -1)
        q = _rmsnorm(q, lp["q_norm"].reshape(-1), eps)
        k = _rmsnorm(k, lp["k_norm"].reshape(-1), eps)
        q = _rope(q.reshape(s, heads, -1), theta)
        k = _rope(k.reshape(s, kv_heads, -1), theta)
        v = jnp.einsum("se,ekd->skd", h, lp["wv"])
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], F32))
        outs = []
        for lo in range(0, s, q_block):
            qb = q[lo:lo + q_block]
            scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
            qi = jnp.arange(lo, lo + qb.shape[0])[:, None]
            ki = jnp.arange(s)[None, :]
            scores = jnp.where(ki <= qi, scores, -jnp.inf)
            outs.append(jnp.einsum(
                "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
        attn = jnp.concatenate(outs, axis=0)
        x = x + jnp.einsum("shd,hde->se", attn, lp["wo"])
        h2 = _rmsnorm(x, lp["mlp_norm"], eps)
        return x + _experts(h2, lp, top_k, renormalize)


def ref_hidden(params, tokens, conf: dict, q_block: int = 1024):
    """Final-layer hidden states [S, E] of one sequence of token ids."""
    x = params["embed"].astype(F32)[jnp.asarray(tokens)]
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = ref_layer(x, lp, theta=float(conf["rope_theta"]),
                      eps=float(conf["rms_norm_eps"]),
                      top_k=int(conf["num_experts_per_tok"]),
                      renormalize=bool(conf["norm_topk_prob"]),
                      q_block=q_block)
    return x


def ref_logits(params, tokens, conf: dict, positions=None):
    """Logits [len(positions), V] of one sequence (all positions if None)."""
    x = ref_hidden(params, tokens, conf)
    if positions is not None:
        x = x[jnp.asarray(positions)]
    return _head(x, params["final_norm"], params["unembed"],
                 eps=float(conf["rms_norm_eps"]))


def ref_loss(params, tokens, conf: dict, row_block: int = 1024) -> float:
    """Mean next-token cross-entropy over a [B, S+1] batch with full masks:
    position t of tokens[:, :-1] predicts tokens[:, t+1]; no auxiliary
    router term. Logits are taken `row_block` positions at a time so [S, V]
    is never whole."""
    total, count = 0.0, 0
    for row in tokens:
        x = ref_hidden(params, row[:-1], conf)
        labels = jnp.asarray(row[1:])
        for lo in range(0, x.shape[0], row_block):
            logits = _head(x[lo:lo + row_block], params["final_norm"],
                           params["unembed"], eps=float(conf["rms_norm_eps"]))
            logp = jax.nn.log_softmax(logits, axis=-1)
            picked = jnp.take_along_axis(
                logp, labels[lo:lo + row_block, None], axis=-1)
            total += float(-jnp.sum(picked))
            count += int(picked.shape[0])
    return total / count
