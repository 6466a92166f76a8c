"""The plain reference of the llama block (blocks/llama.py, which imports
this file when its ref_logits / ref_loss are first called): the decoder
block's forward pass and loss in straightforward jax.numpy, float32, matmul
precision "highest" — no kernel, no cache, no remat, no batching. It follows
the published description of the two families the benchmark runs (InternLM2,
Mistral):

    h   = x + Wo . softmax(causal(q k^T / sqrt(d))) v     q,k with RoPE,
          q,k,v = Wq,Wk,Wv . rmsnorm(x); K/V heads shared by groups (GQA)
    out = h + Wdown . (silu(Wgate . rmsnorm(h)) * (Wup . rmsnorm(h)))
    logits = Wunembed . rmsnorm(out_last_layer)

RoPE rotates the two HALVES of a head (the rotate_half convention of both
published implementations). Queries are processed in blocks of `q_block`
rows against all keys only to bound the score matrix at long sequences;
each block is the same plain softmax.

It takes the program's parameter tree (embed, layers{attn_norm, wq, wk, wv,
wo, mlp_norm, w_gate, w_up, w_down} stacked over layers, final_norm,
unembed) and nothing else from the program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, theta):
    """x: [S, H, D] -> rotated by position 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]      # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("theta", "eps", "q_block"))
def ref_layer(x, lp, *, theta, eps, q_block):
    """One block on one sequence. x: [S, E] float32."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(F32), lp)
        s = x.shape[0]
        h = _rmsnorm(x, lp["attn_norm"], eps)
        q = _rope(jnp.einsum("se,ehd->shd", h, lp["wq"]), theta)
        k = _rope(jnp.einsum("se,ekd->skd", h, lp["wk"]), theta)
        v = jnp.einsum("se,ekd->skd", h, lp["wv"])
        n_rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, n_rep, axis=1)
        v = jnp.repeat(v, n_rep, axis=1)
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
        gate = jnp.einsum("se,ef->sf", h2, lp["w_gate"])
        up = jnp.einsum("se,ef->sf", h2, lp["w_up"])
        return x + jnp.einsum("sf,fe->se", jax.nn.silu(gate) * up, lp["w_down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, unembed, *, eps):
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("se,ev->sv",
                          _rmsnorm(x, final_norm.astype(F32), eps),
                          unembed.astype(F32))


def ref_hidden(params, tokens, conf: dict, q_block: int = 1024):
    """Final-layer hidden states [S, E] of one sequence of token ids."""
    x = params["embed"].astype(F32)[jnp.asarray(tokens)]
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = ref_layer(x, lp, theta=float(conf["rope_theta"]),
                      eps=float(conf["rms_norm_eps"]), q_block=q_block)
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
    position t of tokens[:, :-1] predicts tokens[:, t+1]. Logits are taken
    `row_block` positions at a time so [S, V] is never whole."""
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
