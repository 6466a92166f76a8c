"""The plain reference of the Olmo-Hybrid block (blocks/olmo_hybrid.py, which
imports this file when its ref_logits / ref_loss are first called): the
forward pass and the loss in straightforward jax.numpy, float32, matmul
precision "highest" — no kernel, no cache, no chunked scan, and nothing of
ray_tpu's but the parameter tree. Residual stream x, norm = RMSNorm with a
learned scale; in BOTH kinds of layer the norm sits on the sublayer's
OUTPUT and there is none on its input:

    x <- x + norm_a(F_mix(x));   x <- x + norm_m(W_down (silu(W_gate x) * W_up x))
    logits = W_unembed norm_f(x_last_layer)

Linear layer, F_mix = gated delta rule (arXiv:2412.06464), per token t:
    [q~ | k~ | v~] = x W_q | x W_k | x W_v,  z = x W_g,  a = x W_a,  b = x W_b
    c_t = silu(sum_{j<4} w[:, j] * u_{t-3+j})     causal depthwise conv over
                                                  the channels of [q~|k~|v~]
    q_t = c_q / sqrt(sum c_q^2 + 1e-6) / sqrt(dk),  k_t likewise without
    the 1/sqrt(dk), v_t = c_v                      per head
    beta_t = 2 sigmoid(b_t),  alpha_t = exp(-exp(A_log) softplus(a_t + dt_bias))
    S <- alpha_t S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t
    F_mix = concat_h(RMSNorm_dv(o_t; w_o) * silu(z_t)) W_out
The state S [dk, dv] of each head starts at zero and is carried token by
token under ONE lax.scan per block of rows — the per-token recurrence, not
the chunked form the program runs.

Full layer, F_mix = causal softmax attention over all heads, q and k each
RMS-normalised over the WHOLE projection (all heads x head_dim, one learned
scale an entry) and NOT rotated: no rotary embedding, scores x d^-1/2.

Long sequences (the cell's reference prompts are 4k-35k tokens) are walked
in blocks of rows — the MLP and the linear layers `ROWS` at a time (the
state and the last three conv inputs carried between blocks), attention
queries in blocks against all keys — only so that no temporary grows with
rows x width; each block is the same plain arithmetic. Matrices are upcast
to float32 where they are used, one at a time."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.reference import F32, _head, _rmsnorm

ROWS = 2048
L2_EPS = 1e-6


def _mat(w):
    """A matrix of the tree, float32, its trailing dims flattened."""
    return w.astype(F32).reshape(w.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp_residual(x, lp, *, eps):
    """x + norm_m(MLP(x)) on a block of rows."""
    with jax.default_matmul_precision("highest"):
        inner = jax.nn.silu(x @ _mat(lp["w_gate"])) * (x @ _mat(lp["w_up"]))
        return x + _rmsnorm(inner @ _mat(lp["w_down"]),
                            lp["mlp_norm"].astype(F32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _linear_rows(x, lp, state, tail, *, eps):
    """x + norm_a(F_lin(x)) on a block of rows [R, E], from the state
    [H, dk, dv] and the three conv inputs [3, C] left by the rows before.
    -> (rows out, state, tail)."""
    with jax.default_matmul_precision("highest"):
        heads, dk = lp["wq"].shape[1:]
        dv = lp["wv"].shape[2]
        rows = x.shape[0]
        u = jnp.concatenate(
            [x @ _mat(lp["wq"]), x @ _mat(lp["wk"]), x @ _mat(lp["wv"])],
            axis=-1)                                            # [R, C]
        window = jnp.concatenate([tail, u], axis=0)             # [R + 3, C]
        w = lp["conv_w"].astype(F32)                            # [C, 4]
        c = jax.nn.silu(sum(window[j:j + rows] * w[:, j] for j in range(4)))
        q = c[:, :heads * dk].reshape(rows, heads, dk)
        k = c[:, heads * dk:2 * heads * dk].reshape(rows, heads, dk)
        v = c[:, 2 * heads * dk:].reshape(rows, heads, dv)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) / dk ** 0.5
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        beta = 2.0 * jax.nn.sigmoid(x @ _mat(lp["wb"]))         # [R, H]
        alpha = jnp.exp(-jnp.exp(lp["a_log"].astype(F32)) * jax.nn.softplus(
            x @ _mat(lp["wa"]) + lp["dt_bias"].astype(F32)))

        def token(S, xs):
            q_t, k_t, v_t, a_t, b_t = xs
            S = a_t[:, None, None] * S
            upd = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
            S = S + k_t[:, :, None] * upd[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        state, o = jax.lax.scan(token, state, (q, k, v, alpha, beta))
        z = (x @ _mat(lp["wg"])).reshape(rows, heads, dv)
        y = _rmsnorm(o, lp["o_norm"].astype(F32), eps) * jax.nn.silu(z)
        out = y.reshape(rows, -1) @ lp["wo"].astype(F32).reshape(heads * dv, -1)
        return (x + _rmsnorm(out, lp["attn_norm"].astype(F32), eps), state,
                window[rows:])


@functools.partial(jax.jit, static_argnames=("eps", "q_block"))
def _full_mix(x, lp, *, eps, q_block):
    """x + norm_a(F_full(x)) on the whole sequence [S, E]."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        heads, d = lp["wq"].shape[1:]
        q = _rmsnorm(x @ _mat(lp["wq"]),
                     lp["q_norm"].astype(F32).reshape(-1), eps)
        k = _rmsnorm(x @ _mat(lp["wk"]),
                     lp["k_norm"].astype(F32).reshape(-1), eps)
        q = q.reshape(s, heads, d)
        k = k.reshape(s, heads, d)
        v = (x @ _mat(lp["wv"])).reshape(s, heads, d)
        outs = []
        for lo in range(0, s, q_block):
            qb = q[lo:lo + q_block]
            scores = jnp.einsum("qhd,khd->hqk", qb, k) / d ** 0.5
            qi = jnp.arange(lo, lo + qb.shape[0])[:, None]
            scores = jnp.where(jnp.arange(s)[None, :] <= qi, scores, -jnp.inf)
            outs.append(jnp.einsum(
                "hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v))
        attn = jnp.concatenate(outs, axis=0).reshape(s, -1)
        out = attn @ lp["wo"].astype(F32).reshape(heads * d, -1)
        return x + _rmsnorm(out, lp["attn_norm"].astype(F32), eps)


def _by_rows(fn, x):
    return jnp.concatenate(
        [fn(x[lo:lo + ROWS]) for lo in range(0, x.shape[0], ROWS)], axis=0)


def ref_hidden(params, tokens, conf: dict):
    """Final-layer hidden states [S, E] of one sequence of token ids."""
    eps = float(conf["rms_norm_eps"])
    x = params["embed"].astype(F32)[jnp.asarray(tokens)]
    s = x.shape[0]
    # scores [heads, q_block, S] float32 stay near half a gigabyte
    q_block = max(64, min(1024, (1 << 22) // s // 64 * 64))
    seen = {"linear_attention": 0, "full_attention": 0}
    for kind in conf["layer_types"]:
        i = seen[kind]
        seen[kind] += 1
        if kind == "linear_attention":
            lp = jax.tree.map(lambda a: a[i], params["linear_layers"])
            heads, dk = lp["wq"].shape[1:]
            state = jnp.zeros((heads, dk, lp["wv"].shape[2]), F32)
            tail = jnp.zeros((3, lp["conv_w"].shape[0]), F32)
            blocks = []
            for lo in range(0, s, ROWS):
                rows, state, tail = _linear_rows(
                    x[lo:lo + ROWS], lp, state, tail, eps=eps)
                blocks.append(rows)
            x = jnp.concatenate(blocks, axis=0)
        else:
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x = _full_mix(x, lp, eps=eps, q_block=q_block)
        x = _by_rows(functools.partial(_mlp_residual, lp=lp, eps=eps), x)
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
