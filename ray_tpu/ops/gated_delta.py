"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): a linear-attention
layer whose cache is ONE matrix per head, whatever the sequence's length.

Per head, with q_t, k_t in R^dk (L2-normalised), v_t in R^dv, a decay
alpha_t = exp(g_t) in (0, 1) and a write strength beta_t:

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   S in R^{dk x dv}, f32

Three forms of the same map, each from ANY starting state:

  recurrence   one token at a time under `lax.scan`: the definition, and
               the oracle the other two are held to (tests/test_gated_delta).
  chunked      the prefill form: over chunks of `chunk` tokens the
               within-chunk dependence is one triangular system, solved
               WITHOUT a substitution loop (the strictly lower matrix is
               nilpotent, so (I + A)^-1 is a product of log2(chunk)
               factors, taken over 8-row blocks and joined by halves), and
               everything else is matmuls; only three small
               matmuls a chunk depend on the carried state.
  state_step   the decode form: one token into the states of the LIVE rows
               of a state pool, in place — a row that is not live costs no
               state traffic.

Beside them the layer's causal depthwise convolution (kernel K, SiLU), whose
cache is the last K-1 inputs of every channel (the "conv tail").

The state pool's layout is [rows, dk, H*dv]: 96 x 5760 float32 at the
published widths tiles the chip's (8, 128) registers with no padding (a
[..., dk, dv] leaf would pad its 192-wide minor dim to 256 lanes, a third
more bytes and traffic), and every operation of the decode step is
elementwise or a reduction over the MAJOR dim in it: q, k, alpha and beta
are expanded from per-head to per-(head, value) lanes by one small matmul
with a constant 0/1 matrix. The chunked form works on per-head [dk, dv]
matrices and relays the state once on the way in and out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
L2_EPS = 1e-6
CHUNK = 64


def gate_and_beta(a, b, a_log, dt_bias):
    """(g, beta) of the tokens from the two H-wide projections a, b
    [..., H] (any float dtype), both float32: the log-decay
    g = -exp(A_log) softplus(a + dt_bias) <= 0 and the write strength
    beta = 2 sigmoid(b) — the factor 2 lets the transition
    I - beta k k^T take eigenvalues in (-1, 1) (`allow_neg_eigval`)."""
    a = a.astype(jnp.float32)
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
        a + dt_bias.astype(jnp.float32))
    return g, 2.0 * jax.nn.sigmoid(b.astype(jnp.float32))


def causal_conv(u, w, tail):
    """Causal depthwise convolution + SiLU. u [B, S, C] inputs, w [C, K],
    tail [B, K-1, C] the K-1 inputs before u (zeros at a sequence's start)
    -> float32 [B, S, C]: silu(sum_j w[:, j] * input_{t-K+1+j})."""
    K = w.shape[1]
    S = u.shape[1]
    window = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    w = w.astype(jnp.float32)
    acc = sum(window[:, j:j + S].astype(jnp.float32) * w[:, j]
              for j in range(K))
    return jax.nn.silu(acc)


def conv_tail(u, tail, length):
    """The conv tail after `length` (<= S, traced) tokens of u [B, S, C]
    behind `tail` [B, K-1, C]: the K-1 inputs before position `length`."""
    window = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    return lax.dynamic_slice_in_dim(window, length, tail.shape[1], axis=1)


def split_heads(c, n_heads: int, d_k: int, d_v: int):
    """Conv output [..., H*(2 dk + dv)] (float32; channels [q | k | v],
    head-major inside each) -> q, k [..., H, dk] L2-normalised per head
    (q also scaled by dk^-1/2), v [..., H, dv]."""
    hk = n_heads * d_k
    lead = c.shape[:-1]
    q = c[..., :hk].reshape(lead + (n_heads, d_k))
    k = c[..., hk:2 * hk].reshape(lead + (n_heads, d_k))
    v = c[..., 2 * hk:].reshape(lead + (n_heads, d_v))
    q = q * lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS)
    k = k * lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    return q * d_k ** -0.5, k, v


# ------------------------------------------------------------ three forms


def recurrence(q, k, v, g, beta, state):
    """The definition, token by token. q, k [B, T, H, dk], v [B, T, H, dv],
    g, beta [B, T, H], state [B, H, dk, dv], all float32
    -> (o [B, T, H, dv], final state)."""
    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        r = jnp.einsum("bhkv,bhk->bhv", S, k_t, precision=HIGHEST)
        u = b_t[..., None] * (v_t - r)
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=HIGHEST)

    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, g, beta))
    state, o = lax.scan(step, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), state


def _unit_lower_inverse(a, leaf: int = 8):
    """(I + A)^-1 for strictly lower triangular A [..., C, C], C a power of
    two, with no substitution loop. A block of at most `leaf` rows is
    inverted by the nilpotent product — with N = -A,
    (I - N)^-1 = (I + N)(I + N^2)(I + N^4) — and two inverted halves are
    joined by [[P, 0], [-Q A21 P, Q]]. The product over a whole 64-row
    chunk is NOT used: its factors hold N^32, whose entries pass 1e18 when
    the chunk's keys are alike (beta k_i.k_j near 1 or 2) and float32 then
    cancels to nothing; an 8-row leaf stops at N^4."""
    C = a.shape[-1]
    if C <= leaf:
        n = -a
        inv = jnp.eye(C, dtype=a.dtype) + n
        span = 2
        while span < C:
            n = jnp.matmul(n, n, precision=HIGHEST)
            inv = inv + jnp.matmul(inv, n, precision=HIGHEST)
            span *= 2
        return inv
    h = C // 2
    p, q = _unit_lower_inverse(
        jnp.stack([a[..., :h, :h], a[..., h:, h:]]), leaf)
    low = -jnp.matmul(jnp.matmul(q, a[..., h:, :h], precision=HIGHEST), p,
                      precision=HIGHEST)
    return jnp.concatenate([
        jnp.concatenate([p, jnp.zeros_like(p)], axis=-1),
        jnp.concatenate([low, q], axis=-1)], axis=-2)


def chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The prefill form: same arguments and results as `recurrence`.

    With the cumulative log-decay c_i inside a chunk (gamma_i = exp c_i,
    Gamma_ij = exp(c_i - c_j) for i >= j):
        T = (I + tril(diag(beta) (K K^T . Gamma), -1))^-1 diag(beta)
        W = T (gamma . K),  U = T V,  D = U - W S
        S' = gamma_C S + (gamma_C / gamma . K)^T D
        O  = (gamma . Q) S + (Q K^T . Gamma . causal) D
    Everything but D, S' and O is computed for all chunks at once; the scan
    over chunks carries S and runs three matmuls a chunk. A tail shorter
    than a chunk is padded with g = 0, beta = 0, which leaves S alone."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk if T >= chunk else 1 << (T - 1).bit_length()
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    N = (T + pad) // C

    def chunks(a):  # [B, N*C, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((B, N, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v = chunks(q), chunks(k), chunks(v)          # [N, B, H, C, d]
    g, beta = chunks(g), chunks(beta)                  # [N, B, H, C]
    c = jnp.cumsum(g, axis=-1)
    gamma = jnp.exp(c)
    lower = jnp.tril(jnp.ones((C, C), bool))
    # exp of a masked difference: the upper triangle would overflow
    big = jnp.exp(jnp.where(lower, c[..., :, None] - c[..., None, :], -jnp.inf))
    kk = jnp.einsum("nbhik,nbhjk->nbhij", k, k, precision=HIGHEST)
    a = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  beta[..., None] * kk * big, 0.0)
    t = _unit_lower_inverse(a) * beta[..., None, :]
    w = jnp.matmul(t, gamma[..., None] * k, precision=HIGHEST)   # [.., C, dk]
    u = jnp.matmul(t, v, precision=HIGHEST)                      # [.., C, dv]
    att = jnp.einsum("nbhik,nbhjk->nbhij", q, k, precision=HIGHEST) * big
    q_in = gamma[..., None] * q
    k_out = jnp.exp(c[..., -1:] - c)[..., None] * k
    decay = gamma[..., -1]                                       # [N, B, H]

    def step(S, xs):
        w_c, u_c, att_c, q_c, k_c, d_c = xs
        d = u_c - jnp.matmul(w_c, S, precision=HIGHEST)
        o = (jnp.matmul(q_c, S, precision=HIGHEST)
             + jnp.matmul(att_c, d, precision=HIGHEST))
        S = d_c[..., None, None] * S + jnp.einsum(
            "bhck,bhcv->bhkv", k_c, d, precision=HIGHEST)
        return S, o

    state, o = lax.scan(step, state.astype(jnp.float32),
                        (w, u, att, q_in, k_out, decay))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)     # [B, N, C, H, dv]
    return o.reshape(B, N * C, H, dv)[:, :T], state


# ------------------------------------------------ the pool's state layout


def to_pool_layout(state):
    """[..., H, dk, dv] -> the pool's [..., dk, H*dv]."""
    H, dk, dv = state.shape[-3:]
    return jnp.moveaxis(state, -3, -2).reshape(
        state.shape[:-3] + (dk, H * dv))


def from_pool_layout(rows, n_heads: int):
    """The pool's [..., dk, H*dv] -> [..., H, dk, dv]."""
    dk, hv = rows.shape[-2:]
    return jnp.moveaxis(
        rows.reshape(rows.shape[:-2] + (dk, n_heads, hv // n_heads)), -2, -3)


def _head_lanes(n_heads: int, d_v: int):
    """[H, H*dv] 0/1: x [.., H] @ it repeats each head's value over its dv
    lanes."""
    return jnp.repeat(jnp.eye(n_heads, dtype=jnp.float32), d_v, axis=1)


def state_step(pool, layer, rows, n_live, q, k, v, g, beta):
    """The decode form, in place on a state pool. pool [L, R, dk, H*dv]
    float32; `rows` [B] int32 lists the LIVE rows first, `n_live` of them;
    q, k [B, H, dk], v [B, H, dv], g, beta [B, H] (float32) are indexed by
    row. One token goes into each live row's state of `layer`:
    -> (o [B, H, dv], zero for a row not live; the pool). The loop runs
    `n_live` times and touches nothing else of the pool."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    lanes = _head_lanes(H, dv)

    def body(i, carry):
        pool, out = carry
        r = rows[i]
        S = lax.dynamic_slice(
            pool, (layer, r, 0, 0), (1, 1, dk, H * dv))[0, 0].astype(jnp.float32)
        # per-head scalars and vectors onto the (head, value) lanes
        per_head = jnp.concatenate(
            [k[r].T, q[r].T, jnp.exp(g[r])[None], beta[r][None]], axis=0)
        wide = jnp.matmul(per_head, lanes, precision=HIGHEST)  # [2dk+2, H*dv]
        k_w, q_w = wide[:dk], wide[dk:2 * dk]
        S = S * wide[2 * dk]
        u = wide[2 * dk + 1] * (v[r].reshape(-1) - jnp.sum(S * k_w, axis=0))
        S = S + k_w * u
        out = lax.dynamic_update_slice(
            out, jnp.sum(S * q_w, axis=0)[None], (r, 0))
        pool = lax.dynamic_update_slice(
            pool, S.astype(pool.dtype)[None, None], (layer, r, 0, 0))
        return pool, out

    pool, out = lax.fori_loop(
        0, n_live, body, (pool, jnp.zeros((B, H * dv), jnp.float32)))
    return out.reshape(B, H, dv), pool
