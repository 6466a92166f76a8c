"""The plain reference of the Xing4.0 block (blocks/xing4.py, which imports
this file when its ref_logits / ref_loss are first called): the forward pass
in straightforward jax.numpy, float32, matmul precision "highest" — no
kernel, no cache, no sorting, no absorbed projection, and nothing imported
from ray_tpu. Per token there are n = hc_mult residual streams X [n, C],
X_0 = the embedding row copied n times. A layer is two sublayers F
(attention, then feed-forward), each under its OWN hyper-connection (mHC,
arXiv:2512.24880 over arXiv:2409.19606):

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)     one RMS over n*C
    H~     = alpha . (x~ phi) + b      phi [n*C, 2n + n^2], three gains alpha
    H_pre  = sigmoid(H~[:n])           H_post = 2 sigmoid(H~[n:2n])
    H_res  = SK(clip(H~[2n:], -30, 30) as [n, n])
             SK: exp, then hc_sinkhorn_iters rounds of rows / (sum + hc_eps),
             columns / (sum + hc_eps)
    u = H_pre X;   y = F(RMSNorm(u));   X <- H_res X + H_post^T y
    after the last layer h = sum_i X[i], final norm, output head

F_att is latent attention (MLA, DeepSeek-V2 §2.1), MATERIALISED:

    c_q = RMSNorm(h Wq_a);  q = c_q Wq_b -> heads x (nope + rope)
    [c_kv | k_r] = h Wkv_a;  c_kv <- RMSNorm(c_kv)
    [k_nope | v] = c_kv Wkv_b -> heads x (nope + v)
    score = (q_nope . k_nope + RoPE(q_rope) . RoPE(k_r)) . s, causal softmax
    out   = concat_heads(softmax . v) Wo
    RoPE: YaRN inv_freq (theta^(-2i/d) blended with the same over `factor`,
    ramp between the correction dims of beta_fast / beta_slow over the
    original positions), cos/sin times mscale ratio; rotates the two HALVES
    s = (nope + rope)^-1/2 . (0.1 mscale_all_dim ln factor + 1)^2

F_ffn is a gated SiLU MLP in the leading `first_k_dense_replace` layers and
afterwards

    sc = sigmoid(h Wr);  chosen = top-k of (sc + bias);  w = sc[chosen]
    w <- w / (sum w + 1e-20) if norm_topk_prob;  w <- w . routed_scaling_factor
    y = sum_chosen w_e E_e(h) + E_shared(h)        every E a gated SiLU MLP

EVERY expert is computed on EVERY token and masked by its weight (zero
where not chosen): no capacity, nothing dropped.

How it is laid out, and why. The reference reads whole requests of 4k-17k
tokens beside a replica that fills the chip, so nothing the size of the
sequence x hidden is kept on the device twice: the streams live on the HOST
between calls, in chunks of `CHUNK` rows; everything but attention is per
token and runs chunk by chunk; a layer's k_nope and v (the only tensors
attention needs of other tokens) are expanded once for the whole sequence;
weights are upcast ONE matrix (one expert) at a time, inside the call that
uses it, and a layer is never sliced out of its stack outside such a call (an
expert layer's three stacks are 1.4 GB): the calls take the whole stack and
the layer's index, and an expert's matrices are picked `[layer, expert]`
inside the loop over experts. The sequence is padded to a multiple of `PAD_TO` so that the
requests of one check share compiled shapes; padded rows come after every
real token, so causality hides them.

Near-ties of the router (`reference.router_tie_margin` in the file, 0 =
off). The top-k choice among the experts' biased scores is a step function
of its input: where the k-th and the (k+1)-th score lie closer than the
arithmetic of a bfloat16 replica can tell apart, either choice is a right
answer, and with seeded weights the two answers' logits differ by their
whole size. So at the positions whose logits are ASKED for (`positions`),
and only there, the reference follows every choice the margin admits: a row
splits at an expert layer into one row per admissible set of k experts
(`tie_choices`), each carried through the remaining layers on its own
(against the keys and values of the sequence's main pass: what one token's
choice does to LATER tokens reaches them through attention over thousands
of keys, and is left out), up to `MAX_BRANCHES` rows a position, nearest
ties first. `ref_branch_logits` gives every branch's logits; `ref_logits` folds
them into one row a position: for each token, the reference's top logit
less the token's SMALLEST distance from the top over the branches — so the
harness's near-argmax rule asks that the served token be near the top under
at least one routing the margin admits. With no tie within the margin that
is the plain row, value for value.

It takes the program's parameter tree (embed, dense_layers{...},
layers{...}, final_norm, unembed; attention leaves wq_a, q_a_norm, wq_b
[q, heads, nope + rope], wkv_a [hidden, kv + rope], kv_a_norm, wkv_b
[kv, heads, nope + v], wo; hyper-connection leaves hc_{attn,mlp}_{phi,
alpha,bias}; router, router_bias, w_gate/w_up/w_down per expert,
ws_gate/ws_up/ws_down shared) and nothing else from the program."""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
CHUNK = 128    # rows of one per-token call (scores are [heads, CHUNK, S])
MAX_BRANCHES = 256  # routings followed at one asked position, nearest ties first
PAD_TO = 2048  # sequence lengths are padded up to a multiple of this
VOCAB_BLOCK = 16384


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def yarn_inv_freq(conf: dict) -> np.ndarray:
    rs, dim = conf["rope_scaling"], conf["qk_rope_head_dim"]
    theta, orig = float(conf["rope_theta"]), rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    extra = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / rs["factor"] * ramp + extra * (1 - ramp)).astype(np.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def score_scale(conf: dict) -> float:
    rs = conf["rope_scaling"]
    return ((conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]) ** -0.5
            * _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2)


def _rope(x, pos, inv_freq, m):
    """x [S, ..., d] at positions pos [S]: rotate the two halves."""
    ang = pos.astype(F32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = (jnp.cos(ang) * m).reshape(shape), (jnp.sin(ang) * m).reshape(shape)
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _settings(conf: dict) -> tuple:
    """What the jitted pieces need of the file, hashable."""
    rs = conf["rope_scaling"]
    return (
        ("eps", float(conf["rms_norm_eps"])), ("n", int(conf["hc_mult"])),
        ("iters", int(conf["hc_sinkhorn_iters"])),
        ("hc_eps", float(conf["hc_eps"])),
        ("clamp", float(conf["mhc_h_res_clamp_max"])),
        ("nope", int(conf["qk_nope_head_dim"])),
        ("rank", int(conf["kv_lora_rank"])),
        ("scale", float(score_scale(conf))),
        ("rope_m", _mscale(rs["factor"], rs["mscale"])
         / _mscale(rs["factor"], rs["mscale_all_dim"])),
        ("top_k", int(conf["num_experts_per_tok"])),
        ("renorm", bool(conf["norm_topk_prob"])),
        ("route_scale", float(conf["routed_scaling_factor"])),
    )


def hc_maps(x, phi, alpha, bias, st: dict):
    """x [S, n, C] -> H_pre [S, n], H_post [S, n], H_res [S, n, n]."""
    n = st["n"]
    flat = x.reshape(x.shape[0], -1)
    flat = flat / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True) + st["eps"])
    proj = flat @ phi
    pre = alpha[0] * proj[:, :n] + bias[:n]
    post = alpha[1] * proj[:, n:2 * n] + bias[n:2 * n]
    res = alpha[2] * proj[:, 2 * n:] + bias[2 * n:]
    m = jnp.exp(jnp.clip(res, -st["clamp"], st["clamp"])).reshape(-1, n, n)
    for _ in range(st["iters"]):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + st["hc_eps"])
        m = m / (jnp.sum(m, axis=1, keepdims=True) + st["hc_eps"])
    return jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post), m


class _Layer:
    """Layer `li` of a stack of layers, leaf by leaf: `lp[name]` slices that
    one leaf, `lp.expert(name, e)` one expert's matrix of it."""

    def __init__(self, stack, li):
        self.stack, self.li = stack, li

    def __contains__(self, name):
        return name in self.stack

    def __getitem__(self, name):
        return self.stack[name][self.li]

    def expert(self, name, e):
        return self.stack[name][self.li, e]


def _hc_in(x, lp, sub, st):
    """-> (u [S, C] = H_pre X, H_post, H_res X)."""
    h_pre, h_post, h_res = hc_maps(
        x, lp[f"hc_{sub}_phi"].astype(F32), lp[f"hc_{sub}_alpha"].astype(F32),
        lp[f"hc_{sub}_bias"].astype(F32), st)
    return (jnp.einsum("sn,snc->sc", h_pre, x), h_post,
            jnp.einsum("smn,snc->smc", h_res, x))


def _hc_out(kept, h_post, y):
    return kept + h_post[:, :, None] * y[:, None, :]


def _attn_rows(x, lp, pos, inv_freq, st):
    """The attention sublayer's per-token half on rows x [S, n, C]:
    -> (q_nope [S, H, nope], q_rope [S, H, rope] roped, c_kv [S, rank]
    normed, k_rope [S, rope] roped, H_post, H_res X)."""
    u, h_post, kept = _hc_in(x, lp, "attn", st)
    h = _rmsnorm(u, lp["attn_norm"].astype(F32), st["eps"])
    c_q = _rmsnorm(h @ lp["wq_a"].astype(F32), lp["q_a_norm"].astype(F32),
                   st["eps"])
    q = jnp.einsum("sq,qhd->shd", c_q, lp["wq_b"].astype(F32))
    ckv = h @ lp["wkv_a"].astype(F32)
    c_kv = _rmsnorm(ckv[:, :st["rank"]], lp["kv_a_norm"].astype(F32), st["eps"])
    k_rope = _rope(ckv[:, st["rank"]:], pos, inv_freq, st["rope_m"])
    q_rope = _rope(q[..., st["nope"]:], pos, inv_freq, st["rope_m"])
    return q[..., :st["nope"]], q_rope, c_kv, k_rope, h_post, kept


@functools.partial(jax.jit, static_argnames=("settings",))
def latent_rows(x, stack, li, pos, inv_freq, *, settings):
    """What a chunk of tokens leaves for later ones: (c_kv, k_rope)."""
    with jax.default_matmul_precision("highest"):
        _, _, c_kv, k_rope, _, _ = _attn_rows(
            x, _Layer(stack, li), pos, inv_freq, dict(settings))
        return c_kv, k_rope


@functools.partial(jax.jit, static_argnames=("nope",))
def expand(c_kv, stack, li, *, nope):
    """[S, rank] -> per-head k_nope [S, H, nope], v [S, H, v]."""
    with jax.default_matmul_precision("highest"):
        kv = jnp.einsum("sr,rhd->shd", c_kv, stack["wkv_b"][li].astype(F32))
        return kv[..., :nope], kv[..., nope:]


def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))
            ) @ w_down.astype(F32)


def _scores(h, lp):
    """-> (scores [S, X], scores + bias: what the choice is made on)."""
    scores = jax.nn.sigmoid(h @ lp["router"].astype(F32))
    return scores, scores + lp["router_bias"].astype(F32)


def _experts(h, lp, st, idx=None):
    """Sigmoid-routed experts + the shared one on h [S, C]: every expert
    on every token, weighted (0 where not chosen). `idx` [S, k], when
    given, IS the choice (a branch of a near-tie)."""
    scores, biased = _scores(h, lp)
    if idx is None:
        _, idx = jax.lax.top_k(biased, st["top_k"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if st["renorm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * st["route_scale"]
    weight = jnp.sum(
        jax.nn.one_hot(idx, scores.shape[-1], dtype=F32) * w[..., None], axis=1)

    def one(acc, xs):  # one expert's matrices are picked and upcast here
        e, p = xs
        return acc + p[:, None] * _gated(
            h, lp.expert("w_gate", e), lp.expert("w_up", e),
            lp.expert("w_down", e)), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h), (jnp.arange(scores.shape[-1]), weight.T))
    return out + _gated(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])


def _attend(x, lp, pos, inv_freq, k_nope, k_rope, v, st, own: bool):
    """The attention sublayer on rows x [S, n, C] at positions pos against
    the layer's keys and values of the WHOLE sequence (k_nope [K, H, nope],
    k_rope [K, rope], v [K, H, v]; key j sits at position j). `own`: the
    row's key and value AT its position are its own (a branch row, whose
    stream is not the main pass's), the sequence's only before it."""
    q_nope, q_rope, c_kv, k_r, h_post, kept = _attn_rows(x, lp, pos, inv_freq, st)
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) * st["scale"]
    at = jnp.arange(k_nope.shape[0])[None, :]
    seen = at < pos[:, None] if own else at <= pos[:, None]
    scores = jnp.where(seen[None], scores, -jnp.inf)
    if own:
        kv = jnp.einsum("sr,rhd->shd", c_kv, lp["wkv_b"].astype(F32))
        mine = (jnp.einsum("qhd,qhd->hq", q_nope, kv[..., :st["nope"]])
                + jnp.einsum("qhd,qd->hq", q_rope, k_r)) * st["scale"]
        p = jax.nn.softmax(
            jnp.concatenate([scores, mine[..., None]], axis=-1), axis=-1)
        attn = (jnp.einsum("hqk,khd->qhd", p[..., :-1], v)
                + p[..., -1].T[..., None] * kv[..., st["nope"]:])
    else:
        attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return _hc_out(kept, h_post,
                   jnp.einsum("shd,hde->se", attn, lp["wo"].astype(F32)))


def _ffn_in(x, lp, st):
    u, h_post, kept = _hc_in(x, lp, "mlp", st)
    return _rmsnorm(u, lp["mlp_norm"].astype(F32), st["eps"]), h_post, kept


def _ffn(x, lp, st, idx=None):
    """The feed-forward sublayer on rows x [S, n, C]."""
    h2, h_post, kept = _ffn_in(x, lp, st)
    if "router" in lp:
        y = _experts(h2, lp, st, idx)
    else:
        y = _gated(h2, lp["w_gate"], lp["w_up"], lp["w_down"])
    return _hc_out(kept, h_post, y)


@functools.partial(jax.jit, static_argnames=("settings",))
def layer_rows(x, stack, li, pos, inv_freq, k_nope, k_rope, v, *, settings):
    """One whole layer (`li` of `stack`) on the rows x [S, n, C] of the
    main pass."""
    st, lp = dict(settings), _Layer(stack, li)
    with jax.default_matmul_precision("highest"):
        return _ffn(_attend(x, lp, pos, inv_freq, k_nope, k_rope, v, st,
                            own=False), lp, st)


@functools.partial(jax.jit, static_argnames=("settings",))
def branch_attend(x, stack, li, pos, inv_freq, k_nope, k_rope, v, *, settings):
    """Branch rows through the attention sublayer -> (x, what the router
    chooses on [S, X]; None in a dense layer)."""
    st, lp = dict(settings), _Layer(stack, li)
    with jax.default_matmul_precision("highest"):
        x = _attend(x, lp, pos, inv_freq, k_nope, k_rope, v, st, own=True)
        biased = _scores(_ffn_in(x, lp, st)[0], lp)[1] if "router" in lp else None
        return x, biased


@functools.partial(jax.jit, static_argnames=("settings",))
def branch_ffn(x, stack, li, idx, *, settings):
    st = dict(settings)
    with jax.default_matmul_precision("highest"):
        return _ffn(x, _Layer(stack, li), st, idx)


def tie_choices(biased, k: int, margin: float) -> list:
    """Every set of k experts that scores within `margin` of the cut admit:
    [(cost, experts)], nearest first; cost = what the set's scores lack of
    the k largest (0: the top-k itself, always first). An expert more than
    `margin` above the (k+1)-th score is in every set, one more than
    `margin` below the k-th in none."""
    biased = np.asarray(biased, np.float64)
    order = np.argsort(-biased, kind="stable")
    s = biased[order]
    sure = [int(e) for e, v in zip(order[:k], s[:k]) if v > s[k] + margin]
    open_ = [int(e) for e, v in zip(order, s)
             if s[k - 1] - margin <= v <= s[k] + margin]
    best = float(s[:k].sum())
    out = [(best - float(biased[sure + list(c)].sum()), sure + list(c))
           for c in itertools.combinations(open_, k - len(sure))]
    return sorted(out, key=lambda t: t[0])


class _Branches:
    """The rows that follow the router's near-ties at the asked positions:
    x [R, n, C] on the host, each with its position's index, what its
    choices cost so far, and the (layer, cost) of every tie it took the
    far side of."""

    def __init__(self, x, pos, margin, top_k):
        self.x, self.pos = x, np.asarray(pos, np.int32)
        self.owner = list(range(len(pos)))
        self.cost = [0.0] * len(pos)
        self.took = [[] for _ in pos]
        self.margin, self.top_k = float(margin), int(top_k)

    @staticmethod
    def _chunks(fn, *arrays):
        """fn over CHUNK rows at a time (one compiled shape) -> outputs
        cut back to the rows given."""
        r, outs = arrays[0].shape[0], []
        for lo in range(0, r, CHUNK):
            part = [a[lo:lo + CHUNK] for a in arrays]
            n = part[0].shape[0]
            part = [jnp.asarray(np.pad(
                a, [(0, CHUNK - n)] + [(0, 0)] * (a.ndim - 1))) for a in part]
            out = fn(*part)
            out = out if isinstance(out, tuple) else (out,)
            outs.append([None if o is None else np.asarray(o)[:n] for o in out])
        return [None if col[0] is None else np.concatenate(col)
                for col in zip(*outs)]

    def layer(self, depth, stack, li, inv_freq, k_nope, k_rope, v, settings):
        r = len(self.owner)
        x, biased = self._chunks(
            lambda x, pos: branch_attend(x, stack, li, pos, inv_freq, k_nope,
                                         k_rope, v, settings=settings),
            self.x, self.pos)
        idx = np.zeros((r, self.top_k), np.int32)
        if biased is not None:
            rows = []  # (owner, cost, took, source row, experts)
            for i in range(r):
                for cost, experts in tie_choices(biased[i], self.top_k,
                                                 self.margin):
                    took = self.took[i] + ([(depth, cost)] if cost else [])
                    rows.append((self.owner[i], self.cost[i] + cost, took,
                                 i, experts))
            kept = []
            for o in sorted(set(self.owner)):  # nearest ties first
                mine = sorted((t for t in rows if t[0] == o),
                              key=lambda t: t[1])
                kept.extend(mine[:MAX_BRANCHES])
            self.owner = [t[0] for t in kept]
            self.cost = [t[1] for t in kept]
            self.took = [t[2] for t in kept]
            src = np.asarray([t[3] for t in kept])
            x, self.pos = x[src], self.pos[src]
            idx = np.asarray([t[4] for t in kept], np.int32)
        self.x, = self._chunks(
            lambda x, idx: branch_ffn(x, stack, li, idx, settings=settings),
            x, idx)


def _layers(params):
    """The model's layers in order: (the stack a layer lies in, its index)."""
    for name in ("dense_layers", "layers"):
        if name in params:
            n = jax.tree.leaves(params[name])[0].shape[0]
            for i in range(n):
                yield params[name], jnp.int32(i)


def _forward(params, tokens, conf: dict, chunk=None, pad_to=None,
             follow=None):
    """The main pass: hidden states [S, C] after the last layer (streams
    summed), float32, of one sequence of token ids, on the host (module
    docstring). `follow` = (positions, margin): also the branch rows at
    those positions -> (hidden, _Branches with x after the last layer)."""
    chunk, pad_to = chunk or CHUNK, pad_to or PAD_TO
    tokens = np.asarray(tokens, np.int32)
    s = int(tokens.size)
    padded = -(-s // pad_to) * pad_to
    tokens = np.concatenate([tokens, np.zeros(padded - s, np.int32)])
    settings, n = _settings(conf), int(conf["hc_mult"])
    inv_freq = jnp.asarray(yarn_inv_freq(conf))
    starts = range(0, padded, chunk)
    # rows are gathered before they are upcast: the table is never whole in f32
    xs = [np.asarray(jnp.repeat(
        params["embed"][jnp.asarray(tokens[lo:lo + chunk])].astype(F32)[:, None],
        n, axis=1)) for lo in starts]
    pos = [jnp.arange(lo, min(lo + chunk, padded), dtype=jnp.int32)
           for lo in starts]
    branches = None
    if follow is not None:
        at = np.asarray(follow[0], np.int64)
        branches = _Branches(
            np.stack([xs[p // chunk][p % chunk] for p in at]), at, follow[1],
            conf["num_experts_per_tok"])
    for depth, (stack, li) in enumerate(_layers(params)):
        rows = [latent_rows(jnp.asarray(x), stack, li, p, inv_freq,
                            settings=settings) for x, p in zip(xs, pos)]
        c_kv = jnp.concatenate([r[0] for r in rows])
        k_rope = jnp.concatenate([r[1] for r in rows])
        del rows
        k_nope, v = expand(c_kv, stack, li, nope=int(conf["qk_nope_head_dim"]))
        del c_kv
        if branches is not None:
            branches.layer(depth, stack, li, inv_freq, k_nope, k_rope, v,
                           settings)
        xs = [np.asarray(layer_rows(jnp.asarray(x), stack, li, p, inv_freq,
                                    k_nope, k_rope, v, settings=settings))
              for x, p in zip(xs, pos)]
    return np.concatenate(xs)[:s].sum(axis=1), branches


def ref_hidden(params, tokens, conf: dict, chunk: int = None,
               pad_to: int = None) -> np.ndarray:
    """Hidden states [S, C] after the last layer (streams summed)."""
    return _forward(params, tokens, conf, chunk, pad_to)[0]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, final_norm, unembed_block, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, final_norm.astype(F32), eps) @ unembed_block.astype(F32)


def _head(x, params, conf):
    """Logits [rows, V]; the output matrix is upcast a block of columns at
    a time."""
    x, v = jnp.asarray(x), params["unembed"].shape[1]
    return jnp.concatenate([
        _head_block(x, params["final_norm"],
                    params["unembed"][:, lo:lo + VOCAB_BLOCK],
                    eps=float(conf["rms_norm_eps"]))
        for lo in range(0, v, VOCAB_BLOCK)], axis=1)


def tie_margin(conf: dict) -> float:
    return float((conf.get("reference") or {}).get("router_tie_margin", 0.0))


def ref_branch_logits(params, tokens, conf: dict, positions, **kw) -> list:
    """For each of `positions`: {"logits" [B, V], "cost" [B], "took": per
    branch the (layer, cost) of the ties it took the far side of} over the
    B routings the file's margin admits there; branch 0 is the reference's
    own choice (cost 0)."""
    _, br = _forward(params, tokens, conf, follow=(positions, tie_margin(conf)),
                     **kw)
    x = br.x.sum(axis=1)
    logits = np.concatenate([np.asarray(_head(x[lo:lo + CHUNK], params, conf))
                             for lo in range(0, x.shape[0], CHUNK)])
    out = []
    for o in range(len(positions)):
        rows = [i for i, owner in enumerate(br.owner) if owner == o]
        out.append({"logits": logits[rows],
                    "cost": np.asarray([br.cost[i] for i in rows]),
                    "took": [br.took[i] for i in rows]})
    return out


def ref_logits(params, tokens, conf: dict, positions=None, **kw):
    """Logits [len(positions), V] of one sequence (all positions if None).
    With a tie margin in the file and positions asked for, a row is the
    fold over the admissible routings (module docstring): the reference's
    top logit less each token's smallest distance from the top."""
    if positions is None or not tie_margin(conf):
        x = ref_hidden(params, tokens, conf, **kw)
        if positions is not None:
            x = x[np.asarray(positions)]
        return _head(x, params, conf)
    rows = []
    for b in ref_branch_logits(params, tokens, conf, positions, **kw):
        below = b["logits"] - b["logits"].max(axis=-1, keepdims=True)
        rows.append(b["logits"][0].max() + below.max(axis=0))
    return np.stack(rows)


def ref_loss(params, tokens, conf: dict, row_block: int = 256, **kw) -> float:
    """Mean next-token cross-entropy over a [B, S+1] batch with full masks:
    position t of tokens[:, :-1] predicts tokens[:, t+1]; no auxiliary
    term."""
    total, count = 0.0, 0
    for row in np.asarray(tokens):
        x = ref_hidden(params, row[:-1], conf, **kw)
        labels = jnp.asarray(row[1:])
        for lo in range(0, x.shape[0], row_block):
            logp = jax.nn.log_softmax(
                _head(x[lo:lo + row_block], params, conf), axis=-1)
            picked = jnp.take_along_axis(
                logp, labels[lo:lo + row_block, None], axis=-1)
            total += float(-jnp.sum(picked))
            count += int(picked.shape[0])
    return total / count
