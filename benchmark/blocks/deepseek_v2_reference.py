"""The plain reference of the DeepSeek-V2 block (blocks/deepseek_v2.py, which
imports this file when its ref_logits / ref_loss are first called): the
forward pass in straightforward jax.numpy, float32, matmul precision
"highest" — no kernel, no cache, no sorting, no absorbed projection, and
nothing imported from ray_tpu. A layer is pre-norm with plain residuals
(arXiv:2405.04434):

    h = x + F_att(RMSNorm(x));   y = h + F_ffn(RMSNorm(h))
    after the last layer: final norm, output head (untied)

F_att is latent attention (MLA, §2.1), MATERIALISED:

    c_q = RMSNorm(u Wq_a);  q = c_q Wq_b -> heads x (nope + rope)
    [c_kv | k_r] = u Wkv_a;  c_kv <- RMSNorm(c_kv)
    [k_nope | v] = c_kv Wkv_b -> heads x (nope + v)
    score = (q_nope . k_nope + RoPE(q_rope) . RoPE(k_r)) . s, causal softmax
    out   = concat_heads(softmax . v) Wo
    RoPE: YaRN inv_freq (theta^(-2i/d) blended with the same over `factor`,
    ramp between the correction dims of beta_fast / beta_slow over the
    original positions), cos/sin times mscale ratio; rotates the two HALVES
    s = (nope + rope)^-1/2 . (0.1 mscale_all_dim ln factor + 1)^2

F_ffn is a gated SiLU MLP in the leading `first_k_dense_replace` layers and
afterwards (`group_limited_greedy`, softmax scores)

    p = softmax(u Wr)                         over ALL routed experts, f32
    group score = max p over each of n_group groups of consecutive experts
    keep the topk_group best groups, zero p elsewhere
    chosen = the num_experts_per_tok largest of what is left;  w = p[chosen]
    w <- w / sum w if norm_topk_prob;  w <- w . routed_scaling_factor
    y = sum_{chosen e in [lo, hi)} w_e E_e(u) + E_shared(u)

every E a gated SiLU MLP, E_shared ONE of width n_shared_experts x
moe_intermediate_size. `[lo, hi)` is the RANGE OF EXPERTS WHOSE PART IS
SUMMED: all routed experts, or one chip's share of an expert-parallel
deployment (the file's, unless `experts=` says otherwise) — the router
scores and chooses over all of them either way, what the chosen experts
outside the range would add is left out, and that partial result goes on to
the next layer. `shared=False` leaves the shared experts out too (the test
that adds the shares up counts them once). Every expert of the range is
computed on every token and masked by its weight (zero where not chosen):
no capacity, nothing dropped. The parameter tree's expert stacks hold the
experts from `stack_first` on (the file's first held expert), so expert e
is row e - stack_first.

How it is laid out, and why. The reference runs beside a replica that
fills the chip, so the residual stream lives on the HOST between calls, in
chunks of `CHUNK` rows; everything but attention is per token and runs
chunk by chunk; a layer's k_nope and v (the only tensors attention needs of
other tokens) are expanded once for the whole sequence; weights are upcast
ONE matrix (one expert) at a time, inside the call that uses it, and a layer
is never sliced out of its stack outside such a call: the calls take the
whole stack and the layer's index, and an expert's matrices are picked
`[layer, expert]` inside the loop over the range. The sequence is padded to
a multiple of `PAD_TO` so that the requests of one check share compiled
shapes; padded rows come after every real token, so causality hides them.

Near-ties of the router (`reference.router_tie_margin` in the file, in
units of the router's LOGITS, 0 = off). Both cuts of the choice — the
topk_group-th against the next group, the k-th against the next expert —
are step functions of their input: where two candidates lie closer than the
arithmetic of a bfloat16 replica can tell apart, either choice is a right
answer. A tie between a held and an absent expert is a tie like any other:
it decides whether this chip adds a part at all. So at the positions whose
logits are ASKED for (`positions`), and only there, the reference follows
every choice the margin admits: a row splits at an expert layer into one
row per admissible set of experts (`route_choices`: every admissible set of
groups, and under each every admissible set of k experts), each carried
through the remaining layers on its own (against the keys and values of the
sequence's main pass: what one token's choice does to LATER tokens reaches
them through attention over hundreds of keys, and is left out), up to
`MAX_BRANCHES` rows a position, nearest ties first. `ref_branch_logits`
gives every branch's logits; `ref_logits` folds them into one row a
position: for each token, the reference's top logit less the token's
SMALLEST distance from the top over the branches — so the harness's
near-argmax rule asks that the served token be near the top under at least
one routing the margin admits. With no tie within the margin that is the
plain row, value for value.

It takes the program's parameter tree (embed, dense_layers{...},
layers{...}, final_norm, unembed; attention leaves wq_a, q_a_norm, wq_b
[q, heads, nope + rope], wkv_a [hidden, kv + rope], kv_a_norm, wkv_b
[kv, heads, nope + v], wo; router [hidden, routed], w_gate/w_up/w_down per
held expert, ws_gate/ws_up/ws_down shared) and nothing else from the
program."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import common

# what latent attention under YaRN and a gated MLP are in ANY block comes
# from the Xing4.0 reference, as the other references take theirs from
# benchmark/reference.py: RMSNorm, the YaRN tables and score scale, RoPE
# over two halves, the expansion of cached rows to per-head keys and
# values, a layer picked leaf by leaf out of its stack, the output head in
# blocks of columns, the sets a margin admits at ONE cut, rows in chunks
_X = common._load_module("blocks", "xing4_reference")
_BLOCK = common._load_module("blocks", "deepseek_v2")
F32, CHUNK, MAX_BRANCHES = _X.F32, _X.CHUNK, _X.MAX_BRANCHES
_rmsnorm, _rope, _gated, _mscale = _X._rmsnorm, _X._rope, _X._gated, _X._mscale
yarn_inv_freq, score_scale, expand = _X.yarn_inv_freq, _X.score_scale, _X.expand
_Layer, _layers, _head, tie_margin = _X._Layer, _X._layers, _X._head, _X.tie_margin
PAD_TO = 1024  # sequence lengths are padded up to a multiple of this


def _settings(conf: dict, experts=None, shared: bool = True,
              stack_first=None) -> tuple:
    """What the jitted pieces need of the file, hashable."""
    rs = conf["rope_scaling"]
    _, held, first = _BLOCK.expert_share(conf)
    lo, hi = experts if experts is not None else (first, first + held)
    return (
        ("eps", float(conf["rms_norm_eps"])),
        ("nope", int(conf["qk_nope_head_dim"])),
        ("rank", int(conf["kv_lora_rank"])),
        ("scale", float(score_scale(conf))),
        ("rope_m", _mscale(rs["factor"], rs["mscale"])
         / _mscale(rs["factor"], rs["mscale_all_dim"])),
        ("top_k", int(conf["num_experts_per_tok"])),
        ("n_group", int(conf["n_group"])),
        ("topk_group", int(conf["topk_group"])),
        ("renorm", bool(conf["norm_topk_prob"])),
        ("route_scale", float(conf["routed_scaling_factor"])),
        ("lo", int(lo)), ("hi", int(hi)), ("shared", bool(shared)),
        ("stack_first", int(first if stack_first is None else stack_first)),
    )


def _attn_rows(x, lp, pos, inv_freq, st):
    """The attention sublayer's per-token half on rows x [S, C]:
    -> (q_nope [S, H, nope], q_rope [S, H, rope] roped, c_kv [S, rank]
    normed, k_rope [S, rope] roped)."""
    u = _rmsnorm(x, lp["attn_norm"].astype(F32), st["eps"])
    c_q = _rmsnorm(u @ lp["wq_a"].astype(F32), lp["q_a_norm"].astype(F32),
                   st["eps"])
    q = jnp.einsum("sq,qhd->shd", c_q, lp["wq_b"].astype(F32))
    ckv = u @ lp["wkv_a"].astype(F32)
    c_kv = _rmsnorm(ckv[:, :st["rank"]], lp["kv_a_norm"].astype(F32), st["eps"])
    k_rope = _rope(ckv[:, st["rank"]:], pos, inv_freq, st["rope_m"])
    q_rope = _rope(q[..., st["nope"]:], pos, inv_freq, st["rope_m"])
    return q[..., :st["nope"]], q_rope, c_kv, k_rope


@functools.partial(jax.jit, static_argnames=("settings",))
def latent_rows(x, stack, li, pos, inv_freq, *, settings):
    """What a chunk of tokens leaves for later ones: (c_kv, k_rope)."""
    with jax.default_matmul_precision("highest"):
        _, _, c_kv, k_rope = _attn_rows(
            x, _Layer(stack, li), pos, inv_freq, dict(settings))
        return c_kv, k_rope


def _router_logits(h, lp):
    return h @ lp["router"].astype(F32)


def group_limited_top_k(probs, st):
    """The published choice on probs [S, R] -> idx [S, k]."""
    s, r = probs.shape
    g = st["n_group"]
    best = jnp.max(probs.reshape(s, g, r // g), axis=-1)
    _, kept = jax.lax.top_k(best, st["topk_group"])
    keep = jnp.zeros((s, g), bool).at[jnp.arange(s)[:, None], kept].set(True)
    left = jnp.where(jnp.repeat(keep, r // g, axis=1), probs, 0.0)
    return jax.lax.top_k(left, st["top_k"])[1]


def route(h, lp, st, idx=None):
    """-> (w [S, k], idx [S, k]) over ALL routed experts. `idx`, when
    given, IS the choice (a branch of a near-tie)."""
    probs = jax.nn.softmax(_router_logits(h, lp), axis=-1)
    if idx is None:
        idx = group_limited_top_k(probs, st)
    w = jnp.take_along_axis(probs, idx, axis=-1)
    if st["renorm"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * st["route_scale"], idx


def _experts(h, lp, st, idx=None):
    """The routed experts of the range [lo, hi) + the shared ones on h
    [S, C]: every expert of the range on every token, weighted (0 where not
    chosen)."""
    w, idx = route(h, lp, st, idx)
    routed = lp["router"].shape[-1]
    weight = jnp.sum(
        jax.nn.one_hot(idx, routed, dtype=F32) * w[..., None], axis=1)

    def one(acc, xs):  # one expert's matrices are picked and upcast here
        e, p = xs
        row = e - st["stack_first"]
        return acc + p[:, None] * _gated(
            h, lp.expert("w_gate", row), lp.expert("w_up", row),
            lp.expert("w_down", row)), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (jnp.arange(st["lo"], st["hi"]), weight.T[st["lo"]:st["hi"]]))
    if st["shared"]:
        out = out + _gated(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    return out


def _attend(x, lp, pos, inv_freq, k_nope, k_rope, v, st, own: bool):
    """x + the attention sublayer on rows x [S, C] at positions pos against
    the layer's keys and values of the WHOLE sequence (k_nope [K, H, nope],
    k_rope [K, rope], v [K, H, v]; key j sits at position j). `own`: the
    row's key and value AT its position are its own (a branch row, whose
    stream is not the main pass's), the sequence's only before it."""
    q_nope, q_rope, c_kv, k_r = _attn_rows(x, lp, pos, inv_freq, st)
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
    return x + jnp.einsum("shd,hde->se", attn, lp["wo"].astype(F32))


def _ffn(x, lp, st, idx=None):
    """x + the feed-forward sublayer on rows x [S, C]."""
    h2 = _rmsnorm(x, lp["mlp_norm"].astype(F32), st["eps"])
    if "router" in lp:
        return x + _experts(h2, lp, st, idx)
    return x + _gated(h2, lp["w_gate"], lp["w_up"], lp["w_down"])


@functools.partial(jax.jit, static_argnames=("settings",))
def layer_rows(x, stack, li, pos, inv_freq, k_nope, k_rope, v, *, settings):
    """One whole layer (`li` of `stack`) on the rows x [S, C] of the main
    pass."""
    st, lp = dict(settings), _Layer(stack, li)
    with jax.default_matmul_precision("highest"):
        return _ffn(_attend(x, lp, pos, inv_freq, k_nope, k_rope, v, st,
                            own=False), lp, st)


@functools.partial(jax.jit, static_argnames=("settings",))
def branch_attend(x, stack, li, pos, inv_freq, k_nope, k_rope, v, *, settings):
    """Branch rows through the attention sublayer -> (x, the router's
    logits [S, R]; None in a dense layer)."""
    st, lp = dict(settings), _Layer(stack, li)
    with jax.default_matmul_precision("highest"):
        x = _attend(x, lp, pos, inv_freq, k_nope, k_rope, v, st, own=True)
        logits = None
        if "router" in lp:
            logits = _router_logits(
                _rmsnorm(x, lp["mlp_norm"].astype(F32), st["eps"]), lp)
        return x, logits


@functools.partial(jax.jit, static_argnames=("settings",))
def branch_ffn(x, stack, li, idx, *, settings):
    st = dict(settings)
    with jax.default_matmul_precision("highest"):
        return _ffn(x, _Layer(stack, li), st, idx)


def tie_choices(scores, k: int, margin: float) -> list:
    """Every set of k candidates that scores within `margin` of the cut
    admit, [(cost, members)] nearest first (the Xing4.0 reference's, which
    takes a cut for granted); with no candidate beyond the k there is no
    cut to tie at."""
    if k >= len(scores):
        return [(0.0, list(range(len(scores))))]
    return _X.tie_choices(scores, k, margin)


def route_choices(logits, k: int, n_group: int, topk_group: int,
                  margin: float, limit: int = MAX_BRANCHES) -> list:
    """Every choice of k experts the margin admits under group-limited
    routing, on one token's router logits [R] (softmax keeps their order,
    within a token and between its groups' best): [(cost, experts)],
    nearest first, the published choice first (cost 0). The cost of a
    choice is what its groups lack of the best groups plus what its
    experts lack of the best experts among those groups."""
    logits = np.asarray(logits, np.float64)
    per = logits.size // n_group
    best = logits.reshape(n_group, per).max(axis=1)
    found: dict = {}
    for g_cost, groups in tie_choices(best, topk_group, margin)[:limit]:
        left = np.full(logits.size, -1e30)
        for g in groups:
            left[g * per:(g + 1) * per] = logits[g * per:(g + 1) * per]
        for e_cost, experts in tie_choices(left, k, margin)[:limit]:
            key = tuple(sorted(experts))
            cost = g_cost + e_cost
            if cost < found.get(key, (np.inf,))[0]:
                found[key] = (cost, experts)
    return sorted(found.values(), key=lambda t: t[0])[:limit]


class _Branches:
    """The rows that follow the router's near-ties at the asked positions:
    x [R, C] on the host, each with its position's index, what its choices
    cost so far, and the (layer, cost) of every tie it took the far side
    of."""

    def __init__(self, x, pos, margin, conf):
        self.x, self.pos = x, np.asarray(pos, np.int32)
        self.owner = list(range(len(pos)))
        self.cost = [0.0] * len(pos)
        self.took = [[] for _ in pos]
        self.margin = float(margin)
        self.top_k = int(conf["num_experts_per_tok"])
        self.groups = (int(conf["n_group"]), int(conf["topk_group"]))

    _chunks = staticmethod(_X._Branches._chunks)

    def layer(self, depth, stack, li, inv_freq, k_nope, k_rope, v, settings):
        r = len(self.owner)
        x, logits = self._chunks(
            lambda x, pos: branch_attend(x, stack, li, pos, inv_freq, k_nope,
                                         k_rope, v, settings=settings),
            self.x, self.pos)
        idx = np.zeros((r, self.top_k), np.int32)
        if logits is not None:
            rows = []  # (owner, cost, took, source row, experts)
            for i in range(r):
                for cost, experts in route_choices(
                        logits[i], self.top_k, *self.groups, self.margin):
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


def _forward(params, tokens, conf: dict, chunk=None, pad_to=None,
             follow=None, **share):
    """The main pass: hidden states [S, C] after the last layer, float32,
    of one sequence of token ids, on the host (module docstring). `follow`
    = (positions, margin): also the branch rows at those positions ->
    (hidden, _Branches with x after the last layer). `share`: `_settings`'
    `experts`, `shared`, `stack_first`."""
    chunk, pad_to = chunk or CHUNK, pad_to or PAD_TO
    tokens = np.asarray(tokens, np.int32)
    s = int(tokens.size)
    padded = -(-s // pad_to) * pad_to
    tokens = np.concatenate([tokens, np.zeros(padded - s, np.int32)])
    settings = _settings(conf, **share)
    inv_freq = jnp.asarray(yarn_inv_freq(conf))
    starts = range(0, padded, chunk)
    # rows are gathered before they are upcast: the table is never whole in f32
    xs = [np.asarray(
        params["embed"][jnp.asarray(tokens[lo:lo + chunk])].astype(F32))
        for lo in starts]
    pos = [jnp.arange(lo, min(lo + chunk, padded), dtype=jnp.int32)
           for lo in starts]
    branches = None
    if follow is not None:
        at = np.asarray(follow[0], np.int64)
        branches = _Branches(
            np.stack([xs[p // chunk][p % chunk] for p in at]), at, follow[1],
            conf)
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
    return np.concatenate(xs)[:s], branches


def ref_hidden(params, tokens, conf: dict, **kw) -> np.ndarray:
    """Hidden states [S, C] after the last layer."""
    return _forward(params, tokens, conf, **kw)[0]


def ref_branch_logits(params, tokens, conf: dict, positions, **kw) -> list:
    """For each of `positions`: {"logits" [B, V], "cost" [B], "took": per
    branch the (layer, cost) of the ties it took the far side of} over the
    B routings the file's margin admits there; branch 0 is the reference's
    own choice (cost 0)."""
    _, br = _forward(params, tokens, conf, follow=(positions, tie_margin(conf)),
                     **kw)
    logits = np.concatenate([
        np.asarray(_head(br.x[lo:lo + CHUNK], params, conf))
        for lo in range(0, br.x.shape[0], CHUNK)])
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
