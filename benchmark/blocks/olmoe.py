"""The OLMoE block (`model_type: olmoe`, OlmoeDecoderLayer of transformers'
modeling_olmoe.py): pre-norm attention with RMSNorm over the WHOLE q and k
projection (QK-norm) before RoPE, and a sparse-expert MLP — softmax in
float32 over all experts, the `num_experts_per_tok` largest, their weights
NOT renormalised unless `norm_topk_prob`, every routed (token, expert) pair
computed: no capacity, no dropped token, no shared expert.

The four names every block gives the harness (`common.load_block`) are
here: the mapping onto the program's TransformerConfig, the required-FLOPs
count, and the plain float32 reference, which lives in
benchmark/blocks/olmoe_reference.py and is imported when it is first asked
for — the driver process loads this file for the first two and never opens
JAX."""

from __future__ import annotations

from benchmark import common

KNOWN = frozenset(common.BOOKKEEPING) | {
    # published keys mapped onto a TransformerConfig field
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "rope_theta",
    "rms_norm_eps", "num_experts", "num_experts_per_tok", "norm_topk_prob",
    # checked below
    "model_type", "hidden_act", "attention_bias", "tie_word_embeddings",
    "clip_qkv", "rope_scaling",
}


def transformer_kwargs(conf: dict) -> dict:
    """The published keys, renamed to the program's TransformerConfig
    fields. A key this block does not know is refused by name, and so is a
    value it has no path for (`clip_qkv`, `rope_scaling`, a bias, another
    activation, tied embeddings): running without it would be another
    model under this one's name. QK-norm has no key: it is part of the
    model class, so it is always on. Experts are dropless (no capacity)."""
    name = conf.get("name")
    unknown = sorted(set(conf) - KNOWN)
    if unknown:
        raise ValueError(
            f"{name}: {', '.join(unknown)}: not a key the olmoe block maps "
            "or knows")
    if conf.get("model_type") != "olmoe":
        raise ValueError(f"{name}: model_type is not olmoe")
    for key in ("clip_qkv", "rope_scaling"):
        if conf.get(key) is not None:
            raise ValueError(
                f"{name}: {key}={conf[key]!r}: the olmoe block has no path "
                f"for a non-null {key}")
    if conf.get("hidden_act") != "silu" or conf.get("attention_bias") \
            or conf.get("tie_word_embeddings"):
        raise ValueError(f"{name}: not the block this harness maps")
    if conf["head_dim"] * conf["num_attention_heads"] != conf["hidden_size"]:
        raise ValueError(
            f"{name}: head_dim x num_attention_heads is not hidden_size")
    return dict(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], rope_theta=float(conf["rope_theta"]),
        max_seq_len=conf["run"]["max_seq_len"], tie_embeddings=False,
        rms_norm_eps=float(conf["rms_norm_eps"]), qk_norm=True,
        n_experts=conf["num_experts"], top_k=conf["num_experts_per_tok"],
        moe_renormalize=bool(conf["norm_topk_prob"]),
        moe_capacity_factor=None,
    )


# ------------------------------------------------------------- arithmetic


def matmul_params(conf: dict) -> dict:
    """Parameters that sit in the matrix multiplications ONE token goes
    through, per layer and in the output head: attention 4·E·H·D (MHA or
    GQA), the router E·X, and the k experts a token is routed to, 3·E·F
    each — not the X experts the layer holds. The embedding table is a
    lookup and the norm scales are elementwise: neither is counted."""
    e, h, kv, d = (conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"])
    attn = e * h * d + 2 * e * kv * d + h * d * e
    router = e * conf["num_experts"]
    experts = conf["num_experts_per_tok"] * 3 * e * conf["intermediate_size"]
    return {"layer": attn + router + experts, "head": e * conf["vocab_size"],
            "layers": conf["num_hidden_layers"]}


def required_train_flops_per_token(conf: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE for one token of a
    `seq_len` sequence: 2 per multiply-add, backward = 2 x forward, so
    3 x forward; attention counted causal ((seq_len+1)/2 keys on average,
    for QK^T and for PV). Only the routed experts count. No recomputation,
    lookup, norm, rope or softmax."""
    p = matmul_params(conf)
    matmul = 2.0 * (p["layers"] * p["layer"] + p["head"])
    attn = (p["layers"] * 2 * 2.0 * conf["num_attention_heads"]
            * conf["head_dim"] * (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


# -------------------------------------------------------------- reference


def _reference():
    return common._load_module("blocks", "olmoe_reference")


def ref_logits(params, tokens, conf: dict, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all positions
    if None), from the PROGRAM's parameter tree."""
    return _reference().ref_logits(params, tokens, conf, positions=positions)


def ref_loss(params, tokens, conf: dict) -> float:
    """What the program's `metrics["loss"]` is for this block: the mean
    next-token cross-entropy over a [B, S+1] batch, in float32. The program
    adds no auxiliary (load-balancing or router-z) term to its loss, and
    neither does this."""
    return _reference().ref_loss(params, tokens, conf)
