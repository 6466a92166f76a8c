"""The llama-style block (RMSNorm, RoPE, GQA, gated SiLU MLP, no bias,
untied embeddings): InternLM2 and Mistral as the benchmark runs them. A
configuration file without a `block` key is this block.

The four names every block gives the harness (`common.load_block`) are
here: the mapping onto the program's TransformerConfig, the required-FLOPs
count, and the plain float32 reference, which lives in
benchmark/reference.py and is imported when it is first asked for — the
driver process loads this file for the first two and never opens JAX."""

from __future__ import annotations

from benchmark import common

KNOWN = frozenset(common.BOOKKEEPING) | {
    # published keys mapped onto a TransformerConfig field
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "rope_theta",
    # checked below, or read by the reference
    "hidden_act", "bias", "tie_word_embeddings", "sliding_window",
    "rms_norm_eps",
}


def transformer_kwargs(conf: dict) -> dict:
    """The published keys, renamed to the program's TransformerConfig
    fields. Anything else in the file is refused, not ignored: a key this
    block does not know (`num_experts`, say) belongs to another block, and
    dropping it would run this block under that model's name."""
    unknown = sorted(set(conf) - KNOWN)
    if unknown:
        raise ValueError(
            f"{conf.get('name')}: {', '.join(unknown)}: not a key the llama "
            "block maps or knows; a block that does goes in benchmark/blocks/")
    if conf.get("hidden_act") != "silu" or conf.get("bias") \
            or conf.get("tie_word_embeddings") or conf.get("sliding_window"):
        raise ValueError(f"{conf.get('name')}: not the block this harness maps")
    return dict(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], d_head=conf["head_dim"],
        d_ff=conf["intermediate_size"], rope_theta=float(conf["rope_theta"]),
        max_seq_len=conf["run"]["max_seq_len"], tie_embeddings=False,
    )


# ------------------------------------------------------------- arithmetic


def matmul_params(conf: dict) -> dict:
    """Parameters that sit in matrix multiplications, per layer and in the
    output head. The embedding table is a lookup and the norm scales are
    elementwise: neither is counted."""
    e, h, kv, d = (conf["hidden_size"], conf["num_attention_heads"],
                   conf["num_key_value_heads"], conf["head_dim"])
    attn = e * h * d + 2 * e * kv * d + h * d * e
    mlp = 3 * e * conf["intermediate_size"]
    return {"layer": attn + mlp, "head": e * conf["vocab_size"],
            "layers": conf["num_hidden_layers"]}


def required_train_flops_per_token(conf: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE for one token of a
    `seq_len` sequence: 2 per multiply-add, backward = 2 x forward, so
    3 x forward. Attention is counted causal: token i attends to i+1
    keys, (seq_len+1)/2 on average, for QK^T and for PV. Recomputation
    under remat is work the implementation chose, not required work, and is
    not counted; neither are the embedding lookup, norms, rope, softmax."""
    p = matmul_params(conf)
    matmul = 2.0 * (p["layers"] * p["layer"] + p["head"])
    attn = (p["layers"] * 2 * 2.0 * conf["num_attention_heads"]
            * conf["head_dim"] * (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


# -------------------------------------------------------------- reference


def ref_logits(params, tokens, conf: dict, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all positions
    if None), from the PROGRAM's parameter tree."""
    from benchmark import reference

    return reference.ref_logits(params, tokens, conf, positions=positions)


def ref_loss(params, tokens, conf: dict) -> float:
    """What the program's `metrics["loss"]` is for this block: the mean
    next-token cross-entropy over a [B, S+1] batch, in float32."""
    from benchmark import reference

    return reference.ref_loss(params, tokens, conf)
