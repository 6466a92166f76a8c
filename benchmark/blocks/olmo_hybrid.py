"""The Olmo-Hybrid block (`model_type: olmo_hybrid`): a layer PERIOD of three
gated-delta-rule (linear-attention) layers and one full-attention layer,
both kinds with their RMSNorm on each sublayer's OUTPUT (x + norm(F(x)),
the OLMo 2 / OLMo 3 placement), the full-attention layer with QK-norm over
the whole projection and WITHOUT rotary embedding (`rope_theta: null`), a
gated SiLU MLP in every layer. The equations are in
benchmark/blocks/olmo_hybrid_reference.py.

The four names every block gives the harness (`common.load_block`) are
here: the mapping onto the program's TransformerConfig, the required-FLOPs
count, and the plain float32 reference, imported when it is first asked
for — the driver process loads this file for the first two and never opens
JAX."""

from __future__ import annotations

from benchmark import common

KNOWN = frozenset(common.BOOKKEEPING) | {
    # published keys mapped onto a TransformerConfig field
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
    "layer_types", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    # checked below
    "model_type", "hidden_act", "attention_bias", "tie_word_embeddings",
    "linear_allow_neg_eigval", "rope_parameters",
}
PERIOD = ("linear_attention", "linear_attention", "linear_attention",
          "full_attention")
KINDS = {"linear_attention": "linear", "full_attention": "full"}


def _refuse(name, why):
    raise ValueError(f"{name}: {why}")


def transformer_kwargs(conf: dict) -> dict:
    """The published keys, renamed to the program's TransformerConfig
    fields. A key this block does not know is refused by name, and so is a
    value it has no path for (a `layer_types` that is not whole periods of
    linear x 3 + full, a non-null `rope_theta`, unequal key and value head
    counts, a delta rule without its negative eigenvalues, a bias, another
    activation, tied embeddings): running without it would be another
    model under this one's name. Norm placement, QK-norm, head_dim =
    hidden / heads and "no rope" have no key: they are the model class
    (the file lists them under `assumed`)."""
    name = conf.get("name")
    unknown = sorted(set(conf) - KNOWN)
    if unknown:
        _refuse(name, f"{', '.join(unknown)}: not a key the olmo_hybrid "
                      "block maps or knows")
    if conf.get("model_type") != "olmo_hybrid":
        _refuse(name, "model_type is not olmo_hybrid")
    if conf.get("hidden_act") != "silu" or conf.get("attention_bias") \
            or conf.get("tie_word_embeddings"):
        _refuse(name, "not the block this harness maps")
    types = list(conf["layer_types"])
    if len(types) != conf["num_hidden_layers"] or not types \
            or len(types) % len(PERIOD) \
            or any(tuple(types[i:i + len(PERIOD)]) != PERIOD
                   for i in range(0, len(types), len(PERIOD))):
        _refuse(name, f"layer_types={types!r}: the olmo_hybrid block maps "
                      f"num_hidden_layers whole periods of {list(PERIOD)}")
    rope = conf.get("rope_parameters")
    if not isinstance(rope, dict) or set(rope) != {"rope_theta"} \
            or rope["rope_theta"] is not None:
        _refuse(name, f"rope_parameters={rope!r}: the olmo_hybrid block has "
                      "a path for `rope_theta: null` (no rotary embedding) "
                      "only")
    for key, want in (("linear_allow_neg_eigval", True),
                      ("linear_num_value_heads", conf["linear_num_key_heads"]),
                      ("num_key_value_heads", conf["num_attention_heads"])):
        if conf.get(key) != want:
            _refuse(name, f"{key}={conf.get(key)!r}: the olmo_hybrid block "
                          f"has a path for {want!r} only")
    if conf["hidden_size"] % conf["num_attention_heads"]:
        _refuse(name, "hidden_size is not whole heads")
    return dict(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"],
        max_seq_len=conf["run"]["max_seq_len"], tie_embeddings=False,
        rms_norm_eps=float(conf["rms_norm_eps"]), qk_norm=True,
        use_rope=False, norm_placement="post",
        layer_period=tuple(KINDS[t] for t in PERIOD),
        linear_n_heads=conf["linear_num_key_heads"],
        linear_d_k=conf["linear_key_head_dim"],
        linear_d_v=conf["linear_value_head_dim"],
        linear_conv_kernel=conf["linear_conv_kernel_dim"],
    )


# ------------------------------------------------------------- arithmetic


def matmul_params(conf: dict) -> dict:
    """Parameters in the matrix multiplications ONE token goes through.
    `linear`: the projections q, k (H x dk each), v and the output gate
    (H x dv each), the two H-wide gates a and b, the output projection
    (H x dv -> hidden). `full`: 4 x hidden^2 (MHA). `mlp`: the gated MLP's
    three. The depthwise convolution (4 multiply-adds a channel), the
    norms and the gates' elementwise maps are not matmuls and are not
    counted; the embedding table is a lookup."""
    e, h = conf["hidden_size"], conf["linear_num_key_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    linear = e * h * (2 * dk + 2 * dv) + 2 * e * h + h * dv * e
    n = conf["layer_types"].count("linear_attention")
    return {"linear": linear, "full": 4 * e * e,
            "mlp": 3 * e * conf["intermediate_size"],
            "linear_layers": n, "full_layers": len(conf["layer_types"]) - n,
            "head": e * conf["vocab_size"]}


def recurrence_flops_per_token(conf: dict) -> float:
    """The delta rule's own work for one token of one linear layer: three
    passes over every head's dk x dv state (S^T k, the rank-one update,
    S^T q), 2 FLOPs an entry."""
    return 3 * 2.0 * (conf["linear_num_key_heads"]
                      * conf["linear_key_head_dim"]
                      * conf["linear_value_head_dim"])


def required_train_flops_per_token(conf: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE for one token of a
    `seq_len` sequence: 2 per multiply-add, backward = 2 x forward, so
    3 x forward. A linear layer: its matmuls and the recurrence, whatever
    the length. A full layer: its matmuls and causal attention
    ((seq_len+1)/2 keys on average, for QK^T and for PV: 2 x 2 x hidden a
    key). No recomputation, lookup, norm, convolution or softmax."""
    p = matmul_params(conf)
    layers = p["linear_layers"] + p["full_layers"]
    matmul = 2.0 * (p["linear_layers"] * p["linear"]
                    + p["full_layers"] * p["full"] + layers * p["mlp"]
                    + p["head"])
    recur = p["linear_layers"] * recurrence_flops_per_token(conf)
    attn = (p["full_layers"] * 2 * 2.0 * conf["hidden_size"]
            * (seq_len + 1) / 2.0)
    return 3.0 * (matmul + recur + attn)


# -------------------------------------------------------------- reference


def _reference():
    return common._load_module("blocks", "olmo_hybrid_reference")


def ref_logits(params, tokens, conf: dict, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all positions
    if None), from the PROGRAM's parameter tree."""
    return _reference().ref_logits(params, tokens, conf, positions=positions)


def ref_loss(params, tokens, conf: dict) -> float:
    """Mean next-token cross-entropy over a [B, S+1] batch, in float32."""
    return _reference().ref_loss(params, tokens, conf)
