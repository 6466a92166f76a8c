"""The Xing4.0 block (`model_type: xing4_0`): DeepSeek-V3's layer — latent
attention (MLA) with YaRN RoPE, `first_k_dense_replace` leading dense layers,
then sigmoid-routed experts (`noaux_tc`: the choice on score + bias, the
weights from the unbiased scores, renormalised and scaled) beside one shared
expert — with every residual add replaced by a manifold-constrained
hyper-connection over `hc_mult` streams (mHC, arXiv:2512.24880). The
equations are in benchmark/blocks/xing4_reference.py.

The four names every block gives the harness (`common.load_block`) are
here: the mapping onto the program's TransformerConfig, the required-FLOPs
count, and the plain float32 reference, imported when it is first asked
for — the driver process loads this file for the first two and never opens
JAX."""

from __future__ import annotations

from benchmark import common

KNOWN = frozenset(common.BOOKKEEPING) | {
    # published keys mapped onto a TransformerConfig field
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "intermediate_size", "moe_intermediate_size", "first_k_dense_replace",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "routed_scaling_factor", "scoring_func",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "rope_scaling", "rms_norm_eps",
    "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max",
    # checked below
    "model_type", "hidden_act", "attention_bias", "tie_word_embeddings",
    "num_key_value_heads", "topk_method", "n_group", "topk_group",
    "moe_layer_freq", "ep_size", "num_nextn_predict_layers",
    # read by the reference alone (router_tie_margin: xing4_reference.py)
    "reference",
}
YARN_KEYS = {"type", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}


def _refuse(name, why):
    raise ValueError(f"{name}: {why}")


def transformer_kwargs(conf: dict) -> dict:
    """The published keys, renamed to the program's TransformerConfig
    fields. A key this block does not know is refused by name, and so is a
    value it has no path for: running without it would be another model
    under this one's name."""
    name = conf.get("name")
    unknown = sorted(set(conf) - KNOWN)
    if unknown:
        _refuse(name, f"{', '.join(unknown)}: not a key the xing4 block "
                      "maps or knows")
    if conf.get("model_type") != "xing4_0":
        _refuse(name, "model_type is not xing4_0")
    if conf.get("hidden_act") != "silu" or conf.get("attention_bias") \
            or conf.get("tie_word_embeddings"):
        _refuse(name, "not the block this harness maps")
    for key, want in (("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                      ("n_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
                      ("ep_size", 1),
                      ("num_key_value_heads", conf["num_attention_heads"])):
        if conf.get(key) != want:
            _refuse(name, f"{key}={conf.get(key)!r}: the xing4 block has a "
                          f"path for {want!r} only")
    dep = conf.get("departures")
    if conf.get("num_nextn_predict_layers") and not (
            isinstance(dep, dict) and dep.get("num_nextn_predict_layers") == 0):
        _refuse(name, "num_nextn_predict_layers: the xing4 block builds no "
                      "next-token-prediction module; a file that keeps the "
                      "published count says `num_nextn_predict_layers: 0` "
                      "under `departures`")
    rs = conf.get("rope_scaling") or {}
    if rs.get("type") != "yarn" or set(rs) != YARN_KEYS:
        _refuse(name, f"rope_scaling={rs!r}: the xing4 block maps YaRN "
                      f"with exactly {sorted(YARN_KEYS)}")
    if conf["mhc_h_res_clamp_min"] != -conf["mhc_h_res_clamp_max"]:
        _refuse(name, "mhc_h_res_clamp_min is not -mhc_h_res_clamp_max")
    if not 0 < conf["first_k_dense_replace"] < conf["num_hidden_layers"]:
        _refuse(name, "first_k_dense_replace leaves no dense or no expert layer")
    return dict(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_layers=conf["num_hidden_layers"], n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_head=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        d_ff=conf["moe_intermediate_size"], rope_theta=float(conf["rope_theta"]),
        max_seq_len=conf["run"]["max_seq_len"], tie_embeddings=False,
        rms_norm_eps=float(conf["rms_norm_eps"]),
        q_lora_rank=conf["q_lora_rank"], kv_lora_rank=conf["kv_lora_rank"],
        qk_nope_head_dim=conf["qk_nope_head_dim"],
        qk_rope_head_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"],
        rope_factor=float(rs["factor"]),
        rope_original_max=int(rs["original_max_position_embeddings"]),
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        first_k_dense=conf["first_k_dense_replace"],
        d_ff_dense=conf["intermediate_size"],
        n_experts=conf["n_routed_experts"], top_k=conf["num_experts_per_tok"],
        moe_scoring="sigmoid", moe_renormalize=bool(conf["norm_topk_prob"]),
        moe_route_scale=float(conf["routed_scaling_factor"]),
        n_shared_experts=conf["n_shared_experts"], moe_capacity_factor=None,
        hc_mult=conf["hc_mult"], hc_sinkhorn_iters=conf["hc_sinkhorn_iters"],
        hc_eps=float(conf["hc_eps"]),
        hc_res_clamp=float(conf["mhc_h_res_clamp_max"]),
    )


# ------------------------------------------------------------- arithmetic


def matmul_params(conf: dict) -> dict:
    """Parameters in the matrix multiplications ONE token goes through.
    `attn`: MLA's five (hidden x q_lora, q_lora x heads x (nope + rope),
    hidden x (kv_lora + rope), kv_lora x heads x (nope + v), heads x v x
    hidden). `hc`: the two hyper-connection projections of a layer,
    (hc_mult x hidden) x (2 hc_mult + hc_mult^2) each. `dense`: the leading
    layers' gated MLP. `experts`: the router, the k routed experts a token
    goes to and the shared one, 3 x hidden x moe_intermediate each — not
    the experts the layer holds. The embedding table is a lookup and the
    norm scales, the Sinkhorn rounds and the stream mixes are elementwise:
    none is counted."""
    e, h = conf["hidden_size"], conf["num_attention_heads"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    q, r, n = conf["q_lora_rank"], conf["kv_lora_rank"], conf["hc_mult"]
    attn = (e * q + q * h * (dn + dr) + e * (r + dr) + r * h * (dn + dv)
            + h * dv * e)
    hc = 2 * (n * e) * (2 * n + n * n)
    dense = 3 * e * conf["intermediate_size"]
    experts = (e * conf["n_routed_experts"]
               + (conf["num_experts_per_tok"] + conf["n_shared_experts"])
               * 3 * e * conf["moe_intermediate_size"])
    k = conf["first_k_dense_replace"]
    return {"attn": attn, "hc": hc, "dense": dense, "experts": experts,
            "dense_layers": k, "expert_layers": conf["num_hidden_layers"] - k,
            "head": e * conf["vocab_size"]}


def required_train_flops_per_token(conf: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE for one token of a
    `seq_len` sequence: 2 per multiply-add, backward = 2 x forward, so
    3 x forward; attention counted causal ((seq_len+1)/2 keys on average)
    and materialised — (nope + rope) per head and key for QK^T, v_head_dim
    for PV. Only the routed experts a token goes to and the shared one
    count. No recomputation, lookup, norm, rope, softmax or Sinkhorn."""
    p = matmul_params(conf)
    layers = p["dense_layers"] + p["expert_layers"]
    matmul = 2.0 * (layers * (p["attn"] + p["hc"])
                    + p["dense_layers"] * p["dense"]
                    + p["expert_layers"] * p["experts"] + p["head"])
    per_key = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
               + conf["v_head_dim"])
    attn = (layers * 2.0 * conf["num_attention_heads"] * per_key
            * (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


# -------------------------------------------------------------- reference


def _reference():
    return common._load_module("blocks", "xing4_reference")


def ref_logits(params, tokens, conf: dict, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all positions
    if None), from the PROGRAM's parameter tree."""
    return _reference().ref_logits(params, tokens, conf, positions=positions)


def ref_loss(params, tokens, conf: dict) -> float:
    """Mean next-token cross-entropy over a [B, S+1] batch, in float32; no
    auxiliary router term and no next-token-prediction module (the
    configuration leaves it out)."""
    return _reference().ref_loss(params, tokens, conf)
