"""The DeepSeek-V2 block (`model_type: deepseek_v2`, arXiv:2405.04434):
latent attention (MLA) with YaRN RoPE under a PLAIN residual,
`first_k_dense_replace` leading dense layers, then softmax-routed experts
chosen by `group_limited_greedy` (the experts lie in `n_group` groups of
consecutive ones, a group scores its best expert, the top-k are taken among
the `topk_group` best groups; the weights are the chosen probabilities, not
renormalised, times `routed_scaling_factor`) beside `n_shared_experts`
shared ones. The equations are in benchmark/blocks/deepseek_v2_reference.py.

A file may describe ONE CHIP'S SHARE of an expert-parallel deployment
(model-configs guide, section 4): `n_routed_experts` is then the count held
here and is listed in `reduced`, `published.n_routed_experts` is the
router's width, and `stands_for` says over how many chips a layer is
divided (`expert_parallel`) and which of them this is (`expert_rank`): the
chip holds the `n_routed_experts` experts from expert `expert_rank x
n_routed_experts` on. The router scores and chooses over the published
count; the chip computes the chosen pairs it holds and the shared experts,
in the program and in the reference alike. `vocab_size`, where reduced, is
a smaller vocabulary.

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
    "norm_topk_prob", "routed_scaling_factor", "n_group", "topk_group",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rope_theta", "rope_scaling", "rms_norm_eps",
    # checked below
    "model_type", "hidden_act", "attention_bias", "tie_word_embeddings",
    "num_key_value_heads", "scoring_func", "topk_method", "moe_layer_freq",
    # training's sequence-wise balance loss: nothing a forward pass reads
    "seq_aux",
    # read by the reference alone (router_tie_margin: deepseek_v2_reference.py)
    "reference",
}
YARN_KEYS = {"type", "factor", "original_max_position_embeddings",
             "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}


def _refuse(name, why):
    raise ValueError(f"{name}: {why}")


def expert_share(conf: dict) -> tuple:
    """(experts the router scores, experts held here, the first held one).
    A file whose `n_routed_experts` is not under `reduced` holds them all."""
    held = int(conf["n_routed_experts"])
    if "n_routed_experts" not in (conf.get("reduced") or ()):
        return held, held, 0
    name = conf.get("name")
    routed = (conf.get("published") or {}).get("n_routed_experts")
    dep = conf.get("stands_for")
    if not isinstance(routed, int) or not isinstance(dep, dict):
        _refuse(name, "n_routed_experts is reduced: the file has to give "
                      "published.n_routed_experts and, under stands_for, "
                      "expert_parallel and expert_rank")
    ways, rank = dep.get("expert_parallel"), dep.get("expert_rank")
    if routed % held or ways != routed // held:
        _refuse(name, f"n_routed_experts={held} held of {routed} published "
                      f"is not one of expert_parallel={ways!r} equal shares")
    if not isinstance(rank, int) or not 0 <= rank < ways:
        _refuse(name, f"expert_rank={rank!r} is not one of {ways} shares")
    return routed, held, rank * held


def transformer_kwargs(conf: dict) -> dict:
    """The published keys, renamed to the program's TransformerConfig
    fields. A key this block does not know is refused by name, and so is a
    value it has no path for: running without it would be another model
    under this one's name."""
    name = conf.get("name")
    unknown = sorted(set(conf) - KNOWN)
    if unknown:
        _refuse(name, f"{', '.join(unknown)}: not a key the deepseek_v2 "
                      "block maps or knows")
    if conf.get("model_type") != "deepseek_v2":
        _refuse(name, "model_type is not deepseek_v2")
    if conf.get("hidden_act") != "silu" or conf.get("attention_bias") \
            or conf.get("tie_word_embeddings"):
        _refuse(name, "not the block this harness maps")
    for key, want in (("scoring_func", "softmax"),
                      ("topk_method", "group_limited_greedy"),
                      ("moe_layer_freq", 1),
                      ("num_key_value_heads", conf["num_attention_heads"])):
        if conf.get(key) != want:
            _refuse(name, f"{key}={conf.get(key)!r}: the deepseek_v2 block "
                          f"has a path for {want!r} only")
    rs = conf.get("rope_scaling") or {}
    if rs.get("type") != "yarn" or set(rs) != YARN_KEYS:
        _refuse(name, f"rope_scaling={rs!r}: the deepseek_v2 block maps "
                      f"YaRN with exactly {sorted(YARN_KEYS)}")
    if not 0 < conf["first_k_dense_replace"] < conf["num_hidden_layers"]:
        _refuse(name, "first_k_dense_replace leaves no dense or no expert layer")
    routed, held, first = expert_share(conf)
    groups, kept = conf["n_group"], conf["topk_group"]
    if groups < 1 or routed % groups:
        _refuse(name, f"n_group={groups} does not divide the {routed} "
                      "routed experts")
    if not 0 < kept <= groups or \
            conf["num_experts_per_tok"] > kept * (routed // groups):
        _refuse(name, f"topk_group={kept} of n_group={groups} cannot give "
                      f"{conf['num_experts_per_tok']} experts a token")
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
        n_experts=held, n_routed_experts=routed, expert_offset=first,
        top_k=conf["num_experts_per_tok"], moe_n_group=groups,
        moe_topk_group=kept, moe_scoring="softmax",
        moe_renormalize=bool(conf["norm_topk_prob"]),
        moe_route_scale=float(conf["routed_scaling_factor"]),
        n_shared_experts=conf["n_shared_experts"], moe_capacity_factor=None,
    )


# ------------------------------------------------------------- arithmetic


def matmul_params(conf: dict) -> dict:
    """Parameters in the matrix multiplications ONE token goes through ON
    THIS CHIP. `attn`: MLA's five (hidden x q_lora, q_lora x heads x (nope +
    rope), hidden x (kv_lora + rope), kv_lora x heads x (nope + v), heads x
    v x hidden). `dense`: the leading layers' gated MLP. `experts`: the
    router over ALL routed experts, the shared experts, and the routed
    experts a token goes to HERE: of its k, the share held / routed in
    expectation (6 x 40 / 160 = 1.5 at the published sizes; an expectation
    under even routing, not a count of one step), 3 x hidden x
    moe_intermediate each. The embedding table is a lookup and the norm
    scales are elementwise: neither is counted."""
    e, h = conf["hidden_size"], conf["num_attention_heads"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    q, r = conf["q_lora_rank"], conf["kv_lora_rank"]
    attn = (e * q + q * h * (dn + dr) + e * (r + dr) + r * h * (dn + dv)
            + h * dv * e)
    dense = 3 * e * conf["intermediate_size"]
    routed, held, _ = expert_share(conf)
    experts = (e * routed
               + (conf["num_experts_per_tok"] * held / routed
                  + conf["n_shared_experts"])
               * 3 * e * conf["moe_intermediate_size"])
    k = conf["first_k_dense_replace"]
    return {"attn": attn, "dense": dense, "experts": experts,
            "dense_layers": k, "expert_layers": conf["num_hidden_layers"] - k,
            "head": e * conf["vocab_size"]}


def required_train_flops_per_token(conf: dict, seq_len: int) -> float:
    """FLOPs the forward and backward passes REQUIRE of this chip for one
    token of a `seq_len` sequence: 2 per multiply-add, backward = 2 x
    forward, so 3 x forward; attention counted causal ((seq_len+1)/2 keys
    on average) and materialised — (nope + rope) per head and key for QK^T,
    v_head_dim for PV. Of the routed experts only this chip's expected
    share of a token's k counts (`matmul_params`). No recomputation,
    lookup, norm, rope or softmax."""
    p = matmul_params(conf)
    layers = p["dense_layers"] + p["expert_layers"]
    matmul = 2.0 * (layers * p["attn"] + p["dense_layers"] * p["dense"]
                    + p["expert_layers"] * p["experts"] + p["head"])
    per_key = (conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"]
               + conf["v_head_dim"])
    attn = (layers * 2.0 * conf["num_attention_heads"] * per_key
            * (seq_len + 1) / 2.0)
    return 3.0 * (matmul + attn)


# -------------------------------------------------------------- reference


def _reference():
    return common._load_module("blocks", "deepseek_v2_reference")


def ref_logits(params, tokens, conf: dict, positions=None):
    """Float32 logits [len(positions), V] of one sequence (all positions
    if None), from the PROGRAM's parameter tree, over the experts the file
    says are held."""
    return _reference().ref_logits(params, tokens, conf, positions=positions)


def ref_loss(params, tokens, conf: dict) -> float:
    """Mean next-token cross-entropy over a [B, S+1] batch, in float32; no
    auxiliary router term."""
    return _reference().ref_loss(params, tokens, conf)
