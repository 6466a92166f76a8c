"""The DeepSeek-V2 block's file (blocks/deepseek_v2.py) as the driver process
uses it — mapping, the chip's share, refusals, FLOPs count, all without jax
— its configuration and cell as BENCHMARK.json declares them, and the
readers that come with it, on a trace small enough to compute by hand.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, span_reduce  # noqa: E402

DSV2 = "deepseek-v2-l5-ep4"
CELL = "deepseek-v2-l5-ep4.long-gen-saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = [
    "decode_device_ms.ep4", "decode_host_ms.ep4", "engine_decode_step_ms.ep4",
    "prefill_device_ms.ep4", "device_idle_share.ep4", "itl_p50_ms.ep4",
    "itl_p95_ms.ep4", "ttft_p90_ms.ep4", "moe_device_ms.ep4",
    "moe_held_share", "moe_weight_roofline.ep4", "mla_attention_ms.ep4",
    "mla_attention_roofline.ep4"]


def _bench():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_deepseek_v2_file_resolves_to_its_block_and_maps_every_key():
    conf = common.load_config(DSV2)
    block = common.load_block(conf)
    assert block.__file__ == os.path.join(
        common.BENCH_DIR, "blocks", "deepseek_v2.py")
    assert block.transformer_kwargs(conf) == dict(
        vocab_size=25600, d_model=5120, n_layers=5, n_heads=128,
        n_kv_heads=128, d_head=192, d_ff=1536, rope_theta=10000.0,
        max_seq_len=5120, tie_embeddings=False, rms_norm_eps=1e-6,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_factor=40.0,
        rope_original_max=4096, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_mscale=0.707, rope_mscale_all_dim=0.707, first_k_dense=1,
        d_ff_dense=12288, n_experts=40, n_routed_experts=160, expert_offset=0,
        top_k=6, moe_n_group=8, moe_topk_group=3, moe_scoring="softmax",
        moe_renormalize=False, moe_route_scale=16.0, n_shared_experts=2,
        moe_capacity_factor=None)
    assert set(conf) <= block.KNOWN
    assert block.expert_share(conf) == (160, 40, 0)
    # another chip of the four: the same file under another rank
    other = {**conf, "stands_for": {**conf["stands_for"], "expert_rank": 3}}
    assert block.expert_share(other) == (160, 40, 120)
    # a file that holds every expert says so by NOT reducing the count
    whole = {**conf, "n_routed_experts": 160, "reduced": ["num_hidden_layers"]}
    assert block.expert_share(whole) == (160, 160, 0)


@pytest.mark.parametrize("change,word", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"ep_size": 4}, "ep_size"),
    ({"scoring_func": "sigmoid"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"topk_method": "noaux_tc"}, "topk_method"),
    ({"n_group": 7}, "n_group"),
    ({"topk_group": 9}, "topk_group"),
    ({"n_routed_experts": 48}, "n_routed_experts"),
    ({"stands_for": "prose"}, "n_routed_experts"),
    ({"stands_for": {"expert_parallel": 8, "expert_rank": 0}},
     "expert_parallel"),
    ({"stands_for": {"expert_parallel": 4, "expert_rank": 4}}, "expert_rank"),
    ({"published": {"num_hidden_layers": 60}}, "n_routed_experts"),
    ({"rope_scaling": None}, "rope_scaling"),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, "rope_scaling"),
    ({"first_k_dense_replace": 0}, "first_k_dense_replace"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"num_key_value_heads": 16}, "num_key_value_heads"),
    ({"model_type": "deepseek_v3"}, "model_type"),
])
def test_deepseek_v2_block_refuses_by_name_what_it_has_no_path_for(change,
                                                                   word):
    conf = {**common.load_config(DSV2), **change}
    with pytest.raises(ValueError, match=word):
        common.load_block(conf).transformer_kwargs(conf)


@pytest.mark.parametrize("other,word", [
    ("llama", "first_k_dense_replace"), ("olmoe", "first_k_dense_replace"),
    ("xing4", "n_shared_experts|seq_aux|not a key")])
def test_the_other_blocks_refuse_the_deepseek_v2_file(other, word):
    conf = common.load_config(DSV2)
    with pytest.raises(ValueError, match=word):
        common.load_block({"block": other}).transformer_kwargs(conf)


def test_file_equals_the_catalog_row_but_for_reduced():
    """What the file changed of the published config is in the file itself
    (`reduced`, `published`, `stands_for`); where the catalog beside the
    model-configs guide has the row (it differs between machines), every
    other key equals it."""
    conf = common.load_config(DSV2)
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert conf["published"] == {"num_hidden_layers": 60,
                                 "n_routed_experts": 160, "vocab_size": 102400}
    assert {k: conf[k] for k in conf["reduced"]} == {
        "num_hidden_layers": 5, "n_routed_experts": 40, "vocab_size": 25600}
    dep = conf["stands_for"]
    assert (dep["expert_parallel"], dep["expert_rank"]) == (4, 0)
    assert "experts 0-39" in dep["why"] and "10.33 GB" in dep["why"]
    assert len(conf["assumed"]) >= 5
    # the widths, the router and the heads as published (a width is never cut)
    assert (conf["hidden_size"], conf["intermediate_size"],
            conf["moe_intermediate_size"], conf["q_lora_rank"],
            conf["kv_lora_rank"], conf["num_attention_heads"]) == (
        5120, 12288, 1536, 1536, 512, 128)
    assert (conf["n_group"], conf["topk_group"], conf["num_experts_per_tok"],
            conf["routed_scaling_factor"], conf["n_shared_experts"]) == (
        8, 3, 6, 16, 2)
    row = None
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next((r for r in map(json.loads, f)
                        if r["name"] == "DeepSeek-V2"), None)
    if row is None:
        pytest.skip("no DeepSeek-V2 row beside the model-configs guide here")
    assert conf["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if conf.get(k, "-") != v]
    assert sorted(differs) == sorted(conf["reduced"])
    assert {k: row["config"][k] for k in conf["reduced"]} == {
        k: conf["published"][k] for k in conf["reduced"]}


def test_deepseek_v2_block_loads_without_jax_and_counts_flops():
    """Per layer MLA 5120.1536 + 1536.128.192 + 5120.576 + 512.128.256 +
    128.128.5120 = 149,225,472; the dense layer 3 . 5120 . 12288 =
    188,743,680; an expert layer the router 5120 . 160 = 819,200 + (6 . 40 /
    160 = 1.5 routed HERE in expectation + 2 shared) . 23,592,960 =
    83,394,560; the head 5120 . 25,600 = 131,072,000. The file's 1 + 4
    layers: 2 x 1,399,521,280 of matmuls + causal attention 5 . 2 . 128 .
    (192 + 128) . 4097 / 2 = 839,065,600 at 4,096 = 3,638,108,160 forward,
    x 3."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from benchmark import common\n"
        f"conf = common.load_config('{DSV2}')\n"
        "block = common.load_block(conf)\n"
        "block.transformer_kwargs(conf)\n"
        "print(block.matmul_params(conf))\n"
        "print(block.required_train_flops_per_token(conf, 4096))\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
    )
    out = subprocess.run([sys.executable, "-c", code, common.ROOT],
                         capture_output=True, text=True, check=True).stdout
    parts, flops = out.strip().splitlines()
    assert eval(parts) == {
        "attn": 149225472, "dense": 188743680, "experts": 83394560.0,
        "dense_layers": 1, "expert_layers": 4, "head": 131072000}
    assert float(flops) == 3.0 * 3638108160


def test_the_cell_is_declared_and_only_appended_pr42():
    bench = _bench()
    assert bench["configs"][-1]["name"] == DSV2
    assert bench["configs"][-1]["reduced"] == common.load_config(DSV2)["reduced"]
    assert bench["configs"][-1]["file"] == f"benchmark/configs/{DSV2}.json"
    assert bench["workloads"][-1] == {
        **bench["workloads"][-1], "name": CELL, "config": DSV2,
        "traffic": "long-gen-saturated", "chips": 1}
    assert len(bench["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert all(len(e["why"]) <= 200
               for e in bench["workloads"] + bench["configs"])
    # judged end to end on completed tokens per second and set-up alone
    reports = {m["name"] for m in bench["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert reports == {"serve_tokens_per_s", "setup_s"}
    serve = next(m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == CELL and serve["bound"] == 0.01
    # every reader of this PR is a new file that lists this cell alone
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW_METRICS):] == NEW_METRICS
    assert len(names) == 59 + len(NEW_METRICS)
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["name"] in NEW_METRICS and m["workloads"] == [CELL]
            assert m["moves"] == "serve_tokens_per_s"
            assert os.path.exists(os.path.join(
                common.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
            if "roofline" in m["name"] or m["name"].endswith("share"):
                assert m["unit"] == "%"
    # a layer's name is one the benchmark has already
    layers = {m["layer"] for m in bench["per_layer"][:59]}
    assert {m["layer"] for m in bench["per_layer"][59:]} <= layers


def test_the_cells_traffic_and_the_engine_that_serves_it():
    cell = common.load_workload(CELL)
    chat = common.load_workload("olmoe-1b-7b-l3.chat")
    assert set(cell) == set(chat)  # the chat cells' keys, its own values
    assert cell["system_prompts"] == chat["system_prompts"] == {
        "lengths": [256, 384, 512, 512, 640, 768, 896, 1024], "zipf_s": 1.1}
    assert cell["user_turn"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.8, "min": 32, "max": 2048}
    assert cell["max_new_tokens"] == {"dist": "lognormal", "median": 512,
                                      "sigma": 0.7, "min": 128, "max": 2048}
    assert (cell["arrivals"], cell["schedule_seed"], cell["drain_s"],
            cell["trace_at_fraction"], cell["trace_seconds"]) == (
        "poisson", 23, 120, 0.6, 3.0)
    assert (cell["reference_prompts"], cell["reference_new_tokens"],
            cell["logit_tolerance"]) == (3, 8, 0.0625)
    conf = common.load_config(DSV2)
    eng = conf["engine"]
    longest = 1024 + 2048 + 2048
    assert longest == eng["max_seq_len"] == conf["run"]["max_seq_len"]
    assert eng["max_batch_size"] == 64
    assert eng["num_blocks"] == 1 + 64 * longest // 64
    turn = cell["user_turn"]
    assert eng["prefill_buckets"] == [32, 64, 128, 256, 512, 1024, 2048] == [
        b for b in eng["prefill_buckets"] if turn["min"] <= b <= turn["max"]]
    assert eng["prefill_chunk_tokens"] in eng["prefill_buckets"]
    # in the router's logits: between the widest tie a sound replica broke
    # the other way (0.05) and where a replica without groups still misses
    assert 0.05 < conf["reference"]["router_tie_margin"] < 0.2
    # the rate is 1.25 x the knee the cell's `why` names
    why = next(w["why"] for w in _bench()["workloads"] if w["name"] == CELL)
    assert f"{cell['rate_per_s']:g} req/s = 1.25 x the knee" in why


# ---------------------------------- what the accepted benchmark still holds
#
# Three of the benchmark's own tests pin a LAST place that this PR's
# appended entries take (the tier-1 re-export leaves them out for those
# lines alone, tests/test_benchmark_yardstick.py; the files are the
# benchmark's own). What they hold besides is held here.

ACCEPTED_CONFIGS = [
    "internlm2-1.8b-l12", "internlm2-1.8b", "mistral-7b-v0.3-l6",
    "olmoe-1b-7b-l3", "xing4.0-29b-a4b-l5", "olmo-hybrid-7b-l8"]
ACCEPTED_CELLS = [
    "internlm2-1.8b-l12.pretrain-4k", "mistral-7b-v0.3-l6.chat",
    "internlm2-1.8b.pretrain-4k-fsdp4", "mistral-7b-v0.3-l6.chat-saturated",
    "olmoe-1b-7b-l3.chat", "xing4.0-29b-a4b-l5.docs-qa",
    "olmo-hybrid-7b-l8.sessions"]
ACCEPTED_LAYER_METRICS = [
    "step_ms", "input_wait_ms", "collective_share",
    "device_idle_share.train", "queue_wait_ms", "engine_decode_step_ms",
    "engine_prefill_ms", "prefix_reuse_share", "device_idle_share.serve",
    "loadgen_late_ms", "ttft_p90_ms", "ttft_p95_ms", "itl_p50_ms",
    "decode_device_ms", "decode_host_ms", "admit_stall_ms",
    "prefill_device_ms", "paged_attention_ms", "paged_attention_roofline",
    "flash_attention_ms", "flash_attention_roofline",
    "engine_decode_step_ms.saturated", "decode_device_ms.saturated",
    "device_idle_share.saturated", "ttft_p90_ms.saturated",
    "ttft_p95_ms.saturated", "itl_p50_ms.saturated", "moe_device_ms",
    "moe_weight_roofline", "moe_imbalance", "decode_device_ms.latent",
    "decode_host_ms.latent", "prefill_device_ms.latent",
    "mla_attention_ms", "mla_attention_roofline", "hc_device_ms",
    "itl_p95_ms.latent", "itl_p50_ms.latent",
    "engine_decode_step_ms.latent", "device_idle_share.latent",
    "moe_device_ms.latent",
    "gdn_step_ms", "gdn_step_roofline", "gdn_scan_ms", "gdn_scan_roofline",
    "state_restore_ms", "paged_attention_ms.hybrid",
    "paged_attention_roofline.hybrid", "decode_device_ms.hybrid",
    "decode_host_ms.hybrid", "prefill_device_ms.hybrid",
    "device_idle_share.hybrid", "engine_decode_step_ms.hybrid",
    "itl_p95_ms.hybrid",
    "replica_ttft_ms", "ttft_hop_ms", "ttft_ingress_ms",
    "replica_presubmit_ms", "first_pull_wait_ms"]


def test_benchmark_json_is_the_parents_plus_appended_entries_pr42():
    """test_request_clock_readers.py::
    test_benchmark_json_is_the_parents_plus_the_five_of_pr39 with this PR's
    configuration, cell and thirteen readers BEHIND what it lists: the
    accepted names in their order, every accepted list as it was but
    `serve_tokens_per_s`'s, which gains this cell at its end."""
    bench = _bench()
    xing, hybrid = ACCEPTED_CELLS[5], ACCEPTED_CELLS[6]
    assert [c["name"] for c in bench["configs"]] == ACCEPTED_CONFIGS + [DSV2]
    assert [w["name"] for w in bench["workloads"]] == ACCEPTED_CELLS + [CELL]
    assert (bench["run_seconds"], bench["command"], bench["paths"]) == (
        40, ["python3", "benchmark/run.py"], ["benchmark"])
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("train_tokens_per_s_per_chip", 0.01), ("serve_tokens_per_s", 0.01),
        ("ttft_p50_ms", 0.08), ("itl_p95_ms", 0.02), ("setup_s", 0.1)]
    assert bench["configs"][5]["reduced"] == common.load_config(
        ACCEPTED_CONFIGS[5])["reduced"]
    assert bench["workloads"][6] == {
        **bench["workloads"][6], "config": ACCEPTED_CONFIGS[5],
        "traffic": "sessions", "chips": 1}
    names = [m["name"] for m in bench["per_layer"]]
    assert len(ACCEPTED_LAYER_METRICS) == 59
    assert names == ACCEPTED_LAYER_METRICS + NEW_METRICS
    for m in bench["per_layer"][41:54]:
        assert m["workloads"] == [hybrid], m["name"]
    joined = [m for m in bench["end_to_end"] + bench["per_layer"][:41]
              if hybrid in m.get("workloads", [])]
    assert [m["name"] for m in joined] == [
        "serve_tokens_per_s", "ttft_p50_ms", "queue_wait_ms",
        "engine_prefill_ms", "prefix_reuse_share", "loadgen_late_ms",
        "ttft_p90_ms", "ttft_p95_ms"]
    for m in joined:  # this cell behind the last, on one list; nothing moved
        tail = [CELL] if m["name"] == "serve_tokens_per_s" else []
        assert m["workloads"][-2 - len(tail):] == [xing, hybrid] + tail
    ttft, = [m for m in bench["end_to_end"] if m["name"] == "ttft_p50_ms"]
    assert ttft["workloads"] == [ACCEPTED_CELLS[1], ACCEPTED_CELLS[4], xing,
                                 hybrid]
    sources = {"replica_ttft_ms": "program_span", "ttft_hop_ms": "host_clock",
               "ttft_ingress_ms": "program_span",
               "replica_presubmit_ms": "program_span",
               "first_pull_wait_ms": "program_span"}
    for m in bench["per_layer"][54:59]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": sources[m["name"]], "layer": "serve front",
                     "moves": "ttft_p50_ms", "workloads": ttft["workloads"]}
    # no accepted metric but the one lists the new cell
    assert [m["name"] for m in bench["end_to_end"] + bench["per_layer"][:59]
            if CELL in m.get("workloads", [])] == ["serve_tokens_per_s"]


def _with_one_line_relaxed(filename, test, old, new):
    """An accepted test of benchmark/tests/<filename> with the ONE line that
    pins a last place replaced, every other assertion as it stands."""
    import inspect

    mod = common._load_module("tests", filename)
    src = inspect.getsource(getattr(mod, test))
    assert src.count(old) == 1, (test, old)
    scope = dict(vars(mod))
    exec(src.replace(old, new), scope)
    return scope[test]


def test_the_xing4_cell_is_declared_as_pr33_left_it_pr42():
    """Behind Xing4.0's cell on a list: nothing, the hybrid cell, or the
    hybrid cell and this PR's (`serve_tokens_per_s`)."""
    _with_one_line_relaxed(
        "test_olmo_hybrid_block",
        "test_the_xing4_cell_is_declared_as_pr33_left_it",
        "in ([], [HYBRID_CELL]), m[",
        f"in ([], [HYBRID_CELL], [HYBRID_CELL, {CELL!r}]), m[")()


def test_the_hybrid_cell_keeps_its_metrics_pr42():
    """PR 39's five are per_layer[54:59]; this PR's readers lie behind
    them and list neither Xing4.0's cell nor the hybrid one."""
    _with_one_line_relaxed(
        "test_request_clock_readers",
        "test_the_hybrid_cell_keeps_its_metrics_and_gains_the_five_of_pr39",
        'for m in bench["per_layer"][54:]:',
        'for m in bench["per_layer"][54:59]:')()


# ------------------------------------------------------------- the readers

# One decode execution, 100-200 us, inside an `engine.decode` span 90-210 us
# of 64 slots that attends kv_tokens 40000, routed moe_pairs 1536 (64 x 6 x 4
# expert layers) of which moe_pairs_held 400, moe_touched 144 (of 4 x 40).
# Its operations: the latent kernel (110-130), the router (130-133,
# moe.route) with the group selection inside it (133-135, moe.route/
# moe.groups), a grouped matmul (135-160, no scope), the shared experts
# (160-170, moe.experts/moe.shared), the head (180-200). One prefill
# execution, 300-350 us, inside an `engine.prefill` span 290-360 us.
SHARE_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 100000000 duration_ps: 100000000 }
    events { metadata_id: 11 offset_ps: 300000000 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 110000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 130000000 duration_ps: 3000000 }
    events { metadata_id: 8 offset_ps: 133000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 135000000 duration_ps: 25000000 }
    events { metadata_id: 5 offset_ps: 160000000 duration_ps: 10000000 }
    events { metadata_id: 7 offset_ps: 180000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 310000000 duration_ps: 30000000 } }
  event_metadata { key: 2 value { id: 2 name: "%mla_paged_attention.3 = bf16[64,1,128,512]{3,2,1,0} custom-call(s32[64,80]{1,0} %t, bf16[64,1,128,640]{3,2,1,0} %q), custom_call_target=\\"tpu_custom_call\\"" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/mla_paged_attention" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[64,160]{1,0} fusion(bf16[64,5120]{1,0} %h), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.route/ne,ex->nx/dot_general:" } } }
  event_metadata { key: 8 value { id: 8 name: "%fusion.8 = f32[64,160]{1,0} fusion(f32[64,160]{1,0} %p), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.route/moe.groups/select_n:" } } }
  event_metadata { key: 4 value { id: 4 name: "%ragged-dot-none.1 = bf16[384,1536]{1,0} custom-call(s32[160]{0} %m, bf16[384,5120]{1,0} %x, bf16[160,5120,1536]{2,1,0} %w), custom_call_target=\\"tpu_custom_call\\"" stats { metadata_id: 1 str_value: "ragged-dot-none" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = bf16[64,5120]{1,0} fusion(bf16[64,3072]{1,0} %g), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.experts/moe.shared/nf,fe->ne/dot_general:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = f32[64,25600]{1,0} fusion(bf16[64,5120]{1,0} %x), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/be,ev->bv/dot_general:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_paged_decode(1927483290925264665)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_paged_prefill(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 120000000 stats { metadata_id: 1 int64_value: 64 } stats { metadata_id: 2 int64_value: 40000 } stats { metadata_id: 3 int64_value: 1536 } stats { metadata_id: 4 int64_value: 400 } stats { metadata_id: 5 int64_value: 144 } }
    events { metadata_id: 2 offset_ps: 290000000 duration_ps: 70000000 } }
  event_metadata { key: 1 value { id: 1 name: "engine.decode" } }
  event_metadata { key: 2 value { id: 2 name: "engine.prefill" } }
  stat_metadata { key: 1 value { id: 1 name: "slots" } }
  stat_metadata { key: 2 value { id: 2 name: "kv_tokens" } }
  stat_metadata { key: 3 value { id: 3 name: "moe_pairs" } }
  stat_metadata { key: 4 value { id: 4 name: "moe_pairs_held" } }
  stat_metadata { key: 5 value { id: 5 name: "moe_touched" } }
}
"""
FACTS = {"kind": "serve", "trace": {}, "after": {"device_kind": "TPU v5 lite"}}


def _trace(tmp_path, monkeypatch, text, cell):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(text)
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(raw)
    tr = span_reduce.Trace(ProfileData.from_serialized_xspace(raw), cell)
    monkeypatch.setattr(span_reduce, "trace_of", lambda facts: tr)
    monkeypatch.setattr(span_reduce, "newest_xplane", lambda: path)
    return tr


@pytest.mark.parametrize("metric,want", [
    # the one kernel event inside the decode execution: 20 us
    ("mla_attention_ms.ep4", 20 / 1e3),
    # 40,000 tokens x 5 layers: bytes 200,000 x 1,152 B = 230.4 MB over
    # 819e9 B/s = 281.32 us; FLOPs 200,000 x 2 x 128 x 1,088 = 5.57056e10
    # over 197e12 = 282.77 us: FLOPs bind at 128 heads. Over 20 us
    ("mla_attention_roofline.ep4", 100 * (5.57056e10 / 197e12) / 20e-6),
    # router 3 + groups 2 + grouped matmul 25 + shared experts 10 us
    ("moe_device_ms.ep4", 40 / 1e3),
    ("moe_held_share", 100 * 400 / 1536),
    # 144 touched experts x 3 x 5120 x 1536 x 2 B = 6.794772e9 B over
    # 819e9 B/s = 8.2964 ms, over the expert layer's 40 us
    ("moe_weight_roofline.ep4",
     100 * (144 * 3 * 5120 * 1536 * 2 / 819e9) / 40e-6),
    ("decode_device_ms.ep4", 100 / 1e3),
    # the span's 120 us less the 80 us the device is busy inside it
    ("decode_host_ms.ep4", 40 / 1e3),
    ("prefill_device_ms.ep4", 50 / 1e3),
])
def test_share_readers_by_hand(tmp_path, monkeypatch, metric, want):
    _trace(tmp_path, monkeypatch, SHARE_TRACE, CELL)
    assert common.load_reader(metric)(FACTS) == pytest.approx(want, rel=1e-9)


# The same trace in a session whose device clock runs 15 us early: both
# executions START BEFORE their spans (decode 100 against 105, prefill 300
# against 305), which is what the driver's traced run of seed 280819910 read
# at the real size (0.25-1.5 ms early; BENCHMARK_REFUSED.md of PR 42).
EARLY_TRACE = SHARE_TRACE.replace(
    "offset_ps: 90000000 duration_ps: 120000000",
    "offset_ps: 105000000 duration_ps: 120000000").replace(
    "offset_ps: 290000000 duration_ps: 70000000",
    "offset_ps: 305000000 duration_ps: 70000000")
JOINED = {"decode_device_ms.ep4": "decode_device_ms",
          "decode_host_ms.ep4": "decode_host_ms",
          "prefill_device_ms.ep4": "prefill_device_ms",
          "moe_device_ms.ep4": "moe_device_ms",
          "mla_attention_ms.ep4": "mla_attention_ms",
          "mla_attention_roofline.ep4": "mla_attention_roofline"}


@pytest.mark.parametrize("metric,want", [
    ("decode_device_ms.ep4", 100 / 1e3),
    # the span's 120 us less the 80 us busy from 100 (the execution's start)
    # to 225 (the span's end)
    ("decode_host_ms.ep4", 40 / 1e3),
    ("prefill_device_ms.ep4", 50 / 1e3),
    ("moe_device_ms.ep4", 40 / 1e3),
    ("mla_attention_ms.ep4", 20 / 1e3),
    ("mla_attention_roofline.ep4", 100 * (5.57056e10 / 197e12) / 20e-6),
    ("moe_weight_roofline.ep4",
     100 * (144 * 3 * 5120 * 1536 * 2 / 819e9) / 40e-6),
])
def test_share_readers_keep_an_execution_that_starts_before_its_span(
        tmp_path, monkeypatch, metric, want):
    """An execution belongs to the span it overlaps most: the readers of
    this cell read what they read on one clock, where the accepted join
    (`Span.holds`) finds no execution inside a span and says nothing."""
    assert EARLY_TRACE != SHARE_TRACE
    _trace(tmp_path, monkeypatch, EARLY_TRACE, CELL)
    assert common.load_reader(metric)(FACTS) == pytest.approx(want, rel=1e-9)
    if metric in JOINED:
        assert common.load_reader(JOINED[metric])(FACTS) is None


def test_the_overlap_join_leaves_out_what_has_no_span_of_its_own(
        tmp_path, monkeypatch):
    """A whole execution whose span the window's edge cut is not counted,
    and a neighbour's span that it touches by less than half of itself does
    not adopt it."""
    from benchmark import span_join

    tr = _trace(tmp_path, monkeypatch, SHARE_TRACE, CELL)
    joined = span_join.trace_of(FACTS)
    assert span_join.trace_of(FACTS) is joined and joined.tr is tr
    [decode] = joined.executions("jit_paged_decode", inside="engine.decode")
    assert decode.stats["span"].name == "engine.decode"
    assert joined.executions("jit_paged_decode") == tr.executions(
        "jit_paged_decode")
    # the decode execution (100-200) against the prefill's span (290-360):
    # no overlap; against a span that covers its last 40 us only: not half
    assert joined.executions("jit_paged_decode", inside="engine.prefill") == []
    late = span_reduce.Span("engine.late", 160e3, 400e3)
    tr.spans["engine.late"] = [late]
    assert joined.executions("jit_paged_decode", inside="engine.late") == []
    assert [r.name for r in joined.executions(
        "jit_paged_prefill", inside="engine.late")] == ["jit_paged_prefill"]
    assert span_join.overlap(late, decode) == 40e3


@pytest.mark.parametrize("metric,want", [
    ("itl_p50_ms.ep4", 10.5), ("itl_p95_ms.ep4", None),
    # serve_engine_step_s{phase=decode}: (3.0 - 1.0) s over 20 steps
    ("engine_decode_step_ms.ep4", 100.0), ("device_idle_share.ep4", 30.0),
    ("ttft_p90_ms.ep4", 46.0),
])
def test_the_step_and_tail_twins_by_hand_ep4(metric, want):
    gaps = [float(i) for i in range(1, 21)]
    facts = {"kind": "serve", "trace": {"idle_share_pct": 30.0},
             "client": {"ttft_ms": [10.0, 20.0, 30.0, 40.0, 50.0],
                        "itl_ms": gaps},
             "before": {"hist": {"decode_step": {"sum": 1.0, "count": 10}}},
             "after": {"hist": {"decode_step": {"sum": 3.0, "count": 30}}}}
    if want is None:  # run.py's own end-to-end statistic, on the same gaps
        want = common.percentile(gaps, 95)
    assert common.load_reader(metric)(facts) == pytest.approx(want)


def test_the_flops_bound_binds_at_128_heads():
    mod = common._load_module("layer_metrics", "mla_attention_roofline")
    nbytes, flops = mod.latent_work(common.load_config(DSV2), 1000)
    assert (nbytes, flops) == (1000 * 5 * 1152, 1000 * 5 * 2 * 128 * (576 + 512))
    # the v5e's ridge is 197e12 / 819e9 = 240.5 FLOP a byte
    assert flops / nbytes == pytest.approx(241.8, abs=0.1)
    peaks = common.peaks_for("TPU v5 lite")
    assert flops / peaks["bf16_flops_per_s"] > nbytes / peaks["hbm_bytes_per_s"]
    ep4 = common._load_module("layer_metrics", "moe_weight_roofline.ep4")
    assert ep4.expert_bytes(common.load_config(DSV2)) == 3 * 5120 * 1536 * 2


@pytest.mark.parametrize("metric", [
    "moe_held_share", "moe_weight_roofline.ep4", "mla_attention_ms.ep4",
    "mla_attention_roofline.ep4", "moe_device_ms.ep4"])
def test_share_readers_find_nothing_in_another_cells_trace(
        tmp_path, monkeypatch, metric):
    """What a program without the counts gives: no kernel of that name, no
    scope, no `moe_pairs_held` — None, and nothing raised."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "small_spans.xplane.txt")) as f:
        _trace(tmp_path, monkeypatch, f.read(), "mistral-7b-v0.3-l6.chat")
    assert common.load_reader(metric)(FACTS) is None
    assert common.load_reader(metric)({**FACTS, "trace": None}) is None


def test_the_all_experts_readers_read_nothing_from_this_file(tmp_path,
                                                             monkeypatch):
    # the file says n_routed_experts: the accepted readers ask for num_experts
    _trace(tmp_path, monkeypatch, SHARE_TRACE, CELL)
    assert common.load_reader("moe_imbalance")(FACTS) is None
    assert common.load_reader("moe_weight_roofline")(FACTS) is None
