"""The Xing4.0 block's file (blocks/xing4.py) as the driver process uses it —
mapping, refusals, FLOPs count, all without jax — its configuration and cell
as BENCHMARK.json declares them, and the readers that come with it, on a
trace small enough to compute by hand.

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

XING = "xing4.0-29b-a4b-l5"
CELL = "xing4.0-29b-a4b-l5.docs-qa"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_xing4_file_resolves_to_its_block_and_maps_every_key():
    conf = common.load_config(XING)
    block = common.load_block(conf)
    assert block.__file__ == os.path.join(common.BENCH_DIR, "blocks", "xing4.py")
    assert block.transformer_kwargs(conf) == dict(
        vocab_size=131072, d_model=3584, n_layers=5, n_heads=32, n_kv_heads=32,
        d_head=192, d_ff=1024, rope_theta=10000.0, max_seq_len=18432,
        tie_embeddings=False, rms_norm_eps=1e-6, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_factor=64.0, rope_original_max=4096,
        rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
        rope_mscale_all_dim=1.0, first_k_dense=1, d_ff_dense=9216,
        n_experts=64, top_k=4, moe_scoring="sigmoid", moe_renormalize=True,
        moe_route_scale=2.0, n_shared_experts=1, moe_capacity_factor=None,
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_res_clamp=30.0)
    assert set(conf) <= block.KNOWN


@pytest.mark.parametrize("change,word", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"n_group": 8}, "n_group"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"departures": ["prose"]}, "num_nextn_predict_layers"),
    ({"rope_scaling": None}, "rope_scaling"),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, "rope_scaling"),
    ({"mhc_h_res_clamp_min": -10}, "mhc_h_res_clamp_min"),
    ({"first_k_dense_replace": 0}, "first_k_dense_replace"),
    ({"model_type": "deepseek_v3"}, "model_type"),
])
def test_xing4_block_refuses_by_name_what_it_has_no_path_for(change, word):
    conf = {**common.load_config(XING), **change}
    with pytest.raises(ValueError, match=word):
        common.load_block(conf).transformer_kwargs(conf)


@pytest.mark.parametrize("other,word", [
    ("llama", "first_k_dense_replace"), ("olmoe", "first_k_dense_replace")])
def test_the_other_blocks_refuse_the_xing4_file(other, word):
    conf = common.load_config(XING)
    with pytest.raises(ValueError, match=word):
        common.load_block({"block": other}).transformer_kwargs(conf)


def test_published_values_are_kept_in_the_file_and_match_the_catalog():
    """What the file changed or leaves out of the published config is in
    the file itself (`published`, `departures`); where the catalog beside
    the model-configs guide has the row (it differs between machines), every
    other key equals it."""
    conf = common.load_config(XING)
    assert conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert conf["published"] == {"num_hidden_layers": 40,
                                 "first_k_dense_replace": 2,
                                 "num_nextn_predict_layers": 1}
    assert conf["departures"]["num_nextn_predict_layers"] == 0
    assert conf["num_nextn_predict_layers"] == 1  # the published count stays
    assert len(conf["assumed"]) >= 5
    row = None
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next((r for r in map(json.loads, f)
                        if r["name"] == "Xing4.0-29B-A4B"), None)
    if row is None:
        pytest.skip("no Xing4.0-29B-A4B row beside the model-configs guide here")
    assert conf["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if conf.get(k, "-") != v]
    assert sorted(differs) == sorted(conf["reduced"])
    assert {k: row["config"][k] for k in conf["reduced"]} == {
        k: conf["published"][k] for k in conf["reduced"]}


def test_xing4_block_loads_without_jax_and_counts_flops():
    """Per layer MLA 28,409,856 (3584.768 + 768.32.192 + 3584.576 +
    512.32.256 + 32.128.3584) + hyper-connections 2 . 14336 . 24 = 688,128;
    the dense layer 3 . 3584 . 9216 = 99,090,432; an expert layer the router
    229,376 + (4 routed + 1 shared) . 11,010,048 = 55,279,616; the head
    469,762,048. The file's 1 + 4 layers: 2 x 935,460,864 = 1,870,921,728
    of matmuls + causal attention 5 . 2 . 32 . (192 + 128) . 4097 / 2 =
    209,766,400 at 4,096 = 2,080,688,128 forward, x 3."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from benchmark import common\n"
        f"conf = common.load_config('{XING}')\n"
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
        "attn": 28409856, "hc": 688128, "dense": 99090432,
        "experts": 55279616, "dense_layers": 1, "expert_layers": 4,
        "head": 469762048}
    assert float(flops) == 3.0 * 2080688128


def test_the_cell_is_declared_and_only_appended():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == XING
    assert bench["configs"][-1]["reduced"] == common.load_config(XING)["reduced"]
    assert bench["workloads"][-1] == {
        **bench["workloads"][-1], "name": CELL, "config": XING,
        "traffic": "docs-qa", "chips": 1}
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", [CELL])}
    assert {"serve_tokens_per_s", "ttft_p50_ms", "setup_s",
            "decode_device_ms.latent",
            "decode_host_ms.latent", "prefill_device_ms.latent",
            "mla_attention_ms", "mla_attention_roofline", "hc_device_ms",
            "moe_device_ms.latent", "itl_p95_ms.latent", "itl_p50_ms.latent",
            "engine_decode_step_ms.latent", "device_idle_share.latent",
            "queue_wait_ms", "engine_prefill_ms",
            "prefix_reuse_share", "ttft_p90_ms", "ttft_p95_ms",
            "loadgen_late_ms"} <= listed
    # itl_p95_ms spread past half its bound in the driver's two sets of six
    # (0.898 / 1.328 ms against 0.826: PERF.md section 6, PR 33): the tail
    # and every metric that moves it are off this cell, the tail itself and
    # the step's numbers stand per layer beside completed tokens per second
    assert not {"itl_p95_ms", "itl_p50_ms", "engine_decode_step_ms",
                "device_idle_share.serve", "moe_device_ms"} & listed
    # the kernel's byte count (2 x kv heads x head_dim) is not a latent pool's;
    # the expert weights a step READS need a count of the experts it touches
    # that the decode program does not give yet (PERF.md section 7, PR 33)
    assert not {"paged_attention_ms", "paged_attention_roofline",
                "moe_imbalance", "moe_weight_roofline",
                "moe_weight_roofline.latent"} & listed
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert moves["prefill_device_ms.latent"] == moves["prefill_device_ms"]
    reports = {m["name"] for m in bench["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert m["moves"] in reports, m["name"]
    for m in bench["per_layer"]:
        if CELL in m["workloads"] and m["workloads"] != [CELL]:
            assert m["workloads"][-1] == CELL  # appended, nothing else moved
    cell = common.load_workload(CELL)
    chat = common.load_workload("olmoe-1b-7b-l3.chat")
    assert set(cell) == set(chat)  # the chat cells' keys, its own values
    assert cell["system_prompts"] == {
        "lengths": [4096, 6144, 8192, 8192, 10240, 12288, 14336, 16384],
        "zipf_s": 1.1}
    assert cell["user_turn"] == {"dist": "lognormal", "median": 64,
                                 "sigma": 0.6, "min": 32, "max": 256}
    assert cell["max_new_tokens"] == chat["max_new_tokens"]
    conf = common.load_config(XING)
    longest = 16384 + 256 + 512
    assert longest <= conf["engine"]["max_seq_len"] == conf["run"]["max_seq_len"]
    assert conf["engine"]["num_blocks"] == 1 + 32 * 18432 // 64
    # the prefill programs a run can reach: the questions' powers of two
    # (a 256-wide program for every question read + 6 ms an admission and
    # three times the scatter: PERF.md, PR 33), one width for a cold chunk,
    # a cached-context bucket for every document — and no others
    buckets = conf["engine"]["prefill_buckets"]
    turn = cell["user_turn"]
    assert buckets[:4] == [32, 64, 128, 256] == [
        b for b in buckets if turn["min"] <= b <= turn["max"]]
    assert conf["engine"]["prefill_chunk_tokens"] in buckets
    assert len(buckets) == 8
    assert all(any(b >= n for b in buckets)
               for n in cell["system_prompts"]["lengths"])
    # the reference follows the router's near-ties; nothing is scaled to it
    assert 0 < conf["reference"]["router_tie_margin"] <= 0.05
    assert not any("1/16" in a for a in conf["assumed"])


# ------------------------------------------------------------- the readers

# One decode execution, 100-200 us, inside an `engine.decode` span 90-210 us
# that attends kv_tokens 1000 and routed moe_pairs 96 (6 slots x 4 experts x
# 4 expert layers), moe_hottest 8. Its operations: the attention sublayer's mix
# (100-110, scope hc.mix), the latent kernel (110-130), the router (130-135,
# moe.route), a grouped matmul (135-160, no scope), the shared expert
# (160-170, moe.experts/moe.shared), the FFN sublayer's mix (170-175,
# hc.mix), the head (180-200). One prefill execution, 300-350 us, inside an
# `engine.prefill` span 290-360 us, with a latent kernel of its own (310-340)
# that no decode reader may count.
LATENT_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 100000000 duration_ps: 100000000 }
    events { metadata_id: 11 offset_ps: 300000000 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 110000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 130000000 duration_ps: 5000000 }
    events { metadata_id: 4 offset_ps: 135000000 duration_ps: 25000000 }
    events { metadata_id: 5 offset_ps: 160000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 170000000 duration_ps: 5000000 }
    events { metadata_id: 7 offset_ps: 180000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 310000000 duration_ps: 30000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[32,4,3584]{2,1,0} fusion(bf16[32,4,3584]{2,1,0} %x), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/hc.mix/reduce_sum:" } } }
  event_metadata { key: 2 value { id: 2 name: "%mla_paged_attention.3 = bf16[32,1,32,512]{3,2,1,0} custom-call(s32[32,288]{1,0} %t, bf16[32,1,32,640]{3,2,1,0} %q), custom_call_target=\\"tpu_custom_call\\"" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/mla_paged_attention" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[32,64]{1,0} fusion(bf16[32,3584]{1,0} %h), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.route/ne,ex->nx/dot_general:" } } }
  event_metadata { key: 4 value { id: 4 name: "%ragged-dot-none.1 = bf16[128,1024]{1,0} custom-call(s32[1]{0} %m, bf16[128,3584]{1,0} %x, bf16[64,3584,1024]{2,1,0} %w), custom_call_target=\\"tpu_custom_call\\"" stats { metadata_id: 1 str_value: "ragged-dot-none" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = bf16[32,3584]{1,0} fusion(bf16[32,1024]{1,0} %g), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.experts/moe.shared/nf,fe->ne/dot_general:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = bf16[32,4,3584]{2,1,0} fusion(f32[32,4,3584]{2,1,0} %k), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/hc.mix/add:" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7 = f32[32,131072]{1,0} fusion(bf16[32,3584]{1,0} %x), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/be,ev->bv/dot_general:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_paged_decode(1927483290925264665)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_paged_prefill(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 120000000 stats { metadata_id: 1 int64_value: 6 } stats { metadata_id: 2 int64_value: 1000 } stats { metadata_id: 3 int64_value: 96 } stats { metadata_id: 4 int64_value: 8 } }
    events { metadata_id: 2 offset_ps: 290000000 duration_ps: 70000000 } }
  event_metadata { key: 1 value { id: 1 name: "engine.decode" } }
  event_metadata { key: 2 value { id: 2 name: "engine.prefill" } }
  stat_metadata { key: 1 value { id: 1 name: "slots" } }
  stat_metadata { key: 2 value { id: 2 name: "kv_tokens" } }
  stat_metadata { key: 3 value { id: 3 name: "moe_pairs" } }
  stat_metadata { key: 4 value { id: 4 name: "moe_hottest" } }
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
    ("mla_attention_ms", 20 / 1e3),
    # 1000 tokens x 5 layers x 576 x 2 B = 5.76 MB over 819e9 B/s = 7.033 us
    # (the FLOPs, 1000 x 5 x 2 x 32 x 1088 = 3.4816e8 over 197e12 = 1.767
    # us, do not bind), over 20 us
    ("mla_attention_roofline", 100 * (5.76e6 / 819e9) / 20e-6),
    # both mixes: 10 + 5 us
    ("hc_device_ms", 15 / 1e3),
    # router 5 + grouped matmul 25 + shared expert 10 us
    ("moe_device_ms.latent", 40 / 1e3),
    ("decode_device_ms.latent", 100 / 1e3),
    # the span's 120 us less the 95 us the device is busy inside it
    ("decode_host_ms.latent", 25 / 1e3),
    ("prefill_device_ms.latent", 50 / 1e3),
])
def test_latent_readers_by_hand(tmp_path, monkeypatch, metric, want):
    _trace(tmp_path, monkeypatch, LATENT_TRACE, CELL)
    assert common.load_reader(metric)(FACTS) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("metric,want", [
    # the gaps of the client's records: median of 20 is the mean of the
    # 10th and 11th, the 95th percentile what common.percentile gives
    ("itl_p50_ms.latent", 10.5),
    ("itl_p95_ms.latent", None),
    # serve_engine_step_s{phase=decode}: (3.0 - 1.0) s over 20 steps
    ("engine_decode_step_ms.latent", 100.0),
    ("device_idle_share.latent", 30.0),
])
def test_the_step_and_tail_twins_by_hand(metric, want):
    gaps = [float(i) for i in range(1, 21)]
    facts = {"kind": "serve", "trace": {"idle_share_pct": 30.0},
             "client": {"ttft_ms": [10.0], "itl_ms": gaps},
             "before": {"hist": {"decode_step": {"sum": 1.0, "count": 10}}},
             "after": {"hist": {"decode_step": {"sum": 3.0, "count": 30}}}}
    if want is None:  # run.py's own end-to-end statistic, on the same gaps
        want = common.percentile(gaps, 95)
        assert 19.0 <= want <= 20.0
    assert common.load_reader(metric)(facts) == pytest.approx(want)
    assert common.load_reader("itl_p95_ms.latent")(
        {**facts, "client": {"itl_ms": []}}) is None


def test_the_flops_bound_is_counted_too():
    mod = common._load_module("layer_metrics", "mla_attention_roofline")
    nbytes, flops = mod.latent_work(common.load_config(XING), 1000)
    assert (nbytes, flops) == (1000 * 5 * 1152, 1000 * 5 * 2 * 32 * (576 + 512))
    assert flops / nbytes == pytest.approx(60.4, abs=0.1)  # bytes bind on a v5e


@pytest.mark.parametrize("metric", [
    "mla_attention_ms", "mla_attention_roofline", "hc_device_ms"])
def test_latent_readers_find_nothing_in_another_cells_trace(
        tmp_path, monkeypatch, metric):
    """What the parent gives for a metric new in this PR: no kernel of that
    name, no scope, another file — None, and nothing raised."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "small_spans.xplane.txt")) as f:
        _trace(tmp_path, monkeypatch, f.read(), "mistral-7b-v0.3-l6.chat")
    assert common.load_reader(metric)(FACTS) is None
    assert common.load_reader(metric)({**FACTS, "trace": None}) is None


def test_moe_imbalance_reads_nothing_from_this_file(tmp_path, monkeypatch):
    # the file says n_routed_experts: the accepted reader asks for num_experts
    _trace(tmp_path, monkeypatch, LATENT_TRACE, CELL)
    assert common.load_reader("moe_imbalance")(FACTS) is None
    assert common.load_reader("moe_weight_roofline")(FACTS) is None
