"""The OLMoE block's file (blocks/olmoe.py) as the driver process uses it —
mapping, refusals, FLOPs count, all without jax — and the three readers
that come with it, on a trace small enough to compute by hand.

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

OLMOE = "olmoe-1b-7b-l3"
OLMOE_CHAT = "olmoe-1b-7b-l3.chat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_olmoe_file_resolves_to_its_block_and_maps_every_key():
    conf = common.load_config(OLMOE)
    block = common.load_block(conf)
    assert block.__file__ == os.path.join(common.BENCH_DIR, "blocks", "olmoe.py")
    assert block.transformer_kwargs(conf) == dict(
        vocab_size=50304, d_model=2048, n_layers=3, n_heads=16, n_kv_heads=16,
        d_head=128, d_ff=1024, rope_theta=10000.0, max_seq_len=4096,
        tie_embeddings=False, rms_norm_eps=1e-5, qk_norm=True, n_experts=64,
        top_k=8, moe_renormalize=False, moe_capacity_factor=None)


def test_olmoe_file_equals_the_catalog_but_for_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    conf = common.load_config(OLMOE)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "OLMoE-1B-7B-0125-Instruct")
    assert conf["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if conf.get(k, "-") != v]
    assert differs == conf["reduced"] == ["num_hidden_layers"]


def test_olmoe_block_loads_without_jax_and_counts_flops():
    """Per layer 4·E·H·D + E·X router + k·3·E·F (only the routed experts),
    the head E·V, attention causal — by hand for the published 16 layers at
    4096: 2 x (16 x 67,239,936 + 103,022,592) + 16 x 16,781,312 =
    2,626,224,128 forward, x 3."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from benchmark import common\n"
        "conf = common.load_config('olmoe-1b-7b-l3')\n"
        "block = common.load_block(conf)\n"
        "block.transformer_kwargs(conf)\n"
        "print(block.required_train_flops_per_token(conf, 4096))\n"
        "conf['num_hidden_layers'] = 16\n"
        "print(block.required_train_flops_per_token(conf, 4096))\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n")
    out = subprocess.run([sys.executable, "-c", code, common.ROOT],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    cut, whole = map(float, out.stdout.split())
    layer = 4 * 2048 * 16 * 128 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    attn = 2 * 2 * 16 * 128 * (4096 + 1) / 2
    assert whole == 3 * (2 * (16 * layer + 2048 * 50304) + 16 * attn) \
        == 7_878_672_384
    assert cut == 3 * (2 * (3 * layer + 2048 * 50304) + 3 * attn)


@pytest.mark.parametrize("key,value", [
    ("clip_qkv", 8.0), ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("sliding_window", 4096), ("shared_expert_intermediate_size", 1024),
    ("model_type", "qwen2_moe"),
])
def test_olmoe_block_refuses_by_name_what_it_does_not_know(key, value):
    conf = common.load_config(OLMOE)
    with pytest.raises(ValueError, match=key):
        common.load_block(conf).transformer_kwargs({**conf, key: value})


def test_llama_block_still_refuses_the_olmoe_file():
    conf = common.load_config(OLMOE)
    llama = common.load_block({"block": "llama"})
    with pytest.raises(ValueError, match="num_experts"):
        llama.transformer_kwargs(conf)


def test_cells_of_this_pr_are_declared_and_only_appended():
    """PR 24's eight metrics keep their cells first (the exact lists that
    test_span_reduce.py pins can no longer hold once a cell is appended);
    each new metric has its reader's file; the saturated cell copies the
    chat cell's traffic but for its rate."""
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name in ("decode_device_ms", "decode_host_ms", "admit_stall_ms",
                 "prefill_device_ms", "paged_attention_ms",
                 "paged_attention_roofline"):
        assert per_layer[name]["workloads"] == [
            "mistral-7b-v0.3-l6.chat", OLMOE_CHAT]
    reports = {c["name"]: set() for c in bench["workloads"]}
    for m in bench["end_to_end"]:
        for cell in m.get("workloads", reports):
            reports[cell].add(m["name"])
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "layer_metrics", f"{m['name']}.py")), m["name"]
        for cell in m["workloads"]:
            assert m["moves"] in reports[cell], (m["name"], cell)
    sat = "mistral-7b-v0.3-l6.chat-saturated"
    assert reports[sat] == {"serve_tokens_per_s", "setup_s"}
    a = common.load_workload("mistral-7b-v0.3-l6.chat")
    b = common.load_workload(sat)
    assert {k for k in a if a[k] != b[k]} == {"rate_per_s", "name"}
    assert b["rate_per_s"] == 9.4
    c = common.load_workload(OLMOE_CHAT)
    assert {k for k in a if a[k] != c[k]} <= {"rate_per_s", "name", "config"}


# ------------------------------------------------------------- the readers

# One decode execution, 100-200 us, inside an `engine.decode` span 90-210 us
# that carries moe_pairs 96 and moe_hottest 6; a second span (no execution
# inside: cut) with 48 and 6. Operations of the execution: a fusion outside
# the expert layer (100-130), the router's fusion (130-140, scope
# moe.route), a grouped matmul the compiler named itself (140-170, no
# scope), the combine (170-180, scope moe.experts), the head (180-200).
# A grouped matmul at 300-310 lies in no decode execution.
MOE_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 100000000 duration_ps: 100000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 130000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 140000000 duration_ps: 30000000 }
    events { metadata_id: 4 offset_ps: 170000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 180000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 300000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[32,2048]{1,0} fusion(bf16[32,2048]{1,0} %p), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/bshd,hde->bse/dot_general:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[32,64]{1,0} fusion(bf16[32,2048]{1,0} %h), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.route/ne,ex->nx/dot_general:" } } }
  event_metadata { key: 3 value { id: 3 name: "%ragged-dot-none.1 = bf16[256,1024]{1,0} custom-call(s32[1]{0} %m, bf16[256,2048]{1,0} %x, bf16[64,2048,1024]{2,1,0} %w), custom_call_target=\\"tpu_custom_call\\"" stats { metadata_id: 1 str_value: "ragged-dot-none" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4 = bf16[32,2048]{1,0} fusion(bf16[256,2048]{1,0} %y), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/moe.experts/reduce_sum:" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = f32[32,50304]{1,0} fusion(bf16[32,2048]{1,0} %x), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/be,ev->bv/dot_general:" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_paged_decode(1927483290925264665)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 90000000 duration_ps: 120000000 stats { metadata_id: 1 int64_value: 4 } stats { metadata_id: 2 int64_value: 96 } stats { metadata_id: 3 int64_value: 6 } }
    events { metadata_id: 1 offset_ps: 290000000 duration_ps: 30000000 stats { metadata_id: 1 int64_value: 2 } stats { metadata_id: 2 int64_value: 48 } stats { metadata_id: 3 int64_value: 6 } } }
  event_metadata { key: 1 value { id: 1 name: "engine.decode" } }
  stat_metadata { key: 1 value { id: 1 name: "slots" } }
  stat_metadata { key: 2 value { id: 2 name: "moe_pairs" } }
  stat_metadata { key: 3 value { id: 3 name: "moe_hottest" } }
}
"""
FACTS = {"kind": "serve", "trace": {}, "after": {"device_kind": "TPU v5 lite"}}


@pytest.fixture
def moe_trace(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(MOE_TRACE)
    path = str(tmp_path / "moe.xplane.pb")
    with open(path, "wb") as f:
        f.write(raw)
    tr = span_reduce.Trace(ProfileData.from_serialized_xspace(raw), OLMOE_CHAT)
    monkeypatch.setattr(span_reduce, "trace_of", lambda facts: tr)
    monkeypatch.setattr(span_reduce, "newest_xplane", lambda: path)
    return path


def test_op_names_come_from_the_event_metadata(moe_trace):
    reader = common._load_module("layer_metrics", "moe_device_ms")
    table = reader.op_names(moe_trace)
    assert len(table) == 5
    assert sorted(v for v in table.values() if "moe." in v) == [
        "jit(paged_decode)/while/body/closed_call/moe.experts/reduce_sum:",
        "jit(paged_decode)/while/body/closed_call/moe.route/ne,ex->nx/dot_general:",
    ]


@pytest.mark.parametrize("metric,want", [
    # router 10 + grouped matmul 30 + combine 10 us in the one execution
    ("moe_device_ms", 50 / 1e3),
    # 3 layers x 64 experts x 3 x 2048 x 1024 x 2 B = 2,415,919,104 B over
    # 819e9 B/s = 2,949.84 us, over 50 us
    ("moe_weight_roofline", 100 * (2415919104 / 819e9) / 50e-6),
    # both recorded spans: (6 + 6) x 64 / (96 + 48)
    ("moe_imbalance", 12 * 64 / 144),
])
def test_moe_readers_by_hand(moe_trace, metric, want):
    assert common.load_reader(metric)(FACTS) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("metric", [
    "moe_device_ms", "moe_weight_roofline", "moe_imbalance"])
def test_moe_readers_find_nothing_in_a_dense_trace(
        tmp_path, monkeypatch, metric):
    # the chat cell's trace: no scope, no grouped matmul, no attribute
    from jax.profiler import ProfileData

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "small_spans.xplane.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = str(tmp_path / "dense.xplane.pb")
    with open(path, "wb") as f:
        f.write(raw)
    tr = span_reduce.Trace(ProfileData.from_serialized_xspace(raw),
                           "mistral-7b-v0.3-l6.chat")
    monkeypatch.setattr(span_reduce, "trace_of", lambda facts: tr)
    monkeypatch.setattr(span_reduce, "newest_xplane", lambda: path)
    assert common.load_reader(metric)(FACTS) is None
    assert common.load_reader(metric)({**FACTS, "trace": None}) is None


@pytest.mark.parametrize("metric,source", [
    ("engine_decode_step_ms.saturated", "engine_decode_step_ms"),
    ("decode_device_ms.saturated", "decode_device_ms"),
    ("device_idle_share.saturated", "device_idle_share.serve"),
    ("ttft_p90_ms.saturated", "ttft_p90_ms"),
    ("ttft_p95_ms.saturated", "ttft_p95_ms"),
    ("itl_p50_ms.saturated", "itl_p50_ms"),
])
def test_saturated_readers_are_the_chat_cell_readers(metric, source):
    facts = {"kind": "serve", "trace": None,
             "client": {"ttft_ms": [10.0, 20.0, 40.0], "itl_ms": [1.0, 3.0]},
             "before": {"hist": {"decode_step": {"sum": 1.0, "count": 10}}},
             "after": {"hist": {"decode_step": {"sum": 3.0, "count": 30}}}}
    assert common.load_reader(metric)(facts) == common.load_reader(source)(facts)
