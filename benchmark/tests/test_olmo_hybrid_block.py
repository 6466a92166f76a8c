"""The Olmo-Hybrid block's file (blocks/olmo_hybrid.py) as the driver process
uses it — mapping, refusals, FLOPs count, all without jax — its configuration
and cell as BENCHMARK.json declares them, and the readers that come with it,
on a trace small enough to compute by hand.

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

HYBRID = "olmo-hybrid-7b-l8"
HYBRID_CELL = "olmo-hybrid-7b-l8.sessions"
XING = "xing4.0-29b-a4b-l5"
XING_CELL = "xing4.0-29b-a4b-l5.docs-qa"
LOGIT_TOLERANCE = 0.035
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]


def test_hybrid_file_resolves_to_its_block_and_maps_every_key():
    conf = common.load_config(HYBRID)
    block = common.load_block(conf)
    assert block.__file__ == os.path.join(
        common.BENCH_DIR, "blocks", "olmo_hybrid.py")
    assert block.transformer_kwargs(conf) == dict(
        vocab_size=100352, d_model=3840, n_layers=8, n_heads=30, n_kv_heads=30,
        d_head=128, d_ff=11008, max_seq_len=35328, tie_embeddings=False,
        rms_norm_eps=1e-6, qk_norm=True, use_rope=False, norm_placement="post",
        layer_period=("linear", "linear", "linear", "full"),
        linear_n_heads=30, linear_d_k=96, linear_d_v=192, linear_conv_kernel=4)
    assert set(conf) <= block.KNOWN


@pytest.mark.parametrize("change,word", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"layer_types": PERIOD + ["full_attention"] * 4}, "layer_types"),
    ({"layer_types": PERIOD[::-1] * 2}, "layer_types"),
    ({"layer_types": PERIOD}, "layer_types"),  # not num_hidden_layers long
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_parameters"),
    ({"rope_parameters": None}, "rope_parameters"),
    ({"linear_allow_neg_eigval": False}, "linear_allow_neg_eigval"),
    ({"linear_num_value_heads": 60}, "linear_num_value_heads"),
    ({"num_key_value_heads": 6}, "num_key_value_heads"),
    ({"model_type": "olmo3"}, "model_type"),
    ({"attention_bias": True}, "not the block"),
])
def test_hybrid_block_refuses_by_name_what_it_has_no_path_for(change, word):
    conf = {**common.load_config(HYBRID), **change}
    with pytest.raises(ValueError, match=word):
        common.load_block(conf).transformer_kwargs(conf)


@pytest.mark.parametrize("other", ["llama", "olmoe", "xing4"])
def test_the_other_blocks_refuse_the_hybrid_file(other):
    conf = common.load_config(HYBRID)
    with pytest.raises(ValueError, match="layer_types"):
        common.load_block({"block": other}).transformer_kwargs(conf)


def test_hybrid_published_values_are_in_the_file_and_match_the_catalog():
    """What the file changed from the published config is in the file itself
    (`published`); where the catalog beside the model-configs guide has the
    row (it differs between machines), every other key equals it."""
    conf = common.load_config(HYBRID)
    assert conf["reduced"] == ["num_hidden_layers", "layer_types"]
    assert conf["published"] == {"num_hidden_layers": 32,
                                 "layer_types": PERIOD * 8}
    assert conf["layer_types"] == PERIOD * 2
    assert len(conf["assumed"]) >= 7 and "stands_for" in conf
    row = None
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next((r for r in map(json.loads, f)
                        if r["name"] == "Olmo-Hybrid-7B"), None)
    if row is None:
        pytest.skip("no Olmo-Hybrid-7B row beside the model-configs guide here")
    assert conf["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if conf.get(k, "-") != v]
    assert sorted(differs) == sorted(conf["reduced"])
    assert {k: row["config"][k] for k in conf["reduced"]} == conf["published"]


def test_hybrid_block_loads_without_jax_and_counts_flops():
    """A linear layer's matmuls: q, k, v, gate 3840 . 30 . (96 + 96 + 192 +
    192) = 66,355,200, the two gates 2 . 3840 . 30 = 230,400, the output
    30 . 192 . 3840 = 22,118,400: 88,704,000. A full layer 4 . 3840^2 =
    58,982,400; the MLP 3 . 3840 . 11008 = 126,812,160; the head
    385,351,680. 6 + 2 layers: 2 x 2,050,037,760 of matmuls + the recurrence
    6 . 3 . 2 . 30 . 96 . 192 = 19,906,560 + causal attention
    2 . 2 . 2 . 3840 . 4097 / 2 = 62,929,920 at 4,096 = 4,182,912,000
    forward, x 3."""
    code = (
        "import sys; sys.path.insert(0, sys.argv[1])\n"
        "from benchmark import common\n"
        f"conf = common.load_config('{HYBRID}')\n"
        "block = common.load_block(conf)\n"
        "block.transformer_kwargs(conf)\n"
        "print(block.matmul_params(conf))\n"
        "print(block.recurrence_flops_per_token(conf))\n"
        "print(block.required_train_flops_per_token(conf, 4096))\n"
        "assert 'jax' not in sys.modules and 'numpy' not in sys.modules\n"
    )
    out = subprocess.run([sys.executable, "-c", code, common.ROOT],
                         capture_output=True, text=True, check=True).stdout
    parts, recur, flops = out.strip().splitlines()
    assert eval(parts) == {
        "linear": 88704000, "full": 58982400, "mlp": 126812160,
        "linear_layers": 6, "full_layers": 2, "head": 385351680}
    assert float(recur) == 3317760.0
    assert float(flops) == 3.0 * 4182912000


def test_benchmark_json_is_the_parents_plus_appended_entries():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]] == [
        "internlm2-1.8b-l12", "internlm2-1.8b", "mistral-7b-v0.3-l6",
        "olmoe-1b-7b-l3", "xing4.0-29b-a4b-l5", HYBRID]
    assert [w["name"] for w in bench["workloads"]] == [
        "internlm2-1.8b-l12.pretrain-4k", "mistral-7b-v0.3-l6.chat",
        "internlm2-1.8b.pretrain-4k-fsdp4",
        "mistral-7b-v0.3-l6.chat-saturated", "olmoe-1b-7b-l3.chat",
        "xing4.0-29b-a4b-l5.docs-qa", HYBRID_CELL]
    assert (bench["run_seconds"], bench["command"], bench["paths"]) == (
        40, ["python3", "benchmark/run.py"], ["benchmark"])
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("train_tokens_per_s_per_chip", 0.01), ("serve_tokens_per_s", 0.01),
        ("ttft_p50_ms", 0.08), ("itl_p95_ms", 0.02), ("setup_s", 0.1)]
    assert bench["configs"][-1]["reduced"] == common.load_config(HYBRID)["reduced"]
    assert bench["workloads"][-1] == {
        **bench["workloads"][-1], "config": HYBRID, "traffic": "sessions",
        "chips": 1}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    # the 41 metrics the parent had keep their names and order; the new cell
    # joins the END of eight accepted lists (the two end-to-end metrics it is
    # judged on beside set-up, and the six unpinned readers that move
    # `ttft_p50_ms`) and of no other; this PR's 13 metrics follow them
    names = [m["name"] for m in bench["per_layer"]]
    new = ["gdn_step_ms", "gdn_step_roofline", "gdn_scan_ms",
           "gdn_scan_roofline", "state_restore_ms",
           "paged_attention_ms.hybrid", "paged_attention_roofline.hybrid",
           "decode_device_ms.hybrid", "decode_host_ms.hybrid",
           "prefill_device_ms.hybrid", "device_idle_share.hybrid",
           "engine_decode_step_ms.hybrid", "itl_p95_ms.hybrid"]
    assert len(names) == 41 + len(new) and names[41:] == new
    assert names[40] == "moe_device_ms.latent" and names[0] == "step_ms"
    for m in bench["per_layer"][41:]:
        assert m["workloads"] == [HYBRID_CELL], m["name"]
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    joined = [m for m in bench["end_to_end"] + bench["per_layer"][:41]
              if HYBRID_CELL in m.get("workloads", [])]
    assert [m["name"] for m in joined] == [
        "serve_tokens_per_s", "ttft_p50_ms", "queue_wait_ms",
        "engine_prefill_ms", "prefix_reuse_share", "loadgen_late_ms",
        "ttft_p90_ms", "ttft_p95_ms"]
    for m in joined:  # appended behind the cell that was last, nothing moved
        assert m["workloads"][-2:] == [XING_CELL, HYBRID_CELL], m["name"]


def test_the_hybrid_cell_is_declared_with_its_metrics():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind]
              if HYBRID_CELL in m.get("workloads", [HYBRID_CELL])}
    ttft_side = {"gdn_scan_ms", "gdn_scan_roofline", "state_restore_ms",
                 "prefill_device_ms.hybrid", "queue_wait_ms",
                 "engine_prefill_ms", "prefix_reuse_share", "loadgen_late_ms",
                 "ttft_p90_ms", "ttft_p95_ms"}
    step_side = {"gdn_step_ms", "gdn_step_roofline",
                 "paged_attention_ms.hybrid",
                 "paged_attention_roofline.hybrid", "decode_device_ms.hybrid",
                 "decode_host_ms.hybrid", "device_idle_share.hybrid",
                 "engine_decode_step_ms.hybrid", "itl_p95_ms.hybrid"}
    reports = {m["name"] for m in bench["end_to_end"]
               if HYBRID_CELL in m.get("workloads", [HYBRID_CELL])}
    # ISSUE 35's judges. `itl_p95_ms` stays off: over two sets of six seeds
    # it spread by 1.8 / 3.3 % against half its 2 % bound (a gap is a step or
    # a step + an admission of ~75 ms), so the tail is `itl_p95_ms.hybrid`
    # per layer, and the accepted readers that move it come as `.hybrid`
    # twins that move completed tokens per second (PERF.md section 6, PR 35)
    assert reports == {"serve_tokens_per_s", "ttft_p50_ms", "setup_s"}
    assert listed == reports | ttft_side | step_side
    # the admission's readers move the first token, the step's the rate
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert {moves[n] for n in ttft_side} == {"ttft_p50_ms"}
    assert {moves[n] for n in step_side} == {"serve_tokens_per_s"}
    assert moves["prefill_device_ms.hybrid"] == moves["prefill_device_ms"]
    # the pinned step and kernel readers (test_olmoe_block.py holds their
    # lists to two cells) and the accepted kernel reader's count (8 layers
    # of keys and values where this pool holds 2) stay off the cell
    assert not {"decode_device_ms", "decode_host_ms", "admit_stall_ms",
                "prefill_device_ms", "paged_attention_ms",
                "paged_attention_roofline", "itl_p95_ms", "itl_p50_ms",
                "engine_decode_step_ms", "device_idle_share.serve"} & listed
    cell = common.load_workload(HYBRID_CELL)
    chat = common.load_workload("olmoe-1b-7b-l3.chat")
    assert set(cell) == set(chat)  # the chat cells' keys, its own values
    assert cell["system_prompts"] == {
        "lengths": [4096, 6144, 8192, 8192, 12288, 16384, 24576, 32768],
        "zipf_s": 1.1}
    assert cell["user_turn"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.8, "min": 64, "max": 2048}
    assert cell["max_new_tokens"] == {"dist": "lognormal", "median": 128,
                                      "sigma": 0.8, "min": 16, "max": 512}
    assert (cell["arrivals"], cell["schedule_seed"], cell["drain_s"],
            cell["trace_at_fraction"], cell["trace_seconds"],
            cell["reference_prompts"], cell["reference_new_tokens"]) == (
                "poisson", 23, 40, 0.4, 3.0, 3, 8)
    # between the two readings of PERF.md section 6, PR 35: the sound runs'
    # largest and the bfloat16-state control's smallest, both on the chip
    assert cell["logit_tolerance"] == LOGIT_TOLERANCE
    conf = common.load_config(HYBRID)
    eng = conf["engine"]
    assert 32768 + 2048 + 512 == eng["max_seq_len"] == conf["run"]["max_seq_len"]
    assert eng["max_seq_len"] % 64 == 0
    # every history is whole prefill chunks: a snapshot lies at its end
    chunk = eng["prefill_chunk_tokens"]
    assert all(n % chunk == 0 for n in cell["system_prompts"]["lengths"])
    assert eng["num_blocks"] == 1 + sum(cell["system_prompts"]["lengths"]) // 64 + 800
    assert eng["n_snapshots"] >= sum(cell["system_prompts"]["lengths"]) // chunk
    # the prefill programs a run can reach: four turn widths x three
    # cached-context buckets + the two cold chunks of a history's start
    buckets = eng["prefill_buckets"]
    turn = cell["user_turn"]
    widths = [b for b in buckets if b <= turn["max"]]
    assert widths == [256, 512, 1024, 2048] and chunk in widths
    contexts = sorted({min(b for b in buckets if b >= n)
                       for n in cell["system_prompts"]["lengths"]})
    assert contexts == [8192, 16384, 35328]
    assert len(widths) * len(contexts) + 2 <= 16


def test_the_xing4_cell_is_declared_as_pr33_left_it():
    """What test_xing4_block.py::test_the_cell_is_declared_and_only_appended
    holds, less the LAST place of each list, which a later cell takes (the
    tier-1 re-export leaves that test out for those lines alone; the file
    is the benchmark's own). Xing4.0's entries are found by name."""
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {kind: {e["name"]: e for e in bench[kind]}
               for kind in ("configs", "workloads")}
    assert by_name["configs"][XING]["reduced"] == common.load_config(XING)["reduced"]
    assert by_name["workloads"][XING_CELL] == {
        **by_name["workloads"][XING_CELL], "config": XING,
        "traffic": "docs-qa", "chips": 1}
    # each in its place: the fifth configuration, the sixth cell
    assert bench["configs"][4]["name"] == XING
    assert bench["workloads"][5]["name"] == XING_CELL
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind]
              if XING_CELL in m.get("workloads", [XING_CELL])}
    assert {"serve_tokens_per_s", "ttft_p50_ms", "setup_s",
            "decode_device_ms.latent",
            "decode_host_ms.latent", "prefill_device_ms.latent",
            "mla_attention_ms", "mla_attention_roofline", "hc_device_ms",
            "moe_device_ms.latent", "itl_p95_ms.latent", "itl_p50_ms.latent",
            "engine_decode_step_ms.latent", "device_idle_share.latent",
            "queue_wait_ms", "engine_prefill_ms",
            "prefix_reuse_share", "ttft_p90_ms", "ttft_p95_ms",
            "loadgen_late_ms"} <= listed
    assert not {"itl_p95_ms", "itl_p50_ms", "engine_decode_step_ms",
                "device_idle_share.serve", "moe_device_ms"} & listed
    assert not {"paged_attention_ms", "paged_attention_roofline",
                "moe_imbalance", "moe_weight_roofline",
                "moe_weight_roofline.latent"} & listed
    # nothing of the hybrid cell's is on it
    assert not {n for n in listed if n.endswith(".hybrid")
                or n.startswith(("gdn_", "state_"))}
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert moves["prefill_device_ms.latent"] == moves["prefill_device_ms"]
    reports = {m["name"] for m in bench["end_to_end"]
               if XING_CELL in m.get("workloads", [XING_CELL])}
    for m in bench["per_layer"]:
        if XING_CELL in m["workloads"]:
            assert m["moves"] in reports, m["name"]
    # appended behind the cells before it; behind it only a later cell
    for m in bench["per_layer"] + bench["end_to_end"]:
        w = m.get("workloads", [])
        if XING_CELL in w and w != [XING_CELL]:
            assert w[w.index(XING_CELL) + 1:] in ([], [HYBRID_CELL]), m["name"]
    cell = common.load_workload(XING_CELL)
    chat = common.load_workload("olmoe-1b-7b-l3.chat")
    assert set(cell) == set(chat)
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
    buckets = conf["engine"]["prefill_buckets"]
    turn = cell["user_turn"]
    assert buckets[:4] == [32, 64, 128, 256] == [
        b for b in buckets if turn["min"] <= b <= turn["max"]]
    assert conf["engine"]["prefill_chunk_tokens"] in buckets
    assert len(buckets) == 8
    assert all(any(b >= n for b in buckets)
               for n in cell["system_prompts"]["lengths"])
    assert 0 < conf["reference"]["router_tie_margin"] <= 0.05
    assert not any("1/16" in a for a in conf["assumed"])


# ------------------------------------------------------------- the readers

# One decode execution, 1000-2000 us, inside an `engine.decode` span 900-2100
# us of 2 live slots that attend kv_tokens 3000. Its operations: a linear
# layer's conv (1000-1050, scope gdn.conv), the loop over the live rows
# (1050-1250, scope gdn.step) with one of its turns inside it (1100-1200),
# the paged kernel (1300-1500), the head (1800-2000). A snapshot decode left
# behind it (2150-2190, `engine.state_snapshot`, inside no admission). Then
# an admission (`batcher.admit` 2800-4200): the restore (2820-2880), the
# prefill span (2900-4100, tokens 512) around one prefill execution
# (3000-4000) whose operations are the conv (3000-3100, gdn.conv), the
# chunked scan (3100-3400, gdn.scan) and a paged kernel of its own
# (3500-3600) that no decode reader may count, and the snapshot behind the
# chunk (4020-4090).
HYBRID_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 1000000000 duration_ps: 1000000000 }
    events { metadata_id: 11 offset_ps: 3000000000 duration_ps: 1000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 1050000000 duration_ps: 200000000 }
    events { metadata_id: 3 offset_ps: 1100000000 duration_ps: 100000000 }
    events { metadata_id: 4 offset_ps: 1300000000 duration_ps: 200000000 }
    events { metadata_id: 5 offset_ps: 1800000000 duration_ps: 200000000 }
    events { metadata_id: 6 offset_ps: 3000000000 duration_ps: 100000000 }
    events { metadata_id: 7 offset_ps: 3100000000 duration_ps: 300000000 }
    events { metadata_id: 4 offset_ps: 3500000000 duration_ps: 100000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[32,1,11520]{2,1,0} fusion(bf16[32,4,11520]{2,1,0} %w), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/gdn.conv/mul:" } } }
  event_metadata { key: 2 value { id: 2 name: "%while.7 = (s32[], f32[6,160,96,5760]{3,2,1,0}) while(%tuple.3), condition=%cond, body=%body" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/gdn.step/while" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[96,5760]{1,0} fusion(f32[1,1,96,5760]{3,2,1,0} %s), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/gdn.step/while/body/mul:" } } }
  event_metadata { key: 4 value { id: 4 name: "%paged_attention.3 = bf16[32,1,32,128]{3,2,1,0} custom-call(s32[32,552]{1,0} %t, bf16[32,1,32,128]{3,2,1,0} %q), custom_call_target=\\"tpu_custom_call\\"" stats { metadata_id: 1 str_value: "jit(paged_decode)/while/body/closed_call/paged_attention" } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.5 = f32[32,100352]{1,0} fusion(bf16[32,3840]{1,0} %x), kind=kOutput" stats { metadata_id: 1 str_value: "jit(paged_decode)/be,ev->bv/dot_general:" } } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6 = f32[1,512,11520]{2,1,0} fusion(bf16[1,515,11520]{2,1,0} %w), kind=kLoop" stats { metadata_id: 1 str_value: "jit(paged_prefill)/while/body/closed_call/gdn.conv/mul:" } } }
  event_metadata { key: 7 value { id: 7 name: "%while.9 = (s32[], f32[1,30,96,192]{3,2,1,0}) while(%tuple.5), condition=%cond.1, body=%body.1" stats { metadata_id: 1 str_value: "jit(paged_prefill)/while/body/closed_call/gdn.scan/while" } } }
  event_metadata { key: 10 value { id: 10 name: "jit_paged_decode(1927483290925264665)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_paged_prefill(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 900000000 duration_ps: 1200000000 stats { metadata_id: 1 int64_value: 2 } stats { metadata_id: 2 int64_value: 3000 } }
    events { metadata_id: 4 offset_ps: 2150000000 duration_ps: 40000000 stats { metadata_id: 3 int64_value: 4160 } }
    events { metadata_id: 5 offset_ps: 2800000000 duration_ps: 1400000000 }
    events { metadata_id: 3 offset_ps: 2820000000 duration_ps: 60000000 stats { metadata_id: 3 int64_value: 4096 } }
    events { metadata_id: 2 offset_ps: 2900000000 duration_ps: 1200000000 stats { metadata_id: 3 int64_value: 512 } }
    events { metadata_id: 4 offset_ps: 4020000000 duration_ps: 70000000 stats { metadata_id: 3 int64_value: 4608 } } }
  event_metadata { key: 1 value { id: 1 name: "engine.decode" } }
  event_metadata { key: 2 value { id: 2 name: "engine.prefill" } }
  event_metadata { key: 3 value { id: 3 name: "engine.state_restore" } }
  event_metadata { key: 4 value { id: 4 name: "engine.state_snapshot" } }
  event_metadata { key: 5 value { id: 5 name: "batcher.admit" } }
  stat_metadata { key: 1 value { id: 1 name: "slots" } }
  stat_metadata { key: 2 value { id: 2 name: "kv_tokens" } }
  stat_metadata { key: 3 value { id: 3 name: "tokens" } }
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
    # the loop over the live rows and the turn inside it: their union, 200 us
    ("gdn_step_ms", 200 / 1e3),
    # 2 slots x 6 layers x 2 x 30 x 96 x 192 x 4 B = 53,084,160 B over
    # 819e9 B/s = 64.82 us, over 200 us
    ("gdn_step_roofline", 100 * (53084160 / 819e9) / 200e-6),
    # conv 100 + scan 300 us of the one prefill
    ("gdn_scan_ms", 400 / 1e3),
    # 512 tokens x 6 layers x 17,280 values x 2 B = 106,168,320 B over 819e9
    # = 129.6 us (the FLOPs, 512 x 6 x 30 x 6 x 96 x 192 = 1.019e10 over
    # 197e12 = 51.7 us, do not bind), over 400 us
    ("gdn_scan_roofline", 100 * (106168320 / 819e9) / 400e-6),
    # the restore 60 + the snapshot behind the chunk 70 us, one admission;
    # the snapshot decode left (40 us) is inside none
    ("state_restore_ms", 130 / 1e3),
    # the decode execution's one kernel event, not the prefill's
    ("paged_attention_ms.hybrid", 200 / 1e3),
    # 3000 tokens x 2 x 2 FULL layers x 30 heads x 128 x 2 B = 92,160,000 B
    # over 819e9 = 112.5 us, over 200 us
    ("paged_attention_roofline.hybrid", 100 * (92160000 / 819e9) / 200e-6),
    ("decode_device_ms.hybrid", 1000 / 1e3),
    # the span's 1200 us less the 650 us the device is busy inside it
    ("decode_host_ms.hybrid", 550 / 1e3),
    ("prefill_device_ms.hybrid", 1000 / 1e3),
])
def test_hybrid_readers_by_hand(tmp_path, monkeypatch, metric, want):
    _trace(tmp_path, monkeypatch, HYBRID_TRACE, HYBRID_CELL)
    assert common.load_reader(metric)(FACTS) == pytest.approx(want, rel=1e-9)


def test_an_execution_counts_by_its_span_not_by_its_operations(
        tmp_path, monkeypatch):
    """A second decode execution with FEWER operations (one live row less)
    inside a span of its own: span_reduce's `executions` drops it as cut,
    the hybrid readers keep it."""
    extra = HYBRID_TRACE.replace(
        'events { metadata_id: 11 offset_ps: 3000000000',
        'events { metadata_id: 10 offset_ps: 5000000000 duration_ps: 600000000 }\n'
        '    events { metadata_id: 11 offset_ps: 3000000000').replace(
        'events { metadata_id: 6 offset_ps: 3000000000',
        'events { metadata_id: 4 offset_ps: 5100000000 duration_ps: 100000000 }\n'
        '    events { metadata_id: 6 offset_ps: 3000000000').replace(
        'events { metadata_id: 4 offset_ps: 2150000000',
        'events { metadata_id: 1 offset_ps: 4900000000 duration_ps: 800000000 '
        'stats { metadata_id: 1 int64_value: 1 } '
        'stats { metadata_id: 2 int64_value: 1000 } }\n'
        '    events { metadata_id: 4 offset_ps: 2150000000')
    tr = _trace(tmp_path, monkeypatch, extra, HYBRID_CELL)
    assert len(tr.executions("jit_paged_decode", inside="engine.decode")) == 1
    assert common.load_reader("decode_device_ms.hybrid")(FACTS) == \
        pytest.approx((1000 + 600) / 2 / 1e3)
    assert common.load_reader("paged_attention_ms.hybrid")(FACTS) == \
        pytest.approx((200 + 100) / 2 / 1e3)
    assert common.load_reader("decode_device_ms")(FACTS) == pytest.approx(1.0)


def test_the_accepted_kernel_reader_would_count_every_layer(tmp_path,
                                                            monkeypatch):
    """Why `paged_attention_roofline.hybrid` has a count of its own: the
    accepted one multiplies by num_hidden_layers (8) and reads head_dim from
    a key this file does not have."""
    _trace(tmp_path, monkeypatch, HYBRID_TRACE, HYBRID_CELL)
    with pytest.raises(KeyError, match="head_dim"):
        common.load_reader("paged_attention_roofline")(FACTS)
    mod = common._load_module("layer_metrics", "paged_attention_roofline.hybrid")
    assert mod.kv_bytes(common.load_config(HYBRID), 1) == 30720


def test_the_scan_count_takes_the_larger_bound():
    mod = common._load_module("layer_metrics", "gdn_scan_roofline")
    nbytes, flops = mod.scan_work(common.load_config(HYBRID), 1000)
    assert (nbytes, flops) == (1000 * 6 * 17280 * 2,
                               1000 * 6 * 30 * 6 * 96 * 192)
    assert flops / nbytes == pytest.approx(96.0)  # bytes bind on a v5e (240)
    step = common._load_module("layer_metrics", "gdn_step_roofline")
    assert step.state_bytes(common.load_config(HYBRID), 1) == 6 * 2 * 2211840


@pytest.mark.parametrize("metric,want", [
    ("itl_p95_ms.hybrid", "p95"),
    # serve_engine_step_s{phase=decode}: (3.0 - 1.0) s over 20 steps
    ("engine_decode_step_ms.hybrid", 100.0),
    ("device_idle_share.hybrid", 30.0),
    # the accepted readers the cell joins, on the same facts: the tails
    # beside run.py's own median of the first-token times
    ("ttft_p90_ms", 1.0 + 0.9 * 3),
    ("ttft_p95_ms", 1.0 + 0.95 * 3),
    # serve_queue_wait_s: (0.9 - 0.1) s over 8 requests; the prefill spans:
    # (2.4 - 0.4) s over 10
    ("queue_wait_ms", 100.0),
    ("engine_prefill_ms", 200.0),
    # 9,000 tokens reused of 9,000 + 1,000 computed
    ("prefix_reuse_share", 90.0),
    ("loadgen_late_ms", 1.0 + 0.95 * 2),
])
def test_the_hybrid_client_and_step_twins_by_hand(metric, want):
    gaps = [float(i) for i in range(1, 21)]
    facts = {"kind": "serve", "trace": {"idle_share_pct": 30.0},
             "client": {"ttft_ms": [4.0, 1.0, 3.0, 2.0], "itl_ms": gaps,
                        "late_ms": [1.0, 2.0, 3.0]},
             "before": {"hist": {"decode_step": {"sum": 1.0, "count": 10},
                                 "queue_wait": {"sum": 0.1, "count": 2},
                                 "prefill_step": {"sum": 0.4, "count": 5}},
                        "engine": {"prefix_tokens_reused": 1000,
                                   "prefill_tokens": 500}},
             "after": {"hist": {"decode_step": {"sum": 3.0, "count": 30},
                                "queue_wait": {"sum": 0.9, "count": 10},
                                "prefill_step": {"sum": 2.4, "count": 15}},
                       "engine": {"prefix_tokens_reused": 10000,
                                  "prefill_tokens": 1500}}}
    if want == "p95":  # run.py's own end-to-end statistic, on the same gaps
        want = common.percentile(gaps, 95)
        assert 19.0 <= want <= 20.0
    assert common.load_reader(metric)(facts) == pytest.approx(want)
    assert common.load_reader("itl_p95_ms.hybrid")(
        {**facts, "client": {"itl_ms": [], "ttft_ms": []}}) is None


@pytest.mark.parametrize("metric", [
    "gdn_step_ms", "gdn_step_roofline", "gdn_scan_ms", "gdn_scan_roofline",
    "state_restore_ms", "paged_attention_roofline.hybrid"])
def test_hybrid_readers_find_nothing_in_another_cells_trace(
        tmp_path, monkeypatch, metric):
    """What the parent gives for a metric new in this PR: no scope, no
    span, another file — None, and nothing raised."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "small_spans.xplane.txt")) as f:
        _trace(tmp_path, monkeypatch, f.read(), "mistral-7b-v0.3-l6.chat")
    assert common.load_reader(metric)(FACTS) is None
    assert common.load_reader(metric)({**FACTS, "trace": None}) is None
