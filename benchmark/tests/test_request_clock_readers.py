"""The five *serve front* readers of PR 39 — what of a first token's time
lies outside the replica's submit -> first token, and where — each on facts
and a trace small enough to compute by hand; and BENCHMARK.json as the
parent left it plus this PR's five metrics.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, span_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT_CELL = "mistral-7b-v0.3-l6.chat"
OLMOE_CELL = "olmoe-1b-7b-l3.chat"
XING_CELL = "xing4.0-29b-a4b-l5.docs-qa"
HYBRID = "olmo-hybrid-7b-l8"
HYBRID_CELL = "olmo-hybrid-7b-l8.sessions"
TTFT_CELLS = [CHAT_CELL, OLMOE_CELL, XING_CELL, HYBRID_CELL]
THIS_PR = ["replica_ttft_ms", "ttft_hop_ms", "ttft_ingress_ms",
           "replica_presubmit_ms", "first_pull_wait_ms"]
TRACE_READERS = THIS_PR[2:]

# Four requests' first tokens on the batcher's line, each a zero-length
# `batcher.first_token` span inside its `batcher.admit`, and their first
# pulls on the puller's line. Three came through the proxy: proxy_us +
# ingress_us = 100 + 1900, 300 + 2200, 200 + 3800 us -> 2.0, 2.5, 4.0 ms,
# replica_us 40, 60, 500. The fourth (req "1f-9", a handle caller) has no
# proxy stages and replica_us 80. waited_us of the four pulls: 900, 100,
# 2500, 1300.
CLOCK_TRACE = """
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "continuous-batcher" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 5000000000 stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 str_value: "7c-1" } }
    events { metadata_id: 2 offset_ps: 5999000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 str_value: "7c-1" } stats { metadata_id: 3 int64_value: 100 } stats { metadata_id: 4 int64_value: 1900 } stats { metadata_id: 5 int64_value: 40 } stats { metadata_id: 6 int64_value: 700 } stats { metadata_id: 7 int64_value: 4990 } }
    events { metadata_id: 2 offset_ps: 9000000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 str_value: "7c-2" } stats { metadata_id: 3 int64_value: 300 } stats { metadata_id: 4 int64_value: 2200 } stats { metadata_id: 5 int64_value: 60 } stats { metadata_id: 6 int64_value: 10 } stats { metadata_id: 7 int64_value: 5100 } }
    events { metadata_id: 2 offset_ps: 15000000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 2 } stats { metadata_id: 2 str_value: "client-id" } stats { metadata_id: 3 int64_value: 200 } stats { metadata_id: 4 int64_value: 3800 } stats { metadata_id: 5 int64_value: 500 } stats { metadata_id: 6 int64_value: 10 } stats { metadata_id: 7 int64_value: 5100 } }
    events { metadata_id: 2 offset_ps: 21000000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 3 } stats { metadata_id: 2 str_value: "1f-9" } stats { metadata_id: 5 int64_value: 80 } stats { metadata_id: 6 int64_value: 10 } stats { metadata_id: 7 int64_value: 5100 } } }
  lines { id: 2 name: "worker-exec" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 6900000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 0 } stats { metadata_id: 2 str_value: "7c-1" } stats { metadata_id: 8 int64_value: 900 } }
    events { metadata_id: 3 offset_ps: 9100000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 1 } stats { metadata_id: 2 str_value: "7c-2" } stats { metadata_id: 8 int64_value: 100 } }
    events { metadata_id: 3 offset_ps: 17500000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 2 } stats { metadata_id: 2 str_value: "client-id" } stats { metadata_id: 8 int64_value: 2500 } }
    events { metadata_id: 3 offset_ps: 22300000000 duration_ps: 1000000 stats { metadata_id: 1 int64_value: 3 } stats { metadata_id: 2 str_value: "1f-9" } stats { metadata_id: 8 int64_value: 1300 } } }
  event_metadata { key: 1 value { id: 1 name: "batcher.admit" } }
  event_metadata { key: 2 value { id: 2 name: "batcher.first_token" } }
  event_metadata { key: 3 value { id: 3 name: "batcher.first_pull" } }
  stat_metadata { key: 1 value { id: 1 name: "rid" } }
  stat_metadata { key: 2 value { id: 2 name: "req" } }
  stat_metadata { key: 3 value { id: 3 name: "proxy_us" } }
  stat_metadata { key: 4 value { id: 4 name: "ingress_us" } }
  stat_metadata { key: 5 value { id: 5 name: "replica_us" } }
  stat_metadata { key: 6 value { id: 6 name: "queue_us" } }
  stat_metadata { key: 7 value { id: 7 name: "prefill_us" } }
  stat_metadata { key: 8 value { id: 8 name: "waited_us" } }
}
"""
TRACED = {"kind": "serve", "trace": {}}


def _clock_trace(tmp_path, monkeypatch, text, cell):
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(text)
    path = str(tmp_path / "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(raw)
    tr = span_reduce.Trace(ProfileData.from_serialized_xspace(raw), cell)
    monkeypatch.setattr(span_reduce, "trace_of", lambda facts: tr)
    monkeypatch.setattr(span_reduce, "newest_xplane", lambda: path)
    return tr


def _window(failed=0):
    """A window of four requests. The client: first tokens 30, 20, 50, 40 ms
    after they were due (mean 35), sent 1, 2, 3, 2 ms late (mean 2). The
    replica: serve_ttft_s grew by 0.104 s over 4 requests (mean 26 ms)."""
    return {"kind": "serve", "trace": None,
            "client": {"failed": failed, "ttft_ms": [30.0, 20.0, 50.0, 40.0],
                       "late_ms": [1.0, 2.0, 3.0, 2.0]},
            "before": {"hist": {"ttft": {"sum": 0.5, "count": 10}}},
            "after": {"hist": {"ttft": {"sum": 0.604, "count": 14}}}}


@pytest.mark.parametrize("metric,want", [
    # medians: of 2.0, 2.5, 4.0 (the handle caller's span has no proxy
    # stages); of 0.04, 0.06, 0.5, 0.08; of 0.9, 0.1, 2.5, 1.3
    ("ttft_ingress_ms", 2.5),
    ("replica_presubmit_ms", 0.07),
    ("first_pull_wait_ms", 1.1),
])
def test_request_clock_trace_readers_by_hand(tmp_path, monkeypatch, metric,
                                             want):
    tr = _clock_trace(tmp_path, monkeypatch, CLOCK_TRACE, CHAT_CELL)
    assert len(tr.named("batcher.first_token")) == 4
    assert [s.stats["req"] for s in tr.named("batcher.first_pull")] == [
        "7c-1", "7c-2", "client-id", "1f-9"]
    assert common.load_reader(metric)(TRACED) == pytest.approx(want)
    # an untraced run (the sweep calls the readers too)
    monkeypatch.undo()
    assert common.load_reader(metric)({**TRACED, "trace": None}) is None


def test_request_clock_window_readers_by_hand():
    facts = _window()
    assert common.load_reader("replica_ttft_ms")(facts) == pytest.approx(26.0)
    # 35 - 2 - 26: what lies outside submit -> first token
    assert common.load_reader("ttft_hop_ms")(facts) == pytest.approx(7.0)
    # a failed request's first-token time is a stand-in (the run's worst):
    # no hop is computed from it; the replica's own mean stands
    failed = _window(failed=1)
    assert common.load_reader("ttft_hop_ms")(failed) is None
    assert common.load_reader("replica_ttft_ms")(failed) == pytest.approx(26.0)
    # no first token in the window, or telemetry off in the replica
    idle = _window()
    idle["after"] = idle["before"]
    off = {**_window(), "before": {"hist": {}}, "after": {"hist": {}}}
    for facts in (idle, off):
        assert common.load_reader("replica_ttft_ms")(facts) is None
        assert common.load_reader("ttft_hop_ms")(facts) is None


@pytest.mark.parametrize("metric", THIS_PR)
def test_request_clock_readers_find_nothing_where_nothing_is(
        tmp_path, monkeypatch, metric):
    """What the parent gives for a metric new in this PR — a trace without
    the two spans — and what a training cell's facts give: None, and
    nothing raised."""
    with open(os.path.join(HERE, "small_spans.xplane.txt")) as f:
        _clock_trace(tmp_path, monkeypatch, f.read(), CHAT_CELL)
    if metric in TRACE_READERS:
        assert common.load_reader(metric)(TRACED) is None
    train = {"kind": "train", "train": {"steps": 5}, "trace": {}}
    assert common.load_reader(metric)(train) is None


# ----------------------------------------------- BENCHMARK.json, appended


def _bench():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_is_the_parents_plus_the_five_of_pr39():
    """test_olmo_hybrid_block.py::
    test_benchmark_json_is_the_parents_plus_appended_entries, every
    assertion but the COUNT of per-layer metrics (54 there; the tier-1
    re-export leaves that test out for that line alone, the file is the
    benchmark's own): the 54 accepted names in their order, this PR's five
    behind them, and nothing else moved."""
    bench = _bench()
    assert [c["name"] for c in bench["configs"]] == [
        "internlm2-1.8b-l12", "internlm2-1.8b", "mistral-7b-v0.3-l6",
        "olmoe-1b-7b-l3", "xing4.0-29b-a4b-l5", HYBRID]
    assert [w["name"] for w in bench["workloads"]] == [
        "internlm2-1.8b-l12.pretrain-4k", CHAT_CELL,
        "internlm2-1.8b.pretrain-4k-fsdp4",
        "mistral-7b-v0.3-l6.chat-saturated", OLMOE_CELL, XING_CELL,
        HYBRID_CELL]
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
    names = [m["name"] for m in bench["per_layer"]]
    accepted = [
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
        # PR 35's thirteen
        "gdn_step_ms", "gdn_step_roofline", "gdn_scan_ms",
        "gdn_scan_roofline", "state_restore_ms",
        "paged_attention_ms.hybrid", "paged_attention_roofline.hybrid",
        "decode_device_ms.hybrid", "decode_host_ms.hybrid",
        "prefill_device_ms.hybrid", "device_idle_share.hybrid",
        "engine_decode_step_ms.hybrid", "itl_p95_ms.hybrid"]
    assert len(accepted) == 54 and names == accepted + THIS_PR
    for m in bench["per_layer"][41:54]:
        assert m["workloads"] == [HYBRID_CELL], m["name"]
    for m in bench["per_layer"][41:]:
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
    # this PR's five: the serve front's, each on the four cells that report
    # `ttft_p50_ms`, in the order that metric lists them
    ttft, = [m for m in bench["end_to_end"] if m["name"] == "ttft_p50_ms"]
    assert ttft["workloads"] == TTFT_CELLS
    sources = {"replica_ttft_ms": "program_span", "ttft_hop_ms": "host_clock",
               "ttft_ingress_ms": "program_span",
               "replica_presubmit_ms": "program_span",
               "first_pull_wait_ms": "program_span"}
    for m in bench["per_layer"][54:]:
        assert m == {"name": m["name"], "unit": "ms", "better": "lower",
                     "source": sources[m["name"]], "layer": "serve front",
                     "moves": "ttft_p50_ms", "workloads": TTFT_CELLS}


def test_the_hybrid_cell_keeps_its_metrics_and_gains_the_five_of_pr39():
    """test_olmo_hybrid_block.py::
    test_the_hybrid_cell_is_declared_with_its_metrics with the set of
    metrics that list the cell held as "what PR 35 declared, plus this
    PR's five" instead of exactly (left out of the tier-1 re-export for
    that line alone); every assertion on the cell's and the
    configuration's files is unchanged."""
    bench = _bench()
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
    assert reports == {"serve_tokens_per_s", "ttft_p50_ms", "setup_s"}
    assert reports | ttft_side | step_side <= listed
    assert listed - (reports | ttft_side | step_side) == set(THIS_PR)
    moves = {m["name"]: m["moves"] for m in bench["per_layer"]}
    assert {moves[n] for n in ttft_side | set(THIS_PR)} == {"ttft_p50_ms"}
    assert {moves[n] for n in step_side} == {"serve_tokens_per_s"}
    assert moves["prefill_device_ms.hybrid"] == moves["prefill_device_ms"]
    assert not {"decode_device_ms", "decode_host_ms", "admit_stall_ms",
                "prefill_device_ms", "paged_attention_ms",
                "paged_attention_roofline", "itl_p95_ms", "itl_p50_ms",
                "engine_decode_step_ms", "device_idle_share.serve"} & listed
    cell = common.load_workload(HYBRID_CELL)
    chat = common.load_workload(OLMOE_CELL)
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
    assert cell["logit_tolerance"] == 0.035
    conf = common.load_config(HYBRID)
    eng = conf["engine"]
    assert 32768 + 2048 + 512 == eng["max_seq_len"] == conf["run"]["max_seq_len"]
    assert eng["max_seq_len"] % 64 == 0
    chunk = eng["prefill_chunk_tokens"]
    assert all(n % chunk == 0 for n in cell["system_prompts"]["lengths"])
    assert eng["num_blocks"] == 1 + sum(cell["system_prompts"]["lengths"]) // 64 + 800
    assert eng["n_snapshots"] >= sum(cell["system_prompts"]["lengths"]) // chunk
    buckets = eng["prefill_buckets"]
    turn = cell["user_turn"]
    widths = [b for b in buckets if b <= turn["max"]]
    assert widths == [256, 512, 1024, 2048] and chunk in widths
    contexts = sorted({min(b for b in buckets if b >= n)
                       for n in cell["system_prompts"]["lengths"]})
    assert contexts == [8192, 16384, 35328]
    assert len(widths) * len(contexts) + 2 <= 16
    # each new list keeps Xing4.0 directly before the hybrid cell, which
    # test_the_xing4_cell_is_declared_as_pr33_left_it asks of every list
    for m in bench["per_layer"][54:]:
        w = m["workloads"]
        assert w[w.index(XING_CELL) + 1:] == [HYBRID_CELL], m["name"]
