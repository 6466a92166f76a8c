"""Tests of the span reduction and the eight readers PR 24 added, on small
traces in XSpace text form; every expected number is worked out by hand
below. With the benchmark, outside the repo's tier-1 run:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, span_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT, TRAIN = "mistral-7b-v0.3-l6.chat", "internlm2-1.8b-l12.pretrain-4k"
SERVE_FACTS = {"kind": "serve", "trace": {},
               "after": {"device_kind": "TPU v5 lite"}}
TRAIN_FACTS = {"kind": "train", "trace": {},
               "train": {"device_kind": "TPU v5 lite"}}
NEW = ["decode_device_ms", "decode_host_ms", "admit_stall_ms",
       "prefill_device_ms", "paged_attention_ms", "paged_attention_roofline",
       "flash_attention_ms", "flash_attention_roofline"]

# one training step 1,000-11,000 us with a forward (1,000 us), a backward dq
# (1,500) and dkv (2,500); a second step that the stop cut after its forward
STEP_TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 9 offset_ps: 1000000000 duration_ps: 10000000000 }
    events { metadata_id: 9 offset_ps: 11000000000 duration_ps: 3000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 5000000000 duration_ps: 1000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 1500000000 }
    events { metadata_id: 4 offset_ps: 7500000000 duration_ps: 2500000000 }
    events { metadata_id: 1 offset_ps: 10000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 11000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 13000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%jvp_flash_attention_fwd_.2 = bf16[2,16,4096,128]{3,2,1,0} custom-call(), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%flash_attention_bwd_dq.7 = bf16[2,16,4096,128]{3,2,1,0} custom-call(), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 4 value { id: 4 name: "%shard_map_flash_attention_bwd_dkv_.8 = bf16[2,8,4096,128]{3,2,1,0} custom-call(), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 9 value { id: 9 name: "jit_step_fn(9726832607307812960)" } }
}
"""


def _trace(text: str, cell: str) -> span_reduce.Trace:
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(text)
    return span_reduce.Trace(ProfileData.from_serialized_xspace(raw), cell)


@pytest.fixture(scope="module")
def serve_trace():
    with open(os.path.join(HERE, "small_spans.xplane.txt")) as f:
        return _trace(f.read(), CHAT)


@pytest.fixture(scope="module")
def step_trace():
    return _trace(STEP_TRACE, TRAIN)


def _read(monkeypatch, tr, metric, facts):
    monkeypatch.setattr(span_reduce, "trace_of", lambda f: tr)
    return common.load_reader(metric)(facts)


# the shapes of mistral-7b-v0.3-l6, written out: K and V, 6 layers, 8 KV
# heads of 128, bf16 -> 2 x 6 x 8 x 128 x 2 = 24,576 bytes a cached token
MISTRAL_KV_BYTES_PER_TOKEN = 24576
# internlm2-1.8b-l12 at 4096: a token meets (4096 + 1) / 2 keys on average,
# 16 heads of 128, 2 FLOPs a multiply-add -> 2 x 16 x 128 x 2048.5 =
# 8,390,656 FLOPs a token and matmul; x 4096 tokens x 2 sequences a chip
INTERNLM_FLASH_MATMUL_FLOPS = 8390656 * 4096 * 2


@pytest.mark.parametrize("metric,want", [
    # decode executions inside a recorded span: 100-150 and 220-280 us. The
    # one at 0-40 has no span (cut by the start), the one at 300-310 is cut
    ("decode_device_ms", (50 + 60) / 2 / 1e3),
    # span 90-160 holds the key split (1 us) and the decode (50): 70 - 51;
    # span 210-290 holds its decode (60): 80 - 60
    ("decode_host_ms", (19 + 20) / 2 / 1e3),
    # passes that stepped the engine: 85-162 admits nothing, 162-295 admits
    # for 44 us; the idle pass 40-85 carries no `slots` and is left out
    ("admit_stall_ms", (0 + 44) / 2 / 1e3),
    ("prefill_device_ms", 30 / 1e3),
    # kernel events inside those two executions: 6 + 4 and 12 + 8 us; the
    # kernel's event inside the prefill (5 us) is no decode time
    ("paged_attention_ms", (10 + 20) / 2 / 1e3),
    # kv_tokens 100 + 300 -> 400 x 24,576 B over 819e9 B/s = 12.0029 us,
    # over 30 us of kernel time
    ("paged_attention_roofline",
     100 * (400 * MISTRAL_KV_BYTES_PER_TOKEN / 819e9) / 30e-6),
    ("flash_attention_ms", None),
    ("flash_attention_roofline", None),
])
def test_serving_readers_by_hand(monkeypatch, serve_trace, metric, want):
    got = _read(monkeypatch, serve_trace, metric, SERVE_FACTS)
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))


@pytest.mark.parametrize("metric,want", [
    # the whole step holds 1,000 + 1,500 + 2,500 us of kernels; the cut
    # step's forward is left out on both sides
    ("flash_attention_ms", 5.0),
    # forward 2 + dq 2 + dkv 2 required matmuls over 197e12 FLOP/s
    ("flash_attention_roofline",
     100 * (6 * INTERNLM_FLASH_MATMUL_FLOPS / 197e12) / 5e-3),
    ("decode_device_ms", None), ("paged_attention_roofline", None),
])
def test_training_readers_by_hand(monkeypatch, step_trace, metric, want):
    got = _read(monkeypatch, step_trace, metric, TRAIN_FACTS)
    assert got == (None if want is None else pytest.approx(want, rel=1e-9))
    if metric == "flash_attention_roofline":
        assert 0 < got < 100  # 41.9 %


@pytest.mark.parametrize("metric", NEW)
def test_readers_find_nothing_without_names(monkeypatch, metric):
    """The parent's trace: programs named jit__decode_body / jit__unknown,
    kernels named after their wrapper, no spans. And an untraced run."""
    parent = _trace("""
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 2 offset_ps: 0 duration_ps: 50000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 50000000 } }
  event_metadata { key: 1 value { id: 1 name: "%closed_call.3 = bf16[8] custom-call(), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "jit__decode_body(77)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 60000000 } }
  event_metadata { key: 1 value { id: 1 name: "$kv_paging.py:1154 _plain_step" } } }
""", CHAT)
    for facts in (SERVE_FACTS, TRAIN_FACTS):
        assert _read(monkeypatch, parent, metric, facts) is None
    monkeypatch.undo()
    assert common.load_reader(metric)({"kind": "serve", "trace": None}) is None


def test_whole_executions_and_kernel_names(serve_trace, step_trace):
    runs = [r for r in serve_trace.runs if r.name == "jit_paged_decode"]
    assert [r.stats["n_ops"] for r in runs] == [4, 4, 4, 1]
    assert [r.stats["whole"] for r in runs] == [True, True, True, False]
    assert len(serve_trace.executions("jit_paged_decode")) == 3
    assert len(serve_trace.executions(
        "jit_paged_decode", inside="engine.decode")) == 2
    assert [r.stats["whole"] for r in step_trace.runs] == [True, False]
    assert sorted({k.name for k in step_trace.kernels}) == [
        "flash_attention_bwd_dkv", "flash_attention_bwd_dq",
        "flash_attention_fwd"]
    assert span_reduce.kernel_of("%flash_attention_bwd.5 = f32[] x()") \
        == "flash_attention_bwd"
    assert span_reduce.kernel_of("%closed_call.3 = f32[] x()") is None
    # the name must be the instruction's own, not an operand's
    assert span_reduce.kernel_of(
        "%fusion.9 = f32[] fusion(f32[] %paged_attention.3)") is None


def test_idle_time_goes_to_the_innermost_span(serve_trace):
    """Window 0-310 us, busy 40 + 1 + 50 + 30 + 60 + 10 = 191, idle 119.
    inputs 91-98 (busy 95-96) and 211-218; fetch 101-158 (busy to 150) and
    221-288 (busy to 280); prefill 165-205 (busy 170-200); dispatch 98-101
    and 218-221 (each busy for its last us); admit keeps 163-165 and
    205-207; the decode spans are all leaves; the passes keep 40-85, 85-90,
    162-163, 207-210, 294-295; no span covers 295-300. The Python frame on
    the same line (`$batching.py:761 _loop`) is no span."""
    got = serve_trace.idle_by_span()
    want_us = {"engine.inputs": 13, "engine.fetch": 16, "engine.prefill": 10,
               "engine.dispatch": 4, "engine.reserve": 2,
               "engine.bookkeep": 4, "batcher.emit": 6, "batcher.admit": 4,
               "engine.decode": 0, "batcher.iteration": 55, "(no span)": 5}
    assert {k: round(v * 1e6, 6) for k, v in got.items()
            if k in want_us} == want_us
    assert got["idle_s"] == pytest.approx(119e-6)
    assert got["covered_share"] == pytest.approx(114 / 119)
    assert serve_trace.busy_inside(90e3, 160e3) == pytest.approx(51e3)


def test_roofline_counts_by_hand():
    _, mistral = span_reduce.shapes(CHAT)
    assert span_reduce.paged_attention_bytes(mistral, 1000) \
        == 1000 * MISTRAL_KV_BYTES_PER_TOKEN == 24_576_000
    cell, internlm = span_reduce.shapes(TRAIN)
    unit = span_reduce.flash_matmul_flops(internlm, cell)
    assert unit == INTERNLM_FLASH_MATMUL_FLOPS == 68_736_253_952
    # forward 2 + backward 4 matmuls in each of 12 layers are exactly the
    # attention part of the benchmark's required FLOPs per token
    block = common.load_block(internlm)
    p = block.matmul_params(internlm)
    matmul_part = 3 * 2.0 * (p["layers"] * p["layer"] + p["head"])
    attention_part = block.required_train_flops_per_token(
        internlm, cell["seq_len"]) - matmul_part
    tokens = cell["seq_len"] * cell["batch_per_chip"]
    assert 6 * 12 * unit / tokens == pytest.approx(attention_part, rel=1e-12)
    assert sum(span_reduce.FLASH_MATMULS[k] for k in (
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv")) == 6 == 2 + span_reduce.FLASH_MATMULS[
            "flash_attention_bwd"]


def test_every_new_metric_is_declared_with_its_cells():
    import json

    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    train = [TRAIN, "internlm2-1.8b.pretrain-4k-fsdp4"]
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == (train if name.startswith("flash") else [CHAT])
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "layer_metrics", f"{name}.py"))
    assert [m["name"] for m in bench["per_layer"][-8:]] == NEW
