"""Tests of the benchmark's own arithmetic. They sit with the benchmark,
outside the repo's tier-1 run:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import common, serve_runner, trace_reduce, traffic  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the block every configuration file here resolves to (test_blocks.py)
LLAMA = common.load_block({})


# ------------------------------------------------------------ percentile


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),   # (100-1)*0.95 = 94.05 -> 95.05
    ([7], 95, 7.0),
    ([5, 1], 0, 1.0),
    ([5, 1], 100, 5.0),
])
def test_percentile(values, q, want):
    assert common.percentile(values, q) == pytest.approx(want)


def test_percentile_matches_numpy():
    import numpy as np

    xs = np.random.default_rng(0).lognormal(size=317)
    for q in (5, 50, 90, 95, 99):
        assert common.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


# ------------------------------------------------- required FLOPs per token


def test_flops_internlm2_by_hand():
    """InternLM2-1.8B: d 2048, 16 q / 8 kv heads x 128, FFN 8192, vocab
    92,544. Per layer: wq 2048x2048 + wk,wv 2 x 2048x1024 + wo 2048x2048 =
    12,582,912; MLP 3 x 2048 x 8192 = 50,331,648; together 62,914,560.
    Head 2048 x 92,544 = 189,530,112."""
    layer, head = 62_914_560, 189_530_112
    for name, layers in (("internlm2-1.8b-l12", 12), ("internlm2-1.8b", 24)):
        conf = common.load_config(name)
        p = LLAMA.matmul_params(conf)
        assert (p["layer"], p["head"], p["layers"]) == (layer, head, layers)
        # causal attention at S=4096: QK^T and PV, 2 FLOPs a multiply-add,
        # 16 heads x 128, (4096+1)/2 keys on average
        attn = layers * 2 * 2 * 16 * 128 * 4097 / 2
        want = 3 * (2 * (layers * layer + head) + attn)
        assert LLAMA.required_train_flops_per_token(conf, 4096) == want
    # the l12 cut: 5.67 GFLOP of matmul + 0.60 of causal attention
    conf = common.load_config("internlm2-1.8b-l12")
    assert LLAMA.required_train_flops_per_token(conf, 4096) / 1e9 == \
        pytest.approx(6.2712, abs=1e-4)


def test_flops_mistral_by_hand():
    """Mistral-7B-v0.3: d 4096, 32 q / 8 kv heads x 128, FFN 14336, vocab
    32,768. Per layer: 4096x4096 x 2 + 4096x1024 x 2 = 41,943,040; MLP
    3 x 4096 x 14336 = 176,160,768; together 218,103,808."""
    conf = common.load_config("mistral-7b-v0.3-l6")
    p = LLAMA.matmul_params(conf)
    assert p == {"layer": 218_103_808, "head": 134_217_728, "layers": 6}
    attn = 6 * 2 * 2 * 32 * 128 * (1024 + 1) / 2
    assert LLAMA.required_train_flops_per_token(conf, 1024) == \
        3 * (2 * (6 * 218_103_808 + 134_217_728) + attn)


def test_causal_attention_is_half_of_full():
    conf = common.load_config("internlm2-1.8b")
    f = LLAMA.required_train_flops_per_token
    matmul = f(conf, 0) - 3 * 24 * 4 * 16 * 128 * 0.5
    full = 3 * 24 * 2 * 2 * 16 * 128 * 4096  # every token sees every key
    assert (f(conf, 4096) - matmul) / full == pytest.approx(0.5, rel=1e-3)


def test_configs_map_onto_the_program():
    for name in ("internlm2-1.8b", "internlm2-1.8b-l12", "mistral-7b-v0.3-l6"):
        kw = LLAMA.transformer_kwargs(common.load_config(name))
        assert kw["n_heads"] * kw["d_head"] == kw["d_model"]
        assert kw["n_heads"] % kw["n_kv_heads"] == 0 and kw["max_seq_len"] == 4096
    bad = dict(common.load_config("internlm2-1.8b"), sliding_window=4096)
    with pytest.raises(ValueError):
        LLAMA.transformer_kwargs(bad)


def test_peaks_missing_kind_is_an_error():
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            common.peaks_for(kind)


def test_seed_above_32_bits_is_kept_apart():
    seeds = [0, 1, 2**31 - 1, 2**31, 2**31 + 1, 2**31 + 7, 3_000_000_007]
    out = [common.jax_seed(s) for s in seeds]
    assert all(0 <= x < 2**31 for x in out) and len(set(out)) == len(seeds)


# ----------------------------------------------------- open-loop schedule


CELL = common.load_workload("mistral-7b-v0.3-l6.chat")


def test_schedule_same_seed_same_everything():
    a = traffic.build_schedule(CELL, 32768, 2**31 + 5, 40.0)
    b = traffic.build_schedule(CELL, 32768, 2**31 + 5, 40.0)
    assert a == b


def test_schedule_every_seed_replays_one_trace():
    a = traffic.build_schedule(CELL, 32768, 11, 40.0)
    b = traffic.build_schedule(CELL, 32768, 12, 40.0)
    key = lambda s: [(r["due_s"], r["sys"], r["user_len"],  # noqa: E731
                      r["max_new_tokens"]) for r in s["requests"]]
    assert key(a) == key(b)                         # the same trace
    assert [r["tokens"] for r in a["requests"]] != \
        [r["tokens"] for r in b["requests"]]        # other token values
    assert a["system_prompts"] != b["system_prompts"]


def test_schedule_follows_the_cell_file():
    s = traffic.build_schedule(CELL, 32768, 3, 40.0)
    reqs = s["requests"]
    assert len(reqs) == math.floor(CELL["rate_per_s"] * 40.0)
    assert [len(p) for p in s["system_prompts"]] == CELL["system_prompts"]["lengths"]
    assert all(0.0 < r["due_s"] < 40.0 for r in reqs)
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    for r in reqs:
        assert 16 <= r["user_len"] <= 1024 and 16 <= r["max_new_tokens"] <= 512
        sp = s["system_prompts"][r["sys"]]
        assert r["tokens"][:len(sp)] == sp
        assert len(r["tokens"]) == len(sp) + r["user_len"]
        assert all(1 <= t < 32768 for t in r["tokens"])
    # Zipf: the first system prompt is the most popular
    counts = [sum(r["sys"] == i for r in reqs) for i in range(8)]
    assert counts[0] == max(counts)


def test_schedule_rate_override_is_the_sweep():
    s = traffic.build_schedule(CELL, 32768, 3, 20.0, rate=4.0)
    assert len(s["requests"]) == 80


# ------------------------------------------- client arithmetic (lateness)


def _rec(due, sent, first, toks, want, ok=True):
    at = [first + 0.01 * i for i in range(toks)] if first is not None else []
    return {"due_s": due, "sent_s": sent, "first_s": first,
            "end_s": at[-1] if at else None, "token_at": at,
            "tokens": toks, "want": want, "ok": ok, "status": "HTTP/1.1 200 OK"}


def test_client_metrics_time_from_due_not_from_send():
    recs = [_rec(1.0, 1.004, 1.1, 10, 10),          # sent 4 ms late
            _rec(2.0, 2.5, 2.6, 5, 5),              # generator stalled 0.5 s
            _rec(9.5, 9.5, 9.9, 100, 100)]          # ends after the window
    m = serve_runner.client_metrics(recs, seconds=10.0)
    assert m["attempted"] == 3 and m["failed"] == 0
    assert m["late_ms"] == pytest.approx([4.0, 500.0, 0.0])
    # the stalled request's first token is 600 ms from when it was DUE
    assert sorted(m["ttft_ms"]) == pytest.approx([100.0, 400.0, 600.0])
    # 10 + 5 tokens, and the 11 of the third stream that came by 10.0 s
    assert m["tokens_in_window"] == 26 and m["in_flight_at_end"] == 1
    assert len(m["itl_ms"]) == 9 + 4 + 99
    assert all(g == pytest.approx(10.0) for g in m["itl_ms"])


def test_client_metrics_a_failed_request_counts_as_the_worst():
    recs = [_rec(1.0, 1.0, 1.2, 10, 10),
            _rec(2.0, 2.0, 2.1, 3, 10, ok=False),   # stream ended short
            _rec(3.0, 3.0, None, 0, 10, ok=False)]  # refused: no token
    m = serve_runner.client_metrics(recs, seconds=10.0)
    assert m["failed"] == 2 and len(m["ttft_ms"]) == 3
    worst = max(m["ttft_ms"])
    assert m["ttft_ms"].count(worst) == 2 and worst >= (10.0 + 60.0 - 3.0) * 1e3
    assert m["tokens_in_window"] == 13    # what arrived, failed or not


def test_sse_parser_counts_whole_lines_only():
    c = serve_runner._Conn({"max_new_tokens": 3}, None, b"")
    serve_runner._absorb(c, b"HTTP/1.1 200 OK\r\nTransfer-Encoding: chunked\r\n\r\n"
                            b"a\r\ndata: 17\n\n\r\n9\r\ndata: ", 1.0)
    assert c.token_at == [1.0] and " 200" in c.status
    serve_runner._absorb(c, b"5\n\n\r\n", 2.0)         # the split line completes
    serve_runner._absorb(c, b"a\r\ndata: 9\n\n\r\nf\r\ndata: [DONE]\n\n\r\n0\r\n\r\n", 3.0)
    assert c.token_at == [1.0, 2.0, 3.0] and c.done
    assert c.buf.endswith(b"0\r\n\r\n")


# ---------------------------------------------------------- trace reduction


def _load_small_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "small_trace.xplane.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return ProfileData.from_serialized_xspace(raw)


def test_union_length_does_not_count_overlap_twice():
    total, merged = trace_reduce.union_length([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [[0, 3], [5, 8]]


def test_trace_reduction_on_the_recorded_trace():
    """small_trace.xplane.txt, times in us. Device 0: fusion.1 10-30,
    all-gather.2 25-45 (overlaps the fusion by 5), fusion.1 again 60-70,
    flash_fwd 70-80, then %while.8 82-90 ENCLOSING fusion.1 83-85 and a
    Pallas custom call 86-89. Device 1: one op 0-50. The window is the
    extent of the device operations, 0-90: the host events that run on to
    100 and 120 (stop_trace, an idle event loop) are outside it. The
    'Steps' line must not be read as ops."""
    r = trace_reduce.reduce_xspace(_load_small_trace())
    assert r["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    assert r["window_s"] == pytest.approx(90e-6)
    # device 0 busy: [10,45] + [60,80] + [82,90] = 63; device 1: 50
    assert r["busy_s_per_device"] == pytest.approx([63e-6, 50e-6])
    assert r["busy_s"] == pytest.approx(56.5e-6)
    assert r["idle_share_pct"] == pytest.approx(100 * (1 - 56.5 / 90))
    assert r["collective_s"] == pytest.approx(20e-6)
    assert r["collective_share_pct"] == pytest.approx(100 * 20 / 90)
    ops = dict((n, t) for n, t in r["device_ops"])
    assert ops["fusion"] == pytest.approx(32e-6)      # 20 + 10 + 2, summed
    # the loop's SELF time: 8 less the 2 + 3 nested inside it
    assert ops["while"] == pytest.approx(3e-6)
    assert ops["custom-call[tpu_custom_call]"] == pytest.approx(3e-6)
    assert r["device_ops"][0][0] == "fusion" and "step 1" not in ops
    # gaps on device 0 inside the window: 0-10, 45-60, 80-82. Over the last
    # two the dispatching thread was in bench.loss_to_host (the innermost
    # span covering them); over the first only train_loop was open. The
    # event-loop thread sat in select() throughout and claims nothing
    gaps = dict((n, t) for n, t in r["idle_gaps"])
    assert gaps == {"bench.loss_to_host": pytest.approx(17e-6),
                    "train_loop": pytest.approx(10e-6)}


def test_self_times_and_short_names():
    ops = [("a", 0.0, 10.0), ("b", 1.0, 3.0), ("c", 2.0, 1.0), ("b", 20.0, 5.0)]
    assert trace_reduce.self_times(ops) == {"a": 7.0, "b": 7.0, "c": 1.0}
    assert trace_reduce.short_name(
        "%fusion.12 = bf16[2,8]{1,0} fusion(bf16[2,8] %p.1), kind=kLoop") == "fusion"
    assert trace_reduce.short_name("all-gather-start.3") == "all-gather-start"
    assert trace_reduce.short_name("%add_fusion = f32[] fusion()") == "add_fusion"


def test_trace_without_device_ops_gives_nothing():
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "main" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } } '
        'event_metadata { key: 1 value { id: 1 name: "f" } } }')
    assert trace_reduce.reduce_xspace(
        ProfileData.from_serialized_xspace(raw)) is None
