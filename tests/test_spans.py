"""The serving hot path's one span primitive (ray_tpu/util/profiling.py:
span), the spans the batcher and the paged engine open with it, and the
names the device programs and Pallas kernels carry.

Everything here runs on the CPU: `jax.profiler.start_trace` works there and
host spans land on the `/host:CPU` plane, one line per thread, with their
attributes as event stats. Nothing here is a device time."""

import dataclasses
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import CONFIGS
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.transformer import pack_decode_inputs, pack_prefill_inputs
from ray_tpu.serve import telemetry
from ray_tpu.util import profiling
from ray_tpu.util.profiling import span

DECODE_LEAVES = ("engine.reserve", "engine.inputs", "engine.dispatch",
                 "engine.fetch", "engine.bookkeep")


class _Trace:
    """`with _Trace(dir) as tr:` traces the block (Python tracer off: the
    spans are TraceMe events, not Python frames); afterwards
    `tr.spans(name)` -> [(start_ns, end_ns, stats)] of the host events of
    that name, in time order."""

    def __init__(self, log_dir):
        self.dir = str(log_dir)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        self.events = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.events += [
                        (ev.name, float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns), dict(ev.stats))
                        for ev in line.events
                        if ev.name.startswith(("engine.", "batcher.", "t."))]

    def spans(self, name):
        return sorted((s, e, st) for n, s, e, st in self.events if n == name)


def _inside(inner, outers):
    """the one of `outers` whose interval contains `inner`'s."""
    hits = [o for o in outers if o[0] <= inner[0] and inner[1] <= o[1]]
    assert len(hits) == 1, (inner, hits)
    return hits[0]


def _tiny_engine(tel, **kw):
    cfg = dataclasses.replace(CONFIGS["tiny"], max_seq_len=128)
    return cfg, PagedDecodeEngine(cfg, max_batch_size=2, seed=0,
                                  block_tokens=8, telemetry=tel, **kw)


def _prefill_hist(tel):
    snap = tel.engine_step._snapshot()["values"]
    ents = [e for k, e in snap.items() if ("phase", "prefill") in set(k)]
    return sum(e["sum"] for e in ents), sum(e["count"] for e in ents)


# ------------------------------------------------------------- (a) helper


@pytest.mark.parametrize("with_tel", [True, False], ids=["tel", "no-tel"])
def test_span_feeds_three_sinks_one_duration(tmp_path, monkeypatch, with_tel):
    tel = telemetry.ServeTelemetry(recorder_capacity=16) if with_tel else None
    seen = []
    if with_tel:
        monkeypatch.setattr(
            tel, "observe_phase", lambda p, d: seen.append((p, d)))
    else:
        # no telemetry: the span may not even read the clock
        class _NoClock:
            @staticmethod
            def monotonic():
                raise AssertionError("span read the clock without telemetry")

        monkeypatch.setattr(profiling, "time", _NoClock)
    with _Trace(tmp_path) as tr:
        with span("t.span", tel, phase="decode", event="decode", slot=1,
                  tokens=5) as sp:
            time.sleep(0.02)
            sp.set(slots=(0, 3), kv_tokens=41)
        with span("t.dropped", tel, phase="verify", event="verify") as sp:
            sp.drop()
    (s, e, stats), = tr.spans("t.span")
    # a sequence (the recorder's slot ids) is written to the trace as its length
    assert stats == {"slot": 1, "tokens": 5, "slots": 2, "kv_tokens": 41}
    assert len(tr.spans("t.dropped")) == 1  # the trace keeps the interval
    if not with_tel:
        return
    (phase, dur), = seen  # the dropped span fed nothing
    ev, = tel.recorder.snapshot()
    assert phase == "decode" and ev["name"] == "decode" and ev["slot"] == 1
    assert ev["dur"] == dur  # ONE duration, not two clock reads
    assert ev["args"] == {"tokens": 5, "slots": (0, 3), "kv_tokens": 41}
    assert 0.02 <= dur < 0.5 and abs((e - s) / 1e9 - dur) < 5e-3


def test_span_without_recorder_event_or_phase():
    tel = telemetry.ServeTelemetry(recorder_capacity=16)
    before = _prefill_hist(tel)
    with span("t.leaf", tel):  # no phase, no event: trace only
        pass
    with span("t.admit", tel, slot=0, rid=9) as sp:
        sp.event = "request"  # named once the admission succeeded
    ev, = tel.recorder.snapshot()
    assert (ev["name"], ev["slot"], ev["args"]) == ("request", 0, {"rid": 9})
    assert _prefill_hist(tel) == before


def test_mark_is_a_zero_length_span_that_reads_no_clock(tmp_path, monkeypatch):
    class _NoClock:
        @staticmethod
        def monotonic():
            raise AssertionError("mark read the clock")

    monkeypatch.setattr(profiling, "time", _NoClock)
    with _Trace(tmp_path) as tr:
        with span("t.outer"):
            profiling.mark("t.mark", slot=3, waited_us=1250, req="a-1")
    (s, e, stats), = tr.spans("t.mark")
    assert stats == {"slot": 3, "waited_us": 1250, "req": "a-1"}
    assert e - s < 1e6  # under a millisecond: nothing runs inside it
    _inside((s, e), tr.spans("t.outer"))


# ------------------------------------------- (b) spans of batcher + engine


def test_batcher_and_engine_spans_nest(tmp_path):
    from ray_tpu.serve.batching import ContinuousBatcher

    tel = telemetry.ServeTelemetry(recorder_capacity=512)
    cfg, eng = _tiny_engine(tel, prefix_cache=False)
    rng = np.random.default_rng(0)
    want = {"a": (11, 6), "b": (19, 4)}  # prompt length, max_new_tokens
    # compile outside the trace, then run the traced requests
    eng.admit(0, {"tokens": rng.integers(1, cfg.vocab_size, size=11),
                  "max_new_tokens": 3})
    eng.step([0])
    eng.release(0)
    with _Trace(tmp_path) as tr:
        # the loop starts inside the trace: a pass already open when the
        # session starts is cut by the window's edge and not recorded
        b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.0,
                              telemetry=tel)
        try:
            streams = {
                k: b.submit(tokens=rng.integers(1, cfg.vocab_size, size=p),
                            max_new_tokens=n)
                for k, (p, n) in want.items()}
            outs = {k: list(s) for k, s in streams.items()}
        finally:
            b.close()
    assert {k: len(o) for k, o in outs.items()} == {"a": 6, "b": 4}

    its = tr.spans("batcher.iteration")
    admits = tr.spans("batcher.admit")
    prefills = tr.spans("engine.prefill")
    decodes = tr.spans("engine.decode")
    by_rid = {streams[k].request_id: want[k] for k in want}
    assert sorted(st["rid"] for _, _, st in admits) == sorted(by_rid)
    for pf in prefills:  # iteration > admit > prefill
        ad = _inside(pf, admits)
        _inside(ad, its)
        assert pf[2]["tokens"] == by_rid[ad[2]["rid"]][0]
        assert pf[2]["slot"] == ad[2]["slot"] and pf[2]["last"] == 1
    assert len(prefills) == len(admits) == 2

    # kv_tokens and slots, recomputed from first principles: a request
    # admitted with prompt P and max_new N holds position P after its
    # prefill (which emits token 1) and takes part in the next N - 1
    # decode steps; step k of those attends to P + k + 1 tokens
    live = {}  # rid -> [position, decode steps left]
    pending = sorted(admits, key=lambda a: a[1])
    assert len(decodes) >= 5
    for dec in decodes:
        it = _inside(dec, its)  # iteration > decode
        while pending and pending[0][1] <= dec[0]:
            rid = pending.pop(0)[2]["rid"]
            live[rid] = [by_rid[rid][0], by_rid[rid][1] - 1]
        assert dec[2]["slots"] == it[2]["slots"] == len(live)
        assert dec[2]["kv_tokens"] == sum(p + 1 for p, _ in live.values())
        # ... in that many of its 8-token blocks, of a [2, Nmax] table
        assert dec[2]["kv_blocks_walked"] == sum(
            p // 8 + 1 for p, _ in live.values())
        assert dec[2]["kv_table_blocks"] == 2 * eng.blocks_per_slot
        for rid in list(live):
            live[rid][0] += 1
            live[rid][1] -= 1
            if not live[rid][1]:
                del live[rid]
        kids = [k for leaf in DECODE_LEAVES for k in tr.spans(leaf)
                if dec[0] <= k[0] and k[1] <= dec[1]]
        assert len(kids) == len(DECODE_LEAVES)  # one of each, in order
        assert [k[0] for k in kids] == sorted(k[0] for k in kids)
    assert not live and not pending
    for em in tr.spans("batcher.emit"):
        _inside(em, its)
    assert len(tr.spans("batcher.emit")) == len(decodes)
    # the recorder keeps its documented names, fed from the same spans
    names = [e["name"] for e in tel.recorder.snapshot()]
    assert names.count("request") == 2 and names.count("prefill_chunk") == 3
    assert names.count("decode") == len(decodes) + 1  # + the warm-up step
    dec_ev = [e for e in tel.recorder.snapshot() if e["name"] == "decode"]
    assert all(isinstance(e["args"]["slots"], tuple) for e in dec_ev)


def test_first_token_and_first_pull_spans_once_a_request(tmp_path):
    """A request that carries a clock (telemetry.RequestClock, as the proxy
    and the handle send it): `batcher.first_token` with the five stage
    durations and `batcher.first_pull` with `waited_us`, ONCE each however
    many tokens stream, `req` on both and on `batcher.admit`; a request
    that carries none (submitted outside any request) has the spans
    without `req` and without the stages before `submit`."""
    from ray_tpu.serve.batching import ContinuousBatcher

    tel = telemetry.ServeTelemetry(recorder_capacity=512)
    cfg, eng = _tiny_engine(tel, prefix_cache=False)
    rng = np.random.default_rng(0)
    eng.admit(0, {"tokens": rng.integers(1, cfg.vocab_size, size=11),
                  "max_new_tokens": 3})
    eng.step([0])
    eng.release(0)
    def stage_counts():  # the registry is the process's: read deltas
        snap = tel.request_stage._snapshot()["values"]
        return {dict(k)["stage"]: v["count"] for k, v in snap.items()}

    counts0 = stage_counts()
    ctx = telemetry.new_request("client-7")
    ctx.t_recv = time.time()
    ctx.t_call = time.time()
    ctx.received()  # stage 3, as Replica.handle_request stamps it
    with _Trace(tmp_path) as tr:
        b = ContinuousBatcher(eng, max_batch_size=2, batch_wait_timeout_s=0.0,
                              telemetry=tel)
        try:
            with telemetry.request_scope(ctx):
                carried = b.submit(
                    tokens=rng.integers(1, cfg.vocab_size, size=11),
                    max_new_tokens=20)
            bare = b.submit(tokens=rng.integers(1, cfg.vocab_size, size=9),
                            max_new_tokens=4)
            assert len(list(carried)) == 20 and len(list(bare)) == 4
        finally:
            b.close()
    firsts = {st["rid"]: (s, e, st)
              for s, e, st in tr.spans("batcher.first_token")}
    pulls = {st["rid"]: st for _, _, st in tr.spans("batcher.first_pull")}
    admits = {st["rid"]: (s, e, st) for s, e, st in tr.spans("batcher.admit")}
    assert set(firsts) == set(pulls) == set(admits) == {
        carried.request_id, bare.request_id}  # one of each a request
    s, e, st = firsts[carried.request_id]
    assert set(st) == {"rid", "req", "slot", "proxy_us", "ingress_us",
                       "replica_us", "queue_us", "prefill_us"}
    assert st["req"] == "client-7" and st["slot"] == carried._slot
    assert all(st[k] >= 0 for k in st if k.endswith("_us"))
    # the stream's own clock: submit -> admission -> first token
    assert st["queue_us"] == int(
        (carried.t_admit - carried.t_submit) * 1e6)
    assert st["prefill_us"] == int(
        (carried.t_first - carried.t_admit) * 1e6)
    assert st["replica_us"] == int(
        (carried.t_submit - ctx.t_replica_mono) * 1e6)
    assert st["ingress_us"] == int((ctx.t_replica - ctx.t_call) * 1e6)
    # opened where the admission returned the token: inside its span, and
    # (all but) zero-length, so what reads `batcher.admit` reads the same
    a0, a1, ast = admits[carried.request_id]
    assert a0 <= s and e <= a1 and e - s < 1e6
    assert ast == {"rid": carried.request_id, "req": "client-7",
                   "slot": carried._slot}
    assert set(pulls[carried.request_id]) == {"rid", "req", "waited_us"}
    assert pulls[carried.request_id]["req"] == "client-7"
    assert pulls[carried.request_id]["waited_us"] >= 0
    assert set(firsts[bare.request_id][2]) == {
        "rid", "slot", "queue_us", "prefill_us"}
    assert set(pulls[bare.request_id]) == {"rid", "waited_us"}
    assert set(admits[bare.request_id][2]) == {"rid", "slot"}
    # the histogram: each stage once, for the request that carried them
    assert {k: n - counts0.get(k, 0)
            for k, n in stage_counts().items()} == {
        "proxy_dispatch": 1, "handle_transit": 1, "replica_presubmit": 1,
        "first_pull_wait": 2}
    # and the recorder's admission events carry the id
    reqs = [ev["args"] for ev in tel.recorder.snapshot()
            if ev["name"] == "request"]
    assert reqs == [{"rid": carried.request_id, "req": "client-7"},
                    {"rid": bare.request_id}]


# -------------------------------- (c) the prefill span covers the fetch


def test_prefill_span_covers_first_token_fetch(tmp_path):
    tel = telemetry.ServeTelemetry(recorder_capacity=64)
    cfg, eng = _tiny_engine(tel, prefix_cache=False)
    wait_s = 0.05

    class _Late:
        """a device result that takes `wait_s` to materialise."""

        def __array__(self, *a, **k):
            time.sleep(wait_s)
            return np.array([7], np.int32)

    def stub_prefill(params, pool, *a):
        return _Late(), None, pool  # returns at once, like an enqueue

    eng._prefill = stub_prefill
    sum0, n0 = _prefill_hist(tel)
    with _Trace(tmp_path) as tr:
        tok, done = eng.admit(0, {"tokens": np.arange(1, 10),
                                  "max_new_tokens": 4})
    assert tok == 7 and not done
    sum1, n1 = _prefill_hist(tel)
    assert n1 - n0 == 1 and sum1 - sum0 >= wait_s
    ev, = [e for e in tel.recorder.snapshot() if e["name"] == "prefill_chunk"]
    assert ev["dur"] == pytest.approx(sum1 - sum0)  # one duration, two sinks
    assert ev["args"] == {"tokens": 9, "ctx": 0, "last": True}
    (s, e, stats), = tr.spans("engine.prefill")
    assert (e - s) / 1e9 >= wait_s
    assert stats == {"slot": 0, "tokens": 9, "ctx": 0, "last": 1}


# --------------------------------- (c2) the expert layer's two attributes


def test_decode_span_carries_the_expert_load(tmp_path):
    """A sparse-expert model: every `engine.decode` span carries
    `moe_pairs` (live slots x top_k x layers) and `moe_hottest` (the load
    of the step's fullest expert, summed over the layers), and
    `engine.stats()` their sums; a dense model's span carries neither."""
    cfg = dataclasses.replace(CONFIGS["tiny_moe"], max_seq_len=128,
                              moe_capacity_factor=None)
    eng = PagedDecodeEngine(cfg, max_batch_size=2, seed=0, block_tokens=8)
    rng = np.random.default_rng(0)
    for slot, n in ((0, 11), (1, 5)):
        eng.admit(slot, {"tokens": rng.integers(1, cfg.vocab_size, size=n),
                         "max_new_tokens": 8})
    eng.step([0, 1])  # compiled outside the trace
    with _Trace(tmp_path) as tr:
        eng.step([0, 1])
        eng.step([0])
    both, one = (st for _, _, st in tr.spans("engine.decode"))
    per_slot = cfg.top_k * cfg.n_layers
    assert both["moe_pairs"] == 2 * per_slot and one["moe_pairs"] == per_slot
    # one token's top_k experts differ: a layer's fullest expert holds one
    # pair; two tokens share an expert or not: one or two pairs a layer
    assert one["moe_hottest"] == cfg.n_layers
    assert cfg.n_layers <= both["moe_hottest"] <= 2 * cfg.n_layers
    # ... and so each is a group the step reads: top_k a layer for one
    # token, that to twice that for two
    assert one["moe_touched"] == per_slot
    assert per_slot <= both["moe_touched"] <= 2 * per_slot
    stats = eng.stats()
    assert stats["moe_pairs"] == 5 * per_slot
    assert stats["moe_hottest"] >= 3 * cfg.n_layers
    assert 4 * per_slot <= stats["moe_touched"] <= 5 * per_slot

    _, dense = _tiny_engine(None)
    dense.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 4})
    dense.step([0])
    with _Trace(tmp_path / "dense") as tr:
        dense.step([0])
    (_, _, st), = tr.spans("engine.decode")
    assert not {"moe_pairs", "moe_hottest", "moe_touched"} & set(st)
    assert dense.stats()["moe_pairs"] == dense.stats()["moe_touched"] == 0


def test_moe_touched_counts_the_groups_the_live_slots_read(tmp_path,
                                                           monkeypatch):
    """`moe_touched` on a hand-made routing: the number of distinct
    (layer, expert) groups with a pair among the LIVE slots — the numpy
    count — riding the array `moe_hottest` comes in (one fetch a step);
    a slot that is not stepped routes too (to the null block) and is left
    out. None for a dense model, as `moe_hottest` is."""
    import ray_tpu.models.transformer as tfm

    cfg = dataclasses.replace(CONFIGS["tiny_moe"], max_seq_len=128,
                              n_layers=3, n_experts=8, top_k=3,
                              moe_capacity_factor=None)
    # slot -> its three experts, the same in every layer (a decode step's
    # rows are the slots; a prefill's rows all take the first line)
    choice = np.array([[0, 1, 2], [2, 3, 7], [1, 2, 3], [5, 6, 7]], np.int32)
    route = tfm._moe_route

    def hand_made(x, lp, cfg):
        w, _ = route(x, lp, cfg)
        rows = jnp.arange(x.shape[0]) % len(choice) * (x.shape[0] == 4)
        return w, jnp.asarray(choice)[rows]

    monkeypatch.setattr(tfm, "_moe_route", hand_made)
    eng = PagedDecodeEngine(cfg, max_batch_size=4, seed=0, block_tokens=8)
    for slot in range(4):
        eng.admit(slot, {"tokens": np.arange(1, 8 + slot),
                         "max_new_tokens": 8})
    fetched = []
    decode = eng._decode_step

    def spy(*a):
        out = decode(*a)
        fetched.append(out[0])
        return out

    eng._decode_step = spy
    eng.step([0, 1, 2, 3])  # compiled outside the trace
    with _Trace(tmp_path) as tr:
        for live in ([0, 1, 2, 3], [0, 2], [3], [1, 3]):
            eng.step(live)
    want_touched, want_hottest = [], []
    for live in ([0, 1, 2, 3], [0, 1, 2, 3], [0, 2], [3], [1, 3]):
        load = np.bincount(choice[live].ravel(), minlength=cfg.n_experts)
        want_touched.append(cfg.n_layers * int(np.count_nonzero(load)))
        want_hottest.append(cfg.n_layers * int(load.max()))
    assert want_touched == [21, 21, 12, 9, 15]
    spans = [st for _, _, st in tr.spans("engine.decode")]
    assert [st["moe_touched"] for st in spans] == want_touched[1:]
    assert [st["moe_hottest"] for st in spans] == want_hottest[1:]
    # both counts ride the one vector the step fetches, behind its 4 tokens
    assert all(a.shape == (4 + 2,) and a.dtype == jnp.int32 for a in fetched)
    assert [a[4:].tolist() for a in fetched] == [
        list(pair) for pair in zip(want_hottest, want_touched)]
    stats = eng.stats()
    assert stats["moe_touched"] == sum(want_touched)
    assert stats["moe_hottest"] == sum(want_hottest)

    _, dense = _tiny_engine(None)
    dense.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 4})
    out = dense._decode_step(*_program_args(dense, "paged_decode")[1])
    assert out[0].shape == (dense.max_batch_size,)  # the tokens alone


# --------------------------------------------- (d) names on the device


def _program_args(eng, which):
    B, K1 = eng.max_batch_size, 3
    zB = np.zeros(B, np.int32)
    key = jax.random.PRNGKey(0)
    if which == "paged_decode":
        return eng._decode_step, (
            eng.params, eng.pool,
            pack_decode_inputs(eng._tables, zB, zB, zB, zB), key)
    if which == "paged_verify":
        zK = np.zeros((B, K1), np.int32)
        return eng._verify_step, (eng.params, eng.pool, eng._tables, zK, zB,
                                  zB, zK, zK, key)
    if which == "copy_blocks":
        one = np.ones(1, np.int32)
        return eng._copy_blocks, (eng.pool, one, one)
    fn, = eng._prefill.programs.values()
    return fn, (eng.params, eng.pool, pack_prefill_inputs(
        eng._tables[0], np.zeros(16, np.int32), 9, 0), key)


@pytest.mark.parametrize(
    "which", ["paged_prefill", "paged_decode", "paged_verify", "copy_blocks"])
def test_serving_programs_lower_under_stable_names(which):
    _, eng = _tiny_engine(False, prefix_cache=False,
                          prefill_buckets=(16,))
    if which == "paged_prefill":  # the prefill program is built on demand
        eng.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(eng, which)
    assert f"module @jit_{which} " in fn.lower(*args).as_text()[:200]


@pytest.mark.parametrize("capacity", [None, 1.25], ids=["dropless", "capacity"])
@pytest.mark.parametrize("which", ["paged_prefill", "paged_decode"])
def test_expert_layer_lowers_under_its_two_scopes(which, capacity):
    """`moe.route` and `moe.experts` reach the operations' names in every
    program: what benchmark/layer_metrics/moe_device_ms.py finds the expert
    layer's device time by."""
    cfg = dataclasses.replace(CONFIGS["tiny_moe"], max_seq_len=128,
                              moe_capacity_factor=capacity)
    eng = PagedDecodeEngine(cfg, max_batch_size=2, seed=0, block_tokens=8,
                            prefix_cache=False, prefill_buckets=(16,))
    if which == "paged_prefill":
        eng.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(eng, which)
    text = fn.lower(*args).as_text(debug_info=True)
    assert "moe.route/" in text and "moe.experts/" in text
    _, dense = _tiny_engine(False, prefix_cache=False, prefill_buckets=(16,))
    if which == "paged_prefill":
        dense.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(dense, which)
    # by scope, not by "moe.": a location in the text may name a test file
    # (tests/test_olmoe.py, where a cached helper was first traced)
    dense_text = fn.lower(*args).as_text(debug_info=True)
    assert "moe.route" not in dense_text and "moe.experts" not in dense_text


def _flash_fwd(q, k, v):
    from ray_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, block_q=16, block_k=16, interpret=True)


def _flash_bwd(q, k, v):
    return jax.grad(lambda *a: _flash_fwd(*a).sum(), argnums=(0, 1, 2))(
        q, k, v)


def _flash_bwd_one_block(q, k, v):
    return _flash_bwd(q[:, :16], k[:, :16], v[:, :16])


def _paged(q, k, v):
    from ray_tpu.ops.paged_attention import paged_attention

    pool = jnp.zeros((4, 8, 2, 16), jnp.float32)
    return paged_attention(
        q[:, :1, :, :], pool, pool, jnp.zeros((1, 2), jnp.int32),
        jnp.zeros((1,), jnp.int32), impl="kernel", interpret=True)


def _mla(q, k, v):
    from ray_tpu.ops.paged_attention import mla_paged_attention

    pool = jnp.zeros((1, 4, 8, 1, 128), jnp.float32)
    return mla_paged_attention(
        jnp.zeros((1, 1, 2, 128), jnp.float32), pool,
        jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32), layer=0,
        rank=32, scale=1.0, impl="kernel", interpret=True)


@pytest.mark.parametrize("fn,kv_heads,names", [
    (_flash_fwd, 2, ["flash_attention_fwd"]),
    (_flash_bwd, 1, ["flash_attention_fwd", "flash_attention_bwd_dq",
                     "flash_attention_bwd_dkv"]),
    (_flash_bwd_one_block, 2, ["flash_attention_fwd", "flash_attention_bwd"]),
    (_paged, 2, ["paged_attention"]),
    (_mla, 2, ["mla_paged_attention"]),
], ids=["flash_fwd", "flash_bwd", "flash_bwd_fused", "paged", "mla_paged"])
def test_kernels_carry_their_names(fn, kv_heads, names):
    q = jnp.ones((1, 32, 2, 16), jnp.float32)
    kv = jnp.ones((1, 32, kv_heads, 16), jnp.float32)
    calls = {}  # kernel name -> the scope it was called under

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                calls[name] = str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(q, kv, kv).jaxpr)
    assert sorted(calls) == sorted(names)
    # pallas_call enters its name as a named scope too (under grad:
    # "jvp(flash_attention_fwd)"), which is what the TPU compiler names the
    # instruction after (%flash_attention_fwd.<n>)
    for name, scope in calls.items():
        assert name in scope.split("/")[-1], (name, scope)


# ------------------- (e) the latent / hyper-connection layer's own names


def _latent_engine(**kw):
    """A small model of the Xing4.0 layer family: latent attention, a dense
    layer before sigmoid-routed experts with a shared one, four streams."""
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_head=24, d_ff=32, max_seq_len=128, n_experts=4, top_k=2,
        moe_capacity_factor=None, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        first_k_dense=1, d_ff_dense=96, moe_scoring="sigmoid",
        moe_route_scale=2.0, n_shared_experts=1, hc_mult=4)
    return cfg, PagedDecodeEngine(
        cfg, max_batch_size=2, seed=0, block_tokens=8, prefix_cache=False,
        prefill_buckets=(16,), **kw)


@pytest.mark.parametrize("which", ["paged_prefill", "paged_decode"])
def test_latent_layer_lowers_under_its_scopes_and_kernel(which):
    """`hc.mix` (both sublayers' mixes), `moe.shared` NESTED under
    `moe.experts` (so `moe_device_ms` counts the shared expert) and the
    kernel's own name `mla_paged_attention`: what the readers
    hc_device_ms and mla_attention_ms find
    their operations by. The programs keep their names."""
    _, eng = _latent_engine(attention_impl="fused")
    if which == "paged_prefill":
        eng.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(eng, which)
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{which} " in text
    for scope in ("hc.mix/", "moe.route/", "moe.experts/moe.shared/"):
        assert scope in text, scope
    # (on the CPU the fused op takes its XLA twin; the kernel's name is
    # pinned in test_kernels_carry_their_names)
    _, plain = _tiny_engine(False, prefix_cache=False, prefill_buckets=(16,))
    if which == "paged_prefill":
        plain.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(plain, which)
    plain_text = fn.lower(*args).as_text(debug_info=True)
    assert "hc.mix" not in plain_text and "moe.shared" not in plain_text


def test_latent_decode_span_and_stats_keep_their_attributes(tmp_path):
    """`engine.decode` keeps `kv_tokens`, `moe_pairs`, `moe_hottest` — the
    last two over the EXPERT layers, not all layers — and `engine.stats()`
    says what a resident token costs by the pool's own leaves."""
    cfg, eng = _latent_engine()
    eng.admit(0, {"tokens": np.arange(1, 12), "max_new_tokens": 8})
    eng.step([0])  # compiled outside the trace
    with _Trace(tmp_path) as tr:
        eng.step([0])
    (_, _, st), = tr.spans("engine.decode")
    assert st["kv_tokens"] == 13
    assert st["moe_pairs"] == cfg.top_k * 1  # one expert layer of two layers
    assert st["moe_hottest"] == 1
    assert st["moe_touched"] == cfg.top_k
    stats = eng.stats()
    assert stats["kv_bytes_per_token"] == cfg.n_layers * 128 * 2
    assert stats["kv_pool_bytes"] == eng.pool["kv"].nbytes
    assert stats["attention_kernel"] == "gather"  # "pallas" only on a TPU


# ---- one chip's share of an expert-parallel layer, group-limited routing ----


def _share_engine(**kw):
    """A small model of the DeepSeek-V2 layer as one chip's share: latent
    attention under a plain residual, a dense layer before softmax-routed
    experts in 4 groups of which 2 are kept, 4 of 16 experts held (rank 1),
    two shared experts."""
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=3, n_heads=8, n_kv_heads=8,
        d_head=24, d_ff=32, max_seq_len=128, n_experts=4, top_k=3,
        n_routed_experts=16, expert_offset=4, moe_n_group=4, moe_topk_group=2,
        moe_renormalize=False, moe_route_scale=16.0, moe_capacity_factor=None,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_k_dense=1, d_ff_dense=96,
        n_shared_experts=2)
    return cfg, PagedDecodeEngine(
        cfg, max_batch_size=4, seed=0, block_tokens=8, prefix_cache=False,
        prefill_buckets=(16,), **kw)


@pytest.mark.parametrize("which", ["paged_prefill", "paged_decode"])
def test_group_limited_layer_lowers_under_its_scopes(which):
    """`moe.groups` NESTED under `moe.route` (so `moe_device_ms` counts the
    group selection with the router) and `moe.shared` under `moe.experts`;
    a layer with one group has no such scope. The programs keep their
    names."""
    _, eng = _share_engine(attention_impl="fused")
    if which == "paged_prefill":
        eng.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(eng, which)
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{which} " in text
    for scope in ("moe.route/moe.groups/", "moe.experts/moe.shared/"):
        assert scope in text, scope
    assert "hc.mix" not in text  # the plain residual
    _, one_group = _latent_engine(attention_impl="fused")
    if which == "paged_prefill":
        one_group.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(one_group, which)
    assert "moe.groups" not in fn.lower(*args).as_text(debug_info=True)


def test_share_decode_span_and_stats_carry_the_held_counts(tmp_path):
    """`engine.decode` of a replica that holds a share: `moe_pairs` is what
    the live slots' router chose over ALL routed experts (slots x top_k x
    expert layers), `moe_pairs_held` those on held experts, `moe_hottest`
    / `moe_touched` over the held experts alone; the latent pool's walk is
    counted as the per-head pool's is (`kv_blocks_walked` of
    `kv_table_blocks`). `engine.stats()` sums them and says what is held
    of what is routed. A replica that holds every expert reports
    `moe_pairs_held == moe_pairs`."""
    cfg, eng = _share_engine()
    rng = np.random.default_rng(0)
    for slot, n in ((0, 11), (1, 5), (2, 19)):
        eng.admit(slot, {"tokens": rng.integers(1, cfg.vocab_size, size=n),
                         "max_new_tokens": 8})
    eng.step([0, 1, 2])  # compiled outside the trace
    with _Trace(tmp_path) as tr:
        eng.step([0, 1, 2])
        eng.step([2])
    three, one = (st for _, _, st in tr.spans("engine.decode"))
    per_slot = cfg.top_k * 2  # two EXPERT layers of three
    assert three["moe_pairs"] == 3 * per_slot and one["moe_pairs"] == per_slot
    for st in (three, one):
        assert 0 <= st["moe_pairs_held"] <= st["moe_pairs"]
        assert st["moe_touched"] <= st["moe_pairs_held"]
        assert st["moe_hottest"] <= st["moe_pairs_held"]
        assert st["moe_touched"] <= 2 * cfg.n_experts  # held groups a layer
    # kv_tokens 13 + 7 + 21, in 2 + 1 + 3 blocks of 8, of a 4 x 16 table
    assert three["kv_tokens"] == 41
    assert three["kv_blocks_walked"] == 6 and one["kv_blocks_walked"] == 3
    assert three["kv_table_blocks"] == one["kv_table_blocks"] == 4 * 16
    stats = eng.stats()
    assert (stats["experts_held"], stats["experts_routed"]) == (4, 16)
    assert stats["moe_pairs"] == 7 * per_slot
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs"]
    assert stats["kv_blocks_walked"] == 6 + 6 + 3
    assert stats["kv_table_blocks"] == 3 * 64
    assert stats["param_bytes"] == sum(
        a.nbytes for a in jax.tree.leaves(eng.params))

    whole_cfg, whole = _latent_engine()
    whole.admit(0, {"tokens": np.arange(1, 12), "max_new_tokens": 8})
    whole.step([0])
    with _Trace(tmp_path / "whole") as tr:
        whole.step([0])
    (_, _, st), = tr.spans("engine.decode")
    assert st["moe_pairs_held"] == st["moe_pairs"] == whole_cfg.top_k
    stats = whole.stats()
    assert stats["experts_held"] == stats["experts_routed"] == 4
    assert stats["moe_pairs_held"] == stats["moe_pairs"]


@pytest.mark.parametrize("asked,word", [
    (dict(kv_cache_dtype="int8"), "kv_dtype"),
    (dict(speculative_k=2), "speculative_k"),
    (dict(mesh="a mesh"), "mesh"),
], ids=["int8", "speculation", "mesh"])
def test_the_shares_latent_pool_refuses_what_it_has_no_path_for(asked, word):
    with pytest.raises(NotImplementedError, match=f"latent.*{word}"):
        _share_engine(**asked)


# ---- a hybrid cache: linear (gated-delta-rule) layers beside full ones -----


def _hybrid_engine(tel=False, **kw):
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=2,
        d_head=16, d_ff=64, max_seq_len=128, qk_norm=True, use_rope=False,
        norm_placement="post", layer_period=("linear",) * 3 + ("full",),
        linear_n_heads=2, linear_d_k=8, linear_d_v=16, dtype=jnp.float32)
    return cfg, PagedDecodeEngine(
        cfg, max_batch_size=2, seed=0, block_tokens=8, telemetry=tel,
        prefill_buckets=(16,), n_snapshots=3, **kw)


@pytest.mark.parametrize("which", ["paged_prefill", "paged_decode"])
def test_linear_layers_lower_under_their_scopes(which):
    """`gdn.conv` in both programs, `gdn.scan` (the chunked form) in
    prefill, `gdn.step` (the in-place state step) in decode: what the
    readers gdn_scan_ms and gdn_step_ms find the linear layers' device time
    by. No Pallas kernel is written yet; the names `gdn_chunk_scan` /
    `gdn_state_step` are the readers' for when one is. The programs keep
    their names, and a model without linear layers has none of the scopes."""
    _, eng = _hybrid_engine()
    if which == "paged_prefill":
        eng.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(eng, which)
    text = fn.lower(*args).as_text(debug_info=True)
    assert f"module @jit_{which} " in text
    own, other = (("gdn.scan/", "gdn.step/") if which == "paged_prefill"
                  else ("gdn.step/", "gdn.scan/"))
    assert "gdn.conv/" in text and own in text and other not in text
    _, plain = _tiny_engine(False, prefix_cache=False, prefill_buckets=(16,))
    if which == "paged_prefill":
        plain.admit(0, {"tokens": np.arange(1, 10), "max_new_tokens": 2})
    fn, args = _program_args(plain, which)
    assert "gdn." not in fn.lower(*args).as_text(debug_info=True)
    readers = {}
    for name in ("gdn_step_ms", "gdn_scan_ms"):
        from benchmark import common

        mod = common._load_module("layer_metrics", name)
        readers[name] = (mod.SCOPES, mod.KERNELS)
    assert readers == {"gdn_step_ms": (("gdn.step",), ("gdn_state_step",)),
                       "gdn_scan_ms": (("gdn.scan", "gdn.conv"),
                                       ("gdn_chunk_scan",))}


def test_copy_state_lowers_under_its_name():
    _, eng = _hybrid_engine()
    one = np.ones(1, np.int32)
    assert "module @jit_copy_state " in eng._copy_state.lower(
        eng.pool, one, one).as_text()[:200]


def test_state_restore_and_snapshot_spans(tmp_path):
    """`engine.state_snapshot` (slot -> snapshot: behind a prefill chunk
    that ends on a block boundary, and when decode crosses one) and
    `engine.state_restore` (snapshot -> slot, at admission) with `slot` and
    `tokens`; `engine.decode` keeps `slots` and `kv_tokens`; the counters of
    `engine.stats()`."""
    cfg, eng = _hybrid_engine()
    hist = np.arange(1, 17)  # two whole blocks
    eng.admit(0, {"tokens": hist, "max_new_tokens": 2})
    eng.step([0])
    eng.release(0)
    with _Trace(tmp_path) as tr:
        eng.admit(1, {"tokens": np.concatenate([hist, [40, 41, 42]]),
                      "max_new_tokens": 8})
        for _ in range(6):  # 19 -> 25: crosses the boundary at 24
            eng.step([1])
    (_, _, st), = tr.spans("engine.state_restore")
    assert (st["slot"], st["tokens"]) == (1, 16)
    (_, _, st), = tr.spans("engine.state_snapshot")
    assert st["tokens"] == 24
    decodes = [st for _, _, st in tr.spans("engine.decode")]
    assert [d["kv_tokens"] for d in decodes] == [20, 21, 22, 23, 24, 25]
    assert all(d["slots"] == 1 for d in decodes)
    stats = eng.stats()
    assert stats["state_restores"] == 1
    assert stats["state_snapshots"] == 2  # the history's end, the tail at 24
    assert stats["prefix_tokens_reused"] == 16
    assert stats["state_snapshot_evictions"] == 0
    assert stats["state_rows_total"] == 2 + 3 and stats["state_rows_free"] == 1
    assert stats["state_bytes_per_seq"] == sum(
        eng.pool[n][:, 0].nbytes for n in ("state", "conv"))
    assert stats["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 4  # ONE full layer
    assert stats["kv_pool_bytes"] == eng.pool["k"].nbytes + eng.pool["v"].nbytes
