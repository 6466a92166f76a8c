"""Cluster-wide KV plane (ISSUE 18 acceptance).

The transfer path must be INVISIBLE to the tokens: a replica that imports
a peer's prefix blocks continues greedy generation token-for-token
identically to a cold monolithic replica — fp and int8 pools, gather and
fused attention — while its prefill counters prove the prefix was
imported, not recomputed. Content-addressed keys are deterministic across
processes and disjoint across engine geometry (a poisoned int8 payload
must never enter an fp pool). Disaggregated prefill/decode is greedy-
identical to monolithic and survives a mid-handoff transfer fault by
local recompute (never wrong tokens), and prefix-affinity routing is a
bounded tie-break that load always overrides.
"""

import dataclasses
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import faults
from ray_tpu.models import CONFIGS, init_params
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.serve import kv_transfer as kt
from ray_tpu.serve.batching import ContinuousBatcher

TINY = dataclasses.replace(CONFIGS["tiny"], dtype=jnp.float32, max_seq_len=256)
ENGINE_KW = dict(max_batch_size=2, seed=0, block_tokens=16, num_blocks=64,
                 model_id="m")
PROMPT = list(range(7, 107))  # 100 tokens -> 6 exportable 16-token blocks


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(jax.random.PRNGKey(0), TINY)


def _mk(params, **over):
    kw = dict(ENGINE_KW)
    kw.update(over)
    return PagedDecodeEngine(TINY, params, **kw)


def _gen(eng, slot, prompt, n):
    tok, done = eng.admit(slot, {"tokens": prompt, "max_new_tokens": n})
    out = [tok]
    while not done:
        tok, done = eng.step([slot])[slot]
        out.append(tok)
    eng.release(slot)
    return out


# -------------------------------------------- key determinism / poisoning


def test_transfer_keys_deterministic_across_processes(tiny_params):
    """Two engines in SEPARATE processes, same fixture weights/geometry ->
    byte-identical content-addressed key chains."""
    eng = _mk(tiny_params)
    local = eng.transfer_keys(np.asarray(PROMPT, np.int32), 6)
    script = (
        "import dataclasses, jax, jax.numpy as jnp, numpy as np\n"
        "from ray_tpu.models import CONFIGS, init_params\n"
        "from ray_tpu.models.kv_paging import PagedDecodeEngine\n"
        "cfg = dataclasses.replace(CONFIGS['tiny'], dtype=jnp.float32,"
        " max_seq_len=256)\n"
        "params = init_params(jax.random.PRNGKey(0), cfg)\n"
        "eng = PagedDecodeEngine(cfg, params, max_batch_size=2, seed=0,"
        " block_tokens=16, num_blocks=64, model_id='m')\n"
        "keys = eng.transfer_keys(np.arange(7, 107, dtype=np.int32), 6)\n"
        "print(','.join(k.hex() for k in keys))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env=env, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr
    remote = proc.stdout.strip().splitlines()[-1].split(",")
    assert remote == [k.hex() for k in local]


def test_transfer_keys_disjoint_across_geometry(tiny_params):
    """Different kv dtype, block_tokens, or model identity -> DISJOINT key
    spaces: a key can never address a block from another pool layout."""
    toks = np.asarray(PROMPT, np.int32)
    base = set(_mk(tiny_params).transfer_keys(toks, 4))
    int8 = set(_mk(tiny_params, kv_cache_dtype="int8").transfer_keys(toks, 4))
    bt32 = set(_mk(tiny_params, block_tokens=32).transfer_keys(toks, 2))
    other = set(_mk(tiny_params, model_id="m2").transfer_keys(toks, 4))
    assert not (base & int8) and not (base & bt32) and not (base & other)


def test_poison_int8_block_never_imports_into_fp_pool(tiny_params):
    """An int8 export presented to an fp-pool engine is REJECTED before
    any byte reaches the pool (sig mismatch), and counted."""
    src = _mk(tiny_params, kv_cache_dtype="int8")
    _gen(src, 0, PROMPT, 4)
    payload = src.export_prefix(np.asarray(PROMPT, np.int32))
    assert payload is not None and "k_scale" in payload["blocks"]
    dst = _mk(tiny_params)  # fp pool
    assert dst.import_prefix(payload) == 0
    assert dst.kv_import_rejects == 1 and dst.kv_blocks_imported == 0
    # tampered chain keys must also reject, even with a matching sig
    ok = src.export_prefix(np.asarray(PROMPT, np.int32))
    ok["keys"] = list(ok["keys"])
    ok["keys"][-1] = b"\x00" * len(ok["keys"][-1])
    dst8 = _mk(tiny_params, kv_cache_dtype="int8")
    assert dst8.import_prefix(ok) == 0 and dst8.kv_import_rejects == 1


# ------------------------------------------------ round-trip token parity


@pytest.mark.parametrize(
    "kv_dtype,attn",
    [("fp", "gather"), ("fp", "fused"),
     ("int8", "gather"), ("int8", "fused")],
    ids=["fp-gather", "fp-fusedxla", "int8-gather", "int8-fusedxla"],
)
def test_import_resumes_token_identical(tiny_params, kv_dtype, attn):
    """Warm A -> pack -> unpack -> import into B: B's continuation is
    token-identical to cold monolithic C, and B's counters prove the
    prefix arrived over the wire instead of being recomputed."""
    over = dict(kv_cache_dtype=kv_dtype, attention_impl=attn)
    a, b, c = (_mk(tiny_params, **over) for _ in range(3))
    out_a = _gen(a, 0, PROMPT, 8)
    payload = a.export_prefix(np.asarray(PROMPT, np.int32))
    assert payload is not None and a.kv_exports == 1
    meta, buf = kt.pack_payload(payload)
    imported = b.import_prefix(kt.unpack_payload(meta, buf))
    assert imported == 96  # 6 blocks * 16 tokens
    out_b = _gen(b, 0, PROMPT, 8)
    out_c = _gen(c, 0, PROMPT, 8)
    assert out_a == out_b == out_c
    assert b.kv_blocks_imported == 6 and b.kv_tokens_imported == 96
    # B prefilled only the 4-token tail past the imported chain
    assert b.stats()["prefill_tokens"] < c.stats()["prefill_tokens"]


def test_unpack_rejects_truncation_and_corruption(tiny_params):
    eng = _mk(tiny_params)
    _gen(eng, 0, PROMPT, 4)
    meta, buf = kt.pack_payload(
        eng.export_prefix(np.asarray(PROMPT, np.int32))
    )
    with pytest.raises(kt.KVTransferError):
        kt.unpack_payload(meta, np.asarray(buf)[: buf.size // 2])
    bad = np.array(buf, copy=True)
    bad[0] ^= 0xFF
    with pytest.raises(kt.KVTransferError):
        kt.unpack_payload(meta, bad)
    # the round trip itself is lossless
    rt = kt.unpack_payload(meta, buf)
    for name, arr in rt["blocks"].items():
        np.testing.assert_array_equal(arr, payload_leaf := np.asarray(
            eng.export_prefix(np.asarray(PROMPT, np.int32))["blocks"][name]
        ))
        assert arr.dtype == payload_leaf.dtype


# ---------------------------------------------------- hints and the digest


def test_prefix_hint_window_and_request_shapes():
    long_a = list(range(200))
    long_b = list(range(200))
    long_b[-1] = 7  # differs past the hint window only
    assert kt.prefix_hint(long_a) == kt.prefix_hint(long_b)
    assert kt.prefix_hint(long_a, hint_tokens=200) != kt.prefix_hint(
        long_b, hint_tokens=200
    )
    assert kt.prefix_hint([]) == ""
    h = kt.prefix_hint(long_a)
    assert kt.request_hint((), {"tokens": long_a}) == h
    assert kt.request_hint(({"tokens": long_a},), {}) == h  # proxy body
    assert kt.request_hint(({"prompt": long_a},), {}) == h
    assert kt.request_hint(("not-a-request",), {}) == ""


def test_manager_digest_is_bounded_lru(tiny_params):
    eng = _mk(tiny_params)
    batcher = ContinuousBatcher(eng)
    try:
        m = kt.KVTransferManager(batcher, digest_size=2)
        for start in (0, 1000, 2000):
            prompt = list(range(start, start + 64))
            list(batcher.submit(tokens=prompt, max_new_tokens=2))
            m.note_prompt(prompt)
        d = m.digest()
        assert len(d) == 2  # oldest hint evicted
        assert all(depth >= 1 for depth in d.values())
        assert kt.prefix_hint(list(range(64))) not in d
    finally:
        batcher.close()


# ------------------------------------------- replica-level monotonic stats


def test_replica_prefill_tokens_monotonic_across_batcher_replacement():
    """Satellite (f): Replica.stats' prefill_tokens must never go
    backwards when the callable swaps its batcher (engine rebuild)."""
    from ray_tpu.serve.replica import Replica

    class FakeBatcher:
        _serve_drainable = True

        def __init__(self, prefill):
            self._s = {"max_batch_size": 2, "active": 0, "queued": 0,
                       "prefill_tokens": prefill}

        def stats(self):
            return dict(self._s)

    class Holder:
        def __init__(self):
            self.batcher = FakeBatcher(100)

        def __call__(self):
            return None

    r = Replica("dep", Holder, (), {})
    assert r.stats()["prefill_tokens"] == 100
    r.callable.batcher._s["prefill_tokens"] = 150
    assert r.stats()["prefill_tokens"] == 150
    r.callable.batcher = FakeBatcher(10)  # replacement resets its counter
    assert r.stats()["prefill_tokens"] == 160  # 150 retained + 10 fresh
    r.callable.batcher._s["prefill_tokens"] = 30
    assert r.stats()["prefill_tokens"] == 180


# ------------------------------------------------- affinity routing (unit)


def test_prefix_affinity_is_a_bounded_tie_break(monkeypatch):
    """The hint steers routing toward the advertised replica ONLY while
    its queue stays within max_skew of the two-choices floor — load wins
    when depths diverge, so a hot prefix cannot pin a replica."""
    from ray_tpu.serve import long_poll
    from ray_tpu.serve.handle import DeploymentHandle

    class R:
        def __init__(self, aid):
            self._actor_id = aid

    class FakeWatcher:
        digest = {"hintX": ("aid-2", 6)}

    monkeypatch.setattr(long_poll, "get_prefix_watcher",
                        lambda name: FakeWatcher())
    h = DeploymentHandle("dep")
    h._replicas = [R("aid-0"), R("aid-1"), R("aid-2")]
    h._counts = {0: 0, 1: 0, 2: 0}
    for _ in range(20):
        assert h._pick_replica("hintX") == 2
    # unknown hint: plain two-choices (never crashes, stays in range)
    assert h._pick_replica("nope") in (0, 1, 2)
    # the advertised replica is overloaded beyond the skew cap: load wins
    h._counts = {0: 0, 1: 0, 2: 50}
    for _ in range(20):
        assert h._pick_replica("hintX") != 2
    # advertised replica left the set: hint is ignored
    FakeWatcher.digest = {"hintX": ("gone", 6)}
    assert h._pick_replica("hintX") in (0, 1, 2)


# --------------------------------------------------------- serve e2e (ray)


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def _replicas(name):
    ctl = ray_tpu.get_actor(serve.CONTROLLER_NAME)
    return ray_tpu.get(ctl.get_replicas.remote(name), timeout=30)


def _reference_tokens(kv_dtype="fp", attn="gather", n=8):
    """Cold monolithic greedy output for PROMPT with the e2e weights."""
    params = init_params(jax.random.PRNGKey(0), TINY)
    eng = _mk(params, kv_cache_dtype=kv_dtype, attention_impl=attn)
    return _gen(eng, 0, PROMPT, n)


@pytest.mark.parametrize(
    "kv_dtype,attn", [("fp", "gather"), ("int8", "fused")],
    ids=["fp-gather", "int8-fusedxla"],
)
def test_cross_replica_prefix_hit_e2e(serve_cluster, kv_dtype, attn):
    """The acceptance path: replica A computes a prompt, replica B serves
    the same prompt by IMPORTING A's blocks over the bulk plane — B's
    prefill_tokens shows the prefix was not recomputed, and B's output is
    token-identical to a cold monolithic engine."""
    ek = dict(ENGINE_KW, kv_cache_dtype=kv_dtype, attention_impl=attn)
    Dep = serve.deployment(name="kvgen", num_replicas=2)(
        serve.KVGenerationServer
    )
    serve.run(
        Dep.bind(TINY, engine_kwargs=ek, deployment="kvgen"), name="kvgen"
    )
    reps = _replicas("kvgen")
    assert len(reps) == 2
    out_a = ray_tpu.get(reps[0].handle_request.remote(
        "generate", (PROMPT,), {"max_new_tokens": 8}), timeout=240)
    out_b = ray_tpu.get(reps[1].handle_request.remote(
        "generate", (PROMPT,), {"max_new_tokens": 8}), timeout=240)
    expected = _reference_tokens(kv_dtype, attn)
    assert out_a["tokens"] == out_b["tokens"] == expected
    sa = ray_tpu.get(reps[0].stats.remote(), timeout=30)
    sb = ray_tpu.get(reps[1].stats.remote(), timeout=30)
    # B imported the chain instead of recomputing it: 6 blocks in, only
    # the 4-token tail prefilled (A prefilled all 100)
    assert sb["kv_blocks_imported"] == 6
    assert sb["prefill_tokens"] < sa["prefill_tokens"]
    assert sb["kv_transfer_hits"] == 1 and sb["kv_transfer_pulls"] == 1
    assert sa["kv_blocks_exported"] == 6
    # wire accounting (satellite b): bytes by direction on both ends
    assert sb["kv_transfer_bytes_by_direction"]["import"] > 0
    assert sa["kv_transfer_bytes_by_direction"]["export"] > 0
    assert sb["prefix_remote_hit_rate"] == 1.0
    # both replicas advertise the chain for the affinity digest
    hint = kt.prefix_hint(PROMPT)
    assert sb["prefix_digest"].get(hint, 0) >= 6


def test_prefix_affinity_digest_harvest_e2e():
    """Layer-2 end to end: with serve_prefix_affinity on, the controller
    harvests replicas' hint->depth digests on its heartbeat, keeps them
    keyed by replica actor id, and publishes over serve:prefix:<dep> —
    the handle-side PrefixWatcher receives the snapshot."""
    os.environ["RAY_TPU_SERVE_PREFIX_AFFINITY"] = "1"
    try:
        ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
        Dep = serve.deployment(name="kvaff", num_replicas=1)(
            serve.KVGenerationServer
        )
        h = serve.run(
            Dep.bind(TINY, engine_kwargs=dict(ENGINE_KW), deployment="kvaff"),
            name="kvaff",
        )
        out = h.generate.remote(PROMPT, max_new_tokens=4).result(
            timeout_s=240
        )
        assert out["tokens"] == _reference_tokens(n=4)
        hint = kt.prefix_hint(PROMPT)
        ctl = ray_tpu.get_actor(serve.CONTROLLER_NAME)
        from ray_tpu.serve.long_poll import get_prefix_watcher

        w = get_prefix_watcher("kvaff")
        deadline = time.time() + 30  # harvest rides the ~5s heartbeat
        digest = {}
        while time.time() < deadline and hint not in digest:
            digest = ray_tpu.get(
                ctl.get_prefix_digest.remote("kvaff"), timeout=10
            )
            time.sleep(0.5)
        assert hint in digest, "controller never harvested the digest"
        aid, depth = digest[hint]
        assert depth >= 6
        assert aid == getattr(_replicas("kvaff")[0], "_actor_id", None)
        while time.time() < deadline and hint not in w.digest:
            time.sleep(0.25)
        assert w.digest.get(hint) == (aid, depth)
    finally:
        os.environ.pop("RAY_TPU_SERVE_PREFIX_AFFINITY", None)
        serve.shutdown()
        ray_tpu.shutdown()


def test_disaggregated_prefill_decode_greedy_parity(serve_cluster):
    """serve_disaggregate mode: prefill pool runs the prompt to
    completion, hands blocks to the decode pool over the transfer path,
    and decode resumes token-for-token identically to monolithic."""
    h = serve.deploy_disaggregated("dis", TINY, engine_kwargs=dict(ENGINE_KW))
    out = h.generate.remote(PROMPT, max_new_tokens=8).result(timeout_s=240)
    assert out["tokens"] == _reference_tokens()
    sd = ray_tpu.get(_replicas("dis")[0].stats.remote(), timeout=30)
    sp = ray_tpu.get(_replicas("dis-prefill")[0].stats.remote(), timeout=30)
    assert sd["kv_blocks_imported"] == 6 and sd["kv_transfer_hits"] == 1
    assert sp["kv_blocks_exported"] == 6
    # decode prefilled only the tail; prefill did the heavy 100 tokens
    assert sd["prefill_tokens"] < sp["prefill_tokens"]


def test_disaggregated_survives_mid_handoff_transfer_fault():
    """Satellite (a): kv_transfer_drop kills the first handoff mid-flight
    (truncated payload). Decode detects it (CRC/length), falls back to
    LOCAL recompute — tokens still exactly right — and counts the
    fallback; the NEXT handoff succeeds."""
    os.environ["RAY_TPU_FAULTS"] = "kv_transfer_drop:1"
    try:
        ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
        h = serve.deploy_disaggregated(
            "disx", TINY, engine_kwargs=dict(ENGINE_KW)
        )
        out = h.generate.remote(PROMPT, max_new_tokens=8).result(
            timeout_s=240
        )
        assert out["tokens"] == _reference_tokens()  # NEVER wrong tokens
        sd = ray_tpu.get(_replicas("disx")[0].stats.remote(), timeout=30)
        assert sd["kv_transfer_fallbacks_total"] >= 1
        assert sd["kv_transfer_hits"] == 0
        # second request: the directive was one-shot, the handoff lands
        prompt2 = list(range(300, 400))
        out2 = h.generate.remote(prompt2, max_new_tokens=8).result(
            timeout_s=240
        )
        params = init_params(jax.random.PRNGKey(0), TINY)
        assert out2["tokens"] == _gen(_mk(params), 0, prompt2, 8)
        sd2 = ray_tpu.get(_replicas("disx")[0].stats.remote(), timeout=30)
        assert sd2["kv_transfer_hits"] == 1
    finally:
        os.environ.pop("RAY_TPU_FAULTS", None)
        serve.shutdown()
        ray_tpu.shutdown()


def test_in_process_transfer_drop_falls_back(tiny_params, monkeypatch):
    """The same fault at manager level, no cluster: armed directive
    truncates the packed buffer; the importer's unpack raises and the
    puller falls back (counter bumped), tokens unaffected."""
    faults.arm("kv_transfer_drop:1")
    try:
        assert faults.kv_transfer_action() == "drop"  # one-shot nth=1
        assert faults.kv_transfer_action() is None
    finally:
        faults.disarm()
