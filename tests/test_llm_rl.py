"""Generation-based RL (rl/llm) + live weight hot-swap (serve/weight_swap).

Acceptance (ISSUE 20):
  - learning gate: PPO and GRPO mean reward improves in trend on a toy
    token task, pinned seeds;
  - logprob parity: the engine's streamed behavior logprobs match a dense
    teacher-forced re-forward on the sampled ids (gather and fused
    attention);
  - swap gate: >= 4 in-flight SSE streams survive a live weight swap — no
    stream drops, the post-swap continuation is greedy-identical to a
    fresh engine on the new weights (recompute semantics), and
    serve_weight_version advances MID-stream;
  - chaos: a truncated weight pull (weight_swap_drop) leaves the replica
    serving the OLD version intact, counted in weight_swap_fallbacks_total;
  - carried item: hot-swap refreshes the speculative drafter —
    swap-then-speculate stays greedy-identical to a fresh engine.
"""

import dataclasses
import json
import socket
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu._private import faults
from ray_tpu.models import CONFIGS, init_params
from ray_tpu.models.kv_paging import PagedDecodeEngine
from ray_tpu.models.speculative import NGramDrafter, ReplayDrafter
from ray_tpu.rl.llm import (
    GenerationRLTrainer,
    LLMRolloutWorker,
    gae_advantages,
    grpo_advantages,
)
from ray_tpu.serve.batching import ContinuousBatcher
from ray_tpu.util.metrics import local_counter_by_tag, rl_reward_mean_gauge


def _cfg():
    import jax.numpy as jnp

    # fp32 end to end: the parity and identity assertions compare the
    # decode path against a dense re-forward bit-for-bit-ish
    return dataclasses.replace(CONFIGS["tiny"], dtype=jnp.float32)


def _params(seed):
    import jax

    return init_params(jax.random.PRNGKey(seed), _cfg())


def _greedy(params, prompt, n, **kw):
    """Fresh-engine greedy reference continuation."""
    eng = PagedDecodeEngine(
        _cfg(), params, temperature=0.0, num_blocks=64, telemetry=False, **kw
    )
    tok, done = eng.admit(
        0, {"tokens": np.asarray(prompt, np.int32), "max_new_tokens": n}
    )
    out = [tok] if tok is not None else []
    while not done:
        res = eng.step([0])
        if 0 in res:
            items, done = res[0]
            out += items if isinstance(items, list) else [items]
    return out


def _dense_reward(prompt, resp):
    """Toy token task: fraction of response tokens in the low half of the
    vocab — dense signal, learnable by pure policy gradient."""
    r = np.asarray(resp)
    return float((r < 128).mean()) if r.size else 0.0


# ----------------------------------------------------------- logprob parity


@pytest.mark.parametrize("attn", ["gather", "fused"])
def test_engine_logprobs_match_dense_reforward(attn):
    """The (token, logprob) pairs the engine streams are the logprobs of
    the ACTUAL sampling distribution: a dense teacher-forced re-forward
    with identical sampler semantics (fp32, vocab-pad mask, temperature)
    reproduces them on the sampled ids."""
    from ray_tpu.rl.llm import LLMLearner

    cfg = _cfg()
    params = _params(0)
    worker = LLMRolloutWorker(
        cfg, params, _dense_reward, group_size=2, max_new_tokens=6,
        temperature=1.0, seed=0,
        engine_kwargs={"num_blocks": 64, "attention_impl": attn},
    )
    try:
        batch = worker.rollout([[11, 12, 13], [21, 22, 23, 24]])
    finally:
        worker.close()
    learner = LLMLearner(cfg, params, algo="grpo", temperature=1.0)
    lp = learner.policy_logp(batch["tokens"])
    m = batch["loss_mask"] > 0
    assert m.any()
    err = np.abs(lp[m] - batch["behavior_logp"][m]).max()
    assert err < 1e-4, f"behavior vs re-forward logprob drift {err}"
    # behavior logprobs are real probabilities of the sampled ids
    assert (batch["behavior_logp"][m] <= 0).all()


# ------------------------------------------------------------ learning gate


def test_ppo_reward_improves():
    tr = GenerationRLTrainer(
        _cfg(), _dense_reward, [[11, 12, 13], [21, 22, 23]], algo="ppo",
        seed=1, group_size=2, max_new_tokens=6, lr=2e-2,
        engine_kwargs={"num_blocks": 128},
    )
    try:
        rewards = [tr.step()["reward_mean"] for _ in range(8)]
    finally:
        tr.close()
    early = float(np.mean(rewards[:3]))
    late = float(np.mean(rewards[-3:]))
    assert late > early + 0.1, f"PPO did not learn: {rewards}"
    assert max(rewards) == max(rewards[3:]), rewards  # best comes late
    # on-policy weight sync ran every iteration
    assert tr.worker.weight_version == 8


def test_grpo_reward_improves():
    tr = GenerationRLTrainer(
        _cfg(), _dense_reward, [[11, 12, 13], [21, 22, 23]], algo="grpo",
        seed=0, group_size=4, max_new_tokens=6, lr=2e-2,
        engine_kwargs={"num_blocks": 128},
    )
    try:
        rewards = [tr.step()["reward_mean"] for _ in range(8)]
    finally:
        tr.close()
    early = float(np.mean(rewards[:3]))
    late = float(np.mean(rewards[-3:]))
    assert late > early + 0.1, f"GRPO did not learn: {rewards}"
    # rl metrics satellite: the push-registry gauge carries the last
    # batch's mean reward under the worker's deployment/replica tags
    vals = rl_reward_mean_gauge()._values
    assert any(
        dict(k).get("deployment") == "rl_llm" for k in vals
    ), vals
    by_dep = local_counter_by_tag("rl_rollout_tokens_total", "deployment")
    assert by_dep.get("rl_llm", 0) >= 8 * 2 * 4 * 6  # iters*prompts*group*len


# --------------------------------------------------------------- advantages


def test_grpo_advantages_group_relative():
    rewards = np.array([1.0, 0.0, 3.0, 3.0], np.float32)
    group = np.array([0, 0, 1, 1])
    mask = np.ones((4, 3), np.float32)
    mask[0, 2] = 0.0
    adv = grpo_advantages(rewards, group, mask)
    # group 0: normalized to +/-1; group 1: zero variance -> zero adv
    assert adv[0, 0] > 0.9 and adv[1, 0] < -0.9
    assert adv[0, 2] == 0.0  # masked position carries nothing
    assert np.allclose(adv[2:], 0.0)
    # singleton group has no peers: zero advantage by construction
    solo = grpo_advantages(np.array([5.0]), np.array([0]), np.ones((1, 3)))
    assert np.allclose(solo, 0.0)


def test_gae_terminal_reward_and_masking():
    # one sequence, 4 positions, response on t=1..2, zero critic
    rewards = np.array([2.0], np.float32)
    values = np.zeros((1, 4), np.float32)
    mask = np.array([[0.0, 1.0, 1.0, 0.0]], np.float32)
    adv, ret = gae_advantages(rewards, values, mask, gamma=1.0, lam=1.0)
    # terminal (t=2) carries the full reward; t=1 bootstraps through it
    assert adv[0, 2] == pytest.approx(2.0)
    assert adv[0, 1] == pytest.approx(2.0)  # gamma=lam=1: discounted sum
    assert adv[0, 0] == 0.0 and adv[0, 3] == 0.0
    assert ret[0, 2] == pytest.approx(2.0)  # value 0 -> return == advantage


# ------------------------------------------------- swap semantics (no ray)


def test_set_params_recompute_semantics_midstream():
    """Direct engine: a swap mid-generation preempts the slot; its
    readmitted continuation is greedy-identical to a FRESH engine on the
    new weights fed prompt+generated-so-far — the recompute contract the
    serving swap rides."""
    p0, p1 = _params(0), _params(1)
    prompt = list(range(1, 9))
    eng = PagedDecodeEngine(
        _cfg(), p0, temperature=0.0, num_blocks=64, telemetry=False
    )
    tok, done = eng.admit(
        0, {"tokens": np.asarray(prompt, np.int32), "max_new_tokens": 12}
    )
    seq = [tok]
    for _ in range(4):
        items, done = eng.step([0])[0]
        seq += items if isinstance(items, list) else [items]
    assert not done
    k = len(seq)
    old_sig = eng.transfer_sig
    assert eng.set_params(p1) == 1
    assert eng.weight_version == 1 and eng.weight_swaps == 1
    assert eng.transfer_sig != old_sig  # stale chain keys disjoint
    assert len(eng.prefix_cache) == 0  # old-weight KV flushed
    # the batcher's readmit path: full history prefills under NEW weights
    hist = np.asarray(prompt + seq, np.int32)
    tok2, done = eng.admit(0, {"tokens": hist, "max_new_tokens": 12 - k})
    post = [tok2] if tok2 is not None else []
    while not done:
        res = eng.step([0])
        if 0 in res:
            items, done = res[0]
            post += items if isinstance(items, list) else [items]
    assert seq == _greedy(p0, prompt, 12)[:k]
    assert post == _greedy(p1, prompt + seq, 12 - k)


def test_swap_refreshes_drafter_greedy_identity():
    """Carried item: hot-swap rebuilds the drafter — swap-then-speculate
    emits exactly what a fresh engine on the new weights (same drafter
    config) emits, and a ReplayDrafter's old-weight recordings are
    dropped rather than burned on doomed verify spans."""
    p0, p1 = _params(0), _params(1)
    prompt = list(range(1, 9))
    spec = {"speculative_k": 3}
    eng = PagedDecodeEngine(
        _cfg(), p0, temperature=0.0, num_blocks=64, telemetry=False,
        drafter=NGramDrafter(), **spec,
    )
    tok, done = eng.admit(
        0, {"tokens": np.asarray(prompt, np.int32), "max_new_tokens": 8}
    )
    while not done:
        items, done = eng.step([0])[0]
    eng.release(0)
    eng.set_params(p1)
    tok, done = eng.admit(
        0, {"tokens": np.asarray(prompt, np.int32), "max_new_tokens": 8}
    )
    out = [tok]
    while not done:
        items, done = eng.step([0])[0]
        out += items if isinstance(items, list) else [items]
    assert out == _greedy(p1, prompt, 8, drafter=NGramDrafter(), **spec)

    replay = ReplayDrafter([[1, 2, 3, 4, 5]])
    eng2 = PagedDecodeEngine(
        _cfg(), p0, temperature=0.0, num_blocks=64, telemetry=False,
        drafter=replay, **spec,
    )
    eng2.set_params(p1)
    assert replay.sequences == []  # old-weight recordings dropped


# --------------------------------------------------------- weight plane e2e


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_weight_publish_pull_swap_identity(serve_cluster):
    """Publisher -> bulk-plane leaves (chunked) -> subscriber pull ->
    verified swap: the subscribing engine then generates exactly what a
    fresh engine on the published weights generates."""
    from ray_tpu.serve.weight_swap import WeightPublisher, WeightSubscriber

    p0, p1 = _params(0), _params(1)
    eng = PagedDecodeEngine(
        _cfg(), p0, temperature=0.0, num_blocks=64, telemetry=False
    )
    bat = ContinuousBatcher(eng, telemetry=False)
    try:
        sub = WeightSubscriber(eng, "swap_t", batcher=bat)
        pub = WeightPublisher("swap_t", chunk_bytes=8192)  # multi-chunk leaves
        assert pub.publish(p1) == 1
        assert sub.poll_once(timeout=10.0)
        assert eng.weight_version == 1
        assert sub.bytes_pulled == pub.published_bytes > 0
        prompt = np.arange(1, 9, dtype=np.int32)
        s = bat.submit(tokens=prompt, max_new_tokens=5)
        toks = []
        while True:
            items, done = s.next_batch(wait_s=10.0)
            toks += items
            if done:
                break
        assert toks == _greedy(p1, prompt, 5)
        # stale manifests never re-apply
        assert not sub.apply({"version": 1})
    finally:
        bat.close()


def test_weight_swap_drop_leaves_old_version_serving(serve_cluster):
    """Chaos satellite: weight_swap_drop truncates the pull -> leaf
    verification fails -> the swap aborts WHOLE. The replica keeps
    serving version 0 (old-weights greedy identity proves the tree was
    never half-swapped) and the fallback is counted; the retry after the
    fault clears adopts cleanly."""
    from ray_tpu.serve.weight_swap import WeightPublisher, WeightSubscriber

    p0, p1 = _params(0), _params(1)
    eng = PagedDecodeEngine(
        _cfg(), p0, temperature=0.0, num_blocks=64, telemetry=False
    )
    bat = ContinuousBatcher(eng, telemetry=False)
    before = local_counter_by_tag(
        "weight_swap_fallbacks_total", "none"
    ).get("untagged", 0)
    try:
        sub = WeightSubscriber(eng, "swap_chaos", batcher=bat)
        pub = WeightPublisher("swap_chaos")
        faults.arm("weight_swap_drop:1")
        try:
            pub.publish(p1)
            assert not sub.poll_once(timeout=10.0)  # fallback, not a swap
        finally:
            faults.disarm()
        assert sub.fallbacks == 1 and sub.swaps == 0
        assert eng.weight_version == 0 and eng.weight_swaps == 0
        after = local_counter_by_tag(
            "weight_swap_fallbacks_total", "none"
        ).get("untagged", 0)
        assert after == before + 1
        # still serving the OLD weights, correctly
        prompt = np.arange(1, 9, dtype=np.int32)
        s = bat.submit(tokens=prompt, max_new_tokens=4)
        toks = []
        while True:
            items, done = s.next_batch(wait_s=10.0)
            toks += items
            if done:
                break
        assert toks == _greedy(p0, prompt, 4)
        # fault cleared: the next published version adopts
        pub.publish(p1)
        assert sub.poll_once(timeout=10.0)
        assert eng.weight_version == 2 and sub.fallbacks == 1
    finally:
        bat.close()


def _sse_client(host, port, body_obj, out, key):
    s = socket.create_connection((host, int(port)), timeout=120)
    body = json.dumps(body_obj).encode()
    s.sendall(
        b"POST /generate HTTP/1.1\r\nHost: x\r\n"
        b"Content-Type: application/json\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body
    )
    buf = b""
    while True:
        data = s.recv(65536)
        if not data:
            break
        buf += data
        if b"0\r\n\r\n" in buf:
            break
    s.close()
    out[key] = buf


def test_sse_streams_survive_live_weight_swap(serve_cluster):
    """The swap gate: 4 in-flight SSE streams ride out a live weight swap
    — none drops, each delivers its full token budget, and the replica's
    serve_weight_version (engine + telemetry gauge) advances while the
    streams are demonstrably mid-flight."""

    @serve.deployment
    class Gen:
        def __init__(self):
            import dataclasses as dc

            import jax
            import jax.numpy as jnp

            from ray_tpu.models import CONFIGS, init_params
            from ray_tpu.models.kv_paging import PagedDecodeEngine
            from ray_tpu.serve.batching import ContinuousBatcher
            from ray_tpu.serve.weight_swap import WeightSubscriber

            cfg = dc.replace(CONFIGS["tiny"], dtype=jnp.float32)
            self.engine = PagedDecodeEngine(
                cfg, init_params(jax.random.PRNGKey(0), cfg),
                temperature=0.0, max_batch_size=4, num_blocks=128, seed=0,
            )
            self.batcher = ContinuousBatcher(self.engine, max_batch_size=4)
            self.sub = WeightSubscriber(
                self.engine, "swap_sse", batcher=self.batcher
            ).start()

        def __call__(self, body):
            from ray_tpu import serve as _serve

            stream = self.batcher.submit(
                tokens=body["tokens"],
                max_new_tokens=body.get("max_new_tokens"),
            )
            return _serve.sse_stream(stream)

        def version(self):
            gauge_m = getattr(self.engine._tel, "weight_version", None)
            gauge = dict(gauge_m._values) if gauge_m is not None else {}
            return {
                "engine": self.engine.weight_version,
                "swaps": self.engine.weight_swaps,
                "gauge": max(gauge.values()) if gauge else -1,
            }

    h = serve.run(Gen.bind(), name="swap_sse", route_prefix="/generate")
    host, port = serve.proxy_address().split(":")

    n_tokens = 40
    outs = {}
    threads = [
        threading.Thread(
            target=_sse_client,
            args=(host, port,
                  {"tokens": [1 + i] * 6, "max_new_tokens": n_tokens},
                  outs, i),
        )
        for i in range(4)
    ]
    for t in threads:
        t.start()

    # publish the new version while all four streams are in flight
    from ray_tpu.serve.weight_swap import WeightPublisher

    time.sleep(0.3)  # streams demonstrably decoding
    assert not outs, "streams finished before the swap landed — no gate"
    WeightPublisher("swap_sse").publish(_params(1))
    # version advances MID-stream: observed before the clients complete
    deadline = time.time() + 60
    seen_mid_stream = False
    while time.time() < deadline:
        v = h.version.remote().result(timeout_s=10)
        if v["engine"] >= 1:
            seen_mid_stream = len(outs) < 4
            break
        time.sleep(0.02)
    for t in threads:
        t.join(timeout=120)

    assert set(outs) == {0, 1, 2, 3}, f"stream(s) dropped: {set(outs)}"
    for i, buf in outs.items():
        events = [ln for ln in buf.split(b"\n") if ln.startswith(b"data: ")]
        assert len(events) == n_tokens + 1, (i, len(events))
        assert events[-1] == b"data: [DONE]"
        assert b"event: cut" not in buf and b"event: error" not in buf
    v = h.version.remote().result(timeout_s=10)
    assert v["engine"] == 1 and v["swaps"] == 1
    assert v["gauge"] == 1.0  # serve_weight_version gauge advanced
    assert seen_mid_stream, "swap landed only after every stream finished"
