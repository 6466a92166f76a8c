"""MoE dispatch correctness + FLOPs scaling (VERDICT r1 item 6: per-step
FLOPs must scale with top_k, not n_experts)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import CONFIGS, init_params, make_forward
from ray_tpu.models.transformer import TransformerConfig


def _cfg(n_experts, impl, **kw):
    return dataclasses.replace(
        CONFIGS["tiny_moe"], n_experts=n_experts, moe_impl=impl, **kw
    )


def test_dispatch_matches_dense_oracle():
    """With generous capacity (no drops) the capacity-based dispatch equals
    the dense every-expert-computes-every-token oracle."""
    cfg_d = _cfg(4, "dense", dtype=jnp.float32)
    cfg_s = _cfg(4, "dispatch", moe_capacity_factor=4.0, dtype=jnp.float32)  # no drops
    params = init_params(jax.random.PRNGKey(0), cfg_d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg_d.vocab_size)
    out_d = make_forward(cfg_d)(params, tokens)
    out_s = make_forward(cfg_s)(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out_d, np.float32), np.asarray(out_s, np.float32),
        rtol=1e-4, atol=1e-4,
    )


@pytest.mark.parametrize("renormalize", [True, False])
def test_dropless_matches_dense_oracle(renormalize):
    """No capacity set: every routed pair is computed, so the sorted
    grouped-matmul path equals the oracle with nothing chosen to fit — with
    the top-k weights renormalised and as the softmax gave them."""
    kw = dict(dtype=jnp.float32, moe_renormalize=renormalize)
    cfg_d = _cfg(4, "dense", **kw)
    cfg_s = _cfg(4, "dispatch", moe_capacity_factor=None, **kw)
    params = init_params(jax.random.PRNGKey(0), cfg_d)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg_d.vocab_size)
    np.testing.assert_allclose(
        np.asarray(make_forward(cfg_d)(params, tokens), np.float32),
        np.asarray(make_forward(cfg_s)(params, tokens), np.float32),
        rtol=1e-4, atol=1e-4,
    )


def test_dropless_gradients_reach_router_and_experts():
    from ray_tpu.models.transformer import make_loss_fn

    cfg = _cfg(4, "dispatch", moe_capacity_factor=None, dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    grads = jax.grad(make_loss_fn(cfg))(params, {"tokens": tokens})
    for name in ("router", "w_gate", "w_up", "w_down"):
        g = np.asarray(grads["layers"][name])
        assert np.isfinite(g).all() and np.abs(g).max() > 0, name


def test_dispatch_flops_scale_with_top_k_not_n_experts():
    """Doubling n_experts at fixed top_k must NOT double MLP FLOPs."""

    def compiled_flops(n_experts, impl):
        cfg = _cfg(n_experts, impl)
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((4, 32), jnp.int32)
        fwd = jax.jit(make_forward(cfg))
        cost = fwd.lower(params, tokens).compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost["flops"])

    f4 = compiled_flops(4, "dispatch")
    f16 = compiled_flops(16, "dispatch")
    d4 = compiled_flops(4, "dense")
    d16 = compiled_flops(16, "dense")
    # dense dispatch scales ~linearly with experts; capacity dispatch must
    # stay roughly flat (router matmul grows negligibly)
    assert d16 / d4 > 2.0, (d4, d16)
    assert f16 / f4 < 1.5, (f4, f16)


def test_dispatch_trains():
    """Gradients flow through router + experts and loss decreases-ish."""
    from ray_tpu.models.transformer import make_loss_fn
    import optax

    cfg = _cfg(4, "dispatch", top_k=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    loss_fn = make_loss_fn(cfg)
    opt = optax.adam(1e-2)
    state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "mask": jnp.ones_like(tokens)}

    @jax.jit
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        upd, state = opt.update(grads, state)
        return optax.apply_updates(params, upd), state, loss

    losses = []
    for _ in range(8):
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    # router gradient is nonzero
    grads = jax.grad(loss_fn)(params, batch)
    assert float(jnp.abs(grads["layers"]["router"]).sum()) > 0


def test_dispatch_multidevice_ep_sharding(fresh_compile):
    """The dispatch path compiles and runs under an ep-sharded mesh (GSPMD
    inserts the all-to-alls from the sharding constraints)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh
    from ray_tpu.train.step import default_optimizer, make_sharded_init, make_train_step

    cfg = dataclasses.replace(
        CONFIGS["tiny_moe"], dtype=jnp.float32, moe_impl="dispatch", top_k=2
    )
    mesh = build_mesh(MeshSpec(ep=4, dp=2))
    rules = PRESET_RULES["full"].with_overrides(seq=None, kv_seq=None)
    opt = default_optimizer(lr=1e-3, warmup=1)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh, rules, opt, shardings)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 33)), jnp.int32),
        "mask": jnp.ones((8, 33), jnp.int32),
    }
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
