"""End-to-end sharded training on the 8-device CPU mesh: the permanent
integration test (SURVEY §7.1 M3 'minimum slice')."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import CONFIGS, init_params, make_forward, param_specs
from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh
from ray_tpu.train.step import (
    default_optimizer,
    make_sharded_init,
    make_train_step,
)
import dataclasses


def _batch(cfg, b=8, key=0):
    rng = np.random.default_rng(key)
    tokens = rng.integers(0, cfg.vocab_size, size=(b, 33), dtype=np.int32)
    return {"tokens": jnp.asarray(tokens), "mask": jnp.ones((b, 33), jnp.int32)}


def test_forward_shapes():
    cfg = CONFIGS["tiny"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    fwd = make_forward(cfg)
    logits = fwd(params, jnp.zeros((2, 16), jnp.int32))
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == cfg.dtype


def test_specs_match_params():
    for name in ("tiny", "tiny_moe"):
        cfg = CONFIGS[name]
        params = init_params(jax.random.PRNGKey(0), cfg)
        specs = param_specs(cfg)
        pleaves = jax.tree.structure(params)
        sleaves = jax.tree.structure(
            specs, is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x
            )
        )
        assert pleaves == sleaves
        # ndim of each param matches its logical spec length
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x
            )
        )
        for p, s in zip(flat_p, flat_s):
            assert p.ndim == len(s), (p.shape, s)


@pytest.mark.parametrize(
    "preset,mesh_spec",
    [
        ("dp", MeshSpec(dp=8)),
        ("fsdp", MeshSpec(dp=2, fsdp=4)),
        ("fsdp_tp", MeshSpec(dp=2, fsdp=2, tp=2)),
    ],
)
def test_train_loss_decreases(preset, mesh_spec):
    cfg = CONFIGS["tiny"]
    mesh = build_mesh(mesh_spec)
    rules = PRESET_RULES[preset]
    opt = default_optimizer(lr=1e-2, warmup=1)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh, rules, opt, shardings)
    batch = _batch(cfg)
    losses = []
    for i in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses
    assert int(state.step) == 10


def test_fsdp_actually_shards_params():
    cfg = CONFIGS["tiny"]
    mesh = build_mesh(MeshSpec(fsdp=8))
    rules = PRESET_RULES["fsdp"]
    opt = default_optimizer()
    init_fn, _ = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    wq = state.params["layers"]["wq"]
    # embed dim (axis 1) sharded over fsdp=8
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[1] == wq.shape[1] // 8


def test_ring_attention_training():
    cfg = dataclasses.replace(CONFIGS["tiny"], attention="ring")
    mesh = build_mesh(MeshSpec(dp=2, sp=4))
    rules = PRESET_RULES["fsdp_tp_sp"].with_overrides(embed=None, heads=None, mlp=None, vocab=None)
    opt = default_optimizer(lr=1e-2, warmup=1)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    step = make_train_step(cfg, mesh, rules, opt, shardings)
    batch = _batch(cfg)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_ring_equals_dense_loss():
    """Same params, same batch: ring-attention loss == dense loss."""
    from ray_tpu.models.transformer import make_loss_fn

    cfg_d = CONFIGS["tiny"]
    cfg_r = dataclasses.replace(cfg_d, attention="ring")
    mesh = build_mesh(MeshSpec(sp=8))
    rules = PRESET_RULES["fsdp_tp_sp"].with_overrides(embed=None, heads=None, mlp=None, vocab=None)
    params = init_params(jax.random.PRNGKey(0), cfg_d)
    batch = _batch(cfg_d, b=2)
    dense = make_loss_fn(cfg_d)(params, batch)
    ring = jax.jit(make_loss_fn(cfg_r, rules, mesh))(params, batch)
    np.testing.assert_allclose(float(dense), float(ring), rtol=2e-2)


def test_moe_training(fresh_compile):
    cfg = CONFIGS["tiny_moe"]
    mesh = build_mesh(MeshSpec(dp=2, ep=4))
    rules = PRESET_RULES["fsdp_tp_ep"].with_overrides(embed=None, heads=None, mlp=None, vocab=None)
    opt = default_optimizer(lr=1e-2, warmup=1)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    # experts sharded over ep
    wg = state.params["layers"]["w_gate"]
    assert wg.sharding.shard_shape(wg.shape)[1] == cfg.n_experts // 4
    step = make_train_step(cfg, mesh, rules, opt, shardings)
    batch = _batch(cfg)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


def test_flash_qkv_remat_matches_full():
    """flash_qkv (mlp gate/up recomputed in backward) must give the same
    loss/grads as the no-policy remat — it only changes WHAT is saved."""
    cfg_full = dataclasses.replace(CONFIGS["tiny"], remat_policy="full")
    cfg_qkv = dataclasses.replace(CONFIGS["tiny"], remat_policy="flash_qkv")
    mesh = build_mesh(MeshSpec(dp=8))
    rules = PRESET_RULES["dp"]
    opt = default_optimizer(lr=1e-2, warmup=1)
    batch = _batch(CONFIGS["tiny"], b=8)
    losses = {}
    for name, cfg in (("full", cfg_full), ("qkv", cfg_qkv)):
        init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
        state = init_fn(jax.random.PRNGKey(0))
        step = make_train_step(cfg, mesh, rules, opt, shardings)
        ls = []
        for _ in range(3):
            state, m = step(state, batch)
            ls.append(float(m["loss"]))
        losses[name] = ls
    # bf16 recompute reassociates sums; divergence stays ~1e-4 over steps
    np.testing.assert_allclose(losses["full"], losses["qkv"], rtol=1e-3)


def test_hbm_limit_memory_levers():
    """The gpt_1b HBM-fit levers, exercised at tiny scale: bf16 adam
    momentum (mu leaves store bf16) and compute-dtype grads both train."""
    cfg = CONFIGS["tiny"]
    mesh = build_mesh(MeshSpec(dp=8))
    rules = PRESET_RULES["dp"]
    opt = default_optimizer(lr=1e-2, warmup=1, mu_dtype=jnp.bfloat16)
    init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
    state = init_fn(jax.random.PRNGKey(0))
    # adam mu (first moment) leaves carry the requested dtype
    adam_state = state.opt_state[1][0]  # chain(clip, adamw) -> adamw ScaleByAdamState
    mu_leaf = jax.tree.leaves(adam_state.mu)[0]
    assert mu_leaf.dtype == jnp.bfloat16
    step = make_train_step(cfg, mesh, rules, opt, shardings, compute_dtype_grads=True)
    batch = _batch(cfg)
    losses = []
    for _ in range(8):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses
    # gpt_1b is the HBM-limit config the bench uses; keep it registered
    assert CONFIGS["gpt_1b"].num_params() > 1.0e9


@pytest.mark.parametrize(
    "spec,rules,extra",
    [
        pytest.param(
            MeshSpec(tp=2, fsdp=4), PRESET_RULES["fsdp_tp"], {}, id="tp2_fsdp4"
        ),
        pytest.param(
            MeshSpec(pp=2, dp=4),
            PRESET_RULES["full"].with_overrides(seq=None, kv_seq=None),
            dict(pp_stages=2, pp_microbatches=2, n_layers=4),
            id="pp2_dp4",
        ),
    ],
)
def test_flash_on_a_mesh_matches_dense(fresh_compile, spec, rules, extra):
    """On a multi-device mesh the flash kernel runs per shard of batch and
    heads inside a shard_map (a Mosaic kernel cannot be auto-partitioned)
    — nested inside the pipeline's own manual region under pp. Same loss
    as dense attention on the same mesh, parameters and batch."""
    losses = {}
    for attention in ("dense", "flash"):
        cfg = dataclasses.replace(
            CONFIGS["tiny"], attention=attention, dtype=jnp.float32, **extra
        )
        mesh = build_mesh(spec)
        opt = default_optimizer(lr=1e-3, warmup=1)
        init_fn, shardings = make_sharded_init(cfg, mesh, rules, opt)
        step = make_train_step(cfg, mesh, rules, opt, shardings)
        _, m = step(init_fn(jax.random.PRNGKey(0)), _batch(cfg))
        losses[attention] = float(m["loss"])
    np.testing.assert_allclose(losses["flash"], losses["dense"], rtol=1e-4)
