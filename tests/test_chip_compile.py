"""The main path's Pallas kernels, compiled for a described TPU v5e chip.

No chip is attached: `get_topology_desc` hands the installed TPU compiler a
description of one, and lowering with `interpret=False` raises whatever
Mosaic would raise on the real device (unaligned slices, loads from the
wrong memory space, too much VMEM). Interpret mode notices none of that.
A compile that passes is not a run — it yields no result and no time.

The topology is described inside a module-scoped fixture, after a test of
this file has started, and only here: the process that loads the TPU
library keeps it until exit, so a second test file doing the same could
land on another xdist worker and skip in silence.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_attention import paged_attention

BLOCK_TOKENS = 64
POOL_BLOCKS = 513  # the gpt_1b smoke pool
TABLE_BLOCKS = 32  # 2048 tokens per slot


# an executable compiled for an absent chip can be written to the persistent
# cache but not read back (the next compile warns and recompiles): keep
# these compiles out of it
pytestmark = pytest.mark.usefixtures("fresh_compile")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (batch, q heads, kv heads, seq, d_head): gpt2_125m, gpt_1b, a GQA shape
FLASH_SHAPES = [
    pytest.param(16, 12, 12, 1024, 64, id="gpt2_125m"),
    pytest.param(6, 16, 16, 1024, 128, id="gpt_1b"),
    pytest.param(1, 32, 8, 2048, 128, id="gqa32x8"),
]


def _flash(q, k, v):
    # as models/transformer.py calls it: head-major, 1024-wide tiles
    return flash_attention(
        q, k, v, block_q=1024, block_k=1024, layout="bhsd", interpret=False
    )


@pytest.mark.parametrize("b,h,kv,seq,d", FLASH_SHAPES)
def test_flash_forward_compiles(one_chip, b, h, kv, seq, d):
    q = jax.ShapeDtypeStruct((b, h, seq, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, kv, seq, d), jnp.bfloat16, sharding=one_chip)
    _compile(_flash, q, k, k)


@pytest.mark.parametrize("b,h,kv,seq,d", FLASH_SHAPES)
def test_flash_grad_compiles(one_chip, b, h, kv, seq, d):
    q = jax.ShapeDtypeStruct((b, h, seq, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, kv, seq, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return _flash(q, k, v).astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)


def test_flash_compiles_per_shard_on_four_chips(topo):
    """GSPMD cannot partition a Mosaic kernel, so on a mesh the model runs
    flash per shard of batch and heads (transformer.py, attend): manual
    over every mesh axis — the compiler refuses it under anything less."""
    from jax.sharding import NamedSharding

    from ray_tpu.parallel import MeshSpec, PRESET_RULES, build_mesh
    from ray_tpu.parallel.sharding import manual_shard_map

    mesh = build_mesh(MeshSpec(fsdp=4), devices=topo.devices)
    spec = PRESET_RULES["fsdp"].spec("batch", "heads", None, None)
    q = jax.ShapeDtypeStruct(
        (4, 16, 1024, 128), jnp.bfloat16, sharding=NamedSharding(mesh, spec)
    )
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.jit(_flash).lower(q, q, q)
    per_shard = manual_shard_map(
        _flash, mesh, (spec, spec, spec), spec, mesh.axis_names
    )
    _compile(per_shard, q, q, q)


def _paged(one_chip, *, q_len, h, kv, d, batch=8, int8=False, **kw):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds(
        (POOL_BLOCKS, BLOCK_TOKENS, kv, d), jnp.int8 if int8 else jnp.bfloat16
    )
    args = [
        sds((batch, q_len, h, d), jnp.bfloat16), pool, pool,
        sds((batch, TABLE_BLOCKS), jnp.int32), sds((batch,), jnp.int32),
    ]
    if int8:
        scale = sds((POOL_BLOCKS, kv), jnp.float32)
        args += [scale, scale]

    def fn(q, k_pool, v_pool, tables, positions, k_scale=None, v_scale=None):
        return paged_attention(
            q, k_pool, v_pool, tables, positions, k_scale=k_scale,
            v_scale=v_scale, impl="kernel", interpret=False, **kw,
        )

    return _compile(fn, *args)


# q = 1 decode, q = 5 speculative verify (k = 4), q = 256 prefill chunk
@pytest.mark.parametrize("q_len", [1, 5, 256], ids=["decode", "verify", "prefill"])
@pytest.mark.parametrize(
    "h,d", [pytest.param(16, 128, id="gpt_1b"), pytest.param(12, 64, id="gpt2_125m")]
)
def test_paged_kernel_compiles(one_chip, q_len, h, d):
    _paged(one_chip, q_len=q_len, h=h, kv=h, d=d)


def test_paged_kernel_partial_out_compiles(one_chip):
    # the per-shard form sharded pools merge with merge_partials
    _paged(one_chip, q_len=1, h=16, kv=16, d=128, partial_out=True,
           signed_tables=True)


def test_paged_kernel_gqa_compiles(one_chip):
    _paged(one_chip, q_len=1, h=32, kv=8, d=128)


@pytest.mark.parametrize("q_len", [1, 5, 256], ids=["decode", "verify", "prefill"])
def test_paged_kernel_int8_compiles(one_chip, q_len):
    # the scales used to ride in pl.ANY and be loaded in the body, which
    # Mosaic refuses and interpret mode never noticed
    _paged(one_chip, q_len=q_len, h=16, kv=16, d=128, int8=True)
