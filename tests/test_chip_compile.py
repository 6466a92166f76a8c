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

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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


def _paged(one_chip, *, q_len, h, kv, d, batch=8, int8=False,
           blocks=POOL_BLOCKS, table=TABLE_BLOCKS, **kw):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds(
        (blocks, BLOCK_TOKENS, kv, d), jnp.int8 if int8 else jnp.bfloat16
    )
    args = [
        sds((batch, q_len, h, d), jnp.bfloat16), pool, pool,
        sds((batch, table), jnp.int32), sds((batch,), jnp.int32),
    ]
    if int8:
        scale = sds((blocks, kv), jnp.float32)
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


@pytest.mark.parametrize("q_len", [1, 256], ids=["decode", "prefill"])
def test_paged_kernel_olmoe_heads_compile(one_chip, q_len):
    # OLMoE: 16 q heads on 16 kv heads (n_rep 1), at its own pool
    _paged(one_chip, q_len=q_len, h=16, kv=16, d=128, batch=32, blocks=2049,
           table=64)


@pytest.mark.parametrize("q_len", [1, 5, 256], ids=["decode", "verify", "prefill"])
def test_paged_kernel_int8_compiles(one_chip, q_len):
    # the scales used to ride in pl.ANY and be loaded in the body, which
    # Mosaic refuses and interpret mode never noticed
    _paged(one_chip, q_len=q_len, h=16, kv=16, d=128, int8=True)


# ---- the whole paged programs: nothing in them scales with the pool ------
#
# The pool ([L, N, bt, KV, D] per K/V leaf) goes through the layer loop of
# make_paged_decoder as carried state, written and read in place. Whether
# that engaged shows without a chip: compiled for two pool sizes, the
# program's temporaries must not grow with the pool, and the optimized HLO
# must hold no copy, dynamic-slice or dynamic-update-slice that produces a
# K/V leaf or one layer of it. (With the pool as the scan's xs/ys every
# layer was sliced out of the stack and written back: temporaries grew by
# a whole leaf and more.)

PAGED_SLOTS = 32
PAGED_TABLE = 64  # blocks per slot: 4096 tokens
# both pool sizes lie on the same side of a step the compiler's memory-space
# assignment takes near 3.0 GB of arguments (other buffers prefetched, +369
# MB of temporaries whatever the pool holds); 2049 is the benchmark's pool
PAGED_POOLS = (1036, 4144)
PAGED_VARIANTS = [
    pytest.param("fused", False, id="fused-fp"),
    pytest.param("fused", True, id="fused-int8"),
    pytest.param("gather", False, id="gather-fp"),
]


def _mistral_block(n_layers=2):
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=32768, d_model=4096, n_layers=n_layers, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14336, max_seq_len=4096,
    )


def _olmoe_block(n_layers=2):
    """OLMoE-1B-7B's layer as benchmark/blocks/olmoe.py maps it."""
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=50304, d_model=2048, n_layers=n_layers, n_heads=16,
        n_kv_heads=16, d_head=128, d_ff=1024, max_seq_len=4096,
        n_experts=64, top_k=8, moe_capacity_factor=None,
        moe_renormalize=False, qk_norm=True, rms_norm_eps=1e-5,
    )


def _compile_paged_program(one_chip, monkeypatch, program, impl, int8,
                           num_blocks, cfg=None, tree="f32"):
    """One of make_paged_decoder's programs for the described chip, from
    shapes alone. `tree` "f32" hands it init_params' float32 tree, "held"
    the tree a PagedDecodeEngine holds (serving_params of it)."""
    from ray_tpu.models.transformer import (
        init_paged_kv_cache, init_params, make_paged_decoder, serving_params,
    )

    # the fused path asks the backend whether to lower through Mosaic
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = cfg or _mistral_block()
    kv_dtype = jnp.int8 if int8 else None
    prefill, decode, verify, _ = make_paged_decoder(
        cfg, block_tokens=BLOCK_TOKENS, kv_dtype=kv_dtype,
        attention_impl=impl,
    )

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree,
        )

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def tree_of():
        params = init_params(jax.random.PRNGKey(0), cfg)
        return serving_params(cfg, params) if tree == "held" else params

    params = on_chip(jax.eval_shape(tree_of))
    pool = on_chip(jax.eval_shape(
        lambda: init_paged_kv_cache(cfg, num_blocks, BLOCK_TOKENS,
                                    dtype=kv_dtype)))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    B, T = PAGED_SLOTS, PAGED_TABLE
    if program == "decode":
        lowered = decode.lower(params, pool, i32(B, T + 4), key)
    elif program == "verify":  # k = 4 drafts
        lowered = verify.lower(
            params, pool, i32(B, T), i32(B, 5), i32(B), i32(B), i32(B, 5),
            i32(B, 5), key)
    else:  # a 128-token user turn behind 512 cached tokens
        lowered = jax.jit(
            functools.partial(prefill, ctx_blocks=8, Sb=128),
            donate_argnums=(1,)
        ).lower(params, pool, i32(3 + 128 + T), key)
    compiled = lowered.compile()
    if impl == "fused":
        assert "tpu_custom_call" in compiled.as_text()
    return cfg, compiled


# (ROOT?, name, result dtype, result dims, op, the rest of the line)
_HLO_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(([^\n]*)", re.M)


def _pool_sized_moves(hlo_text, cfg, num_blocks):
    """Instructions of the optimized HLO that copy, slice out or write back
    a whole K/V leaf or one layer of it, by their result shape."""
    layer = f"{num_blocks},{BLOCK_TOKENS},{cfg.n_kv_heads},{cfg.d_head}"
    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    return [
        f"%{name}: {op} -> [{dims}]"
        for _, name, _, dims, op, _ in _HLO_INSTRUCTION.findall(hlo_text)
        if dims.endswith(layer) and (
            op.startswith(moves)
            or (op == "fusion" and any(m in name for m in moves)))
    ]


def _assert_nothing_scales_with_the_pool(one_chip, monkeypatch, program,
                                         impl, int8, cfg=None,
                                         pools=PAGED_POOLS, tree="f32"):
    small, large = pools
    temps = []
    for num_blocks in pools:
        cfg, compiled = _compile_paged_program(
            one_chip, monkeypatch, program, impl, int8, num_blocks, cfg,
            tree=tree)
        moves = _pool_sized_moves(compiled.as_text(), cfg, num_blocks)
        assert not moves, f"{num_blocks} blocks: {moves}"
        mem = compiled.memory_analysis()
        # the donated pool is the output: no second pool is allocated
        pool_bytes = 2 * cfg.n_layers * num_blocks * BLOCK_TOKENS * (
            cfg.n_kv_heads * cfg.d_head * (1 if int8 else 2))
        assert mem.alias_size_in_bytes >= pool_bytes
        temps.append(mem.temp_size_in_bytes)
    block = BLOCK_TOKENS * cfg.n_kv_heads * cfg.d_head * (1 if int8 else 2)
    allowed = 2 * block  # one block per K/V leaf
    if int8:
        # the two [L, N, KV] f32 scale leaves (1/2048 of the pool's bytes)
        # are relaid once a step, OUTSIDE the layer loop, into the layout
        # the loop's gathers want and back: at most one lane-padded row per
        # block and layer each way (2.4 KB a block measured, against the
        # 256 KB a block that two layers of int8 K and V hold)
        allowed += 2 * 2 * cfg.n_layers * (large - small) * 128 * 4
    assert temps[1] - temps[0] < allowed, temps


# every counter below holds for the float32 tree a caller may still hand the
# programs and for the tree an engine holds (serving_params of it)
TREES = pytest.mark.parametrize("tree", ["f32", "held"])


@TREES
@pytest.mark.parametrize("impl,int8", PAGED_VARIANTS)
def test_paged_decode_program_moves_no_pool(one_chip, monkeypatch, impl, int8,
                                            tree):
    _assert_nothing_scales_with_the_pool(
        one_chip, monkeypatch, "decode", impl, int8, tree=tree)


@TREES
@pytest.mark.parametrize("program", ["prefill", "verify"])
def test_paged_prefill_and_verify_move_no_pool(one_chip, monkeypatch, program,
                                               tree):
    _assert_nothing_scales_with_the_pool(
        one_chip, monkeypatch, program, "fused", False, tree=tree)


# ---- the OLMoE geometry: dropless experts in the paged programs ----------


@TREES
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_olmoe_paged_programs_move_no_pool(one_chip, monkeypatch, program,
                                           tree):
    # this block's step in the compiler's memory-space assignment (+537 MB
    # of temporaries, whatever the pool holds) lies between 1036 and 2049
    # blocks: the benchmark's pool and twice it are on its far side
    _assert_nothing_scales_with_the_pool(
        one_chip, monkeypatch, program, "fused", False, cfg=_olmoe_block(),
        pools=(2049, 4144), tree=tree)


@TREES
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_olmoe_experts_are_grouped_matmuls(one_chip, monkeypatch, program,
                                           tree):
    """Dropless routing compiles to the chip's grouped matmul, three a
    layer, at the FLOPs of the routed pairs: no worst-case buffer of every
    expert for every token ([64, N, d_model], or the [64, N, d_ff] behind
    it) exists in either program."""
    cfg, compiled = _compile_paged_program(
        one_chip, monkeypatch, program, "fused", False, 2049,
        cfg=_olmoe_block(), tree=tree)
    text = compiled.as_text()
    assert len(re.findall(r"= \S+ custom-call\([^\n]*ragged_dot_tiling",
                          text)) == 3
    n = 32 if program == "decode" else 128
    shapes = set(re.findall(r"= \w+\[([\d,]+)\]", text))
    for width in (cfg.d_model, cfg.d_ff):
        for dims in (f"{cfg.n_experts},{n},{width}",
                     f"{n},{cfg.n_experts},{width}"):
            assert dims not in shapes, dims
    # every routed pair's row is there: N x top_k sorted rows of d_model
    assert f"{n * cfg.top_k},{cfg.d_model}" in shapes


# ---- the latent (MLA) geometry: one 640-wide row a token ------------------


def _xing4_block(n_layers=2):
    """Xing4.0-29B-A4B's layers as benchmark/blocks/xing4.py maps them: one
    leading dense layer, then expert layers."""
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=131072, d_model=3584, n_layers=n_layers, n_heads=32,
        n_kv_heads=32, d_head=192, d_ff=1024, max_seq_len=18432,
        q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_factor=64.0,
        rope_original_max=4096, rope_mscale_all_dim=1.0, first_k_dense=1,
        d_ff_dense=9216, n_experts=64, top_k=4, moe_scoring="sigmoid",
        moe_route_scale=2.0, n_shared_experts=1, moe_capacity_factor=None,
        hc_mult=4,
    )


@pytest.mark.parametrize("batch,q_len,table,heads", [
    (32, 1, 288, 32), (1, 256, 260, 32), (64, 1, 80, 128), (1, 2048, 80, 128)],
    ids=["decode", "prefill", "decode-128-heads", "prefill-128-heads"])
def test_mla_kernel_compiles(one_chip, batch, q_len, table, heads):
    """The latent kernel at the benchmark cells' shapes: 32 heads over
    640-wide rows, 16 blocks a grid step, an 18,432-token table (Xing4.0);
    128 heads, 64 slots and a 5,120-token table (DeepSeek-V2), where a
    prefill tile of 16 queries x 128 heads would pass the kernel's VMEM
    and `block_rows` cuts it to 4 queries. The grid's second bound is
    traced (the call's live (slot, step) pairs): Mosaic takes it."""
    import importlib

    pa = importlib.import_module("ray_tpu.ops.paged_attention")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, tables, positions):
        return pa.mla_paged_attention(
            q, pool, tables, positions, layer=jnp.int32(1), rank=512,
            scale=0.14, impl="kernel", interpret=False)

    compiled = _compile(
        fn, sds((batch, q_len, heads, 640), jnp.bfloat16),
        sds((2, 1036, BLOCK_TOKENS, 1, 640), jnp.bfloat16),
        sds((batch, table), jnp.int32), sds((batch,), jnp.int32))
    text = compiled.as_text()
    assert "mla_paged_attention" in text and "tpu_custom_call" in text
    # the [L, N, bt, 1, W] -> [L, N, bt, W] view is free: no copy of the pool
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mla_prefill_tile_is_sized_in_rows(one_chip):
    """16 queries x 128 heads a tile is refused by the chip's compiler (the
    f32 accumulator and score tile alone are 2 x 4 MB, twice with the
    pipeline's q and output tiles): what `block_rows` is for."""
    import importlib

    pa = importlib.import_module("ray_tpu.ops.paged_attention")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, pool, tables, positions):
        return pa.mla_paged_attention(
            q, pool, tables, positions, layer=jnp.int32(1), rank=512,
            scale=0.14, impl="kernel", interpret=False, block_rows=16 * 128)

    with pytest.raises(Exception, match="(?i)vmem|memory|exceed"):
        _compile(fn, sds((1, 256, 128, 640), jnp.bfloat16),
                 sds((2, 1036, BLOCK_TOKENS, 1, 640), jnp.bfloat16),
                 sds((1, 80), jnp.int32), sds((1,), jnp.int32))


def _deepseek_v2_share(n_layers=2):
    """DeepSeek-V2's layers as benchmark/blocks/deepseek_v2.py maps the
    chip's share: 40 of 160 experts under the 160-wide group-limited
    router, 128 heads, a plain residual."""
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=25600, d_model=5120, n_layers=n_layers, n_heads=128,
        n_kv_heads=128, d_head=192, d_ff=1536, max_seq_len=5120,
        q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, rope_factor=40.0,
        rope_original_max=4096, rope_mscale=0.707, rope_mscale_all_dim=0.707,
        first_k_dense=1, d_ff_dense=12288, n_experts=40, n_routed_experts=160,
        expert_offset=0, top_k=6, moe_n_group=8, moe_topk_group=3,
        moe_renormalize=False, moe_route_scale=16.0, n_shared_experts=2,
        moe_capacity_factor=None,
    )


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_share_programs_group_the_held_experts_alone(one_chip, monkeypatch,
                                                     program):
    """Decode and prefill of one chip's share: three grouped matmuls a
    layer whose weight operand is the HELD experts' stack (40 groups a
    layer under a 160-wide router), read in place; every routed pair has a
    row (N x 6 of d_model) and the group sizes decide which are multiplied;
    the latent kernel at 128 heads is in the program and the pool is not
    copied."""
    cfg, compiled = _compile_paged_program(
        one_chip, monkeypatch, program, "fused", False, 2049,
        cfg=_deepseek_v2_share(3), tree="held")
    text = compiled.as_text()
    assert "mla_paged_attention" in text
    insts = _HLO_INSTRUCTION.findall(text)
    calls = [(dims, rest) for _, _, _, dims, op, rest in insts
             if op == "custom-call" and "ragged_dot_tiling" in rest]
    assert len(calls) == 3
    n = (32 if program == "decode" else 128) * cfg.top_k
    assert sorted(dims for dims, _ in calls) == sorted(
        [f"{n},{cfg.d_ff}", f"{n},{cfg.d_ff}", f"{n},{cfg.d_model}"])
    defs = {name: (op, rest) for _, name, _, _, op, rest in insts}
    for _, rest in calls:
        operands = re.findall(r"%([^\s,)]+)", rest.split(")")[0])
        assert _operand_source(defs, operands[-1]) in (
            "parameter", "get-tuple-element"), rest[:200]
    shapes = set(re.findall(r"= \w+\[([\d,]+)\]", text))
    # the router is as wide as the deployment, the stacks as the share
    assert any(d.endswith(f",{cfg.router_width}") for d in shapes)
    assert not any(d.startswith(f"{cfg.router_width},{cfg.d_model},")
                   for d in shapes)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cfg.n_layers * 2049 * BLOCK_TOKENS * 640 * 2


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_latent_paged_programs_move_no_pool(one_chip, monkeypatch, program):
    """Decode and prefill over the latent pool: the donated pool is the
    output, the kernel is in the program, and what the program keeps
    besides does not grow with the pool."""
    temps = []
    for num_blocks in (1036, 4144):
        cfg, compiled = _compile_paged_program(
            one_chip, monkeypatch, program, "fused", False, num_blocks,
            cfg=_xing4_block(), tree="held")
        # ONE kernel a layer body (the dense stack's and the expert
        # stack's), whose grid bound is the call's live (slot, step) pairs:
        # no second kernel for another occupancy
        calls = [name for _, name, _, _, op, rest
                 in _HLO_INSTRUCTION.findall(compiled.as_text())
                 if op == "custom-call" and "tpu_custom_call" in rest
                 and not name.startswith("ragged-dot")]
        assert len(calls) == 2 and all(
            c.startswith("mla_paged_attention") for c in calls), calls
        mem = compiled.memory_analysis()
        pool_bytes = cfg.n_layers * num_blocks * BLOCK_TOKENS * 640 * 2
        assert mem.alias_size_in_bytes >= pool_bytes
        temps.append(mem.temp_size_in_bytes)
    assert temps[1] - temps[0] < 2 * BLOCK_TOKENS * 640 * 2, temps


# ---- the experts are read in place in their [L, X, ...] stack -------------
#
# The grouped matmul takes a whole buffer as its weight operand. A layer's
# [X, d_model, d_ff] slice of the stack was therefore COPIED in front of
# each call (`dynamic-slice_bitcast_fusion`, three a layer: 61 % of OLMoE's
# device time, 56 % of Xing4.0's, PERF.md PR 34). The paged programs now
# hand the kernel the stack itself, viewed as L*X groups.


def _operand_source(defs, name):
    """The op that made `name`, seen through bitcasts."""
    op, rest = defs[name]
    while op == "bitcast":
        op, rest = defs[re.match(r"%([^\s,)]+)", rest).group(1)]
    return op


@TREES
@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("block", ["olmoe", "xing4"])
def test_expert_weights_are_never_copied_out_of_their_stack(
        one_chip, monkeypatch, block, program, tree):
    # two expert layers each (Xing4.0's behind its dense one): a stack of
    # one layer IS a layer's experts
    cfg = _olmoe_block() if block == "olmoe" else _xing4_block(3)
    _, compiled = _compile_paged_program(
        one_chip, monkeypatch, program, "fused", False, 2049, cfg=cfg,
        tree=tree)
    text = compiled.as_text()
    insts = _HLO_INSTRUCTION.findall(text)
    X, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    one_layer = {f"{X},{d},{f}", f"{X},{f},{d}"}
    made = [f"%{name}: {op} -> [{dims}]"
            for _, name, _, dims, op, _ in insts if dims in one_layer]
    assert not made, made
    defs = {name: (op, rest) for _, name, _, _, op, rest in insts}
    calls = [rest for _, _, _, _, op, rest in insts
             if op == "custom-call" and "ragged_dot_tiling" in rest]
    assert len(calls) == 3
    for rest in calls:
        operands = re.findall(r"%([^\s,)]+)", rest.split(")")[0])
        assert _operand_source(defs, operands[-1]) in (
            "parameter", "get-tuple-element"), rest[:200]


# ---- the held tree: no weight is cast inside a paged program -------------
#
# A PagedDecodeEngine holds serving_params of its tree: matmul weights,
# embed and unembed already in the compute dtype. Handed that tree, the
# programs' own astype calls compile to nothing; handed init_params'
# float32 tree they cast every weight in every call — as a `convert`, as a
# fusion whose root is one, or as the chip's `copy` that changes type and
# layout at once (wq / wk / wv) — and keep the bfloat16 copies as
# temporaries.

_HLO_COMPUTATION = re.compile(
    r"^(?:ENTRY )?%(\S+) \([^\n]*\{\n(.*?)^\}", re.M | re.S)


def _weight_casts(hlo_text, params):
    """Instructions of the optimized HLO that write a whole weight leaf
    (a stacked matmul leaf, embed or unembed, by its dims) in bfloat16
    from float32. What sits inside a fusion's body is not written
    anywhere: there only the root counts, as the fusion's own result."""
    from ray_tpu.models.transformer import _MATMUL_KEYS

    leaves = [params["layers"][k] for k in _MATMUL_KEYS
              if k in params["layers"]]
    leaves += [params["embed"], params["unembed"]]
    whole = {",".join(map(str, leaf.shape)) for leaf in leaves}
    bodies = {name: list(_HLO_INSTRUCTION.finditer(body))
              for name, body in _HLO_COMPUTATION.findall(hlo_text)}
    root_op = {name: next((m.group(5) for m in insts if m.group(1)), None)
               for name, insts in bodies.items()}
    dtype_of = {m.group(2): m.group(3)
                for insts in bodies.values() for m in insts}
    fused = set(re.findall(r" fusion\([^\n]*calls=%([^\s,]+)", hlo_text))
    found = []
    for name, insts in bodies.items():
        if name in fused:
            continue
        for m in insts:
            _, inst, dtype, dims, op, rest = m.groups()
            if dtype != "bf16" or dims not in whole:
                continue
            operand = re.match(r"%([^\s,)]+)", rest)
            source = dtype_of.get(operand.group(1)) if operand else None
            callee = re.search(r"calls=%([^\s,]+)", rest)
            if (op == "convert"
                    or (op == "copy" and source == "f32")
                    or (op == "fusion" and callee
                        and root_op.get(callee.group(1)) == "convert")):
                found.append(f"%{inst}: {op} -> {dtype}[{dims}]")
    return found


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("block", ["mistral", "olmoe"])
def test_held_tree_is_cast_in_no_paged_program(one_chip, monkeypatch, block,
                                               program):
    from ray_tpu.models.transformer import _MATMUL_KEYS, init_params

    cfg = _mistral_block() if block == "mistral" else _olmoe_block()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    casts, temps = {}, {}
    for tree in ("f32", "held"):
        _, compiled = _compile_paged_program(
            one_chip, monkeypatch, program, "fused", False, 2049, cfg,
            tree=tree)
        casts[tree] = _weight_casts(compiled.as_text(), params)
        temps[tree] = compiled.memory_analysis().temp_size_in_bytes
    # the control: on the float32 tree every stacked leaf and embed are
    # cast and written (unembed alone is read as float32 inside the head
    # matmul's own fusion, and written nowhere)
    written = {c[c.index("[") + 1:-1] for c in casts["f32"]}
    assert written == {
        ",".join(map(str, leaf.shape))
        for leaf in [params["embed"]] + [
            params["layers"][k] for k in _MATMUL_KEYS if k in params["layers"]]
    }, casts["f32"]
    assert not casts["held"], casts["held"]
    # and the bfloat16 copies were temporaries of every call (to within a
    # thousandth: what else the two programs keep packs a little differently)
    stacked = sum(
        2 * params["layers"][k].size for k in _MATMUL_KEYS
        if k in params["layers"])
    assert temps["f32"] - temps["held"] >= 0.999 * stacked, (temps, stacked)


# ---- the hybrid geometry: a state pool beside the KV pool ------------------


def _olmo_hybrid_block(n_layers=4):
    """Olmo-Hybrid-7B's layers as benchmark/blocks/olmo_hybrid.py maps them:
    one period of three linear layers and a full one."""
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=100352, d_model=3840, n_layers=n_layers, n_heads=30,
        n_kv_heads=30, d_head=128, d_ff=11008, max_seq_len=35328,
        qk_norm=True, use_rope=False, norm_placement="post",
        layer_period=("linear", "linear", "linear", "full"),
        linear_n_heads=30, linear_d_k=96, linear_d_v=192,
    )


HYBRID_ROWS = 160  # the cell's: 32 slots + 128 snapshots


def _compile_hybrid(one_chip, monkeypatch, program, num_blocks=1036):
    from ray_tpu.models.transformer import (
        init_paged_kv_cache, init_params, make_paged_decoder, serving_params,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _olmo_hybrid_block()
    prefill, decode, _, _ = make_paged_decoder(
        cfg, block_tokens=BLOCK_TOKENS, attention_impl="fused")

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(lambda: serving_params(
        cfg, init_params(jax.random.PRNGKey(0), cfg))))
    pool = on_chip(jax.eval_shape(lambda: init_paged_kv_cache(
        cfg, num_blocks, BLOCK_TOKENS, state_rows=HYBRID_ROWS)))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    B, T = PAGED_SLOTS, PAGED_TABLE
    if program == "decode":
        lowered = decode.lower(params, pool, i32(B, T + 4), key)
    else:  # a 512-token turn behind 2,048 cached tokens
        lowered = jax.jit(
            functools.partial(prefill, ctx_blocks=32, Sb=512),
            donate_argnums=(1,)
        ).lower(params, pool, i32(3 + 512 + T), key)
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()  # the paged kernel
    return cfg, pool, compiled


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_hybrid_programs_copy_no_pool_and_no_state_leaf(one_chip, monkeypatch,
                                                        program):
    """Both pools go through the period scan in place. The KV leaves hold 32
    heads for the model's 30 (`kv_pool_heads`): with 30 the device layout
    puts the heads major over a block's tokens and every program relays the
    whole pool in and out (two pool-sized copies a step, found here). The
    state leaf [L_lin, rows, 96, 5760] float32 and the conv leaf
    [L_lin, rows, 34560] tile (8, 128) with no padding; the only
    instructions that yield a state leaf's shape are the in-place updates of
    one row (decode: one a live slot and layer, inside the loop over the
    live rows; prefill: one a layer)."""
    cfg, pool, compiled = _compile_hybrid(one_chip, monkeypatch, program)
    text = compiled.as_text()
    kv = ",".join(map(str, pool["k"].shape))
    assert kv.endswith("64,32,128") and cfg.n_kv_heads == 30
    state = ",".join(map(str, pool["state"].shape))
    conv = ",".join(map(str, pool["conv"].shape))
    assert (state, conv) == (f"3,{HYBRID_ROWS},96,5760", f"3,{HYBRID_ROWS},34560")
    found = _HLO_INSTRUCTION.findall(text)
    for _, name, dtype, dims, op, _ in found:
        if dims in (kv, state, conv):  # copy, copy-start (a prefetch), ...
            assert not op.startswith("copy"), (name, op, dims)
            assert not (op == "fusion" and "copy" in name), (name, dims)
    # the leaves' device layout is row-major and tiled without padding
    assert f"f32[{state}]{{3,2,1,0:T(8,128)}}" in text
    assert f"bf16[{conv}]{{2,1,0:T(8,128)(2,1)}}" in text
    assert f"bf16[{kv}]{{4,3,2,1,0:T(8,128)(2,1)}}" in text
    mem = compiled.memory_analysis()
    pool_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(pool))
    assert mem.alias_size_in_bytes >= pool_bytes  # donated, updated in place
    # nothing a pool's size among the temporaries (a layer's weights are
    # read in place too: a period's layers sliced out together first were
    # 1.2 GB of copies a step)
    assert mem.temp_size_in_bytes < 600e6, mem.temp_size_in_bytes
    # a state row is read and written one at a time
    row = "1,1,96,5760"
    assert any(dims == row and op.startswith("dynamic-slice") or
               (op == "fusion" and "dynamic-slice" in name and dims == row)
               for _, name, _, dims, op, _ in found) or f"[{row}]" in text


# ---- the walk of the paged kernel is bounded by what lives (ISSUE 40) -----


@pytest.mark.parametrize("block", ["mistral", "olmoe", "hybrid"])
def test_decode_holds_one_paged_attention_call_a_layer_body(
        one_chip, monkeypatch, block):
    """The kernel's grid bounds (live slots, the longest live walk) are
    operands of ONE custom call a layer body, named `paged_attention` — the
    name the benchmark's readers find it by. The scalars that bound the walk
    come from the step's own tables and positions inside the program: no
    second kernel, nothing that scales with the pool, and the same one
    decode program whatever the occupancy (the engine's side of that:
    tests/test_engine_host_traffic.py,
    test_kv_blocks_walked_is_the_live_slots_live_blocks)."""
    if block == "hybrid":
        cfg, pool, compiled = _compile_hybrid(one_chip, monkeypatch, "decode")
        heads, num_blocks = pool["k"].shape[3], pool["k"].shape[1]
    else:
        num_blocks = 2049
        cfg, compiled = _compile_paged_program(
            one_chip, monkeypatch, "decode", "fused", False, num_blocks,
            cfg={"mistral": _mistral_block, "olmoe": _olmoe_block}[block](),
            tree="held")
        heads = cfg.n_kv_heads
    text = compiled.as_text()
    calls = [name for _, name, _, _, op, rest in _HLO_INSTRUCTION.findall(text)
             if op == "custom-call" and "tpu_custom_call" in rest
             and not name.startswith("ragged-dot")]
    assert len(calls) == 1 and calls[0].startswith("paged_attention"), calls
    # whatever the kernel's wrapper computes, nothing of it is pool-sized
    leaf = dataclasses.replace(cfg, n_kv_heads=heads)  # the pool's own heads
    assert not _pool_sized_moves(text, leaf, num_blocks)
