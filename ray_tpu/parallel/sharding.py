"""Logical-axis sharding rules (GSPMD annotations).

Arrays are annotated with *logical* axis names; a ShardingRules table maps
them to mesh axes and GSPMD inserts all collectives. This replaces the
reference's entire DP engine zoo (torch DDP wrap train_loop_utils.py:75,
FSDP :92-101, DeepSpeed launcher) with one declarative table:

  DDP        -> batch: (dp, fsdp); params unsharded
  ZeRO/FSDP  -> same + embed/mlp sharded on fsdp
  Megatron   -> heads/mlp on tp, embed replicated
  sequence   -> seq activations on sp (ring attention handles the halo)
  MoE        -> experts on ep
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# canonical logical axis names used by models/
LOGICAL_AXES = (
    "batch",      # tokens batch dim
    "seq",        # sequence dim of activations
    "kv_seq",     # sequence dim of K/V (ring attention shards this)
    "embed",      # model/hidden dim
    "heads",      # attention heads
    "kv_heads",   # key/value heads (GQA)
    "head_dim",   # per-head dim
    "mlp",        # FFN intermediate dim
    "vocab",      # vocabulary dim
    "layers",     # stacked-layer dim (scanned layers / pipeline stages)
    "expert",     # MoE experts
    "stage",      # pipeline stage dim
)

MeshAxes = Union[None, str, Tuple[str, ...]]


class ShardingRules:
    def __init__(self, rules: Dict[str, MeshAxes]):
        unknown = set(rules) - set(LOGICAL_AXES)
        if unknown:
            raise ValueError(f"Unknown logical axes: {sorted(unknown)}")
        self.rules = dict(rules)

    def mesh_axes(self, logical: Optional[str]) -> MeshAxes:
        if logical is None:
            return None
        return self.rules.get(logical)

    def spec(self, *logical_axes: Optional[str]) -> P:
        out, used = [], set()
        for ax in logical_axes:
            m = self.mesh_axes(ax)
            if isinstance(m, tuple):
                m = tuple(a for a in m if a not in used)
                used.update(m)
                out.append(m if m else None)
            else:
                if m in used:
                    m = None
                if m is not None:
                    used.add(m)
                out.append(m)
        return P(*out)

    def with_overrides(self, **overrides: MeshAxes) -> "ShardingRules":
        r = dict(self.rules)
        r.update(overrides)
        return ShardingRules(r)

    def without_axis(self, axis: str) -> "ShardingRules":
        """Drop one mesh axis from every mapping — e.g. the per-slice view
        of a dcn="dp" table, used inside a vmap(spmd_axis_name="dcn")
        region where the dcn dimension is already spoken for."""
        r: Dict[str, MeshAxes] = {}
        for k, v in self.rules.items():
            if isinstance(v, tuple):
                t = tuple(a for a in v if a != axis)
                r[k] = t if t else None
            else:
                r[k] = None if v == axis else v
        return ShardingRules(r)


# --- presets ---------------------------------------------------------------

def make_rules(
    *,
    fsdp_params: bool = True,
    tensor_parallel: bool = True,
    sequence_parallel: bool = False,
    expert_parallel: bool = False,
    dcn: Optional[str] = None,
) -> ShardingRules:
    """`dcn` places ONE parallelism across the slow slice boundary of a
    multi-slice mesh (parallel/multislice.py):

      dcn="dp"  batch -> ("dcn", "dp", "fsdp"): data-parallel outer loop,
                gradient all-reduce crosses DCN once per step.
      dcn="pp"  stage -> ("dcn", "pp"): pipeline stage-groups mapped one
                per slice, boundary ppermutes cross DCN.

    Bandwidth-hungry axes (tp/sp/ep) are never offered a dcn mapping."""
    if dcn not in (None, "dp", "pp"):
        raise ValueError(
            f"dcn must be None, 'dp' or 'pp' (got {dcn!r}); tp/sp/ep "
            "traffic is per-layer bandwidth and cannot cross the slice "
            "boundary"
        )
    rules: Dict[str, MeshAxes] = {
        "batch": ("dcn", "dp", "fsdp") if dcn == "dp" else ("dp", "fsdp"),
        "seq": "sp" if sequence_parallel else None,
        "kv_seq": "sp" if sequence_parallel else None,
        "embed": "fsdp" if fsdp_params else None,
        "heads": "tp" if tensor_parallel else None,
        "kv_heads": "tp" if tensor_parallel else None,
        "head_dim": None,
        "mlp": "tp" if tensor_parallel else None,
        "vocab": "tp" if tensor_parallel else None,
        "layers": None,
        "expert": "ep" if expert_parallel else None,
        "stage": ("dcn", "pp") if dcn == "pp" else "pp",
    }
    return ShardingRules(rules)


PRESET_RULES: Dict[str, ShardingRules] = {
    # pure data parallel: params replicated
    "dp": make_rules(fsdp_params=False, tensor_parallel=False),
    # ZeRO-3: params sharded on fsdp along embed
    "fsdp": make_rules(tensor_parallel=False),
    # Megatron TP + FSDP
    "fsdp_tp": make_rules(),
    # + ring-attention sequence parallel
    "fsdp_tp_sp": make_rules(sequence_parallel=True),
    # MoE
    "fsdp_tp_ep": make_rules(expert_parallel=True),
    "full": make_rules(sequence_parallel=True, expert_parallel=True),
}


def logical_spec(rules: ShardingRules, *axes: Optional[str]) -> P:
    return rules.spec(*axes)


def logical_sharding(mesh: Mesh, rules: ShardingRules, *axes: Optional[str]) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(*axes))


def constrain(x, rules: ShardingRules, *axes: Optional[str], mesh: Optional[Mesh] = None):
    """with_sharding_constraint by logical names (inside jit)."""
    spec = rules.spec(*axes)
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def manual_shard_map(f, mesh: Mesh, in_specs, out_specs, manual_axes):
    """jax.shard_map, manual over `manual_axes` only: the other mesh axes
    stay under GSPMD inside the region (partial-manual lowering). Nested
    inside another manual region (flash attention under the pipeline's),
    the region takes the context mesh and claims only the axes that are
    still automatic."""
    manual = frozenset(manual_axes)
    outer = jax.sharding.get_abstract_mesh()
    if not outer.empty and outer.manual_axes:
        mesh, manual = None, manual - frozenset(outer.manual_axes)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False, axis_names=manual,
    )


def tree_shardings(mesh: Mesh, rules: ShardingRules, spec_tree):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, rules.spec(*axes)),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x),
    )
