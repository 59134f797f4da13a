"""Logical-axis sharding rules.

Params and caches are annotated with *logical* axis names ("embed", "heads",
"ffn", "kv_blocks", ...); `ShardingRules` maps logical → mesh axes. This is
the flax `logical_axis_rules` idea kept dependency-free: one table controls
how every tensor in the model shards, so changing the parallel layout never
touches model code.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.parallel.mesh import AxisNames

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis name → mesh axis (or None = replicate)."""

    rules: Dict[str, MeshAxes] = field(
        default_factory=lambda: {
            # weights
            "vocab": AxisNames.TP,  # embedding / lm_head vocab shard
            "embed": None,  # d_model replicated
            "heads": AxisNames.TP,  # attention heads
            "kv_heads": AxisNames.TP,
            "head_dim": None,
            "ffn": AxisNames.TP,  # MLP hidden
            "experts": AxisNames.EP,
            "layers": None,  # stacked-layer leading axis (pp later)
            # activations
            "batch": AxisNames.DP,
            "seq": AxisNames.SP,
            # paged KV cache
            "kv_blocks": None,  # block pool is per-replica
        }
    )

    def spec(self, *logical: Optional[str]) -> P:
        return P(*(self.rules.get(ax) if ax else None for ax in logical))

    def sharding(self, mesh: Mesh, *logical: Optional[str]) -> NamedSharding:
        return NamedSharding(mesh, self.spec(*logical))


def logical_to_physical(
    rules: ShardingRules, mesh: Mesh, logical_axes: Tuple[Optional[str], ...]
) -> NamedSharding:
    return rules.sharding(mesh, *logical_axes)


def param_shardings(param_axes, rules: ShardingRules, mesh: Mesh):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: rules.sharding(mesh, *axes),
        param_axes,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def shard_params(params, param_axes, rules: ShardingRules, mesh: Mesh):
    """device_put a param pytree onto the mesh per the rules."""
    shardings = param_shardings(param_axes, rules, mesh)
    return jax.tree.map(jax.device_put, params, shardings)
