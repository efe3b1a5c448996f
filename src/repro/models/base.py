"""Minimal module-lite substrate: parameter declaration trees.

A model is declared as a nested dict of ``P`` leaves (shape + logical axes +
init).  From one declaration we derive, structurally:
  * init_tree     — materialized jnp parameters
  * abstract_tree — ShapeDtypeStructs (for dry-run lowering, no allocation)
  * spec_tree     — jax.sharding.PartitionSpec per leaf via logical-axis rules

Logical axes: "embed", "heads", "kv_heads", "head_dim", "ff", "vocab",
"experts", "lru", "conv", "layers" (stack, never sharded), None.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class P:
    """One parameter declaration."""

    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones | embed
    scale: Optional[float] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(key, p: P, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "embed":
        scale = p.scale or 1.0
        return jax.random.normal(key, p.shape, dtype) * scale
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    scale = p.scale or (1.0 / math.sqrt(max(fan_in, 1)))
    return jax.random.normal(key, p.shape, dtype) * scale


def is_decl(x) -> bool:
    return isinstance(x, P)


def init_tree(decl, rng, dtype=jnp.float32):
    leaves, treedef = jax.tree.flatten(decl, is_leaf=is_decl)
    keys = jax.random.split(rng, len(leaves))
    vals = [_init_leaf(k, p, dtype) for k, p in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def abstract_tree(decl, dtype=jnp.float32):
    return jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), decl, is_leaf=is_decl)


def param_count(decl) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree.leaves(decl, is_leaf=is_decl))


# ---------------------------------------------------------------------------
# logical-axis -> mesh-axis rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical parameter/activation axes onto mesh axes."""

    embed: Any = "data"        # FSDP / ZeRO-3: weight d_model dim over data
    heads: Any = "model"       # Megatron TP
    kv_heads: Any = "model"
    head_dim: Any = None
    ff: Any = "model"
    vocab: Any = "model"
    experts: Any = "model"     # EP when divisible (checked per model)
    lru: Any = "model"
    conv: Any = None
    batch: Any = ("pod", "data")
    seq: Any = None            # SP for long-context decode
    kv_seq: Any = None
    layers: Any = None

    def spec_for(self, axes: tuple[Optional[str], ...]) -> PartitionSpec:
        return PartitionSpec(*(getattr(self, a) if a else None for a in axes))


def spec_tree(decl, rules: ShardingRules, mesh=None):
    """Specs per leaf; when `mesh` is given, drop shardings whose mesh-axis
    product does not divide the dimension (e.g. GQA kv_heads=8 on model=16 —
    those weights replicate across TP ranks, the standard GQA fallback)."""

    def leaf(p: P):
        spec = rules.spec_for(p.axes)
        if mesh is None:
            return spec
        fixed = []
        for dim, part in zip(p.shape, spec):
            if part is None:
                fixed.append(None)
                continue
            parts = part if isinstance(part, tuple) else (part,)
            prod = 1
            for a in parts:
                prod *= mesh.shape[a]
            fixed.append(part if dim % prod == 0 else None)
        return PartitionSpec(*fixed)

    return jax.tree.map(leaf, decl, is_leaf=is_decl)


def constrain(x, dist, axes: tuple[Optional[str], ...]):
    """with_sharding_constraint by logical axes on ``dist``'s mesh; a no-op
    when there is no mesh (single-device runs)."""
    if dist is None or dist.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(dist.mesh, dist.rules.spec_for(axes)))
