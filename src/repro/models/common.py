"""Parameter plans: one source of truth for shapes, logical sharding axes and
initialization — consumed by `init` (real arrays), `abstract` (dry-run
ShapeDtypeStructs) and `partition_specs` (NamedShardings).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sharding import Rules


@dataclass(frozen=True)
class PSpec:
    """Plan for one parameter tensor."""
    shape: Tuple[int, ...]
    names: Tuple[Optional[str], ...]        # logical axes, len == len(shape)
    init: str = "normal"                    # normal | zeros | ones
    scale: float = 1.0                      # multiplier on fan-in init
    dtype: Any = jnp.bfloat16
    fan_in: int = 0                         # 0: the second-to-last dim

    def stacked(self, layers: int) -> "PSpec":
        return PSpec((layers,) + self.shape, (None,) + self.names,
                     self.init, self.scale, self.dtype, self.fan_in)


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def plan_map(fn, plan):
    return jax.tree.map(fn, plan, is_leaf=is_pspec)


def stack_plan(plan, layers: int):
    """Prepend a layer dimension to every parameter (scan-over-layers)."""
    return plan_map(lambda p: p.stacked(layers), plan)


def abstract_params(plan):
    return plan_map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), plan)


def partition_specs(plan, rules: Rules):
    return plan_map(lambda p: rules.spec(p.shape, p.names), plan)


def init_params(rng, plan):
    """Deterministic init: every leaf keyed by its tree path (stable hash —
    Python's hash() is per-process randomized and would make two processes
    initialize different models from the same seed)."""
    import zlib
    flat, treedef = jax.tree.flatten_with_path(plan, is_leaf=is_pspec)

    def one(path, p: PSpec):
        if p.init == "zeros":
            return jnp.zeros(p.shape, p.dtype)
        if p.init == "ones":
            return jnp.ones(p.shape, p.dtype)
        key = jax.random.fold_in(
            rng, zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31))
        fan_in = p.fan_in or (p.shape[-2] if len(p.shape) >= 2
                              else p.shape[-1])
        std = p.scale / np.sqrt(max(fan_in, 1))
        return (std * jax.random.normal(key, p.shape, jnp.float32)).astype(p.dtype)

    return treedef.unflatten([one(path, p) for path, p in flat])


# ----------------------------------------------------------------- plan sugar

def linear(din: int, dout: int, dtype=jnp.bfloat16,
           names: Tuple[Optional[str], Optional[str]] = ("wfsdp", "wtp"),
           scale: float = 1.0) -> PSpec:
    return PSpec((din, dout), names, "normal", scale, dtype)


def norm_scale(d: int, dtype=jnp.bfloat16) -> PSpec:
    return PSpec((d,), ("norm",), "ones", dtype=dtype)


def bias(d: int, name: Optional[str] = "norm", dtype=jnp.bfloat16) -> PSpec:
    return PSpec((d,), (name,), "zeros", dtype=dtype)
