"""Parameter-spec machinery.

A model is described as a pytree of :class:`ParamSpec` (shape + logical axis
names + init law). From that single source of truth we derive:

* real parameters        — ``init_params(key, specs)`` (works under
  ``jax.eval_shape`` for the dry-run: no allocation needed there),
* sharding               — ``repro.launch.sharding`` maps logical axis names
  to mesh axes per the parallelism rules,
* abstract inputs        — ``jax.ShapeDtypeStruct`` stand-ins for lowering.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]      # logical axis names (len == ndim)
    dtype: str = "bfloat16"
    init: str = "normal"                 # normal | zeros | ones | rwkv_decay
    scale: Optional[float] = None        # default: 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    @property
    def sds(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, jnp.dtype(self.dtype))


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def tree_specs(tree):
    """Flatten treating ParamSpec as leaves."""
    return jax.tree_util.tree_flatten(tree, is_leaf=is_spec)


def init_one(key: jax.Array, spec: ParamSpec) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "rwkv_decay":
        # w0 init so that exp(-exp(w0)) spans useful decay range per channel
        n = int(np.prod(spec.shape)) if spec.shape else 1
        ramp = jnp.linspace(-6.0, 1.0, n).reshape(spec.shape or ())
        return ramp.astype(spec.dtype)
    scale = spec.scale if spec.scale is not None else fan_in_scale(spec.shape)
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(spec.dtype)


def fan_in_scale(shape: Tuple[int, ...]) -> float:
    """The default init scale 1/sqrt(fan_in) of one weight of ``shape``."""
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1] if shape else 1, 1)
    return 1.0 / float(np.sqrt(fan_in))


def init_params(key: jax.Array, specs):
    leaves, treedef = tree_specs(specs)
    keys = jax.random.split(key, max(len(leaves), 1))
    arrs = [init_one(k, s) for k, s in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, arrs)


def abstract_params(specs):
    """ShapeDtypeStruct pytree for .lower() without allocation."""
    leaves, treedef = tree_specs(specs)
    return jax.tree_util.tree_unflatten(treedef, [s.sds for s in leaves])


def stack_specs(specs, n: int, axis_name: str = "layers"):
    """Add a leading stacking dimension (for lax.scan over layers).  The
    default init scale is resolved per layer first: the stacking axis is
    not a fan-in."""
    leaves, treedef = tree_specs(specs)
    stacked = [
        ParamSpec((n,) + s.shape, (axis_name,) + s.axes, s.dtype, s.init,
                  s.scale if s.scale is not None else fan_in_scale(s.shape))
        for s in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, stacked)


def param_count(specs) -> int:
    leaves, _ = tree_specs(specs)
    return int(sum(np.prod(s.shape) for s in leaves))


def param_bytes(specs) -> int:
    leaves, _ = tree_specs(specs)
    return int(sum(np.prod(s.shape) * jnp.dtype(s.dtype).itemsize for s in leaves))
