"""Parameter-spec machinery: one declaration drives init, abstract shapes,
and sharding.

Models declare their parameters as pytrees of :class:`ParamSpec` (shape +
logical axes + initializer).  From that single tree we derive:

* ``init_params``     — concrete arrays (smoke tests, examples, training);
* ``abstract_params`` — ``jax.ShapeDtypeStruct`` twins (the multi-pod dry-run
  never allocates);
* ``logical_axes``    — pytree of logical-axis tuples consumed by
  ``repro.sharding.rules`` to build ``NamedSharding`` trees.

This is the MaxText-style "logical axis" pattern, reimplemented minimally in
pure JAX.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declaration of one parameter tensor."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]  # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones | embed | query
    scale: float | None = None  # stddev override for normal init
    dtype: Any = None  # override of the model-wide param dtype

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def _fan_in(shape: tuple[int, ...]) -> int:
    # For projection tensors (in_dims..., out_dims...): treat all but the last
    # axis as fan-in.  Good enough for init purposes.
    if len(shape) <= 1:
        return max(shape[0] if shape else 1, 1)
    return int(np.prod(shape[:-1]))


def _std(spec: ParamSpec) -> float:
    if spec.scale is not None:
        return float(spec.scale)
    if spec.init in ("embed", "query"):
        return 0.02
    return float(1.0 / np.sqrt(_fan_in(spec.shape)))


@functools.partial(jax.jit, static_argnames=("shape", "init", "std", "dtype"))
def _init_leaf(key, *, shape, init, std, dtype):
    """One leaf, drawn and cast in a single program.

    Jitted so the f32 draw, the scale and the cast fuse: a full-width
    stacked leaf never sits on the device in f32 beside its cast copy.
    """
    if init == "zeros":
        val = jnp.zeros(shape)
    elif init == "ones":
        val = jnp.ones(shape)
    elif init in ("normal", "embed", "query"):
        val = std * jax.random.normal(key, shape)
    else:
        raise ValueError(f"unknown init {init!r}")
    return val.astype(dtype)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, key: jax.Array, param_dtype=jnp.float32):
    """Materialise a ParamSpec tree into concrete arrays (deterministic)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        out.append(_init_leaf(
            k, shape=tuple(spec.shape), init=spec.init, std=_std(spec),
            dtype=jnp.dtype(spec.dtype or param_dtype)))
    return jax.tree.unflatten(treedef, out)


def abstract_params(specs, param_dtype=jnp.float32):
    """ShapeDtypeStruct twin of :func:`init_params` — zero allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype or param_dtype),
        specs,
        is_leaf=is_spec,
    )


def logical_axes(specs):
    """Pytree of logical-axis tuples (same structure as the params)."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def stack_specs(spec_tree, n: int, stack_axis_name: str = "layers"):
    """Prepend a stacking dim (e.g. scanned layers) to every spec in a tree."""
    return jax.tree.map(
        lambda s: ParamSpec(
            shape=(n,) + s.shape,
            axes=(stack_axis_name,) + s.axes,
            init=s.init,
            scale=s.scale,
            dtype=s.dtype,
        ),
        spec_tree,
        is_leaf=is_spec,
    )


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return int(sum(np.prod(s.shape) for s in leaves))
