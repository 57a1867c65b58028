"""Parameter-spec machinery.

A model is described by a nested dict of ``ParamSpec``s (shape + logical axis
names + init). From one spec tree we derive:
  * initialized parameter pytrees (``init_params``),
  * abstract ShapeDtypeStructs with shardings for the dry-run (``abstract_params``),
  * logical-axis trees for sharding rules (``param_axes``).

Logical axis vocabulary (mapped to mesh axes in ``repro.models.sharding``):
  embed      d_model dim of a weight            -> FSDP ('data') when enabled
  mlp        FFN hidden dim                     -> 'model'
  heads      query-head dim                     -> 'model'
  kv_heads   kv-head dim                        -> 'model'
  vocab      vocabulary dim                     -> 'model'
  experts    MoE expert dim                     -> ('data','model') (EP) or None
  layers     stacked-scan leading dim           -> None
  (None)     unsharded dim
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"         # normal | zeros | ones | small_normal
    scale: Optional[float] = None  # stddev override; default 1/sqrt(fan_in)
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack_spec(spec: ParamSpec, n: int) -> ParamSpec:
    """Prepend a scanned 'layers' dim."""
    return dataclasses.replace(
        spec, shape=(n, *spec.shape), axes=("layers", *spec.axes))


def stack_specs(tree, n: int):
    return jax.tree.map(lambda s: stack_spec(s, n), tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def _fan_in(shape: tuple[int, ...]) -> int:
    if len(shape) == 0:
        return 1
    if len(shape) == 1:
        return shape[0]
    return int(np.prod(shape[:-1]))


def _init_one(spec: ParamSpec, key) -> jax.Array:
    dtype = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    scale = spec.scale
    if scale is None:
        scale = 1.0 / np.sqrt(max(1, _fan_in(spec.shape)))
    if spec.init == "small_normal":
        scale = 0.02
    x = jax.random.normal(key, spec.shape, jnp.float32) * scale
    return x.astype(dtype)


def _flatten_with_path(tree, prefix=()):
    if isinstance(tree, ParamSpec):
        yield prefix, tree
        return
    for k in sorted(tree.keys()):
        yield from _flatten_with_path(tree[k], prefix + (k,))


def init_params(specs, rng):
    """Initialize a param pytree from a spec tree, path-deterministic.

    Each leaf's key folds in a stable hash of its path (crc32, not Python's
    per-process salted ``hash``), so one seed gives the same parameters in
    every process."""
    def build(tree, prefix=()):
        if isinstance(tree, ParamSpec):
            key = rng
            for p in prefix:
                key = jax.random.fold_in(
                    key, zlib.crc32(p.encode()) % (2**31))
            return _init_one(tree, key)
        return {k: build(v, prefix + (k,)) for k, v in tree.items()}
    return build(specs)


def param_axes(specs):
    """Same-structure tree of logical-axes tuples."""
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def abstract_params(specs, sharding_fn=None):
    """ShapeDtypeStructs (with shardings if `sharding_fn(axes)` given)."""
    def mk(s: ParamSpec):
        if sharding_fn is None:
            return jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype))
        return jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype),
                                    sharding=sharding_fn(s.axes, s.shape))
    return jax.tree.map(mk, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def count_params(specs) -> int:
    return sum(int(np.prod(s.shape)) for _, s in _flatten_with_path(specs))


def param_bytes(specs) -> int:
    return sum(int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
               for _, s in _flatten_with_path(specs))
