"""The benchmark's weights: one rule, drawn from the seed.

Weights are data, like prompts: the benchmark makes them and hands them to
the system under test, and the plain reference draws the same ones again
from the seed on its own. Each architecture (``architectures/``) names its
leaves and their ids and shapes; every leaf of layer ``l`` comes from the
key ``fold_in(fold_in(seed_key, leaf_id), l)``, so one layer can be drawn
alone (the reference does, layer by layer) and all layers at once (the
program's stacked tree, in one jitted call) with identical bits.

Values are drawn in float32 and rounded to bfloat16, the type they are
served in; the reference computes in float32 on those same bf16 values.
Matrices are N(0, 1/fan_in); norm weights are 1 + N(0, 0.1^2) so that a
dropped norm weight shows; token embeddings are N(0, 1).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

SERVED_DTYPE = jnp.bfloat16
NORM_STD = 0.1


class Sizes(dict):
    """An architecture's widths by name. Hashable, so that a jitted
    function can take them as a static argument; never changed once
    made."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def seed_key(seed: int) -> jax.Array:
    """A threefry key from all 64 bits of ``seed`` (``jax.random.key`` wraps
    seeds of 2**32 and more to the same key)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def draw(key, leaf_id: int, shape: tuple, fan_in, layer=0) -> jax.Array:
    """One leaf (of one layer), drawn in float32 and served as bf16."""
    k = jax.random.fold_in(jax.random.fold_in(key, leaf_id), layer)
    x = jax.random.normal(k, shape, jnp.float32)
    x = 1.0 + NORM_STD * x if fan_in is None else x * (fan_in ** -0.5)
    return x.astype(SERVED_DTYPE)


def on_devices(model, make, key) -> dict:
    """``make(key)``, the seed's weights in the program's tree, made on the
    devices in one jitted call with the shardings ``model`` gives them."""
    shardings = jax.tree.map(lambda a: a.sharding,
                             model.abstract_params(dtype=SERVED_DTYPE))
    want = jax.tree.structure(shardings)
    got = jax.tree.structure(jax.eval_shape(make, key))
    if want != got:
        raise ValueError(f"the program's parameter tree changed: {want} "
                         f"is not the benchmark's {got}")
    return jax.jit(make, out_shardings=shardings)(key)
