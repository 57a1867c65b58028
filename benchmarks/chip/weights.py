"""The benchmark's weights: one definition, drawn from the seed.

Weights are data, like prompts: the benchmark makes them and hands them to
the system under test, and the plain reference draws the same ones again
from the seed on its own. Every leaf of layer ``l`` comes from the key
``fold_in(fold_in(seed_key, leaf_id), l)``, so one layer can be drawn alone
(the reference does, layer by layer) and all layers at once (the program's
stacked tree, in one jitted call) with identical bits.

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

# leaf name -> id folded into the seed key (fixed forever: changing one
# changes every weight)
LEAF_IDS = {"tok": 1, "out": 2, "final_norm": 3, "ln1": 10, "w_q": 11,
            "w_k": 12, "w_v": 13, "w_o": 14, "ln2": 15, "w_gate": 16,
            "w_up": 17, "w_down": 18}
LAYER_LEAVES = ("ln1", "w_q", "w_k", "w_v", "w_o", "ln2", "w_gate", "w_up",
                "w_down")
NORM_STD = 0.1


def sizes(config: dict) -> dict:
    """The widths the weights need, from a configuration file's keys."""
    return {"d": config["hidden_size"], "f": config["intermediate_size"],
            "h": config["num_attention_heads"],
            "kv": config["num_key_value_heads"], "dh": config["head_dim"],
            "v": config["vocab_size"], "layers": config["num_hidden_layers"]}


def shapes(s: dict) -> dict:
    """leaf -> (shape of one layer's leaf, fan-in or None for a norm)."""
    d, f, h, kv, dh, v = s["d"], s["f"], s["h"], s["kv"], s["dh"], s["v"]
    return {"tok": ((v, d), 1), "out": ((d, v), d), "final_norm": ((d,), None),
            "ln1": ((d,), None), "w_q": ((d, h, dh), d),
            "w_k": ((d, kv, dh), d), "w_v": ((d, kv, dh), d),
            "w_o": ((h, dh, d), h * dh), "ln2": ((d,), None),
            "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f)}


def seed_key(seed: int) -> jax.Array:
    """A threefry key from all 64 bits of ``seed`` (``jax.random.key`` wraps
    seeds of 2**32 and more to the same key)."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def draw(key, name: str, shape: tuple, fan_in, layer=0) -> jax.Array:
    """One leaf (of one layer), drawn in float32 and served as bf16."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    x = jax.random.normal(k, shape, jnp.float32)
    x = 1.0 + NORM_STD * x if fan_in is None else x * (fan_in ** -0.5)
    return x.astype(SERVED_DTYPE)


def layer_weights(key, s: dict, layer) -> dict:
    """Layer ``layer``'s leaves, bf16, by leaf name."""
    sh = shapes(s)
    return {n: draw(key, n, *sh[n], layer=layer) for n in LAYER_LEAVES}


def global_weights(key, s: dict) -> dict:
    sh = shapes(s)
    return {n: draw(key, n, *sh[n]) for n in ("tok", "out", "final_norm")}


def stacked(key, s: dict) -> dict:
    """Every layer's leaves stacked on a leading layer axis, plus the
    global leaves: what the served model holds."""
    per = jax.vmap(lambda l: layer_weights(key, s, l))(
        jnp.arange(s["layers"], dtype=jnp.uint32))
    return {**per, **global_weights(key, s)}
