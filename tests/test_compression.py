"""int8 transfer/gradient compression: error bounds + error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.compression import (decompress_tree, dequantize_int8,
                                    ef_compress, ef_compress_tree, ef_init,
                                    quantize_int8, roundtrip_int8)


@given(n=st.integers(1, 2048), scale=st.floats(1e-3, 1e3))
@settings(max_examples=60, deadline=None)
def test_roundtrip_error_bound(n, scale):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n,)) * scale, jnp.float32)
    y = roundtrip_int8(x, block=256)
    # symmetric int8: per-block error <= absmax/127/2 (+rounding slack)
    blocks = np.asarray(x)
    err = np.abs(np.asarray(y) - blocks)
    bound = np.abs(blocks).max() / 127.0 * 0.55 + 1e-9
    assert err.max() <= max(bound, np.abs(blocks).max() / 127.0)


def test_quantize_shapes():
    x = jnp.ones((1000,), jnp.float32)
    q, s, shape = quantize_int8(x, block=256)
    assert q.shape == (4, 256) and s.shape == (4,)
    y = dequantize_int8(q, s, shape)
    assert y.shape == (1000,)
    np.testing.assert_allclose(np.asarray(y), 1.0, rtol=1e-2)


def test_error_feedback_is_unbiased_over_steps():
    """With EF, the *accumulated* compressed updates converge to the
    accumulated true gradients (the 1-bit-Adam guarantee)."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=(512,)), jnp.float32) * 1e-3
    residual = jnp.zeros((512,), jnp.float32)
    applied = jnp.zeros((512,), jnp.float32)
    for _ in range(50):
        (q, s), residual = ef_compress(g_true, residual, block=256)
        applied += dequantize_int8(q, s, (512,))
    target = g_true * 50
    np.testing.assert_allclose(np.asarray(applied), np.asarray(target),
                               atol=float(jnp.abs(g_true).max()) * 1.1)


def test_ef_tree_roundtrip():
    params = {"a": jnp.ones((300,)), "b": {"c": jnp.ones((256, 2))}}
    grads = jax.tree.map(lambda p: p * 0.1, params)
    res = ef_init(params)
    comp, res2 = ef_compress_tree(grads, res)
    dec = decompress_tree(comp)
    for g, d in zip(jax.tree.leaves(grads), jax.tree.leaves(dec)):
        np.testing.assert_allclose(np.asarray(d), np.asarray(g), atol=2e-3)


def test_compressed_pod_mean_single_axis():
    """compressed_pod_mean inside shard_map == plain mean (1 pod)."""
    from functools import partial
    from repro.core.compression import compressed_pod_mean
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("pod",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(512,)),
                    jnp.float32)
    fn = jax.shard_map(partial(compressed_pod_mean, pod_axis="pod"),
                       mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    # int8 error bound: absmax/127/2 ~ 1.4e-2 for N(0,1) extremes
    np.testing.assert_allclose(np.asarray(fn(x)), np.asarray(x), atol=3e-2)
