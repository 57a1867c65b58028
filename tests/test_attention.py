"""Chunked attention vs naive reference; decode/prefill parity primitives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (chunked_attention, decode_attention,
                                    decode_attention_with_row, decode_slot)


def naive(q, k, v, causal=True, window=0, scale=None):
    B, Sq, Hq, dh = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = dh ** -0.5 if scale is None else scale
    qg = q.reshape(B, Sq, Hkv, G, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, Hq, dh)


@pytest.mark.parametrize("causal,window,q_chunk", [
    (True, 0, 16), (True, 0, 64), (False, 0, 16),
    (True, 32, 16), (True, 16, 8),
])
def test_chunked_matches_naive(causal, window, q_chunk):
    rng = np.random.default_rng(0)
    B, Hq, Hkv, S, dh = 2, 4, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, S, Hq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    out = chunked_attention(q, k, v, causal=causal, window=window,
                            q_chunk=q_chunk)
    ref = naive(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_matches_last_row_of_prefill():
    rng = np.random.default_rng(1)
    B, Hq, Hkv, S, dh = 2, 4, 2, 32, 16
    q = jnp.asarray(rng.normal(size=(B, S, Hq, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)), jnp.float32)
    full = chunked_attention(q, k, v, causal=True, q_chunk=8)
    dec = decode_attention(q[:, -1:], k, v,
                           valid_mask=jnp.arange(S) <= S - 1)
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]),
                               rtol=2e-5, atol=2e-5)



@pytest.mark.parametrize("S,window,pos", [
    (16, 0, 0),     # empty prefix: the new row is all there is
    (16, 0, 9),     # mid-cache: slots past pos hold stale values
    (8, 8, 13),     # wrapped ring: slot 13 % 8 holds position 5
])
def test_decode_with_row_matches_written_cache(S, window, pos):
    """Attention over the unmodified cache plus the new row equals
    attention over the cache with the row written at its slot."""
    rng = np.random.default_rng(2)
    B, Hq, Hkv, dh = 2, 4, 2, 16

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = normal(B, 1, Hq, dh)
    k_cache, v_cache = normal(B, S, Hkv, dh), normal(B, S, Hkv, dh)
    k_new, v_new = normal(B, 1, Hkv, dh), normal(B, 1, Hkv, dh)
    slot = decode_slot(pos, S, window)
    slots = jnp.arange(S)
    out = decode_attention_with_row(q, k_cache, v_cache,
                                    (slots < pos) & (slots != slot),
                                    k_new, v_new)
    written = decode_attention(q, k_cache.at[:, slot].set(k_new[:, 0]),
                               v_cache.at[:, slot].set(v_new[:, 0]),
                               (slots <= pos) | (window > 0 and pos >= S))
    np.testing.assert_allclose(np.asarray(out), np.asarray(written),
                               rtol=2e-6, atol=2e-6)

def test_mla_shapes_and_grad():
    from repro.config.base import get_config
    from repro.models.attention import mla_forward, mla_specs
    from repro.models.params import init_params
    cfg = get_config("deepseek-v3-671b").reduced()
    p = init_params(mla_specs(cfg), jax.random.key(0))
    x = jnp.ones((2, 16, cfg.d_model), jnp.float32) * 0.1
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))

    def f(p):
        out, _ = mla_forward(p, x, pos, cfg, q_chunk=8)
        return jnp.sum(out ** 2)
    g = jax.grad(f)(p)
    assert all(not bool(jnp.isnan(l).any()) for l in jax.tree.leaves(g))
