"""Plain reference of a dense llama-style decoder, and its int8 control.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: token embedding, per layer RMSNorm, rotary
attention (rotate-half, GQA, causal), SwiGLU MLP, a final RMSNorm and the
output projection. No kernel, cache or batching of the program is used;
the weights are drawn from the seed by ``weights`` layer by layer, so the
whole model never has to fit beside anything else.

The controls are the same pass in a precision below the model's bf16:
``mode="int8"`` quantizes every weight matrix per output channel and every
activation entering a projection per token, symmetric int8, products
accumulated in int32; ``mode="fp8"`` rounds the same operands, scaled the
same way, to float8 e4m3 and accumulates in float32. Attention scores and
softmax stay float32. They are the lower precisions a later change to a
bf16 model would be tempted by.

``gaps`` scores served sequences: at each position that produced a served
token, how far that token's reference logit lies below the reference's
best. For a control, the token at each position is the control's own
first choice.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

import weights as W

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 256          # query rows per attention block: bounds the scores
LEN_STEP = 256         # sequences are right-padded to a multiple of this


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (T, heads, dh), positions 0..T-1, rotate-half convention."""
    T, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q8(x, axis):
    """Symmetric int8 along ``axis``: (int8 values, float32 scales)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _f8(x, axis):
    """x rounded to float8 e4m3 after scaling its largest magnitude along
    ``axis`` to the format's largest (448): (values as float32, scales)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32), s


def matmul(x, w, mode):
    """x: (T, k) @ w: (k, n) in float32, int8 (int32 sums) or fp8."""
    if mode == "f32":
        return jnp.dot(x, w, precision=HI)
    if mode == "fp8":
        (xq, xs), (wq, ws) = _f8(x, 1), _f8(w, 0)
        return jnp.dot(xq, wq, precision=HI) * xs * ws
    (xq, xs), (wq, ws) = _q8(x, 1), _q8(w, 0)
    acc = jax.lax.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def attention(q, k, v):
    """Causal GQA. q: (T, H, dh); k, v: (T, KV, dh) -> (T, H, dh)."""
    T, H, dh = q.shape
    KV = k.shape[1]
    qg = q.reshape(T // Q_CHUNK, Q_CHUNK, KV, H // KV, dh)
    kpos = jnp.arange(T)

    def block(args):
        qc, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qc, k, precision=HI) * dh ** -0.5
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)
    out = jax.lax.map(block, (qg, jnp.arange(T // Q_CHUNK)))
    return out.reshape(T, H, dh)


@partial(jax.jit, static_argnames=("mode", "theta", "eps"))
def layer(w, x, *, mode, theta, eps):
    """One decoder layer on one sequence x: (T, d)."""
    T, d = x.shape
    H, dh = w["w_q"].shape[1:]
    KV = w["w_k"].shape[1]
    h = rmsnorm(x, w["ln1"], eps)
    q = matmul(h, w["w_q"].reshape(d, H * dh), mode).reshape(T, H, dh)
    k = matmul(h, w["w_k"].reshape(d, KV * dh), mode).reshape(T, KV, dh)
    v = matmul(h, w["w_v"].reshape(d, KV * dh), mode).reshape(T, KV, dh)
    a = attention(rope(q, theta), rope(k, theta), v)
    x = x + matmul(a.reshape(T, H * dh), w["w_o"].reshape(H * dh, d), mode)
    h = rmsnorm(x, w["ln2"], eps)
    g = matmul(h, w["w_gate"], mode)
    u = matmul(h, w["w_up"], mode)
    return x + matmul(jax.nn.silu(g) * u, w["w_down"], mode)


@partial(jax.jit, static_argnames=("mode", "eps"))
def head(g, x, *, mode, eps):
    """Final norm and output projection: logits (T, vocab)."""
    return matmul(rmsnorm(x, g["final_norm"], eps), g["out"], mode)


@jax.jit
def embed(tok, ids):
    return jnp.take(tok, ids, axis=0)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


@partial(jax.jit, static_argnames=("s",))
def _layer_weights(key, s, layer_idx):
    return _f32(W.layer_weights(key, dict(s), layer_idx))


@partial(jax.jit, static_argnames=("s",))
def _global_weights(key, s):
    return _f32(W.global_weights(key, dict(s)))


def padded_len(n: int) -> int:
    return -(-n // LEN_STEP) * LEN_STEP


def logits(config: dict, seed: int, seqs: list, positions: list,
           mode: str = "f32", device=None) -> list:
    """Logits (len(pos), vocab) float32 at ``positions`` of each token
    sequence, on ``device``. Each sequence is right-padded, which causal
    attention keeps from every earlier position."""
    s = W.sizes(config)
    frozen = tuple(sorted(s.items()))
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    device = device or jax.devices()[0]
    with jax.default_device(device):
        key = W.seed_key(seed)
        g = _global_weights(key, frozen)
        xs = []
        for t in seqs:
            ids = np.zeros(padded_len(len(t)), np.int32)
            ids[:len(t)] = t
            xs.append(embed(g["tok"], jnp.asarray(ids)))
        for l in range(s["layers"]):
            w = _layer_weights(key, frozen, jnp.uint32(l))
            xs = [layer(w, x, mode=mode, theta=theta, eps=eps) for x in xs]
            del w
        return [head(g, x[jnp.asarray(pos)], mode=mode, eps=eps)
                for x, pos in zip(xs, positions)]


@jax.jit
def _gap(ref, tokens):
    """ref: (n, V) reference logits; tokens: (n,) -> (n,) gaps."""
    best = jnp.max(ref, axis=-1)
    return best - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]


def served_sequence(prompt, served):
    """The tokens the reference reads (the prompt, then every served token
    but the last) and the positions whose next token was served."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    pos = np.arange(len(prompt) - 1, len(seq))
    return seq, pos


def _stats(g: np.ndarray) -> dict:
    """The widest gap, the mean gap and the share of positions whose token
    is not the reference's first choice."""
    return {"max": float(g.max()), "mean": float(g.mean()),
            "off": float(np.mean(g > 0))}


def gaps(config: dict, seed: int, rows: list, controls=(),
         device=None) -> dict:
    """Gaps of the served tokens of ``rows`` (pairs of prompt and served
    tokens) below the float32 reference's best (``"program"``), and of
    each control's own first choices at the same positions."""
    seqs, poss = map(list, zip(*(served_sequence(p, t) for p, t in rows)))
    ref = logits(config, seed, seqs, poss, "f32", device)
    out = {"program": _stats(np.concatenate(
        [np.asarray(_gap(r, jnp.asarray(t)))
         for r, (_, t) in zip(ref, rows)]))}
    for mode in controls:
        low = logits(config, seed, seqs, poss, mode, device)
        out[mode] = _stats(np.concatenate(
            [np.asarray(_gap(r, jnp.argmax(c, -1)))
             for r, c in zip(ref, low)]))
    out["tokens"] = int(sum(len(t) for _, t in rows))
    return out
