"""The float32 reference's shared parts, the controls, and gap scoring.

Each architecture (``architectures/``) computes its own layers with what
is here: ``matmul`` is every matrix product, in float32 at
``Precision.HIGHEST`` for the reference, and in a precision below the
model's bf16 for a control: ``mode="int8"`` quantizes every weight matrix
per output channel and every activation entering a projection per token,
symmetric int8, products accumulated in int32; ``mode="fp8"`` rounds the
same operands, scaled the same way, to float8 e4m3 and accumulates in
float32. Attention scores and softmax stay float32. They are the lower
precisions a later change to a bf16 model would be tempted by.

``gaps`` scores served sequences with an architecture's ``logits``: at
each position that produced a served token, how far that token's
reference logit lies below the reference's best. For a control, the token
at each position is the control's own first choice.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


@jax.jit
def embed(tok, ids):
    return jnp.take(tok, ids, axis=0)


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


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


def gaps(logits, config: dict, seed: int, rows: list, controls=(),
         device=None) -> dict:
    """Gaps of the served tokens of ``rows`` (pairs of prompt and served
    tokens) below the float32 reference's best (``"program"``), and of
    each control's own first choices at the same positions; ``logits`` is
    the configuration's architecture's."""
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
