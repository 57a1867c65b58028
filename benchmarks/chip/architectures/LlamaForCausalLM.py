"""LlamaForCausalLM: a dense llama-style decoder, as the benchmark knows it.

Everything here depends on the layer's shape; what does not (the seed's
key and draw, the precision controls, gap scoring, the engine) is shared
(``harness.py`` gives the contract this file keeps).

- Weights: the leaf ids and shapes, drawn by ``weights.draw`` per layer,
  and the stacked tree the program serves.
- Reference: token embedding, per layer RMSNorm, rotary attention
  (rotate-half, GQA, causal), SwiGLU MLP, a final RMSNorm and the output
  projection, in float32 with every matrix product through
  ``reference.matmul`` (``HIGHEST`` precision, or a control's int8 or
  fp8). No kernel, cache or batching of the program is used; the weights
  are drawn from the seed layer by layer, so the whole model never has to
  fit beside anything else.
- Program: the registered architecture the configuration names
  (``program_arch``), and the weights in its parameter tree.
- Counts: what a dense llama-style decoder has to do for the tokens
  really served, never what a compiled program happens to do: prefill
  over the real prompt tokens with causal attention (half the score
  matrix), decode over each row's live context only, not the cache's
  allocated length. A multiply-add is two operations. ``s`` is ``sizes``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

import reference as R
import weights as W

# leaf name -> id folded into the seed key (fixed forever: changing one
# changes every weight)
LEAF_IDS = {"tok": 1, "out": 2, "final_norm": 3, "ln1": 10, "w_q": 11,
            "w_k": 12, "w_v": 13, "w_o": 14, "ln2": 15, "w_gate": 16,
            "w_up": 17, "w_down": 18}
LAYER_LEAVES = ("ln1", "w_q", "w_k", "w_v", "w_o", "ln2", "w_gate", "w_up",
                "w_down")
Q_CHUNK = 256          # query rows per attention block: bounds the scores
LEN_STEP = 256         # sequences are right-padded to a multiple of this
BF16 = 2               # bytes per served weight, activation and cache element


def sizes(config: dict) -> W.Sizes:
    """The widths the weights need, from a configuration file's keys."""
    return W.Sizes(d=config["hidden_size"], f=config["intermediate_size"],
                   h=config["num_attention_heads"],
                   kv=config["num_key_value_heads"], dh=config["head_dim"],
                   v=config["vocab_size"], layers=config["num_hidden_layers"])


# -- weights ---------------------------------------------------------------

def shapes(s: dict) -> dict:
    """leaf -> (shape of one layer's leaf, fan-in or None for a norm)."""
    d, f, h, kv, dh, v = s["d"], s["f"], s["h"], s["kv"], s["dh"], s["v"]
    return {"tok": ((v, d), 1), "out": ((d, v), d), "final_norm": ((d,), None),
            "ln1": ((d,), None), "w_q": ((d, h, dh), d),
            "w_k": ((d, kv, dh), d), "w_v": ((d, kv, dh), d),
            "w_o": ((h, dh, d), h * dh), "ln2": ((d,), None),
            "w_gate": ((d, f), d), "w_up": ((d, f), d), "w_down": ((f, d), f)}


def layer_weights(key, s: dict, layer) -> dict:
    """Layer ``layer``'s leaves, bf16, by leaf name."""
    sh = shapes(s)
    return {n: W.draw(key, LEAF_IDS[n], *sh[n], layer=layer)
            for n in LAYER_LEAVES}


def global_weights(key, s: dict) -> dict:
    sh = shapes(s)
    return {n: W.draw(key, LEAF_IDS[n], *sh[n])
            for n in ("tok", "out", "final_norm")}


def stacked(key, s: dict) -> dict:
    """Every layer's leaves stacked on a leading layer axis, plus the
    global leaves: what the served model holds."""
    per = jax.vmap(lambda l: layer_weights(key, s, l))(
        jnp.arange(s["layers"], dtype=jnp.uint32))
    return {**per, **global_weights(key, s)}


# -- reference ---------------------------------------------------------------

def rope(x, theta):
    """x: (T, heads, dh), positions 0..T-1, rotate-half convention."""
    T, _, dh = x.shape
    half = dh // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA. q: (T, H, dh); k, v: (T, KV, dh) -> (T, H, dh)."""
    T, H, dh = q.shape
    KV = k.shape[1]
    qg = q.reshape(T // Q_CHUNK, Q_CHUNK, KV, H // KV, dh)
    kpos = jnp.arange(T)

    def block(args):
        qc, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qc, k, precision=R.HI) * dh ** -0.5
        qpos = i * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=R.HI)
    out = jax.lax.map(block, (qg, jnp.arange(T // Q_CHUNK)))
    return out.reshape(T, H, dh)


@partial(jax.jit, static_argnames=("mode", "theta", "eps"))
def layer(w, x, *, mode, theta, eps):
    """One decoder layer on one sequence x: (T, d)."""
    T, d = x.shape
    H, dh = w["w_q"].shape[1:]
    KV = w["w_k"].shape[1]
    matmul = R.matmul
    h = R.rmsnorm(x, w["ln1"], eps)
    q = matmul(h, w["w_q"].reshape(d, H * dh), mode).reshape(T, H, dh)
    k = matmul(h, w["w_k"].reshape(d, KV * dh), mode).reshape(T, KV, dh)
    v = matmul(h, w["w_v"].reshape(d, KV * dh), mode).reshape(T, KV, dh)
    a = attention(rope(q, theta), rope(k, theta), v)
    x = x + matmul(a.reshape(T, H * dh), w["w_o"].reshape(H * dh, d), mode)
    h = R.rmsnorm(x, w["ln2"], eps)
    g = matmul(h, w["w_gate"], mode)
    u = matmul(h, w["w_up"], mode)
    return x + matmul(jax.nn.silu(g) * u, w["w_down"], mode)


@partial(jax.jit, static_argnames=("mode", "eps"))
def head(g, x, *, mode, eps):
    """Final norm and output projection: logits (T, vocab)."""
    return R.matmul(R.rmsnorm(x, g["final_norm"], eps), g["out"], mode)


@partial(jax.jit, static_argnames=("s",))
def _layer_weights(key, s, layer_idx):
    return R.f32(layer_weights(key, s, layer_idx))


@partial(jax.jit, static_argnames=("s",))
def _global_weights(key, s):
    return R.f32(global_weights(key, s))


def padded_len(n: int) -> int:
    return -(-n // LEN_STEP) * LEN_STEP


def logits(config: dict, seed: int, seqs: list, positions: list,
           mode: str = "f32", device=None) -> list:
    """Logits (len(pos), vocab) float32 at ``positions`` of each token
    sequence, on ``device``. Each sequence is right-padded, which causal
    attention keeps from every earlier position."""
    s = sizes(config)
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    device = device or jax.devices()[0]
    with jax.default_device(device):
        key = W.seed_key(seed)
        g = _global_weights(key, s)
        xs = []
        for t in seqs:
            ids = np.zeros(padded_len(len(t)), np.int32)
            ids[:len(t)] = t
            xs.append(R.embed(g["tok"], jnp.asarray(ids)))
        for l in range(s["layers"]):
            w = _layer_weights(key, s, jnp.uint32(l))
            xs = [layer(w, x, mode=mode, theta=theta, eps=eps) for x in xs]
            del w
        return [head(g, x[jnp.asarray(pos)], mode=mode, eps=eps)
                for x, pos in zip(xs, positions)]


# -- program -----------------------------------------------------------------

def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    registered architecture with every size the file states."""
    from repro.config.base import get_config
    cfg = dataclasses.replace(
        get_config(config["program_arch"]),
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=config["torch_dtype"])
    if cfg.family != "dense" or cfg.attn_type != "full" or cfg.qkv_bias:
        raise ValueError(f"{config['name']}: the program's "
                         f"{config['program_arch']} is not a dense llama "
                         f"block, which is all this reference computes")
    return cfg


def program_tree(key, s: dict) -> dict:
    """The seed's weights in the program's parameter tree."""
    w = stacked(key, s)
    return {"embed": {"tok": w["tok"], "out": w["out"]},
            "final_norm": w["final_norm"],
            "decoder": {"ln1": w["ln1"], "ln2": w["ln2"],
                        "attn": {n: w[n] for n in
                                 ("w_q", "w_k", "w_v", "w_o")},
                        "mlp": {n: w[n] for n in
                                ("w_gate", "w_up", "w_down")}}}


def program_params(model, s: dict, key) -> dict:
    return W.on_devices(model, partial(program_tree, s=s), key)


# -- counts ------------------------------------------------------------------

def layer_matrix_params(s: dict) -> int:
    d, f, h, kv, dh = s["d"], s["f"], s["h"], s["kv"], s["dh"]
    return d * (h + 2 * kv) * dh + h * dh * d + 3 * d * f


def params(s: dict) -> int:
    """Every parameter: embedding, output projection, the layers' matrices
    and norm weights, and the final norm."""
    d = s["d"]
    return (2 * s["v"] * d + d
            + s["layers"] * (layer_matrix_params(s) + 2 * d))


def attention_flops(s: dict, q_len: int, kv_len: int) -> int:
    """Scores and weighted values for ``q_len`` queries, the last of which
    sees ``kv_len`` keys, causal: query i sees kv_len - q_len + i + 1."""
    first = kv_len - q_len + 1
    seen = q_len * (first + kv_len) // 2
    return 4 * s["h"] * s["dh"] * seen * s["layers"]


def prefill_flops(s: dict, prompt_lens) -> int:
    """Prefill of a batch: every layer over every prompt token, and the
    output projection at each row's last token."""
    lin = 2 * s["layers"] * layer_matrix_params(s)
    return sum(t * lin + attention_flops(s, t, t) + 2 * s["d"] * s["v"]
               for t in prompt_lens)


def decode_flops(s: dict, contexts) -> int:
    """Decode steps: one token per (row, step) whose context, the token
    included, is each entry of ``contexts``."""
    per_tok = (2 * s["layers"] * layer_matrix_params(s)
               + 2 * s["d"] * s["v"])
    return sum(per_tok + attention_flops(s, 1, c) for c in contexts)


def kv_bytes(s: dict, tokens: int) -> int:
    """Keys and values of ``tokens`` positions over all layers."""
    return 2 * s["kv"] * s["dh"] * BF16 * s["layers"] * tokens


def decode_step_bytes(s: dict, contexts, chips: int = 1) -> float:
    """HBM bytes one decode step needs on each chip: every weight matrix
    and the output projection once (split over ``chips``), the norms on
    every chip, the batch's embedding rows, and each row's live keys and
    values (split over ``chips``) with the new ones written."""
    d = s["d"]
    sharded = (s["layers"] * layer_matrix_params(s) + d * s["v"]) * BF16
    norms = (2 * s["layers"] + 1) * d * BF16
    rows = len(contexts) * d * BF16
    kv = sum(kv_bytes(s, c) for c in contexts)
    return (sharded + kv) / chips + norms + rows


def flash_flops(s: dict, batch: int, t: int) -> int:
    """The flash kernel in one prefill: causal attention of ``batch`` rows
    of ``t`` tokens over every layer."""
    return batch * attention_flops(s, t, t)


def flash_bytes(s: dict, batch: int, t: int) -> int:
    """Its HBM traffic at the least: q, k and v read once, out written."""
    return batch * t * s["dh"] * BF16 * (2 * s["h"] + 2 * s["kv"]) \
        * s["layers"]
