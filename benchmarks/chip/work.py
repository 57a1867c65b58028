"""Operations and bytes the algorithm needs, from sizes and request shapes.

These count what a dense llama-style decoder has to do for the tokens
really served, never what a compiled program happens to do: prefill over
the real prompt tokens with causal attention (half the score matrix),
decode over each row's live context only, not the cache's allocated
length. A multiply-add is two operations. ``s`` is ``weights.sizes``.
"""

from __future__ import annotations

BF16 = 2      # bytes per served weight, activation and cache element


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
