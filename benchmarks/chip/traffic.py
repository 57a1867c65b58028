"""The one traffic generator: a traffic file's parameters -> batches.

Every traffic mix is a JSON file under ``traffic/`` read by this code:

- ``batch_size``: requests per batch; the loop is closed (one caller sends
  a batch when the last one has returned);
- ``prompt_len`` and ``max_new``: ``{"values": [...], "weights": [...]}``,
  fixed values so that the padded shapes, and so the programs compiled,
  are few and all warmed up;
- ``batches``: the schedule's length; a window that outlasts it starts it
  again.

The amount of work is fixed and the seed changes only its content and
order within a batch: all prompts of a batch have one length
(length-bucketed batching, which the engine serves without padding), the
batches' prompt lengths follow a smooth weighted round robin, so that
every prefix of the schedule holds the lengths in proportion, and each
batch's count of each output length is the weights apportioned to the
batch (largest remainder). The seed draws the prompt tokens and which row
gets which output length.
"""

from __future__ import annotations

import numpy as np


def apportion(n: int, weights) -> list[int]:
    """Integer counts summing to ``n`` in proportion to ``weights``
    (largest remainder, ties to the earlier value)."""
    w = np.asarray(weights, float)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    order = sorted(range(len(w)), key=lambda i: (-(exact[i] - counts[i]), i))
    for i in order[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def round_robin(weights, n: int) -> list[int]:
    """Smooth weighted round robin: ``n`` indices, each prefix in
    proportion to ``weights`` as nearly as whole counts allow."""
    w = np.asarray(weights, float)
    cur = np.zeros_like(w)
    out = []
    for _ in range(n):
        cur += w
        i = int(np.argmax(cur))
        cur[i] -= w.sum()
        out.append(i)
    return out


def _rows(n: int, dist: dict, rng) -> np.ndarray:
    vals = np.repeat(dist["values"], apportion(n, dist["weights"]))
    return rng.permutation(vals)


def schedule(traffic: dict, vocab: int, seed: int) -> list[list[tuple]]:
    """The run's batches: lists of (prompt int32 array, max_new)."""
    rng = np.random.default_rng(seed)
    B, n = traffic["batch_size"], traffic["batches"]
    plen, new = traffic["prompt_len"], traffic["max_new"]
    lens = [np.full(B, plen["values"][i])
            for i in round_robin(plen["weights"], n)]
    out = []
    for lb in lens:
        mb = _rows(B, new, rng)
        out.append([(rng.integers(0, vocab, int(L), dtype=np.int32), int(m))
                    for L, m in zip(lb, mb)])
    return out


def shapes(batches: list) -> dict:
    """(padded prompt length, decode steps) -> the first batch of that
    shape, in order of first use: one prefill and one decode program
    each."""
    seen = {}
    for b in batches:
        seen.setdefault((max(len(p) for p, _ in b), max(m for _, m in b)), b)
    return seen
