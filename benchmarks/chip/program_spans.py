"""The serving engine's own spans in a trace, and the device's idle time
inside them.

``ServeEngine`` (``src/repro/launch/serve.py``) annotates its calls on the
profiler's clock: ``serve.prefill`` and ``serve.decode`` around each call,
``serve.decode_step`` around each decode step (the steps tile the call),
and inside a prefill call or a step the host phases ``serve.inputs``,
``serve.dispatch``, ``serve.sample``, ``serve.readback`` and ``serve.emit``,
one after another; ``python.gc`` covers each collection of generation 1 or
2, inside whatever phase it interrupts. A program without these spans
gives no windows, and the readers built on this module then read ``None``.

Every sweep here is a sort and a linear pass (``trace_reduce``'s
``merge`` / ``overlap`` / ``subtract``), so a window of thousands of steps
costs no more than the reduction itself.
"""

from __future__ import annotations

import trace_reduce as TR

PREFILL, DECODE, STEP = "serve.prefill", "serve.decode", "serve.decode_step"
PHASES = ("serve.inputs", "serve.dispatch", "serve.sample",
          "serve.readback", "serve.emit")
GC = "python.gc"
NONE = "none"


def spans(tr: TR.Trace, name: str) -> list:
    """Sorted (start, end) of the host events called ``name``."""
    return sorted((s, e) for n, s, e in tr.host if n == name)


def idle_split(tr: TR.Trace, windows: list) -> dict:
    """Device idle time inside ``windows`` (merged), in ns averaged over the
    chips, split by the innermost program span that covers it: a phase,
    ``python.gc`` (which wins over the phase it interrupts), or ``none``.
    The values sum to the windows' idle time."""
    gc = TR.merge(spans(tr, GC))
    phases = {p: TR.subtract(TR.merge(spans(tr, p)), gc) for p in PHASES}
    out = dict.fromkeys((*PHASES, GC, NONE), 0.0)
    for chip in tr.chips:
        busy = TR.merge((o[1], o[2]) for o in tr.ops[chip])
        idle = TR.subtract(windows, busy)
        named = 0.0
        for label, iv in (*phases.items(), (GC, gc)):
            t = TR.overlap(idle, iv)
            out[label] += t / len(tr.chips)
            named += t
        out[NONE] += (TR.length(idle) - named) / len(tr.chips)
    return out


def longest_idle(tr: TR.Trace, windows: list) -> tuple:
    """(ns, label) of the longest device idle interval inside ``windows``
    on the first chip, labelled as ``idle_split`` labels time: by the
    innermost program span that covers the interval's middle."""
    busy = TR.merge((o[1], o[2]) for o in tr.ops[tr.chips[0]])
    s, e = max(TR.subtract(windows, busy), key=lambda g: g[1] - g[0])
    mid = (s + e) / 2
    label = next((n for n in (GC, *PHASES)
                  if any(a <= mid <= b for a, b in spans(tr, n))), NONE)
    return e - s, label


def describe_split(split: dict, count: int, per: str) -> str:
    """``label ms/<per> (share %)`` for each part of an idle split."""
    total = sum(split.values()) or 1.0
    return ", ".join(f"{k} {v * 1e-6 / count:.4f} ms/{per} "
                     f"({100.0 * v / total:.1f}%)"
                     for k, v in split.items())


def steps_per_call(tr: TR.Trace) -> list:
    """For each ``serve.decode`` span, in order, the ``serve.decode_step``
    spans that start inside it: [[(start, end), ...], ...]."""
    calls = spans(tr, DECODE)
    out = [[] for _ in calls]
    i = 0
    for s, e in spans(tr, STEP):
        while i < len(calls) and calls[i][1] <= s:
            i += 1
        if i < len(calls) and calls[i][0] <= s:
            out[i].append((s, e))
    return out
