"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``), whose
``XLA Ops`` line has one event per operation run, named by its HLO
instruction (``%fusion.12 = bf16[...] fusion(...)``, kept here as
``fusion.12``), and host planes whose lines hold the host's events, the
harness's ``bench.*`` annotations among them. Host and device events share
one clock (nanoseconds). Operations nest: a ``while`` loop's event spans
the events of its body, so time by operation is self time (an event's
duration less its children's), and exposure is judged against leaves.

From that this module gives, per chip: busy time (the union of the
operations' intervals) inside a set of windows, the time of the operations
a predicate picks, and the part of the collectives' time during which no
other operation ran; and over all chips, the operations that took most
time and the longest idle gaps with what the host was doing in them.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@dataclasses.dataclass
class Trace:
    ops: dict            # chip -> [(name, start_ns, end_ns, self_ns, leaf)]
    host: list           # [(name, start_ns, end_ns)] of every host line
    spans: list          # the ``bench.*`` host events

    @property
    def chips(self) -> list:
        return sorted(self.ops)

    def windows(self, name: str) -> list:
        return merge((s, e) for n, s, e in self.spans if n == name)

    def extent(self) -> tuple:
        """First span's start to last span's end."""
        return (min(s for _, s, _ in self.spans),
                max(e for _, _, e in self.spans))


def nest(events) -> list:
    """(name, start, end) -> (name, start, end, self time, is a leaf), by
    start, an event nesting in the last open one that covers it."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, s, e, e - s, True] for n, s, e in evs]
    stack = []
    for i, (_, s, e, _, _) in enumerate(out):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= out[stack[-1]][2]:
            parent = out[stack[-1]]
            parent[3] -= e - s
            parent[4] = False
        stack.append(i)
    return [tuple(x) for x in out]


def short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = nest(
                        (short(e.name), e.start_ns, e.end_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events)
    spans = [h for h in host if h[0].startswith(SPAN_PREFIX)]
    return Trace(ops, host, spans)


def merge(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def overlap(a: list, b: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def subtract(a: list, b: list) -> list:
    """Merged ``a`` minus merged ``b``, in one pass over both."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def length(iv: list) -> float:
    return sum(e - s for s, e in iv)


def busy_ns(tr: Trace, chip: int, windows: list) -> float:
    return overlap(merge((o[1], o[2]) for o in tr.ops[chip]), windows)


def mean_busy_ns(tr: Trace, windows: list) -> float:
    """Busy time in windows, averaged over the chips."""
    return sum(busy_ns(tr, c, windows) for c in tr.chips) / len(tr.chips)


def op_ns(tr: Trace, chip: int, pick, windows: list) -> float:
    """Time of the operations whose name ``pick`` accepts, in windows."""
    return overlap(merge((o[1], o[2]) for o in tr.ops[chip] if pick(o[0])),
                   windows)


def exposed_collective_ns(tr: Trace, chip: int, windows: list) -> float:
    """Collective time in windows during which no other leaf operation
    ran."""
    leaves = [o for o in tr.ops[chip] if o[4]]
    coll = merge((o[1], o[2]) for o in leaves if COLLECTIVE.search(o[0]))
    other = merge((o[1], o[2]) for o in leaves
                  if not COLLECTIVE.search(o[0]))
    return overlap(subtract(coll, other), windows)


def top_ops(tr: Trace, n: int = 10) -> list:
    """[name, self seconds per chip] of the operations that took most
    time."""
    tot = defaultdict(float)
    for chip in tr.chips:
        for name, _, _, self_ns, _ in tr.ops[chip]:
            tot[name] += self_ns * 1e-9 / len(tr.chips)
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> list:
    """[what the host was doing, seconds] of the longest gaps between
    operations on the first chip inside the traced extent. The label is
    the shortest host event that covers the gap's middle, inside the
    ``bench.*`` span that covers it."""
    lo, hi = tr.extent()
    busy = merge((o[1], o[2]) for o in tr.ops[tr.chips[0]])
    gaps = sorted(subtract([(lo, hi)], busy), key=lambda g: g[0] - g[1])[:n]
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        span = min((h for h in tr.spans if h[1] <= mid <= h[2]),
                   key=lambda h: h[2] - h[1], default=None)
        inner = min((h for h in tr.host if h[1] <= mid <= h[2]
                     and not h[0].startswith(SPAN_PREFIX)),
                    key=lambda h: h[2] - h[1], default=None)
        label = " > ".join(x[0] for x in (span, inner) if x) or "no host event"
        out.append([label, (e - s) * 1e-9])
    return out
