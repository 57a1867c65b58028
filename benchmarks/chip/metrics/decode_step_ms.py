"""decode_step_ms: the median length of the engine's ``serve.decode_step``
spans, in ms: one whole step as the host lives it (inputs, dispatch,
sampling, the token read-back and its bookkeeping), on the profiler's
clock. ``describe`` gives the count, the 95th percentile, the longest step,
the prompt length of the batch that holds it and the host phase in which
the device idled most during it."""

import numpy as np

import program_spans as PS


def read(run):
    steps = PS.spans(run.trace, PS.STEP)
    if not steps:
        return None
    return float(np.median([e - s for s, e in steps])) * 1e-6


def describe(run):
    tr = run.trace
    steps = PS.spans(tr, PS.STEP)
    ms = np.array([e - s for s, e in steps]) * 1e-6
    worst = steps[int(ms.argmax())]
    calls = PS.spans(tr, PS.DECODE)
    holder = [i for i, (s, e) in enumerate(calls)
              if s <= worst[0] and worst[1] <= e]
    plen = (run.batches[holder[0]].padded_len
            if holder and len(calls) == len(run.batches) else "unknown")
    idle = PS.idle_split(tr, [worst])
    phase = max(idle, key=idle.get)
    return (f"{len(ms)} steps, p95 {np.percentile(ms, 95):.4f} ms, "
            f"max {ms.max():.4f} ms in a batch of prompt length {plen}, "
            f"its device idle {sum(idle.values()) * 1e-6:.4f} ms, most in "
            f"{phase} ({idle[phase] * 1e-6:.4f} ms)")
