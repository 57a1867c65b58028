"""decode_step_idle_ms: device idle time per decode step, in ms: the
``serve.decode_step`` spans' length less the device's busy time inside
them (averaged over the chips), over the number of steps. ``describe``
splits it by the host phase that was running (``program_spans``). Twin of
``idle_share.decode``, which reads the harness's ``bench.decode`` spans:
this times the number of steps, over the ``serve.decode`` spans' summed
length, is that share to within the calls' time outside their steps."""

import program_spans as PS
import trace_reduce as TR


def split(run):
    steps = PS.spans(run.trace, PS.STEP)
    if not steps:
        return None, 0
    return PS.idle_split(run.trace, TR.merge(steps)), len(steps)


def read(run):
    parts, n = split(run)
    return sum(parts.values()) * 1e-6 / n if parts else None


def describe(run):
    parts, n = split(run)
    return PS.describe_split(parts, n, "step")
