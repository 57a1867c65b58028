"""prefill_idle_ms: device idle time per prefill call, in ms: the
``serve.prefill`` spans' length less the device's busy time inside them
(averaged over the chips), over the number of calls. ``describe`` splits
it by the host phase that was running (``program_spans``) and names the
longest idle interval's phase. Twin of ``idle_share.prefill``, which reads
the whole traced window."""

import program_spans as PS
import trace_reduce as TR


def split(run):
    calls = PS.spans(run.trace, PS.PREFILL)
    if not calls:
        return None, 0
    return PS.idle_split(run.trace, TR.merge(calls)), len(calls)


def read(run):
    parts, n = split(run)
    return sum(parts.values()) * 1e-6 / n if parts else None


def describe(run):
    parts, n = split(run)
    gap, phase = PS.longest_idle(run.trace, TR.merge(
        PS.spans(run.trace, PS.PREFILL)))
    return (f"{PS.describe_split(parts, n, 'call')}; longest gap "
            f"{gap * 1e-6:.4f} ms in {phase}")
