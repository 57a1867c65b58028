"""idle_share.prefill: share of the traced window with no device
operation running, in %, averaged over the chips, in the cell where
prefill takes most of the time."""

import trace_reduce as TR


def read(run):
    tr = run.trace
    if not tr.spans:
        return None
    win = [tr.extent()]
    return 100.0 * (1.0 - TR.mean_busy_ns(tr, win) / TR.length(win))
