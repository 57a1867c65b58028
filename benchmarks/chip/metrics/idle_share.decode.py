"""idle_share.decode: share of the decode spans with no device operation
running, in %, averaged over the chips: the host's per-step work (the
Python loop, the token read-back, dispatch) as the chip sees it."""

import trace_reduce as TR


def read(run):
    tr = run.trace
    win = tr.windows("bench.decode")
    if not win:
        return None
    return 100.0 * (1.0 - TR.mean_busy_ns(tr, win) / TR.length(win))
