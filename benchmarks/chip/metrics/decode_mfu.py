"""decode_mfu: the decode steps' share of the chips' bf16 peak, in %.

FLOPs of every (row, step) the engine ran (the architecture's
``decode_flops``: all layers and the output projection for one token,
attention over the row's live context) over the device's busy time inside
the ``bench.decode`` spans (averaged over the chips), times chips times
peak. Rows decoded past their own request's end count: the engine runs
them, and ``gen_tok_s`` is where that waste shows. The device's idle
time in the spans is ``idle_share.decode``'s.
"""

import trace_reduce as TR


def contexts(b):
    """Live context (token included) of each row at each decode step."""
    return [b.padded_len + s + 1 for s in range(b.steps)
            for _ in range(b.size)]


def read(run):
    win = run.trace.windows("bench.decode")
    if not win:
        return None
    flops = sum(run.cell.arch.decode_flops(run.sizes, contexts(b))
                for b in run.batches)
    t = TR.mean_busy_ns(run.trace, win) * 1e-9
    return 100.0 * flops / (t * run.chips * run.peak["bf16_flops"])
