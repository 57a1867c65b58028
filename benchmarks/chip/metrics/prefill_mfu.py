"""prefill_mfu: the prefill's share of the chips' bf16 peak, in %.

FLOPs the prompts need (the architecture's ``prefill_flops``: every
real prompt token through every layer, causal attention, the last token's
logits) over the
device's busy time inside the ``bench.prefill`` spans (averaged over the
chips), times chips times peak. Time the device sits idle in those spans
is ``idle_share``'s, not this metric's.
"""

import trace_reduce as TR


def read(run):
    win = run.trace.windows("bench.prefill")
    if not win:
        return None
    flops = sum(run.cell.arch.prefill_flops(run.sizes,
                                            [len(p) for p in b.prompts])
                for b in run.batches)
    t = TR.mean_busy_ns(run.trace, win) * 1e-9
    return 100.0 * flops / (t * run.chips * run.peak["bf16_flops"])
