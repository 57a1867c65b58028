"""decode_hbm_roofline: the decode steps' share of HBM bandwidth, in %.

Bytes each chip needs per step (the architecture's
``decode_step_bytes``: its share of the weights and of each row's live
keys and values, not the cache's allocated length) summed over the steps
run, over the device's busy time inside the ``bench.decode`` spans
(averaged over the chips) times the chip's HBM bandwidth. Decode at these
batch sizes is bound by bytes, not FLOPs (``decode_mfu`` is the other).
The device's idle time in the spans is ``idle_share.decode``'s.
"""

import trace_reduce as TR


def read(run):
    win = run.trace.windows("bench.decode")
    if not win:
        return None
    nbytes = sum(run.cell.arch.decode_step_bytes(
        run.sizes, [b.padded_len + s + 1] * b.size, run.chips)
        for b in run.batches for s in range(b.steps))
    t = TR.mean_busy_ns(run.trace, win) * 1e-9
    return 100.0 * nbytes / (t * run.peak["hbm_bytes_per_s"])
