"""flash_attention_roofline: the flash kernel's share of its roofline, in %.

The least time the kernel could take for the prefills run, the larger of
its FLOPs over the bf16 peak and its bytes over HBM bandwidth
(the architecture's ``flash_flops`` / ``flash_bytes``, per chip), over
the kernel's own device time in the trace, averaged over the chips.
``describe`` names the bound that binds.
"""

import re

import numpy as np

import trace_reduce as TR

# the custom call of repro.kernels.flash_attention, named for its function
KERNEL = re.compile(r"^flash_attention(\.\d+)?$")


def bounds(run):
    """(FLOP-bound seconds, byte-bound seconds) per chip."""
    f = sum(run.cell.arch.flash_flops(run.sizes, b.size, b.padded_len)
            for b in run.batches)
    n = sum(run.cell.arch.flash_bytes(run.sizes, b.size, b.padded_len)
            for b in run.batches)
    return (f / run.chips / run.peak["bf16_flops"],
            n / run.chips / run.peak["hbm_bytes_per_s"])


def describe(run) -> str:
    f, b = bounds(run)
    return (f"bound by {'FLOPs' if f >= b else 'bytes'}: {f:.6g} s of "
            f"FLOPs, {b:.6g} s of bytes per chip")


def read(run):
    lo, hi = run.trace.extent()
    t = np.mean([TR.op_ns(run.trace, c, KERNEL.search, [(lo, hi)])
                 for c in run.trace.chips]) * 1e-9
    if t == 0:
        return None
    return 100.0 * max(bounds(run)) / t
