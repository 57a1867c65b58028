"""decode_useful_share: the share of the decoded (row, step) pairs that a
request asked for, in %: the requests' ``max_new`` summed, over each
batch's rows times the ``serve.decode_step`` spans inside its
``serve.decode`` span (the i-th batch served is the i-th call). Every row
decodes to its batch's longest request, so the rest is padding waste.
``None`` when the trace's calls and the window's batches do not pair up.
``describe`` also gives, per batch, the steps' summed length over the
decode call's host time."""

import program_spans as PS


def paired(run):
    calls = PS.steps_per_call(run.trace)
    if not calls or len(calls) != len(run.batches):
        return None
    return list(zip(run.batches, calls))


def read(run):
    pairs = paired(run)
    if pairs is None:
        return None
    asked = sum(sum(b.max_new) for b, _ in pairs)
    return 100.0 * asked / sum(b.size * len(st) for b, st in pairs)


def describe(run):
    pairs = paired(run)
    ratio = [sum(e - s for s, e in st) * 1e-9 / (b.t_done - b.t_first)
             for b, st in pairs]
    return (f"{len(pairs)} batches, {sum(len(st) for _, st in pairs)} "
            f"steps; steps' length / decode call's host time "
            f"{min(ratio):.4f}-{max(ratio):.4f}")
