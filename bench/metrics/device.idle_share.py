"""device: the share of the traced runs' span in which no operation ran
on the device, in percent (1 - union of op intervals / span)."""

import tracereduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    spans = ctx.trace.runs()
    lo, hi = spans[0].start, spans[-1].end
    return 100.0 * (1.0 - tracereduce.busy(ctx.trace.ops, lo, hi) / (hi - lo))
