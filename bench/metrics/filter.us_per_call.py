"""kernels: device time per call of the FILTER engine's Pallas fold
(``segment_spmm_pallas``'s custom call), in us.  The wrapper's per-call
sort and searchsorted are XLA ops that a v5e trace does not name; they
show in the breakdown as ``sort.*``."""

import tracereduce


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.runs()
    lo, hi = spans[0].start, spans[-1].end
    calls = [e for e in tracereduce.named(ctx.trace.ops, ["segment_spmm_pallas"])
             if lo <= e.start < hi]
    if not calls:
        return None
    return sum(e.dur for e in calls) / len(calls) / 1e3
