"""whole sweep: the least time the chip could take to read every input
edge the traced runs covered once (its destination id, plus its weight
where the program reads one) at peak HBM bandwidth, as a share of the
device's busy time over those runs, in percent.  It counts the same work
whatever implements the sweep, so no implementation can pass 100%."""

import tracereduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    spans = ctx.trace.runs()
    busy_s = tracereduce.busy(ctx.trace.ops, spans[0].start, spans[-1].end) / 1e9
    least_s = (sum(r.edges for r in ctx.runs) * ctx.traffic["bytes_per_edge"]
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / busy_s
