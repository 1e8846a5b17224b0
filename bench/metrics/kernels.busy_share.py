"""engines and kernels: the share of the device's busy time in the three
graph kernels' Pallas calls (the custom calls named after their jitted
wrappers), in percent.  The wrappers' XLA ops around them (sort,
searchsorted, reduce) carry no name in a v5e trace and count outside."""

import tracereduce

KERNELS = ("segment_spmm_pallas", "frontier_compact_pallas", "hyb_gather_pallas")


def read(ctx):
    if ctx.trace is None:
        return None
    spans = ctx.trace.runs()
    lo, hi = spans[0].start, spans[-1].end
    mine = tracereduce.named(ctx.trace.ops, KERNELS)
    if not mine:
        return None
    return 100.0 * tracereduce.busy(mine, lo, hi) / tracereduce.busy(ctx.trace.ops, lo, hi)
