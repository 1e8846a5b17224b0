"""driver: device-idle time under run_hytm's ``hytm.init`` span (the
initial state's eager ops, the calibrator, the info shapes, the history
buffers), in ms per traced run: the part of ``driver.idle_ms_per_run``
the host spends before the first dispatch."""

import tracereduce

PHASES = ("hytm.init",)


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    ops = [(e.start, e.end) for e in ctx.trace.ops]
    runs, idle, found = ctx.trace.runs(), 0.0, False
    for run in runs:
        phases = [(e.start, e.end) for e in ctx.trace.host
                  if e.name in PHASES and run.start <= e.start < run.end]
        found = found or bool(phases)
        gaps = tracereduce.gaps(ops, run.start, run.end)
        idle += sum(tracereduce.covered(gaps, lo, hi) for lo, hi in phases)
    return idle / len(runs) / 1e6 if found else None
