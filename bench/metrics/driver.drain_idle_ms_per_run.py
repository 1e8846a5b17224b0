"""driver: device-idle time under run_hytm's ``hytm.drain`` and
``hytm.result`` spans (the history fetched to the host and its rows
appended; the values and Δ fetched and the result built), in ms per
traced run: the part of ``driver.idle_ms_per_run`` spent bringing a
run's output to the host."""

import tracereduce

PHASES = ("hytm.drain", "hytm.result")


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
