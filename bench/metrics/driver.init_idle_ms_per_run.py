"""driver: device-idle time under run_hytm's ``hytm.init`` span (the
initial state's eager ops, the calibrator, the info shapes, the history
buffers), in ms per traced run: the part of ``driver.idle_ms_per_run``
the host spends before the first dispatch."""

import tracereduce

PHASES = ("hytm.init",)


def read(ctx):
    return tracereduce.phase_idle_ms(ctx.trace, PHASES)
