"""driver: device-idle time under run_hytm's ``hytm.drain`` and
``hytm.result`` spans (the history fetched to the host and its rows
appended; the values and Δ fetched and the result built), in ms per
traced run: the part of ``driver.idle_ms_per_run`` spent bringing a
run's output to the host."""

import tracereduce

PHASES = ("hytm.drain", "hytm.result")


def read(ctx):
    return tracereduce.phase_idle_ms(ctx.trace, PHASES)
