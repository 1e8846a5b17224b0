"""stream: device-idle time under ``DeltaCSR.apply``'s ``stream.apply``
span (the batch's validation, the host log's edits, the patch scatter's
dispatch, a merge-compaction), in ms per traced run: the part of a
batch's time the host spends applying it."""

import tracereduce

PHASES = ("stream.apply",)


def read(ctx):
    return tracereduce.phase_idle_ms(ctx.trace, PHASES)
