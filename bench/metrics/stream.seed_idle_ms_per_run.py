"""stream: device-idle time under ``run_incremental``'s ``stream.seed``
span (the routed-through invalidation over the live edges, the reset
and the seeded frontier), in ms per traced run: the part of a batch's
time the host spends seeding the warm start."""

import tracereduce

PHASES = ("stream.seed",)


def read(ctx):
    return tracereduce.phase_idle_ms(ctx.trace, PHASES)
