"""engines and kernels: the share of the device's busy time in the traced
runs spent inside the three engines' relaxes (the ``engine.*`` scopes and
everything under them: the Pallas kernels, ``segment_spmm``'s ordering,
the gathers and scatters around them), in percent.  Partitions with no
active edge clip to FILTER, so their skipped visits count here too."""

import tracereduce


def read(ctx):
    return tracereduce.scope_share(
        ctx.trace, lambda scopes, s: any(scopes.within(s, e) for e in scopes.ENGINE_SCOPES))
