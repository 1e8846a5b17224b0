"""kernels: the share of the device's busy time in the traced runs spent
ordering the FILTER engine's edges for ``segment_spmm`` (the
``filter.order`` scope: the per-call sort, ``searchsorted``, the
``[order]`` gathers and pads, all but the Pallas call), in percent."""

import tracereduce


def read(ctx):
    return tracereduce.scope_share(ctx.trace, lambda scopes, s: scopes.within(s, scopes.FILTER_ORDER))
