"""whole sweep: the share of the device's busy time in the traced runs
spent loading each partition visit's block (the ``sweep.block`` scope:
the block slices, the ``frontier[src]`` gather, the operand), in
percent.  A visit does this block-wide whatever its frontier."""

import tracereduce


def read(ctx):
    return tracereduce.scope_share(ctx.trace, lambda scopes, s: scopes.within(s, scopes.SWEEP_BLOCK))
