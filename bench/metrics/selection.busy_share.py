"""selection: the share of the device's busy time in the traced runs
spent in Algorithm 1 (the ``select`` scope: partition stats, engine
choice, task combining, the priority schedule, the diagnostics), in
percent."""

import tracereduce


def read(ctx):
    return tracereduce.scope_share(ctx.trace, lambda scopes, s: scopes.within(s, scopes.SELECT))
