"""device: the share of the device's busy time in the traced runs whose
op the program maps to a named scope (``repro.obs.scopes``), in
percent: how much of the time the scope metrics can attribute.
Each op counts its own time (less the ops it encloses), under the
innermost scope of its HLO instruction in the programs the run
compiled."""

import tracereduce


def read(ctx):
    return tracereduce.scope_share(ctx.trace, lambda scopes, s: s is not None)
