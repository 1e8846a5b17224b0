"""selection: the share of the traced runs' (iteration, partition) picks
that Algorithm 1 gave the FILTER engine, in percent.  A count, from the
history each run returns."""

import numpy as np
from repro.core.cost_model import FILTER


def read(ctx):
    picks = np.concatenate([r.engines.ravel() for r in ctx.runs])
    if picks.size == 0:
        return None
    return 100.0 * float(np.mean(picks == FILTER))
