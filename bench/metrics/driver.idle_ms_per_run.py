"""driver: device-idle time inside each traced run, in ms per run: the
host's dispatch, history drains and result transfer within run_hytm, as
distinct from the gaps between runs."""

import tracereduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    spans = ctx.trace.runs()
    idle = sum(s.dur - tracereduce.busy(ctx.trace.ops, s.start, s.end) for s in spans)
    return idle / len(spans) / 1e6
