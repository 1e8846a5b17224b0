"""engines and kernels: the share of the device's busy time in the traced
runs spent inside the three engines' relaxes (the ``engine.*`` scopes and
everything under them: the Pallas kernels, ``segment_spmm``'s ordering,
the gathers and scatters around them), in percent.  Partitions with no
active edge clip to FILTER, so their skipped visits count here too."""

import tracereduce


def scope_shares(ctx, scopes):
    """Percent of the traced runs' device busy time by innermost scope,
    each op counting its own time (less the ops it encloses); None
    without a trace or a registered program.  Kept on the trace, so the
    scope metrics of one run reduce it once."""
    if ctx.trace is None:
        return None
    memo = vars(ctx.trace)
    if "scope_shares" not in memo:
        spans = ctx.trace.runs()
        inside = [e for s in spans for e in ctx.trace.ops if s.start <= e.start < s.end]
        times = scopes.scope_times(
            ((e.name, t) for e, t in tracereduce.self_times(inside)), tracereduce.op_label)
        busy = sum(tracereduce.busy(inside, s.start, s.end) for s in spans)
        memo["scope_shares"] = {s: 100.0 * t / busy for s, t in times.items()} or None
    return memo["scope_shares"]


def read(ctx):
    try:
        from repro.obs import scopes
    except ImportError:  # a program that names no device scopes
        return None
    shares = scope_shares(ctx, scopes)
    return None if shares is None else sum(v for s, v in shares.items() if any(scopes.within(s, e) for e in scopes.ENGINE_SCOPES))
