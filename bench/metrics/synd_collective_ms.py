"""synd_collective_ms (ms, collectives): device time of the collective
operations inside the commit and scrub programs, per committed step, from
the trace, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    r = ctx.trace.rules
    s = ctx.trace.op_s(r["collective_ops"], r["collective_programs"])
    return 1e3 * s / ctx.steps if s > 0 else None
