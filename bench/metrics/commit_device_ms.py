"""commit_device_ms (ms, engines): device time of the commit program per
committed step, from the trace, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    s = ctx.trace.program_s(ctx.trace.rules["commit_programs"])
    return 1e3 * s / ctx.steps if s > 0 else None
