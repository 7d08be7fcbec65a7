"""commit_dispatch_ms (ms, pool): host wall of `Pool` commit dispatch,
the sum over the count of the pool's `commit_dispatch_ms` histogram over
the window's commits (its interpolated percentiles are not used)."""


def read(ctx):
    total_ms, n = ctx.dispatch
    return total_ms / n if n else None
