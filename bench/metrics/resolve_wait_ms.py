"""resolve_wait_ms (ms, pool): host wall blocked on commit verdicts per
committed step, the program's `pool.resolve` spans."""
import spans


def read(ctx):
    s = spans.program_spans(ctx)
    if s is None:
        return None
    return 1e3 * spans.wall_s(s, "pool.resolve") / ctx.steps
