"""idle_share (%, device): 1 - (union of device operation intervals) over
the traced window, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.trace_window_s:
        return None
    busy = ctx.trace.busy_s()
    return 100.0 * (1.0 - busy / ctx.trace_window_s) if busy > 0 else None
