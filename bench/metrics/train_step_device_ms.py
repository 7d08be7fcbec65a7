"""train_step_device_ms (ms, model step): device time of the train-step
program per committed step, from the trace, averaged over the chips."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    s = ctx.trace.program_s(ctx.trace.rules["model_step_programs"])
    return 1e3 * s / ctx.steps if s > 0 else None
