"""dispatch_idle_ms (ms, device): device-idle time inside the program's
`trainer.dispatch` spans per committed step, averaged over the chips:
the chip waiting on the host's launch of the next step."""
import spans


def read(ctx):
    s = spans.program_spans(ctx)
    if s is None:
        return None
    idle = spans.idle_s(ctx.trace, s, "trainer.dispatch")
    return None if idle is None else 1e3 * idle / ctx.steps
