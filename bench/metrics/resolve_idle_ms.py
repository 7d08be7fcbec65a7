"""resolve_idle_ms (ms, device): device-idle time inside the program's
`trainer.resolve` spans per committed step, averaged over the chips: the
chip waiting while the host resolves a step (its verdict, loss, scrub
cadence and bookkeeping)."""
import spans


def read(ctx):
    s = spans.program_spans(ctx)
    if s is None:
        return None
    idle = spans.idle_s(ctx.trace, s, "trainer.resolve")
    return None if idle is None else 1e3 * idle / ctx.steps
