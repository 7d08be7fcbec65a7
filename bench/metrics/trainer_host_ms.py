"""trainer_host_ms (ms, trainer): the host's own time in the trainer's
step loop per committed step: the self time of the program's
`trainer.dispatch` and `trainer.resolve` spans, less the program spans
nested in them (the train step's launch, the commit's dispatch, the wait
on its verdict, a scrub)."""
import spans


def read(ctx):
    s = spans.program_spans(ctx)
    if s is None:
        return None
    return 1e3 * spans.self_s(s, {"trainer.dispatch", "trainer.resolve"}) \
        / ctx.steps
