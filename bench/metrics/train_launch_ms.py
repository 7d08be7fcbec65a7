"""train_launch_ms (ms, model step): host wall of launching the train
step per committed step, the program's `trainer.train_step` spans.  A
large value means the runtime holds the launch, e.g. until the buffers
for its outputs are free."""
import spans


def read(ctx):
    s = spans.program_spans(ctx)
    if s is None:
        return None
    return 1e3 * spans.wall_s(s, "trainer.train_step") / ctx.steps
