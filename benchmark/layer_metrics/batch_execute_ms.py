"""Mean `execute` span of the window's batches, in ms: the batched
program's one dispatch, the wait for it, the results fetch and their
assembly (`SweepRunner.run()`)."""

from lib import served


def read(ctx):
    return served.batch_span_ms(ctx, "execute")
