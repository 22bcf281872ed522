"""Host clock around `Simulator.warmup()`: compile, or load from the
persistent cache, plus the one dispatch warmup() makes."""


def read(ctx):
    return ctx.spans.total("warmup") or None
