"""1 - (union of the device's leaf operations) / (the traced span of
`run()`), in percent, from the profiler trace."""


def read(ctx):
    if ctx.profile is None:
        return None
    return 100.0 * ctx.profile["idle_share"]
