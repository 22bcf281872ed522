"""Programs launched on the device (events of the trace's "XLA Modules"
line) within one traced `run()`.  Read only where the traced slice is a
whole run: a slice of quanta says nothing about a run."""


def read(ctx):
    if ctx.profile is None or not ctx.own.get("traced_whole_run"):
        return None
    return ctx.profile["launches"]
