"""Programs the drive loop launched in the last completed `run()` of the
window: `Simulator.last_run_dispatches`, counted by the program itself,
so it is read in host-driven cells too (where the trace holds a slice and
`dispatches_per_run` cannot).  The traced slices that follow the window
are a whole `run()` (the same count) or `run_chunk()`s, which leave the
counter alone."""


def read(ctx):
    n = getattr(ctx.own.get("sim"), "last_run_dispatches", None)
    return n or None
