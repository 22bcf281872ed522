"""`fetch` + `results` spans of the traced whole `run()`, in ms: the
device_get of control flags and statistics, and the assembly of
SimResults on the host.  Read only where the traced slice is a whole
run: a slice of quanta ends in no results fetch."""

from lib import scope_trace


def read(ctx):
    if scope_trace.get(ctx) is None or not ctx.own.get("traced_whole_run"):
        return None
    return scope_trace.span_ms(ctx, "fetch", "results")
