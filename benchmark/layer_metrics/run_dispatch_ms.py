"""Mean `dispatch` span of the traced slice, in ms: the call into the
compiled runner until it returns its futures (the program's own span,
`Simulator.attach_tracer`; lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.span_ms(ctx, "dispatch", mean=True)
