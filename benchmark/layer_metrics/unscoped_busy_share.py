"""Share of the device's busy time in leaf operations under no
registered scope: instructions XLA added on its own, with no `op_name`
(copies on the loop carry, `copy-start`/`copy-done`, slices).  Over 30
the attribution is not good enough to plan from (lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.share(ctx, lambda s: s == scope_trace.UNSCOPED)
