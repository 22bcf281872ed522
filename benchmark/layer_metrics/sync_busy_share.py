"""Share of the device's busy time under `gt.sync.*`: barriers, the
mutex + cond block, joins (lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.share(ctx, lambda s: s.startswith("gt.sync."))
