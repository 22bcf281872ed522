"""Share of the device's busy time under `gt.fetch` and `gt.core*`: the
trace read and record decode, classification, cost, commit and clock
update, and the iocoom pipeline algebra (lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.share(
        ctx, lambda s: s == "gt.fetch" or s.startswith("gt.core"))
