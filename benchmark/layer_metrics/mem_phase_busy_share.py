"""Share of the device's busy time inside the memory engine's six gated
phases (`gt.mem.<phase>`: each phase's cond, gate and both arms); what
lies outside them is `mem_ungated_busy_share` (lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.share(
        ctx, lambda s: s.startswith("gt.mem.")
        and s not in scope_trace.MEM_UNGATED)
