"""Share of the device's busy time the memory engine takes OUTSIDE its
gated phases: `gt.mem.base` (the working-set gather, the merged directory
scatter, the whole-engine gate, `mem_idle_out`) and `gt.mem.stage_flush`
(the staged directory writes, once per inner block).  This is what
`mem_phase_skip_share` cannot see (lib/scope_trace.py)."""

from lib import scope_trace


def read(ctx):
    return scope_trace.share(ctx, lambda s: s in scope_trace.MEM_UNGATED)
