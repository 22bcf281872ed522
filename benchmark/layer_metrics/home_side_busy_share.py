"""Share of the device's busy time on the DIRECTORY's side of the memory
engine: the three home phases (`gt.mem.home_evict`, `gt.mem.home_start`,
`gt.mem.home_finish`), the consolidated base around them (`gt.mem.base`:
the working-set gather over the entry and sharers stores, the merged
scatter, the home-activity gate) and the staged directory writes
(`gt.mem.stage_flush`).  It is what grows with the resident directory
(2.4 GB at 1024 tiles); the requester / sharer side is the rest of
`gt.mem.*` (lib/scope_trace.py)."""

from lib import scope_trace

HOME_SIDE = ("gt.mem.home_evict", "gt.mem.home_start", "gt.mem.home_finish",
             ) + scope_trace.MEM_UNGATED


def read(ctx):
    return scope_trace.share(ctx, lambda s: s in HOME_SIDE)
