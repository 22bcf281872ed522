"""Share of the device's busy time under `gt.mem.stage_flush` alone: the
private-L2 directory's staging flush, once per inner block of a staged
program (`memory/engine.py: dir_stage_flush`; staging is on where the
sharers store is 64 MB or more and the program has no mesh: the two
`coh-1024*` configurations).  As an XLA scatter-add of row deltas a flush
is a pass over the 2.1 GB sharers store and two 201 MB temporaries, 19 ms
whatever was staged; since PR 43 a program lowered for a TPU lands the
staged slots' tiles alone through `memory/row_landing.py:
dir_stage_landing` (0.3-0.4 ms for a block's ~2,100 slots; PERF.md
section 6, PR 43).  `mem_ungated_busy_share` holds this scope together
with `gt.mem.base`; this is the flush by itself.  A program without the
scope (no staging: every other configuration) reads nothing."""

from lib import scope_trace

SCOPE = "gt.mem.stage_flush"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
