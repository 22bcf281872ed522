"""Share of the device's busy time under `gt.mem.stage_overlay` alone:
what a staged private-L2 program pays to read the directory's sharers
THROUGH its staging table (`memory/engine.py`: the two `coh-1024*`
configurations and `canneal-dvfs-1024`).  Two halves carry the scope: at
the working set's gather, once per iteration whose home gate is open, the
INDEX - for every way of the three gathered set rows a lane the latest
staged slot, a compare and a max over the table's keys - and, inside each
home phase that runs, ONE gather of a `[T]`-row value out of the table
for the way that phase reads.  Before PR 46 the value of every way of
every gathered row was fetched at gather time (49,152 rows of 128 bytes an
open iteration, 11% of `memstress1024-coh`'s busy time: PERF.md section
6, PR 46) under `gt.mem.base`.  The index lies inside `gt.mem.base` and a
fetch inside its phase's scope, but a scope trace counts an operation for
its DEEPEST scope, so `mem_ungated_busy_share` and `home_side_busy_share`
(fixed lists in `lib/`) do not hold it - add this to compare them across
PR 46 - while `mem_phase_busy_share` (every `gt.mem.*` scope outside the
un-gated two) does.  A program without the scope (an unstaged one, a
sharded one, and every program before PR 46) reads nothing."""

from lib import scope_trace

SCOPE = "gt.mem.stage_overlay"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
