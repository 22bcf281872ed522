"""Share of the device's busy time under `gt.mem.entry_land` alone: the
home phases' entry-word plan landed on the private-L2 directory's entry
store, once per iteration whose home gate is open, in a program that
carries the store as u32 words (`memory/engine.py: _entry_land`;
`memory/state.py: entry_as_words` - the staged programs: the two
`coh-1024*` configurations and `canneal-dvfs-1024`).  A program lowered
for a TPU lands the plan's live words' tiles alone through
`memory/row_landing.py: dir_entry_landing`; as a scatter-add on the int64
store the same plan cost five passes over a 64 MB half, 0.97 ms an open
iteration (PERF.md section 6, PR 45), and was read under `gt.mem.base`
and no scope at all (its relayouts).  The scope lies inside `gt.mem.base`
but a scope trace counts an operation for its DEEPEST scope, so
`mem_ungated_busy_share` and `home_side_busy_share` (fixed lists in
`lib/`) do not hold it: add this to compare them across PR 45.  A program
without the scope (an int64 entry store: every other configuration, and
every program before PR 45) reads nothing."""

from lib import scope_trace

SCOPE = "gt.mem.entry_land"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
