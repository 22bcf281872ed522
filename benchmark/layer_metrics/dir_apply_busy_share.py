"""Share of the device's busy time under `gt.mem.dir_apply`: the shared-L2
engine's three home phases each hand a compact row plan out of their
`lax.cond`, and the plan is landed on the embedded directory (the
`u32[1024,1024,256]` sharers store: 1.07 GB at 1024 tiles) OUTSIDE the
cond - a scatter-add of 1,024 rows that costs about 3 ms on the chip
whatever it adds (`memory/engine_shl2.py: _cond_dir`, `_dir_apply_rows`;
PERF.md section 6, PR 38).  Since PR 38 a plan lands only where its phase
ran (`engine._run_if`); what is left is what a home-activity gate finer
than the phase's (ROADMAP M5) or one merged landing (D5) would remove.  A
program without the scope (the parent of the PR that registered it; the
private-L2 engine) reads nothing.  (`_dir_apply_rows` scatters the store
row-flat so that the scatter keeps its `op_name`: XLA re-creates a
two-index scatter without one, and `lib/scope_trace.py` then files the
fusion under the phase - 83% of busy time read 3%, PR 38's first run.)"""

from lib import scope_trace

SCOPE = "gt.mem.dir_apply"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
