"""Share of the device's busy time under `gt.energy` alone: a tile's
energy interval closed at the operating point that was in force
(`graphite_tpu/power/accounting.py: close_interval`, inside
`_dvfs_block`'s taken arm): the event counts since the last close times
the level's integer prices, leakage times the elapsed clock, masked to the
tiles whose request succeeded.  Inside `gt.dvfs`, so inside
`dvfs_busy_share` too.  A program without the scope (the parent of the PR
that registered it; power modelling off: every other configuration) reads
nothing."""

from lib import scope_trace

SCOPE = "gt.energy"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
