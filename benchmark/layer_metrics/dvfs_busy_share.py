"""Share of the device's busy time under `gt.dvfs`: the DVFS_SET block of
`engine/step.py` - the iteration's test for a request, and in the
iterations that have one the taken arm (`_dvfs_block`: the request
checked against the V/f table, the per-tile table's two planes written)
with the energy interval's close nested in it (`gt.energy`, which
`energy_busy_share` reports alone).  Every program carries the test; only
a trace with DVFS_SET records takes the arm (`canneal-dvfs-1024`: 1,024
requests at each of five temperature steps).  A program without the scope
(cached from before the scopes) reads nothing."""

from lib import scope_trace

SCOPES = ("gt.dvfs", "gt.energy")


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or not any(s in sh for s in SCOPES):
        return None
    return sum(sh.get(s, 0.0) for s in SCOPES)
