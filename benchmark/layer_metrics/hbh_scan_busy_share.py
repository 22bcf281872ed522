"""Share of the device's busy time under `gt.net.hbh.scan`: the first
half of `models/network_hop_by_hop._dense_contention` - every packet's XY
path as membership masks over `[packets, h, w]` grids, the max-plus scan
of the serial hop recurrence (two directional `cummax`es a field) and the
per-cell queue delays with their M/G/1 arm: elementwise work over the
grids, int64 throughout.  Inside `gt.net.route`, so inside
`net_busy_share` too.  A program without the scope (the parent of the PR
that registered it; a target under another network model) reads
nothing."""

from lib import scope_trace

SCOPE = "gt.net.hbh.scan"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
