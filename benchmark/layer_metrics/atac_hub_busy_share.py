"""Share of the device's busy time under `gt.net.atac.hub`: the two
hub-queue charges of `models/network_atac.route_atac` - every unicast
coherence message that leaves its cluster reads and commits its send
hub's queue and then its receive hub's (`queue_models.
scatter_queue_delay`: one gather, the M/G/1 arm's 32-step integer
division, four scatters onto the `[2 * n_clusters + 1, 10]` int64 queue
table, twice a call).  Inside `gt.net.route`, so inside `net_busy_share`
too.  A program without the scope (the parent of the PR that registered
it; a target under another network model) reads nothing."""

from lib import scope_trace

SCOPE = "gt.net.atac.hub"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
