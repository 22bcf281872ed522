"""Share of the device's busy time under `gt.net.hbh.commit`: the second
half of `models/network_hop_by_hop._dense_contention` - the occupancy
commit, six port planes each reduced over the packet axis (max of the
arrivals, sums of the processing times, the event counters) and written
back into the `[n_tiles * 6 + 1, 10]` port store.  Inside `gt.net.route`,
so inside `net_busy_share` too.  A program without the scope (the parent
of the PR that registered it; a target under another network model) reads
nothing."""

from lib import scope_trace

SCOPE = "gt.net.hbh.commit"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
