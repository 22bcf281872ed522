"""Share of the device's busy time under `gt.net.atac.fanout`: the ATAC
leg of `memory/engine.py: mem_net_fanout` - a home's INV / FLUSH / WB
multicast (a broadcast sweep of all 1,024 tiles once an ACKwise entry
has overflowed its k pointers): the `[T, T]` zero-load latency, ONet-pair
and target-rank matrices (an int64 `cumsum` over 8 MB at 1,024 tiles) and
the one send-hub charge of `k_onet * flits`.  Inside `gt.net.route`, so
inside `net_busy_share` too.  A program without the scope (the parent of
the PR that registered it; a target under another network model) reads
nothing."""

from lib import scope_trace

SCOPE = "gt.net.atac.fanout"


def read(ctx):
    sh = scope_trace.shares(scope_trace.get(ctx))
    if sh is None or SCOPE not in sh:
        return None
    return sh[SCOPE]
