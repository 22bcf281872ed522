"""Broadcast invalidation sweeps per trace record: the summed
`mem_counters["dir_broadcasts"]` of a reading's `SimResults` over the
trace's own record count.  Under `ackwise` (and `limited_broadcast`) a
directory entry keeps k sharer pointers; an exclusive request to a line
with more sharers than that invalidates by a broadcast to every tile, and
only the true holders acknowledge.  0 under `full_map` - every other
memory cell.  A constant of the traffic, not of the speed (every reading
is bit-identical, and `correct` holds it to the stored reference): it
says that the path the cell exists for was taken, and must never move
under a speed PR.  The invalidations it caused are printed beside it.  A
program whose results carry no such counter reads nothing."""

import numpy as np


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    if not rs:
        return None
    counters = getattr(rs[0]["results"], "mem_counters", None) or {}
    if "dir_broadcasts" not in counters:
        return None
    total = {k: int(np.asarray(counters[k]).astype(np.int64).sum())
             for k in ("dir_broadcasts", "invalidations") if k in counters}
    print(f"broadcast counters of one reading: {total} over "
          f"{rs[0]['records']} records")
    return total["dir_broadcasts"] / rs[0]["records"]
