"""Directory accesses per trace record: the summed
`mem_counters["dir_accesses"]` of a reading's `SimResults` over the
trace's own record count.  A constant of the traffic, not of the speed
(every reading is bit-identical, and `correct` holds it to the stored
reference): it says how much of the cell's work is the directory's -
0.005 in the FFT skeleton at 1024 tiles, over 1 in a coherence cell - and
must never move under a speed PR.  The invalidations are printed beside
it.  A program whose results carry no such counter reads nothing."""

import numpy as np


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    if not rs:
        return None
    counters = getattr(rs[0]["results"], "mem_counters", None) or {}
    if "dir_accesses" not in counters:
        return None
    total = {k: int(np.asarray(counters[k]).astype(np.int64).sum())
             for k in ("dir_accesses", "invalidations") if k in counters}
    print(f"directory counters of one reading: {total} over "
          f"{rs[0]['records']} records")
    return total["dir_accesses"] / rs[0]["records"]
