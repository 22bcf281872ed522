"""Share of the port reads that took the queue model's analytical arm:
`100 * sum(analytical_reads) / sum(requests)` of a reading's
`SimResults.noc_counters`.  A `history_tree` port queue tracks
`max_list_size` (100) cycles of history; a packet that arrives at a port
whose window has moved past it gets the M/G/1 waiting time from the
port's running moments instead of its place in the queue
(`models/queue_models.py`, `network_hop_by_hop.delay_at`).  It says which
regime of the contention model the cell measures - a backlog within the
window, or far beyond it - and is a constant of the traffic that a speed
PR must not move (`correct` holds every counter to the stored
reference).  A program whose results carry no such counters reads
nothing."""

import numpy as np


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    if not rs:
        return None
    counters = getattr(rs[0]["results"], "noc_counters", None)
    if not counters:
        return None
    reads = int(np.asarray(counters["requests"]).astype(np.int64).sum())
    ana = int(np.asarray(counters["analytical_reads"]).astype(np.int64).sum())
    print(f"port reads of one reading: {reads}, {ana} of them on the "
          f"analytical arm")
    return 100.0 * ana / reads if reads else None
