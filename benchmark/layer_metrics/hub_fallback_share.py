"""Share of the hub reads that took the queue model's analytical arm:
`100 * sum(analytical_reads) / sum(requests)` of a reading's
`SimResults.atac_counters`.  A `history_tree` hub queue tracks
`max_list_size` (100) cycles of history; a packet that reaches a hub
whose window has moved past it gets the M/G/1 waiting time from the hub's
running moments instead of its place in the queue
(`models/queue_models.py`).  It says which regime of the contention model
the cell measures - here the saturated one: a broadcast sweep books about
a thousand copies' worth of occupancy on its send hub at once - and is a
constant of the traffic that a speed PR must not move (`correct` holds
every counter to the stored reference).  A program whose results carry no
such counters reads nothing."""

from probe_golden_atac import hub_sums


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    total = hub_sums(rs[0]["results"]) if rs else None
    if not total or not total["requests"][0]:
        return None
    reads, ana = total["requests"][0], total["analytical_reads"][0]
    print(f"hub reads of one reading: {reads}, {ana} of them on the "
          f"analytical arm")
    return 100.0 * ana / reads
