"""Cycles a packet waits at an optical hub, on average:
`sum(delay_cycles) / sum(requests)` of a reading's
`SimResults.atac_counters` (the memory network's 64 send hubs and 64
receive hubs, `[2 * n_clusters]`).  A constant of the traffic, not of the
speed (every reading is bit-identical and `correct` holds every counter
to the stored reference): it says how saturated the hubs are in what the
cell simulates, and must never move under a speed PR.  The four
counters' sums are printed beside it, send hubs and receive hubs apart,
and where the configuration carries a `golden_envelope` (the independent
golden's numbers on this trace, each with a limit) the reading's numbers
beside the golden's and the limits.  A program whose results carry no
`atac_counters` (the parent of the PR that added them; another network
model) reads nothing."""

from probe_golden_atac import hub_sums, print_against_golden


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    total = hub_sums(rs[0]["results"]) if rs else None
    if not total or not total["requests"][0]:
        return None
    print("hub counters of one reading (all hubs, of them the send hubs): "
          f"{total}")
    print_against_golden(rs[0]["results"], ctx.config.get(
        "golden_envelope", {}).get("statistics", {}))
    return total["delay_cycles"][0] / total["requests"][0]
