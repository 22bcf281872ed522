"""Share of the packets' summed latency that is port contention:
`100 * sum(delay_cycles) in ps / sum(total_packet_latency_ps)` of a
reading - `SimResults.noc_counters` (the user NoC's per-port event
counters, `[n_tiles, 6]`) over the mailboxes' latency sum.  0 under a
contention-free network.  A constant of the traffic, not of the speed
(every reading is bit-identical and `correct` holds it to the stored
reference): it says how much of what the cell simulates the contention
model decides, and must never move under a speed PR.  The four counters'
sums are printed beside it, and where the configuration carries a
`golden_envelope` (the independent golden's numbers on this trace, each
with a limit: the reference is the engine's own, and `lib/checks.py`
compares hashes only) the reading's numbers are printed beside the
golden's and the limits.  A program whose results carry no such counters
(the parent of the PR that added them; another network model) reads
nothing."""

import numpy as np

from probe_golden_hbh import envelope, numbers


def _against_golden(res, env: dict) -> None:
    """One line per statistic of the envelope: this reading's number,
    the golden's, their distance and its limit.  Printed, judged in
    tier-1 (tests/test_hbh256_cell.py) on the stored hashes."""
    got = numbers(res, env)
    golden = {k: v["golden"] for k, v in env.items()}
    for k, pct, limit, outside in envelope(golden, got, env):
        print(f"golden envelope {k}: reading {got[k]} golden {golden[k]} "
              f"({pct:.3f}%, limit {limit}%){' OUTSIDE' if outside else ''}")


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    if not rs:
        return None
    res = rs[0]["results"]
    counters = getattr(res, "noc_counters", None)
    hbh = getattr(ctx.own["sim"].params, "user_hbh", None)
    if not counters or hbh is None:
        return None
    total = {k: int(np.asarray(v).astype(np.int64).sum())
             for k, v in counters.items()}
    latency_ps = int(np.asarray(res.total_packet_latency_ps).sum())
    print(f"port counters of one reading: {total}, summed packet latency "
          f"{latency_ps} ps")
    _against_golden(res, ctx.config.get("golden_envelope", {}).get(
        "statistics", {}))
    delay_ps = total["delay_cycles"] * 10**6 / hbh.freq_mhz
    return 100.0 * delay_ps / latency_ps if latency_ps else None
