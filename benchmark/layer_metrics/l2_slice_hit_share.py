"""Share of the requests a home started that its L2 SLICE served:
`100 * l2_hits / (l2_hits + l2_misses)`, summed over a reading's
`SimResults.mem_counters`.  In the shared-L2 engine every L1 miss goes to
the line's home slice; a slice miss allocates the line and fetches it from
DRAM.  A constant of the traffic, not of the speed (every reading is
bit-identical and `correct` holds it to the stored reference): it says
how much of the cell's work the slices absorb, and must never move under
a speed PR.  The directory accesses and invalidations are printed beside
it, and where the configuration carries a `golden_envelope` (the
independent golden's sums on this traffic, each with a limit: the
reference is the engine's own, and `lib/checks.py` compares hashes only)
the reading's sums are printed beside the golden's and the limits.  A
program whose results carry no such counters reads nothing."""

import numpy as np


def _against_golden(res, env) -> None:
    """One line per statistic of the envelope: this reading's sum, the
    golden's, their distance and its limit.  Printed, judged in tier-1
    (tests/test_shl2_memstress_golden.py) on the stored hashes."""
    for k, v in (env or {}).get("statistics", {}).items():
        a = res.clock_ps if k == "clock_ps" else res.mem_counters.get(k)
        if a is None:
            continue
        got = int(np.asarray(a).astype(np.int64).sum())
        pct = 100.0 * abs(got - v["golden"]) / max(1, v["golden"])
        print(f"golden envelope {k}: reading {got} golden {v['golden']} "
              f"({pct:.3f}%, limit {v['limit_pct']}%)"
              + (" OUTSIDE" if pct > v["limit_pct"] else ""))


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    if not rs:
        return None
    counters = getattr(rs[0]["results"], "mem_counters", None) or {}
    if "l2_hits" not in counters or "l2_misses" not in counters:
        return None
    total = {k: int(np.asarray(counters[k]).astype(np.int64).sum())
             for k in ("l2_hits", "l2_misses", "dir_accesses",
                       "invalidations") if k in counters}
    print(f"slice counters of one reading: {total}")
    _against_golden(rs[0]["results"], ctx.config.get("golden_envelope"))
    asked = total["l2_hits"] + total["l2_misses"]
    return 100.0 * total["l2_hits"] / asked if asked else None
