"""DVFS_SET requests a reading took: the trace's DVFS_SET records less
the rejected ones (`SimResults.dvfs_counters["errors"]`, summed).  A
constant of the traffic (`canneal-dvfs-1024`: 5,120, none rejected), held
by `correct` through the stored reference; it says that the arm the cell
exists for was taken, and must never move under a speed PR.  Printed
beside it: the number of distinct final core frequencies (6 under the
rotating schedule; a run in which the arm was not taken reads 0 sets and
1 frequency), the summed energy of the reading and its largest component.
A program whose results carry no `dvfs_counters` (the parent of the PR
that added them; a configuration with no [dvfs] section) reads nothing."""

import numpy as np


def read(ctx):
    rs = [r for r in ctx.readings if r.get("records")]
    batch = ctx.own.get("batch")
    if not rs or batch is None:
        return None
    res = rs[0]["results"]
    counters = getattr(res, "dvfs_counters", None)
    if not counters:
        return None
    from graphite_tpu.trace.schema import Op

    asked = int((np.asarray(batch.op) == int(Op.DVFS_SET)).sum())
    rejected = int(np.asarray(counters["errors"]).sum())
    final = np.unique(np.asarray(counters["freq_mhz"])[:, 0])
    print(f"DVFS_SET records {asked}, rejected {rejected}; "
          f"{len(final)} distinct final core frequencies "
          f"{[int(f) for f in final]} MHz")
    energy = getattr(res, "energy_pj", None)
    if energy:
        parts = {k: int(np.asarray(v).sum()) for k, v in energy.items()
                 if k != "total"}
        top = max(parts, key=parts.get)
        print(f"energy of one reading: {int(np.asarray(energy['total']).sum())}"
              f" pJ over {len(energy['total'])} tiles, largest component "
              f"{top} {parts[top]} pJ")
    return asked - rejected
