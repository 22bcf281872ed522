"""The shared-L2 golden model against the shared-L2 engine at sizes tier-1
cannot afford.  Runs on the CPU (minutes); counts only, never a time or a
rate.

    python benchmark/probe_golden_shl2.py                 # every case below
    python benchmark/probe_golden_shl2.py overflow-256x64 cell-1024

`shl2-mesi-1024-memstress`'s reference is the engine's own
(`cpu-backend`): its traffic races, and the golden
(`golden/memory_model_shl2.py`) orders a race in another valid way.  This
script is what stands beside it (with tests/test_shl2_memstress_golden.py,
which is tier-1 and stops at 64 tiles); `probe_golden.py` is the same for
the private-L2 engine, and is not edited.

- `overflow-*`: the cell's generator with its PRIVATE half only
  (`shared_fraction=0`: no two tiles touch one line).  Settled in PR 38
  (PERF.md section 6): in a shared L2, line-disjoint is not
  SLICE-disjoint.  Every tile's private lines live in other tiles'
  slices: a line's slice is `line % n_tiles` and its set in the slice
  `line % l2_sets`, so the lines of many tiles meet in one 8-way set of
  one home.  Once more than 8 live lines meet there, every new one takes
  the LRU way, and which line that is depends on the ORDER in which the
  tiles' requests reached the home.  The golden takes requests by issue
  clock, the engine by arrival at the home: both are valid under lax
  synchronisation.  (First parting at 256 x 64, shrunk: tiles 253 and 61
  each fetch a line of home 44, set 812; 253 issues 20 ns earlier and
  arrives 4 ns later - 14 hops against 2 - so the two sides hold the two
  lines in opposite LRU order, and the 9th line of the set evicts the
  one tile 253 reads again.)  `slice_pressure` says, from the trace alone,
  how many distinct lines meet in the fullest slice set.  An overflowing
  set is necessary for the two sides to part, not sufficient: at 64 x 64
  eleven sets overflow and every statistic is equal at this seed.
- `fits-*`: the same generator with `working_set_bytes` cut so that no
  slice set can overflow (8 lines a tile at 1024 tiles: 8 lines a set).
  Race-free, and BIT-EXACT on `clock_ps` and the 18 memory counters the
  shared-L2 golden keeps (19 statistics): the script exits 1 if it is not.
- `cell-1024`: the cell's own traffic
  (configs/shl2-mesi-1024-memstress.json), as an envelope: |engine -
  golden| / golden of each summed statistic, in percent, and the tiles
  whose statistics differ - HELD to the configuration's
  `golden_envelope` (each statistic's stored golden and engine sums and
  its limit): the script exits 1 where a sum is not the stored one or a
  percentage is over its limit.  `correct` holds the chip to the stored
  digest exactly, and the digest is the engine's own; this is what holds
  the digest to the golden (tier-1:
  tests/test_shl2_memstress_golden.py runs the same check).
- `control-1024`: the limits' other reading.  The engine under the
  configuration's `control` (`pr_l1_sh_l2_msi`: no E state) against the
  golden under the cell's protocol must come out OUTSIDE the envelope, by
  at least one limit (exit 1 if it is inside every one).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG = "shl2-mesi-1024-memstress"

PRIVATE = {"working_set_bytes": 32768, "write_fraction": 0.4,
           "shared_fraction": 0.0, "seed": 7}
# name -> (generator kwargs, must be bit-exact)
CASES = {
    "overflow-64x64": ({**PRIVATE, "n_tiles": 64, "n_accesses": 64}, False),
    "fits-64x64": ({**PRIVATE, "n_tiles": 64, "n_accesses": 64,
                    "working_set_bytes": 8192}, True),
    "overflow-256x64": ({**PRIVATE, "n_tiles": 256, "n_accesses": 64}, False),
    "fits-256x64": ({**PRIVATE, "n_tiles": 256, "n_accesses": 64,
                     "working_set_bytes": 2048}, True),
    "overflow-1024x64": ({**PRIVATE, "n_tiles": 1024, "n_accesses": 64},
                         False),
    "fits-1024x64": ({**PRIVATE, "n_tiles": 1024, "n_accesses": 64,
                      "working_set_bytes": 512}, True),
    "cell-1024": (None, False),
    "control-1024": (None, False),
}


def summed(stats: dict) -> dict:
    """{statistic: its sum over tiles} of `digest.statistics` output
    (`mem_counters.` dropped from the names)."""
    import numpy as np

    return {k.split(".", 1)[-1]: int(np.asarray(v).astype(np.int64).sum())
            for k, v in stats.items()}


def envelope(golden: dict, engine: dict, limits: dict) -> list:
    """[(statistic, |engine - golden| / golden in percent, limit,
    outside)] for every statistic of `limits` ({name: {"limit_pct"}})."""
    out = []
    for k, lim in limits.items():
        pct = 100.0 * abs(engine[k] - golden[k]) / max(1, golden[k])
        out.append((k, pct, lim["limit_pct"], pct > lim["limit_pct"]))
    return out


def held(env: dict, gold: dict, eng: dict, control: bool) -> int:
    """Print golden against engine sums beside the limits of the
    configuration's `golden_envelope` (`env`); 0 if the sums are the
    stored ones and the envelope came out as it must: every statistic
    inside its limit, or with `control` at least one outside."""
    stored = "control" if control else "engine"
    stale = [k for k, v in env.items()
             if gold.get(k) != v["golden"] or eng.get(k) != v[stored]]
    if stale:
        print(f"  sums that are not the stored ones: {stale}")
        return 1
    rows = envelope(gold, eng, env)
    for k, pct, limit, outside in rows:
        print(f"  {k}: golden {gold[k]} {stored} {eng[k]} ({pct:.3f}%, "
              f"limit {limit}%){' OUTSIDE' if outside else ''}")
    n_out = sum(r[3] for r in rows)
    print(f"  {n_out} of {len(rows)} outside their limits")
    return 0 if (n_out > 0) == control else 1


def control_sums(cell: dict) -> "tuple[dict, dict]":
    """(golden under the cell's protocol, engine under the control's),
    summed, on the cell's traffic."""
    import graphite_tpu  # noqa: F401  (x64)
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.golden import run_golden
    from lib import digest, target

    batch = target.build_trace(cell)
    gold = run_golden(target.build_sim_config(cell), batch)
    res = Simulator(target.build_sim_config({"config_text": {
        **cell["config_text"], **cell["control"]["config_text"]}}), batch,
        **cell["simulator"]).run()
    return summed(digest.statistics(gold)), summed(digest.statistics(res))


def slice_pressure(batch, mp) -> "tuple[int, int]":
    """(distinct lines in the fullest set of any L2 slice, slice sets
    holding more lines than ways), from the trace alone."""
    import numpy as np

    from graphite_tpu.trace.schema import FLAG_MEM0_VALID

    lines = np.unique(
        batch.addr0[(batch.flags & FLAG_MEM0_VALID) != 0].astype(np.int64)
        // mp.line_size)
    _, per_set = np.unique(
        (lines % mp.n_tiles) * mp.l2.num_sets + lines % mp.l2.num_sets,
        return_counts=True)
    return int(per_set.max()), int((per_set > mp.l2.num_ways).sum())


def compare(kwargs: dict, config_text_args: dict) -> dict:
    """`probe_golden.compare` (golden against the host-driven engine, every
    statistic the golden keeps) with the SLICE sets' pressure in place of
    the directory sets'."""
    from graphite_tpu.memory.params import MemParams
    from graphite_tpu.trace.synthetic import memory_stress_trace
    from lib import target
    from probe_golden import compare as golden_against_engine

    out = golden_against_engine(kwargs, config_text_args)
    mp = MemParams.from_config(target.build_sim_config({"config_text": {
        **config_text_args, "tiles": kwargs["n_tiles"]}}))
    out["fullest_set_lines"], out["sets_over_ways"] = slice_pressure(
        memory_stress_trace(**kwargs), mp)
    out["l2_ways"] = mp.l2.num_ways
    return out


def main(argv=None) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    for d in (HERE, ROOT):
        if d not in sys.path:
            sys.path.insert(0, d)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="*", default=list(CASES))
    names = ap.parse_args(argv).cases
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        cell = json.load(f)
    rc = 0
    for name in names:
        kwargs, exact = CASES[name]
        if name == "control-1024":
            print(f"{name}: the engine under {cell['control']['config_text']}"
                  f" against the golden under the cell's protocol",
                  flush=True)
            if held(cell["golden_envelope"]["statistics"],
                    *control_sums(cell), control=True):
                print(f"  FAILED: {name} must come out outside the "
                      f"envelope, on the stored sums")
                rc = 1
            continue
        out = compare(kwargs or cell["trace"]["kwargs"], cell["config_text"])
        d = out["differ"]
        print(f"{name}: {len(d)} of {out['statistics']} statistics differ; "
              f"fullest slice set {out['fullest_set_lines']} lines of "
              f"{out['l2_ways']} ways, {out['sets_over_ways']} sets over; "
              f"func_errors {out['func_errors']}; CPU s {out['cpu_s']}",
              flush=True)
        for k, v in d.items():
            rel = 100.0 * abs(v["engine"] - v["golden"]) / max(1, v["golden"])
            print(f"  {k}: golden {v['golden']} engine {v['engine']} "
                  f"({rel:.3f}%), {v['tiles']} tiles")
        if (exact and d) or out["func_errors"]:
            print(f"  FAILED: {name} must be bit-exact with no functional "
                  f"error")
            rc = 1
        if name == "cell-1024" and held(
                cell["golden_envelope"]["statistics"],
                {k: v["golden"] for k, v in d.items()},
                {k: v["engine"] for k, v in d.items()}, control=False):
            print(f"  FAILED: {name} must lie inside the envelope, on the "
                  f"stored sums")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
