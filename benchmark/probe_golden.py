"""The golden interpreter against the engine at sizes tier-1 cannot afford.
Runs on the CPU (minutes); counts only, never a time or a rate.

    python benchmark/probe_golden.py                 # every case below
    python benchmark/probe_golden.py overflow-256x64 cell-1024

`coh-1024-memstress`'s reference is the engine's own (`cpu-backend`): its
traffic races, and the golden orders a race in another valid way.  This
script is what stands beside it (with tests/test_memstress1024_golden.py,
which is tier-1 and stops at 64 tiles):

- `overflow-*`: the cell's generator with its PRIVATE half only
  (`shared_fraction=0`: no two tiles touch one line).  ROADMAP M6, settled
  in PR 35: the two sides differ by a miss or two, because tiles that share
  no line still meet in a DIRECTORY SET.  The generator bases tile t's
  working set at t * working_set_bytes, a home is `line % n_tiles` and a
  directory set `line % dir_sets`, so the lines of many tiles fall into one
  16-way set of one home; once more than 16 live lines meet there, every
  new one replaces a victim (NULLIFY: the first way with the fewest
  sharers) and the victim depends on the ORDER in which the tiles' requests
  reached the home.  The golden takes requests by issue clock, the engine
  by arrival within an iteration: both are valid under lax
  synchronisation.  `set_pressure` says, from the trace alone, how many
  distinct lines meet in the fullest set.
- `fits-*`: the same generator with `working_set_bytes` cut so that no
  directory set can overflow (16 lines a tile at 1024 tiles: 16 lines a
  set).  Race-free, and BIT-EXACT on `clock_ps` and all 21 memory
  counters: the script exits 1 if it is not.
- `cell-1024`: the cell's own traffic (configs/coh-1024-memstress.json),
  as an envelope: |engine - golden| / golden of each summed statistic, in
  percent, and the tiles whose clocks differ.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

PRIVATE = {"working_set_bytes": 32768, "write_fraction": 0.4,
           "shared_fraction": 0.0, "seed": 7}
# name -> (generator kwargs, must be bit-exact)
CASES = {
    "fits-64x64": ({**PRIVATE, "n_tiles": 64, "n_accesses": 64}, True),
    "overflow-256x64": ({**PRIVATE, "n_tiles": 256, "n_accesses": 64}, False),
    "fits-256x64": ({**PRIVATE, "n_tiles": 256, "n_accesses": 64,
                     "working_set_bytes": 4096}, True),
    "sparse-1024x8": ({**PRIVATE, "n_tiles": 1024, "n_accesses": 8}, False),
    "overflow-1024x16": ({**PRIVATE, "n_tiles": 1024, "n_accesses": 16},
                         False),
    "overflow-1024x64": ({**PRIVATE, "n_tiles": 1024, "n_accesses": 64},
                         False),
    "fits-1024x64": ({**PRIVATE, "n_tiles": 1024, "n_accesses": 64,
                      "working_set_bytes": 1024}, True),
    "cell-1024": (None, False),
}


def set_pressure(batch, mp) -> "tuple[int, int]":
    """(distinct lines in the fullest directory set, sets holding more
    lines than ways), from the trace alone."""
    import numpy as np

    from graphite_tpu.trace.schema import FLAG_MEM0_VALID

    lines = np.unique(
        batch.addr0[(batch.flags & FLAG_MEM0_VALID) != 0].astype(np.int64)
        // mp.line_size)
    homes = np.asarray(mp.mc_tiles)[lines % len(mp.mc_tiles)]
    _, per_set = np.unique(homes * mp.dir_sets + lines % mp.dir_sets,
                           return_counts=True)
    return int(per_set.max()), int((per_set > mp.dir_ways).sum())


def compare(kwargs: dict, config_text_args: dict) -> dict:
    import numpy as np

    import graphite_tpu  # noqa: F401  (x64)
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.golden import run_golden
    from graphite_tpu.trace.synthetic import memory_stress_trace
    from lib import target

    sc = target.build_sim_config({"config_text": {
        **config_text_args, "tiles": kwargs["n_tiles"]}})
    batch = memory_stress_trace(**kwargs)
    t0 = time.perf_counter()
    gold = run_golden(sc, batch)
    t1 = time.perf_counter()
    sim = Simulator(sc, batch, barrier_host=True)
    res = sim.run()
    t2 = time.perf_counter()
    g = {"clock_ps": np.asarray(gold.clock_ps), **{
        k: np.asarray(v) for k, v in gold.mem_counters.items()}}
    e = {"clock_ps": np.asarray(res.clock_ps), **{
        k: np.asarray(res.mem_counters[k]) for k in gold.mem_counters}}
    differ = {}
    for k in g:
        if not np.array_equal(g[k], e[k]):
            n = kwargs["n_tiles"]
            differ[k] = {
                "golden": int(g[k].astype(np.int64).sum()),
                "engine": int(e[k].astype(np.int64).sum()),
                "tiles": int((g[k] != e[k]).reshape(n, -1).any(1).sum())}
    fullest, over = set_pressure(batch, sim.params.mem)
    return {"statistics": len(g), "differ": differ,
            "fullest_set_lines": fullest, "sets_over_ways": over,
            "dir_ways": sim.params.mem.dir_ways,
            "func_errors": int(np.asarray(res.func_errors)),
            "cpu_s": {"golden": round(t1 - t0), "engine": round(t2 - t1)}}


def main(argv=None) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    for d in (HERE, ROOT):
        if d not in sys.path:
            sys.path.insert(0, d)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="*", default=list(CASES))
    names = ap.parse_args(argv).cases
    with open(os.path.join(HERE, "configs", "coh-1024-memstress.json")) as f:
        cell = json.load(f)
    rc = 0
    for name in names:
        kwargs, exact = CASES[name]
        out = compare(kwargs or cell["trace"]["kwargs"], cell["config_text"])
        d = out["differ"]
        print(f"{name}: {len(d)} of {out['statistics']} statistics differ; "
              f"fullest directory set {out['fullest_set_lines']} lines of "
              f"{out['dir_ways']} ways, {out['sets_over_ways']} sets over; "
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
    return rc


if __name__ == "__main__":
    sys.exit(main())
