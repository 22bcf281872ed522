"""The golden interpreter against the engine on `canneal-dvfs-1024`'s own
trace at the cell's own size.  Runs on the CPU (minutes); counts only,
never a time or a rate.

    python benchmark/probe_golden_dvfs.py                 # both cases
    python benchmark/probe_golden_dvfs.py cell-1024

The configuration's reference is the engine's own (`cpu-backend`): 15,625
lines shared by 1,024 free-running tiles race, and the golden
(`golden/interpreter.py` + `golden/memory_model.py`, serial, one record
at a time in simulated-time order) orders a race in another valid way
(BASELINE.md's racy carve-out).  Where nothing races the two agree bit
for bit across the retunes - clocks, memory counters, the V/f table and
every energy component (tests/test_canneal_dvfs.py, 16 tiles, tier-1).
This script is what stands beside the reference at 1024 tiles, as
`probe_golden_hbh.py` does for `hbh-256-radix`.

- `cell-1024`: the cell's own trace under the cell's configuration,
  golden against engine, HELD to the configuration's `golden_envelope`:
  every number must be the stored one and every percentage inside its
  limit, else exit 1.  The trace's directory set pressure is printed
  first (`probe_golden.set_pressure`: the fullest set, the sets over
  their 16 ways).
- `control-1024`: the limits' other reading.  The engine under the
  configuration's `control` (the shipped single DVFS domain: no
  synchronization delay at the L2 <-> directory crossing) against the
  golden under the cell's two domains must come out OUTSIDE the envelope
  by at least one limit (exit 1 if it is inside every one).
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG = "canneal-dvfs-1024"
CASES = ("cell-1024", "control-1024")


def main(argv=None) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    for d in (HERE, ROOT):
        if d not in sys.path:
            sys.path.insert(0, d)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cases", nargs="*", default=list(CASES))
    names = ap.parse_args(argv).cases
    if set(names) - set(CASES):
        ap.error(f"cases are {CASES}")
    with open(os.path.join(HERE, "configs", CONFIG + ".json")) as f:
        cell = json.load(f)
    env = cell["golden_envelope"]["statistics"]

    import graphite_tpu  # noqa: F401  (x64)
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.golden import run_golden
    from graphite_tpu.memory.params import MemParams
    from lib import target
    from probe_golden import set_pressure
    from probe_golden_hbh import held, numbers

    batch = target.build_trace(cell)
    sc = target.build_sim_config(cell)
    mp = MemParams.from_config(sc)
    fullest, over = set_pressure(batch, mp)
    print(f"set pressure of the trace: the fullest directory set holds "
          f"{fullest} distinct lines of {mp.dir_ways} ways, {over} sets "
          f"hold more lines than ways", flush=True)
    print("golden: run_golden on the cell's trace under the cell's two "
          "domains", flush=True)
    gold = numbers(run_golden(sc, batch), env)
    rc = 0
    for name in names:
        control = name == "control-1024"
        text = {**cell["config_text"],
                **(cell["control"]["config_text"] if control else {})}
        print(f"{name}: the engine under domains {text['dvfs_domains']} "
              f"against the golden", flush=True)
        res = Simulator(target.build_sim_config({"config_text": text}),
                        batch, **cell["simulator"]).run()
        if held(env, gold, numbers(res, env), control):
            print(f"  FAILED: {name} must come out "
                  f"{'outside' if control else 'inside'} the envelope, on "
                  f"the stored numbers")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
