"""The serial ATAC golden against the engine on `atac-ackwise-1024-
memstress`'s own trace at the cell's own size.  Runs on the CPU
(minutes); counts only, never a time or a rate.

    python benchmark/probe_golden_atac.py                 # both cases
    python benchmark/probe_golden_atac.py cell-1024

The configuration's reference is the engine's own (`cpu-backend`): 128
lines shared by 1,024 free-running tiles race, and the golden
(`golden/interpreter.py` + `golden/memory_model.py` with the serial hub
oracle `_AtacNet`, one packet at a time in simulated-time order) orders a
race in another valid way (BASELINE.md's racy carve-out); the engine
also routes the packets of one iteration against the hub state of before
the iteration (`queue_models.scatter_queue_delay`'s same-call contract).
Where one packet a hub an iteration holds and nothing races the two agree
bit for bit - clocks, the 21 memory counters and the four hub counters
(tests/test_atac1024_cell.py, 64 tiles, tier-1).  This script is what
stands beside the reference at 1,024 tiles, as `probe_golden_hbh.py` does
for `hbh-256-radix`.

- `cell-1024`: the cell's own trace under the cell's configuration,
  golden against engine, HELD to the configuration's `golden_envelope`:
  every number must be the stored one and every percentage inside its
  limit, else exit 1.  The tail of the tile clocks is printed for both
  (median, 90th, 99th percentile, last) and the largest wait a hub's
  queue can have handed out beside `_ceil_div_bounded`'s 2^32 ceiling.
- `control-1024`: the limits' other reading.  The engine under the
  configuration's `control` (`emesh_hop_counter`: no hub, no
  `atac_counters`) against the golden under the cell's network must come
  out OUTSIDE the envelope by at least one limit (exit 1 if it is inside
  every one).

The two helpers below are the counter readers' too
(`layer_metrics/hub_wait_cycles_per_packet.py`, `hub_fallback_share.py`):
a reader is loaded by its file's path, so what two of them share lives
here, beside `probe_golden_hbh.py`'s `numbers` and `envelope`.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG = "atac-ackwise-1024-memstress"
CASES = ("cell-1024", "control-1024")


def hub_sums(res) -> "dict | None":
    """{counter: (its sum over all hubs, over the send hubs)} of a
    result's `atac_counters`; None where it carries none."""
    import numpy as np

    counters = getattr(res, "atac_counters", None)
    if not counters:
        return None
    half = len(counters["requests"]) // 2
    return {k: (int(np.asarray(v).astype(np.int64).sum()),
                int(np.asarray(v)[:half].astype(np.int64).sum()))
            for k, v in counters.items()}


def print_against_golden(res, env: dict) -> None:
    """One line per statistic of the envelope: this result's number, the
    golden's, their distance and its limit.  Printed by the counter
    readers in every traced run; judged in tier-1
    (tests/test_atac1024_cell.py) on the stored hashes."""
    from probe_golden_hbh import envelope, numbers

    got = numbers(res, env)
    golden = {k: v["golden"] for k, v in env.items()}
    for k, pct, limit, outside in envelope(golden, got, env):
        print(f"golden envelope {k}: reading {got[k]} golden {golden[k]} "
              f"({pct:.3f}%, limit {limit}%){' OUTSIDE' if outside else ''}")


def tail(clock_ps) -> str:
    import numpy as np

    c = np.sort(np.asarray(clock_ps))
    at = [c[len(c) // 2], c[int(len(c) * 0.9)], c[int(len(c) * 0.99)],
          c[-1]]
    return ("median / 90th / 99th percentile / last tile clock: "
            + " / ".join(f"{int(v) / 1e6:.3f}" for v in at) + " us")


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

    import numpy as np

    import graphite_tpu  # noqa: F401  (x64)
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.golden import run_golden
    from graphite_tpu.models import queue_models as qm
    from lib import target
    from probe_golden_hbh import held, numbers

    batch = target.build_trace(cell)
    print("golden: run_golden on the cell's trace under the cell's "
          "network and directory", flush=True)
    gold_res = run_golden(target.build_sim_config(cell), batch)
    gold = numbers(gold_res, env)
    print(f"  golden {tail(gold_res.clock_ps)}")
    rc = 0
    for name in names:
        control = name == "control-1024"
        text = {**cell["config_text"],
                **(cell["control"]["config_text"] if control else {})}
        print(f"{name}: the engine under {text['network']} against the "
              f"golden", flush=True)
        sim = Simulator(target.build_sim_config({"config_text": text}),
                        batch, **cell["simulator"])
        res = sim.run()
        print(f"  engine {tail(res.clock_ps)}")
        if not control:
            # an M/G/1 wait is at most 999 * sum_st2 / (2 * sum_st): the
            # largest any hub's moments can give, beside the ceiling
            hubs = np.asarray(sim.state.mem.noc.hub_queues.data)
            st = np.maximum(hubs[:, qm.COL_SUM_ST], 1)
            most = int((999 * hubs[:, qm.COL_SUM_ST2] // (2 * st)).max())
            print(f"  largest M/G/1 wait the hubs' final moments allow: "
                  f"{most} cycles (ceiling 2^32 - 1 = {2**32 - 1})")
        if held(env, gold, numbers(res, env), control):
            print(f"  FAILED: {name} must come out "
                  f"{'outside' if control else 'inside'} the envelope, on "
                  f"the stored numbers")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
