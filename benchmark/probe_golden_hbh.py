"""The serial per-hop golden against the dense hop-by-hop engine at the
cell's own size.  Runs on the CPU (a minute a case); counts only, never a
time or a rate.

    python benchmark/probe_golden_hbh.py                  # both cases
    python benchmark/probe_golden_hbh.py cell-256

`hbh-256-radix`'s reference is the engine's own (`cpu-backend`): the
engine routes the packets of one iteration against the port state of
before the iteration (the same-call batching contract,
`models/network_hop_by_hop.py`), the golden (`golden/interpreter.py`:
`run_golden` with `_HbhNet`, an independent per-hop loop over per-port
queue dicts) one packet at a time.  They agree bit for bit where at most
one packet reads a port in an iteration (tests/test_hbh256_golden.py: the
prefix tree, tier-1) and part on the all-to-all.  This script is what
stands beside the reference at 256 tiles, as `probe_golden_shl2.py` does
for the shared-L2 cell.

- `cell-256`: the cell's own trace under the cell's configuration, golden
  against engine, HELD to the configuration's `golden_envelope`: every
  number must be the stored one (the golden's and the engine's) and every
  percentage inside its limit, else exit 1.  `correct` holds the chip to
  the stored digest exactly, and the digest is the engine's own; this is
  what holds the digest to the golden (tier-1: tests/test_hbh256_cell.py
  checks the engine's side and the limits on the stored numbers).
- `control-256`: the limits' other reading.  The engine under the
  configuration's `control` (`emesh_hop_counter`: no contention, no port
  counter) against the golden under the cell's network must come out
  OUTSIDE the envelope, by at least one limit (exit 1 if it is inside
  every one), on the stored numbers.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CONFIG = "hbh-256-radix"
CASES = ("cell-256", "control-256")


def numbers(res, env: dict) -> dict:
    """{key: the number} of every statistic of `env` (the
    configuration's `golden_envelope.statistics`: a dotted `statistic` of
    the result, reduced by `sum` or `max`); 0 where the result has no
    such statistic (the control's `noc_counters`)."""
    import numpy as np

    out = {}
    for k, v in env.items():
        a = res
        for part in v["statistic"].split("."):
            a = a.get(part) if isinstance(a, dict) else getattr(a, part, None)
            if a is None:
                break
        if a is None:
            out[k] = 0
            continue
        a = np.asarray(a).astype(np.int64)
        out[k] = int(a.max() if v["reduce"] == "max" else a.sum())
    return out


def envelope(golden: dict, engine: dict, env: dict) -> list:
    """[(key, |engine - golden| / golden in percent, limit, outside)]."""
    out = []
    for k, v in env.items():
        pct = 100.0 * abs(engine[k] - golden[k]) / max(1, golden[k])
        out.append((k, pct, v["limit_pct"], pct > v["limit_pct"]))
    return out


def held(env: dict, gold: dict, eng: dict, control: bool) -> int:
    """Print golden against engine beside the limits; 0 if the numbers
    are the stored ones and the envelope came out as it must: every
    statistic inside its limit, or with `control` at least one outside."""
    stored = "control" if control else "engine"
    stale = [k for k, v in env.items()
             if gold[k] != v["golden"] or eng[k] != v[stored]]
    if stale:
        print(f"  numbers that are not the stored ones: {stale}")
        return 1
    rows = envelope(gold, eng, env)
    for k, pct, limit, outside in rows:
        print(f"  {k}: golden {gold[k]} {stored} {eng[k]} ({pct:.3f}%, "
              f"limit {limit}%){' OUTSIDE' if outside else ''}")
    n_out = sum(r[3] for r in rows)
    print(f"  {n_out} of {len(rows)} outside their limits")
    return 0 if (n_out > 0) == control else 1


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
    from lib import target

    batch = target.build_trace(cell)
    print("golden: run_golden on the cell's trace under the cell's "
          "network", flush=True)
    gold = numbers(run_golden(target.build_sim_config(cell), batch), env)
    rc = 0
    for name in names:
        control = name == "control-256"
        text = {**cell["config_text"],
                **(cell["control"]["config_text"] if control else {})}
        print(f"{name}: the engine under {text['network']} against the "
              f"golden", flush=True)
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
