"""Write a V/f campaign configuration's reference: for every (stream,
level) of the traffic's pool the golden interpreter's numbers and the
engine's digest.  Runs on the CPU; costs no chip time.

    python benchmark/make_reference_vf.py <config> --traffic <mix>

Two references, both of the job's own trace at the configuration's own
size (a job's level is in its trace, `drivers/campaign_vf_closed.py:
pool_trace`, so nothing is swept but the trace), and `correct` holds
every envelope to both (`campaign_vf_closed.judge`):

- `golden` (origin `golden`): `golden/interpreter.py: run_golden`, the
  serial interpreter - independent of `engine/`, `sweep/`, `serve/` and
  `vmap`.  canneal's swaps race and the golden takes another valid order
  than the engine (BASELINE.md), so it gives no exact digest of this
  traffic: under `golden["jobs"]["s<stream>-f<MHz>"]` the file keeps its
  NUMBER for every statistic of the configuration's
  `golden_envelope.statistics` (clocks, barrier waits, misses,
  invalidations, DRAM reads, the total energy and every `energy_pj`
  component), and a served envelope must lie inside each one's
  `limit_pct` of it.
- `jobs` (origin `cpu-backend`, as `make_reference_campaign.py`'s, whose
  `solo_hashes` makes each of them): plain solo `Simulator(config, the
  job's trace).run()` on XLA's CPU backend, WITHOUT `serve/`, `sweep/` or
  `vmap` - NOT independent of the engine; the bit-exact check on the
  lowering and on the served path.  One SHA-256 over the hashes of all
  statistics of `SimResults` (`lib/digest.py`: every `energy_pj`
  component and every `dvfs_counters` column among them) a job.  The
  configuration's own target (its `trace`: the pool's first stream at
  the first level) is kept statistic by statistic as well, in the form
  `make_reference.py` writes.

`--workers N` makes the runs in N processes (each compiles its own solo
programs: a trace is a compile-time constant of the solo program).  An
existing reference is never overwritten: a changed reference is a changed
result, and only a benchmark PR may make one.
"""

import argparse
import json
import os
import sys

from make_reference_campaign import HERE, ORIGIN, solo_hashes


def golden_numbers(cfg: dict, driver_name: str, job) -> dict:
    """{statistic of the configuration's `golden_envelope`: the golden
    interpreter's number} of one job."""
    import graphite_tpu  # noqa: F401  (x64)
    from graphite_tpu.golden import run_golden
    from lib import paths, target
    from probe_golden_hbh import numbers

    driver = paths.load_module("drivers", driver_name)
    return numbers(run_golden(target.build_sim_config(cfg),
                              driver.pool_trace(cfg, job)),
                   cfg["golden_envelope"]["statistics"])


def _one(task):
    origin, cfg, name, job = task
    if origin == "golden":
        return golden_numbers(cfg, name, job)
    return solo_hashes(cfg, name, job, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--workers", type=int, default=1)
    args = ap.parse_args(argv)

    from lib import digest, paths, target

    cfg = target.load_config(args.config)
    traffic = paths.load_json("traffic", args.traffic + ".json")
    out_path = os.path.join(HERE, "references", args.config + ".json")
    if os.path.exists(out_path):
        raise SystemExit(f"{out_path} exists; a reference is never "
                         f"overwritten")
    name = traffic["driver"]
    driver = paths.load_module("drivers", name)
    driver.check_generator(cfg, traffic)

    jobs = [(s, k) for s in traffic["pool"] for k in traffic["levels"]]
    # the configuration's own trace is the first job's: one run serves both
    tasks = [(origin, cfg, name, job) for origin in (ORIGIN, "golden")
             for job in jobs]
    if args.workers > 1:
        import multiprocessing as mp

        with mp.get_context("spawn").Pool(args.workers) as pool:
            done = pool.map(_one, tasks, chunksize=1)
    else:
        done = [_one(t) for t in tasks]
    keys = [driver.job_key(s, driver.level_mhz(k)) for s, k in jobs]
    solo, gold = done[:len(jobs)], done[len(jobs):]
    own = solo[0]
    import jax

    doc = {
        "config": args.config,
        "traffic": args.traffic,
        "config_text": cfg["config_text"],
        "trace": cfg["trace"],
        "origin": ORIGIN,
        "origins": [ORIGIN, "golden"],
        "made_by": f"benchmark/make_reference_vf.py on the CPU; "
                   f"jax {jax.__version__}",
        "statistics": {k: {"origin": ORIGIN, "sha256": h}
                       for k, h in sorted(own.items())},
        "digest": digest.combined(own),
        "jobs": {key: digest.combined(hs) for key, hs in zip(keys, solo)},
        "jobs_statistics": len(own),
        "golden": {
            "origin": "golden",
            "made_by": "golden/interpreter.py: run_golden on each job's "
                       "own trace, serial, on the CPU",
            "jobs": dict(zip(keys, gold)),
        },
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"{out_path}: {len(doc['jobs'])} digests of {len(own)} "
          f"statistics each and as many golden jobs of {len(gold[0])} "
          f"numbers; the configuration's own run {doc['digest'][:16]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
