"""Write a campaign configuration's reference: one digest per (stream,
latency) of the traffic's pool.  Runs on the CPU; costs no chip time.

    python benchmark/make_reference_campaign.py <config> --traffic <mix>

A served job is, by definition, bit-identical to a plain
`Simulator(config with [dram] latency = L, trace).run()` on its own.  The
reference is that solo run on XLA's CPU backend (origin `cpu-backend`, as
`make_reference.py`'s), made WITHOUT `serve/`, `sweep/`, `vmap` or the
knob operands: the latency goes in through the config text.  It is NOT
independent of the engine, and `--origin golden` would not help: the
golden interpreter models no iocoom, and where free-running tiles share
lines it orders same-line races differently from the engine (both valid),
so it gives no exact digest of a campaign's traffic.  The served program
is held to the golden in `tests/test_campaign_golden.py` instead.  For every stream of the traffic's `pool` and
every latency of its `dram_latency_ns` the file keeps the SHA-256 over
the hashes of all statistics of `SimResults` (`lib/digest.py`), under
`jobs["s<stream>-L<latency>"]`.  The configuration's own target (its
`config_text` as written: the default latency, and its `trace`: the
pool's first stream) is kept statistic by statistic as well, in the form
`make_reference.py` writes, so that the loaders and `selfcheck.py` read
this file like any other reference.

`--workers N` makes the runs in N processes (each compiles its own solo
programs: a trace is a compile-time constant of the solo program).  An
existing reference is never overwritten: a changed reference is a changed
result, and only a benchmark PR may make one.
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

ORIGIN = "cpu-backend"


def solo_hashes(cfg: dict, driver_name: str, stream: int,
                latency_ns: "int | None") -> dict:
    """{statistic: sha256} of one plain solo run on the CPU backend.
    `latency_ns` None runs the configuration's text as written."""
    import graphite_tpu  # noqa: F401  (x64)
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.tools._template import config_text
    from lib import digest, paths, target

    args = dict(cfg["config_text"])
    text = config_text(args.pop("tiles"), **args)
    if latency_ns is not None:
        text += f"\n[dram]\nlatency = {int(latency_ns)}\n"
    driver = paths.load_module("drivers", driver_name)
    sim = Simulator(SimConfig(ConfigFile.from_string(text)),
                    driver.pool_trace(cfg, stream), **cfg["simulator"])
    target.check_expectations(cfg, sim)
    if latency_ns is not None \
            and sim.params.mem.dram_latency_ns != int(latency_ns):
        raise SystemExit(f"the built target's DRAM latency is "
                         f"{sim.params.mem.dram_latency_ns} ns, not "
                         f"{latency_ns}")
    return digest.hashes(digest.statistics(sim.run()))


def _one(task):
    return task[2], task[3], solo_hashes(*task)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--data-dir", default=HERE, help="where configs/, "
                    "traffic/ and references/ are (the self-check's: "
                    "selfcheck_data)")
    args = ap.parse_args(argv)

    from lib import digest, paths, target

    data_dir = os.path.abspath(args.data_dir)
    paths.BENCH_DIR = data_dir          # where load_json looks
    cfg = target.load_config(args.config)
    traffic = paths.load_json("traffic", args.traffic + ".json")
    out_path = os.path.join(data_dir, "references", args.config + ".json")
    if os.path.exists(out_path):
        raise SystemExit(f"{out_path} exists; a reference is never "
                         f"overwritten")
    paths.BENCH_DIR = HERE              # drivers live with the benchmark
    driver = paths.load_module("drivers", traffic["driver"])
    driver.check_generator(cfg, traffic)

    name = traffic["driver"]
    tasks = [(cfg, name, traffic["pool"][0], None)] + [
        (cfg, name, s, lat) for s in traffic["pool"]
        for lat in traffic["dram_latency_ns"]]
    if args.workers > 1:
        import multiprocessing as mp

        with mp.get_context("spawn").Pool(args.workers) as pool:
            done = pool.map(_one, tasks, chunksize=1)
    else:
        done = [_one(t) for t in tasks]

    (_, _, own), jobs = done[0], done[1:]
    statistics = {k: {"origin": ORIGIN, "sha256": h}
                  for k, h in sorted(own.items())}
    import jax

    doc = {
        "config": args.config,
        "traffic": args.traffic,
        "config_text": cfg["config_text"],
        "trace": cfg["trace"],
        "origin": ORIGIN,
        "origins": [ORIGIN],
        "made_by": f"benchmark/make_reference_campaign.py on the CPU; "
                   f"jax {jax.__version__}",
        "statistics": statistics,
        "digest": digest.combined(own),
        "jobs": {driver.job_key(s, lat): digest.combined(hs)
                 for s, lat, hs in jobs},
        "jobs_statistics": len(own),
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"{out_path}: {len(doc['jobs'])} digests of {len(own)} "
          f"statistics each; the configuration's own run "
          f"{doc['digest'][:16]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
