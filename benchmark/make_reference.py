"""Write a configuration's reference digest.  Runs on the CPU; costs no
chip time.

    python benchmark/make_reference.py <config> --origin cpu-backend
    python benchmark/make_reference.py <config> --origin golden,cpu-backend
    python benchmark/make_reference.py <config> --origin golden \
        --save-stats /root/scratch/x.npz          # a long run, kept
    python benchmark/make_reference.py <config> \
        --from-stats golden=/root/scratch/x.npz,cpu-backend=/root/...npz

Origins:
  golden       graphite_tpu.golden.run_golden, the sequential interpreter,
               independent of the engine (simple core only).  Provides
               clock_ps and every mem_counters array.
  cpu-backend  the same engine on XLA's CPU backend: every statistic of
               SimResults.  Not independent of the engine; it catches the
               chip's emulated int64, a miscompile, and any later PR that
               moves a statistic.
Where two origins are given, the first provides what it can and the later
ones the rest; a statistic both provide must agree, or nothing is written
(`--record-disagreement` writes the first one's and records the other's).

The file is `benchmark/references/<config>.json`.  An existing reference
is never overwritten: a changed reference is a changed result, and only a
benchmark PR may make one (delete the file there, and say why in PERF.md).
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

GOLDEN_KEYS = ("clock_ps", "mem_counters.")


def run_origin(origin: str, cfg: dict) -> dict:
    import graphite_tpu  # noqa: F401  (x64)
    from lib import digest, target

    sc = target.build_sim_config(cfg)
    batch = target.build_trace(cfg)
    if origin == "golden":
        from graphite_tpu.golden import run_golden

        stats = digest.statistics(run_golden(sc, batch))
        return {k: v for k, v in stats.items() if k.startswith(GOLDEN_KEYS)}
    if origin == "cpu-backend":
        from graphite_tpu.engine.simulator import Simulator

        sim = Simulator(sc, batch, **cfg["simulator"])
        target.check_expectations(cfg, sim)
        return digest.statistics(sim.run())
    raise SystemExit(f"unknown origin {origin!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--origin", default="cpu-backend")
    ap.add_argument("--save-stats", help="write the (one) origin's raw "
                    "statistics to this .npz and no reference")
    ap.add_argument("--from-stats", help="origin=file.npz[,origin=...]: "
                    "take saved statistics instead of running")
    ap.add_argument("--record-disagreement", action="store_true",
                    help="where a later origin disagrees with an earlier "
                    "one, keep the earlier one's hash as the reference and "
                    "record the later one's beside it (a finding to put in "
                    "PERF.md), instead of writing nothing")
    ap.add_argument("--data-dir", default=HERE, help="where configs/ and "
                    "references/ are (the self-check's: selfcheck_data)")
    args = ap.parse_args(argv)

    import numpy as np

    from lib import digest, target

    from lib import paths

    data_dir = os.path.abspath(args.data_dir)
    paths.BENCH_DIR = data_dir          # where load_json looks
    cfg = target.load_config(args.config)
    out_path = os.path.join(data_dir, "references", args.config + ".json")
    if os.path.exists(out_path) and not args.save_stats:
        raise SystemExit(f"{out_path} exists; a reference is never "
                         f"overwritten")
    if args.from_stats:
        per_origin = []
        for item in args.from_stats.split(","):
            origin, path = item.split("=", 1)
            with np.load(path) as z:
                per_origin.append((origin, {k: z[k] for k in z.files}))
    else:
        per_origin = [(o, run_origin(o, cfg))
                      for o in args.origin.split(",")]
    if args.save_stats:
        (origin, stats), = per_origin
        np.savez(args.save_stats, **stats)
        print(f"saved {len(stats)} statistics of {origin} to "
              f"{args.save_stats}")
        return 0

    reference = {}
    for origin, stats in per_origin:
        for k, h in digest.hashes(stats).items():
            if k in reference:
                if reference[k]["sha256"] != h:
                    if not args.record_disagreement:
                        raise SystemExit(
                            f"{k}: {origin} disagrees with "
                            f"{reference[k]['origin']}; nothing written")
                    reference[k].setdefault("disagrees", {})[origin] = h
                    continue
                reference[k]["agrees"] = reference[k].get("agrees", []) + [
                    origin]
            else:
                reference[k] = {"sha256": h, "origin": origin}
    import jax

    doc = {
        "config": args.config,
        "origins": [o for o, _ in per_origin],
        "made_by": "benchmark/make_reference.py on the CPU; jax "
                   + jax.__version__,
        "trace": cfg["trace"],
        "config_text": cfg["config_text"],
        "digest": digest.combined({k: v["sha256"]
                                   for k, v in reference.items()}),
        "statistics": reference,
    }
    with open(out_path, "x") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path}: {len(reference)} statistics, digest "
          f"{doc['digest']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
