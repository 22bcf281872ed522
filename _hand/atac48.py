"""PR 48's first chip call: the 1,024-tile ATAC target through the normal
path (`Simulator(config, trace, barrier_host=True)` -> `warmup()` ->
`run()`), every statistic against the stored CPU-backend reference,
statistic by statistic, with the numbers kept for a diff on the host.

    chiprun --chips 1 -- python _hand/atac48.py            # on the chip
    JAX_PLATFORMS=cpu python _hand/atac48.py --tiles 64    # rehearsal

Writes `chiprun_out/pr48/stats_<platform>.npz` (every statistic of the
run) and prints the sum and the largest element of each statistic whose
hash is not the reference's.  The reference stores hashes only: the CPU's
numbers to set them beside are the builder's
(`make_reference.py --save-stats`).
"""

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiles", type=int, default=1024)
    args = ap.parse_args(argv)

    import numpy as np

    import graphite_tpu  # noqa: F401  (x64; places the compile cache)
    import jax
    from graphite_tpu.engine.simulator import Simulator
    from lib import digest, target

    cfg = target.load_config("atac-ackwise-1024-memstress")
    if args.tiles != 1024:
        cfg["config_text"]["tiles"] = args.tiles
        cfg["trace"]["kwargs"]["n_tiles"] = args.tiles
        cfg["expect"] = {}
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    sim = Simulator(target.build_sim_config(cfg), target.build_trace(cfg),
                    **cfg["simulator"])
    target.check_expectations(cfg, sim)
    initial = sim.state
    t0 = time.perf_counter()
    sim.warmup()
    print(f"warmup {time.perf_counter() - t0:.1f} s", flush=True)
    for i in range(2):
        sim.state = initial
        t0 = time.perf_counter()
        res = sim.run()
        print(f"run {i}: {time.perf_counter() - t0:.3f} s, "
              f"{sim.last_n_iterations} iterations, {res.n_quanta} quanta, "
              f"{sim.last_run_dispatches} dispatches, func_errors "
              f"{res.func_errors}", flush=True)
    stats = digest.statistics(res)
    out = os.path.join(ROOT, "chiprun_out", "pr48")
    os.makedirs(out, exist_ok=True)
    np.savez(os.path.join(out, f"stats_{dev.platform}.npz"), **stats)
    if args.tiles == 1024:
        ref = target.load_reference("atac-ackwise-1024-memstress")
        bad = digest.compare(digest.hashes(stats), ref["statistics"])
        print(f"statistics differing from the reference: {len(bad)} of "
              f"{len(ref['statistics'])}")
        for k in bad:
            a = np.asarray(stats[k]).astype(np.int64) if k in stats else None
            print(f"  {k}: " + ("missing" if a is None else
                                f"sum {int(a.sum())} max {int(a.max())}"))
    peak = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {peak.get('peak_bytes_in_use')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
