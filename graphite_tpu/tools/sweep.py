"""Campaign CLI: run a knob-grid x seed sweep as ONE compiled program.

The batched-campaign frontend (sweep/runner.py): a grid spec over timing
knobs crossed with trace seeds becomes a [B]-batched vmapped run — one
XLA compile for the whole campaign, one JSON line per simulation on
stdout, one trailing summary line with campaign throughput (sims/s and
amortized per-sim ms/iteration).

Usage:
  python -m graphite_tpu.tools.sweep --tiles 16 \\
      --knob dram_latency_ns=50,100,200 --knob hop_latency_cycles=1,2
  python -m graphite_tpu.tools.sweep --seeds 1,2,3,4   # trace sweep
  python -m graphite_tpu.tools.sweep --dryrun          # tiny CPU smoke

Knob axes cross-multiply (grid_points); seeds replicate the grid per
trace.  `--dryrun` pins JAX to CPU and shrinks the workload — the
smoke-test shape `tests/test_sweep.py` also exercises.

Energy in the rows: `SweepOutcome.json_rows()` gives every sim of a
target that models power (`[dvfs]` + `[general] enable_power_modeling`)
`energy_pj_total` (the integrated `SimResults.energy_pj["total"]` summed
over the tiles - not the telemetry series' `energy_pj`),
`dvfs_transitions` and, where the whole sim ended on one CORE frequency,
`dvfs_level_mhz`.  Such a target is a config text, so a program (class)
of its own, and runs on the `solo` layout only (a device mesh refuses
it: the energy accumulators have no shard spec).  This CLI builds no
such target; `SweepRunner(config, traces)` takes one from code.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def parse_knob_axes(specs: "list[str]") -> dict:
    """--knob name=v1,v2,... (repeatable) -> {name: [int, ...]}."""
    axes = {}
    for spec in specs:
        if "=" not in spec:
            raise SystemExit(f"--knob {spec!r}: expected name=v1,v2,...")
        name, _, vals = spec.partition("=")
        try:
            axes[name.strip()] = [int(v) for v in vals.split(",") if v.strip()]
        except ValueError:
            raise SystemExit(f"--knob {spec!r}: values must be integers")
        if not axes[name.strip()]:
            raise SystemExit(f"--knob {spec!r}: no values")
    return axes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="batched simulation campaign (one compile, B sims); "
        "rows of a power target carry energy_pj_total, dvfs_transitions, "
        "dvfs_level_mhz")
    ap.add_argument("--tiles", type=int, default=16)
    ap.add_argument("--workload", default="memstress",
                    help="memstress (seedable) or a trace/benchmarks name")
    ap.add_argument("--knob", action="append", default=[],
                    metavar="NAME=V1,V2,...",
                    help="knob axis (repeatable; axes cross-multiply)")
    ap.add_argument("--seeds", default="7",
                    help="comma-separated memstress trace seeds")
    ap.add_argument("--accesses", type=int, default=40,
                    help="memstress accesses per tile")
    ap.add_argument("--clock", default="lax_barrier",
                    choices=("lax", "lax_barrier"))
    ap.add_argument("--protocol", default="pr_l1_pr_l2_dram_directory_msi")
    ap.add_argument("--network", default="emesh_hop_counter")
    ap.add_argument("--max-quanta", type=int, default=1_000_000)
    ap.add_argument("--layout", default=None,
                    help="device layout: solo | batch | tile | 2d | "
                    "DBxDT (e.g. 2x2 — batch_shards x tile_shards; "
                    "default: auto from residency + device count)")
    ap.add_argument("--dryrun", action="store_true",
                    help="CPU smoke: force JAX_PLATFORMS=cpu, shrink the "
                    "workload, cap the grid at 4 points")
    args = ap.parse_args(argv)

    if args.dryrun:
        # must land before jax initializes its backends
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.tiles = min(args.tiles, 8)
        args.accesses = min(args.accesses, 16)

    import graphite_tpu  # noqa: F401  (x64)

    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.sweep import SweepRunner, grid_points
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace import synthetic

    axes = parse_knob_axes(args.knob)
    try:
        grid = grid_points(**axes) if axes else [{}]
    except ValueError as e:
        raise SystemExit(f"--knob: {e}")
    seeds = [int(s) for s in args.seeds.split(",") if s.strip()]
    if args.dryrun:
        grid = grid[:4] if axes else [
            {"dram_latency_ns": 60}, {"dram_latency_ns": 180}]
        seeds = seeds[:2]

    shared = args.workload == "memstress"
    sc = SimConfig(ConfigFile.from_string(config_text(
        args.tiles, shared_mem=shared, protocol=args.protocol,
        network=args.network, clock_scheme=args.clock)))

    def make_trace(seed):
        if args.workload == "memstress":
            return synthetic.memory_stress_trace(
                args.tiles, n_accesses=args.accesses,
                working_set_bytes=1 << 13, write_fraction=0.4,
                shared_fraction=0.5, seed=seed)
        from graphite_tpu.trace.benchmarks import BENCHMARKS

        if args.workload not in BENCHMARKS:
            raise SystemExit(
                f"unknown workload {args.workload!r} (memstress or: "
                f"{', '.join(sorted(BENCHMARKS))})")
        return BENCHMARKS[args.workload](args.tiles)

    # seeds x grid: each seed's trace replicated across the knob grid
    if args.workload != "memstress" and len(seeds) > 1:
        raise SystemExit("--seeds applies to the memstress workload only")
    from graphite_tpu.sweep import pack_traces

    traces, points, meta = [], [], []
    for s in seeds:
        tr = make_trace(s)
        for p in grid:
            traces.append(tr)
            points.append(p)
            meta.append(s)

    layout = args.layout
    if layout and "x" in layout:
        try:
            db, dt = (int(v) for v in layout.split("x"))
        except ValueError:
            raise SystemExit(
                f"bad --layout {layout!r}: DBxDT needs two integers")
        layout = (db, dt)
    runner = SweepRunner(sc, pack_traces(traces, seeds=meta), points,
                         layout=layout)
    t0 = time.perf_counter()
    out = runner.run(max_quanta=args.max_quanta)
    elapsed = time.perf_counter() - t0
    for row in out.json_rows():
        print(json.dumps(row))
    total_iters = int(out.n_iterations.sum())
    print(json.dumps({
        "summary": True,
        "sweep_batch": runner.n_sims,
        "layout": out.layout,
        "wall_s": round(elapsed, 3),
        "sims_per_s": round(runner.n_sims / elapsed, 3),
        # amortized per-sim cost of one engine iteration: campaign wall
        # over the total useful iterations served across the batch
        "ms_per_iter_amortized": round(1000 * elapsed / max(total_iters, 1),
                                       4),
        "dryrun": bool(args.dryrun),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
