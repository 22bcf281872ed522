"""The north-star-shaped coherence run: 1024-tile SPLASH-2 FFT with the
FULL memory engine (MSI, per-line true addresses, auto-sized directory).

One process, one chip: run it alone, never as the child of a process
that has touched a device.  `chip_smoke.py` builds the same target in
its own process (phase `coh-1024`); this tool remains for by-hand runs
of the variants — hop-by-hop memory NoC, a reduced directory, the
memory-stress workload, other trace lengths.

Usage: python -m graphite_tpu.tools.coherence1024 [--net hbh|hopctr]
       [--dir full|small] [--workload fft|memstress] [--points N]
Prints ONE JSON line: {"config": ..., "instr": N, "wall_s": S, "rate": R}.
"""

from __future__ import annotations

import argparse
import json
import time


def run_one(net: str, dir_size: str, points: int,
            workload: str = "fft") -> dict:
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace.benchmarks import fft_trace

    # the reference's default lax_barrier scheme: at this scale the
    # Simulator auto-selects the host-driven barrier loop (barrier_host;
    # see the selection rule in engine/simulator.py and PERF.md)
    text = config_text(
        1024, shared_mem=True, clock_scheme="lax_barrier",
        network="emesh_hop_by_hop" if net == "hbh" else "emesh_hop_counter")
    if dir_size == "small":
        # quarter-size directory: 0.73 GB of sharer state instead of the
        # auto-sized 2.4 GB — the rung that fits alongside XLA's
        # scatter-staging copies today
        text += "\n[dram_directory]\ntotal_entries = 4096\n" \
                "associativity = 16\n"
    sc = SimConfig(ConfigFile.from_string(text))
    if workload == "memstress":
        from graphite_tpu.trace import synthetic

        batch = synthetic.memory_stress_trace(
            1024, n_accesses=4 * points, working_set_bytes=1 << 15,
            write_fraction=0.4, shared_fraction=0.5, seed=7)
    else:
        batch = fft_trace(1024, points_per_tile=points, use_memory=True)
    # donate the input state: halves the big-state HBM residency
    sim = Simulator(sc, batch, donate=True)
    t0 = time.perf_counter()
    res = sim.run()
    wall = time.perf_counter() - t0
    # warm second instance for the honest steady rate: adopt the first
    # instance's compiled runner so the timed region excludes
    # retrace/recompile (a fresh jit wrapper would re-trace)
    sim2 = Simulator(sc, batch, donate=True)
    sim2.adopt_runner(sim)
    # free the donor's post-run state before the timed run — at 1024
    # tiles it holds the full directory alongside sim2's donated state
    sim.state = None
    t1 = time.perf_counter()
    res = sim2.run()
    wall = time.perf_counter() - t1
    return {
        "config": f"1024t_{workload}_msi_{net}_{dir_size}dir",
        "instr": res.total_instructions,
        "wall_s": round(wall, 2),
        "rate": round(res.total_instructions / wall),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", choices=("hbh", "hopctr"), default="hbh")
    ap.add_argument("--dir", dest="dir_size", choices=("full", "small"),
                    default="full")
    ap.add_argument("--points", type=int, default=16)
    ap.add_argument("--workload", choices=("fft", "memstress"),
                    default="fft")
    args = ap.parse_args()
    out = run_one(args.net, args.dir_size, args.points, args.workload)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
