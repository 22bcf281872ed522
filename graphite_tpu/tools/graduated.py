"""Run the BASELINE.json graduated configs end to end and report each.

The five configs scale the stack up exactly as BASELINE.json lists them:
 1. 16-tile default (simple core, emesh_hop_counter), ping_pong
 2. 64-tile iocoom + pr_l1_pr_l2_dram_directory_msi, SPLASH-2 FFT
 3. 256-tile emesh_hop_by_hop (finite-buffer contention), SPLASH-2 RADIX
 4. 1024-tile mesh sharded over the device mesh, PARSEC blackscholes
 5. 1024-tile + DVFS + power modeling, PARSEC canneal (the benchmark's
    `canneal-dvfs-1024`: two DVFS domains, every tile retuned at every
    temperature step, energy integrated by interval)

Usage: python -m graphite_tpu.tools.graduated [--only N] [--small]
  --small scales tile counts down 4x for quick CPU validation.

Prints one line per config: completion time, instructions, wall seconds,
aggregate simulated instr/s.
"""

from __future__ import annotations

import argparse
import sys
import time


from graphite_tpu.tools._template import config_text


def _cfg(tiles, core="simple", network="emesh_hop_counter",
         shared_mem=False, protocol="pr_l1_pr_l2_dram_directory_msi"):
    return config_text(tiles, core=core, network=network,
                       shared_mem=shared_mem, protocol=protocol,
                       scheme="full_map")


def run_config(n: int, small: bool):
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.trace import synthetic
    from graphite_tpu.trace.benchmarks import (
        blackscholes_trace, canneal_trace, fft_trace, radix_trace,
    )

    scale = 4 if small else 1
    if n == 1:
        tiles = 16 // scale if small else 16
        sc = SimConfig(ConfigFile.from_string(_cfg(tiles)))
        batch = synthetic.ping_pong_trace(tiles)
        label = f"{tiles}-tile simple/hop-counter ping_pong"
    elif n == 2:
        tiles = 64 // scale
        sc = SimConfig(ConfigFile.from_string(
            _cfg(tiles, core="iocoom", shared_mem=True)))
        batch = fft_trace(tiles, points_per_tile=64 if small else 256,
                          use_memory=True)
        label = f"{tiles}-tile iocoom+MSI FFT"
    elif n == 3:
        tiles = 256 // scale
        sc = SimConfig(ConfigFile.from_string(
            _cfg(tiles, network="emesh_hop_by_hop")))
        # SPLASH-2's size, 1M keys at radix 1024 over 256 tiles: the
        # trace of the benchmark's cell `hbh256-radix`
        # (benchmark/configs/hbh-256-radix.json), so that the repo lists
        # one 256-tile RADIX
        batch = radix_trace(tiles, keys_per_tile=256 if small else 4096,
                            radix=1024)
        label = f"{tiles}-tile hop-by-hop RADIX"
    elif n == 4:
        tiles = 1024 // scale
        sc = SimConfig(ConfigFile.from_string(_cfg(tiles)))
        batch = blackscholes_trace(
            tiles, options_per_tile=128 if small else 2048)
        # shard the tile axis over every available device (ICI mesh); on
        # one chip this is the degenerate 1-device mesh, and the driver's
        # dryrun_multichip validates the multi-device path on a CPU mesh
        from graphite_tpu.parallel.mesh import make_tile_mesh

        mesh = make_tile_mesh()
        label = (f"{tiles}-tile sharded blackscholes "
                 f"({mesh.devices.size}-device mesh)")
        return label, Simulator(sc, batch, mesh=mesh)
    elif n == 5:
        tiles = 1024 // scale
        # the target and the trace of the benchmark's cell
        # `canneal1024-dvfs` (benchmark/configs/canneal-dvfs-1024.json),
        # so that the repo lists one 1024-tile canneal: core and caches
        # in one DVFS domain, directory and networks in another, power
        # modelling on, five temperature steps of nine swaps with the
        # rotating V/f schedule; --small keeps a quarter in tiles and
        # footprint.  Host-driven like the cell (ROADMAP M14).
        text = config_text(
            tiles, shared_mem=True, dvfs=True, power=True,
            dvfs_domains="<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE> "
            "<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")
        sc = SimConfig(ConfigFile.from_string(text))
        batch = canneal_trace(tiles, footprint_lines=15625 // scale,
                              swaps_per_tile=9, temperature_steps=5,
                              dvfs_schedule="rotate-levels")
        label = f"{tiles}-tile 2-domain DVFS+power stepped canneal"
        return label, Simulator(sc, batch, barrier_host=True)
    else:
        raise SystemExit(f"no config {n}")
    return label, Simulator(sc, batch)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=int, default=0)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--in-process", action="store_true",
                    help="run all configs in this process instead of one "
                    "child process each")
    args = ap.parse_args()

    if not args.only and not args.in_process:
        # One child per config, one at a time.  A chip belongs to one
        # process, so this parent must never initialise a backend: it
        # imports the package (which imports jax and sets config flags)
        # but calls nothing that touches a device.
        import subprocess

        failures = 0
        for n in (1, 2, 3, 4, 5):
            p = subprocess.run(
                [sys.executable, "-m", "graphite_tpu.tools.graduated",
                 "--only", str(n)] + (["--small"] if args.small else []),
                capture_output=True, text=True)
            for line in p.stdout.strip().splitlines():
                # forward result lines AND the per-config JSON line
                # (phase-skip observability) to the captured output
                if line.startswith(("config", "  ", "{")):
                    print(line)
            if p.returncode != 0:
                failures += 1
                err = (p.stderr or "").strip().splitlines()
                print(f"config {n}: FAIL "
                      f"({err[-1][:120] if err else 'no stderr'})")
        print(f"{failures} failure(s)")
        return 1 if failures else 0

    import graphite_tpu  # noqa: F401

    failures = 0
    for n in ([args.only] if args.only else [1, 2, 3, 4, 5]):
        label, sim = run_config(n, args.small)
        sim.warmup()
        t0 = time.perf_counter()
        res = sim.run()
        dt = time.perf_counter() - t0
        ok = res.func_errors == 0
        failures += 0 if ok else 1
        print(f"config {n}: {label}: {res.completion_time_ps // 1000} ns, "
              f"{res.total_instructions} instrs, {dt:.2f}s wall, "
              f"{res.total_instructions / dt / 1e6:.2f}M instr/s "
              f"{'PASS' if ok else 'FAIL'}")
        # one machine-readable line per config: gate skip rates
        # alongside throughput
        import json

        print(json.dumps({
            "config": n,
            "instr_per_s": round(res.total_instructions / dt),
            "engine_iters": int(sim.last_n_iterations),
            "phase_skips": sim.last_phase_skips,
        }))
        if n == 5:
            # energy is a statistic of the run (SimResults.energy_pj,
            # integrated at the operating point in force): every tile's
            ok5 = (res.energy_pj is not None
                   and int(res.dvfs_counters["errors"].sum()) == 0)
            failures += 0 if ok5 else 1
            if res.energy_pj is not None:
                finals = sorted(
                    {int(f) for f in res.dvfs_counters["freq_mhz"][:, 0]})
                print(f"  final core frequencies (MHz): {finals}; "
                      f"rejected DVFS requests: "
                      f"{int(res.dvfs_counters['errors'].sum())}")
                for k, v in res.energy_pj.items():
                    print(f"  energy {k}: {int(v.sum())} pJ over "
                          f"{len(v)} tiles (min {int(v.min())}, max "
                          f"{int(v.max())} a tile)")
    print(f"{failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
