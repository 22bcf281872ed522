"""Measure multi-device step wall-clock vs single-device (virtual mesh).

`parallel/mesh.py` replicates the sync tables and `func_mem` and relies
on whole-program GSPMD — the concern is that mailbox
scatters and replicated-buffer updates lower to cross-device collectives
that make the 8-device step *slower* than one device.  Real ICI speedups
cannot be measured on one chip; what a virtual CPU mesh CAN measure is
pathology: if the 8-device program is catastrophically slower than the
single-device program on identical hardware resources, the sharded lowering
is broken.  Run:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m graphite_tpu.tools.shard_bench

Output is JSON lines — one row per workload with
{"metric", "value", "unit", "vs_baseline"} plus
companions: the single-device and GSPMD wall-clocks, and the STATIC
collective counts of the packed-exchange lowering (analysis/comms.py
over a SweepRunner tile-axis lowering of the same config —
`collectives_per_iter` / `ici_bytes_per_iter` / stray count), so every
measured number sits next to the collective budget that explains it.
`vs_baseline` is the shard_map/single wall ratio: ~1 means the sharded
lowering costs what the math costs; GSPMD's ~10x is the pathology the
packed exchange exists to avoid.

With fewer than 2 visible devices the bench emits a single
{"skipped": true, "reason": ...} row and exits 0 — the measured
comparison needs a mesh, and a silent half-run would look like data.
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np


def _timed(sc, batch, mesh, repeats=3, spmd=None):
    """Best-of-N wall-clock of the compiled run, compile excluded: warm up
    and time the SAME Simulator instance (each instance owns its own jitted
    runner), restoring the initial state between repeats."""
    from graphite_tpu.engine.simulator import Simulator

    sim = Simulator(sc, batch, mesh=mesh, spmd=spmd)
    init_state = sim.state
    sim.warmup()
    best = float("inf")
    res = None
    for _ in range(repeats):
        sim.state = init_state
        t0 = time.perf_counter()
        res = sim.run()
        best = min(best, time.perf_counter() - t0)
    return best, res


def _static_comms(sc, batch, n_dev: int) -> dict:
    """The static collective budget of the same config sharded over the
    tile axis: lower a (1, n_dev) batch x tile campaign of `batch` over
    a device-less AbstractMesh (no devices consumed — pure tracing) and
    run the comms extractor over its main loop.  These are the numbers
    BUDGETS.json ratchets for the registered mesh programs, computed
    here for the BENCHED shape so the measured ratio sits next to the
    collective count that explains it."""
    from graphite_tpu.analysis import comms
    from graphite_tpu.analysis.audit import spec_from_sweep
    from graphite_tpu.sweep import SweepRunner

    runner = SweepRunner(sc, [batch], layout=(1, n_dev))
    spec = spec_from_sweep("shard-bench", runner, 4096)
    rep = comms.comms_report(spec)
    return {
        "static_collectives_per_iter": int(rep.collectives_per_iter),
        "static_ici_bytes_per_iter": int(rep.ici_bytes_per_iter),
        "static_stray_collectives": len(rep.strays()),
    }


def main() -> int:
    n_dev = len(jax.devices())
    if n_dev < 2:
        print(json.dumps({
            "skipped": True,
            "reason": f"needs a multi-device platform (found {n_dev} "
            f"device); run with JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8",
            "metric": "multi-device step wall-clock"}))
        return 0

    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.parallel.mesh import make_tile_mesh
    from graphite_tpu.tools._template import (
        coherence_stress_workload, config_text,
    )
    from graphite_tpu.trace import synthetic

    mesh = make_tile_mesh(n_dev)
    rows = []

    # workload 1: full-MSI coherence stress (the [T, T] mailbox path)
    sc, batch = coherence_stress_workload(64, n_accesses=200)
    t1, r1 = _timed(sc, batch, None)
    tsm, rsm = _timed(sc, batch, mesh)  # shard_map (default)
    np.testing.assert_array_equal(r1.clock_ps, rsm.clock_ps)
    tg, rg = _timed(sc, batch, mesh, spmd="gspmd")
    np.testing.assert_array_equal(r1.clock_ps, rg.clock_ps)
    rows.append(("msi_stress_64t", sc, batch, t1, tsm, tg))

    # workload 2: memoryless message ring (the USER-net mailbox path)
    sc2 = SimConfig(ConfigFile.from_string(config_text(64)))
    batch2 = synthetic.message_ring_batch(64, n_rounds=64,
                                          compute_per_round=8)
    t1b, _ = _timed(sc2, batch2, None)
    tsmb, _ = _timed(sc2, batch2, mesh)
    tgb, _ = _timed(sc2, batch2, mesh, spmd="gspmd")
    rows.append(("ring_64t", sc2, batch2, t1b, tsmb, tgb))

    # workload 3: shared-L2 coherence stress — round 5 put the shL2
    # engines on the packed exchange; its multi-device overhead should
    # sit near the MSI program's, not GSPMD's ~10x
    sc3, batch3 = coherence_stress_workload(
        64, n_accesses=200, protocol="pr_l1_sh_l2_msi")
    t1c, r1c = _timed(sc3, batch3, None)
    tsmc, rsmc = _timed(sc3, batch3, mesh)
    np.testing.assert_array_equal(r1c.clock_ps, rsmc.clock_ps)
    tgc, rgc = _timed(sc3, batch3, mesh, spmd="gspmd")
    np.testing.assert_array_equal(r1c.clock_ps, rgc.clock_ps)
    rows.append(("shl2_stress_64t", sc3, batch3, t1c, tsmc, tgc))

    for name, wsc, wbatch, single, sharded, gspmd in rows:
        print(json.dumps({
            "metric": f"multi-device step wall-clock ({name}, "
            f"{n_dev} dev shard_map)",
            "value": round(sharded * 1e3, 1),
            "unit": "ms",
            "vs_baseline": round(sharded / single, 4),
            "single_ms": round(single * 1e3, 1),
            "gspmd_ms": round(gspmd * 1e3, 1),
            "gspmd_vs_single": round(gspmd / single, 4),
            "devices": n_dev,
            **_static_comms(wsc, wbatch, n_dev),
        }))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
