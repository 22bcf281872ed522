"""Chip smoke: the simulator's main path, once, on the attached TPU.

    python chip_smoke.py               # one chip: device, oracle-16,
                                       # ref-default-64, coh-1024, serve
    python chip_smoke.py --four-chips  # four chips: shard-1024 only

One process, through the entry points a user calls (`Simulator.run()`,
`CampaignService`), at the reference's own default target (64 tiles,
iocoom, T1 caches, MSI directory, hop-counter NoC, lax_barrier at
1000 ns, SPLASH-2 FFT 64K points), at 1024 tiles with the full
directory, and through a served campaign with its persistent program
store.  Every phase prints one JSON line naming the device it ran on,
what ran, and wall seconds cold (compile included) and warm — smoke
timings, not benchmark metrics.  A failed check exits non-zero at once.
The last line, printed only when every phase passed, is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.

`--rehearse` is for the CPU: it skips the TPU check and shrinks every
size, prints the same phase lines (each naming the CPU) and never the
final line.  For `--four-chips --rehearse` give the CPU four virtual
devices: XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
# coh-1024's FFT points per tile: the cut that keeps a cold run of the
# script inside its 1200 s limit (the state is at real size; the compile
# alone is ~6 min).
COH_POINTS = 16


def _check(ok, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _device() -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, "device": _device(), **fields}),
          flush=True)


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def _timed(fn):
    """(result, wall seconds) — `fn` must block on its own result
    (`Simulator.run()` fetches its statistics to the host; `warmup()`
    ends in block_until_ready)."""
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def _diff(a, b) -> "list[str]":
    """Names of the SimResults statistics that differ between a and b."""
    import numpy as np

    bad = []
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if f.name in ("mem_counters", "detailed_stalls"):
            for k in (x or {}):
                if not np.array_equal(np.asarray(x[k]), np.asarray(y[k])):
                    bad.append(f"{f.name}.{k}")
        elif f.name in ("telemetry", "profile", "hist"):
            continue
        elif not np.array_equal(np.asarray(x), np.asarray(y)):
            bad.append(f.name)
    return bad


def _trace_instructions(batch) -> int:
    """The trace's own instruction count, independent of the engine:
    every static/branch/dynamic record is one instruction (SPAWN is
    not), a BBLOCK record carries its count in aux0."""
    from graphite_tpu.trace.schema import Op

    op = batch.op
    one = (op < 20) & (op != int(Op.SPAWN))
    return int(one.sum()) + int(batch.aux0[op == int(Op.BBLOCK)].sum())


def _check_run(res, batch, what: str) -> None:
    """The checks every simulated run must pass.  A mailbox overflow or
    a deadlock raises inside run(); reaching here means neither."""
    _check(res.func_errors == 0, f"{what}: func_errors={res.func_errors}")
    _check(bool((res.clock_ps > 0).all()),
           f"{what}: a tile's clock did not advance")
    want = (_trace_instructions(batch) + int(res.recv_instructions.sum())
            + int(res.sync_instructions.sum()))
    _check(res.total_instructions == want,
           f"{what}: total_instructions={res.total_instructions}, the "
           f"trace (+ charged recv/sync stalls) counts {want}")


def _ref_default_config(tiles: int, core: str = "iocoom"):
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.tools._template import config_text

    return SimConfig(ConfigFile.from_string(config_text(
        tiles, core=core, shared_mem=True, clock_scheme="lax_barrier")))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(rehearse: bool) -> None:
    dev = _device()
    if not rehearse:
        _check(dev["platform"] == "tpu",
               f"jax found {dev}; this script runs on a TPU only "
               f"(--rehearse is the CPU mode)")
    _emit("device", ran="jax.devices()")


def phase_oracle(tiles: int, points: int) -> None:
    """Engine vs the sequential golden interpreter, bit for bit.  The
    golden models the `simple` core only, so this is the reference
    default with that one substitution (MSI directory, hop-counter NoC
    and lax_barrier as in ref-default-64)."""
    import numpy as np

    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.golden import run_golden
    from graphite_tpu.trace.benchmarks import fft_trace

    sc = _ref_default_config(tiles, core="simple")
    batch = fft_trace(tiles, points_per_tile=points, use_memory=True)
    sim = Simulator(sc, batch)
    res, cold = _timed(sim.run)
    gold = run_golden(sc, batch)
    _check(np.array_equal(res.clock_ps, gold.clock_ps),
           "oracle-16: clocks differ from the golden interpreter")
    for k, g in gold.mem_counters.items():
        _check(np.array_equal(np.asarray(res.mem_counters[k]), g),
               f"oracle-16: memory counter {k} differs from the golden")
    _check_run(res, batch, "oracle-16")
    _emit("oracle-16",
          ran=f"{tiles}-tile simple+MSI lax_barrier FFT {points} pts/tile "
              f"vs golden.run_golden",
          cold_wall_s=cold, instructions=res.total_instructions,
          counters_compared=len(gold.mem_counters), bit_identical=True)


def phase_ref_default(tiles: int, points: int) -> None:
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.trace.benchmarks import fft_trace

    sc = _ref_default_config(tiles)
    batch = fft_trace(tiles, points_per_tile=points, use_memory=True)
    sim = Simulator(sc, batch)
    mp = sim.params.mem
    # the template sets no cache or directory size: the engine's own
    # defaults must be the reference's T1 caches and auto directory
    kb = {n: c.num_sets * c.num_ways * mp.line_size // 1024
          for n, c in (("l1i", mp.l1i), ("l1d", mp.l1d), ("l2", mp.l2))}
    _check(kb == {"l1i": 16, "l1d": 32, "l2": 512}
           and (mp.l1i.num_ways, mp.l1d.num_ways, mp.l2.num_ways)
           == (4, 4, 8) and mp.dir_type == "full_map"
           and mp.dir_ways == 16
           and mp.protocol == "pr_l1_pr_l2_dram_directory_msi"
           and sim.params.iocoom is not None
           and sim.quantum_ps == 1_000_000 and not sim.barrier_host,
           f"ref-default-64: the template does not carry the reference "
           f"defaults (caches {kb} KB, directory {mp.dir_type})")
    _, compile_s = _timed(sim.warmup)
    res1, warm1 = _timed(sim.run)
    # run() leaves the finished state in sim.state: the second run is a
    # fresh instance over the same trace on the first one's program
    sim2 = Simulator(sc, batch)
    sim2.adopt_runner(sim)
    res2, warm2 = _timed(sim2.run)
    _check_run(res1, batch, "ref-default-64")
    bad = _diff(res1, res2)
    _check(not bad, f"ref-default-64: two runs differ in {bad}")
    _emit("ref-default-64",
          ran=f"{tiles}-tile iocoom, T1 caches (L1I 16K/4w, L1D 32K/4w, "
              f"L2 512K/8w), MSI full_map directory {mp.dir_sets}x"
              f"{mp.dir_ways}, emesh_hop_counter, lax_barrier 1000 ns, "
              f"FFT {tiles * points} points",
          cold_wall_s=compile_s, cold_is="warmup(): compile + one run",
          warm_wall_s=[warm1, warm2],
          instructions=res1.total_instructions, n_quanta=res1.n_quanta,
          engine_iterations=int(sim.last_n_iterations),
          completion_time_ns=res1.completion_time_ps // 1000,
          peak_bytes_in_use=_peak_bytes())


def phase_coh(tiles: int, points: int, rehearse: bool) -> None:
    """Built as tools/coherence1024.run_one builds it (full auto-sized
    directory, hop-counter NoC, lax_barrier, FFT), in this process.
    No donation: warmup() needs the input state again, and the run
    establishes whether the state fits without it."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace.benchmarks import fft_trace

    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier",
        network="emesh_hop_counter")))
    batch = fft_trace(tiles, points_per_tile=points, use_memory=True)
    # the rehearsal is below the size where the selection rule picks the
    # host-driven barrier loop; force it so the same path is rehearsed
    sim = Simulator(sc, batch, barrier_host=True if rehearse else None)
    _check(sim.barrier_host, "coh-1024: barrier_host was not selected")
    _, compile_s = _timed(sim.warmup)
    res, run_s = _timed(sim.run)
    _check_run(res, batch, "coh-1024")
    mp = sim.params.mem
    _emit("coh-1024",
          ran=f"{tiles}-tile simple+MSI, full_map directory "
              f"{mp.dir_sets}x{mp.dir_ways}/tile, emesh_hop_counter, "
              f"lax_barrier, FFT {points} pts/tile",
          points_per_tile=points, barrier_host=sim.barrier_host,
          barrier_batch=sim.barrier_batch, donate=sim.donate,
          cold_wall_s=compile_s,
          cold_is="warmup(): compile + one single-quantum dispatch",
          run_wall_s=run_s, instructions=res.total_instructions,
          n_quanta=res.n_quanta,
          engine_iterations=int(sim.last_n_iterations),
          peak_bytes_in_use=_peak_bytes())


def phase_serve(tiles: int, n_accesses: int) -> None:
    import numpy as np

    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.serve import CampaignService, Job
    from graphite_tpu.trace import synthetic

    store = os.path.join(OUT_DIR, "store")
    shutil.rmtree(store, ignore_errors=True)
    os.makedirs(store)
    sc = _ref_default_config(tiles)
    traces = {s: synthetic.memory_stress_trace(
        tiles, n_accesses=n_accesses, working_set_bytes=1 << 13,
        write_fraction=0.4, shared_fraction=0.5, seed=s) for s in (1, 2)}

    def jobs():
        return [Job(f"d{d}-s{s}", sc, traces[s],
                    knobs={"dram_latency_ns": d}, seed=s)
                for d in (60, 100, 140, 180) for s in traces]

    def serve(svc):
        for j in jobs():
            svc.submit(j)
        out = {r.job_id: r for r in svc.drain()}
        for jid, r in out.items():
            _check(r.ok, f"serve: job {jid} failed: {r.error}")
        return out

    def counters(svc):
        return {**svc.counters, "store_fill_errors": int(
            svc.metrics["store_fill_errors_total"].value)}

    first = CampaignService(batch_size=4, store=store)
    got, cold = _timed(lambda: serve(first))
    _check(len(got) == 8, f"serve: {len(got)} of 8 envelopes")
    c1 = counters(first)
    n_classes = len({rep.class_name for rep in first.batch_log})
    _check(c1["compile_count"] == n_classes,
           f"serve: {c1['compile_count']} compiles for {n_classes} "
           f"program class(es)")
    _check(c1["store_fills"] == n_classes and c1["store_integrity"] == 0
           and c1["store_fill_errors"] == 0,
           f"serve: the first service did not fill the store cleanly: {c1}")

    # one of the jobs again as plain Simulator.run(), its knob baked
    # static (a non-default DRAM latency, so the traced knob is what is
    # compared).  One job only: each solo is a ~90 s cold compile of its
    # own (the solo program closes over its trace), and a second does
    # not fit a cold run of the script inside its time limit.
    job = jobs()[5]
    sim = Simulator(sc, job.trace)
    sim.params = dataclasses.replace(sim.params, mem=dataclasses.replace(
        sim.params.mem, **job.knobs))
    ref, solo_s = _timed(sim.run)
    bad = _diff(ref, got[job.job_id].results)
    _check(not bad, f"serve: job {job.job_id} differs from its plain "
                    f"Simulator.run() in {bad}")

    second = CampaignService(batch_size=4, store=store)
    (n_warm, again), warm = _timed(
        lambda: (second.warm_start(), serve(second)))
    c2 = counters(second)
    _check(c2["compile_count"] == 0 and c2["store_hits"] > 0
           and c2["store_integrity"] == 0 and c2["store_fill_errors"] == 0,
           f"serve: the second service did not serve from the store: {c2}")
    for jid, r in got.items():
        bad = _diff(r.results, again[jid].results)
        _check(not bad, f"serve: job {jid} differs between the two "
                        f"services in {bad}")
    _check(bool(np.all([r.results.func_errors == 0 for r in got.values()])),
           "serve: func_errors != 0")
    keys = ("compile_count", "cache_hits", "store_hits", "store_misses",
            "store_fills", "store_integrity", "store_fill_errors", "batches")
    _emit("serve",
          ran=f"CampaignService(batch_size=4, store=...) x2 over 8 jobs of "
              f"the {tiles}-tile reference-default geometry (4 DRAM "
              f"latencies x 2 trace seeds, memory_stress "
              f"{n_accesses} accesses/tile)",
          cold_wall_s=cold, cold_is="first service: compile + fill + serve",
          warm_wall_s=warm,
          warm_is="second service: warm_start() + serve from the store",
          solo_wall_s=solo_s, solo_job=job.job_id,
          solo_is="plain Simulator.run(), its own compile included",
          program_classes=n_classes, warm_started=n_warm,
          first={k: c1[k] for k in keys}, second={k: c2[k] for k in keys},
          peak_bytes_in_use=_peak_bytes())
    # the payloads are tens of MB; what chiprun_out/ may bring back is capped
    shutil.rmtree(store)


def phase_shard(tiles: int, n_accesses: int) -> None:
    """Tile-sharded (shard_map packed exchange) vs one device, bit for
    bit, on the cross-shard coherence attestation workload."""
    import jax
    import numpy as np

    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.parallel.mesh import (
        make_tile_mesh, shard_map_state_specs,
    )
    from graphite_tpu.tools._template import coherence_stress_workload

    _check(len(jax.devices()) >= 4,
           f"shard-1024 needs four devices, jax found {len(jax.devices())}")
    sc, batch = coherence_stress_workload(tiles, n_accesses=n_accesses)
    one = Simulator(sc, batch)
    ref, one_cold = _timed(one.run)
    _check_run(ref, batch, "shard-1024 (one device)")
    one.state = None            # free the single-device copy

    mesh = make_tile_mesh(4)
    sim = Simulator(sc, batch, mesh=mesh)
    _check(sim.spmd == "shard_map", f"spmd program is {sim.spmd}")

    def check_placement(state, when: str) -> None:
        """The block-local [T, ...] leaves really live on four devices,
        a quarter of the tiles each."""
        specs = shard_map_state_specs(state)
        n_local = 0
        for leaf, spec in zip(jax.tree.leaves(state), jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec))):
            _check(len(leaf.sharding.device_set) == 4,
                   f"shard-1024: a state leaf {leaf.shape} is on "
                   f"{len(leaf.sharding.device_set)} device(s) {when}")
            if len(spec) and spec[0] is not None:
                n_local += 1
                shard_rows = {s.data.shape[0]
                              for s in leaf.addressable_shards}
                _check(not leaf.sharding.is_fully_replicated
                       and shard_rows == {tiles // 4},
                       f"shard-1024: per-tile leaf {leaf.shape} is not "
                       f"split four ways {when} (shard rows {shard_rows})")
        _check(n_local > 0, "shard-1024: no block-local leaf found")
        return n_local

    n_local = check_placement(sim.state, "after placement")
    _, cold = _timed(sim.warmup)
    got, warm = _timed(sim.run)
    check_placement(sim.state, "after the run")
    _check(np.array_equal(ref.clock_ps, got.clock_ps),
           "shard-1024: clocks diverge under sharding")
    for k, v in ref.mem_counters.items():
        _check(np.array_equal(np.asarray(v), np.asarray(got.mem_counters[k])),
               f"shard-1024: memory counter {k} diverges under sharding")
    _check(got.func_errors == 0, "shard-1024: functional memory corrupted")
    n_miss = int(np.asarray(ref.mem_counters["l2_misses"]).sum())
    _check(n_miss > 0, "shard-1024: no coherence traffic")
    _emit("shard-1024",
          ran=f"coherence_stress_workload({tiles}, n_accesses={n_accesses}) "
              f"on one device and tile-sharded over make_tile_mesh(4) "
              f"(spmd={sim.spmd})",
          one_device_cold_wall_s=one_cold,
          sharded_cold_wall_s=cold, sharded_warm_wall_s=warm,
          l2_misses=n_miss, block_local_leaves=n_local,
          counters_compared=len(ref.mem_counters), bit_identical=True,
          peak_bytes_in_use=_peak_bytes())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only shard-1024 (needs four devices)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; never prints the "
                    "final ok line")
    args = ap.parse_args(argv)

    import graphite_tpu  # noqa: F401  (x64, compile cache placement)

    t0 = time.perf_counter()
    small = args.rehearse
    phase_device(small)
    if args.four_chips:
        phase_shard(64 if small else 1024, n_accesses=24)
    else:
        phase_oracle(16, points=16)
        phase_ref_default(16 if small else 64, points=16 if small else 1024)
        phase_coh(16 if small else 1024,
                  points=COH_POINTS, rehearse=small)
        phase_serve(16 if small else 64, n_accesses=8 if small else 24)
    total = round(time.perf_counter() - t0, 1)
    if small:
        print(json.dumps({"rehearsal": True, "device": _device(),
                          "total_wall_s": total}), flush=True)
        return 0
    print(json.dumps({"total_wall_s": total}), flush=True)
    print(json.dumps({"ok": True, "device": _device()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
