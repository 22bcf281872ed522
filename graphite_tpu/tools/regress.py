"""Configuration-sweep regression driver — the analog of the reference's
`tools/regress/run_tests.py` + `aggregate_results.py` (compile & schedule
SPLASH-2 x machines x modes with config overrides, aggregate results).

Sweeps the model matrix on small traces: caching protocol x directory
scheme x NoC model x core model, replaying a benchmark trace through each,
and prints one result row per config (completion time, instructions,
func_errors).  Exit code is nonzero if any config fails.

Usage:
  python -m graphite_tpu.tools.regress [--tiles 8] [--quick]
  python -m graphite_tpu.tools.regress --smoke   # tier-1 companion, CPU

`--smoke` is the fast gating/dispatch attestation (runs in well under a
minute on CPU with a warm XLA cache): the 16-tile per-phase-gated vs
ungated engine pair must be bit-identical, the batched host-barrier
dispatch (barrier_batch > 1) must reproduce the per-quantum dispatch
exactly, the B=4 sweep must match sequential runs, telemetry recording
must leave SimResults bit-identical (solo, gated + ungated) and the
B=4 campaign's demuxed timelines must equal sequential telemetry runs
(the per-tile profile ring repeats all three checks in rung 10, plus
the cross-ring per-tile-sums-equal-scalar-series invariant),
the program auditor's jaxpr invariant lints (graphite_tpu/analysis)
must pass on the lowered default programs, every default program's
static cost report must sit within the checked-in BUDGETS.json
ceilings (the round-10 budget gate — kernel proxy, bytes/iter, peak
residency; tools/audit.py --budget-update refreshes after an
intentional change), every default program's canonical fingerprint
must match its registered identity in PROGRAMS.lock (the round-11
identity gate — tools/audit.py --lock-update re-registers), and the
round-18 2D batch x tile campaign must be bit-identical — results,
timelines, per-tile profile rings — to the 1D batch layout and to
sequential solo runs on forced host devices, with the admission
controller bin-packing a too-big-for-one-device sim across devices
(rung 12; standalone via --smoke-mesh2d), and the round-19 runtime
DVFS manager must be invisible at the config's own frequencies
(carried-frequency engines and the B=4 campaign bit-identical to the
constant-folded ones), match the hand-stepped golden interpreter on
in-trace DVFS_SET retunes, and govern deterministically (rung 13),
and the round-20 bounded model checker must exhaust the 2-tile/1-line
MSI and MOSI state spaces with zero invariant violations, replay every
explored transition bit-equal through the vectorized engines, and
catch the seeded 'mosi-owner-skips-wb' mutant with a named data-value
counterexample (rung 14), and the round-21 device-resident latency
histograms must be pure observability (hist on/off SimResults
bit-identical, gated + ungated), conserve events exactly (every
histogram total bit-equals its paired cumulative counter), demux the
B=4 campaign identically to sequential recordings, and export a valid
monotone-stamped Chrome trace via tools/report.py --perfetto
(rung 15), and the round-22 collective/ICI analyzer must pass its
comms audit over the registered mesh programs under the forced-4-
device re-exec (every collective a whitelisted px packed exchange,
every declared-replicated output provably uniform) while the known-bad
legacy unpacked-exchange fixture trips the gspmd-insertion lint with
exit 1 (rung 16).
"""

from __future__ import annotations

import argparse
import itertools
import sys
import time


from graphite_tpu.tools._template import config_text

PROTOCOLS = (
    "pr_l1_pr_l2_dram_directory_msi",
    "pr_l1_pr_l2_dram_directory_mosi",
    "pr_l1_sh_l2_msi",
    "pr_l1_sh_l2_mesi",
)
SCHEMES = ("full_map", "limited_no_broadcast", "ackwise", "limitless")
NETWORKS = ("magic", "emesh_hop_counter", "emesh_hop_by_hop")
CORES = ("simple", "iocoom")


def run_one(tiles, protocol, scheme, network, core, workload):
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.trace.benchmarks import BENCHMARKS

    shared = workload == "canneal"
    cfg = ConfigFile.from_string(config_text(
        tiles, protocol=protocol, scheme=scheme, network=network,
        core=core, shared_mem=shared))
    if workload == "canneal":
        batch = BENCHMARKS[workload](tiles, footprint_lines=256,
                                     swaps_per_tile=6)
    elif workload == "fft":
        batch = BENCHMARKS[workload](tiles, points_per_tile=32)
    else:
        batch = BENCHMARKS[workload](tiles)
    sim = Simulator(SimConfig(cfg), batch)
    res = sim.run()
    return res


def _compare(name, ra, rb):
    """Bit-equality of two SimResults (clocks + memory counters)."""
    import numpy as np

    ok = (np.asarray(ra.clock_ps) == np.asarray(rb.clock_ps)).all()
    if ra.mem_counters is not None:
        for k in ra.mem_counters:
            ok = ok and (np.asarray(ra.mem_counters[k])
                         == np.asarray(rb.mem_counters[k])).all()
    ok = ok and ra.n_quanta == rb.n_quanta
    print(f"{name:44} {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def _timeline_matches(tl, solo) -> bool:
    """A campaign sim's device timeline against its own sequential
    run's: every series but the skip_* ones and the iterations.  A
    batch's gates and its block's exit are keyed on the OR of their
    predicate over its sims (`ParallelCtx.any_sim`), so those count
    what the BATCH's program ran and no solo run is their oracle."""
    import numpy as np

    from graphite_tpu.obs.telemetry import counts_the_program

    keep = [i for i, n in enumerate(tl.series)
            if not counts_the_program(n)]
    return (tl.n_total == solo.n_total
            and np.array_equal(tl.data[:, keep], solo.data[:, keep]))


def smoke(tiles: int = 16) -> int:
    """The tier-1 companion fast path: gated/ungated bit-exactness and
    batched-barrier equivalence at 16 tiles on CPU."""
    import time as _t

    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.trace import synthetic

    t0 = _t.perf_counter()
    failures = 0

    # 1) per-phase gating is mechanism, not policy: gated vs ungated
    #    engines must be bit-identical on coherence-heavy traffic
    #    (mem_gate_bytes=0 forces the whole-engine gate OFF so the
    #    per-phase conds are the only gating in the gated program)
    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax")))
    batch = synthetic.memory_stress_trace(
        tiles, n_accesses=40, working_set_bytes=1 << 13,
        write_fraction=0.4, shared_fraction=0.5, seed=7)
    r_gate = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0).run()
    r_flat = Simulator(sc, batch, phase_gate=False, mem_gate_bytes=0).run()
    failures += _compare("phase-gated vs ungated (MSI, 16t)", r_gate,
                         r_flat)

    # 2) batched host-barrier dispatch == per-quantum dispatch
    sc_b = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier")))
    r_b1 = Simulator(sc_b, batch, barrier_host=True, barrier_batch=1).run()
    r_b8 = Simulator(sc_b, batch, barrier_host=True, barrier_batch=8).run()
    failures += _compare("barrier_batch=8 vs per-quantum dispatch", r_b1,
                         r_b8)

    # 3) batched campaign == sequential runs (round 7, sweep/): B=4 sims
    #    vmapped through ONE compiled program with per-sim traced knobs
    #    must be bit-identical to 4 independent Simulator runs
    from graphite_tpu.sweep import SweepRunner

    seeds = (1, 2, 3, 4)
    sweep_traces = [
        synthetic.memory_stress_trace(
            tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=s)
        for s in seeds
    ]
    sweep = SweepRunner(sc, sweep_traces)
    out = sweep.run()
    for b, s in enumerate(seeds):
        r_seq = Simulator(sc, sweep_traces[b],
                          mailbox_depth=sweep.mailbox_depth).run()
        failures += _compare(f"sweep B=4 sim {b} (seed {s}) vs sequential",
                             out.results[b], r_seq)
    # 4) telemetry is pure observability (round 9): recording a dense
    #    device timeline must leave every SimResults field bit-identical
    #    (gated + ungated), and the B=4 campaign's demuxed [B, S, n]
    #    timelines must equal 4 sequential telemetry runs' rows exactly
    import numpy as np

    from graphite_tpu.obs import TelemetrySpec

    tel = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=64)
    for gate, label in ((True, "gated"), (False, "ungated")):
        r_tel = Simulator(sc_b, batch, phase_gate=gate, mem_gate_bytes=0,
                          telemetry=tel).run()
        r_off = Simulator(sc_b, batch, phase_gate=gate,
                          mem_gate_bytes=0).run()
        failures += _compare(f"telemetry on vs off ({label} MSI, 16t)",
                             r_tel, r_off)
    sweep_tel = SweepRunner(sc_b, sweep_traces, telemetry=tel)
    out_tel = sweep_tel.run()
    for b, s in enumerate(seeds):
        solo = Simulator(sc_b, sweep_traces[b],
                         mailbox_depth=sweep_tel.mailbox_depth,
                         phase_gate=False, mem_gate_bytes=0,
                         telemetry=tel).run().telemetry
        ok = _timeline_matches(out_tel.timelines[b], solo)
        print(f"{f'sweep B=4 sim {b} timeline vs sequential':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1

    # 5) program auditor (round 8): the jaxpr invariant lints must pass
    #    on the lowered default programs — both memory engines (gated,
    #    ungated, shl2), the B=4 sweep campaign, and the telemetry
    #    programs.  Static analysis only: make_jaxpr, no compile.
    from graphite_tpu.analysis import audit, default_programs

    specs = default_programs(8)
    report = audit(specs)
    for row in report.summary_rows():
        name = f"audit {row['program']}"
        ok = row["ok"]
        print(f"{name:44} {'PASS' if ok else 'FAIL'}"
              + ("" if ok else f"  ({row['errors']} error(s))"))
        failures += 0 if ok else 1
    for f in report.findings:
        print(f"    {f}")

    # 6) budget gate (round 10): every default program's static cost
    #    report (analysis/cost.py) must sit within the checked-in
    #    BUDGETS.json ceilings — kernel proxy, bytes/iter, peak
    #    residency.  The same lowered specs as rung 5; no compile.
    from graphite_tpu.analysis import cost as _cost
    from graphite_tpu.analysis import registry as _registry

    try:
        budgets = _cost.load_budgets()
    except FileNotFoundError:
        print(f"{'budget BUDGETS.json':44} FAIL  (missing — run "
              f"tools/audit.py --budget-update)")
        failures += 1
    else:
        # round 11: budgets resolve THROUGH the program registry, so a
        # ceiling measured at a different fingerprint errors loudly
        try:
            reg = _registry.load_lock()
        except FileNotFoundError:
            reg = None
        for spec in specs:
            rep = _cost.cost_report(spec)
            trips = _cost.check_budget(
                rep, budgets,
                record=(reg or {}).get(rep.program))
            name = f"budget {rep.program}"
            print(f"{name:44} {'PASS' if not trips else 'FAIL'}")
            for f in trips:
                print(f"    {f}")
            failures += 1 if trips else 0

    # 7) identity lock (round 11): every default program's canonical
    #    fingerprint (analysis/identity.py) must match its registered
    #    entry in PROGRAMS.lock — geometry and knob signature included.
    #    Same lowered specs as rungs 5-6; tools/audit.py --lock-update
    #    re-registers after an intentional change.
    try:
        lock = _registry.load_lock()
    except FileNotFoundError:
        print(f"{'lock PROGRAMS.lock':44} FAIL  (missing — run "
              f"tools/audit.py --lock-update)")
        failures += 1
    else:
        trips = _registry.check_lock(specs, lock, expect_complete=True)
        by_prog = {}
        for f in trips:
            by_prog.setdefault(f.program, []).append(f)
        for spec in specs:
            name = f"lock {spec.name}"
            fs = by_prog.pop(spec.name, [])
            print(f"{name:44} {'PASS' if not fs else 'FAIL'}")
            for f in fs:
                print(f"    {f}")
            failures += 1 if fs else 0
        for prog, fs in sorted(by_prog.items()):
            print(f"{f'lock {prog}':44} FAIL")
            for f in fs:
                print(f"    {f}")
            failures += 1

    # 8) campaign service (round 13, serve/): a MIXED-GEOMETRY job set
    #    through the admission-controlled service — batched, padded,
    #    cache-served with hit verification on (every hit re-proves the
    #    program fingerprint) — must be bit-identical (results + demuxed
    #    telemetry) to sequential Simulator runs, and each program class
    #    must pay exactly ONE compile.
    from graphite_tpu.serve import CampaignService, Job

    tel_sv = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=32)
    sc4 = SimConfig(ConfigFile.from_string(config_text(
        4, shared_mem=True, clock_scheme="lax")))
    sc8 = SimConfig(ConfigFile.from_string(config_text(
        8, shared_mem=True, clock_scheme="lax")))

    def _mkt(tiles, seed):
        return synthetic.memory_stress_trace(
            tiles, n_accesses=12, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.5, seed=seed)

    svc = CampaignService(batch_size=2, max_quanta=200_000,
                          verify_hits=True)
    serve_jobs = []
    for i, s in enumerate((1, 2, 3)):
        serve_jobs.append(Job(f"t4-{i}", sc4, _mkt(4, s), seed=s))
        serve_jobs.append(Job(f"t8-{i}", sc8, _mkt(8, s), seed=s,
                              telemetry=tel_sv))
    for job in serve_jobs:
        svc.submit(job)
    served = {r.job_id: r for r in svc.drain()}
    for job in serve_jobs:
        sc_j = sc4 if job.n_tiles == 4 else sc8
        seq = Simulator(sc_j, job.trace, telemetry=job.telemetry).run()
        got = served[job.job_id]
        failures += _compare(f"serve {job.job_id} vs sequential",
                             got.results, seq)
        if job.telemetry is not None:
            ok = _timeline_matches(got.telemetry, seq.telemetry)
            print(f"{f'serve {job.job_id} timeline vs sequential':44} "
                  f"{'PASS' if ok else 'FAIL'}")
            failures += 0 if ok else 1
    c = svc.counters
    ok = (c["compile_count"] == 2 and c["cache_hits"] == 2
          and c["failed"] == 0
          and len({b.n_tiles for b in svc.batch_log}) == 2)
    print(f"{'serve 2 classes, 1 compile each':44} "
          f"{'PASS' if ok else 'FAIL'}"
          + ("" if ok else f"  (compiles={c['compile_count']} "
             f"hits={c['cache_hits']} failed={c['failed']})"))
    failures += 0 if ok else 1

    # 9) observability (round 14): the SAME mixed-geometry job set with
    #    span tracing + host metrics ON and the energy_pj telemetry
    #    series priced onto the t8 jobs — SimResults bit-equal to the
    #    rung-8 untraced run (tracing/metrics are host-side, energy is
    #    pure observability on device), every submitted job's span
    #    chain terminal-complete, the energy column equal to the
    #    hand-priced sum of the run's own counters, and both exporters'
    #    output parsing back.
    import io as _io

    from graphite_tpu.obs import EnergyPrices, parse_exposition
    from graphite_tpu.obs.trace import job_breakdown, load_jsonl

    prices = EnergyPrices(
        instruction_pj=3, l1d_access_pj=2, l2_access_pj=9,
        l2_miss_pj=120, invalidation_pj=15, eviction_pj=20,
        dram_access_pj=500, packet_pj=7)
    tel_e = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=32,
                          energy_prices=prices)
    svc9 = CampaignService(batch_size=2, max_quanta=200_000,
                           tracing=True)
    jobs9 = []
    for i, s in enumerate((1, 2, 3)):
        jobs9.append(Job(f"t4-{i}", sc4, _mkt(4, s), seed=s))
        jobs9.append(Job(f"t8-{i}", sc8, _mkt(8, s), seed=s,
                         telemetry=tel_e))
    for job in jobs9:
        svc9.submit(job)
    served9 = {r.job_id: r for r in svc9.drain()}
    for job in jobs9:
        failures += _compare(f"traced serve {job.job_id} vs untraced",
                             served9[job.job_id].results,
                             served[job.job_id].results)
    for i in range(3):
        r9 = served9[f"t8-{i}"]
        res = r9.results
        mc = res.mem_counters
        exp = (3 * int(res.total_instructions)
               + 7 * int(np.sum(res.packets_sent))
               + 2 * int(sum(mc[k].sum() for k in (
                   "l1d_read_hits", "l1d_read_misses",
                   "l1d_write_hits", "l1d_write_misses")))
               + 9 * int(mc["l2_hits"].sum() + mc["l2_misses"].sum())
               + 120 * int(mc["l2_misses"].sum())
               + 15 * int(mc["invalidations"].sum())
               + 20 * int(mc["evictions"].sum())
               + 500 * int(mc["dram_reads"].sum()
                           + mc["dram_writes"].sum()))
        got = int(r9.telemetry.col("energy_pj").sum())
        ok = got == exp
        print(f"{f'serve t8-{i} energy_pj vs hand-priced sum':44} "
              f"{'PASS' if ok else 'FAIL'}"
              + ("" if ok else f"  (got {got}, expected {exp})"))
        failures += 0 if ok else 1
    missing = svc9.tracer.missing_terminal([j.job_id for j in jobs9])
    print(f"{'serve span set terminal-complete':44} "
          f"{'PASS' if not missing else 'FAIL'}"
          + ("" if not missing else f"  (missing: {missing})"))
    failures += 1 if missing else 0
    buf = _io.StringIO()
    n_spans = svc9.export_spans(buf)
    buf.seek(0)
    rows = load_jsonl(buf)
    bd = {r["job"] for r in job_breakdown(rows)}
    ok = (len(rows) == n_spans and n_spans > 0
          and bd == {j.job_id for j in jobs9})
    print(f"{'serve span JSON-lines export round-trip':44} "
          f"{'PASS' if ok else 'FAIL'}")
    failures += 0 if ok else 1
    snap = parse_exposition(svc9.metrics.exposition())
    ok = (snap["queue_dwell_seconds"]["type"] == "histogram"
          and snap["queue_dwell_seconds"]["count"] == len(jobs9)
          and snap["jobs_completed_total"]["value"] == len(jobs9)
          and snap["compiles_total"]["value"] == 2)
    print(f"{'serve metrics exposition parses':44} "
          f"{'PASS' if ok else 'FAIL'}"
          + ("" if ok else f"  ({snap.get('queue_dwell_seconds')})"))
    failures += 0 if ok else 1

    # 10) spatial profiler (round 16, obs/profile.py): recording the
    #     per-tile [S, T, m] ring must leave SimResults bit-identical
    #     (gated + ungated), the B=4 campaign must demux per-sim
    #     per-tile rows equal to sequential profile runs, and — the
    #     free cross-ring invariant — a run carrying BOTH rings on one
    #     sampling cursor must have every shared delta series sum over
    #     T to exactly the round-9 scalar column, with
    #     max(clock_skew) + clock_min == clock_max sample for sample.
    from graphite_tpu.obs import ProfileSpec

    prof = ProfileSpec(sample_interval_ps=1_000_000, n_samples=64)
    for gate, label in ((True, "gated"), (False, "ungated")):
        r_prof = Simulator(sc_b, batch, phase_gate=gate,
                           mem_gate_bytes=0, profile=prof).run()
        r_off = Simulator(sc_b, batch, phase_gate=gate,
                          mem_gate_bytes=0).run()
        failures += _compare(f"profile on vs off ({label} MSI, 16t)",
                             r_prof, r_off)
    sweep_prof = SweepRunner(sc_b, sweep_traces, profile=prof)
    out_prof = sweep_prof.run()
    for b, s in enumerate(seeds):
        solo = Simulator(sc_b, sweep_traces[b],
                         mailbox_depth=sweep_prof.mailbox_depth,
                         phase_gate=False, mem_gate_bytes=0,
                         profile=prof).run().profile
        pf = out_prof.profiles[b]
        ok = (pf.n_total == solo.n_total
              and np.array_equal(pf.data, solo.data)
              and np.array_equal(pf.times_ps, solo.times_ps))
        print(f"{f'sweep B=4 sim {b} profile vs sequential':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1
    # both rings on one cursor, energy priced on BOTH (one shared
    # ladder — obs/telemetry.tile_energy_pj — so energy_pj is part of
    # the cross-ring sum invariant, not just the unit test)
    tel_x = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=64,
                          energy_prices=prices)
    prof_x = ProfileSpec(sample_interval_ps=1_000_000, n_samples=64,
                         energy_prices=prices)
    r_both = Simulator(sc_b, batch, phase_gate=False, mem_gate_bytes=0,
                       telemetry=tel_x, profile=prof_x).run()
    pf, tl = r_both.profile, r_both.telemetry
    ok = pf.n_total == tl.n_total \
        and np.array_equal(pf.times_ps, tl.col("time_ps"))
    for s in ("instructions", "packets_sent", "sync_stall_ps",
              "l2_misses", "invalidations", "evictions", "energy_pj"):
        ok = ok and np.array_equal(pf.col(s).sum(axis=1), tl.col(s))
    ok = ok and np.array_equal(
        pf.col("clock_skew_ps").max(axis=1) + tl.col("clock_min_ps"),
        tl.col("clock_max_ps"))
    print(f"{'cross-ring: per-tile sums == scalar series':44} "
          f"{'PASS' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    # 11) persistent AOT program store (round 17, store/): the rung-8
    #     mixed-geometry job set served through a store-backed service
    #     must be bit-identical to the in-memory serve, a SECOND
    #     service over the same store must warm-start with ZERO
    #     compiles (fleet-once compilation), and `tools/store.py
    #     verify` must exit 0 on the populated store and 1 after
    #     deliberate corruption.
    import shutil as _sh
    import tempfile as _tf

    from graphite_tpu.store import ProgramStore
    from graphite_tpu.tools.store import main as store_main

    store_dir = _tf.mkdtemp(prefix="graphite-regress-store-")
    try:
        def _mkjobs():
            out = []
            for i, s in enumerate((1, 2, 3)):
                out.append(Job(f"t4-{i}", sc4, _mkt(4, s), seed=s))
                out.append(Job(f"t8-{i}", sc8, _mkt(8, s), seed=s,
                               telemetry=tel_sv))
            return out

        svc_st = CampaignService(batch_size=2, max_quanta=200_000,
                                 store=store_dir)
        for job in _mkjobs():
            svc_st.submit(job)
        served_st = {r.job_id: r for r in svc_st.drain()}
        for jid, ref in served.items():
            got = served_st[jid]
            failures += _compare(f"store serve {jid} vs in-memory",
                                 got.results, ref.results)
            if ref.telemetry is not None:
                ok = (got.telemetry.n_total == ref.telemetry.n_total
                      and np.array_equal(got.telemetry.data,
                                         ref.telemetry.data))
                print(f"{f'store serve {jid} timeline':44} "
                      f"{'PASS' if ok else 'FAIL'}")
                failures += 0 if ok else 1
        c_st = svc_st.counters
        ok = (c_st["compile_count"] == 2 and c_st["store_fills"] == 2
              and c_st["store_hits"] == 0
              and c_st["store_integrity"] == 0)
        print(f"{'store cold start: 2 compiles, 2 fills':44} "
              f"{'PASS' if ok else 'FAIL'}"
              + ("" if ok else f"  (compiles={c_st['compile_count']} "
                 f"fills={c_st['store_fills']} "
                 f"hits={c_st['store_hits']} "
                 f"integ={c_st['store_integrity']})"))
        failures += 0 if ok else 1

        svc_w = CampaignService(batch_size=2, max_quanta=200_000,
                                store=store_dir)
        n_warm = svc_w.warm_start()
        for job in _mkjobs():
            svc_w.submit(job)
        served_w = {r.job_id: r for r in svc_w.drain()}
        for jid, ref in served.items():
            failures += _compare(f"warm-start serve {jid} vs in-memory",
                                 served_w[jid].results, ref.results)
        c_w = svc_w.counters
        ok = (n_warm == 2 and c_w["compile_count"] == 0
              and c_w["store_hits"] == 2 and c_w["store_misses"] == 0
              and c_w["store_integrity"] == 0)
        print(f"{'store warm start: 0 compiles, 2 hits':44} "
              f"{'PASS' if ok else 'FAIL'}"
              + ("" if ok else f"  (warm={n_warm} "
                 f"compiles={c_w['compile_count']} "
                 f"hits={c_w['store_hits']} "
                 f"integ={c_w['store_integrity']})"))
        failures += 0 if ok else 1

        rc_clean = store_main(["--store", store_dir, "verify"])
        print(f"{'tools/store.py verify (sound store) == 0':44} "
              f"{'PASS' if rc_clean == 0 else 'FAIL'}")
        failures += 0 if rc_clean == 0 else 1
        import os as _os
        row = ProgramStore(store_dir).entries()[0]
        pbin = _os.path.join(store_dir, "entries", row["entry_id"],
                             "program.bin")
        with open(pbin, "rb") as fh:
            pb = fh.read()
        with open(pbin, "wb") as fh:
            fh.write(pb[:64] + bytes([pb[64] ^ 0xFF]) + pb[65:])
        rc_bad = store_main(["--store", store_dir, "verify"])
        print(f"{'tools/store.py verify (corrupted) == 1':44} "
              f"{'PASS' if rc_bad == 1 else 'FAIL'}")
        failures += 0 if rc_bad == 1 else 1
    finally:
        _sh.rmtree(store_dir, ignore_errors=True)

    # 12) 2D batch x tile campaigns (round 18): the Mesh(('batch',
    #     'tile')) program on forced host devices must be bit-identical
    #     — results, demuxed timelines AND per-tile profile rings — to
    #     the 1D batch-axis layout and to sequential solo runs on the
    #     same job set, and the admission controller must bin-pack a
    #     sim too big for one device's budget ACROSS devices (admitted
    #     as 2D, per-device block <= budget) where a 1-device service
    #     rejects it.  Needs >= 4 devices: run in-process when the
    #     platform has them, else re-exec this rung under
    #     XLA_FLAGS=--xla_force_host_platform_device_count=4.  The child
    #     is forced to JAX_PLATFORMS=cpu: it is a bit-identity check that
    #     needs no chip, so it is safe to start from this process even
    #     when the parent holds one.
    import jax as _jax

    if len(_jax.devices()) >= 4:
        failures += smoke_mesh2d(tiles)
    else:
        import os as _os
        import subprocess as _sp

        env = dict(_os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
        rc = _sp.call([sys.executable, "-m",
                       "graphite_tpu.tools.regress", "--smoke-mesh2d",
                       "--tiles", str(tiles)], env=env)
        print(f"{'mesh2d rung (forced 4-device subprocess)':44} "
              f"{'PASS' if rc == 0 else 'FAIL'}")
        failures += 0 if rc == 0 else 1

    # 13) runtime DVFS manager (round 19, dvfs/): (a) attaching a
    #     DvfsSpec at the config's own domain frequencies must be
    #     bit-identical to the constant-folded engines — gated +
    #     ungated MSI and the B=4 campaign (carried frequency is
    #     mechanism, not policy); (b) an in-trace DVFS_SET retune must
    #     match the hand-stepped golden interpreter exactly — clocks,
    #     instruction counts, rejected-set counters — across an
    #     up-retune, a down-retune, a rejected request and per-tile
    #     divergence; (c) the reactive governor is deterministic: two
    #     fresh engines agree bit-for-bit on results AND on the final
    #     per-domain V/f state.
    from graphite_tpu.dvfs import DvfsSpec, GovernorSpec

    dv0 = DvfsSpec()
    for gate, label in ((True, "gated"), (False, "ungated")):
        r_dv = Simulator(sc, batch, phase_gate=gate, mem_gate_bytes=0,
                         dvfs=dv0).run()
        r_ref = Simulator(sc, batch, phase_gate=gate,
                          mem_gate_bytes=0).run()
        failures += _compare(f"dvfs at config freq vs folded ({label})",
                             r_dv, r_ref)
    out_dv = SweepRunner(sc, sweep_traces, dvfs=dv0).run()
    for b, s in enumerate(seeds):
        failures += _compare(f"dvfs-off sweep B=4 sim {b} vs plain",
                             out_dv.results[b], out.results[b])

    from graphite_tpu.golden.interpreter import run_golden
    from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder

    sc_dv = SimConfig(ConfigFile.from_string("""
[general]
total_cores = 2
mode = lite
max_frequency = 2.0
technology_node = 22
[dvfs]
synchronization_delay = 2
domains = "<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE>, \
<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>"
[network]
user = magic
memory = magic
[core/static_instruction_costs]
ialu = 1
[clock_skew_management]
scheme = lax
"""))

    def _dv_builders():
        b0 = TraceBuilder()
        for _ in range(4):
            b0.instr(Op.IALU)
        b0.dvfs_set(0, 2000)            # AUTO up-retune
        for _ in range(4):
            b0.instr(Op.IALU)
        b1 = TraceBuilder()
        b1.dvfs_set(0, 500)             # AUTO down-retune
        b1.dvfs_set(0, 5000)            # above table max: rejected
        for _ in range(3):
            b1.instr(Op.IALU)
        return [b0, b1]

    batch_dv = TraceBatch.from_builders(_dv_builders())
    sim_dv = Simulator(sc_dv, batch_dv)
    r_eng = sim_dv.run()
    g = run_golden(sc_dv, batch_dv)
    ok = (np.array_equal(np.asarray(r_eng.clock_ps), g.clock_ps)
          and np.array_equal(np.asarray(r_eng.instruction_count),
                             g.instruction_count)
          and np.array_equal(np.asarray(sim_dv.state.dvfs.errors),
                             g.dvfs_errors))
    print(f"{'in-trace DVFS_SET vs golden oracle':44} "
          f"{'PASS' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    gv = DvfsSpec(governor=GovernorSpec(interval_ps=2000, domains=(0,)))
    gov_runs = []
    for _ in range(2):
        sim_g = Simulator(sc_dv, TraceBatch.from_builders(_dv_builders()),
                          dvfs=gv)
        r_g = sim_g.run()
        gov_runs.append((r_g, np.asarray(sim_g.state.dvfs_rt.domain_mhz),
                         np.asarray(sim_g.state.dvfs_rt.domain_mv)))
    failures += _compare("governor determinism (results)",
                         gov_runs[0][0], gov_runs[1][0])
    ok = (np.array_equal(gov_runs[0][1], gov_runs[1][1])
          and np.array_equal(gov_runs[0][2], gov_runs[1][2]))
    print(f"{'governor determinism (final V/f state)':44} "
          f"{'PASS' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    # 14) bounded model checking (round 20, analysis/protocol.py): the
    #     2-tile/1-line MSI and MOSI explorations must exhaust with
    #     ZERO invariant violations, every explored transition must
    #     replay bit-equal through the vectorized engine
    #     (differential mode — the checker attests the SHIPPED
    #     kernels), and the seeded 'mosi-owner-skips-wb' mutant must
    #     be caught with a named data-value counterexample (the
    #     checker's own self-test: a mutant that explores clean means
    #     the rung lost its teeth).
    from graphite_tpu.analysis import protocol as _P

    for proto in ("msi", "mosi"):
        res = _P.explore(proto, 2, 1)
        ok = res.ok and res.states_explored > 0
        print(f"{f'model check {proto} 2t/1l exhaustive':44} "
              f"{'PASS' if ok else 'FAIL'}"
              + ("" if ok else
                 f"  ({[v.invariant for v in res.violations]})"))
        failures += 0 if ok else 1
        if ok:
            d = _P.differential(res)
            ok = d.ok and d.n_ok == res.transitions
            print(f"{f'differential replay {proto} ({d.n_ok} trans)':44} "
                  f"{'PASS' if ok else 'FAIL'}")
            failures += 0 if ok else 1

    mres = _P.explore("mosi", 2, 1, mutant="mosi-owner-skips-wb")
    ok = (not mres.ok
          and any(v.invariant == "data-value" for v in mres.violations))
    print(f"{'mutant self-test names data-value':44} "
          f"{'PASS' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    # 15) latency histograms (round 21, obs/hist.py): a dense device-
    #     resident histogram recording must leave every SimResults
    #     field bit-identical (gated + ungated — the hist=None
    #     off-identity's runtime twin), every histogram total must
    #     bit-equal its paired cumulative counter (the conservation
    #     invariant, on every config this rung runs), the B=4
    #     campaign's demuxed hists must equal sequential solo
    #     recordings bucket-for-bucket, and the unified --perfetto
    #     export must load back as valid JSON with monotone per-track
    #     stamps.
    import json as _json
    import os as _os
    import tempfile as _tf2

    from graphite_tpu.obs import HistSpec, conservation_totals
    from graphite_tpu.tools import report as _report

    hspec = HistSpec()
    hist_ref = None
    for gate, label in ((True, "gated"), (False, "ungated")):
        sim_h = Simulator(sc_b, batch, phase_gate=gate, mem_gate_bytes=0,
                          hist=hspec)
        r_h = sim_h.run()
        r_off = Simulator(sc_b, batch, phase_gate=gate,
                          mem_gate_bytes=0).run()
        failures += _compare(f"hist on vs off ({label} MSI, 16t)",
                             r_h, r_off)
        cons = conservation_totals(
            r_h.hist, r_h, protocol=sim_h.params.mem.protocol)
        ok = (all(a == b for a, b in cons.values())
              and any(a > 0 for a, _ in cons.values()))
        print(f"{f'hist conservation ({label}, {len(cons)} src)':44} "
              f"{'PASS' if ok else 'FAIL'}"
              + ("" if ok else f"  ({cons})"))
        failures += 0 if ok else 1
        hist_ref = r_h.hist
    sweep_h = SweepRunner(sc_b, sweep_traces, hist=hspec)
    out_h = sweep_h.run()
    proto_h = sweep_h.sim.params.mem.protocol
    for b, s in enumerate(seeds):
        solo = Simulator(sc_b, sweep_traces[b],
                         mailbox_depth=sweep_h.mailbox_depth,
                         phase_gate=False, mem_gate_bytes=0,
                         hist=hspec).run()
        hb = out_h.hists[b]
        cons = conservation_totals(hb, out_h.results[b],
                                   protocol=proto_h)
        ok = (np.array_equal(hb.counts, solo.hist.counts)
              and hb.boundaries == solo.hist.boundaries
              and all(a == c for a, c in cons.values()))
        print(f"{f'sweep B=4 sim {b} hist vs sequential':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1
    with _tf2.TemporaryDirectory() as td:
        hp = _os.path.join(td, "hist.npz")
        hist_ref.save(hp)
        outp = _os.path.join(td, "trace.json")
        n_ev = _report.write_perfetto(outp, hists=[hp])
        with open(outp) as fh:
            doc = _json.load(fh)
        evs = doc.get("traceEvents", [])
        ok = n_ev == len(evs) and n_ev > 2
        last = {}
        for e in evs:
            if e["ph"] == "M":
                continue
            ok = ok and e["ts"] >= last.get(e["pid"], 0)
            last[e["pid"]] = e["ts"]
        print(f"{'perfetto export valid JSON + monotone':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1

    # 16) collective/ICI traffic analyzer (round 22, analysis/comms.py):
    #     the comms audit must exit 0 over the registered mesh programs
    #     — every collective a whitelisted px packed exchange, every
    #     declared-replicated shard_map output provably uniform, the
    #     per-phase collective tables emitted — and the known-bad
    #     legacy unpacked-exchange fixture must trip the
    #     gspmd-insertion lint (exit 1, the stray's phase named).  Both
    #     run under the same forced-4-host-device re-exec recipe as
    #     rung 12 so the audit sees a real multi-device platform.
    import os as _os16
    import subprocess as _sp16

    env16 = dict(_os16.environ)
    env16["JAX_PLATFORMS"] = "cpu"
    flags16 = env16.get("XLA_FLAGS", "")
    env16["XLA_FLAGS"] = (
        flags16 + " --xla_force_host_platform_device_count=4").strip()
    rc = _sp16.call(
        [sys.executable, "-m", "graphite_tpu.tools.audit",
         "--programs", "sweep-b4-2d,gated-msi-2d", "--comms"],
        env=env16, stdout=_sp16.DEVNULL)
    print(f"{'comms audit (mesh programs, forced 4-dev)':44} "
          f"{'PASS' if rc == 0 else 'FAIL'}")
    failures += 0 if rc == 0 else 1
    rc = _sp16.call(
        [sys.executable, "-m", "graphite_tpu.tools.audit",
         "--comms-fixture"], env=env16, stdout=_sp16.DEVNULL)
    print(f"{'gspmd-insertion fixture exits 1':44} "
          f"{'PASS' if rc == 1 else 'FAIL'}")
    failures += 0 if rc == 1 else 1

    print(f"{failures} failure(s)  ({_t.perf_counter() - t0:.0f}s)")
    return 1 if failures else 0


def smoke_mesh2d(tiles: int = 16) -> int:
    """Regress rung 12 (round 18): 2D batch x tile campaign equality +
    across-device admission, on >= 4 (forced host) devices."""
    import time as _t

    import jax
    import numpy as np

    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.obs import ProfileSpec, TelemetrySpec
    from graphite_tpu.serve import CampaignService, Job
    from graphite_tpu.sweep import SweepRunner
    from graphite_tpu.trace import synthetic

    t0 = _t.perf_counter()
    failures = 0
    n_dev = len(jax.devices())
    if n_dev < 4:
        print(f"{'mesh2d rung':44} FAIL  (needs >= 4 devices, have "
              f"{n_dev})")
        return 1
    # every tile count this rung uses must split 2 ways
    tiles = tiles if tiles % 2 == 0 else 16
    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier")))
    seeds = (1, 2, 3, 4)
    traces = [
        synthetic.memory_stress_trace(
            tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=s)
        for s in seeds
    ]
    tel = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=64)
    prof = ProfileSpec(sample_interval_ps=1_000_000, n_samples=64)
    # gating forced OFF uniformly so the 2D (vmapped cells), 1D-batch
    # (one gated sim per device) and solo programs record identical
    # skip_* telemetry columns — gating is mechanism, results are
    # bit-identical either way (rung 1)
    gate_kw = dict(phase_gate=False, mem_gate_bytes=0)

    r2d = SweepRunner(sc, traces, layout=(2, 2), telemetry=tel,
                      profile=prof, **gate_kw)
    out2d = r2d.run(max_quanta=200_000)
    r1d = SweepRunner(sc, traces, layout="batch", telemetry=tel,
                      profile=prof, **gate_kw)
    out1d = r1d.run(max_quanta=200_000)
    print(f"{'mesh2d layouts':44} 2d={out2d.layout} 1d={out1d.layout}")
    for b, s in enumerate(seeds):
        solo = Simulator(sc, traces[b], mailbox_depth=r2d.mailbox_depth,
                         telemetry=tel, profile=prof, **gate_kw).run()
        failures += _compare(
            f"2D campaign sim {b} (seed {s}) vs solo",
            out2d.results[b], solo)
        failures += _compare(
            f"2D campaign sim {b} vs 1D-batch",
            out2d.results[b], out1d.results[b])
        tl, pf = out2d.timelines[b], out2d.profiles[b]
        # (but for what the PROGRAM ran: a 2D cell's two sims share the
        # block's exit, `_timeline_matches`)
        ok = _timeline_matches(tl, solo.telemetry)
        print(f"{f'2D sim {b} timeline demux vs solo':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1
        ok = (pf.n_total == solo.profile.n_total
              and np.array_equal(pf.data, solo.profile.data)
              and np.array_equal(pf.times_ps, solo.profile.times_ps))
        print(f"{f'2D sim {b} profile ring demux vs solo':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1
        ok = (_timeline_matches(tl, out1d.timelines[b])
              and np.array_equal(out1d.profiles[b].data, pf.data))
        print(f"{f'2D sim {b} rings vs 1D-batch':44} "
              f"{'PASS' if ok else 'FAIL'}")
        failures += 0 if ok else 1

    # across-device admission: a sim whose per-sim bill exceeds one
    # device's budget is REJECTED by a 1-device service and ADMITTED
    # as a 2D class (per-device block proven <= budget) by one that
    # may bin-pack across devices — results still bit-equal to solo
    from graphite_tpu.analysis.cost import ResidencyBudgetError
    from graphite_tpu.serve.admission import measure_job

    sc_big = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax")))
    big_jobs = [Job(f"big-{i}", sc_big, traces[i], seed=seeds[i])
                for i in range(2)]
    m = measure_job(big_jobs[0], mailbox_depth=8, pad_length=64)
    budget = (m.per_sim_total + m.device_block(2)["total"]) // 2
    try:
        CampaignService(batch_size=2, max_quanta=200_000,
                        hbm_budget_bytes=budget).submit(big_jobs[0])
        print(f"{'1-device service rejects the big sim':44} FAIL")
        failures += 1
    except ResidencyBudgetError:
        print(f"{'1-device service rejects the big sim':44} PASS")
    svc = CampaignService(batch_size=2, max_quanta=200_000,
                          hbm_budget_bytes=budget, n_devices="auto")
    for j in big_jobs:
        svc.submit(j)
    served = {r.job_id: r for r in svc.drain()}
    cls = next(iter(svc.admission.classes.values()))
    ok = (cls.tile_shards > 1
          and cls.device_breakdown()["total"] <= budget
          and all(served[j.job_id].status == "ok" for j in big_jobs))
    print(f"{'big sim admitted as 2D, per-device <= budget':44} "
          f"{'PASS' if ok else 'FAIL'}"
          + ("" if ok else f"  (tile_shards={cls.tile_shards} "
             f"per_dev={cls.device_breakdown()['total']} "
             f"budget={budget})"))
    failures += 0 if ok else 1
    for j in big_jobs:
        seq = Simulator(sc_big, j.trace, **gate_kw).run()
        failures += _compare(f"2D-served {j.job_id} vs sequential",
                             served[j.job_id].results, seq)

    print(f"mesh2d: {failures} failure(s)  "
          f"({_t.perf_counter() - t0:.0f}s)")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", type=int, default=8)
    ap.add_argument("--quick", action="store_true",
                    help="one representative config per axis instead of "
                    "the cross product")
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 companion: 16-tile gated/ungated "
                    "pair + batched-barrier equivalence on CPU")
    ap.add_argument("--smoke-mesh2d", action="store_true",
                    help="rung 12 alone: 2D batch x tile campaign "
                    "equality + across-device admission (needs >= 4 "
                    "devices; --smoke re-execs this under a forced "
                    "4-device host platform when needed)")
    args = ap.parse_args()

    if args.smoke_mesh2d:
        return 1 if smoke_mesh2d(args.tiles if args.tiles != 8
                                 else 16) else 0

    if args.smoke:
        return smoke(args.tiles if args.tiles != 8 else 16)

    if args.quick:
        matrix = [
            ("pr_l1_pr_l2_dram_directory_msi", "full_map", "magic",
             "simple", "canneal"),
            ("pr_l1_pr_l2_dram_directory_mosi", "ackwise",
             "emesh_hop_counter", "iocoom", "canneal"),
            ("pr_l1_sh_l2_mesi", "limited_no_broadcast",
             "emesh_hop_by_hop", "simple", "canneal"),
            ("pr_l1_pr_l2_dram_directory_msi", "full_map",
             "emesh_hop_counter", "iocoom", "fft"),
        ]
    else:
        # memory sweep: protocol x scheme (network/core fixed), then
        # network x core (protocol fixed) on the fft kernel, then the
        # full 13-kernel SPLASH-2/PARSEC roster under the default config
        # (the reference's regress runs every SPLASH-2 app —
        # `tools/regress/run_tests.py:44-58`)
        from graphite_tpu.trace.benchmarks import BENCHMARKS

        matrix = [(p, s, "magic", "simple", "canneal")
                  for p, s in itertools.product(PROTOCOLS, SCHEMES)]
        matrix += [("pr_l1_pr_l2_dram_directory_msi", "full_map", n, c,
                    "fft")
                   for n, c in itertools.product(NETWORKS, CORES)]
        matrix += [("pr_l1_pr_l2_dram_directory_msi", "full_map",
                    "emesh_hop_counter", "simple", w)
                   for w in sorted(BENCHMARKS)
                   if w not in ("canneal", "fft")]

    failures = 0
    print(f"{'protocol':38} {'scheme':22} {'network':18} {'core':7} "
          f"{'workload':8} {'ns':>10} {'instrs':>10} ok")
    for protocol, scheme, network, core, workload in matrix:
        t0 = time.perf_counter()
        try:
            res = run_one(args.tiles, protocol, scheme, network, core,
                          workload)
            ok = res.func_errors == 0
            failures += 0 if ok else 1
            print(f"{protocol:38} {scheme:22} {network:18} {core:7} "
                  f"{workload:8} {res.completion_time_ps // 1000:>10} "
                  f"{res.total_instructions:>10} "
                  f"{'PASS' if ok else 'FAIL'}  ({time.perf_counter()-t0:.0f}s)")
        except Exception as e:  # noqa: BLE001 — a sweep reports, not raises
            failures += 1
            print(f"{protocol:38} {scheme:22} {network:18} {core:7} "
                  f"{workload:8} {'-':>10} {'-':>10} FAIL  {type(e).__name__}: "
                  f"{str(e)[:80]}")
    print(f"{failures} failure(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
