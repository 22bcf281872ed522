"""Benchmark: aggregate simulated instructions/second on one chip.

North star (BASELINE.json): ≥10M aggregate simulated instr/s on the
1024-tile e-mesh running SPLASH-2 FFT.  Default workload: the six-step FFT
trace program (`trace/benchmarks.py` — butterflies + three all-to-all
transposes + barriers, BENCH_POINTS points per tile) replayed through the
full vectorized core/network/sync stack on hop-counter NoC timing.  Set
BENCH_WORKLOAD=ring for the legacy compute+message ring.  Prints exactly
one JSON line.
"""

import json
import os
import sys
import time

N_TILES = int(os.environ.get("BENCH_TILES", "1024"))
WORKLOAD = os.environ.get("BENCH_WORKLOAD", "fft")
# fft: simulated FFT size = BENCH_TILES * BENCH_POINTS points
N_POINTS = int(os.environ.get("BENCH_POINTS", "2048"))
# ring workload knobs
N_ROUNDS = int(os.environ.get("BENCH_ROUNDS", "64"))
COMPUTE_PER_ROUND = int(os.environ.get("BENCH_COMPUTE", "62"))
# Basic-block-granularity replay (one BBLOCK record per straight-line run,
# cycle-identical timing — the engine's native trace granularity).  Set
# BENCH_COMPRESSED=0 to replay one record per instruction instead, which
# measures the raw per-record engine rate.
COMPRESSED = os.environ.get("BENCH_COMPRESSED", "1") != "0"
BASELINE_INSTR_PER_SEC = 10_000_000  # BASELINE.json north star


def main() -> None:
    import graphite_tpu  # noqa: F401  (x64)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.trace import synthetic

    # a measurement names its device and never falls back to the CPU
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip; jax found {device} — "
            f"refusing to report CPU numbers as device metrics")

    cfg_text = f"""
[general]
total_cores = {N_TILES}
mode = lite
max_frequency = 1.0
[network]
user = emesh_hop_counter
memory = emesh_hop_counter
[network/emesh_hop_counter]
flit_width = 64
[network/emesh_hop_counter/router]
delay = 1
[network/emesh_hop_counter/link]
delay = 1
[core/static_instruction_costs]
generic = 1
mov = 1
ialu = 1
imul = 3
falu = 3
fmul = 5
[branch_predictor]
type = one_bit
mispredict_penalty = 14
size = 1024
[clock_skew_management]
scheme = lax
"""
    sc = SimConfig(ConfigFile.from_string(cfg_text))
    if WORKLOAD == "fft":
        from graphite_tpu.trace.benchmarks import fft_trace

        batch = fft_trace(N_TILES, points_per_tile=N_POINTS)
        desc = f"SPLASH-2 FFT {N_TILES * N_POINTS}-point"
    elif WORKLOAD == "ring":
        batch = synthetic.message_ring_batch(
            N_TILES, n_rounds=N_ROUNDS, compute_per_round=COMPUTE_PER_ROUND,
            compressed=COMPRESSED,
        )
        desc = "compute+message workload"
    else:
        from graphite_tpu.trace.benchmarks import BENCHMARKS

        if WORKLOAD not in BENCHMARKS:
            names = ", ".join(["fft", "ring"]
                              + [n for n in BENCHMARKS if n != "fft"])
            raise SystemExit(
                f"unknown BENCH_WORKLOAD {WORKLOAD!r} (choose from: {names})"
            )
        batch = BENCHMARKS[WORKLOAD](N_TILES)
        desc = WORKLOAD
    # Barrier-phased workloads auto-size their [T,T,depth] rings from
    # the trace (Simulator auto_mailbox_depth -> 2 for FFT); the ring
    # workload's unphased send stream keeps an explicit small depth (its
    # recv interlock bounds true occupancy, which the trace-order bound
    # cannot see)
    depth = None if WORKLOAD != "ring" else 8
    # Big per-instruction traces stream host->HBM in windows instead of
    # living resident (trace/schema.py streaming mode): device trace
    # memory is bounded by one [T, W] window regardless of trace length.
    import dataclasses as _dc

    trace_bytes = sum(
        getattr(batch, f.name).nbytes for f in _dc.fields(batch))
    stream = trace_bytes > int(
        os.environ.get("BENCH_STREAM_THRESHOLD", str(1 << 30)))
    window = int(os.environ.get("BENCH_STREAM_WINDOW", "4096"))
    sim = Simulator(sc, batch, mailbox_depth=depth, inner_block=64,
                    stream=stream)

    if stream:
        # warm the XLA cache with a throwaway truncated-trace run (same
        # [T, W] window shapes -> same executables), so the timed run
        # excludes compilation like the resident path's warmup() does
        import numpy as _np

        warm_len = min(batch.length, 2 * window)
        import dataclasses as _dc2

        warm_batch = type(batch)(**{
            f.name: getattr(batch, f.name)[:, :warm_len]
            for f in _dc2.fields(batch)})
        from graphite_tpu.engine.simulator import DeadlockError

        try:
            Simulator(sc, warm_batch, mailbox_depth=depth, inner_block=64,
                      stream=True).run_streamed(window_records=window)
        except DeadlockError:
            # the truncation can cut a blocking record's resolving record
            # on another tile — the run only exists to warm the XLA
            # cache, which it has by the time the loop bails; any OTHER
            # failure must surface (a swallowed compile error would put
            # compilation inside the timed run and deflate the headline)
            pass
        t0 = time.perf_counter()
        results = sim.run_streamed(window_records=window)
        elapsed = time.perf_counter() - t0
    else:
        # Warm-up: compile (and run once) the full device-side loop.
        sim.warmup()
        t0 = time.perf_counter()
        results = sim.run()
        elapsed = time.perf_counter() - t0

    total_instr = results.total_instructions
    ips = total_instr / elapsed

    def _timed_rate(sim2):
        sim2.warmup()
        t0 = time.perf_counter()
        r = sim2.run()
        return r.total_instructions / (time.perf_counter() - t0), sim2

    # Companion rates so the round artifact tracks COHERENCE and NoC-
    # contention throughput, not just the memoryless headline (a
    # regression in either is then visible in BENCH_r*.json): the
    # graduated runner's config-2/3 shapes — 64-tile iocoom + full-MSI
    # FFT, and 256-tile hop-by-hop RADIX.  Skippable for quick local runs
    # with BENCH_COMPANIONS=0.
    companions = {}
    if os.environ.get("BENCH_COMPANIONS", "1") != "0":
        from graphite_tpu.trace.benchmarks import fft_trace, radix_trace
        from graphite_tpu.tools._template import config_text

        sc_msi = SimConfig(ConfigFile.from_string(config_text(
            64, core="iocoom", shared_mem=True, clock_scheme="lax")))
        msi_rate, msi_sim = _timed_rate(Simulator(
            sc_msi, fft_trace(64, points_per_tile=512, use_memory=True),
            inner_block=64))
        sc_hbh = SimConfig(ConfigFile.from_string(config_text(
            256, network="emesh_hop_by_hop", clock_scheme="lax")))
        hbh_rate, _ = _timed_rate(Simulator(
            sc_hbh, radix_trace(256, keys_per_tile=1024),
            inner_block=64))
        companions = {
            "coherence_msi_instr_per_s": round(msi_rate),
            "hop_by_hop_instr_per_s": round(hbh_rate),
            # gate observability (round 6): per-phase lax.cond skip
            # counts + the engine-iteration denominator, so BENCH_r{N}
            # tracks skip rates alongside throughput
            "coherence_msi_phase_skips": msi_sim.last_phase_skips,
            "coherence_msi_engine_iters": int(msi_sim.last_n_iterations),
        }

    # Batched-campaign throughput (round 7, sweep/ subsystem): a B-point
    # timing-knob grid through ONE compiled program with traced knobs.
    # The campaign comparison is COMPILE-INCLUSIVE on both sides,
    # because that is what a knob sweep actually pays: with knobs baked
    # static (the pre-round-7 tool), every grid point is a distinct XLA
    # program — B compiles; the sweep pays one compile for the whole
    # grid.  A representative single point's compile+run is measured as
    # the sequential per-point cost.  Warm per-iteration rates ride
    # along for transparency: on CPU the warm batched iteration does
    # NOT beat the warm gated sequential iteration (vmap turns the
    # activity-gating conds into both-branch selects — PERF.md round-7);
    # the on-chip op-tail amortization claim is a TPU re-measurement
    # item.  Skippable via BENCH_SWEEP=0; B via BENCH_SWEEP_B.
    if os.environ.get("BENCH_SWEEP", "1") != "0":
        from graphite_tpu.sweep import SweepRunner
        from graphite_tpu.tools._template import config_text

        B = int(os.environ.get("BENCH_SWEEP_B", "8"))
        sw_tiles = int(os.environ.get("BENCH_SWEEP_TILES", "16"))
        sc_sw = SimConfig(ConfigFile.from_string(config_text(
            sw_tiles, shared_mem=True, clock_scheme="lax")))
        sw_trace = synthetic.memory_stress_trace(
            sw_tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=7)
        points = [{"dram_latency_ns": 40 + 20 * i} for i in range(B)]
        sweep = SweepRunner(sc_sw, [sw_trace], points)
        t0 = time.perf_counter()
        out = sweep.run()               # compile + run: the campaign cost
        sweep_total_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = sweep.run()               # warm steady-state rate
        sweep_warm_s = time.perf_counter() - t0
        total_iters = max(int(out.n_iterations.sum()), 1)

        # one representative off-default point of the sequential
        # campaign: fresh static params -> its own compile, plus the run
        import dataclasses as _dc3

        seq = Simulator(sc_sw, sw_trace, mailbox_depth=sweep.mailbox_depth)
        seq.params = _dc3.replace(
            seq.params,
            mem=_dc3.replace(seq.params.mem, dram_latency_ns=40))
        t0 = time.perf_counter()
        seq.run()
        seq_point_s = time.perf_counter() - t0
        seq_iters = max(int(seq.last_n_iterations), 1)
        seq2 = Simulator(sc_sw, sw_trace,
                         mailbox_depth=sweep.mailbox_depth)
        seq2.params = seq.params
        seq2.adopt_runner(seq)
        t0 = time.perf_counter()
        seq2.run()
        seq_warm_s = time.perf_counter() - t0

        ms_amort = 1000 * sweep_total_s / total_iters
        ms_seq = 1000 * seq_point_s / seq_iters
        companions.update({
            "sweep_batch": B,
            # steady-state campaign throughput (warm program)
            "sims_per_s": round(B / sweep_warm_s, 3),
            # compile-inclusive campaign economics (the headline):
            # per-useful-iteration cost of the whole grid vs ONE
            # sequential point's compile+run
            "ms_per_iter_amortized": round(ms_amort, 4),
            "ms_per_iter_sequential": round(ms_seq, 4),
            "sweep_vs_sequential": round(ms_amort / ms_seq, 4),
            # warm rates (no compiles anywhere) for transparency
            "ms_per_iter_amortized_warm": round(
                1000 * sweep_warm_s / total_iters, 4),
            "ms_per_iter_sequential_warm": round(
                1000 * seq_warm_s / seq_iters, 4),
        })

    # 2D batch x tile campaign layouts (round 18): warm ms/iter and
    # bytes-per-device for solo vs 1D-batch vs 2D at one fixed
    # geometry, plus the admission outcome for a sim that a 1-device
    # budget rejects (accepted-as-2D across devices).  Needs >= 4
    # devices in THIS process; with fewer the fields are absent.
    # Skippable via BENCH_MESH2D=0.
    if os.environ.get("BENCH_MESH2D", "1") != "0" \
            and len(jax.devices()) >= 4:
        from graphite_tpu.tools.mesh2d_bench import measure_mesh2d

        companions.update(measure_mesh2d())

    # Telemetry overhead (round 9, obs/ subsystem): warm per-iteration
    # cost of recording a DENSE device timeline (every available series,
    # S=256, sampled every barrier quantum — the worst case) vs
    # telemetry=None on the same 16-tile coherence program, plus the
    # timeline-derived summary fields CI tracks (peak USER-net injection
    # rate, mean per-tile clock spread).  Skippable via BENCH_TELEMETRY=0.
    if os.environ.get("BENCH_TELEMETRY", "1") != "0":
        from graphite_tpu.obs import TelemetrySpec
        from graphite_tpu.tools._template import config_text

        tl_tiles = int(os.environ.get("BENCH_TELEMETRY_TILES", "16"))
        sc_tl = SimConfig(ConfigFile.from_string(config_text(
            tl_tiles, shared_mem=True, clock_scheme="lax_barrier")))
        tl_trace = synthetic.memory_stress_trace(
            tl_tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=7)
        base = Simulator(sc_tl, tl_trace)
        base.warmup()
        t0 = time.perf_counter()
        base.run()
        base_s = time.perf_counter() - t0
        base_iters = max(int(base.last_n_iterations), 1)
        tel = Simulator(sc_tl, tl_trace, telemetry=TelemetrySpec(
            sample_interval_ps=int(base.quantum_ps), n_samples=256))
        tel.warmup()
        t0 = time.perf_counter()
        tel_res = tel.run()
        tel_s = time.perf_counter() - t0
        tel_iters = max(int(tel.last_n_iterations), 1)
        ms_off = 1000 * base_s / base_iters
        ms_on = 1000 * tel_s / tel_iters
        tl_summary = tel_res.telemetry.summary()
        companions.update({
            "ms_per_iter_no_telemetry": round(ms_off, 4),
            "ms_per_iter_telemetry": round(ms_on, 4),
            "telemetry_overhead_pct": round(100 * (ms_on / ms_off - 1), 2),
            "telemetry_samples": tl_summary["samples"],
            "telemetry_peak_injection_per_ns": tl_summary.get(
                "peak_injection_per_ns"),
            "telemetry_mean_clock_spread_ps": tl_summary.get(
                "mean_clock_spread_ps"),
        })

    # Spatial-profiler overhead (round 16, obs/profile.py): warm
    # per-iteration cost of recording the DENSE per-tile [S, T, m] ring
    # (every available tile series, S=256, sampled every quantum — the
    # worst case) vs the scalar-telemetry-only ring vs recording
    # nothing, on the same 16-tile coherence program.  MEDIANS of
    # BENCH_PROFILE_REPS warm runs (per-run wall on CPU is noisy at
    # this size), plus the ring's residency bill and the straggler
    # summary CI tracks.  Skippable via BENCH_PROFILE=0.
    if os.environ.get("BENCH_PROFILE", "1") != "0":
        import statistics as _stats

        from graphite_tpu.obs import ProfileSpec, TelemetrySpec
        from graphite_tpu.tools._template import config_text

        pf_tiles = int(os.environ.get("BENCH_PROFILE_TILES", "16"))
        reps = max(1, int(os.environ.get("BENCH_PROFILE_REPS", "3")))
        sc_pf = SimConfig(ConfigFile.from_string(config_text(
            pf_tiles, shared_mem=True, clock_scheme="lax_barrier")))
        pf_trace = synthetic.memory_stress_trace(
            pf_tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=7)

        def _median_ms_iter(mk):
            # run() consumes self.state (a finished sim re-runs as a
            # no-op), so each rep gets a FRESH instance adopting the
            # warmed donor's compiled runner — every sample times a
            # full run, none times a retrace
            donor = mk()
            donor.warmup()
            samples = []
            res2 = sim2 = None
            for _ in range(reps):
                sim2 = mk()
                sim2.adopt_runner(donor)
                t0 = time.perf_counter()
                res2 = sim2.run()
                wall = time.perf_counter() - t0
                assert int(sim2.last_n_iterations) > 0
                samples.append(
                    1000 * wall / int(sim2.last_n_iterations))
            return _stats.median(samples), res2, sim2

        probe = Simulator(sc_pf, pf_trace)
        qps_pf = int(probe.quantum_ps)
        tel_spec = TelemetrySpec(sample_interval_ps=qps_pf,
                                 n_samples=256)
        prof_spec = ProfileSpec(sample_interval_ps=qps_pf,
                                n_samples=256)
        ms_pf_off, _, _ = _median_ms_iter(
            lambda: Simulator(sc_pf, pf_trace))
        ms_pf_tel, _, _ = _median_ms_iter(
            lambda: Simulator(sc_pf, pf_trace, telemetry=tel_spec))
        ms_pf_on, pf_res, pf_sim = _median_ms_iter(
            lambda: Simulator(sc_pf, pf_trace, telemetry=tel_spec,
                              profile=prof_spec))
        pf_summary = pf_res.profile.summary()
        companions.update({
            "ms_per_iter_profile_off": round(ms_pf_off, 4),
            "ms_per_iter_telemetry_only": round(ms_pf_tel, 4),
            "ms_per_iter_profile": round(ms_pf_on, 4),
            "profile_overhead_pct": round(
                100 * (ms_pf_on / ms_pf_tel - 1), 2),
            "profile_ring_bytes": int(
                pf_sim.residency_breakdown()["profile"]),
            "profile_max_skew_ps": pf_summary.get("max_skew_ps"),
            "profile_straggler_tile": pf_summary.get("straggler_tile"),
            "profile_traffic_gini": pf_summary.get("traffic_gini"),
        })

    # Latency-histogram overhead (round 21, obs/hist.py): warm
    # per-iteration cost of the DENSE commit-site scatter-add recording
    # (every available source into the log2 bucket ladder — the worst
    # case) vs the scalar telemetry ring alone vs recording nothing,
    # on the same 16-tile coherence program, plus the deterministic
    # miss-service-latency quantiles CI tracks.  MEDIANS of
    # BENCH_HIST_REPS warm runs.  Skippable via BENCH_HIST=0.
    if os.environ.get("BENCH_HIST", "1") != "0":
        import statistics as _stats_h

        from graphite_tpu.obs import HistSpec, TelemetrySpec
        from graphite_tpu.tools._template import config_text

        hs_tiles = int(os.environ.get("BENCH_HIST_TILES", "16"))
        hs_reps = max(1, int(os.environ.get("BENCH_HIST_REPS", "3")))
        sc_hs = SimConfig(ConfigFile.from_string(config_text(
            hs_tiles, shared_mem=True, clock_scheme="lax_barrier")))
        hs_trace = synthetic.memory_stress_trace(
            hs_tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=7)

        def _median_ms_iter_h(mk):
            # fresh instance per rep adopting the warmed donor's
            # runner — same shape as the profile block's sampler
            donor = mk()
            donor.warmup()
            samples = []
            res2 = sim2 = None
            for _ in range(hs_reps):
                sim2 = mk()
                sim2.adopt_runner(donor)
                t0 = time.perf_counter()
                res2 = sim2.run()
                wall = time.perf_counter() - t0
                assert int(sim2.last_n_iterations) > 0
                samples.append(
                    1000 * wall / int(sim2.last_n_iterations))
            return _stats_h.median(samples), res2, sim2

        probe_h = Simulator(sc_hs, hs_trace)
        tel_h = TelemetrySpec(
            sample_interval_ps=int(probe_h.quantum_ps), n_samples=256)
        ms_hs_off, _, _ = _median_ms_iter_h(
            lambda: Simulator(sc_hs, hs_trace))
        ms_hs_tel, _, _ = _median_ms_iter_h(
            lambda: Simulator(sc_hs, hs_trace, telemetry=tel_h))
        ms_hs_on, hs_res, hs_sim = _median_ms_iter_h(
            lambda: Simulator(sc_hs, hs_trace, hist=HistSpec()))
        hist = hs_res.hist
        companions.update({
            "ms_per_iter_hist_off": round(ms_hs_off, 4),
            "ms_per_iter_hist_scalar_ring": round(ms_hs_tel, 4),
            "ms_per_iter_hist": round(ms_hs_on, 4),
            "hist_overhead_pct": round(
                100 * (ms_hs_on / ms_hs_off - 1), 2),
            "hist_ring_bytes": int(
                hs_sim.residency_breakdown()["hist"]),
            "miss_lat_p50_ps": hist.quantile("miss_lat_ps", 0.5),
            "miss_lat_p95_ps": hist.quantile("miss_lat_ps", 0.95),
            "miss_lat_p99_ps": hist.quantile("miss_lat_ps", 0.99),
        })

    # Campaign-service throughput (round 13, serve/ subsystem): N
    # same-class jobs submitted through the admission-controlled
    # service, batched and served off the fingerprint-keyed compiled-
    # program cache — the service-level view of the round-7 batching
    # win (jobs/s is COMPILE-INCLUSIVE: one compile amortized over the
    # whole job stream is exactly the economics the service sells).
    # The sequential baseline runs the SAME jobs one-by-one through the
    # bit-exact oracle path (a fresh Simulator per job with the knobs
    # baked static — what a campaign without the service pays).
    # Skippable via BENCH_SERVE=0; sizes via BENCH_SERVE_JOBS/_BATCH.
    if os.environ.get("BENCH_SERVE", "1") != "0":
        import dataclasses as _dcs

        from graphite_tpu.serve import CampaignService, Job
        from graphite_tpu.tools._template import config_text

        sv_jobs = int(os.environ.get("BENCH_SERVE_JOBS", "8"))
        sv_batch = int(os.environ.get("BENCH_SERVE_BATCH", "4"))
        sv_tiles = int(os.environ.get("BENCH_SERVE_TILES", "16"))
        sc_sv = SimConfig(ConfigFile.from_string(config_text(
            sv_tiles, shared_mem=True, clock_scheme="lax")))

        def _sv_trace(seed):
            return synthetic.memory_stress_trace(
                sv_tiles, n_accesses=24, working_set_bytes=1 << 13,
                write_fraction=0.4, shared_fraction=0.5, seed=seed)

        jobs = [Job(f"bench-{i}", sc_sv, _sv_trace(i + 1),
                    knobs={"dram_latency_ns": 40 + 10 * i}, seed=i + 1)
                for i in range(sv_jobs)]
        service = CampaignService(batch_size=sv_batch)
        t0 = time.perf_counter()
        for job in jobs:
            service.submit(job)
        served = service.run_all()
        serve_wall = time.perf_counter() - t0
        assert len(served) == sv_jobs and all(r.ok for r in served)
        t0 = time.perf_counter()
        for job in jobs:
            seq_sim = Simulator(sc_sv, job.trace)
            seq_sim.params = _dcs.replace(
                seq_sim.params,
                mem=_dcs.replace(seq_sim.params.mem, **job.knobs))
            seq_sim.run()
        seq_wall = time.perf_counter() - t0
        sv_c = service.counters
        companions.update({
            "serve_jobs": sv_jobs,
            "serve_jobs_per_s": round(sv_jobs / serve_wall, 3),
            "serve_batch_occupancy": round(
                sv_c["mean_batch_occupancy"], 3),
            "serve_cache_hit_rate": round(sv_c["cache_hit_rate"], 3),
            "serve_compile_count": sv_c["compile_count"],
            "serve_vs_sequential": round(seq_wall / serve_wall, 3),
            "sequential_jobs_per_s": round(sv_jobs / seq_wall, 3),
        })

        # Observability overhead (round 14, obs/ host side): the SAME
        # job stream through a service with span tracing + the metrics
        # registry on — so the "observability is ~free" claim is
        # measured, not asserted.  Both runs are compile-inclusive
        # (each service pays its one compile), so the ratio compares
        # like with like.  Skippable via BENCH_OBS=0.
        if os.environ.get("BENCH_OBS", "1") != "0":
            service_t = CampaignService(batch_size=sv_batch,
                                        tracing=True)
            t0 = time.perf_counter()
            for job in jobs:
                service_t.submit(job)
            served_t = service_t.run_all()
            traced_wall = time.perf_counter() - t0
            assert len(served_t) == sv_jobs and all(r.ok for r in served_t)
            dwell = service_t.metrics["queue_dwell_seconds"]
            companions.update({
                "serve_jobs_per_s_traced": round(
                    sv_jobs / traced_wall, 3),
                "obs_overhead_pct": round(
                    100 * (traced_wall / serve_wall - 1), 2),
                "obs_spans": len(service_t.tracer.spans),
                "obs_queue_dwell_p90_s": dwell.quantile(0.9),
            })

        # Persistent AOT program store (round 17, store/ subsystem):
        # the SAME job stream through (a) a cold store-backed service —
        # pays the one compile AND the serialize/fill — then (b) a
        # warm-started second service over the same store, which
        # deserializes instead of compiling.  The warm jobs/s vs the
        # round-13 in-memory serve_jobs_per_s is the fleet cold-start
        # win the store sells; per-class compile vs deserialize wall
        # is the microscopic view.  Skippable via BENCH_STORE=0; rides
        # INSIDE the serve section (it reuses its job set and its
        # serve_wall baseline), so BENCH_SERVE=0 disables it too.
        if os.environ.get("BENCH_STORE", "1") != "0":
            import shutil as _sh
            import tempfile as _tf

            sdir = _tf.mkdtemp(prefix="graphite-bench-store-")
            try:
                service_c = CampaignService(batch_size=sv_batch,
                                            store=sdir)
                t0 = time.perf_counter()
                for job in jobs:
                    service_c.submit(job)
                served_c = service_c.run_all()
                cold_wall = time.perf_counter() - t0
                assert len(served_c) == sv_jobs \
                    and all(r.ok for r in served_c)

                service_w = CampaignService(batch_size=sv_batch,
                                            store=sdir)
                t0 = time.perf_counter()
                n_warm = service_w.warm_start()
                for job in jobs:
                    service_w.submit(job)
                served_w = service_w.run_all()
                warm_wall = time.perf_counter() - t0
                assert len(served_w) == sv_jobs \
                    and all(r.ok for r in served_w)
                c_cold = service_c.counters
                c_warm = service_w.counters
                des = service_w.metrics["store_deserialize_seconds"]
                comp = service_c.metrics["compile_seconds"]
                companions.update({
                    "store_cold_jobs_per_s": round(
                        sv_jobs / cold_wall, 3),
                    "store_warm_jobs_per_s": round(
                        sv_jobs / warm_wall, 3),
                    # warm fleet member vs the round-13 in-memory serve
                    # (both compile-inclusive from THEIR perspective:
                    # the warm one simply has no compiles left to pay)
                    "store_warm_vs_inmem_serve": round(
                        (sv_jobs / warm_wall)
                        / (sv_jobs / serve_wall), 3),
                    "store_compile_s_per_class": round(comp.mean, 3),
                    "store_deserialize_s_per_class": round(
                        des.mean, 3),
                    "store_warm_start_classes": n_warm,
                    "store_cold_compiles": c_cold["compile_count"],
                    "store_warm_compiles": c_warm["compile_count"],
                    "store_fills": c_cold["store_fills"],
                    "store_warm_hits": c_warm["store_hits"],
                })
            finally:
                _sh.rmtree(sdir, ignore_errors=True)

    # Runtime-DVFS overhead + race-to-idle campaign (round 19, dvfs/):
    # (a) warm per-iteration cost of CARRYING per-domain frequency
    # through the quantum loop (DvfsSpec attached at the config's own
    # frequencies, so both memory engines and the network/DRAM timing
    # read carried state instead of constant-folded MemParams) vs the
    # folded baseline on the 16-tile coherence program — MEDIANS of
    # BENCH_DVFS_REPS warm runs; (b) the headline race-to-idle
    # campaign: TWO domain layouts (chip-global, core/uncore split) x
    # a per-domain frequency grid served as ONE job stream with
    # V^2*f-scaled energy pricing, one (energy_pj, wall) trade point
    # per operating point — the rows tools/report.py --trade-curve
    # renders as the energy-vs-wall Pareto frontier.  Skippable via
    # BENCH_DVFS=0; rows also land in $BENCH_DVFS_OUT (JSON-lines)
    # when that is set.
    if os.environ.get("BENCH_DVFS", "1") != "0":
        import statistics as _stats

        from graphite_tpu.dvfs import DvfsSpec
        from graphite_tpu.obs import EnergyPrices, TelemetrySpec
        from graphite_tpu.serve import CampaignService, Job
        from graphite_tpu.tools._template import config_text

        dv_tiles = int(os.environ.get("BENCH_DVFS_TILES", "16"))
        dv_reps = max(1, int(os.environ.get("BENCH_DVFS_REPS", "3")))
        sc_dv = SimConfig(ConfigFile.from_string(config_text(
            dv_tiles, shared_mem=True, clock_scheme="lax_barrier")))
        dv_trace = synthetic.memory_stress_trace(
            dv_tiles, n_accesses=24, working_set_bytes=1 << 13,
            write_fraction=0.4, shared_fraction=0.5, seed=7)

        def _dv_median(mk):
            donor = mk()
            donor.warmup()
            samples = []
            for _ in range(dv_reps):
                sim2 = mk()
                sim2.adopt_runner(donor)
                t0 = time.perf_counter()
                sim2.run()
                wall = time.perf_counter() - t0
                samples.append(
                    1000 * wall / max(int(sim2.last_n_iterations), 1))
            return _stats.median(samples)

        ms_dv_off = _dv_median(lambda: Simulator(sc_dv, dv_trace))
        ms_dv_on = _dv_median(
            lambda: Simulator(sc_dv, dv_trace, dvfs=DvfsSpec()))
        companions.update({
            "ms_per_iter_dvfs_off": round(ms_dv_off, 4),
            "ms_per_iter_dvfs_carried": round(ms_dv_on, 4),
            "dvfs_carry_overhead_pct": round(
                100 * (ms_dv_on / ms_dv_off - 1), 2),
        })

        # race-to-idle: one served stream, two admission classes (the
        # domain layout is part of the config digest AND Job.dvfs
        # joins the class key), frequency grid co-batched per class
        # through the dvfs_domain_mhz knob
        dv_extra = """
[general]
technology_node = 22
[dvfs]
max_frequency = 1.0
synchronization_delay = 2
domains = "{domains}"
"""
        sc_one = SimConfig(ConfigFile.from_string(
            config_text(dv_tiles, shared_mem=True, clock_scheme="lax")
            + dv_extra.format(
                domains="<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE, "
                "DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")))
        sc_two = SimConfig(ConfigFile.from_string(
            config_text(dv_tiles, shared_mem=True, clock_scheme="lax")
            + dv_extra.format(
                domains="<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE>, "
                "<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")))
        prices = EnergyPrices(
            instruction_pj=3, l1d_access_pj=2, l2_access_pj=9,
            l2_miss_pj=120, invalidation_pj=15, eviction_pj=20,
            dram_access_pj=500, packet_pj=7)
        tel_dv = TelemetrySpec(sample_interval_ps=1_000_000,
                               n_samples=256, energy_prices=prices)
        grid_one = ((1000,), (870,), (750,), (500,))
        grid_two = ((1000, 1000), (870, 1000), (750, 870), (500, 630))
        dv_jobs = [
            Job(f"r2i-one-{p[0]}", sc_one, dv_trace,
                knobs={"dvfs_domain_mhz": p}, dvfs=DvfsSpec(),
                telemetry=tel_dv)
            for p in grid_one
        ] + [
            Job(f"r2i-two-{p[0]}-{p[1]}", sc_two, dv_trace,
                knobs={"dvfs_domain_mhz": p}, dvfs=DvfsSpec(),
                telemetry=tel_dv)
            for p in grid_two
        ]
        svc_dv = CampaignService(batch_size=4, max_quanta=200_000)
        t0 = time.perf_counter()
        for job in dv_jobs:
            svc_dv.submit(job)
        served_dv = svc_dv.run_all()
        r2i_wall = time.perf_counter() - t0
        assert len(served_dv) == len(dv_jobs) \
            and all(r.ok for r in served_dv)
        trade = [r.to_json() for r in served_dv]
        assert all("energy_pj" in row for row in trade)
        out_path = os.environ.get("BENCH_DVFS_OUT")
        if out_path:
            with open(out_path, "w") as fh:
                for row in trade:
                    fh.write(json.dumps(row) + "\n")
        companions.update({
            "dvfs_campaign_jobs": len(dv_jobs),
            "dvfs_campaign_classes": svc_dv.counters["compile_count"],
            "dvfs_campaign_wall_s": round(r2i_wall, 3),
            "dvfs_trade_points": [
                {"job": row["job"],
                 "dvfs_domain_mhz": row["dvfs_domain_mhz"],
                 "wall_ns": row["completion_time_ns"],
                 "energy_pj": row["energy_pj"]}
                for row in trade],
        })

    # Static cost-model trajectory (round 12): the audited gated-MSI
    # program's per-iteration kernel/byte proxy and its per-phase/base
    # split (analysis/cost.py — the SAME numbers BUDGETS.json gates), so
    # BENCH_r*.json tracks the proxy on CPU where wall-clock is noisy.
    # Skippable via BENCH_COST=0.
    if os.environ.get("BENCH_COST", "1") != "0":
        from graphite_tpu.analysis.audit import default_programs
        from graphite_tpu.analysis.cost import cost_report

        spec = default_programs(8, names=("gated-msi",))[0]
        rep = cost_report(spec)
        companions.update({
            "cost_program": rep.program,
            "kernels_per_iter": int(rep.kernels_per_iter),
            "bytes_per_iter": int(rep.bytes_per_iter),
            "phase_kernels_per_iter": {
                p.name: int(p.eqns) for p in rep.phase_costs},
            "base_kernels_per_iter": int(rep.base_kernels_per_iter),
        })

    print(
        json.dumps(
            {
                # only the ring workload honors BENCH_COMPRESSED; the
                # benchmark programs always emit bblock-compressed compute
                "metric": f"simulated instr/s ({N_TILES}-tile emesh, "
                f"{desc}, "
                + ("bblock" if COMPRESSED or WORKLOAD != "ring"
                   else "per-instr")
                + " trace)",
                "value": round(ips),
                "unit": "instr/s",
                "vs_baseline": round(ips / BASELINE_INSTR_PER_SEC, 4),
                "device": device,
                **companions,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
