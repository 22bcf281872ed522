"""The spatial profiler (graphite_tpu/obs/profile.py, round 16).

The contract pins:
 - `profile=None` (the default) lowers the HISTORICAL program — jaxpr
   structurally identical to the legacy entry point, with zero profile
   invars (the telemetry=None / knobs=None contract, also enforced by
   the `profile-off` audit lint);
 - recording is pure observability: a profile-enabled run's SimResults
   are bit-equal to its profile=None twin;
 - the recorded per-tile rows match a hand-stepped chunked oracle
   (run_chunk(1) + host-side per-tile differencing) sample for sample;
 - cross-ring consistency: with telemetry + profile on one sampling
   cursor, every shared delta series sums over T to the scalar column
   and max(clock_skew) + clock_min == clock_max;
 - the ring wraps at S exhaustion keeping the LAST S samples;
 - vmapped campaigns demux [B, S, T, m] per-sim profiles equal to
   sequential profile runs (shard_map campaigns gather per-device
   buffers through the same demux);
 - serve jobs with differing profile specs never co-batch (distinct
   admission class keys) and envelopes carry the demuxed TileProfile;
 - the heatmap CLI renders a deterministic golden shape.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from graphite_tpu.analysis import rules
from graphite_tpu.analysis.audit import spec_from_simulator
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.obs import (
    PROFILE_CORE_SERIES, PROFILE_LEVEL_SERIES, ProfileSpec, TileProfile,
    available_tile_series, gini, grid_shape,
)
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic

import targets

TILES = 8
QUANTUM_PS = 1_000_000   # config_text default: 1000 ns lax_barrier


def _config(extra: str = ""):
    return targets.template_config(TILES, extra, shared_mem=True,
                                   clock_scheme="lax_barrier")


def _trace(seed=7, n=24):
    return targets.stress_trace(TILES, seed=seed, n_accesses=n)


def _spec(interval=QUANTUM_PS, s=64, series=None):
    return ProfileSpec(sample_interval_ps=interval, n_samples=s,
                       series=series)


class TestSpec:
    def test_validation(self):
        with pytest.raises(ValueError, match="positive"):
            ProfileSpec(sample_interval_ps=0)
        with pytest.raises(ValueError, match="positive"):
            ProfileSpec(sample_interval_ps=1, n_samples=0)

    def test_resolve_selects_and_dedupes(self):
        sim = Simulator(_config(), _trace())
        spec = _spec(series=("l2_misses", "clock_skew_ps",
                             "l2_misses")).resolve(sim.params)
        assert spec.series == ("l2_misses", "clock_skew_ps")
        assert spec.n_series == 2
        assert spec.n_tiles == TILES
        assert spec.buffer_sig() == ((64, TILES, 2), "int64")

    def test_resolve_rejects_unknown_series(self):
        sim = Simulator(_config(), _trace())
        with pytest.raises(ValueError, match="unavailable profile"):
            _spec(series=("no_such_series",)).resolve(sim.params)

    def test_dense_series_set(self):
        sim = Simulator(_config(), _trace())
        avail = available_tile_series(sim.params)
        assert set(PROFILE_CORE_SERIES) <= set(avail)
        spec = _spec().resolve(sim.params)
        assert spec.series == avail

    def test_memoryless_program_offers_core_series_only(self):
        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, clock_scheme="lax_barrier")))
        batch = synthetic.message_ring_batch(TILES, n_rounds=4,
                                             compute_per_round=8)
        sim = Simulator(sc, batch)
        assert available_tile_series(sim.params) == PROFILE_CORE_SERIES
        with pytest.raises(ValueError, match="unavailable"):
            _spec(series=("l2_misses",)).resolve(sim.params)

    def test_energy_series_needs_prices(self):
        sim = Simulator(_config(), _trace())
        with pytest.raises(ValueError, match="energy_prices"):
            _spec(series=("energy_pj",)).resolve(sim.params)

    def test_ring_bytes_accounting(self):
        sim = Simulator(_config(), _trace())
        spec = _spec(s=32, series=("clock_skew_ps",
                                   "l2_misses")).resolve(sim.params)
        S, T, m = 32, TILES, 2
        assert spec.ring_bytes() == (S * T * m + T * m + S + 2) * 8

    def test_attach_rejects_stream_and_requires_spec(self):
        sim = Simulator(_config(), _trace(), stream=True)
        with pytest.raises(ValueError, match="single-device resident"):
            sim.attach_profile(_spec())
        sim2 = Simulator(_config(), _trace())
        with pytest.raises(TypeError, match="ProfileSpec"):
            sim2.attach_profile({"sample_interval_ps": 1})

    def test_grid_shape_and_gini(self):
        assert grid_shape(64) == (8, 8)
        assert grid_shape(8) == (3, 3)
        assert grid_shape(1) == (1, 1)
        assert gini([1, 1, 1, 1]) == 0.0
        assert gini([0, 0, 0, 0]) == 0.0
        # one tile carries everything: G -> 1 - 1/n
        assert gini([0, 0, 0, 8]) == pytest.approx(0.75)


class TestProgramIdentity:
    def test_profile_none_is_the_baseline_program(self):
        """profile=None must lower jaxpr-identically to the legacy
        entry point that never heard of the profiler, with zero
        profile invars."""
        from graphite_tpu.analysis.identity import same_program
        from graphite_tpu.engine.step import run_simulation

        sim = Simulator(_config(), _trace())
        closed_none, paths = sim.lower(max_quanta=512)
        params, qps = sim.params, sim.quantum_ps

        def legacy(st, tr):
            return run_simulation(params, tr, st, qps, 512)

        closed_legacy = jax.make_jaxpr(legacy)(sim.state,
                                               sim.device_trace)
        assert same_program(closed_none, closed_legacy)
        assert not any("profile" in p for p in paths)
        assert not rules.telemetry_off(closed_none, paths,
                                       state_key="profile",
                                       rule="profile-off")

    def test_profile_off_lint_fires_on_recording_program(self):
        simt = Simulator(_config(), _trace(), profile=_spec())
        closed, paths = simt.lower(max_quanta=512)
        fs = rules.telemetry_off(
            closed, paths, ring_sigs=(simt.profile_spec.buffer_sig(),),
            state_key="profile", rule="profile-off")
        assert fs
        assert all(f.rule == "profile-off" for f in fs)
        assert any("invar" in f.message for f in fs)

    def test_profile_off_lint_catches_internal_ring(self):
        S, T, m = 16, TILES, 4

        def bad(x):
            buf = jnp.zeros((S, T, m), jnp.int64)
            return buf.at[0, 0, 0].set(x)

        closed = jax.make_jaxpr(bad)(jnp.asarray(1, jnp.int64))
        fs = rules.telemetry_off(closed, ["x"],
                                 ring_sigs=(((S, T, m), "int64"),),
                                 state_key="profile",
                                 rule="profile-off")
        assert fs and fs[0].data["shape"] == [S, T, m]

    def test_ring_buffer_forbidden_in_conds(self):
        """Profile-on programs add the [S, T, m] aval to the
        cond-payload forbidden set; the real program passes, a toy cond
        carrying the ring fires."""
        simt = Simulator(_config(), _trace(), phase_gate=True,
                         mem_gate_bytes=0, profile=_spec())
        spec = spec_from_simulator("prof", simt, max_quanta=512)
        assert simt.profile_spec.buffer_sig() in \
            spec.forbidden_cond_avals
        assert spec.expect_profile
        assert not rules.cond_payload(
            spec.closed, forbidden=spec.forbidden_cond_avals)

        sig = simt.profile_spec.buffer_sig()

        def bad(p, buf):
            return jax.lax.cond(p, lambda b: b + 1, lambda b: b, buf)

        closed = jax.make_jaxpr(bad)(True, jnp.zeros(sig[0], jnp.int64))
        assert rules.cond_payload(closed, forbidden=(sig,))

    def test_off_specs_carry_profile_sigs_and_audit_passes(self):
        """Profile-OFF specs carry the canonical dense per-tile ring
        sig (plus the energy variant, one series wider), so the aval
        scan is live; a profile-ON program clears the full audit."""
        from graphite_tpu.analysis.audit import audit

        sim = Simulator(_config(), _trace())
        off = spec_from_simulator("off", sim, max_quanta=512)
        assert not off.expect_profile
        assert off.profile_sig is not None
        (S, T, m), dt = off.profile_sig
        assert T == TILES
        assert off.profile_extra_sigs[0] == ((S, T, m + 1), dt)

        simt = Simulator(_config(), _trace(), phase_gate=True,
                         mem_gate_bytes=0, profile=_spec())
        on = spec_from_simulator("prof-on", simt, max_quanta=512)
        report = audit([off, on])
        assert report.ok, [str(f) for f in report.errors]
        assert "profile-off" in {r.rule for r in report.results
                                 if r.program == "off"}
        assert "profile-off" not in {r.rule for r in report.results
                                     if r.program == "prof-on"}


class TestRecording:
    def test_results_bit_equal_and_profile_attached(self):
        batch = _trace()
        r_off = Simulator(_config(), batch).run()
        sim = Simulator(_config(), batch, profile=_spec())
        r_on = sim.run()
        np.testing.assert_array_equal(r_on.clock_ps, r_off.clock_ps)
        np.testing.assert_array_equal(r_on.instruction_count,
                                      r_off.instruction_count)
        for k in r_off.mem_counters:
            np.testing.assert_array_equal(
                r_on.mem_counters[k], r_off.mem_counters[k], err_msg=k)
        assert r_on.n_quanta == r_off.n_quanta
        assert r_off.profile is None
        pf = r_on.profile
        assert isinstance(pf, TileProfile)
        assert len(pf) > 0 and not pf.wrapped
        assert pf.data.shape[1:] == (TILES, sim.profile_spec.n_series)
        np.testing.assert_array_equal(sim.profile.data, pf.data)
        # the final row is the completion sample; per-tile delta series
        # sum (over samples AND tiles) to the run totals
        assert int(pf.times_ps[-1]) == r_on.completion_time_ps
        assert int(pf.col("instructions").sum()) == r_on.total_instructions
        np.testing.assert_array_equal(pf.col("packets_sent").sum(axis=0),
                                      r_on.packets_sent)
        np.testing.assert_array_equal(
            pf.col("l2_misses").sum(axis=0),
            r_on.mem_counters["l2_misses"])

    def test_rows_match_chunked_oracle(self):
        """Per-tile sample correctness: step the SAME sim quantum by
        quantum from the host (run_chunk(1)), difference the fetched
        per-tile counters by hand, and require the device rows to
        match exactly."""
        batch = _trace()
        series = ("clock_skew_ps", "instructions", "packets_sent",
                  "l2_misses")
        interval = 1_500_000   # 1.5 quanta — forces skipped boundaries
        simt = Simulator(_config(), batch,
                         profile=_spec(interval=interval, series=series))
        pf = simt.run().profile
        order = simt.profile_spec.series

        ref = Simulator(_config(), batch)
        prev = np.zeros((TILES, len(order)), np.int64)
        next_ps = interval
        rows = []
        times = []
        for _ in range(10_000):
            done, _ = ref.run_chunk(1)
            st = ref.state
            clocks, done_mask, instr, sent, mc = jax.device_get(
                (st.core.clock_ps, st.done, st.core.instruction_count,
                 st.net.packets_sent, st.mem.counters.l2_misses))
            pending = clocks[~done_mask]
            sim_time = int(pending.min() if pending.size
                           else clocks.max())
            cur_map = {
                "clock_skew_ps": clocks - clocks.min(),
                "instructions": instr,
                "packets_sent": sent,
                "l2_misses": mc,
            }
            cur = np.stack([np.asarray(cur_map[s], np.int64)
                            for s in order], axis=1)
            if sim_time >= next_ps or done:
                row = np.where(
                    np.array([s not in PROFILE_LEVEL_SERIES
                              for s in order])[None, :],
                    cur - prev, cur)
                rows.append(row)
                times.append(sim_time)
                prev = cur
                next_ps = (sim_time // interval + 1) * interval
            if done:
                break
        assert done
        np.testing.assert_array_equal(pf.data, np.array(rows))
        np.testing.assert_array_equal(pf.times_ps,
                                      np.array(times, np.int64))

    def test_cross_ring_sums_match_scalar_telemetry(self):
        """The free invariant: both rings on one sampling cursor —
        every shared delta series sums over T to the scalar column;
        the skew column reconstructs the clock spread."""
        from graphite_tpu.obs import TelemetrySpec

        batch = _trace()
        tel = TelemetrySpec(sample_interval_ps=QUANTUM_PS, n_samples=64)
        res = Simulator(_config(), batch, telemetry=tel,
                        profile=_spec()).run()
        pf, tl = res.profile, res.telemetry
        assert pf.n_total == tl.n_total
        np.testing.assert_array_equal(pf.times_ps, tl.col("time_ps"))
        for s in ("instructions", "packets_sent", "sync_stall_ps",
                  "l2_misses", "invalidations", "evictions"):
            np.testing.assert_array_equal(
                pf.col(s).sum(axis=1), tl.col(s), err_msg=s)
        np.testing.assert_array_equal(
            pf.col("clock_skew_ps").max(axis=1) + tl.col("clock_min_ps"),
            tl.col("clock_max_ps"))

    def test_per_tile_energy_sums_to_scalar_energy(self):
        from graphite_tpu.obs import EnergyPrices, TelemetrySpec

        prices = EnergyPrices(
            instruction_pj=3, l1d_access_pj=2, l2_access_pj=9,
            l2_miss_pj=120, invalidation_pj=15, eviction_pj=20,
            dram_access_pj=500, packet_pj=7)
        batch = _trace()
        tel = TelemetrySpec(sample_interval_ps=QUANTUM_PS, n_samples=64,
                            series=("energy_pj",),
                            energy_prices=prices)
        prof = ProfileSpec(sample_interval_ps=QUANTUM_PS, n_samples=64,
                           series=("energy_pj",), energy_prices=prices)
        res = Simulator(_config(), batch, telemetry=tel,
                        profile=prof).run()
        np.testing.assert_array_equal(
            res.profile.col("energy_pj").sum(axis=1),
            res.telemetry.col("energy_pj"))

    def test_ring_wraparound_keeps_last_samples(self):
        batch = _trace()
        big = Simulator(_config(), batch, profile=_spec(s=64))
        pf_big = big.run().profile
        assert pf_big.n_total > 2
        small = Simulator(_config(), batch, profile=_spec(s=2))
        pf = small.run().profile
        assert pf.wrapped and pf.n_total == pf_big.n_total
        assert len(pf) == 2
        np.testing.assert_array_equal(pf.data, pf_big.data[-2:])
        np.testing.assert_array_equal(pf.times_ps, pf_big.times_ps[-2:])

    def test_barrier_host_dispatch_records_identically(self):
        batch = _trace()
        pf_dev = Simulator(_config(), batch,
                           profile=_spec()).run().profile
        sim_hb = Simulator(_config(), batch, barrier_host=True,
                           barrier_batch=2, profile=_spec())
        pf_hb = sim_hb.run().profile
        assert pf_hb.n_total == pf_dev.n_total
        np.testing.assert_array_equal(pf_hb.data, pf_dev.data)
        np.testing.assert_array_equal(pf_hb.times_ps, pf_dev.times_ps)

    def test_save_load_roundtrip_and_heatmap_cli(self, tmp_path,
                                                 capsys):
        from graphite_tpu.tools.report import main as report_main

        pf = Simulator(_config(), _trace(),
                       profile=_spec()).run().profile
        path = str(tmp_path / "prof.npz")
        pf.save(path)
        back = TileProfile.load(path)
        assert back.series == pf.series
        assert back.n_total == pf.n_total
        np.testing.assert_array_equal(back.data, pf.data)
        np.testing.assert_array_equal(back.times_ps, pf.times_ps)

        # JSON rows: one per selected series, full [T] vector
        assert report_main([path, "--heatmap", "--format", "json",
                            "--series", "l2_misses"]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines[0]["series"] == "l2_misses"
        assert lines[0]["tiles"] == [
            int(v) for v in pf.tile_slice("l2_misses", "total")]
        assert lines[-1]["straggler_tile"] == \
            pf.summary()["straggler_tile"]

        # golden text render: header + ceil(T/cols) grid rows of shade
        # digits per series, then the summary block
        assert report_main([path, "--heatmap", "--format", "text",
                            "--series", "clock_skew_ps",
                            "--slice", "last"]) == 0
        out = capsys.readouterr().out.splitlines()
        rows, cols = grid_shape(TILES)
        assert out[0].startswith("== sim 0:")
        assert out[1].startswith("-- clock_skew_ps [slice last] min ")
        grid = out[2:2 + rows]
        assert len(grid) == rows
        flat = "".join(grid).replace(" ", "")
        assert len(flat) == TILES
        assert set(flat) <= set("0123456789")
        assert "straggler_tile" in "".join(out)

    def test_timeline_summary_peaks_argmax(self, tmp_path, capsys):
        """The round-16 small fix: scalar timeline summaries name
        their per-series argmax sample/time."""
        from graphite_tpu.obs import TelemetrySpec
        from graphite_tpu.tools.report import main as report_main

        tl = Simulator(_config(), _trace(), telemetry=TelemetrySpec(
            sample_interval_ps=QUANTUM_PS,
            n_samples=64)).run().telemetry
        peaks = tl.summary()["peaks"]
        assert "l2_misses" in peaks and "clock_spread_ps" in peaks
        p = peaks["l2_misses"]
        col = tl.col("l2_misses")
        assert p["max"] == int(col.max())
        assert p["sample"] == int(np.argmax(col))
        assert p["time_ns"] == int(tl.time_ns[np.argmax(col)])
        path = str(tmp_path / "tl.npz")
        tl.save(path)
        assert report_main([path, "--format", "text",
                            "--summary"]) == 0
        assert "peak l2_misses" in capsys.readouterr().out


class TestSweepDemux:
    def test_vmap_campaign_demuxes_per_sim_profiles(self):
        from graphite_tpu.sweep import SweepRunner

        seeds = (1, 2, 3)
        traces = [_trace(seed=s) for s in seeds]
        sweep = SweepRunner(_config(), traces, shard_batch=False,
                            profile=_spec())
        out = sweep.run()
        assert out.profiles is not None and len(out.profiles) == 3
        n_series = sweep.sim.profile_spec.n_series
        for b in range(3):
            pf = out.profiles[b]
            assert pf.data.shape[1:] == (TILES, n_series)
            assert out.results[b].profile is pf
            solo = Simulator(_config(), traces[b],
                             mailbox_depth=sweep.mailbox_depth,
                             phase_gate=False, mem_gate_bytes=0,
                             profile=_spec()).run().profile
            assert pf.n_total == solo.n_total
            np.testing.assert_array_equal(pf.data, solo.data,
                                          err_msg=f"sim {b}")
            np.testing.assert_array_equal(pf.times_ps, solo.times_ps)

    def test_shard_map_campaign_gathers_device_buffers(self):
        from graphite_tpu.sweep import SweepRunner

        if len(jax.devices()) < 2:
            pytest.skip("needs the multi-device CPU platform")
        B = len(jax.devices())
        traces = [_trace(seed=s) for s in range(B)]
        sweep = SweepRunner(_config(), traces, shard_batch=True,
                            profile=_spec())
        out = sweep.run()
        assert len(out.profiles) == B
        for b in (0, B - 1):
            solo = Simulator(_config(), traces[b],
                             mailbox_depth=sweep.mailbox_depth,
                             profile=_spec()).run().profile
            assert out.profiles[b].n_total == solo.n_total
            np.testing.assert_array_equal(out.profiles[b].data,
                                          solo.data, err_msg=f"sim {b}")

    def test_campaign_residency_itemizes_profile_rings(self):
        from graphite_tpu.sweep import SweepRunner

        traces = [_trace(seed=s) for s in (1, 2)]
        sweep = SweepRunner(_config(), traces, shard_batch=False,
                            profile=_spec())
        bd = sweep.residency_breakdown()
        assert bd["profile"] == 2 * sweep.sim.profile_spec.ring_bytes()


class TestServe:
    def test_class_key_splits_on_profile_spec(self):
        from graphite_tpu.serve import CampaignService, Job

        svc = CampaignService(batch_size=4)
        batch = _trace()
        j_off = Job("off", _config(), batch)
        j_a = Job("a", _config(), batch, profile=_spec())
        j_b = Job("b", _config(), batch, profile=_spec(s=32))
        j_a2 = Job("a2", _config(), batch, profile=_spec())
        keys = [svc.admission.class_key(j)
                for j in (j_off, j_a, j_b, j_a2)]
        assert keys[1] != keys[0]
        assert keys[1] != keys[2]
        assert keys[1] == keys[3]

    def test_served_profile_matches_sequential(self):
        from graphite_tpu.serve import CampaignService, Job

        svc = CampaignService(batch_size=2, max_quanta=200_000,
                              verify_hits=True)
        jobs = [Job(f"p{i}", _config(), _trace(seed=i + 1),
                    profile=_spec()) for i in range(2)]
        for j in jobs:
            svc.submit(j)
        served = {r.job_id: r for r in svc.drain()}
        for j in jobs:
            got = served[j.job_id]
            assert got.ok and got.profile is not None
            assert got.to_json()["profile_samples"] == len(got.profile)
            seq = Simulator(_config(), j.trace,
                            mailbox_depth=svc.admission.classes[
                                svc.admission.class_key(j)].mailbox_depth,
                            phase_gate=False, mem_gate_bytes=0,
                            profile=_spec()).run().profile
            assert got.profile.n_total == seq.n_total
            np.testing.assert_array_equal(got.profile.data, seq.data)
        assert svc.counters["compile_count"] == 1

    def test_admission_bill_includes_profile_ring(self):
        from graphite_tpu.serve import CampaignService, Job

        svc = CampaignService(batch_size=2)
        job = Job("p", _config(), _trace(), profile=_spec())
        cls, _ = svc.admission.admit(job)
        assert cls.per_sim_bytes["profile"] == cls.profile.ring_bytes()
        assert "-prof" in svc._class_name(cls)

    def test_serve_cli_profile_out_writes_npz(self, tmp_path, capsys):
        from graphite_tpu.tools.serve import main as serve_main

        jobs = tmp_path / "jobs.jsonl"
        jobs.write_text(json.dumps({
            "id": "cli0", "tiles": 4, "seed": 1, "accesses": 8,
            "profile": {"sample_interval_ps": 1_000_000,
                        "n_samples": 16}}) + "\n")
        out_dir = tmp_path / "profiles"
        assert serve_main(["--jobs", str(jobs), "--batch-size", "1",
                           "--profile-out", str(out_dir)]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        row = next(r for r in lines if r.get("job") == "cli0")
        path = row["profile_file"]
        assert path == str(out_dir / "cli0.npz")
        saved = TileProfile.load(path)
        assert saved.n_tiles == 4
        assert len(saved) == row["profile_samples"]


class TestTradeCurve:
    SPANS = [
        {"trace": "batch-0", "span": "batch", "start_us": 0,
         "dur_us": 900, "occupancy": 1.0, "n_jobs": 2, "capacity": 2},
        {"trace": "batch-1", "span": "batch", "start_us": 0,
         "dur_us": 700, "occupancy": 0.5, "n_jobs": 1, "capacity": 2},
        {"trace": "j0", "span": "queue", "start_us": 0, "dur_us": 100,
         "batch": 0},
        {"trace": "j1", "span": "queue", "start_us": 0, "dur_us": 300,
         "batch": 0},
        {"trace": "j2", "span": "queue", "start_us": 0, "dur_us": 40,
         "batch": 1},
        # no matching batch span: dropped from the scatter
        {"trace": "j3", "span": "queue", "start_us": 0, "dur_us": 5,
         "batch": 9},
        # not a queue span: ignored
        {"trace": "j0", "span": "execute", "start_us": 0, "dur_us": 1,
         "batch": 0},
    ]

    def test_rows_and_buckets(self):
        from graphite_tpu.tools.report import trade_curve_rows

        scatter, curve = trade_curve_rows(self.SPANS)
        assert [s["job"] for s in scatter] == ["j0", "j1", "j2"]
        assert scatter[0] == {"job": "j0", "batch": 0,
                              "queue_dwell_us": 100, "occupancy": 1.0,
                              "n_jobs": 2, "capacity": 2,
                              "execute_us": 900}
        assert [c["occupancy_bucket"] for c in curve] == [0.5, 1.0]
        assert curve[1]["jobs"] == 2
        assert curve[1]["mean_dwell_us"] == 200
        assert curve[1]["max_dwell_us"] == 300
        assert curve[0]["mean_execute_us"] == 700

    def test_cli_render(self, tmp_path, capsys):
        from graphite_tpu.tools.report import main as report_main

        path = tmp_path / "spans.jsonl"
        path.write_text("".join(json.dumps(r) + "\n"
                                for r in self.SPANS))
        assert report_main(["--trade-curve", str(path)]) == 0
        rows = [json.loads(ln) for ln in
                capsys.readouterr().out.strip().splitlines()]
        assert sum(1 for r in rows if r.get("curve")) == 2
        assert sum(1 for r in rows if "job" in r) == 3
        assert report_main(["--trade-curve", str(path), "--format",
                            "text"]) == 0
        out = capsys.readouterr().out
        assert "queue_dwell_us" in out and "occupancy_bucket" in out
