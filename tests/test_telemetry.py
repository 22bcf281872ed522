"""Device-resident telemetry timelines (graphite_tpu/obs/, round 9).

The contract pins:
 - `telemetry=None` (the default) lowers the HISTORICAL program — jaxpr
   string-identical to calling `run_simulation` with no telemetry at
   all, and free of telemetry invars (the knobs=None contract, also
   enforced by the `telemetry-off` audit lint);
 - recording is pure observability: a telemetry-enabled run's
   SimResults are bit-equal to its telemetry=None twin;
 - the recorded rows match a hand-stepped chunked oracle (run_chunk(1)
   + host-side differencing) sample for sample;
 - the ring wraps at S exhaustion keeping the LAST S samples;
 - vmapped campaigns demux [B, S, n_series] per-sim timelines equal to
   sequential telemetry runs (shard_map campaigns gather per-device
   buffers through the same demux);
 - the StatisticsManager device backend writes byte-identical `.trace`
   files to the chunked backend on the same run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from graphite_tpu.analysis import rules
from graphite_tpu.analysis.audit import spec_from_simulator
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.obs import (
    CORE_SERIES, LEVEL_SERIES, Timeline, TelemetrySpec, available_series,
)
from graphite_tpu.obs.telemetry import SKIP_PREFIX, counts_the_program
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
    return TelemetrySpec(sample_interval_ps=interval, n_samples=s,
                         series=series)


class TestSpec:
    def test_validation(self):
        with pytest.raises(ValueError, match="positive"):
            TelemetrySpec(sample_interval_ps=0)
        with pytest.raises(ValueError, match="positive"):
            TelemetrySpec(sample_interval_ps=1, n_samples=0)

    def test_resolve_selects_and_orders(self):
        sim = Simulator(_config(), _trace())
        spec = _spec(series=("instructions", "l2_misses")).resolve(
            sim.params)
        # time_ps is forced first (the demux key)
        assert spec.series == ("time_ps", "instructions", "l2_misses")
        assert spec.n_series == 3
        assert spec.buffer_sig() == ((64, 3), "int64")

    def test_resolve_rejects_unknown_series(self):
        sim = Simulator(_config(), _trace())
        with pytest.raises(ValueError, match="unavailable telemetry"):
            _spec(series=("no_such_series",)).resolve(sim.params)

    def test_dense_series_set_and_skip_names_from_engine(self):
        from graphite_tpu.engine.simulator import mem_phase_names

        sim = Simulator(_config(), _trace())
        avail = available_series(sim.params)
        assert set(CORE_SERIES) <= set(avail)
        # skip_* names come from the engine's own phase-name table —
        # one source of truth, no parallel list
        assert tuple("skip_" + n for n in mem_phase_names(sim.params)) \
            == tuple(s for s in avail if s.startswith("skip_"))

    def test_memoryless_program_offers_core_series_only(self):
        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, clock_scheme="lax_barrier")))
        batch = synthetic.message_ring_batch(TILES, n_rounds=4,
                                             compute_per_round=8)
        sim = Simulator(sc, batch)
        assert available_series(sim.params) == CORE_SERIES
        with pytest.raises(ValueError, match="unavailable"):
            _spec(series=("l2_misses",)).resolve(sim.params)

    def test_attach_rejects_stream_and_requires_spec(self):
        sim = Simulator(_config(), _trace(), stream=True)
        with pytest.raises(ValueError, match="single-device resident"):
            sim.attach_telemetry(_spec())
        sim2 = Simulator(_config(), _trace())
        with pytest.raises(TypeError, match="TelemetrySpec"):
            sim2.attach_telemetry({"sample_interval_ps": 1})


class TestProgramIdentity:
    def test_telemetry_none_is_the_baseline_program(self):
        """telemetry=None must lower jaxpr-identically to the legacy
        entry point that never heard of telemetry (knobs=None contract),
        with zero telemetry invars."""
        from graphite_tpu.engine.step import run_simulation

        sim = Simulator(_config(), _trace())
        closed_none, paths = sim.lower(max_quanta=512)
        params, qps = sim.params, sim.quantum_ps

        def legacy(st, tr):
            return run_simulation(params, tr, st, qps, 512)

        closed_legacy = jax.make_jaxpr(legacy)(sim.state, sim.device_trace)
        # canonical structural equality (analysis/identity.py) — the
        # ONE definition of "same program" the CI lock gate also uses,
        # replacing the old ad-hoc str(jaxpr) comparison
        from graphite_tpu.analysis.identity import same_program

        assert same_program(closed_none, closed_legacy)
        assert not any("telemetry" in p for p in paths)
        assert not rules.telemetry_off(closed_none, paths)

    def test_telemetry_off_lint_fires_on_recording_program(self):
        """Known-bad fixture: the lint must catch a program that DOES
        carry the recording machinery."""
        simt = Simulator(_config(), _trace(), telemetry=_spec())
        closed, paths = simt.lower(max_quanta=512)
        fs = rules.telemetry_off(
            closed, paths, ring_sigs=(simt.telemetry_spec.buffer_sig(),))
        assert fs
        assert all(f.rule == "telemetry-off" for f in fs)
        assert any("invar" in f.message for f in fs)

    def test_telemetry_off_lint_catches_internal_ring(self):
        """A ring materialized INSIDE the program (no invar) is caught
        by the aval scan."""
        S, n = 16, 4

        def bad(x):
            buf = jnp.zeros((S, n), jnp.int64)
            return buf.at[0, 0].set(x)

        closed = jax.make_jaxpr(bad)(jnp.asarray(1, jnp.int64))
        fs = rules.telemetry_off(closed, ["x"], ring_sigs=(((S, n),
                                                            "int64"),))
        assert fs and fs[0].data["shape"] == [S, n]

    def test_ring_buffer_forbidden_in_conds(self):
        """Telemetry-on programs add the ring aval to the cond-payload
        forbidden set; the real program passes, a toy cond carrying the
        ring fires."""
        simt = Simulator(_config(), _trace(), phase_gate=True,
                         mem_gate_bytes=0, telemetry=_spec())
        spec = spec_from_simulator("tel", simt, max_quanta=512)
        assert simt.telemetry_spec.buffer_sig() in \
            spec.forbidden_cond_avals
        assert spec.expect_telemetry
        assert not rules.cond_payload(
            spec.closed, forbidden=spec.forbidden_cond_avals)

        sig = simt.telemetry_spec.buffer_sig()

        def bad(p, buf):
            return jax.lax.cond(p, lambda b: b + 1, lambda b: b, buf)

        closed = jax.make_jaxpr(bad)(
            True, jnp.zeros(sig[0], jnp.int64))
        assert rules.cond_payload(closed, forbidden=(sig,))

    def test_audit_default_programs_include_telemetry(self):
        from graphite_tpu.analysis.audit import (
            DEFAULT_PROGRAM_NAMES, audit, default_programs,
        )

        assert "gated-msi-tel" in DEFAULT_PROGRAM_NAMES
        specs = default_programs(
            TILES, max_quanta=512, names=("gated-msi", "gated-msi-tel"))
        # telemetry-OFF specs carry the canonical dense ring sig so the
        # telemetry-off AVAL scan is live, not just the invar check
        off = next(s for s in specs if s.name == "gated-msi")
        assert not off.expect_telemetry
        assert off.telemetry_sig is not None
        report = audit(specs)
        assert report.ok, [str(f) for f in report.errors]
        assert {r.rule for r in report.results
                if r.program == "gated-msi"} >= {"telemetry-off"}
        assert "telemetry-off" not in {
            r.rule for r in report.results if r.program == "gated-msi-tel"}


class TestRecording:
    def test_results_bit_equal_and_timeline_attached(self):
        batch = _trace()
        r_off = Simulator(_config(), batch).run()
        sim = Simulator(_config(), batch, telemetry=_spec())
        r_on = sim.run()
        np.testing.assert_array_equal(r_on.clock_ps, r_off.clock_ps)
        np.testing.assert_array_equal(r_on.instruction_count,
                                      r_off.instruction_count)
        for k in r_off.mem_counters:
            np.testing.assert_array_equal(r_on.mem_counters[k],
                                          r_off.mem_counters[k], err_msg=k)
        assert r_on.n_quanta == r_off.n_quanta
        assert r_off.telemetry is None
        tl = r_on.telemetry
        assert isinstance(tl, Timeline)
        assert len(tl) > 0 and not tl.wrapped
        assert tl.data.shape[1] == sim.telemetry_spec.n_series
        # Simulator.telemetry reads the same state
        np.testing.assert_array_equal(sim.telemetry.data, tl.data)
        # the final row is the completion sample: its time is the run's
        # completion time, and the delta series sum to the run totals
        assert int(tl.col("time_ps")[-1]) == r_on.completion_time_ps
        assert int(tl.col("instructions").sum()) == r_on.total_instructions
        assert int(tl.col("quanta").sum()) == r_on.n_quanta

    def test_rows_match_chunked_oracle(self):
        """Sample-boundary correctness: step the SAME sim quantum by
        quantum from the host (run_chunk(1)), difference the fetched
        counters by hand, and require the device rows to match
        exactly."""
        batch = _trace()
        series = ("quanta", "instructions", "packets_sent",
                  "clock_min_ps", "clock_max_ps", "clock_mean_ps",
                  "l2_misses", "skip_requester")
        interval = 1_500_000   # 1.5 quanta — forces skipped boundaries
        simt = Simulator(_config(), batch,
                         telemetry=_spec(interval=interval, series=series))
        tl = simt.run().telemetry
        order = simt.telemetry_spec.series

        ref = Simulator(_config(), batch)
        prev = np.zeros(len(order), np.int64)
        next_ps = interval
        quanta = 0
        rows = []
        for _ in range(10_000):
            done, nq = ref.run_chunk(1)
            quanta += nq
            st = ref.state
            clocks, done_mask, instr, sent, mc, skips = jax.device_get(
                (st.core.clock_ps, st.done, st.core.instruction_count,
                 st.net.packets_sent, st.mem.counters.l2_misses,
                 st.mem.phase_skips))
            pending = clocks[~done_mask]
            sim_time = int(pending.min() if pending.size else clocks.max())
            cur = {
                "time_ps": sim_time,
                "quanta": quanta,
                "instructions": int(instr.sum()),
                "packets_sent": int(sent.sum()),
                "clock_min_ps": int(clocks.min()),
                "clock_max_ps": int(clocks.max()),
                "clock_mean_ps": int(clocks.sum()) // TILES,
                "l2_misses": int(mc.sum()),
                "skip_requester": int(skips[0]),
            }
            cur = np.array([cur[s] for s in order], np.int64)
            if sim_time >= next_ps or done:
                delta = np.array(
                    [c if s in LEVEL_SERIES else c - p
                     for s, c, p in zip(order, cur, prev)], np.int64)
                rows.append(delta)
                prev = cur
                next_ps = (sim_time // interval + 1) * interval
            if done:
                break
        assert done
        np.testing.assert_array_equal(tl.data, np.array(rows))

    def test_ring_wraparound_keeps_last_samples(self):
        batch = _trace()
        big = Simulator(_config(), batch, telemetry=_spec(s=64))
        tl_big = big.run().telemetry
        assert tl_big.n_total > 2   # the run takes > 2 samples
        small = Simulator(_config(), batch, telemetry=_spec(s=2))
        tl = small.run().telemetry
        assert tl.wrapped and tl.n_total == tl_big.n_total
        assert len(tl) == 2
        np.testing.assert_array_equal(tl.data, tl_big.data[-2:])

    def test_barrier_host_dispatch_records_identically(self):
        """The batched host-barrier dispatch path samples the same
        timeline as the single-region device loop (the sampling cursor
        rides the carry across dispatches)."""
        batch = _trace()
        tl_dev = Simulator(_config(), batch,
                           telemetry=_spec()).run().telemetry
        sim_hb = Simulator(_config(), batch, barrier_host=True,
                           barrier_batch=2, telemetry=_spec())
        tl_hb = sim_hb.run().telemetry
        assert tl_hb.n_total == tl_dev.n_total
        np.testing.assert_array_equal(tl_hb.data, tl_dev.data)

    def test_save_load_roundtrip_and_report(self, tmp_path, capsys):
        import json

        from graphite_tpu.tools.report import main as report_main

        tl = Simulator(_config(), _trace(),
                       telemetry=_spec()).run().telemetry
        path = str(tmp_path / "tl.npz")
        tl.save(path)
        back = Timeline.load(path)
        assert back.series == tl.series
        assert back.n_total == tl.n_total
        np.testing.assert_array_equal(back.data, tl.data)

        assert report_main([path]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        assert len(lines) == len(tl) + 1   # rows + summary
        assert lines[-1]["samples"] == len(tl)
        assert report_main([path, "--format", "text", "--summary"]) == 0
        assert "mean_clock_spread_ps" in capsys.readouterr().out


class TestEnergySeries:
    """The round-14 `energy_pj` series: cumulative event energy priced
    from the carry's own counters (opt-in via EnergyPrices — the dense
    default selection, and every locked program, is unchanged)."""

    PRICES = None   # built lazily (EnergyPrices import at class scope)

    def _prices(self):
        from graphite_tpu.obs import EnergyPrices

        return EnergyPrices(
            instruction_pj=3, l1i_access_pj=1, l1d_access_pj=2,
            l2_access_pj=9, l2_miss_pj=120, invalidation_pj=15,
            eviction_pj=20, dram_access_pj=500, packet_pj=7)

    def _energy_of(self, instr, sent, mc):
        """The hand-stepped power-model sum: every counter priced by
        the same pJ table the device row folds in."""
        return (3 * int(instr.sum()) + 7 * int(sent.sum())
                + 1 * int(mc.l1i_hits.sum() + mc.l1i_misses.sum())
                + 2 * int(mc.l1d_read_hits.sum()
                          + mc.l1d_read_misses.sum()
                          + mc.l1d_write_hits.sum()
                          + mc.l1d_write_misses.sum())
                + 9 * int(mc.l2_hits.sum() + mc.l2_misses.sum())
                + 120 * int(mc.l2_misses.sum())
                + 15 * int(mc.invalidations.sum())
                + 20 * int(mc.evictions.sum())
                + 500 * int(mc.dram_reads.sum()
                            + mc.dram_writes.sum()))

    def test_energy_rows_match_hand_stepped_power_sum(self):
        """Oracle: step the same sim quantum by quantum from the host,
        price the fetched counters by hand, difference, and require the
        device energy column to match exactly — and the telemetry run's
        SimResults to stay bit-equal to the plain run's."""
        batch = _trace()
        spec = _spec(series=("instructions", "energy_pj"))
        spec = TelemetrySpec(
            sample_interval_ps=spec.sample_interval_ps,
            n_samples=spec.n_samples, series=spec.series,
            energy_prices=self._prices())
        simt = Simulator(_config(), batch, telemetry=spec)
        res = simt.run()
        tl = res.telemetry
        assert tl.series == ("time_ps", "instructions", "energy_pj")

        ref = Simulator(_config(), batch)
        prev_e = 0
        rows = []
        interval = QUANTUM_PS
        next_ps = interval
        for _ in range(10_000):
            done, _ = ref.run_chunk(1)
            st = ref.state
            clocks, done_mask, instr, sent = jax.device_get(
                (st.core.clock_ps, st.done, st.core.instruction_count,
                 st.net.packets_sent))
            mc = jax.device_get(st.mem.counters)
            pending = clocks[~done_mask]
            sim_time = int(pending.min() if pending.size
                           else clocks.max())
            cur_e = self._energy_of(instr, sent, mc)
            if sim_time >= next_ps or done:
                rows.append(cur_e - prev_e)
                prev_e = cur_e
                next_ps = (sim_time // interval + 1) * interval
            if done:
                break
        assert done
        np.testing.assert_array_equal(tl.col("energy_pj"),
                                      np.array(rows, np.int64))
        # pure observability: the priced run's results are bit-equal
        r_off = Simulator(_config(), batch).run()
        np.testing.assert_array_equal(res.clock_ps, r_off.clock_ps)
        for k in r_off.mem_counters:
            np.testing.assert_array_equal(
                res.mem_counters[k], r_off.mem_counters[k], err_msg=k)

    def test_telemetry_off_lint_covers_energy_ring(self):
        """Telemetry-OFF specs carry the dense-plus-energy ring sig
        (one series wider), and the aval scan fires on a program that
        materializes it."""
        from graphite_tpu.analysis.audit import spec_from_simulator

        sim = Simulator(_config(), _trace())
        spec = spec_from_simulator("off", sim, max_quanta=512)
        assert spec.telemetry_extra_sigs
        (S, n), dt = spec.telemetry_sig
        assert spec.telemetry_extra_sigs[0] == ((S, n + 1), dt)

        def bad(x):
            buf = jnp.zeros((S, n + 1), jnp.int64)
            return buf.at[0, 0].set(x)

        closed = jax.make_jaxpr(bad)(jnp.asarray(1, jnp.int64))
        fs = rules.telemetry_off(closed, ["x"],
                                 ring_sigs=spec.telemetry_extra_sigs)
        assert fs and fs[0].data["shape"] == [S, n + 1]
        # ... and the real telemetry-off program still passes with the
        # widened sig set (no false positive from the extra aval)
        assert not rules.telemetry_off(
            spec.closed, spec.invar_paths,
            ring_sigs=(spec.telemetry_sig,) + spec.telemetry_extra_sigs)

    def test_energy_program_passes_audit(self):
        """An energy-recording program clears every lint: the widened
        ring rides no cond, no host sync, gates intact."""
        from graphite_tpu.analysis.audit import audit, \
            spec_from_simulator

        spec_tel = TelemetrySpec(sample_interval_ps=QUANTUM_PS,
                                 n_samples=32,
                                 energy_prices=self._prices())
        simt = Simulator(_config(), _trace(), phase_gate=True,
                         mem_gate_bytes=0, telemetry=spec_tel)
        spec = spec_from_simulator("tel-energy", simt, max_quanta=512)
        assert spec.expect_telemetry
        report = audit([spec])
        assert report.ok, [str(f) for f in report.errors]


class TestSweepDemux:
    def test_vmap_campaign_demuxes_per_sim_timelines(self):
        from graphite_tpu.sweep import SweepRunner

        seeds = (1, 2, 3)
        traces = [_trace(seed=s) for s in seeds]
        sweep = SweepRunner(_config(), traces, shard_batch=False,
                            telemetry=_spec())
        out = sweep.run()
        assert out.timelines is not None and len(out.timelines) == 3
        n_series = sweep.sim.telemetry_spec.n_series
        for b in range(3):
            tl = out.timelines[b]
            assert tl.data.shape[1] == n_series
            assert out.results[b].telemetry is tl
            # bit-identical to this sim's own sequential telemetry run,
            # but for the skip_* series and the iterations: the vmapped
            # program's gates and its block's exit are keyed on the OR
            # over its sims, so it runs no fewer iterations than the
            # sim's own gated run, and each phase in no fewer of them
            solo = Simulator(_config(), traces[b],
                             mailbox_depth=sweep.mailbox_depth,
                             mem_gate_bytes=0,
                             telemetry=_spec()).run().telemetry
            assert tl.n_total == solo.n_total
            own = np.array([not counts_the_program(n) for n in tl.series])
            np.testing.assert_array_equal(tl.data[:, own], solo.data[:, own],
                                          err_msg=f"sim {b}")
            skips = np.array([n.startswith(SKIP_PREFIX)
                              for n in tl.series])
            assert skips.any() and tl.data[:, skips].any()
            it = tl.series.index("iterations")
            assert tl.data[:, it].sum() >= solo.data[:, it].sum()
            ran, ran_solo = (t.data[:, it].sum() - t.data[:, skips].sum(0)
                             for t in (tl, solo))
            assert (ran >= ran_solo).all(), (ran, ran_solo)

    def test_shard_map_campaign_gathers_device_buffers(self):
        from graphite_tpu.sweep import SweepRunner

        if len(jax.devices()) < 2:
            pytest.skip("needs the multi-device CPU platform")
        B = len(jax.devices())
        traces = [_trace(seed=s) for s in range(B)]
        sweep = SweepRunner(_config(), traces, shard_batch=True,
                            telemetry=_spec())
        out = sweep.run()
        assert len(out.timelines) == B
        for b in (0, B - 1):
            # one sim per device runs the plain gated program
            solo = Simulator(_config(), traces[b],
                             mailbox_depth=sweep.mailbox_depth,
                             telemetry=_spec()).run().telemetry
            assert out.timelines[b].n_total == solo.n_total
            np.testing.assert_array_equal(out.timelines[b].data,
                                          solo.data, err_msg=f"sim {b}")


class TestStatisticsBackends:
    STATS = """
[statistics_trace]
enabled = true
statistics = network_utilization
sampling_interval = 500
"""

    def _traces_equal(self, d1, d2):
        import os

        f1 = sorted(os.listdir(d1))
        f2 = sorted(os.listdir(d2))
        assert f1 == f2 and f1, (f1, f2)
        for name in f1:
            a = open(os.path.join(d1, name)).read()
            b = open(os.path.join(d2, name)).read()
            assert a == b, f"{name} differs:\n--- chunked\n{a}\n--- device\n{b}"

    def test_device_backend_matches_chunked_files(self, tmp_path):
        from graphite_tpu.system.statistics import StatisticsManager

        batch = _trace()
        m_ch = StatisticsManager(
            Simulator(_config(self.STATS), batch),
            output_dir=str(tmp_path / "chunked"), backend="chunked")
        r_ch = m_ch.run()
        m_dev = StatisticsManager(
            Simulator(_config(self.STATS), batch),
            output_dir=str(tmp_path / "device"), backend="device")
        r_dev = m_dev.run()
        assert r_dev.n_quanta == r_ch.n_quanta
        np.testing.assert_array_equal(r_dev.clock_ps, r_ch.clock_ps)
        self._traces_equal(str(tmp_path / "chunked"),
                           str(tmp_path / "device"))

    def test_device_backend_matches_chunked_user_net(self, tmp_path):
        """A SEND-carrying memoryless trace exercises the USER-network
        injection rows with nonzero rates."""
        from graphite_tpu.system.statistics import StatisticsManager

        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, clock_scheme="lax_barrier") + self.STATS))
        batch = synthetic.message_ring_batch(TILES, n_rounds=6,
                                             compute_per_round=16)
        m_ch = StatisticsManager(Simulator(sc, batch),
                                 output_dir=str(tmp_path / "chunked"),
                                 backend="chunked")
        m_ch.run()
        m_dev = StatisticsManager(Simulator(sc, batch),
                                  output_dir=str(tmp_path / "device"),
                                  backend="device")
        m_dev.run()
        rows = open(tmp_path / "device" /
                    "network_utilization_user.trace").read()
        assert any(float(ln.split()[1]) > 0
                   for ln in rows.strip().splitlines())
        self._traces_equal(str(tmp_path / "chunked"),
                           str(tmp_path / "device"))

    def test_auto_falls_back_for_state_snapshot_stats(self):
        from graphite_tpu.system.statistics import StatisticsManager

        stats = self.STATS.replace(
            "statistics = network_utilization",
            "statistics = cache_line_replication, network_utilization")
        m = StatisticsManager(Simulator(_config(stats), _trace()))
        assert m.backend == "auto" and not m.device_supported()
        with pytest.raises(ValueError, match="counter-derived"):
            StatisticsManager(Simulator(_config(stats), _trace()),
                              backend="device")

    def test_auto_falls_back_for_meshed_sims(self):
        """A meshed sim must keep the chunked loop under backend='auto'
        even when every enabled statistic is counter-derived — the
        telemetry ring is not threaded through the multi-chip
        exchange, and attach_telemetry would raise."""
        from graphite_tpu.parallel.mesh import make_tile_mesh
        from graphite_tpu.system.statistics import StatisticsManager

        if len(jax.devices()) < 2:
            pytest.skip("needs the multi-device CPU platform")
        sim = Simulator(_config(self.STATS), _trace(),
                        mesh=make_tile_mesh(len(jax.devices())))
        m = StatisticsManager(sim)
        assert m.backend == "auto" and not m.device_supported()
