"""DVFS manager tests: V/f tables, rc codes, in-trace frequency scaling.

Mirrors the reference unit tests `tests/unit/dvfs_basic`, `dvfs_error_codes`
and `frequency_scaling_simple`: AUTO picks the minimum voltage for a
frequency, HOLD fails above the current voltage's maximum, invalid
tile/domain/frequency return the `dvfs.h` rc codes, and a frequency change
rescales subsequent instruction costs.
"""

import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine import Simulator
from graphite_tpu.models import dvfs as dv
from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder


def make_config(n_tiles=2, max_freq="2.0"):
    text = f"""
[general]
total_cores = {n_tiles}
mode = lite
max_frequency = {max_freq}
technology_node = 22
[dvfs]
synchronization_delay = 2
domains = "<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE, DIRECTORY> \
<1.0, NETWORK_USER, NETWORK_MEMORY>"
[network]
user = magic
memory = magic
[core/static_instruction_costs]
ialu = 1
[clock_skew_management]
scheme = lax
"""
    return SimConfig(ConfigFile.from_string(text))


def run_sim(sc, builders):
    sim = Simulator(sc, TraceBatch.from_builders(builders))
    return sim, sim.run()


class TestLevels:
    def test_min_voltage_auto(self):
        p = dv.DvfsParams.from_config(make_config().cfg)
        # max_frequency = 2 GHz: 2000 MHz needs 1.0 V; 0.5*2000=1000 runs
        # at factor 0.5 -> 0.84 V; 0.37*2000=740 at 0.8 V
        assert p.min_voltage_mv(2000) == 1000
        assert p.min_voltage_mv(1000) == 840
        assert p.min_voltage_mv(700) == 800
        assert p.min_voltage_mv(2001) == -1

    def test_initial_voltage_matches_domain_freq(self):
        sc = make_config()
        sim = Simulator(sc, TraceBatch.from_builders(
            [TraceBuilder().instr(Op.IALU), TraceBuilder()]))
        man = dv.DVFSManager(sim)
        rc, f, v = man.get_dvfs(0, 0)
        assert rc == dv.RC_OK
        assert f == pytest.approx(1.0)
        assert v == pytest.approx(0.84)  # 1 GHz at factor 0.5 of 2 GHz


class TestErrorCodes:
    def test_reference_rc_codes(self):
        """dvfs_error_codes.cc sequence."""
        sc = make_config()
        sim = Simulator(sc, TraceBatch.from_builders(
            [TraceBuilder().instr(Op.IALU), TraceBuilder()]))
        man = dv.DVFSManager(sim)
        assert man.get_dvfs(-1, 0)[0] == dv.RC_INVALID_TILE
        assert man.get_dvfs(0, 99)[0] == dv.RC_INVALID_DOMAIN
        assert man.set_dvfs(0, 0, 0.0) == dv.RC_INVALID_FREQUENCY
        assert man.set_dvfs(0, 0, 1.0, voltage_flag=5) == \
            dv.RC_INVALID_VOLTAGE_OPTION
        assert man.set_dvfs(0, 0, 100.0) == dv.RC_INVALID_FREQUENCY
        # drop to a low voltage, then HOLD a too-fast frequency
        assert man.set_dvfs(0, 0, 0.1) == dv.RC_OK
        assert man.set_dvfs(0, 0, 2.0, dv.HOLD) == \
            dv.RC_ABOVE_MAX_FOR_VOLTAGE

    def test_basic_set_get(self):
        """dvfs_basic.cc: AUTO then HOLD round trip."""
        sc = make_config()
        sim = Simulator(sc, TraceBatch.from_builders(
            [TraceBuilder().instr(Op.IALU), TraceBuilder()]))
        man = dv.DVFSManager(sim)
        assert man.set_dvfs(0, 0, 2.0) == dv.RC_OK
        rc, f, v = man.get_dvfs(0, 0)
        assert (f, v) == (pytest.approx(2.0), pytest.approx(1.0))
        assert man.set_dvfs(0, 0, 1.0, dv.HOLD) == dv.RC_OK
        rc, f, v = man.get_dvfs(0, 0)
        assert (f, v) == (pytest.approx(1.0), pytest.approx(1.0))  # held


class TestInTraceScaling:
    def test_frequency_change_rescales_costs(self):
        """frequency_scaling_simple analog: 4 ialu at 1 GHz, retune to
        2 GHz, 4 more: 4*1000 + 4*500 ps."""
        b = TraceBuilder()
        for _ in range(4):
            b.instr(Op.IALU)
        b.dvfs_set(0, 2000)
        for _ in range(4):
            b.instr(Op.IALU)
        sim, r = run_sim(make_config(), [b, TraceBuilder()])
        assert r.clock_ps[0] == 4000 + 2000
        assert int(np.asarray(sim.state.dvfs.errors).sum()) == 0
        assert int(np.asarray(sim.state.dvfs.voltage_mv)[0, 0]) == 1000

    def test_invalid_in_trace_set_counts_error(self):
        b = TraceBuilder()
        b.instr(Op.IALU)
        b.dvfs_set(0, 5000)        # > 2 GHz max: rejected
        b.instr(Op.IALU)
        sim, r = run_sim(make_config(), [b, TraceBuilder()])
        assert r.clock_ps[0] == 2000   # frequency unchanged
        assert int(np.asarray(sim.state.dvfs.errors)[0]) == 1

    def test_hold_in_trace_fails_above_voltage_max(self):
        b = TraceBuilder()
        b.dvfs_set(0, 740)             # AUTO: drops voltage to 0.8 V
        b.dvfs_set(0, 2000, hold=True)  # exceeds 0.8 V max: rejected
        b.instr(Op.IALU)
        sim, r = run_sim(make_config(), [b, TraceBuilder()])
        # still at 740 MHz: one ialu = ceil cycle at 740 MHz
        assert int(np.asarray(sim.state.dvfs.errors)[0]) == 1
        assert int(np.asarray(sim.state.dvfs.freq_mhz)[0, 0]) == 740

    def test_non_core_domain_set_tracked(self):
        b = TraceBuilder()
        b.dvfs_set(1, 1500)            # NETWORK domain
        b.instr(Op.IALU)
        sim, r = run_sim(make_config(), [b, TraceBuilder()])
        assert r.clock_ps[0] == 1000   # core frequency untouched
        assert int(np.asarray(sim.state.dvfs.freq_mhz)[0, 1]) == 1500


class TestLevelTableValidation:
    """`dvfs.levels.validate_levels`: the monotone V-per-f contract."""

    def test_valid_table_passes(self):
        from graphite_tpu.dvfs import validate_levels

        validate_levels((1000, 840, 800), (2000, 1000, 740))

    @pytest.mark.parametrize("volts,freqs,msg", [
        ((1000, 840), (2000,), "length mismatch"),
        ((), (), "empty"),
        ((1000, 0), (2000, 1000), "positive"),
        ((1000, -5), (2000, 1000), "positive"),
        ((1000, 840), (2000, 0), "positive"),
        ((840, 1000), (1000, 2000), "descending"),
        ((1000, 1000), (2000, 1000), "descending"),
        ((1000, 840), (1000, 2000), "monotone"),
    ])
    def test_invalid_tables_raise(self, volts, freqs, msg):
        from graphite_tpu.dvfs import validate_levels

        with pytest.raises(ValueError, match=msg):
            validate_levels(volts, freqs)

    def test_energy_scale_q16_hand_rows(self):
        """V²·f factor vs hand-computed Q16 rows (ref = level 0)."""
        import jax.numpy as jnp

        from graphite_tpu.dvfs import energy_scale_q16

        p = dv.DvfsParams.from_config(make_config().cfg)
        # ref point: 1000 mV, 2000 MHz.  Hand Q16 per stage:
        #   (mv²·256 // ref_mv²) * (f·256 // ref_f)
        sc = energy_scale_q16(
            p, jnp.asarray([2000, 1000, 740]), jnp.asarray(
                [1000, 840, 800]))
        v = np.asarray(sc)
        assert v[0] == 256 * 256                   # table top: exactly 1.0
        assert v[1] == ((840 * 840 * 256) // (1000 * 1000)) \
            * ((1000 * 256) // 2000)               # 180 * 128
        assert v[2] == ((800 * 800 * 256) // (1000 * 1000)) \
            * ((740 * 256) // 2000)                # 163 * 94


def _mem_config(sync_delay, domains):
    from graphite_tpu.tools._template import config_text

    return SimConfig(ConfigFile.from_string(
        config_text(4, shared_mem=True, clock_scheme="lax")
        + f"""
[general]
technology_node = 22
[dvfs]
max_frequency = 1.0
synchronization_delay = {sync_delay}
domains = "{domains}"
"""))


_SPLIT = ("<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE>, "
          "<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")
_FLAT = ("<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE, DIRECTORY, "
         "NETWORK_USER, NETWORK_MEMORY>")


def _mem_trace():
    from graphite_tpu.trace import synthetic

    return synthetic.memory_stress_trace(
        4, n_accesses=10, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=11)


class TestSyncDelayTransitions:
    """Boundary-crossing synchronization delay: charged in BOTH
    directions of an L2<->network handoff (`MemParams.sync_cycles` is
    symmetric in its module pair), live when the domain split is real,
    a Python 0 when it is not."""

    def test_multi_domain_delay_slows_and_knob_matches_config(self):
        batch = _mem_trace()
        r0 = Simulator(_mem_config(0, _SPLIT), batch).run()
        r8 = Simulator(_mem_config(8, _SPLIT), batch).run()
        assert int(r8.completion_time_ps) > int(r0.completion_time_ps)

        # the traced knob reproduces each constant-folded config
        # bit-for-bit — the round-8 "structurally inert" finding is
        # closed only if this holds on a GENUINE multi-domain split
        from graphite_tpu.sweep import SweepRunner

        out = SweepRunner(_mem_config(0, _SPLIT), [batch, batch],
                          [{"sync_delay_cycles": 0},
                           {"sync_delay_cycles": 8}],
                          shard_batch=False).run()
        for res, ref in zip(out.results, (r0, r8)):
            assert np.array_equal(np.asarray(res.clock_ps),
                                  np.asarray(ref.clock_ps))

    def test_single_domain_delay_inert(self):
        batch = _mem_trace()
        r0 = Simulator(_mem_config(0, _FLAT), batch).run()
        r8 = Simulator(_mem_config(8, _FLAT), batch).run()
        assert np.array_equal(np.asarray(r0.clock_ps),
                              np.asarray(r8.clock_ps))


class TestGoldenEquality:
    """Engine vs the hand-stepped golden interpreter with in-trace
    retunes (fixed frequency after the set), at unit-test size."""

    def test_fixed_frequency_and_retune_match_golden(self):
        from graphite_tpu.golden.interpreter import run_golden

        sc = make_config()
        b0 = TraceBuilder()
        b0.dvfs_set(0, 2000)
        for _ in range(4):
            b0.instr(Op.IALU)
        b1 = TraceBuilder()
        for _ in range(4):
            b1.instr(Op.IALU)
        b1.dvfs_set(0, 5000)       # rejected: above table max
        b1.dvfs_set(0, 740)
        for _ in range(2):
            b1.instr(Op.IALU)
        batch = TraceBatch.from_builders([b0, b1])
        sim = Simulator(sc, batch)
        r = sim.run()
        g = run_golden(sc, batch)
        assert np.array_equal(np.asarray(r.clock_ps), g.clock_ps)
        assert np.array_equal(np.asarray(r.instruction_count),
                              g.instruction_count)
        assert np.array_equal(np.asarray(sim.state.dvfs.errors),
                              g.dvfs_errors)
        assert g.core_freq_mhz.tolist() == [2000, 740]


class TestRuntimeSpec:
    """The chip-global `DvfsSpec` (dvfs/runtime.py) on two tiles."""

    def _builders(self):
        """Compute with in-trace retunes, for the governor to act on."""
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

    def test_spec_at_the_configs_frequencies_is_the_folded_engine(self):
        """Carried frequency is mechanism, not policy: a `DvfsSpec` at
        the config's own domain frequencies changes no statistic (of a
        trace without retunes: the in-trace path is the per-tile one)."""
        from graphite_tpu.dvfs import DvfsSpec

        sc = make_config()
        b0, b1 = TraceBuilder(), TraceBuilder()
        for _ in range(6):
            b0.instr(Op.IALU)
        b0.send(1, 8)
        b1.recv(0, 8)
        for _ in range(3):
            b1.instr(Op.IALU)
        batch = TraceBatch.from_builders([b0, b1])
        folded = Simulator(sc, batch).run()
        carried = Simulator(sc, batch, dvfs=DvfsSpec()).run()
        np.testing.assert_array_equal(carried.clock_ps, folded.clock_ps)
        np.testing.assert_array_equal(carried.instruction_count,
                                      folded.instruction_count)
        assert carried.n_quanta == folded.n_quanta

    def test_governor_is_deterministic(self):
        """Two fresh engines under the reactive governor agree bit for bit
        on the results AND on the final per-domain V/f state."""
        from graphite_tpu.dvfs import DvfsSpec, GovernorSpec

        gv = DvfsSpec(governor=GovernorSpec(interval_ps=2000, domains=(0,)))
        runs = []
        for _ in range(2):
            sim = Simulator(make_config(),
                            TraceBatch.from_builders(self._builders()),
                            dvfs=gv)
            res = sim.run()
            runs.append((res, np.asarray(sim.state.dvfs_rt.domain_mhz),
                         np.asarray(sim.state.dvfs_rt.domain_mv)))
        (ra, fa, va), (rb, fb, vb) = runs
        np.testing.assert_array_equal(ra.clock_ps, rb.clock_ps)
        np.testing.assert_array_equal(ra.instruction_count,
                                      rb.instruction_count)
        np.testing.assert_array_equal(fa, fb)
        np.testing.assert_array_equal(va, vb)


class TestEnergyPricing:
    """V²·f-scaled event pricing vs hand-computed rows."""

    def _run(self, prefix_freq=None, dvfs=None):
        from graphite_tpu.obs import EnergyPrices, TelemetrySpec

        b = TraceBuilder()
        if prefix_freq is not None:
            b.dvfs_set(0, prefix_freq)
        for _ in range(8):
            b.instr(Op.IALU)
        tel = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=16,
                            energy_prices=EnergyPrices(instruction_pj=3))
        sim = Simulator(make_config(), TraceBatch.from_builders(
            [b, TraceBuilder()]), telemetry=tel, dvfs=dvfs)
        r = sim.run()
        return int(r.telemetry.col("energy_pj").sum())

    def test_unscaled_baseline(self):
        assert self._run() == 8 * 3

    def test_scaled_at_table_top_is_identity(self):
        """2000 MHz @ 1000 mV is the prices' reference point: the
        scaled series reproduces the unscaled one exactly."""
        from graphite_tpu.dvfs import DvfsSpec

        assert self._run(prefix_freq=2000, dvfs=DvfsSpec()) == 8 * 3

    def test_scaled_at_half_frequency_hand_row(self):
        """1 GHz @ 840 mV: (8·3 · (840²·256//1000²)·(1000·256//2000))
        >> 16 = (24 · 180·128) >> 16 = 8 pJ."""
        from graphite_tpu.dvfs import DvfsSpec

        assert self._run(dvfs=DvfsSpec()) == (24 * 180 * 128) >> 16

    def test_scale_energy_false_keeps_raw_prices(self):
        from graphite_tpu.dvfs import DvfsSpec

        assert self._run(dvfs=DvfsSpec(scale_energy=False)) == 8 * 3


class TestSweepKnob:
    """`dvfs_domain_mhz` as a traced campaign axis: the B-wide grid is
    bit-equal to sequential runs pinned at each operating point."""

    def test_grid_matches_sequential(self):
        from graphite_tpu.dvfs import DvfsSpec
        from graphite_tpu.sweep import SweepRunner

        sc = make_config()

        def mk():
            b = TraceBuilder()
            for _ in range(6):
                b.instr(Op.IALU)
            return [b, TraceBuilder()]

        grid = ((2000, 2000), (1000, 2000), (740, 740))
        traces = [TraceBatch.from_builders(mk()) for _ in grid]
        sweep = SweepRunner(sc, traces,
                            [{"dvfs_domain_mhz": p} for p in grid],
                            shard_batch=False, dvfs=DvfsSpec())
        out = sweep.run()
        for i, p in enumerate(grid):
            solo = Simulator(sc, traces[i],
                             mailbox_depth=sweep.mailbox_depth)
            solo.attach_dvfs(DvfsSpec(), domain_mhz=p)
            ref = solo.run()
            assert np.array_equal(np.asarray(out.results[i].clock_ps),
                                  np.asarray(ref.clock_ps)), p

    def test_knob_requires_spec(self):
        from graphite_tpu.sweep import SweepRunner

        sc = make_config()
        b = TraceBuilder()
        b.instr(Op.IALU)
        with pytest.raises(ValueError, match="dvfs"):
            SweepRunner(sc, [TraceBatch.from_builders(
                [b, TraceBuilder()])],
                [{"dvfs_domain_mhz": (1000, 1000)}], shard_batch=False)


class TestServeClassKey:
    """`Job.dvfs` joins the admission class key: spec splits, knob
    points co-batch."""

    def test_dvfs_splits_and_points_share(self):
        from graphite_tpu.dvfs import DvfsSpec
        from graphite_tpu.serve import Job
        from graphite_tpu.serve.admission import AdmissionController

        sc = make_config()

        def mk():
            b = TraceBuilder()
            for _ in range(4):
                b.instr(Op.IALU)
            return TraceBatch.from_builders([b, TraceBuilder()])

        ctrl = AdmissionController()
        k_plain = ctrl.class_key(Job("plain", sc, mk()))
        k_dvfs = ctrl.class_key(Job("dv", sc, mk(), dvfs=DvfsSpec()))
        k_dvfs2 = ctrl.class_key(Job(
            "dv2", sc, mk(), dvfs=DvfsSpec(),
            knobs={"dvfs_domain_mhz": (1000, 1000)}))
        assert k_plain != k_dvfs          # spec splits the class
        assert k_dvfs == k_dvfs2          # the knob point does NOT


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
