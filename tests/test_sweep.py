"""Batched simulation campaigns (graphite_tpu/sweep/): trace packing,
per-sim bit-equality of the vmapped program against sequential runs, and
recompile-free knob tracing.

The two contract pins:
 - a B=8 same-geometry sweep is BIT-IDENTICAL per-sim to 8 sequential
   Simulator runs (clocks + memory counters + quanta) — vmap's
   while_loop batching rule select-freezes finished sims, so batching
   changes wall-clock shape only, never results;
 - one jax.jit lowering serves a >= 4-point timing-knob grid with zero
   recompiles (compile-count probe), and each traced-knob point matches
   a run with the same values baked statically into the params.
"""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.sweep import (
    Knobs, SweepRunner, grid_points, pack_traces,
)
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.schema import NO_REG, Op, TraceBatch, TraceBuilder


TILES = 8
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config(clock="lax"):
    return SimConfig(ConfigFile.from_string(config_text(
        TILES, shared_mem=True, clock_scheme=clock)))


def _trace(seed, n=16):
    return synthetic.memory_stress_trace(
        TILES, n_accesses=n, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=seed)


def _assert_results_equal(ra, rb, msg=""):
    np.testing.assert_array_equal(ra.clock_ps, rb.clock_ps, err_msg=msg)
    np.testing.assert_array_equal(
        ra.instruction_count, rb.instruction_count, err_msg=msg)
    assert ra.n_quanta == rb.n_quanta, msg
    assert (ra.mem_counters is None) == (rb.mem_counters is None), msg
    if ra.mem_counters is not None:
        for k in ra.mem_counters:
            np.testing.assert_array_equal(
                ra.mem_counters[k], rb.mem_counters[k],
                err_msg=f"{msg}: {k}")


class TestPack:
    def test_pads_to_common_layout_and_roundtrips(self):
        traces = [_trace(s, n) for s, n in ((1, 8), (2, 16), (3, 12))]
        pack = pack_traces(traces, seeds=[1, 2, 3])
        assert pack.n_sims == 3 and pack.n_tiles == TILES
        assert pack.length == max(t.length for t in traces)
        assert pack.lengths.tolist() == [t.length for t in traces]
        assert pack.seeds.tolist() == [1, 2, 3]
        for b, t in enumerate(traces):
            back = pack.sim(b)
            # original records bit-exact; the tail is inert NOP padding
            for f in pack._TRACE_FIELDS:
                np.testing.assert_array_equal(
                    getattr(back, f)[:, : t.length], getattr(t, f),
                    err_msg=f"sim {b} field {f}")
            assert (back.op[:, t.length:] == int(Op.NOP)).all()
            assert (back.rreg0[:, t.length:] == NO_REG).all()
            assert (back.dyn_ps[:, t.length:] == 0).all()

    def test_rejects_mixed_geometry(self):
        other = synthetic.memory_stress_trace(
            TILES * 2, n_accesses=8, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.5, seed=1)
        with pytest.raises(ValueError, match="tile count"):
            pack_traces([_trace(1), other])

    def test_replicate(self):
        pack = pack_traces([_trace(5)]).replicate(3)
        assert pack.n_sims == 3
        np.testing.assert_array_equal(pack.op[0], pack.op[2])


class TestKnobs:
    def test_grid_points_cross_product(self):
        pts = grid_points(dram_latency_ns=[50, 100],
                          hop_latency_cycles=[1, 2, 3])
        assert len(pts) == 6
        assert pts[0] == {"dram_latency_ns": 50, "hop_latency_cycles": 1}
        assert pts[-1] == {"dram_latency_ns": 100, "hop_latency_cycles": 3}

    def test_unknown_knob_rejected(self):
        with pytest.raises(ValueError, match="unknown knob"):
            grid_points(dram_latency=[1])
        base = Knobs.from_params(Simulator(_config(), _trace(1)).params, 0)
        with pytest.raises(ValueError, match="unknown knob"):
            Knobs.stack(base, [{"nope": 3}])

    def test_from_params_reads_static_values(self):
        sim = Simulator(_config("lax_barrier"), _trace(1))
        kn = Knobs.from_params(sim.params, sim.quantum_ps)
        mp = sim.params.mem
        assert int(kn.dram_latency_ns) == mp.dram_latency_ns
        assert int(kn.dir_access_cycles) == mp.dir_access_cycles
        assert int(kn.hop_latency_cycles) == mp.hop_latency_cycles
        assert int(kn.sync_delay_cycles) == mp.sync_delay_cycles
        assert int(kn.quantum_ps) == sim.quantum_ps


@pytest.fixture(scope="module")
def b8_sequential_refs():
    """8 sequential Simulator runs of the B=8 campaign traces (shared by
    both batching-program variants below)."""
    from graphite_tpu.engine.simulator import auto_mailbox_depth

    sc = _config("lax")
    traces = [_trace(seed) for seed in range(1, 9)]
    depth = max(auto_mailbox_depth(t) for t in traces)
    refs = [Simulator(sc, t, mailbox_depth=depth).run() for t in traces]
    return sc, traces, depth, refs


class TestSweepEqualsSequential:
    # the forced-vmap B=8 variant is `slow` (one extra B=8-wide compile):
    # the vmap select-freeze mechanism is already tier-1-pinned at B=2 by
    # test_vmapped_knob_grid_matches_sequential_static and at B=4 by
    # TestActivityGatesUnderTheSimAxis[solo_vmap]; tier-1 pins B=8
    # through the runner's actual program choice
    @pytest.mark.parametrize(
        "shard",
        [None, pytest.param(False, marks=pytest.mark.slow)],
        ids=["auto_shard", "vmap"])
    def test_b8_bit_identical_to_sequential_runs(
            self, b8_sequential_refs, shard):
        """The acceptance pin: a B=8 same-geometry sweep == 8 sequential
        Simulator runs, bit-exact (clocks + memory counters + quanta) —
        for BOTH batching programs: batch-axis shard_map (auto under the
        suite's 8-virtual-device platform) and plain vmap (the
        while_loop batching rule's select-freeze)."""
        sc, traces, depth, refs = b8_sequential_refs
        sweep = SweepRunner(sc, traces, mailbox_depth=depth,
                            shard_batch=shard)
        if shard is None:
            assert sweep.shard_batch  # conftest provides 8 devices
        out = sweep.run()
        assert len(out.results) == 8
        for b in range(8):
            _assert_results_equal(out.results[b], refs[b], msg=f"sim {b}")
        # per-sim gate observability demuxes too
        assert out.phase_skips is not None and len(out.phase_skips) == 8

    def test_validations(self):
        sc = _config()
        with pytest.raises(ValueError, match="counts must match"):
            SweepRunner(sc, [_trace(1), _trace(2)], [{}] * 3)
        with pytest.raises(ValueError, match="single-device"):
            SweepRunner(sc, [_trace(1)], stream=True)
        # mixed memory/memoryless campaign cannot share one program
        b = _trace(2)
        memoryless = dataclasses.replace(
            b, flags=np.zeros_like(b.flags),
            op=np.where(b.op < 20, np.uint8(Op.IALU), b.op))
        with pytest.raises(ValueError, match="agree on touching memory"):
            SweepRunner(sc, [_trace(1), memoryless])


def _hetero_traces():
    """Four sims of one geometry, each needing other blocks of the
    iteration: messages (one ANY_SENDER receive) and barriers; a mutex
    chain, a cond broadcast, a published signal with its COND_JOIN and a
    THREAD_JOIN; memory only; memory only and done within a few
    iterations.  Every sim touches memory (one engine program)."""
    n = TILES
    msg = [TraceBuilder() for _ in range(n)]
    msg[0].barrier_init(0, n)
    for t, b in enumerate(msg):
        b.load(0x10000 * (t + 1), 8).barrier_wait(0)
        for i in range(1, n):
            b.send((t + i) % n, 8)
        for i in range(1, n):
            b.recv((t - i) % n, 8)
        b.store(0x10000 * (t + 1), 8).barrier_wait(0)
    msg[1].send(0, 8)
    msg[0].recv(-1, 8)                      # ANY_SENDER
    mtx = [TraceBuilder() for _ in range(n)]
    mtx[0].mutex_init(0).cond_init(0).barrier_init(1, n)
    for b in mtx:
        b.barrier_wait(1)
    for r in range(2 * n):
        addr = 0x900000 + (r % 2) * 64
        mtx[r % n].mutex_lock(0).load(addr, 8).store(addr, 8) \
            .mutex_unlock(0)
    for t in (1, 2, 3):
        mtx[t].mutex_lock(0).cond_wait(0, 0).instr(Op.IALU) \
            .mutex_unlock(0)
    mtx[0].bblock(1000, 200_000)            # the waiters wait first
    mtx[0].mutex_lock(0).cond_broadcast(0).mutex_unlock(0)
    mtx[4].cond_signal(1, publish=True)
    mtx[5].cond_join(1, 1)
    mtx[6].thread_join(7)
    return [TraceBatch.from_builders(msg), TraceBatch.from_builders(mtx),
            _trace(3), _trace(4, n=2)]


@pytest.fixture(scope="module")
def hetero_refs():
    """The heterogeneous batch and each sim's own sequential run — of
    its PADDED trace, at the batch's ring depth and the batch's memory
    gating, so that every leaf of the final state is comparable."""
    from graphite_tpu.engine.simulator import auto_mailbox_depth

    sc = _config("lax_barrier")
    pack = pack_traces(_hetero_traces())
    depth = max(auto_mailbox_depth(pack.sim(b)) for b in range(4))
    refs = []
    for b in range(4):
        sim = Simulator(sc, pack.sim(b), mailbox_depth=depth,
                        phase_gate=False, mem_gate_bytes=0)
        refs.append((sim.run(), jax.device_get(sim.state)))
    return sc, pack, depth, refs


def _loop_flag(eqn):
    """The first carried value of a `while` equation: the flag of the
    in-place gate `memory/engine._run_if`."""
    p = eqn.params
    return eqn.invars[p["cond_nconsts"] + p["body_nconsts"]].aval


def _assert_whole_results_equal(ra, rb, msg):
    for f in dataclasses.fields(ra):
        a, b = getattr(ra, f.name), getattr(rb, f.name)
        for k in (a if isinstance(a, dict) else (None,)):
            np.testing.assert_array_equal(
                a if k is None else a[k], b if k is None else b[k],
                err_msg=f"{msg}: {f.name} {k or ''}")


class TestActivityGatesUnderTheSimAxis:
    """ISSUE 34: the engine's activity gates take their predicates OR-ed
    over the sim axis (`px.any_sim`), so they stay conds under `vmap`; a
    sim that does not need a block its neighbour needs runs it with every
    lane masked off.  Nothing a sim computes may change."""

    @pytest.mark.parametrize("layout", ["solo", (2, 2)],
                             ids=["solo_vmap", "2d_b2_t2"])
    def test_heterogeneous_batch_bit_identical_to_sequential_runs(
            self, hetero_refs, layout):
        sc, pack, depth, refs = hetero_refs
        if layout != "solo" and len(jax.devices()) < 4:
            pytest.skip("the 2D layout needs 4 devices")
        sweep = SweepRunner(sc, pack, mailbox_depth=depth, layout=layout)
        assert sweep._sims_per_dev == (4 if layout == "solo" else 2)
        out = sweep.run()
        # the batch ran until its slowest sim was done; one finished early
        iters = out.n_iterations.tolist()
        assert min(iters) < max(iters) and iters[3] == min(iters), iters
        for b in range(4):
            _assert_whole_results_equal(out.results[b], refs[b][0],
                                        f"sim {b}")
        # vacuity guards: the sims did exercise the blocks they are for
        assert out.results[0].packets_received.sum() >= 8 * 7 + 1
        assert out.results[1].sync_instructions.sum() > 0
        assert out.results[2].packets_sent.sum() == 0
        # the memory engine's gates engaged (ISSUE 36), less often than
        # in a batch of one stream: the sims' idle iterations differ
        for b in range(4):
            assert 0 < sum(out.phase_skips[b].values()) \
                < 6 * iters[b], out.phase_skips[b]
            assert 0 < out.base_skips[b]["base"] < iters[b]
        # ... and every leaf of every sim's final state, but for the
        # counters of what the PROGRAM skipped (the references ran
        # un-gated and count none)
        states0, dtr = sweep._batched_inputs()
        state = jax.device_get(sweep._get_runner(1_000_000)(
            states0, dtr, sweep.knobs)[0])
        for b in range(4):
            got = jax.tree_util.tree_leaves_with_path(
                jax.tree_util.tree_map(lambda x: x[b], state))
            want = jax.tree_util.tree_leaves(refs[b][1])
            assert len(got) == len(want)
            for (path, g), w in zip(got, want):
                leaf = jax.tree_util.keystr(path)
                if leaf in (".mem.phase_skips", ".mem.base_skips"):
                    assert not np.asarray(w).any() and np.asarray(g).any()
                    continue
                np.testing.assert_array_equal(
                    g, w, err_msg=f"sim {b}: state leaf {leaf}")

    def test_pending_signal_is_not_dropped_by_a_neighbours_sync(self):
        """The mutex/cond block is the one block that changes state with
        every lane masked off: it drops a pending signal as LOST once
        every running tile has reached its time.  Solo, a sim with no
        sync record in flight skips the block and keeps the signal, and
        a waiter that arrives AT the signal's time takes it.  A
        neighbour whose mutex traffic keeps the shared gate open must
        not change that (without the keep in `_mutex_cond_block` sim 0
        loses the signal and deadlocks)."""
        n = TILES
        tie = [TraceBuilder() for _ in range(n)]
        # tile 0 signals at S = one IALU; tile 1 is behind S then (so the
        # signal pends), reaches exactly S, idles there on records of no
        # cost and no sync, and waits at W = S
        tie[0].cond_init(0).instr(Op.IALU).cond_signal(0)
        tie[1].mutex_init(0).mutex_lock(0).thread_spawn(2).instr(Op.IALU)
        for _ in range(3):
            tie[1].thread_spawn(2)
        tie[1].cond_wait(0, 0).instr(Op.IALU).mutex_unlock(0)
        busy = [TraceBuilder() for _ in range(n)]
        busy[0].mutex_init(0)
        for r in range(4 * n):
            busy[r % n].instr(Op.IALU).mutex_lock(0).mutex_unlock(0)
        traces = [TraceBatch.from_builders(tie),
                  TraceBatch.from_builders(busy)]
        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, shared_mem=False, clock_scheme="lax")))
        refs = [Simulator(sc, t, mailbox_depth=4).run() for t in traces]
        assert refs[0].clock_ps[1] > 0      # the waiter was woken
        out = SweepRunner(sc, traces, mailbox_depth=4,
                          layout="solo").run()
        for b in range(2):
            _assert_whole_results_equal(out.results[b], refs[b],
                                        f"sim {b}")


    # the activity gates of `subquantum_iteration`, by the scope their
    # cond is traced under (obs/scopes.py); the ANY_SENDER cond lies
    # inside the net block's branch, whose name stack starts anew
    GATES = ["gt.net.mailbox", None, "gt.sync.barrier",
             "gt.sync.mutex_cond", "gt.sync.mutex_cond", "gt.sync.join",
             "gt.dvfs"]
    # ... and the private-L2 memory engine's (ISSUE 36): the six phase
    # conds, and after the requester's the working-set gather's under
    # the home-activity gate (not under a tile mesh: its rows ride a
    # collective, which must not sit in a cond)
    MEM_GATES = ["gt.mem.requester", "gt.mem.base", "gt.mem.home_evict",
                 "gt.mem.home_start", "gt.mem.sharer",
                 "gt.mem.home_finish", "gt.mem.requester_fill"]

    @pytest.mark.parametrize("layout", ["solo", (2, 2)],
                             ids=["solo_vmap", "2d_b2_t2"])
    def test_every_gate_of_a_b4_program_is_a_cond_on_a_scalar(
            self, layout):
        """Structure, from `SweepRunner.lower()`: each activity gate is a
        `cond` equation whose predicate is rank 0 (a bare `vmap` leaves
        none: a batched predicate turns a cond into both branches and a
        `select_n`); what `vmap` leaves of the reduction is a `pmax` over
        a POSITIONAL axis — no named-axis collective, so nothing for a
        fabric to carry.  The memory engine's seven predicates go the
        same way, and the merged scatter's in-place gate (`_run_if`, a
        zero-or-one-trip `while`) is keyed on a scalar flag."""
        from graphite_tpu.analysis.comms import extract_collectives
        from graphite_tpu.analysis.walk import find_eqns, iter_eqns
        from graphite_tpu.obs.scopes import deepest

        sweep = SweepRunner(_config("lax_barrier"),
                            [_trace(s) for s in range(1, 5)],
                            layout=layout)
        closed, _ = sweep.lower()
        conds = [e for _, e in find_eqns(closed, "cond")]
        mem_gates = [g for g in self.MEM_GATES
                     if layout == "solo" or g != "gt.mem.base"]
        assert [deepest(str(e.source_info.name_stack))
                for e in conds] == mem_gates + self.GATES
        assert [e.invars[0].aval.shape for e in conds] == [()] * len(conds)
        over_sims = [e for e in iter_eqns(closed)
                     if e.primitive.name == "pmax"]
        assert [e.params["axes"] for e in over_sims] == [(0,)] * 15
        assert all(e.outvars[0].aval.shape == () for e in over_sims)
        # the fifteenth is the block's exit (`_quantum_loop`: it runs
        # while ANY sim advanced): the innermost of the three nested
        # loops tests scalars alone - its trip count and that flag - so
        # `vmap` leaves its carry unselected; the quantum's own test,
        # above it, is per sim
        loops = sorted(
            ((site.count("while"), e)
             for site, e in find_eqns(closed, "while")
             if "gt.mem" not in str(e.source_info.name_stack)),
            key=lambda de: de[0])
        assert len(loops) == 3
        tests = [e.params["cond_jaxpr"].jaxpr for _, e in loops]
        rank = [max(len(v.aval.shape) for q in t.eqns for v in q.invars)
                for t in tests]
        assert rank == [2, 1, 0], rank
        assert [q.primitive.name for q in tests[2].eqns] == ["lt", "and"]
        in_place = [e for _, e in find_eqns(closed, "while")
                    if "gt.mem" in str(e.source_info.name_stack)]
        assert [_loop_flag(e).shape for e in in_place] == [()]
        # the analyzer prices none of them, and under the 2D mesh finds
        # the tile axis's packed exchanges and nothing else
        cols = extract_collectives(closed, n_tiles=TILES)
        assert {c.axis_name for c in cols} == (
            set() if layout == "solo" else {"tile"})
        assert {c.primitive for c in cols} <= {"all_gather"}

    @pytest.mark.parametrize("config", ["ref-default-64", "coh-1024"])
    def test_no_sim_axis_no_reduction(self, config):
        """With no sim axis the helper is the identity at trace time: the
        benchmark's solo program of `ref-default-64` and `coh-1024`'s
        host-driven runner hold no reduction over sims and every gate as
        a cond (their fingerprints, parent = change: CHANGES.md, PR 34)."""
        from graphite_tpu.analysis.comms import COLLECTIVE_PRIMS
        from graphite_tpu.analysis.walk import find_eqns, iter_eqns
        from graphite_tpu.parallel.px import IDENT

        pred = jax.numpy.asarray(True)
        assert IDENT.any_sim(pred) is pred
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
        try:
            from lib import target
        finally:
            sys.path.pop(0)
        cfg = target.load_config(config)
        sim = Simulator(target.build_sim_config(cfg),
                        target.build_trace(cfg), **cfg["simulator"])
        assert sim.barrier_host == (config == "coh-1024")
        closed, _ = sim.lower(1_000_000)
        assert not [e for e in iter_eqns(closed)
                    if e.primitive.name in COLLECTIVE_PRIMS]
        gates = [e for _, e in find_eqns(closed, "cond")
                 if str(e.source_info.name_stack).split("/")[-1]
                 in self.GATES]
        assert len(gates) == 6
        assert all(e.invars[0].aval.shape == () for e in gates)

    # ---- the memory engines' gates (ISSUE 36) --------------------------
    # `campaign64-dram`'s batch at 16 tiles: one stream of the cell's
    # generator at its four DRAM latencies, the cell's core

    LATENCIES = (60, 100, 140, 180)
    MEM_TILES = 16

    @classmethod
    def _cell_config(cls, latency_ns=None, **kw):
        text = config_text(cls.MEM_TILES, shared_mem=True,
                           clock_scheme="lax_barrier", **kw)
        if latency_ns is not None:
            text += f"\n[dram]\nlatency = {latency_ns}\n"
        return SimConfig(ConfigFile.from_string(text))

    @classmethod
    def _cell_stream(cls, seed=0, n=40):
        return synthetic.memory_stress_trace(
            cls.MEM_TILES, n_accesses=n, working_set_bytes=8192,
            write_fraction=0.4, shared_fraction=0.5, seed=seed)

    @classmethod
    def _points(cls, n=4):
        return [{"dram_latency_ns": lat} for lat in cls.LATENCIES[:n]]

    @staticmethod
    def _assert_outcomes_equal(got, want):
        assert got.n_iterations.tolist() == want.n_iterations.tolist()
        for b, (ra, rb) in enumerate(zip(got.results, want.results)):
            _assert_whole_results_equal(ra, rb, f"sim {b}")

    def test_memory_gates_batch_of_four_latencies(self):
        """The default `_build_sim` leaves the phase gates on under the
        batch.  Every field of every `SimResults` and every sim's
        iteration count are those of the explicit `phase_gate=False`
        batch and of four sequential `Simulator.run()`s with the latency
        in the config text; what differs is what the program skipped."""
        trace = self._cell_stream()
        sc = self._cell_config(core="iocoom")
        gated = SweepRunner(sc, [trace], self._points(), layout="solo")
        assert gated.sim.params.mem.phase_gate
        assert not gated.sim.params.mem_gate
        out = gated.run()
        off = SweepRunner(sc, [trace], self._points(), layout="solo",
                          phase_gate=False)
        assert not off.sim.params.mem.phase_gate
        out_off = off.run()
        self._assert_outcomes_equal(out, out_off)
        for b, lat in enumerate(self.LATENCIES):
            sim = Simulator(self._cell_config(lat, core="iocoom"), trace)
            _assert_whole_results_equal(out.results[b], sim.run(),
                                        f"latency {lat}")
            # (a block runs while ANY sim of the batch advanced: a sim
            # sits through its neighbours' iterations beside its own)
            assert sim.last_n_iterations - sim.last_idle_iterations \
                == out.n_iterations[b] - out.idle_iterations[b]
            assert sim.last_n_iterations <= out.n_iterations[b]
        # the latencies spread the sims' finishing times
        iters = out.n_iterations.tolist()
        assert iters[0] < iters[3], iters
        for b in range(4):
            skips, base = out.phase_skips[b], out.base_skips[b]
            assert all(0 < v < iters[b] for v in skips.values()), skips
            assert 0 < base["base"] < iters[b] and base["flush"] == 0
            assert not any(out_off.phase_skips[b].values())
            assert not any(out_off.base_skips[b].values())
        # sims that ran the same iterations counted the same skips: the
        # counters count the batch's OR, not a sim's own predicate
        same = [b for b in range(4) if iters[b] == iters[0]]
        assert len(same) > 1
        assert all(out.phase_skips[b] == out.phase_skips[0] for b in same)

    def test_staged_target_flushes_under_a_scalar_flag(self):
        """`dir_stage=True` under `vmap`: the per-block flush is gated in
        place by a counter that rides the batched state, reduced over
        the sims - both zero-or-one-trip loops of the program carry a
        scalar flag, blocks skip their flush, and nothing a sim computes
        differs from the un-gated staged batch."""
        from graphite_tpu.analysis.walk import find_eqns

        # a compute stretch that every tile enters at once (a barrier
        # before it): whole blocks in which no home phase runs
        rng = np.random.default_rng(0)
        bs = [TraceBuilder() for _ in range(self.MEM_TILES)]
        bs[0].barrier_init(0, self.MEM_TILES)
        for t, b in enumerate(bs):
            for part in range(2):
                for _ in range(8):
                    line = int(rng.integers(0, 64)) * 64
                    addr = (0x400000 if rng.random() < 0.5
                            else 0x100000 + t * 8192) + line
                    (b.store if rng.random() < 0.4 else b.load)(addr, 8)
                if part == 0:
                    b.barrier_wait(0)
                    for _ in range(16):
                        b.instr(Op.IALU)
        trace = TraceBatch.from_builders(bs)
        sc = self._cell_config()
        staged = dict(layout="solo", dir_stage=True, inner_block=4)
        gated = SweepRunner(sc, [trace], self._points(), **staged)
        assert gated.sim.params.mem.dir_stage_cap
        closed, _ = gated.lower()
        in_place = [e for _, e in find_eqns(closed, "while")
                    if "gt.mem" in str(e.source_info.name_stack)]
        assert sorted(str(e.source_info.name_stack).split("/")[-1]
                      for e in in_place) == ["gt.mem.base",
                                             "gt.mem.stage_flush"]
        assert [_loop_flag(e).shape for e in in_place] == [(), ()]
        out = gated.run()
        self._assert_outcomes_equal(out, SweepRunner(
            sc, [trace], self._points(), phase_gate=False, **staged).run())
        for b in range(4):
            # (at least: a quantum's last block may be shorter)
            blocks = int(out.n_iterations[b]) // 4
            assert 0 < out.base_skips[b]["flush"] < blocks

    def test_shared_l2_batch_of_two(self):
        """The shared-L2 engine's six phase conds take the same rule: a
        B = 2 `pr_l1_sh_l2_msi` batch under the default build equals two
        sequential runs, its conds keyed on scalars, skips counted."""
        from graphite_tpu.analysis.walk import find_eqns
        from graphite_tpu.memory.engine_shl2 import SHL2_PHASE_NAMES

        trace = self._cell_stream(n=24)
        kw = dict(protocol="pr_l1_sh_l2_msi")
        sweep = SweepRunner(self._cell_config(**kw), [trace],
                            self._points(2), layout="solo")
        assert sweep.sim.params.mem.phase_gate
        closed, _ = sweep.lower()
        phase_conds = [
            e for _, e in find_eqns(closed, "cond")
            if str(e.source_info.name_stack).split("/")[-1]
            in {"gt.mem." + n for n in SHL2_PHASE_NAMES}]
        assert len(phase_conds) == 6
        assert all(e.invars[0].aval.shape == () for e in phase_conds)
        out = sweep.run()
        for b, lat in enumerate(self.LATENCIES[:2]):
            sim = Simulator(self._cell_config(lat, **kw), trace)
            _assert_whole_results_equal(out.results[b], sim.run(),
                                        f"latency {lat}")
            assert sim.last_n_iterations - sim.last_idle_iterations \
                == out.n_iterations[b] - out.idle_iterations[b]
            assert all(v > 0 for v in out.phase_skips[b].values())
        assert out.base_skips is None


class TestKnobTracing:
    def test_grid_single_compile_matches_static_params(self):
        """One jit lowering serves a 4-point knob grid (zero recompiles,
        compile-count probe) and every traced point reproduces a
        fresh static-params run bit-exactly — including a traced
        lax_barrier quantum."""
        from graphite_tpu.engine.state import DeviceTrace
        from graphite_tpu.engine.step import run_simulation

        sc = _config("lax_barrier")
        batch = _trace(3)
        sim = Simulator(sc, batch)
        params, qps = sim.params, sim.quantum_ps
        state0 = sim.state
        trace = DeviceTrace.from_batch(batch)

        runner = jax.jit(lambda st, kn: run_simulation(
            params, trace, st, kn.quantum_ps, 100_000, knobs=kn))
        base = Knobs.from_params(params, qps)
        points = grid_points(dram_latency_ns=[40, 220],
                             hop_latency_cycles=[1, 4])
        points[1]["quantum_ps"] = 7_000_000   # quantum is traced too
        points[2]["sync_delay_cycles"] = 5
        points[3]["dir_access_cycles"] = 11
        assert len(points) >= 4
        got = []
        for p in points:
            kn = jax.tree_util.tree_map(
                lambda x: x[0], Knobs.stack(base, [p]))
            st, nq, deadlock, *_ = runner(state0, kn)
            assert not bool(deadlock)
            got.append((np.asarray(st.core.clock_ps), int(nq),
                        np.asarray(st.mem.counters.dram_total_lat_ps)))
        # the probe: 4 distinct knob points, ONE compiled executable
        assert runner._cache_size() == 1
        # knobs change results (they are live, not dead operands)
        assert not (got[0][0] == got[3][0]).all()

        # static-baked reference runs are a compile each: verify two
        # points — one carrying the traced quantum, one the remaining
        # knobs (the others exercise the same replace path)
        for p, (clk, nq, dram_lat) in (
                (points[1], got[1]), (points[3], got[3])):
            mp2 = dataclasses.replace(
                params.mem,
                **{k: v for k, v in p.items() if k != "quantum_ps"})
            params2 = dataclasses.replace(params, mem=mp2)
            q2 = p.get("quantum_ps", qps)
            st2, nq2, *_ = jax.jit(
                lambda st: run_simulation(params2, trace, st, q2,
                                          100_000))(state0)
            np.testing.assert_array_equal(
                np.asarray(st2.core.clock_ps), clk, err_msg=str(p))
            np.testing.assert_array_equal(
                np.asarray(st2.mem.counters.dram_total_lat_ps), dram_lat,
                err_msg=str(p))
            assert int(nq2) == nq, p

    def test_vmapped_knob_grid_matches_sequential_static(self):
        """End-to-end: a knob grid through SweepRunner (one trace
        replicated) matches per-point Simulators built from configs
        with the values baked in."""
        sc = _config("lax")
        batch = _trace(4)
        points = [{"dram_latency_ns": 55}, {"dram_latency_ns": 210}]
        sweep = SweepRunner(sc, [batch], points)
        out = sweep.run()
        assert out.knobs.point(0)["dram_latency_ns"] == 55
        for b, p in enumerate(points):
            sim = Simulator(sc, batch, mailbox_depth=sweep.mailbox_depth)
            sim.params = dataclasses.replace(
                sim.params,
                mem=dataclasses.replace(sim.params.mem, **p))
            _assert_results_equal(out.results[b], sim.run(), msg=str(p))
        # the two points must actually differ
        assert (out.results[0].completion_time_ps
                != out.results[1].completion_time_ps)
