"""The quantum loop's exit (`engine/step._quantum_loop`): a quantum ends
at its first idle iteration, whatever the block size; `lax_p2p` keeps
whole blocks; the counts of the benchmark's targets."""

import dataclasses
import os
import sys

import numpy as np
import pytest

from graphite_tpu.analysis.walk import find_eqns
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.sweep.runner import SweepRunner
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.benchmarks import fft_trace
from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TILES = 8
BLOCKS = (1, 4, 32)


def _config(**kw):
    return SimConfig(ConfigFile.from_string(config_text(TILES, **kw)))


def _stress(n=24, seed=5):
    return synthetic.memory_stress_trace(
        TILES, n_accesses=n, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=seed)


def _mutex_cond():
    """Producer / consumer pairs over a condition variable, then
    mutex-serialized work: COND_WAIT arrivals, deliveries and lock
    hand-offs move state in iterations where no lane commits."""
    bs = [TraceBuilder() for _ in range(TILES)]
    bs[0].mutex_init(0).mutex_init(1).cond_init(0)
    bs[0].barrier_init(3, TILES)
    for b in bs:
        b.barrier_wait(3)
    bs[1].mutex_lock(0).cond_wait(0, 0).instr(Op.IALU).mutex_unlock(0)
    for _ in range(3):
        bs[0].instr(Op.IALU)
    bs[0].mutex_lock(0).instr(Op.IALU).cond_signal(0).mutex_unlock(0)
    for r in range(3 * TILES):
        bs[r % TILES].instr(Op.IALU).mutex_lock(1).instr(Op.IMUL)
        bs[r % TILES].mutex_unlock(1)
    return TraceBatch.from_builders(bs)


# name -> (config, trace, Simulator keywords, streamed window or None)
WORKLOADS = {
    "msi-staged": (dict(shared_mem=True), _stress,
                   dict(dir_stage=True, mem_gate_bytes=0), None),
    "shl2-mesi": (dict(shared_mem=True, protocol="pr_l1_sh_l2_mesi"),
                  _stress, {}, None),
    "iocoom-fft": (dict(shared_mem=True, core="iocoom"),
                   lambda: fft_trace(TILES, points_per_tile=32), {}, None),
    "mutex-cond": (dict(), _mutex_cond, {}, None),
    "streamed": (dict(), lambda: fft_trace(TILES, points_per_tile=32),
                 dict(stream=True), 16),
}


def _fields(res):
    out = {}
    for f in dataclasses.fields(res):
        v = getattr(res, f.name)
        for k in (v if isinstance(v, dict) else (None,)):
            out[f.name, k] = np.asarray(v if k is None else v[k])
    return out


def _assert_equal(ra, rb, msg):
    a, b = _fields(ra), _fields(rb)
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"{msg}: {k}")


@pytest.mark.parametrize("name", sorted(WORKLOADS))
def test_block_size_changes_nothing(name):
    """`inner_block` is the flush cadence, not the quantum's length: every
    statistic, the iteration count (the sum over quanta of working
    iterations + 1) and the idle count are those of `inner_block` 1."""
    cfg, trace, kw, window = WORKLOADS[name]
    runs = []
    for k in BLOCKS:
        sim = Simulator(_config(**cfg), trace(), inner_block=k, **kw)
        res = (sim.run() if window is None
               else sim.run_streamed(window_records=window))
        runs.append((res, int(sim.last_n_iterations),
                     int(sim.last_idle_iterations)))
    (res, iters, idle), others = runs[0], runs[1:]
    assert 0 < idle < iters
    for k, (r, i, d) in zip(BLOCKS[1:], others):
        _assert_equal(r, res, f"inner_block {k}")
        assert (i, d) == (iters, idle), k
    if name in ("msi-staged", "shl2-mesi", "iocoom-fft"):
        # every quantum of these does some work and ends on the one
        # iteration that sees nothing move
        assert idle == res.n_quanta


def test_block_size_changes_nothing_under_a_sim_axis():
    """A B = 4 batch: the block's trip count is the program's (it runs
    while ANY sim advanced), so a sim's `n_iterations` counts what it sat
    through beside its neighbours and depends on the block; its WORKING
    iterations and every statistic are its solo run's."""
    points = [{"dram_latency_ns": lat} for lat in (60, 100, 140, 180)]
    sc = _config(shared_mem=True)
    outs = [SweepRunner(sc, [_stress()], points, layout="solo",
                        inner_block=k).run() for k in BLOCKS]
    work = outs[0].n_iterations - outs[0].idle_iterations
    for k, out in zip(BLOCKS[1:], outs[1:]):
        for b in range(4):
            _assert_equal(out.results[b], outs[0].results[b],
                          f"inner_block {k}, sim {b}")
        assert (out.n_iterations - out.idle_iterations).tolist() \
            == work.tolist()
        assert (out.n_iterations >= outs[0].n_iterations).all()
    for b in (0, 3):
        text = config_text(TILES, shared_mem=True) \
            + f"\n[dram]\nlatency = {points[b]['dram_latency_ns']}\n"
        sim = Simulator(SimConfig(ConfigFile.from_string(text)), _stress())
        _assert_equal(outs[-1].results[b], sim.run(), f"solo {b}")
        assert sim.last_n_iterations - sim.last_idle_iterations == work[b]
        # one-iteration blocks end a sim's quantum on its own idle one
        assert sim.last_n_iterations == outs[0].n_iterations[b]


def _loop_tests(closed):
    """The primitives of the tests of a lowered program's three nested
    loops (quanta, quantum, block), outermost first."""
    loops = [(site, e) for site, e in find_eqns(closed, "while")
             if "gt.mem" not in str(e.source_info.name_stack)]
    loops.sort(key=lambda se: se[0].count("while"))
    assert len(loops) == 3
    return [[q.primitive.name for q in e.params["cond_jaxpr"].jaxpr.eqns]
            for _, e in loops]


def _p2p_config(scheme):
    return SimConfig(ConfigFile.from_string(
        config_text(TILES, clock_scheme=scheme)
        + "[clock_skew_management/lax_p2p]\nslack = 100\n"))


def test_lax_p2p_keeps_whole_blocks():
    """`p2p_round` advances every iteration and redraws every tile's
    partner, so an idle iteration is no fixed point under `lax_p2p`: its
    program keeps the loop of before — a block's test is its trip count
    alone, a quantum's the block's summed progress — and runs whole
    blocks, where every other scheme stops at the idle iteration."""
    trace = fft_trace(TILES, points_per_tile=32)
    p2p = Simulator(_p2p_config("lax_p2p"), trace)
    assert p2p.params.p2p_slack_ps is not None
    *_, quantum, block = _loop_tests(p2p.lower()[0])
    assert (quantum, block) == (["gt"], ["lt"])
    lax = Simulator(_p2p_config("lax"), trace)
    *_, quantum, block = _loop_tests(lax.lower()[0])
    assert (quantum, block) == (["gt"], ["lt", "and"])
    want = lax.run()
    got = p2p.run()
    np.testing.assert_array_equal(got.clock_ps, want.clock_ps)
    assert p2p.last_n_iterations % p2p.params.inner_block == 0
    assert lax.last_n_iterations % lax.params.inner_block != 0
    assert lax.last_idle_iterations == want.n_quanta == 1


# ---- the benchmark's targets, counted -------------------------------------

# configuration -> {tiles: (iterations, quanta)}; the whole-size counts are
# the cells' `iterations` on the chip (PERF.md section 4)
COUNTS = {
    "coh-1024-memstress": {64: (166, 11), 1024: (733, 37)},
    "shl2-mesi-1024-memstress": {64: (213, 9), 1024: (649, 23)},
    "ref-default-64": {64: (722, 5)},
}


def _target(name, tiles):
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from lib import target
    finally:
        sys.path.pop(0)
    cfg = target.load_config(name)
    cfg["config_text"]["tiles"] = tiles
    cfg["trace"]["kwargs"]["n_tiles"] = tiles
    return Simulator(target.build_sim_config(cfg), target.build_trace(cfg),
                     **cfg["simulator"])


@pytest.mark.parametrize("name,tiles", [
    ("coh-1024-memstress", 64),
    ("shl2-mesi-1024-memstress", 64),
    pytest.param("coh-1024-memstress", 1024, marks=pytest.mark.slow),
    pytest.param("shl2-mesi-1024-memstress", 1024, marks=pytest.mark.slow),
    pytest.param("ref-default-64", 64, marks=pytest.mark.slow),
])
def test_iterations_of_the_cells(name, tiles):
    """One idle iteration a quantum and no block of them: the counts the
    cells report as `iterations` (2,400 / 1,632 / 896 with whole blocks)."""
    sim = _target(name, tiles)
    res = sim.run()
    iters, quanta = COUNTS[name][tiles]
    assert (int(sim.last_n_iterations), res.n_quanta) == (iters, quanta)
    assert sim.last_idle_iterations == quanta
