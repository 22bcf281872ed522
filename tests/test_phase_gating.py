"""Per-phase activity gating (round 6) + batched host-barrier dispatch.

The memory engines' six protocol phases each run under their OWN
scalar-predicate lax.cond (MemParams.phase_gate) whose carried operands
exclude the big directory stores — home phases return compact per-lane
delta plans applied outside the cond (engine._DirAcc / engine_shl2.
_RowAcc).  Gating is pure mechanism: these tests pin bit-exactness vs
the golden oracles and vs the ungated program, assert the program
STRUCTURE at a 1024-tile shape (one cond per phase, no cond output
carrying the directory stores — the round-2 double-buffering pathology),
and pin the batched `barrier_host` dispatch against the per-quantum one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder

import targets
from targets import (
    MOSI, MSI, SHL2_MESI, SHL2_MSI, memory_config as make_config, mutex_rmw,
)


# what a gated run is held to: the golden oracle, or the UNGATED program
# (phase_gate=False: no phase cond, and the base — working-
# set gather, merged scatter, block flush — run every iteration)
AGAINST = ("golden", "ungated")


def assert_exact_gated(sc, batch, against="golden", **kw):
    """Gated run (phase conds and the home-activity gate the ONLY
    gating: whole-engine mem_gate forced off) must be bit-exact vs the
    golden oracle / the ungated program.  On the private-L2 engine the
    home-activity gate must have been both open and closed, or the case
    pins nothing about it."""
    sim = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0, **kw)
    res = sim.run()
    if against == "golden":
        ref = run_golden(sc, batch)
    else:
        ref = Simulator(sc, batch, phase_gate=False, mem_gate_bytes=0,
                        **kw).run()
        np.testing.assert_array_equal(
            np.asarray(res.instruction_count),
            np.asarray(ref.instruction_count), err_msg="instructions")
    np.testing.assert_array_equal(np.asarray(res.clock_ps),
                                  np.asarray(ref.clock_ps), err_msg="clock")
    for k, g in ref.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]),
                                      np.asarray(g), err_msg=k)
    base = sim.last_base_skips
    if base is not None:
        assert 0 < base["base"] < int(sim.last_n_iterations), base
    return res


# ---- bit-exactness vs the golden oracles and the ungated program ----------


@pytest.mark.parametrize("against", AGAINST)
@pytest.mark.parametrize("proto", [MSI, MOSI])
def test_gated_serialized_exact(proto, against):
    assert_exact_gated(make_config(4, proto), mutex_rmw(4, 5), against)


@pytest.mark.parametrize("proto", [SHL2_MSI, SHL2_MESI])
def test_gated_shl2_serialized_exact(proto):
    assert_exact_gated(make_config(4, proto), mutex_rmw(4, 5))


@pytest.mark.parametrize("against", AGAINST)
def test_gated_staged_exact(against):
    """Gating composes with directory write-staging: staged sharers ride
    the small table INSIDE the home-phase conds, the per-block flush is
    gated in place outside them; inner_block=4 crosses many flush
    boundaries, flushed and skipped."""
    assert_exact_gated(make_config(4, MSI), mutex_rmw(4, 4, lines=3),
                       against, dir_stage=True, inner_block=4)


@pytest.mark.parametrize("against", AGAINST)
def test_gated_limited_scheme_exact(against):
    """limited_no_broadcast issues THREE deferred _dir_update calls per
    home-start — the delta plan must sum them exactly."""
    extra = ("[dram_directory]\ndirectory_type = limited_no_broadcast\n"
             "max_hw_sharers = 2\n")
    assert_exact_gated(make_config(4, MSI, extra=extra), mutex_rmw(4, 4),
                       against)


@pytest.mark.parametrize("staged", [False, True])
def test_gated_matches_ungated_racy(staged):
    """On free-running racy traffic the engine may diverge from the
    oracle (documented envelope) but gated and ungated programs must be
    BIT-IDENTICAL to each other: gating is mechanism, not policy.  The
    compute gaps between accesses close the home-activity gate."""
    batch = targets.stress_trace(8, seed=11, n_accesses=80,
                                 shared_fraction=0.6)
    kw = dict(dir_stage=True, inner_block=4) if staged else {}
    assert_exact_gated(make_config(8), batch, "ungated", **kw)


def test_phase_gate_default_on():
    sim = Simulator(make_config(2), mutex_rmw(2, 1))
    assert sim.params.mem.phase_gate


# ---- gate observability ---------------------------------------------------


def test_phase_skip_counts():
    """Serialized traffic leaves most phases idle most iterations: the
    skip counters must be populated and bounded by the iteration count
    (the denominator for skip rates)."""
    sc = make_config(4, MSI)
    sim = Simulator(sc, mutex_rmw(4, 3), phase_gate=True, mem_gate_bytes=0)
    sim.run()
    skips = sim.last_phase_skips
    from graphite_tpu.memory.engine import PHASE_NAMES

    assert set(skips) == set(PHASE_NAMES)
    iters = int(sim.last_n_iterations)
    assert iters > 0
    assert all(0 <= v <= iters for v in skips.values()), (skips, iters)
    # a mutex-serialized workload cannot keep every phase busy every
    # iteration — some skips must have been recorded
    assert sum(skips.values()) > 0


@pytest.mark.parametrize("tail", [9, 0], ids=["idle-tail", "store-tail"])
def test_base_skip_counts_equal_host_count(tail):
    """`last_base_skips` against a count made on the host.  The home-
    activity predicate is true exactly where one of phases 2-5 fires
    (it is a superset of pred2 | pred3 | pred5 by the argument beside
    it, pred4 needs a FWD cell that was there or that phase 3 emitted,
    and each of its terms survives to the predicate that reads it), so
    stepping the program ONE iteration at a time and reading the phase
    skip vector after each gives the count independently of the
    counter: an iteration skipped its base iff it skipped phases 2-5,
    and a block skipped its flush iff all its iterations did — the
    iterations it RAN: the last block of a quantum stops at the idle
    iteration, and its gate compares with that trip count (`tail` sizes
    the closing stretch so that the short block is all idle, or holds
    the stores' home phases)."""
    from graphite_tpu.engine.step import subquantum_iteration

    K = 4
    bs = [TraceBuilder() for _ in range(4)]
    for t, b in enumerate(bs):
        b.load(0x100000 + t * 64, 8)          # a cold miss each
        for _ in range(3 * K + t):            # idle stretches
            b.instr(Op.IALU)
        b.store(0x100000 + ((t + 1) % 4) * 64, 8)   # a neighbour's line
        for _ in range(tail):
            b.instr(Op.IALU)
    batch = TraceBatch.from_builders(bs)
    # lax: one unbounded quantum, so the run is blocks of K iterations
    # up to the first iteration that makes no progress — the loop below
    sc = make_config(4, extra="[clock_skew_management]\nscheme = lax\n")
    sim = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0,
                    dir_stage=True, inner_block=K)
    assert sim.quantum_ps is None
    state0 = sim.state
    res = sim.run()
    assert res.n_quanta == 1
    iters = int(sim.last_n_iterations)
    got = sim.last_base_skips

    qend = jnp.asarray(2**61, jnp.int64)
    step = jax.jit(lambda st: subquantum_iteration(
        sim.params, sim.device_trace, st, qend))
    st, blocks, adv = state0, [], 1
    while adv:                        # _quantum_loop's block structure
        blocks.append([])
        while adv and len(blocks[-1]) < K:
            before = np.asarray(st.mem.phase_skips)
            st, adv = step(st)
            fired = 1 - (np.asarray(st.mem.phase_skips) - before)
            blocks[-1].append(not fired[1:5].any())
    assert sum(map(len, blocks)) == iters
    assert sim.last_idle_iterations == 1
    want = {"base": sum(map(sum, blocks)), "flush": sum(map(all, blocks))}
    assert got == want, (got, want)
    assert 0 < want["base"] < iters and 0 < want["flush"] < len(blocks)
    # the short block: all idle under the idle tail, and skipped; with a
    # home phase in it under the stores' tail, and flushed
    assert len(blocks[-1]) < K
    assert all(blocks[-1]) == (tail > 0)


def test_whole_engine_gate_leaves_home_gate_out():
    """Below the mem_gate ceiling the whole engine sits under ONE cond,
    which skips the base with it: the engine's own home-activity gate
    stays out (measured to cost what it saves there), the iteration
    holds no in-place loop, and `base` counts the whole-engine skips —
    each of which also counts once for every phase."""
    from graphite_tpu.analysis import iter_eqns
    from graphite_tpu.engine.step import subquantum_iteration

    bs = [TraceBuilder() for _ in range(4)]
    for t, b in enumerate(bs):
        b.load(0x100000 + t * 64, 8)
        for _ in range(12):
            b.instr(Op.IALU)
        b.store(0x100000 + ((t + 1) % 4) * 64, 8)
    sim = Simulator(make_config(4), TraceBatch.from_builders(bs))
    assert sim.params.mem_gate and sim.params.mem.phase_gate
    closed = jax.make_jaxpr(lambda st: subquantum_iteration(
        sim.params, sim.device_trace, st,
        jnp.asarray(2**61, jnp.int64)))(sim.state)
    assert not [e for e in iter_eqns(closed) if e.primitive.name == "while"]
    sim.run()
    base = sim.last_base_skips
    assert 0 < base["base"] <= min(sim.last_phase_skips.values())
    assert base["flush"] == 0          # no staging at this size


@pytest.mark.parametrize("counter", ["last_phase_skips", "last_base_skips"])
def test_phase_skips_none_without_memory(counter):
    cfg = """
[general]
total_cores = 2
mode = lite
[core/static_instruction_costs]
ialu = 1
"""
    bs = [TraceBuilder() for _ in range(2)]
    for b in bs:
        b.instr(Op.IALU)
    sim = Simulator(SimConfig(ConfigFile.from_string(cfg)),
                    TraceBatch.from_builders(bs))
    sim.run()
    assert getattr(sim, counter) is None


# ---- program structure at the 1024-tile shape -----------------------------


def test_phase_cond_structure_1024_shape():
    """The acceptance shape: a 1024-tile program (CPU-scaled caches /
    directory) TRACES with per-phase conds — one cond per protocol phase
    — and NO cond output carries the directory entry or sharers stores
    (cond branch outputs are double-buffered by XLA; keeping the big
    stores out of them is what lets gating survive where the >= 1 GB
    whole-engine gate disable used to apply).  Structural jaxpr
    assertion, no TPU wall-clock needed.

    Traversal and the cond-payload assertion are served by the SHARED
    program-auditor pass (graphite_tpu/analysis) — the same walker and
    rule `python -m graphite_tpu.tools.audit` runs on every config, so
    there is one source of truth for jaxpr traversal."""
    T = 1024
    # geometries chosen so the directory entry/sharers avals are UNIQUE
    # in the program (l1i (32,2), l1d (32,4), l2 (64,8) meta vs entry
    # (16,4) / sharers (16,128)) — the aval check below must not false-
    # positive on a cache meta array of coincidentally equal shape
    extra = """
[l1_icache/T1]
cache_size = 4
associativity = 2
[l1_dcache/T1]
cache_size = 8
associativity = 4
[l2_cache/T1]
cache_size = 32
associativity = 8
[dram_directory]
total_entries = 64
associativity = 4
"""
    sc = make_config(T, MSI, extra=extra)
    bs = []
    for t in range(T):
        b = TraceBuilder()
        b.load(0x100000 + t * 64, 8)
        b.store(0x100000 + (t % 7) * 64, 8)
        bs.append(b)
    batch = TraceBatch.from_builders(bs)
    # mem_gate_bytes=0: the big-state regime — whole-engine gate off,
    # per-phase conds are the only gating (exactly the config-5 shape)
    sim = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0)
    assert sim.params.mem_gate is False
    assert sim.params.mem.phase_gate is True

    from graphite_tpu.engine.step import subquantum_iteration

    qend = jnp.asarray(2**61, jnp.int64)
    closed = jax.make_jaxpr(
        lambda st: subquantum_iteration(sim.params, sim.device_trace,
                                        st, qend))(sim.state)

    from graphite_tpu.analysis import iter_eqns
    from graphite_tpu.analysis.rules import cond_payload, phase_conds
    from graphite_tpu.memory.engine import dir_store_avals

    conds = [e for e in iter_eqns(closed)
             if e.primitive.name == "cond"]
    assert conds, "gated program lost its lax.conds"

    # one cond per protocol phase: each phase cond writes at least one
    # uint8[T, T] mailbox type matrix, and nothing else in the program
    # does (jax prunes unmodified pass-through cond outputs, so only the
    # matrices a phase actually writes appear)
    n_phase_conds = len(phase_conds(closed, T))
    assert n_phase_conds == 6, (
        f"expected one cond per protocol phase (6), found "
        f"{n_phase_conds}")

    # no cond output may be (a copy of) the directory stores: the shared
    # cond-payload rule, fed the engine's own store signatures (the
    # geometry above keeps them unique in the program)
    findings = cond_payload(closed,
                            forbidden=dir_store_avals(sim.state.mem))
    assert not findings, (
        "a lax.cond output carries a directory store — the round-2 "
        "double-buffering pathology is back:\n"
        + "\n".join(str(f) for f in findings))


def test_staged_conds_carry_no_entry_words():
    """A staged program carries its entry store as u32 words (PR 45):
    `dir_store_avals` names that form, the landing's choice of a lowering
    platform returns it (`walk.is_platform_choice`: no run-time cond), and
    no phase's cond does."""
    from graphite_tpu.analysis.rules import cond_payload
    from graphite_tpu.engine.step import subquantum_iteration
    from graphite_tpu.memory.engine import dir_store_avals

    batch = synthetic.memory_stress_trace(
        8, n_accesses=8, working_set_bytes=1 << 12, write_fraction=0.4,
        shared_fraction=0.6, seed=11)
    sim = Simulator(make_config(8), batch, dir_stage=True, inner_block=4,
                    mem_gate_bytes=0)
    mp = sim.params.mem
    (entry, dtype), _ = avals = dir_store_avals(sim.state.mem)
    assert (entry, dtype) == ((8, 2 * mp.dir_ways, mp.dir_sets), "uint32")
    closed = jax.make_jaxpr(
        lambda st: subquantum_iteration(
            sim.params, sim.device_trace, st,
            jnp.asarray(2**61, jnp.int64)))(sim.state)
    assert "platform_index" in str(closed)
    assert not cond_payload(closed, forbidden=avals)


# ---- batched host-barrier dispatch ----------------------------------------


class TestBarrierBatch:
    def _workload(self):
        from graphite_tpu.tools._template import config_text

        sc = SimConfig(ConfigFile.from_string(config_text(
            8, shared_mem=True, clock_scheme="lax_barrier")))
        batch = synthetic.memory_stress_trace(
            8, n_accesses=40, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.6, seed=5)
        return sc, batch

    def test_batched_matches_per_quantum_and_device(self):
        sc, batch = self._workload()
        r_dev = Simulator(sc, batch).run()
        r_b1 = Simulator(sc, batch, barrier_host=True,
                         barrier_batch=1).run()
        r_b8 = Simulator(sc, batch, barrier_host=True,
                         barrier_batch=8).run()
        for name, r in (("batch=1", r_b1), ("batch=8", r_b8)):
            assert r_dev.clock_ps.tolist() == r.clock_ps.tolist(), name
            assert r_dev.n_quanta == r.n_quanta, name
            for k in r_dev.mem_counters:
                np.testing.assert_array_equal(
                    np.asarray(r_dev.mem_counters[k]),
                    np.asarray(r.mem_counters[k]), err_msg=f"{name}:{k}")

    def test_batched_deadlock_detected(self):
        from graphite_tpu.engine.simulator import DeadlockError
        from graphite_tpu.tools._template import config_text

        sc = SimConfig(ConfigFile.from_string(config_text(
            4, clock_scheme="lax_barrier")))
        b0 = TraceBuilder()
        b0.recv(1)
        bs = [b0] + [TraceBuilder() for _ in range(3)]
        for b in bs[1:]:
            b.instr(Op.IALU)
        with pytest.raises(DeadlockError):
            Simulator(sc, TraceBatch.from_builders(bs),
                      barrier_host=True, barrier_batch=8).run()


# ---- plain-unroll clamp ---------------------------------------------------


def test_plain_unroll_clamped_and_warns():
    from graphite_tpu.engine.step import PLAIN_UNROLL_MAX

    cfg = """
[general]
total_cores = 2
mode = lite
plain_unroll = 32
[core/static_instruction_costs]
ialu = 1
"""
    bs = [TraceBuilder() for _ in range(2)]
    for b in bs:
        for _ in range(8):
            b.instr(Op.IALU)
    batch = TraceBatch.from_builders(bs)
    with pytest.warns(UserWarning, match="plain_unroll"):
        sim = Simulator(SimConfig(ConfigFile.from_string(cfg)), batch)
    assert sim.params.plain_unroll == PLAIN_UNROLL_MAX
    # the clamped program still runs and matches an explicit-16 run
    r32 = sim.run()
    cfg16 = cfg.replace("plain_unroll = 32", "plain_unroll = 16")
    r16 = Simulator(SimConfig(ConfigFile.from_string(cfg16)), batch).run()
    assert r32.clock_ps.tolist() == r16.clock_ps.tolist()


# ---- dir_stage on shared-L2: the real constraint --------------------------


def test_dir_stage_shl2_states_real_constraint():
    """Round-6 satellite: the shared-L2 rejection must state the REAL
    constraint (the embedded directory writes one row-form scatter per
    phase — nothing to stage), not a stale 'pending support' message."""
    with pytest.raises(ValueError, match="row-form scatter"):
        Simulator(make_config(4, SHL2_MSI), mutex_rmw(4, 1),
                  dir_stage=True)
