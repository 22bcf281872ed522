"""The memory engine's base: one packed directory working-set gather and
one merged row scatter per engine iteration, plus the budget ratchet
that locks the win in.

The structural claims are jaxpr-level (via the shared analysis/walk
traversal) at a 1024-tile shape — the config-5 regime the layout exists
for; the equivalence claims are randomized-trace bit-identity (gated vs
un-gated, staged vs un-staged) and serialized-trace golden-oracle
exactness for both memory engines.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
from jax.extend.core import Literal
import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.schema import TraceBatch, TraceBuilder

import targets
from targets import (
    MOSI, MSI, SHL2_MESI, memory_config as make_config, mutex_rmw,
)


def _assert_results_equal(ra, rb):
    np.testing.assert_array_equal(np.asarray(ra.clock_ps),
                                  np.asarray(rb.clock_ps))
    np.testing.assert_array_equal(np.asarray(ra.instruction_count),
                                  np.asarray(rb.instruction_count))
    for k in ra.mem_counters:
        np.testing.assert_array_equal(np.asarray(ra.mem_counters[k]),
                                      np.asarray(rb.mem_counters[k]),
                                      err_msg=k)


# ---- program structure at the 1024-tile shape -----------------------------

# same unique-aval geometry trick as test_phase_gating: the directory
# entry/sharers avals must not collide with any cache meta array
GEOM = """
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


def _big_shape_sim(T=1024, **kw):
    sc = make_config(T, MSI, extra=GEOM)
    bs = []
    for t in range(T):
        b = TraceBuilder()
        b.load(0x100000 + t * 64, 8)
        b.store(0x100000 + (t % 7) * 64, 8)
        bs.append(b)
    batch = TraceBatch.from_builders(bs)
    sim = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0, **kw)
    assert sim.params.mem_gate is False
    return sim


def _iteration_jaxpr(sim):
    from graphite_tpu.engine.step import subquantum_iteration

    qend = jnp.asarray(2**61, jnp.int64)
    return jax.make_jaxpr(
        lambda st: subquantum_iteration(sim.params, sim.device_trace,
                                        st, qend))(sim.state)


def _store_sites(closed, sig):
    """(gather sites, scatter sites) of the store with signature
    `sig`, at any depth."""
    from graphite_tpu.analysis.walk import aval_sig, iter_eqns_with_site

    gathers, scatters = [], []
    for site, eqn in iter_eqns_with_site(closed):
        name = eqn.primitive.name
        if (not eqn.invars or isinstance(eqn.invars[0], Literal)
                or aval_sig(eqn.invars[0].aval) != sig):
            continue
        if name == "gather":
            gathers.append(site)
        elif name.startswith("scatter"):
            scatters.append(site)
    return gathers, scatters


def _store_ops(closed, sig):
    """(gathers, scatters) on the store with aval signature `sig` at any
    depth of the iteration program."""
    gathers, scatters = _store_sites(closed, sig)
    return len(gathers), len(scatters)


def test_one_gather_one_merged_scatter_1024_shape():
    """The iteration touches each big directory store
    exactly once in each direction: ONE packed working-set row gather up
    front, ONE merged row scatter at the end — for the sharers store AND
    the packed entry-word store."""
    sim = _big_shape_sim()
    closed = _iteration_jaxpr(sim)
    d = sim.state.mem.directory
    sharers_sig = (tuple(d.sharers.shape), str(d.sharers.dtype))
    entry_sig = (tuple(d.entry.shape), str(d.entry.dtype))

    g, s = _store_ops(closed, sharers_sig)
    assert (g, s) == (1, 1), (
        f"sharers store: expected exactly one row gather and one merged "
        f"row scatter per iteration, found {g} gather(s), {s} "
        f"scatter(s)")
    g, s = _store_ops(closed, entry_sig)
    assert (g, s) == (1, 1), (
        f"entry store: expected exactly one row gather and one merged "
        f"row scatter per iteration, found {g} gather(s), {s} "
        f"scatter(s)")


def test_staged_iteration_has_no_sharers_scatter_1024_shape():
    """With directory write-staging the iteration still gathers the
    sharers store exactly once (overlaying the per-lane staging rows)
    but never scatters it — the amortized flush outside the iteration
    is the store's only writer."""
    sim = _big_shape_sim(dir_stage=True, inner_block=4)
    closed = _iteration_jaxpr(sim)
    d = sim.state.mem.directory
    sharers_sig = (tuple(d.sharers.shape), str(d.sharers.dtype))
    g, s = _store_ops(closed, sharers_sig)
    assert (g, s) == (1, 0), (g, s)


def test_phase_conds_survive_consolidation_1024_shape():
    """Six per-phase gating conds — the working set moves the big-store
    traffic out of the phases, not the phases themselves."""
    from graphite_tpu.analysis.rules import phase_conds

    sim = _big_shape_sim()
    closed = _iteration_jaxpr(sim)
    assert len(phase_conds(closed, 1024)) == 6


# ---- the home-activity gate over the base (PR 29) --------------------------

STAGED = dict(dir_stage=True, inner_block=4)


def _program_jaxpr(sim, state=None):
    """The whole program the drive loop dispatches (quantum loop, inner
    blocks, the per-block flush), not one iteration of it."""
    state = sim.state if state is None else state
    if sim.barrier_host:
        return jax.make_jaxpr(sim._hb_get_runner())(
            state, jnp.asarray(0, jnp.int64), jnp.asarray(1, jnp.int32))
    return jax.make_jaxpr(sim._get_runner(8))(state)


def _depth(site):
    """Control-flow constructs enclosing a site."""
    return site.count("cond/branches") + site.count("while/body")


@pytest.mark.parametrize("staged", [False, True])
def test_gated_base_under_control_flow_1024_shape(staged):
    """The gather and both writers of the big directory stores sit under
    control flow, not at the iteration's (block's) top level: the
    working-set gather under the home-activity gate's `cond` (small row
    outputs), the merged scatter and the block flush under its
    zero-or-one-trip `while` (in-place carry) — each exactly ONE
    construct deeper than in the gates-off program, still one gather and
    one scatter per store, six phase conds, and NO cond anywhere in the
    program returns a store."""
    from graphite_tpu.analysis.rules import cond_payload, phase_conds
    from graphite_tpu.memory.engine import dir_store_avals

    kw = STAGED if staged else {}
    sim = _big_shape_sim(**kw)
    entry_sig, sharers_sig = dir_store_avals(sim.state.mem)

    it = _iteration_jaxpr(sim)
    assert len(phase_conds(it, 1024)) == 6
    for sig in (entry_sig, sharers_sig):
        (g,), sc = _store_sites(it, sig)
        assert "cond/branches" in g and "while/body" not in g, g
        assert len(sc) == (0 if staged and sig == sharers_sig else 1)
        for site in sc:
            assert site.startswith("while/body"), site

    prog = _program_jaxpr(sim)
    assert not cond_payload(prog, forbidden=(entry_sig, sharers_sig))
    off = _big_shape_sim(**kw)
    off.params = dataclasses.replace(
        off.params, mem=dataclasses.replace(off.params.mem,
                                            phase_gate=False))
    prog_off = _program_jaxpr(off)
    for sig in (entry_sig, sharers_sig):
        g_on, s_on = _store_sites(prog, sig)
        g_off, s_off = _store_sites(prog_off, sig)
        assert len(g_on) == len(g_off) and len(s_on) == len(s_off) == 1
        # (the flush gathers the sharers rows it adds to: inside its gate)
        for on, ref in zip(g_on + s_on, g_off + s_off):
            assert _depth(on) == _depth(ref) + 1, (on, ref)


def test_gates_off_program_is_the_counter_alone_1024_shape():
    """phase_gate=False (what SweepRunner campaigns compile): the
    home-activity gate adds a carried counter and NOTHING else — the
    program with the counter in its state is equation for equation the
    program without it (same gathers, scatters, conds and whiles at the
    same sites), one carried value apart.  PROGRAMS.lock pins the same
    program against the parent's."""
    from graphite_tpu.analysis.walk import iter_eqns_with_site

    sim = _big_shape_sim(**STAGED)
    sim.params = dataclasses.replace(
        sim.params,
        mem=dataclasses.replace(sim.params.mem, phase_gate=False))
    bare = sim.state.replace(mem=sim.state.mem.replace(base_skips=None))
    with_counter = _program_jaxpr(sim)
    without = _program_jaxpr(sim, bare)

    def eqns(closed):
        return [site for site, _ in iter_eqns_with_site(closed)]

    assert eqns(with_counter) == eqns(without)
    assert (len(with_counter.jaxpr.invars)
            == len(without.jaxpr.invars) + 1)
    assert (len(with_counter.jaxpr.outvars)
            == len(without.jaxpr.outvars) + 1)


# ---- bit-identity: gated vs un-gated, staged vs un-staged ------------------


@pytest.mark.parametrize("proto", [MSI, MOSI])
@pytest.mark.parametrize("seed", [3, 11])
def test_gated_matches_ungated_randomized(proto, seed):
    """Randomized coherence traffic: the gated engine (six phase conds,
    the home-activity gate over the base) must be bit-identical to the
    un-gated one (every phase, the gather and the merged scatter run
    every iteration — what a campaign compiles)."""
    sc = make_config(8, proto)
    batch = targets.stress_trace(8, seed=seed, n_accesses=40,
                                 shared_fraction=0.6)
    r_gated = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0).run()
    r_flat = Simulator(sc, batch, phase_gate=False, mem_gate_bytes=0).run()
    _assert_results_equal(r_gated, r_flat)


def test_staged_matches_unstaged_randomized():
    """The working set composes with directory write-staging (per-lane
    rows): staged == unstaged on shared-line traffic crossing many flush
    boundaries."""
    sc = make_config(8, MSI)
    batch = synthetic.memory_stress_trace(
        8, n_accesses=40, working_set_bytes=1 << 12,
        write_fraction=0.5, shared_fraction=0.7, seed=5)
    r_staged = Simulator(sc, batch, mem_gate_bytes=0, dir_stage=True,
                         inner_block=4).run()
    r_uns = Simulator(sc, batch, mem_gate_bytes=0, dir_stage=False,
                      inner_block=4).run()
    _assert_results_equal(r_staged, r_uns)


# ---- the staging overlay's value, fetched where a way is read (PR 46) ------


def _staged_directory(rng, T, DS, DW, SW, C):
    """A directory with a staging table as a block leaves one half way:
    keys drawn from a few sets, so they REPEAT in a lane (the latest slot
    wins) and meet the gathered sets; -1 beyond each lane's cursor."""
    from graphite_tpu.memory.state import DirectoryArrays

    sn = rng.integers(0, C - 3, T).astype(np.int32)
    hot = rng.integers(0, DS, (T, 3))
    key = (np.take_along_axis(hot, rng.integers(0, 3, (T, C)), 1) * DW
           + rng.integers(0, DW, (T, C))).astype(np.int32)
    skey = np.where(np.arange(C)[None, :] < sn[:, None], key, -1)
    d = DirectoryArrays(
        entry=jnp.asarray(rng.integers(0, 2**40, (T, DS, DW))),
        sharers=jnp.asarray(rng.integers(0, 2**32, (T, DS, DW * SW),
                                         dtype=np.uint32)),
        skey=jnp.asarray(skey, jnp.int32),
        sval=jnp.asarray(rng.integers(0, 2**32, (T, C, SW),
                                      dtype=np.uint32)),
        sn=jnp.asarray(sn))
    return d, hot


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("finish", [False, True], ids=["view", "finish"])
def test_lazy_staged_read_equals_the_eager_overlay(seed, finish):
    """PR 46: a staged single-device working set takes the staging
    table's INDEX at gather time and a view fetches the staged VALUE of
    the way it reads; `entry(way)` and the current value `_dir_update`
    takes its delta against must equal the eager overlay's row
    (`_stage_overlay_rows`, what the sharded working set keeps) at that
    way — with repeated keys in a lane, slots appended AFTER the index
    was taken (on the very key that is read too), earlier phases' deltas
    forwarded onto the same set, and `view_finish`'s choice of row."""
    import types

    from graphite_tpu.memory import engine
    from graphite_tpu.parallel.px import IDENT

    T, DS, DW, SW, C = 8, 8, 4, 2, 16
    rng = np.random.default_rng(100 + seed)
    d, hot = _staged_directory(rng, T, DS, DW, SW, C)
    mp = types.SimpleNamespace(dir_ways=DW, dir_sets=DS)
    # three lines a lane, mostly in the lane's staged sets; some coincide
    sets3 = np.take_along_axis(hot, rng.integers(0, 3, (T, 3)), 1)
    lines = [jnp.asarray(sets3[:, k] + DS * rng.integers(0, 5, T),
                         jnp.int32) for k in range(3)]
    lazy = engine._DirWorkingSet(IDENT, d, mp, lambda: lines)
    eager_px = types.SimpleNamespace(sharded=True, lo=lambda x: x,
                                     ag=lambda x: x)
    eager = engine._DirWorkingSet(eager_px, d, mp, lambda: lines)
    assert lazy.best_rows is not None and eager.best_rows is None
    lt = np.arange(T, dtype=np.int32)[:, None]
    np.testing.assert_array_equal(
        np.asarray(eager.sharer_rows),
        np.asarray(engine._stage_overlay_rows(
            d, jnp.asarray(sets3, jnp.int32), d.sharers[lt, sets3])))
    assert int(jnp.sum(lazy.best_rows > 0)) > 0     # something is staged

    # an earlier phase's plan on view 1's set in half the lanes
    pway = jnp.asarray(rng.integers(0, DW, T), jnp.int32)
    psets = jnp.where(jnp.asarray(rng.random(T) < 0.5),
                      jnp.asarray(sets3[:, 1], jnp.int32),
                      jnp.asarray(rng.integers(0, DS, T), jnp.int32))
    onehot = np.arange(DW)[None, :, None] == np.asarray(pway)[:, None, None]
    pshd = jnp.asarray(np.where(
        onehot, rng.integers(0, 2**32, (T, 1, SW), dtype=np.uint32),
        np.uint32(0)).reshape(T, DW * SW))
    packs = [(psets, pway, jnp.asarray(rng.integers(0, 99, T)), pshd)]

    # the phases before this one appended slots since the index was
    # taken: on the keys that will be read, and on others
    d2 = d
    for _ in range(2):
        d2 = engine._stage_put(
            d2, jnp.asarray(sets3[:, 1], jnp.int32),
            jnp.asarray(rng.integers(0, DW, T), jnp.int32),
            jnp.asarray(rng.random(T) < 0.7),
            jnp.asarray(rng.integers(0, 2**32, (T, SW), dtype=np.uint32)),
            DW)

    if finish:
        # the transaction's line: row 1's set or row 2's, lane by lane
        line = jnp.where(jnp.asarray(rng.random(T) < 0.5), lines[1],
                         lines[2])
        vl, ve = (ws.view_finish(line, packs) for ws in (lazy, eager))
    else:
        vl, ve = (ws.view(1, lines[1], packs) for ws in (lazy, eager))
    np.testing.assert_array_equal(np.asarray(vl.sets), np.asarray(ve.sets))
    ways = [jnp.full(T, w, jnp.int32) for w in range(DW)]
    ways.append(jnp.asarray(rng.integers(0, DW, T), jnp.int32))
    for way in ways:
        got = vl.entry(d2, way)
        want = ve.entry(d, way)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # the row delta of a masked write at that way
        mask = jnp.asarray(rng.random(T) < 0.6)
        new = jnp.asarray(rng.integers(0, 2**32, (T, SW), dtype=np.uint32))
        deltas = []
        for view, dd in ((vl, d2), (ve, d)):
            acc = engine._DirAcc()
            engine._dir_update(dd, view.sets, way, mask, view=view, acc=acc,
                               sharers=new)
            deltas.append(np.asarray(acc.sharers_delta))
        np.testing.assert_array_equal(deltas[0], deltas[1])
        cur = np.asarray(want[3])
        at = (np.arange(DW)[None, :, None] == np.asarray(way)[:, None, None]
              ) & np.asarray(mask)[:, None, None]
        np.testing.assert_array_equal(
            deltas[0].reshape(T, DW, SW),
            np.where(at, (np.asarray(new) - cur)[:, None, :], np.uint32(0)))


def test_a_phase_that_reads_and_updates_one_way_fetches_once():
    """The staged value is memoised per `way` OPERAND: `entry(way)` and
    every `_dir_update` on the same operand share ONE gather of T rows
    of `sval`; another operand fetches again."""
    import types

    from graphite_tpu.memory import engine
    from graphite_tpu.parallel.px import IDENT

    T, DS, DW, SW, C = 8, 8, 4, 2, 16
    rng = np.random.default_rng(7)
    d, hot = _staged_directory(rng, T, DS, DW, SW, C)
    mp = types.SimpleNamespace(dir_ways=DW, dir_sets=DS)
    lines = [jnp.asarray(hot[:, k], jnp.int32) for k in range(3)]

    def phase(d, way, other):
        view = engine._DirWorkingSet(IDENT, d, mp, lambda: lines).view(
            0, lines[0], [])
        acc = engine._DirAcc()
        sharers = view.entry(d, way)[3]
        for k in range(2):
            d = engine._dir_update(d, view.sets, way, sharers[:, 0] % 2 == k,
                                   view=view, acc=acc, sharers=sharers + 1)
        return acc.sharers_delta, view.sharers_at(d, other)

    way = jnp.zeros(T, jnp.int32)
    closed = jax.make_jaxpr(phase)(d, way, way + 1)
    sig = (tuple(d.sval.shape), str(d.sval.dtype))
    gathers, _ = _store_ops(closed, sig)
    assert gathers == 2, gathers      # `way` once, `other` once


def test_staged_iteration_fetches_a_way_a_phase_1024_shape():
    """At the cells' shape the staged iteration gathers `sval` three
    times, `[T, SW]` rows each and each inside its home phase's cond —
    never the `[T, 3, DW, SW]` of the eager overlay (49,152 rows an open
    iteration: PERF.md section 6, PR 46)."""
    from graphite_tpu.analysis.walk import aval_sig, iter_eqns_with_site

    sim = _big_shape_sim(dir_stage=True, inner_block=4)
    closed = _iteration_jaxpr(sim)
    d = sim.state.mem.directory
    sig = (tuple(d.sval.shape), str(d.sval.dtype))
    T, _, SW = d.sval.shape
    outs = [(tuple(eqn.outvars[0].aval.shape), _depth(site))
            for site, eqn in iter_eqns_with_site(closed)
            if eqn.primitive.name == "gather" and eqn.invars
            and not isinstance(eqn.invars[0], Literal)
            and aval_sig(eqn.invars[0].aval) == sig]
    assert [shape for shape, _ in outs] == [(T, SW)] * 3, outs
    assert all(depth >= 1 for _, depth in outs), outs   # inside a cond


# ---- sharded staging: the standing dir_stage gap, closed ------------------


@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 virtual devices")
def test_sharded_dir_stage_matches_single_device():
    """The per-lane staging rows shard with the directory and ride the
    working-set gather block-locally, so a meshed staged
    run must be bit-identical to the single-device staged (and
    unstaged) runs."""
    from graphite_tpu.parallel.mesh import make_tile_mesh
    from graphite_tpu.tools._template import coherence_stress_workload

    sc, batch = coherence_stress_workload(64, protocol=MSI)
    r_solo = Simulator(sc, batch, dir_stage=True, inner_block=4).run()
    r_mesh = Simulator(sc, batch, dir_stage=True, inner_block=4,
                       mesh=make_tile_mesh(8)).run()
    r_uns = Simulator(sc, batch, dir_stage=False, inner_block=4).run()
    _assert_results_equal(r_solo, r_mesh)
    _assert_results_equal(r_solo, r_uns)
    assert int(np.asarray(r_solo.mem_counters["l2_misses"]).sum()) > 0


# ---- golden-oracle exactness (serialized traffic) -------------------------


@pytest.mark.parametrize("proto", [MSI, MOSI, SHL2_MESI])
def test_consolidated_golden_exact(proto):
    """Serialized RMW traffic: the engines (private-L2 MSI/MOSI and
    shared-L2 MESI) stay bit-exact vs the golden interpreters."""
    sc = make_config(4, proto)
    batch = mutex_rmw(4, 4, lines=3)
    res = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0).run()
    gold = run_golden(sc, batch)
    np.testing.assert_array_equal(res.clock_ps, gold.clock_ps)
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)


@pytest.mark.parametrize("proto", [MSI, MOSI])
@pytest.mark.parametrize("gate", [True, False])
def test_consolidated_staged_golden_exact(proto, gate):
    """The staged engine against the golden interpreter, with the
    home-activity gate and the in-place flush gate (`gate`) and with
    every gate off (what a campaign of a staged-size target compiles)."""
    sc = make_config(4, proto)
    batch = mutex_rmw(4, 4, lines=3)
    res = Simulator(sc, batch, phase_gate=gate, mem_gate_bytes=0,
                    dir_stage=True, inner_block=4).run()
    gold = run_golden(sc, batch)
    np.testing.assert_array_equal(res.clock_ps, gold.clock_ps)
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)


# ---- the budget ratchet ---------------------------------------------------


def _fake_report(name="gated-msi", kernels=100, tiles=8):
    from graphite_tpu.analysis.cost import CostReport

    return CostReport(
        program=name, tiles=tiles, n_eqns_total=kernels,
        kernels_per_iter=kernels, bytes_per_iter=10 * kernels,
        arg_bytes=64, out_bytes=64, peak_bytes=1024)


def test_ratchet_refuses_raised_ceiling(tmp_path):
    from graphite_tpu.analysis.cost import (
        BudgetRatchetError, load_budgets, save_budgets,
    )

    path = str(tmp_path / "budgets.json")
    save_budgets([_fake_report(kernels=100)], path)
    # a lower re-measurement ratchets down fine
    save_budgets([_fake_report(kernels=50)], path, ratchet=True)
    assert load_budgets(path)["gated-msi"]["measured"][
        "kernels_per_iter"] == 50
    # a higher one is refused, and the file is untouched
    with pytest.raises(BudgetRatchetError) as e:
        save_budgets([_fake_report(kernels=90)], path, ratchet=True)
    assert "kernels_per_iter" in str(e.value)
    assert load_budgets(path)["gated-msi"]["measured"][
        "kernels_per_iter"] == 50
    # unless the raised metrics are named explicitly
    save_budgets([_fake_report(kernels=90)], path, ratchet=True,
                 allow_increase=("kernels_per_iter", "n_eqns_total",
                                 "bytes_per_iter"))
    assert load_budgets(path)["gated-msi"]["measured"][
        "kernels_per_iter"] == 90


def test_ratchet_cli_self_test(tmp_path, capsys):
    """The CLI fixture: a ratcheted --budget-update against ceilings
    tightened below the real program's cost MUST exit nonzero and write
    nothing — the refusal is the self-test that the ratchet gates."""
    from graphite_tpu.tools.audit import main

    budgets = str(tmp_path / "budgets.json")
    no_lock = str(tmp_path / "absent.lock")
    rc = main(["--programs", "gated-msi", "--budget-update",
               "--budgets-file", budgets, "--lock-file", no_lock])
    assert rc == 0
    with open(budgets) as f:
        data = json.load(f)
    # tighten every ceiling below what the program actually measures
    for m, v in data["gated-msi"]["measured"].items():
        data["gated-msi"]["ceiling"][m] = max(int(v) - 1, 0)
    with open(budgets, "w") as f:
        json.dump(data, f)
    rc = main(["--programs", "gated-msi", "--budget-update", "--ratchet",
               "--budgets-file", budgets, "--lock-file", no_lock])
    out = capsys.readouterr().out
    assert rc == 1
    assert "budget_ratchet_refused" in out
    with open(budgets) as f:
        after = json.load(f)
    assert after["gated-msi"]["ceiling"] == data["gated-msi"]["ceiling"]
    # naming every metric lets the refresh through
    rc = main(["--programs", "gated-msi", "--budget-update", "--ratchet",
               "--budgets-file", budgets, "--lock-file", no_lock]
              + sum((["--allow-increase", m] for m in
                     data["gated-msi"]["measured"]), []))
    assert rc == 0


def test_ratchet_flag_combinations():
    from graphite_tpu.tools.audit import main

    with pytest.raises(SystemExit):
        main(["--ratchet"])                       # needs --budget-update
    with pytest.raises(SystemExit):
        main(["--budget-update", "--allow-increase",
              "kernels_per_iter"])                # needs --ratchet
    with pytest.raises(SystemExit):
        main(["--budget-update", "--ratchet", "--allow-increase",
              "not_a_metric"])                    # unknown metric
