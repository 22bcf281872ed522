"""The shared-L2 directory's row landing (PR 39): `memory/row_landing.py`
against the XLA scatter-add it replaces on the chip, and the choice
between them.

On a v5e an XLA scatter-add of 1,024 rows onto the 1.07 GB sharers store
costs a pass over the store; `row_landing.land_rows` moves the plan's rows
alone.  The kernel runs here under the Pallas interpreter (`interpret=True`:
the same kernel body, its DMAs and its row adds executed on the CPU); the
TPU compiler is asked about it in `tests/test_chip_compile.py`.

- the kernel lands bit for bit what the scatter lands: rows at the slab
  edges, all-zero deltas, deltas that wrap past 2**32, more than one grid
  step, and a plan under `engine._run_if`'s gate, open and closed;
- the whole engine with every landing through the kernel (16 tiles, a
  128-way slice so that a sharers row is lane-aligned) ends in the state the
  scatter ends in, bit for bit;
- the form is chosen from the lowering target and the operands' shapes:
  at 16 tiles with the default slice, and under a sim axis, the jaxpr is
  the parent's scatter-add letter for letter; at a lane-aligned shape the
  CPU lowers the scatter-add and a TPU target the kernel;
- the auditor accepts the program: its walkers read through a
  `pallas_call`, and the cond-payload rule tells the choice of a lowering
  platform from a run-time `lax.cond`.

The private-L2 directory's staging flush (PR 43): `land_staged` against
the XLA flush (`scatter_staged`) it replaces on the chip, the same way -

- after a flush through the kernel the sharers store, `skey` and `sn` are
  bit for bit the XLA flush's: a key repeated in a lane (the latest slot
  wins), two ways of one set, two sets of one 8-row group, an empty table,
  a full lane, more than one grid step, the gate open and closed;
- a staged private-L2 run (16 tiles, a 128-way directory so that a sharers
  row is lane-aligned) with every flush through the kernel ends in the
  state the XLA flush ends in;
- at 16 tiles with the default directory the flush's choice is the
  parent's scatter letter for letter; at a lane-aligned shape the default
  arm is, and the CPU lowers it and a TPU target the kernel.

The private-L2 directory's entry words (PR 45): `land_entry` on the u32
form of the entry store against the int64 scatter-add it replaces -

- the words after a plan through the kernel are bit for bit the int64
  store's after the scatter-add: no live word, one, a lane's three phases
  in one tile, two ways of one group, a carry out of the low word, a
  negative delta, every word of the plan live, more than one grid step,
  the gate open and closed; and `scatter_entry`, the XLA form on the same
  words, lands the same;
- `_dir_apply_merged` on an int64 store is the parent's, letter for
  letter; on u32 words the CPU lowers the XLA form and a TPU target the
  kernel, named under `gt.mem.entry_land`;
- a staged private-L2 run (16 tiles, the default directory: the smallest
  geometry that takes the u32 form) ends in the same statistics and the
  same `line_census` whichever form its state was built in, and with every
  landing through the kernel.

A campaign's sim axis (PR 52): `flush_staged` and `apply_entry` carry a
batching rule (`row_landing._fold_sims`) that folds B sims of T lanes into
B * T lanes and asks the solo choice of the folded shapes -

- under `vmap` at a lane-aligned folded shape a TPU target lowers exactly
  one kernel and no scatter on the store, the CPU the scatter and no
  kernel; at a folded shape the kernel refuses, the scatter on both;
- `vmap` of either entry point through the interpreted kernel (B = 2 and 4,
  the gate open and closed, an operand that is not batched) lands bit for
  bit what the per-sim scatter form lands and what the solo kernel lands on
  the operands folded by hand.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.memory import engine_shl2, row_landing
from graphite_tpu.memory import state as mem_state
from graphite_tpu.memory.engine import (
    _dir_apply_merged, _run_if, dir_stage_flush, line_census,
)
from graphite_tpu.memory.engine_shl2 import (
    ShL2Dir, _dir_apply_rows, _scatter_add_rows,
)
from graphite_tpu.memory.state import DirectoryArrays
from graphite_tpu.parallel.px import IDENT
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.synthetic import memory_stress_trace

U32 = jnp.uint32
TL, S, W = 16, 16, 128      # 16 slabs of 16 rows; a row is one lane tile


def _plan(case):
    rng = np.random.default_rng(39)
    store = rng.integers(0, 2**32, (TL * S, W), dtype=np.uint32)
    sets = rng.integers(0, S, TL)
    delta = rng.integers(0, 2**32, (TL, W), dtype=np.uint32)
    if case == "slab_edges":
        sets = np.where(np.arange(TL) % 2 == 0, 0, S - 1)
    elif case == "zero_deltas":
        delta = np.zeros_like(delta)
    elif case == "wraps":
        store[:] = 0xFFFFFFF0
        delta[:] = rng.integers(0x10, 0x100, delta.shape, dtype=np.uint32)
    rows = np.arange(TL) * S + sets
    return (jnp.asarray(store), jnp.asarray(rows, jnp.int32),
            jnp.asarray(delta))


@pytest.mark.parametrize("case,step,gate", [
    ("slab_edges", row_landing.ROWS_PER_STEP, None),
    ("zero_deltas", row_landing.ROWS_PER_STEP, None),
    ("wraps", row_landing.ROWS_PER_STEP, None),
    ("random", 8, None),                   # two grid steps
    ("random", row_landing.ROWS_PER_STEP, True),
    ("random", row_landing.ROWS_PER_STEP, False),
])
def test_kernel_lands_what_the_scatter_lands(case, step, gate):
    store, rows, delta = _plan(case)
    want = _scatter_add_rows(store, rows, delta)
    if case == "wraps":
        assert (np.asarray(want)[np.asarray(rows)] < 0x100).all()
    land = functools.partial(row_landing.land_rows, rows_per_step=step,
                             interpret=True)

    @jax.jit
    def run(store, rows, delta, live):
        return _run_if(live, lambda s: land(s, rows, delta), store)

    got = run(store, rows, delta, None if gate is None else jnp.asarray(gate))
    np.testing.assert_array_equal(got, store if gate is False else want)


def test_kernel_refuses_what_it_cannot_land():
    store, rows, delta = _plan("random")
    assert row_landing.can_land(TL, S, W)
    assert not row_landing.can_land(TL, S, 8)           # 16 tiles
    assert not row_landing.can_land(TL, 12, W)          # slabs cut a group
    assert not row_landing.can_land(3 * 256, S, W)      # no whole steps
    with pytest.raises(ValueError):
        row_landing.land_rows(store[:, :8], rows, delta[:, :8])


# ---------------------------------------------------------------------------
# the whole engine, every landing through the kernel
# ---------------------------------------------------------------------------

# a 64 KB, 128-way slice: 8 sets, and at 16 tiles (one sharer word a way)
# a sharers row of 128 words - the smallest lane-aligned embedded directory
WIDE_SLICE = "[l2_cache/T1]\ncache_size = 64\nassociativity = 128\n"


def _sim(wide=True, **kw):
    sc = SimConfig(ConfigFile.from_string(config_text(
        16, core="simple", shared_mem=True, clock_scheme="lax_barrier",
        protocol="pr_l1_sh_l2_mesi") + (WIDE_SLICE if wide else "")))
    return Simulator(sc, memory_stress_trace(
        16, n_accesses=24, working_set_bytes=8192, write_fraction=0.4,
        shared_fraction=0.5, seed=7), mem_gate_bytes=0, **kw)


def test_engine_through_the_kernel_ends_where_the_scatter_ends(monkeypatch):
    """On the CPU `platform_dependent` lowers its default branch, the
    scatter-add; with that branch swapped for the interpreted kernel every
    landing of a run goes through the kernel's body."""
    plain = _sim()
    assert plain.state.mem.dir.sharers.shape == (16, 8, 128)
    plain.run()
    calls = []

    def through_kernel(store, rows, delta):
        calls.append(store.shape)
        return row_landing.land_rows(store, rows, delta, interpret=True)

    monkeypatch.setattr(engine_shl2, "_scatter_add_rows", through_kernel)
    kernel = _sim()
    kernel.run()
    assert calls and set(calls) == {(16 * 8, 128)}
    assert int(np.asarray(plain.state.mem.dir.sharers).any())
    for a, b in zip(jax.tree.leaves(plain.state),
                    jax.tree.leaves(kernel.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# which form, decided from the target and the shapes
# ---------------------------------------------------------------------------


def _parents_landing(d, sets, dwd, dshd):
    """`_dir_apply_rows` as the parent of PR 39 wrote it."""
    Tl, S_, W_ = d.sharers.shape
    lt = jnp.arange(Tl, dtype=jnp.int32)
    return d.replace(
        word=d.word.at[lt, sets].add(
            dwd, unique_indices=True, indices_are_sorted=True),
        sharers=d.sharers.reshape(Tl * S_, W_).at[lt * S_ + sets].add(
            dshd, unique_indices=True, indices_are_sorted=True
        ).reshape(Tl, S_, W_))


def _landing_args(width):
    d = ShL2Dir(word=jnp.zeros((TL, S, 8), jnp.int64),
                sharers=jnp.zeros((TL, S, width), U32))
    return (d, jnp.zeros(TL, jnp.int32), jnp.zeros((TL, 8), jnp.int64),
            jnp.zeros((TL, width), U32))


@pytest.mark.parametrize("width,px", [
    (8, IDENT),                                          # 16 tiles
    # a served batch: the SHARED-L2 landing has not adopted the fold of
    # the two private-L2 landings (PR 52; no cell serves a shared-L2
    # batch), so under a sim axis it still takes the scatter-add
    (W, dataclasses.replace(IDENT, sim_axis="sims")),
], ids=["16-tiles", "sim-axis"])
def test_fallback_is_the_parents_scatter_letter_for_letter(width, px):
    args = _landing_args(width)
    got = jax.make_jaxpr(
        lambda d, *plan: _dir_apply_rows(d, px, *plan))(*args)
    want = jax.make_jaxpr(_parents_landing)(*args)
    assert str(got) == str(want)
    assert "pallas_call" not in str(got) and "cond" not in str(got)


def test_lane_aligned_landing_follows_the_lowering_target():
    args = _landing_args(W)
    traced = jax.jit(
        lambda d, *plan: _dir_apply_rows(d, IDENT, *plan)).trace(*args)
    assert "platform_index" in str(traced.jaxpr)
    scatter, kernel = '"stablehlo.scatter"(', "@tpu_custom_call("
    cpu = traced.lower().as_text()
    assert (cpu.count(scatter), cpu.count(kernel)) == (2, 0)
    tpu = traced.lower(lowering_platforms=("tpu",))
    # (the scatter that stays is the int64 word store's)
    assert (tpu.as_text().count(scatter), tpu.as_text().count(kernel)) == (1, 1)
    assert ("/gt.mem.dir_apply/cond/branch_0_fun/dir_row_landing/pallas_call"
            in tpu.as_text(debug_info=True))


# ---------------------------------------------------------------------------
# the auditor
# ---------------------------------------------------------------------------


def test_auditor_reads_through_the_kernel():
    from graphite_tpu.analysis import cost_report, fingerprint, iter_eqns
    from graphite_tpu.analysis.audit import (
        audit_program, spec_from_simulator,
    )

    spec = spec_from_simulator("shl2-mesi-16-wide", _sim(barrier_host=True))
    names = [e.primitive.name for e in iter_eqns(spec.closed)]
    assert names.count("pallas_call") == 3 and "dma_start" in names
    bad = [(r.rule, f.message) for r in audit_program(spec)
           for f in r.findings]
    assert not bad, bad
    assert fingerprint(spec.closed).startswith("gfp1:")
    assert cost_report(spec).kernels_per_iter > 0


def test_cond_payload_tells_a_platform_choice_from_a_cond():
    from graphite_tpu.analysis import cond_payload

    big = jnp.zeros((64, 128), U32)

    def chosen(x):
        return jax.lax.platform_dependent(
            x, tpu=lambda x: x + U32(1), default=lambda x: x + U32(2))

    def gated(x, p):
        return jax.lax.cond(p, lambda x: x + U32(1), lambda x: x, x)

    kw = dict(max_bytes=1024, forbidden=[((64, 128), "uint32")])
    assert not cond_payload(jax.make_jaxpr(chosen)(big), **kw)
    found = cond_payload(jax.make_jaxpr(gated)(big, True), **kw)
    assert [f.rule for f in found] == ["cond-payload"]


# ---------------------------------------------------------------------------
# the staging flush of the private-L2 directory (PR 43)
# ---------------------------------------------------------------------------

# 16 lanes of 16 sets, 8 ways of 16 words: a sharers row is one lane tile
# and a 128-word column holds all 8 ways; a table of 12 slots a lane
FT, FDS, FDW, FSW, FC = 16, 16, 8, 16, 12


def _table(case):
    """(skey, sn) of a staging table as `_stage_put` leaves one: a lane's
    first `sn` slots live, the rest -1."""
    rng = np.random.default_rng(43)
    sn = np.zeros(FT, np.int32)
    skey = np.full((FT, FC), -1, np.int32)

    def put(lane, keys):
        sn[lane] = len(keys)
        skey[lane, :len(keys)] = keys

    key = lambda s, w: s * FDW + w                      # noqa: E731
    if case == "repeated_key":
        put(2, [key(5, 3), key(9, 1), key(5, 3), key(5, 3)])
        put(7, [key(0, 0), key(0, 0)])
    elif case == "two_ways_one_set":
        put(4, [key(6, 0), key(6, 7), key(6, 2)])
    elif case == "two_sets_one_group":
        put(1, [key(8, 5), key(9, 5), key(15, 5), key(8, 4)])
        put(2, [key(0, 1), key(7, 1)])
    elif case == "full_lane":
        put(3, rng.integers(0, 24, FC))     # 24 keys: sets 0-2, repeats
        put(11, [key(2, 2)])
    elif case == "random":
        for lane in range(FT):
            put(lane, rng.integers(0, FDS * FDW, rng.integers(0, FC + 1)))
    else:
        assert case == "empty_table"
    return skey, sn


def _staged_dir(case):
    rng = np.random.default_rng(4300)
    skey, sn = _table(case)
    return DirectoryArrays(
        entry=jnp.zeros((FT, FDS, FDW), jnp.int64),
        sharers=jnp.asarray(rng.integers(
            0, 2**32, (FT, FDS, FDW * FSW), dtype=np.uint32)),
        skey=jnp.asarray(skey), sn=jnp.asarray(sn),
        # (dead slots hold what earlier blocks staged: noise)
        sval=jnp.asarray(rng.integers(0, 2**32, (FT, FC, FSW),
                                      dtype=np.uint32)))


def _flush_through(form, monkeypatch):
    """`engine.dir_stage_flush`, jitted, with the store's update forced
    through `form` (the chooser stepped over)."""
    monkeypatch.setattr(
        row_landing, "flush_staged",
        lambda sharers, skey, sval, sn: form(sharers, skey, sval, sn))
    return jax.jit(dir_stage_flush)


@pytest.mark.parametrize("case,step,gate", [
    ("repeated_key", row_landing.LANES_PER_STEP, None),
    ("two_ways_one_set", row_landing.LANES_PER_STEP, None),
    ("two_sets_one_group", row_landing.LANES_PER_STEP, None),
    ("empty_table", row_landing.LANES_PER_STEP, None),
    ("full_lane", row_landing.LANES_PER_STEP, None),
    ("random", 8, None),                   # two grid steps a slot index
    ("random", row_landing.LANES_PER_STEP, True),
    ("random", row_landing.LANES_PER_STEP, False),
])
def test_staged_kernel_lands_what_the_xla_flush_lands(case, step, gate,
                                                      monkeypatch):
    d = _staged_dir(case)
    live = None if gate is None else jnp.asarray(gate)
    want = _flush_through(
        lambda sharers, skey, sval, sn: row_landing.scatter_staged(
            sharers, skey, sval), monkeypatch)(d, live)
    got = _flush_through(functools.partial(
        row_landing.land_staged, lanes_per_step=step, interpret=True),
        monkeypatch)(d, live)
    if gate is False:
        want = d        # a closed gate leaves store and table as they are
    else:
        assert not np.asarray(want.sn).any()
        assert (np.asarray(want.skey) == -1).all()
        touched = (np.asarray(want.sharers) != np.asarray(d.sharers)).any()
        assert touched == (case != "empty_table")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if case == "repeated_key":
        # the LATEST slot of the key is what the row holds
        s, w = 5, 3
        np.testing.assert_array_equal(
            np.asarray(got.sharers)[2, s, w * FSW:(w + 1) * FSW],
            np.asarray(d.sval)[2, 3])


def test_staged_kernel_refuses_what_it_cannot_land():
    assert row_landing.can_land_staged(1024, 1024, 16, 32)   # the cell
    assert row_landing.can_land_staged(256, 1024, 16, 32)    # its quarter
    assert row_landing.can_land_staged(FT, FDS, FDW, FSW)
    assert not row_landing.can_land_staged(64, 64, 16, 2)    # 64 tiles
    assert not row_landing.can_land_staged(FT, 12, FDW, FSW)  # cut group
    assert not row_landing.can_land_staged(FT, FDS, 16, 24)  # way / column
    assert not row_landing.can_land_staged(1536, FDS, FDW, FSW)
    d = _staged_dir("random")
    with pytest.raises(ValueError):
        row_landing.land_staged(d.sharers[:, :, :64], d.skey,
                                d.sval[:, :, :8], d.sn)


# a directory of 8 sets x 128 ways a slice: at 16 tiles (one sharer word a
# way) a sharers row of 128 words - the smallest lane-aligned private-L2
# directory
WIDE_DIRECTORY = "[dram_directory]\ntotal_entries = 1024\nassociativity = 128\n"


def _staged_sim(**kw):
    sc = SimConfig(ConfigFile.from_string(config_text(
        16, core="simple", shared_mem=True, clock_scheme="lax_barrier")
        + WIDE_DIRECTORY))
    return Simulator(sc, memory_stress_trace(
        16, n_accesses=24, working_set_bytes=8192, write_fraction=0.4,
        shared_fraction=0.5, seed=7), mem_gate_bytes=0, dir_stage=True,
        inner_block=4, **kw)


def test_staged_engine_through_the_kernel_ends_where_the_xla_flush_ends(
        monkeypatch):
    """On the CPU `platform_dependent` lowers its default branch, the XLA
    flush; with the chooser swapped for the interpreted kernel every flush
    of a run goes through the kernel's body."""
    plain = _staged_sim()
    d = plain.state.mem.directory
    assert d.sharers.shape == (16, 8, 128) and d.skey.shape == (16, 12)
    assert row_landing.can_land_staged(16, 8, 128, 1)
    res_plain = plain.run()
    calls = []

    def through_kernel(sharers, skey, sval, sn):
        calls.append(sharers.shape)
        return row_landing.land_staged(sharers, skey, sval, sn,
                                       interpret=True)

    monkeypatch.setattr(row_landing, "flush_staged", through_kernel)
    kernel = _staged_sim()
    res_kernel = kernel.run()
    assert calls and set(calls) == {(16, 8, 128)}
    assert plain.last_base_skips["flush"] < plain.last_n_iterations // 4
    assert int(np.asarray(plain.state.mem.directory.sharers).any())
    assert res_plain.mem_counters["dir_accesses"].sum() > 0
    for f in dataclasses.fields(res_plain):     # every statistic
        a, b = getattr(res_plain, f.name), getattr(res_kernel, f.name)
        for k in (a if isinstance(a, dict) else [None]):
            np.testing.assert_array_equal(
                np.asarray(a if k is None else a[k]),
                np.asarray(b if k is None else b[k]), err_msg=f.name)
    for a, b in zip(jax.tree.leaves(plain.state),
                    jax.tree.leaves(kernel.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_auditor_reads_through_the_staged_kernel():
    """The flush's choice of a lowering platform returns the sharers
    store - which no run-time `lax.cond` may - and the cond-payload rule
    needs no case for it beyond `walk.is_platform_choice` (PR 39's)."""
    from graphite_tpu.analysis import fingerprint, iter_eqns
    from graphite_tpu.analysis.audit import (
        audit_program, spec_from_simulator,
    )

    spec = spec_from_simulator("coh-16-wide-staged",
                               _staged_sim(barrier_host=True))
    names = [e.primitive.name for e in iter_eqns(spec.closed)]
    assert names.count("pallas_call") == 1 and "dma_start" in names
    bad = [(r.rule, f.message) for r in audit_program(spec)
           for f in r.findings]
    assert not bad, bad
    assert fingerprint(spec.closed).startswith("gfp1:")


def _parents_scatter(sharers, skey, sval, DW):
    """The store's update in `engine.dir_stage_flush` as the parent of
    PR 43 wrote it."""
    from graphite_tpu.intmath import nn_div, nn_mod

    T, DS, _ = sharers.shape
    C, SW = sval.shape[1:]
    tiles = np.arange(T, dtype=np.int32)[:, None]
    valid = skey >= 0
    key = jnp.where(valid, skey, 0)
    w = nn_mod(key, DW)
    s = nn_div(key, DW)
    later = (valid[:, :, None] & valid[:, None, :]
             & (key[:, :, None] == key[:, None, :])
             & (np.arange(C)[None, None, :]
                > np.arange(C)[None, :, None]))
    is_last = valid & ~later.any(axis=2)
    row = sharers[tiles, s]
    row3 = row.reshape(T, C, DW, SW)
    cur = jnp.take_along_axis(
        row3, w[:, :, None, None], axis=2)[:, :, 0]
    delta = jnp.where(is_last[..., None], sval - cur, jnp.uint32(0))
    onehot = (np.arange(DW, dtype=np.int32)[None, None, :, None]
              == w[:, :, None, None])
    row_delta = jnp.where(onehot, delta[:, :, None, :],
                          jnp.uint32(0)).reshape(T, C, DW * SW)
    s_oob = jnp.where(is_last, s, DS)
    return sharers.at[tiles, s_oob].add(row_delta, mode="drop")


def _parents_flush(d, live=None):
    """`engine.dir_stage_flush` as the parent of PR 43 wrote it."""
    def flush(stores):
        sharers, skey, sn = stores
        return (_parents_scatter(sharers, skey, d.sval, d.entry.shape[2]),
                jnp.full_like(skey, -1), jnp.zeros_like(sn))

    sharers, skey, sn = _run_if(live, flush, (d.sharers, d.skey, d.sn))
    return d.replace(sharers=sharers, skey=skey, sn=sn)


def _flush_args(ways, way_width):
    return DirectoryArrays(
        entry=jnp.zeros((FT, FDS, ways), jnp.int64),
        sharers=jnp.zeros((FT, FDS, ways * way_width), U32),
        skey=jnp.full((FT, FC), -1, jnp.int32),
        sval=jnp.zeros((FT, FC, way_width), U32),
        sn=jnp.zeros(FT, jnp.int32)), jnp.asarray(True)


def _letters(jaxpr):
    # (a branch or a call takes its constants as inputs, a traced function
    # closes over them: the `;` between the two lists moves)
    return " ".join(str(jaxpr).replace(";", " ").split())


def _forms(fn, *args):
    """[(scatters, kernels)] of `fn` lowered for the CPU and for a TPU."""
    scatter, kernel = '"stablehlo.scatter"(', "@tpu_custom_call("
    traced = jax.jit(fn).trace(*args)
    return [(text.count(scatter), text.count(kernel)) for text in (
        traced.lower().as_text(),
        traced.lower(lowering_platforms=("tpu",)).as_text())]


def _batch(tree, sims):
    return jax.tree.map(lambda x: jnp.stack([x] * sims), tree)


@pytest.mark.parametrize("ways,way_width,sims", [
    (16, 1, None),                                        # 16 tiles
    (FDW, FSW, 2),                                        # a served batch
], ids=["16-tiles", "sim-axis"])
def test_flush_fallback_is_the_parents_flush_letter_for_letter(
        ways, way_width, sims):
    from graphite_tpu.analysis import iter_eqns

    d, live = _flush_args(ways, way_width)
    if sims is None:
        # the choice is a `custom_vmap_call` whose body, letter for
        # letter, is the parent's scatter-add of row deltas: no kernel, no
        # choice of a platform, on the CPU and for a TPU one scatter
        got = jax.make_jaxpr(dir_stage_flush)(d, live)
        call, = [e for e in iter_eqns(got)
                 if e.primitive.name == "custom_vmap_call"]
        parent = jax.make_jaxpr(
            lambda sharers, skey, sval, sn: _parents_scatter(
                sharers, skey, sval, ways))(d.sharers, d.skey, d.sval, d.sn)
        assert _letters(call.params["call"].jaxpr) == _letters(parent.jaxpr)
        assert ("pallas_call" not in str(got)
                and "platform_index" not in str(got))
        assert _forms(dir_stage_flush, d, live) == [(1, 0), (1, 0)]
        return

    # under `vmap` the rule folds the sims into the lanes and asks again:
    # at a lane-aligned folded shape a TPU target lowers ONE kernel and no
    # scatter on the store, the CPU the scatter and no kernel ...
    def served(d, live):
        return jax.vmap(dir_stage_flush, in_axes=(0, None),
                        axis_name="sims")(d, live)

    assert row_landing.can_land_staged(sims * FT, FDS, ways, way_width)
    assert _forms(served, _batch(d, sims), live) == [(1, 0), (0, 1)]
    folded = str(jax.make_jaxpr(served)(_batch(d, sims), live))
    assert f"u32[{sims * FT},{FDS},{ways * way_width}]" in folded
    # ... and at a folded shape the kernel refuses (16 tiles' rows are not
    # lane-aligned), the scatter on the folded store on both
    narrow, _ = _flush_args(16, 1)
    assert not row_landing.can_land_staged(sims * FT, FDS, 16, 1)
    assert _forms(served, _batch(narrow, sims), live) == [(1, 0), (1, 0)]


def test_lane_aligned_flush_follows_the_lowering_target():
    from graphite_tpu.analysis import iter_eqns
    from graphite_tpu.obs.scopes import scope

    d, live = _flush_args(FDW, FSW)

    def flush(d, live):
        with scope("gt.mem.stage_flush"):
            return dir_stage_flush(d, live)

    traced = jax.jit(flush).trace(d, live)
    assert "platform_index" in str(traced.jaxpr)
    # the default arm IS the parent's flush: the choice's last branch,
    # letter for letter, is the parent's scatter-add of row deltas
    choice, = [e for e in iter_eqns(traced.jaxpr)
               if e.primitive.name == "cond"
               and e.params.get("branches_platforms") is not None]
    assert choice.params["branches_platforms"][-1] is None
    parent = jax.make_jaxpr(
        lambda sharers, skey, sval, sn: _parents_scatter(
            sharers, skey, sval, FDW))(d.sharers, d.skey, d.sval, d.sn)

    assert _letters(choice.params["branches"][-1].jaxpr) == _letters(
        parent.jaxpr)
    scatter, kernel = '"stablehlo.scatter"(', "@tpu_custom_call("
    cpu = traced.lower().as_text()
    assert (cpu.count(scatter), cpu.count(kernel)) == (1, 0)
    tpu = traced.lower(lowering_platforms=("tpu",))
    assert (tpu.as_text().count(scatter), tpu.as_text().count(kernel)) == (0, 1)
    assert ("/gt.mem.stage_flush/while/body/cond/branch_0_fun/"
            "dir_stage_landing/pallas_call" in tpu.as_text(debug_info=True))


# ---------------------------------------------------------------------------
# the entry words of the private-L2 directory (PR 45)
# ---------------------------------------------------------------------------

# 16 lanes of 256 sets (two 128-word columns) and 16 ways (two groups): a
# lane's words are four groups of rows, low ways 0-7, 8-15, high 0-7, 8-15
ET, EDS, EDW, EP = 16, 256, 16, 3
I64 = jnp.int64


def _entry_plan(case):
    """(entry, sets, way, delta, live): an int64 store and a `[3, T]`
    plan as `_dir_apply_merged` folds one - a lane's live words distinct."""
    rng = np.random.default_rng(45)
    entry = rng.integers(0, 2**63, (ET, EDS, EDW), dtype=np.int64)
    sets = rng.integers(0, EDS, (EP, ET))
    way = (rng.integers(0, EDW, ET)[None, :] + np.arange(EP)[:, None]) % EDW
    delta = rng.integers(-2**62, 2**62, (EP, ET), dtype=np.int64)
    live = np.zeros((EP, ET), bool)
    if case == "one_word":
        live[1, 5] = True
    elif case == "three_phases_one_tile":
        # lane 3: sets 130, 140, 255 of column 1, ways 8, 9, 15 of group 1
        sets[:, 3], way[:, 3], live[:, 3] = (130, 140, 255), (8, 9, 15), True
        # lane 9: ONE set, three ways of it
        sets[:, 9], way[:, 9], live[:, 9] = 7, (0, 1, 2), True
    elif case == "two_ways_one_group":
        sets[:2, 6], way[:2, 6], live[:2, 6] = 64, (2, 5), True
        sets[1:, 12], way[1:, 12], live[1:, 12] = (0, 127), 15, True
    elif case == "carry":
        # low words near 2**32, small positive deltas: the carry is all
        # the high word gets; and a borrow: a negative delta on a low 0
        entry[:, :, :] = (entry & ~np.int64(0xFFFFFFFF)) | 0xFFFFFFF0
        delta[:] = rng.integers(0x10, 0x100, delta.shape)
        entry[2, sets[0, 2], way[0, 2]] = np.int64(5) << 32
        delta[0, 2] = -1
        live[:] = True
    elif case == "all_live":
        live[:] = True
    elif case == "random":
        live = rng.random((EP, ET)) < 0.4
        delta[rng.random((EP, ET)) < 0.2] = 0       # a phase that wrote
        #                                             what was there
    else:
        assert case == "no_live_word"
    return (jnp.asarray(entry), jnp.asarray(sets, jnp.int32),
            jnp.asarray(way, jnp.int32), jnp.asarray(delta),
            jnp.asarray(live))


def _int64_scatter(entry, sets, way, delta, live):
    """The parent's landing: one scatter-add on the int64 store."""
    lanes = jnp.where(live, jnp.arange(ET, dtype=jnp.int32)[None, :], ET)
    return entry.at[lanes.ravel(), sets.ravel(), way.ravel()].add(
        delta.ravel(), mode="drop", unique_indices=True)


@pytest.mark.parametrize("case,step,gate", [
    ("no_live_word", row_landing.ENTRY_LANES_PER_STEP, None),
    ("one_word", row_landing.ENTRY_LANES_PER_STEP, None),
    ("three_phases_one_tile", row_landing.ENTRY_LANES_PER_STEP, None),
    ("two_ways_one_group", row_landing.ENTRY_LANES_PER_STEP, None),
    ("carry", row_landing.ENTRY_LANES_PER_STEP, None),
    ("all_live", row_landing.ENTRY_LANES_PER_STEP, None),
    ("all_live", 8, None),                 # two grid steps a phase
    ("random", 8, None),
    ("random", row_landing.ENTRY_LANES_PER_STEP, True),
    ("random", row_landing.ENTRY_LANES_PER_STEP, False),
])
def test_entry_kernel_lands_what_the_int64_scatter_lands(case, step, gate):
    entry, *plan = _entry_plan(case)
    want = _int64_scatter(entry, *plan)
    assert bool((want != entry).any()) == (case != "no_live_word")
    if case == "carry":
        # every landed low word wrapped, and the borrow took one from 5
        hit = np.asarray(want != entry)
        assert (np.asarray(want)[hit] & 0xFFFFFFFF < 0x100).sum() == 47
        assert int(want[2, plan[0][0, 2], plan[1][0, 2]]) == (
            (4 << 32) | 0xFFFFFFFF)
    words = row_landing.entry_words(entry)
    assert words.shape == (ET, 2 * EDW, EDS) and words.dtype == U32
    np.testing.assert_array_equal(row_landing.entry_int64(words), entry)
    land = functools.partial(row_landing.land_entry, lanes_per_step=step,
                             interpret=True)

    @functools.partial(jax.jit, static_argnums=0)
    def run(form, words, plan, live):
        return _run_if(live, lambda s: form(s, *plan), words)

    live = None if gate is None else jnp.asarray(gate)
    for form in (land, row_landing.scatter_entry):
        got = row_landing.entry_int64(run(form, words, plan, live))
        np.testing.assert_array_equal(got, entry if gate is False else want)


def test_entry_kernel_refuses_what_it_cannot_land():
    assert row_landing.can_land_entry(1024, 1024, 16)       # the cell
    assert row_landing.can_land_entry(256, 1024, 16)        # its quarter
    assert row_landing.can_land_entry(ET, EDS, EDW)
    assert not row_landing.can_land_entry(ET, 64, EDW)      # cut column
    assert not row_landing.can_land_entry(ET, EDS, 4)       # cut group
    assert not row_landing.can_land_entry(768, EDS, EDW)    # no whole steps
    entry, *plan = _entry_plan("random")
    with pytest.raises(ValueError):
        row_landing.land_entry(
            row_landing.entry_words(entry[:, :64]), *plan)
    with pytest.raises(ValueError):
        row_landing.land_entry(row_landing.entry_words(entry),
                               *(x[:, :8] for x in plan))


def _parents_apply_merged(d, packs, live=None):
    """`engine._dir_apply_merged` as the parent of PR 45 wrote it
    (staged: the sharers deltas ride the staging table)."""
    Tl = d.entry.shape[0]
    t = np.arange(Tl, dtype=np.int32)

    def land(stores):
        sets = [p[0] for p in packs]
        way = [p[1] for p in packs]
        ed = [p[2] for p in packs]
        shd = [p[3] for p in packs]
        n = len(packs)
        drop_e = [jnp.zeros(Tl, jnp.bool_) for _ in range(n)]
        drop_s = [jnp.zeros(Tl, jnp.bool_) for _ in range(n)]
        for j in range(1, n):
            for i in range(j):
                eq_e = ((sets[i] == sets[j]) & (way[i] == way[j])
                        & ~drop_e[i] & ~drop_e[j])
                ed[i] = ed[i] + jnp.where(eq_e, ed[j], 0)
                drop_e[j] = drop_e[j] | eq_e
                eq_s = (sets[i] == sets[j]) & ~drop_s[i] & ~drop_s[j]
                shd[i] = shd[i] + jnp.where(eq_s[:, None], shd[j],
                                            jnp.zeros_like(shd[j]))
                drop_s[j] = drop_s[j] | eq_s
        t_e = jnp.concatenate([jnp.where(dr, Tl, t) for dr in drop_e])
        s_all = jnp.concatenate(sets)
        w_all = jnp.concatenate(way)
        ed_all = jnp.concatenate(ed)
        return (stores[0].at[t_e, s_all, w_all].add(
            ed_all, mode="drop", unique_indices=True),)

    return d.replace(entry=_run_if(live, land, (d.entry,))[0])


def _apply_args(entry):
    d = DirectoryArrays(
        entry=entry, sharers=jnp.zeros((ET, EDS, EDW * 1), U32),
        skey=jnp.full((ET, FC), -1, jnp.int32),
        sval=jnp.zeros((ET, FC, 1), U32), sn=jnp.zeros(ET, jnp.int32))
    _, sets, way, delta, _ = _entry_plan("random")
    # phase 2 names phase 0's word in the even lanes: folded, dropped
    sets = sets.at[2].set(jnp.where(jnp.arange(ET) % 2 == 0, sets[0],
                                    sets[2]))
    way = way.at[2].set(jnp.where(jnp.arange(ET) % 2 == 0, way[0], way[2]))
    packs = [(sets[p], way[p], delta[p], jnp.zeros((ET, EDW), U32))
             for p in range(EP)]
    return d, packs, jnp.asarray(True)


def test_int64_store_keeps_the_parents_landing_letter_for_letter():
    entry = _entry_plan("random")[0]
    args = _apply_args(entry)
    got = jax.make_jaxpr(
        lambda d, packs, live: _dir_apply_merged(d, IDENT, packs, live))(
        *args)
    want = jax.make_jaxpr(_parents_apply_merged)(*args)
    assert str(got) == str(want)
    assert "pallas_call" not in str(got) and "platform_index" not in str(got)
    # and the words land what it lands, folded duplicates and all
    d, packs, live = args
    words = _dir_apply_merged(
        d.replace(entry=row_landing.entry_words(entry)), IDENT, packs, live)
    np.testing.assert_array_equal(
        row_landing.entry_int64(words.entry),
        _parents_apply_merged(d, packs, live).entry)
    assert bool((words.entry != row_landing.entry_words(entry)).any())


def test_entry_landing_follows_the_lowering_target():
    d, packs, live = _apply_args(
        row_landing.entry_words(_entry_plan("random")[0]))
    # a served batch: the rule folds the two sims' lanes and plans, and
    # the folded shape takes the kernel where a TPU is the target
    sims = dataclasses.replace(IDENT, sim_axis="sims")

    def served(d, packs, live):
        return jax.vmap(
            lambda d, packs: _dir_apply_merged(d, sims, packs, live),
            axis_name="sims")(d, packs)

    assert _forms(served, _batch(d, 2), _batch(packs, 2), live) == [
        (1, 0), (0, 1)]
    folded = str(jax.make_jaxpr(served)(_batch(d, 2), _batch(packs, 2),
                                        live))
    assert (f"u32[{2 * ET},{2 * EDW},{EDS}]" in folded
            and f"i32[{EP},{2 * ET}]" in folded)
    traced = jax.jit(
        lambda d, packs, live: _dir_apply_merged(d, IDENT, packs, live)
    ).trace(d, packs, live)
    assert "platform_index" in str(traced.jaxpr)
    scatter, kernel = '"stablehlo.scatter"(', "@tpu_custom_call("
    cpu = traced.lower().as_text()
    assert (cpu.count(scatter), cpu.count(kernel)) == (1, 0)
    tpu = traced.lower(lowering_platforms=("tpu",))
    assert (tpu.as_text().count(scatter), tpu.as_text().count(kernel)) == (0, 1)
    assert ("/while/body/gt.mem.entry_land/cond/branch_0_fun/"
            "dir_entry_landing/pallas_call" in tpu.as_text(debug_info=True))


def _entry_sim(**kw):
    """16 tiles, staged, the default directory (1,024 sets of 16 ways): the
    smallest geometry whose state is built with the u32 words."""
    sc = SimConfig(ConfigFile.from_string(config_text(
        16, core="simple", shared_mem=True, clock_scheme="lax_barrier")))
    return Simulator(sc, memory_stress_trace(
        16, n_accesses=24, working_set_bytes=8192, write_fraction=0.4,
        shared_fraction=0.5, seed=7), mem_gate_bytes=0, dir_stage=True,
        inner_block=4, **kw)


def test_entry_engine_ends_the_same_in_either_form_and_through_the_kernel(
        monkeypatch):
    words = _entry_sim()
    d = words.state.mem.directory
    assert d.entry.shape == (16, 32, 1024) and d.entry.dtype == U32
    assert mem_state.entry_as_words(words.params.mem)
    res_words = words.run()

    calls = []

    def through_kernel(store, sets, way, delta, live):
        calls.append((store.shape, sets.shape))
        return row_landing.land_entry(store, sets, way, delta, live,
                                      interpret=True)

    with monkeypatch.context() as m:
        m.setattr(row_landing, "apply_entry", through_kernel)
        kernel = _entry_sim()
        res_kernel = kernel.run()
    assert calls and set(calls) == {((16, 32, 1024), (3, 16))}

    # the same geometry with its state built in the int64 form: the
    # parent's program
    monkeypatch.setattr(mem_state, "entry_as_words", lambda mp: False)
    int64 = _entry_sim()
    assert int64.state.mem.directory.entry.shape == (16, 1024, 16)
    assert int64.state.mem.directory.entry.dtype == jnp.int64
    res_int64 = int64.run()

    assert res_words.mem_counters["dir_accesses"].sum() > 0
    assert int(np.asarray(d.entry).any()) == 0      # (the initial state)
    assert np.asarray(words.state.mem.directory.entry).any()
    mp = words.params.mem
    lines = range(0, 8192 // 64 + 64)
    census = line_census(words.state.mem, mp, lines)
    assert any(v["dir"] is not None for v in census.values())
    for sim, res in ((kernel, res_kernel), (int64, res_int64)):
        assert line_census(sim.state.mem, mp, lines) == census
        for f in dataclasses.fields(res_words):     # every statistic
            a, b = getattr(res_words, f.name), getattr(res, f.name)
            for k in (a if isinstance(a, dict) else [None]):
                np.testing.assert_array_equal(
                    np.asarray(a if k is None else a[k]),
                    np.asarray(b if k is None else b[k]), err_msg=f.name)
    np.testing.assert_array_equal(
        row_landing.entry_int64(words.state.mem.directory.entry),
        int64.state.mem.directory.entry)
    for a, b in zip(jax.tree.leaves(words.state),
                    jax.tree.leaves(kernel.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# a campaign's sim axis: B sims of T lanes are B * T lanes (PR 52)
# ---------------------------------------------------------------------------

# (sims, the gate, the operand that is NOT batched)
FOLD_CASES = [(2, None, None), (4, None, None), (2, True, None),
              (2, False, None), (4, None, "shared")]
FOLD_IDS = ["B2", "B4", "B2-open", "B2-closed", "B4-unbatched-operand"]
SCATTER_STAGED, SCATTER_ENTRY = (row_landing.scatter_staged,
                                 row_landing.scatter_entry)


def _fold(x, axis=0):
    """`[B, ...]` with the sim axis merged into a sim's lane axis `axis`,
    by hand."""
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(*x.shape[:axis], -1, *x.shape[axis + 2:])


@pytest.mark.parametrize("sims,gate,shared", FOLD_CASES, ids=FOLD_IDS)
def test_vmapped_flush_through_the_kernel_lands_what_each_sim_lands(
        sims, gate, shared, monkeypatch):
    """`vmap(flush_staged)`: on the CPU the choice lowers its default arm,
    `scatter_staged`; with that arm swapped for the interpreted kernel the
    batch lands through `land_staged` on the FOLDED operands."""
    dirs = [_staged_dir(case) for case in (
        "random", "repeated_key", "two_sets_one_group", "full_lane")[:sims]]
    sharers = jnp.stack([d.sharers + U32(b) for b, d in enumerate(dirs)])
    skey = jnp.stack([d.skey for d in dirs])
    sn = jnp.stack([d.sn for d in dirs])
    # (the table's values: one `[T, C, SW]` array for every sim where the
    # case asks for an operand that is not batched)
    sval = dirs[0].sval if shared else jnp.stack(
        [d.sval + U32(b) for b, d in enumerate(dirs)])
    want = jnp.stack([SCATTER_STAGED(
        sharers[b], skey[b], sval if shared else sval[b])
        for b in range(sims)])
    assert bool((want != sharers).any())
    lanes = []

    def through_kernel(sharers, skey, sval):
        lanes.append(sharers.shape[0])
        return row_landing.land_staged(
            sharers, skey, sval, jnp.sum(skey >= 0, axis=1, dtype=jnp.int32),
            interpret=True)

    monkeypatch.setattr(row_landing, "scatter_staged", through_kernel)
    live = None if gate is None else jnp.asarray(gate)

    @jax.jit
    def served(sharers, skey, sval, sn, live):
        return jax.vmap(
            lambda sharers, skey, sval, sn: _run_if(
                live, lambda s: row_landing.flush_staged(s, skey, sval, sn),
                sharers),
            in_axes=(0, 0, None if shared else 0, 0), axis_name="sims")(
            sharers, skey, sval, sn)

    got = served(sharers, skey, sval, sn, live)
    assert lanes[-1] == sims * FT       # (the rule's trace; FT: the body's)
    np.testing.assert_array_equal(got, sharers if gate is False else want)
    by_hand = row_landing.land_staged(
        _fold(sharers), _fold(skey),
        _fold(jnp.stack([sval] * sims) if shared else sval), _fold(sn),
        interpret=True).reshape(sharers.shape)
    np.testing.assert_array_equal(by_hand, want)


@pytest.mark.parametrize("sims,gate,shared", FOLD_CASES, ids=FOLD_IDS)
def test_vmapped_entry_landing_through_the_kernel_lands_what_each_sim_lands(
        sims, gate, shared, monkeypatch):
    """`vmap(apply_entry)` the same way: the `[B, P, T]` plans reach
    `land_entry` as `[P, B * T]`, the stores as B * T lanes - and where the
    STORE is the operand that is not batched, every sim lands its plan on
    a copy of it."""
    plans = [_entry_plan(case) for case in (
        "random", "three_phases_one_tile", "carry", "all_live")[:sims]]
    words = [row_landing.entry_words(p[0]) for p in plans]
    store = words[0] if shared else jnp.stack(words)
    plan = [jnp.stack([p[i] for p in plans]) for i in range(1, 5)]
    want = jnp.stack([SCATTER_ENTRY(
        store if shared else store[b], *(x[b] for x in plan))
        for b in range(sims)])
    lanes = []

    def through_kernel(store, sets, way, delta, live):
        lanes.append((store.shape[0], sets.shape))
        return row_landing.land_entry(store, sets, way, delta, live,
                                      interpret=True)

    monkeypatch.setattr(row_landing, "scatter_entry", through_kernel)
    live = None if gate is None else jnp.asarray(gate)

    @jax.jit
    def served(store, plan, live):
        return jax.vmap(
            lambda store, *plan: _run_if(
                live, lambda s: row_landing.apply_entry(s, *plan), store),
            in_axes=(None if shared else 0, 0, 0, 0, 0), axis_name="sims")(
            store, *plan)

    got = served(store, plan, live)
    assert lanes[-1] == (sims * ET, (EP, sims * ET))
    whole = jnp.stack([store] * sims) if shared else store
    np.testing.assert_array_equal(got, whole if gate is False else want)
    by_hand = row_landing.land_entry(
        _fold(whole), *(_fold(x, 1) for x in plan),
        interpret=True).reshape(whole.shape)
    np.testing.assert_array_equal(by_hand, want)
