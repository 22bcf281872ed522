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
from graphite_tpu.memory.engine import _run_if
from graphite_tpu.memory.engine_shl2 import (
    ShL2Dir, _dir_apply_rows, _scatter_add_rows,
)
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
    (W, dataclasses.replace(IDENT, sim_axis="sims")),    # a served batch
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
