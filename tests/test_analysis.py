"""Program auditor (round 8): jaxpr invariant lints + trace validation.

Each lint gets a known-bad fixture — a toy program that violates
exactly the property the rule guards (a fat array riding a cond, a
knob the step ignores, a clock downcast to int32, a gate vmapped into
a select, a debug print in the device loop) — proving the rule FIRES,
plus clean fixtures proving it doesn't cry wolf.  The real default
configs (both memory engines + the sweep program) must then pass the
whole rule set, and the engine-level taint test proves time-dtype
threads through the REAL program, not just toys.

Trace validation: malformed campaign traces (unmatched RECV, bad
opcode, short-counted barrier) must fail `sweep/pack.py` fast with a
named TraceValidationError, and every legitimate workload must pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from graphite_tpu.analysis import (
    audit, aval_bytes, default_programs, invar_path_strings, iter_eqns,
    used_invar_mask,
)
from graphite_tpu.analysis import rules
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder
from graphite_tpu.trace.validate import (
    TraceValidationError, validate_batch,
)


# ---- walker ---------------------------------------------------------------


def test_walker_reaches_nested_subjaxprs():
    """cond inside scan inside jit: one traversal sees every level."""

    def inner(c, x):
        return lax.cond(x > 0, lambda v: v + 1.0, lambda v: v - 1.0,
                        c), None

    def f(c, xs):
        return jax.jit(lambda c, xs: lax.scan(inner, c, xs))(c, xs)

    closed = jax.make_jaxpr(f)(0.0, jnp.arange(3.0))
    names = {e.primitive.name for e in iter_eqns(closed)}
    assert {"jit", "scan", "cond"} <= names


def test_used_invar_mask_sees_through_while():
    def f(a, b, unused):
        def body(carry):
            x, k = carry
            return (x + b, k + 1)

        x, _ = lax.while_loop(lambda c: c[1] < 3, body, (a, 0))
        return x

    closed = jax.make_jaxpr(f)(1.0, 2.0, 3.0)
    assert used_invar_mask(closed) == [True, True, False]


def test_aval_bytes():
    closed = jax.make_jaxpr(lambda x: x + 1)(
        jnp.zeros((8, 4), jnp.int64))
    assert aval_bytes(closed.jaxpr.invars[0].aval) == 8 * 4 * 8


# ---- rule 1: cond-payload -------------------------------------------------


def _fat_cond_jaxpr():
    def f(x):
        return lax.cond(x.sum() > 0, lambda v: v * 2, lambda v: v, x)

    return jax.make_jaxpr(f)(jnp.zeros((64, 64), jnp.float32))


def test_cond_payload_fires_on_fat_cond():
    fs = rules.cond_payload(_fat_cond_jaxpr(), max_bytes=1024)
    assert fs and fs[0].rule == "cond-payload"
    assert fs[0].severity == rules.SEV_ERROR
    assert fs[0].data["bytes"] == 64 * 64 * 4


def test_cond_payload_fires_on_forbidden_signature():
    """The round-6 form: a cond output matching the directory-store
    aval is an error at ANY size (batch axes ignored, so the vmapped
    program is covered too)."""
    fs = rules.cond_payload(_fat_cond_jaxpr(),
                            forbidden=(((64, 64), "float32"),))
    assert fs and "forbidden" in fs[0].message

    def batched(p, x):
        return lax.cond(p, lambda v: v * 2, lambda v: v, x)

    cb = jax.make_jaxpr(jax.vmap(batched, in_axes=(None, 0)))(
        True, jnp.zeros((3, 64, 64), jnp.float32))
    # vmap of an unbatched pred keeps the cond; its output is [3,64,64]
    fs = rules.cond_payload(cb, forbidden=(((64, 64), "float32"),))
    assert fs, "batch-axis-prefixed store escaped the signature match"


def test_cond_payload_clean_on_small_cond():
    def f(x):
        return lax.cond(x > 0, lambda v: v + 1, lambda v: v, x)

    closed = jax.make_jaxpr(f)(1.0)
    assert not rules.cond_payload(closed, max_bytes=1024)


# ---- rule 2: knob-fold ----------------------------------------------------


def _toy_knobs():
    from graphite_tpu.sweep.knobs import KNOB_FIELDS, Knobs

    return Knobs(**{f: jnp.asarray(5, jnp.int64) for f in KNOB_FIELDS})


def _knob_invars(args):
    from graphite_tpu.sweep.knobs import KNOB_FIELDS

    paths = invar_path_strings(args)
    return {f: [i for i, p in enumerate(paths) if p.endswith("." + f)]
            for f in KNOB_FIELDS}, paths


def test_knob_fold_fires_when_step_ignores_knob():
    kn = _toy_knobs()

    def bad_step(x, kn):
        # reads ONE knob, constant-folds the rest (the bug: engine read
        # static params instead of the traced leaves)
        return x + kn.dram_latency_ns + 100

    closed = jax.make_jaxpr(bad_step)(jnp.zeros((), jnp.int64), kn)
    knob_invars, paths = _knob_invars((jnp.zeros((), jnp.int64), kn))
    fs = rules.knob_fold(closed, knob_invars, paths)
    folded = {f.data["knob"] for f in fs}
    assert "dram_latency_ns" not in folded
    assert "hop_latency_cycles" in folded and "quantum_ps" in folded
    assert all(f.severity == rules.SEV_ERROR for f in fs)


def test_knob_fold_clean_when_all_consumed():
    kn = _toy_knobs()

    def good_step(x, kn):
        # every knob enters the arithmetic — incl. one only via a
        # while-loop body (the engines' actual shape)
        def body(c):
            return (c[0] + kn.dram_latency_ns + kn.dram_processing_ns
                    + kn.dir_access_cycles + kn.hop_latency_cycles
                    + kn.sync_delay_cycles, c[1] + 1)

        out, _ = lax.while_loop(lambda c: c[1] < kn.quantum_ps,
                                body, (x, jnp.asarray(0, jnp.int64)))
        return out

    closed = jax.make_jaxpr(good_step)(jnp.zeros((), jnp.int64), kn)
    knob_invars, paths = _knob_invars((jnp.zeros((), jnp.int64), kn))
    assert not rules.knob_fold(closed, knob_invars, paths)


# ---- rule 3: time-dtype ---------------------------------------------------


def test_time_dtype_fires_on_clock_downcast():
    def bad(clock_ps):
        return (clock_ps + 5).astype(jnp.int32)

    closed = jax.make_jaxpr(bad)(jnp.zeros(4, jnp.int64))
    fs = rules.time_dtype(closed, [0])
    assert fs and fs[0].rule == "time-dtype"
    assert fs[0].data == {"from": "int64", "to": "int32"}


def test_time_dtype_fires_through_loop_carry():
    """The realistic shape: the clock advances inside a while loop,
    then an accumulation narrows it."""

    def bad(clock_ps):
        def body(c):
            return (c[0] + 1000, c[1] + 1)

        clk, _ = lax.while_loop(lambda c: c[1] < 8, body,
                                (clock_ps, jnp.asarray(0, jnp.int64)))
        return clk.astype(jnp.int32).sum()

    closed = jax.make_jaxpr(bad)(jnp.zeros(4, jnp.int64))
    assert rules.time_dtype(closed, [0])


def test_time_dtype_fires_in_while_cond_jaxpr():
    """A narrowing inside the loop CONDITION, tainted only via the
    carry fixpoint, must be reported too — the cond jaxpr has no
    feedback edges of its own but sees the stabilized carry marks."""

    def bad(clock_ps):
        def cond(c):
            clk, b, k = c
            return (b.astype(jnp.int32) < 100).all() & (k < 3)

        def body(c):
            clk, b, k = c
            return (clk + 1, clk, k + 1)  # copies clock into carry b

        clk, _, _ = lax.while_loop(
            cond, body, (clock_ps, jnp.zeros_like(clock_ps), 0))
        return clk

    closed = jax.make_jaxpr(bad)(jnp.zeros(4, jnp.int64))
    assert rules.time_dtype(closed, [0])


def test_time_dtype_allows_delta_narrowing():
    """A difference of clocks is a DELTA (time_types.DELTA_DTYPE) —
    int32 is the documented discipline, not a violation."""

    def ok(clock_ps):
        lat = clock_ps - jnp.min(clock_ps)
        return lat.astype(jnp.int32)

    closed = jax.make_jaxpr(ok)(jnp.zeros(4, jnp.int64))
    assert not rules.time_dtype(closed, [0])


def test_time_dtype_allows_untainted_narrowing():
    def ok(clock_ps, count):
        return clock_ps + count.astype(jnp.int32).astype(jnp.int64)

    closed = jax.make_jaxpr(ok)(jnp.zeros(4, jnp.int64),
                                jnp.zeros(4, jnp.int64))
    assert not rules.time_dtype(closed, [0])


def test_time_dtype_threads_through_real_engine():
    """Taint from state.core.clock_ps must survive the REAL program:
    narrowing the final clock after run_simulation fires the rule
    (proving the engine-sized taint pass isn't vacuously clean)."""
    from graphite_tpu.analysis.audit import clock_invar_indices
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.engine.step import run_simulation

    tiles = 4
    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier")))
    batch = synthetic.memory_stress_trace(
        tiles, n_accesses=8, working_set_bytes=1 << 10,
        write_fraction=0.4, shared_fraction=0.5, seed=3)
    sim = Simulator(sc, batch, phase_gate=True, mem_gate_bytes=0)
    params, qps = sim.params, sim.quantum_ps

    def bad(st, tr):
        out_st, *_ = run_simulation(params, tr, st, qps, 256)
        return out_st.core.clock_ps.astype(jnp.int32)  # the violation

    closed = jax.make_jaxpr(bad)(sim.state, sim.device_trace)
    paths = invar_path_strings((sim.state, sim.device_trace))
    fs = rules.time_dtype(closed, clock_invar_indices(paths))
    assert fs, "clock taint failed to thread through the engine program"


# ---- rule 4: vmap-gate ----------------------------------------------------


def test_vmap_gate_fires_on_batched_gate():
    T = 4

    def gated(pred, m):
        return lax.cond(pred, lambda v: v + 1, lambda v: v, m)

    closed = jax.make_jaxpr(jax.vmap(gated))(
        jnp.ones(3, bool), jnp.zeros((3, T, T), jnp.uint8))
    fs = rules.vmap_gate(closed, T, expect_gated=True, n_phases=1)
    assert fs and fs[0].severity == rules.SEV_WARNING
    assert fs[0].data["phase_conds"] == 0


def test_vmap_gate_clean_on_real_cond_or_ungated():
    T = 4

    def gated(pred, m):
        return lax.cond(pred, lambda v: v + 1, lambda v: v, m)

    closed = jax.make_jaxpr(gated)(True, jnp.zeros((T, T), jnp.uint8))
    assert not rules.vmap_gate(closed, T, expect_gated=True, n_phases=1)
    # ungated programs never warn, batched or not
    batched = jax.make_jaxpr(jax.vmap(gated))(
        jnp.ones(3, bool), jnp.zeros((3, T, T), jnp.uint8))
    assert not rules.vmap_gate(batched, T, expect_gated=False,
                               n_phases=1)


def test_vmap_gate_clean_on_gated_sweep_runner():
    """End-to-end: a vmapped SweepRunner keeps the memory engine's phase
    gates on by default (ISSUE 36: their predicates are OR-ed over the
    sim axis), and the rule finds every phase cond alive - no batched
    cond in the served program; and no cond carries a directory store."""
    from graphite_tpu.analysis.audit import spec_from_sweep
    from graphite_tpu.sweep import SweepRunner

    runner = SweepRunner(*_gated_sweep_args(), shard_batch=False)
    assert runner.sim.params.mem.phase_gate
    spec = spec_from_sweep("gated-vmap", runner, max_quanta=256)
    assert spec.expect_gated
    assert not rules.vmap_gate(spec.closed, spec.n_tiles,
                               spec.expect_gated, n_phases=spec.n_phases)
    assert len(rules.phase_conds(spec.closed, spec.n_tiles)) \
        == spec.n_phases == 6
    assert spec.forbidden_cond_avals
    assert not rules.cond_payload(spec.closed,
                                  forbidden=spec.forbidden_cond_avals)
    # lowering is abstract: auditing must not materialize the [B, ...]
    # campaign state run() caches for execution
    assert runner._states0 is None


def test_vmap_gate_fires_on_bare_vmap_of_a_gated_engine():
    """... while a bare `vmap` of the same gated program - no named sim
    axis, so nothing to reduce the predicates over - still turns every
    phase cond into both branches and a select, and the rule says so."""
    from graphite_tpu.engine.step import run_simulation
    from graphite_tpu.sweep import SweepRunner

    runner = SweepRunner(*_gated_sweep_args(), shard_batch=False)
    params = runner.sim.params
    assert params.mem.phase_gate
    states, traces, knobs = runner.abstract_inputs()
    closed = jax.make_jaxpr(jax.vmap(
        lambda s, t, k: run_simulation(
            params, t, s, k.quantum_ps, 256, knobs=k)))(
                states, traces, knobs)
    fs = rules.vmap_gate(closed, params.n_tiles, True, n_phases=6)
    assert fs and fs[0].rule == "vmap-gate"
    assert fs[0].data["phase_conds"] == 0


def _gated_sweep_args():
    tiles = 4
    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier")))
    return sc, [synthetic.memory_stress_trace(
        tiles, n_accesses=8, working_set_bytes=1 << 10,
        write_fraction=0.4, shared_fraction=0.5, seed=s)
        for s in (1, 2)]


# ---- rule 5: host-sync ----------------------------------------------------


def test_host_sync_fires_on_debug_print():
    def bad(x):
        jax.debug.print("x = {}", x)
        return x + 1

    fs = rules.host_sync(jax.make_jaxpr(bad)(1.0))
    assert fs and fs[0].rule == "host-sync"


def test_host_sync_fires_on_pure_callback():
    def bad(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct((), x.dtype),
            x)

    fs = rules.host_sync(jax.make_jaxpr(bad)(jnp.asarray(1.0)))
    assert fs


def test_host_sync_clean_on_plain_program():
    assert not rules.host_sync(jax.make_jaxpr(lambda x: x * 2)(1.0))


# ---- rule 6: scatter-determinism ------------------------------------------


def test_scatter_determinism_fires_on_aliasing_replace_scatter():
    """Known-bad fixture: a vmapped replace-combiner scatter whose
    traced index rows can collide — XLA leaves the winner
    implementation-defined, so the round-9 masked-add-scatter contract
    must flag it inside batched programs."""
    def bad(x, idx):
        return x.at[idx].set(1.0)

    cb = jax.make_jaxpr(jax.vmap(bad))(
        jnp.zeros((3, 16)), jnp.zeros((3, 4), jnp.int32))
    fs = rules.scatter_determinism(cb, batched=True)
    assert len(fs) == 1 and fs[0].rule == "scatter-determinism"
    assert fs[0].severity == rules.SEV_WARNING
    assert "implementation-defined" in fs[0].message
    # solo (non-batched) programs only police shard_map interiors:
    # the same scatter at top level is out of scope
    assert not rules.scatter_determinism(cb, batched=False)


def test_scatter_determinism_clean_on_commutative_and_unique():
    """Add-combiner scatters commute; unique_indices is an explicit
    no-alias declaration — neither can be nondeterministic."""
    def add(x, idx):
        return x.at[idx].add(1.0)

    ca = jax.make_jaxpr(jax.vmap(add))(
        jnp.zeros((3, 16)), jnp.zeros((3, 4), jnp.int32))
    assert not rules.scatter_determinism(ca, batched=True)

    def uni(x, idx, v):
        return x.at[idx].set(v, unique_indices=True)

    cu = jax.make_jaxpr(jax.vmap(uni))(
        jnp.zeros((3, 16)), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4)))
    assert not rules.scatter_determinism(cu, batched=True)


def test_scatter_determinism_proves_iota_and_wraparound_indices():
    """Index provenance: an iota row and the engines' wraparound idiom
    (`where(h < T, h, h - T)` — both arms congruent mod T) are
    collision-free by construction, even though the scatter replaces."""
    def iota(x, v):
        return x.at[jnp.arange(4, dtype=jnp.int32)].set(v)

    ci = jax.make_jaxpr(jax.vmap(iota))(
        jnp.zeros((3, 16)), jnp.zeros((3, 4)))
    assert not rules.scatter_determinism(ci, batched=True)

    def wrap(x, h):
        idx = jnp.where(h < 8, h, h - 8) \
            + jnp.arange(8, dtype=jnp.int32)
        idx = jnp.where(idx < 8, idx, idx - 8)
        return x.at[idx].set(1.0, mode="drop")

    cw = jax.make_jaxpr(jax.vmap(wrap, in_axes=(0, None)))(
        jnp.zeros((3, 8)), jnp.asarray(3, jnp.int32))
    assert not rules.scatter_determinism(cw, batched=True)


def test_scatter_determinism_allows_masked_scratch_redirect():
    """The round-9 masked-store idiom: disabled lanes select ONE
    dedicated scratch slot, so colliding "writes" all carry the same
    redirect — masked by construction."""
    def masked(x, word, mask):
        idx = jnp.where(mask, word, 16)
        return x.at[idx].set(1.0, mode="drop")

    cm = jax.make_jaxpr(jax.vmap(masked))(
        jnp.zeros((3, 17)), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4), bool))
    assert not rules.scatter_determinism(cm, batched=True)


def test_scatter_determinism_single_row_is_trivially_safe():
    """A lone index row cannot collide with itself: size-1 row axes
    (and rank-1 indices whose only row axis is a vmap batching dim)
    are out of scope even when the index value is fully opaque."""
    def one_row(x, i, v):
        return x.at[i.reshape(1)].set(v)

    cv = jax.make_jaxpr(jax.vmap(one_row))(
        jnp.zeros((3, 16)), jnp.zeros((3,), jnp.int32),
        jnp.zeros((3,)))
    assert not rules.scatter_determinism(cv, batched=True)

    c1 = jax.make_jaxpr(
        lambda x, i: x.at[i.reshape(1)].set(1.0))(
        jnp.zeros(16), jnp.asarray(5, jnp.int32))
    assert not rules.scatter_determinism(c1, batched=True)


def test_scatter_determinism_masked_redirect_needs_all_operands():
    """A masked redirect combined with an OPAQUE operand is not the
    round-9 idiom: `base + where(mask, 0, S)` still collides at the
    base rows, and an opaque array concatenated next to a masked one
    can alias it — the pass-through must require EVERY non-uniform
    operand to be the masked select, not any one of them."""
    def bad_add(x, base, mask):
        idx = base + jnp.where(mask, 0, 16)
        return x.at[idx].set(1.0, mode="drop")

    ca = jax.make_jaxpr(jax.vmap(bad_add))(
        jnp.zeros((3, 32)), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4), bool))
    assert rules.scatter_determinism(ca, batched=True)

    def bad_cat(x, word, opaque, mask):
        idx = jnp.concatenate([jnp.where(mask, word, 16), opaque])
        return x.at[idx].set(1.0, mode="drop")

    cc = jax.make_jaxpr(jax.vmap(bad_cat))(
        jnp.zeros((3, 17)), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4), jnp.int32), jnp.zeros((3, 4), bool))
    assert rules.scatter_determinism(cc, batched=True)

    # a select whose SIBLING arm is fully opaque is not the idiom
    # either: lanes picking the opaque arm can still collide
    def bad_sel(x, word, opaque, p, mask):
        idx = jnp.where(p, opaque, jnp.where(mask, word, 16))
        return x.at[idx].set(1.0, mode="drop")

    cs = jax.make_jaxpr(jax.vmap(bad_sel))(
        jnp.zeros((3, 17)), jnp.zeros((3, 4), jnp.int32),
        jnp.zeros((3, 4), jnp.int32), jnp.zeros((3, 4), bool),
        jnp.zeros((3, 4), bool))
    assert rules.scatter_determinism(cs, batched=True)


def test_scatter_determinism_const_tables_and_row_axis_limits():
    """A hoisted no-repeat host const index table is collision-free
    (the device_put between the constvar and its use must not hide
    it), but per-axis distinctness proofs stop at ONE multi-size row
    axis: [[0, 1], [1, 0]] is distinct along both axes yet rows (0,0)
    and (1,1) both hold index 0."""
    import numpy as np

    def ok_tbl(x, v):
        return x.at[jnp.asarray(np.arange(4, dtype=np.int32))].set(v)

    ct = jax.make_jaxpr(jax.vmap(ok_tbl))(
        jnp.zeros((3, 16)), jnp.zeros((3, 4)))
    assert not rules.scatter_determinism(ct, batched=True)

    def bad_tbl(x, v):
        tbl = jnp.asarray(np.array([[0, 1], [1, 0]], np.int32))
        return x.at[tbl].set(v)

    c2 = jax.make_jaxpr(jax.vmap(bad_tbl))(
        jnp.zeros((3, 16)), jnp.zeros((3, 2, 2)))
    assert rules.scatter_determinism(c2, batched=True)


# ---- the real configs must pass -------------------------------------------


def test_audit_default_programs_clean():
    """The acceptance gate: gated, ungated, shl2, sweep B=4, the
    telemetry-recording gated engine, the combined sweep+telemetry
    campaign, the 2D batch x tile campaign (round 18), the
    multi-domain DVFS campaign (round 19), the histogram-recording
    gated engine (round 21) AND the per-phase-gated 2D campaign
    (round 22) all pass every rule — the same call
    `python -m graphite_tpu.tools.audit` makes."""
    report = audit(tiles=8)
    assert {r.program for r in report.results} == {
        "gated-msi", "ungated-msi", "shl2-mesi", "sweep-b4",
        "gated-msi-tel", "sweep-b4-tel", "sweep-b4-2d", "sweep-b4-dvfs",
        "gated-msi-hist", "gated-msi-2d"}
    # the sweep programs must get the knob-fold rule, the others not
    by_prog = {}
    for r in report.results:
        by_prog.setdefault(r.program, set()).add(r.rule)
    assert "knob-fold" in by_prog["sweep-b4"]
    assert "knob-fold" in by_prog["sweep-b4-tel"]
    # the 2D campaign's knobs must stay live THROUGH the shard_map
    # call boundary — knob-fold runs (and passes) on the composition
    assert "knob-fold" in by_prog["sweep-b4-2d"]
    # the round-19 multi-domain campaign keeps sync_delay_cycles AND
    # dvfs_domain_mhz live — knob-fold runs (and passes) on it, and
    # the dvfs-off lint covers every default program WITHOUT a spec
    assert "knob-fold" in by_prog["sweep-b4-dvfs"]
    assert "dvfs-off" in by_prog["sweep-b4"]
    assert "dvfs-off" not in by_prog["sweep-b4-dvfs"]
    assert "knob-fold" not in by_prog["gated-msi"]
    # the combined campaign records telemetry, so the telemetry-off
    # lint must NOT run on it (the ring is policed via cond-payload)
    assert "telemetry-off" not in by_prog["sweep-b4-tel"]
    assert "telemetry-off" in by_prog["sweep-b4"]
    # the round-21 histogram program records, so the hist-off lint
    # must NOT run on it; every spec-less program gets it
    assert "hist-off" not in by_prog["gated-msi-hist"]
    assert "hist-off" in by_prog["gated-msi"]
    assert report.ok and not report.findings, "\n".join(
        str(f) for f in report.findings)


def test_default_programs_subset_and_unknown():
    with pytest.raises(ValueError, match="unknown program"):
        default_programs(4, names=["nope"])


def test_memoryless_sweep_audits_clean():
    """Memoryless campaigns never read the memory knobs by design
    (Knobs.from_params zeroes them) — the knob-fold required set must
    shrink to the knobs that CAN enter the program."""
    from graphite_tpu.analysis.audit import audit_program, \
        spec_from_sweep
    from graphite_tpu.sweep import SweepRunner

    bs = []
    for _ in range(4):
        b = TraceBuilder()
        for _ in range(6):
            b.instr(Op.IALU)
        bs.append(b)
    tr = TraceBatch.from_builders(bs)
    cfg = """
[general]
total_cores = 4
mode = lite
[core/static_instruction_costs]
ialu = 1
[clock_skew_management]
scheme = lax_barrier
[clock_skew_management/lax_barrier]
quantum = 1000
"""
    runner = SweepRunner(SimConfig(ConfigFile.from_string(cfg)),
                         [tr, tr])
    spec = spec_from_sweep("memoryless", runner, max_quanta=256)
    assert sorted(spec.knob_invars) == ["quantum_ps"]
    results = audit_program(spec)
    assert all(r.ok for r in results), [
        str(f) for r in results for f in r.findings]


def test_barrier_host_program_audits_clean():
    """lower() must hand the auditor the artifact run() executes: for
    barrier_host sims that is the batched host-dispatch region.  With
    the whole-engine mem_gate ON the gate cond legitimately carries
    the memory state (its size ceiling IS the design), so the
    forbidden-store set empties; with mem_gate forced off the delta
    plans must hold in this program too."""
    from graphite_tpu.analysis.audit import audit_program, \
        spec_from_simulator
    from graphite_tpu.engine.simulator import Simulator

    sc = SimConfig(ConfigFile.from_string(config_text(
        8, shared_mem=True, clock_scheme="lax_barrier")))
    batch = synthetic.memory_stress_trace(
        8, n_accesses=8, working_set_bytes=1 << 10,
        write_fraction=0.4, shared_fraction=0.5, seed=1)
    sim = Simulator(sc, batch, barrier_host=True, barrier_batch=4)
    assert sim.params.mem_gate
    spec = spec_from_simulator("bh-gate", sim, max_quanta=256)
    assert spec.forbidden_cond_avals == ()
    assert all(r.ok for r in audit_program(spec))
    sim2 = Simulator(sc, batch, barrier_host=True, barrier_batch=4,
                     phase_gate=True, mem_gate_bytes=0)
    spec2 = spec_from_simulator("bh-nogate", sim2, max_quanta=256)
    assert spec2.forbidden_cond_avals
    results = audit_program(spec2)
    assert all(r.ok for r in results), [
        str(f) for r in results for f in r.findings]


# ---- trace validation -----------------------------------------------------


def _exit_all(builders):
    return TraceBatch.from_builders(builders)


class TestTraceValidation:
    def test_unmatched_recv_fails(self):
        b0, b1 = TraceBuilder(), TraceBuilder()
        b0.recv(1)          # tile 1 never sends
        b1.instr(Op.IALU)
        with pytest.raises(TraceValidationError,
                           match="guaranteed deadlock"):
            validate_batch(_exit_all([b0, b1]))

    def test_any_sender_recv_counts_against_total(self):
        b0, b1 = TraceBuilder(), TraceBuilder()
        b0.recv(-1)         # wildcard, but nobody sends to tile 0
        b1.instr(Op.IALU)
        with pytest.raises(TraceValidationError, match="RECV more"):
            validate_batch(_exit_all([b0, b1]))

    def test_matched_send_recv_passes(self):
        b0, b1 = TraceBuilder(), TraceBuilder()
        b0.send(1)
        b1.recv(0)
        b1.send(0)
        b0.recv(-1)
        assert validate_batch(_exit_all([b0, b1])) == []

    def test_send_out_of_range_fails(self):
        b0, b1 = TraceBuilder(), TraceBuilder()
        b0.send(7)          # only 2 tiles
        b1.instr(Op.IALU)
        with pytest.raises(TraceValidationError, match="outside"):
            validate_batch(_exit_all([b0, b1]))

    def test_bad_opcode_fails(self):
        b0, b1 = TraceBuilder(), TraceBuilder()
        b0.instr(Op.IALU)
        b1.instr(Op.IALU)
        batch = _exit_all([b0, b1])
        batch.op[0, 0] = 200    # not an Op
        with pytest.raises(TraceValidationError, match="opcodes"):
            validate_batch(batch)

    def test_barrier_short_count_fails(self):
        bs = [TraceBuilder() for _ in range(4)]
        bs[0].barrier_init(3, 3)
        for b in bs[:2]:        # only 2 of 3 participants ever wait
            b.barrier_wait(3)
        with pytest.raises(TraceValidationError, match="stranded"):
            validate_batch(_exit_all(bs))

    def test_barrier_uninitialized_fails(self):
        bs = [TraceBuilder() for _ in range(2)]
        for b in bs:
            b.barrier_wait(5)
        with pytest.raises(TraceValidationError, match="never"):
            validate_batch(_exit_all(bs))

    def test_barrier_inconsistent_count_fails(self):
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(1, 2)
        bs[1].barrier_init(1, 1)
        for b in bs:
            b.barrier_wait(1)
        with pytest.raises(TraceValidationError, match="inconsistent"):
            validate_batch(_exit_all(bs))

    def test_barrier_count_out_of_range_fails(self):
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(1, 9)   # > n_tiles
        for b in bs:
            b.barrier_wait(1)
        with pytest.raises(TraceValidationError, match="outside"):
            validate_batch(_exit_all(bs))

    def test_barrier_id_out_of_range_fails(self):
        """The engine CLIPS barrier ids, so an out-of-range id aliases
        another barrier — reject before the per-id analysis lies."""
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(-1, 2)
        for b in bs:
            b.barrier_wait(-1)
        with pytest.raises(TraceValidationError, match="aliasing"):
            validate_batch(_exit_all(bs))
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(70, 2)
        for b in bs:
            b.barrier_wait(70)
        with pytest.raises(TraceValidationError, match="aliasing"):
            validate_batch(_exit_all(bs), n_barriers=64)
        # in range with the bound supplied: fine
        assert validate_batch(_exit_all(bs), n_barriers=128) == []

    def test_barrier_sync_generation_beyond_releases_fails(self):
        """Engine semantics: BARRIER_SYNC #g blocks until barrier_gen
        reaches g, and barrier_gen only advances arrivals // count
        times — a sync past that is a provable deadlock."""
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(1, 2)
        bs[0].barrier_arrive(1)
        bs[1].barrier_arrive(1)        # 2 arrivals / count 2 -> 1 release
        bs[0].barrier_sync(1, 2)       # waits for release #2
        with pytest.raises(TraceValidationError,
                           match="generation 2"):
            validate_batch(_exit_all(bs))

    def test_barrier_sync_satisfied_generation_passes(self):
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(1, 2)
        bs[0].barrier_arrive(1)
        bs[1].barrier_arrive(1)
        bs[0].barrier_sync(1, 1)
        assert [f for f in validate_batch(_exit_all(bs))
                if f.severity == "error"] == []

    def test_mixed_arrive_remainder_warns_not_raises(self):
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].barrier_init(1, 2)
        bs[0].barrier_arrive(1)    # 1 arrival, count 2, non-blocking
        fs = validate_batch(_exit_all(bs))
        assert fs and all(f.severity == "warning" for f in fs)

    def test_valid_workloads_pass(self):
        batch = synthetic.memory_stress_trace(
            8, n_accesses=24, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.5, seed=3)
        assert validate_batch(batch) == []
        from graphite_tpu.trace.benchmarks import BENCHMARKS

        fft = BENCHMARKS["fft"](8, points_per_tile=16)
        assert [f for f in validate_batch(fft)
                if f.severity == "error"] == []

    def test_pack_traces_validates_and_names_sim(self):
        from graphite_tpu.sweep.pack import pack_traces

        good = synthetic.memory_stress_trace(
            4, n_accesses=8, working_set_bytes=1 << 10,
            write_fraction=0.4, shared_fraction=0.5, seed=1)
        b0 = TraceBuilder()
        b0.recv(1)
        bad = _exit_all([b0] + [TraceBuilder() for _ in range(3)])
        with pytest.raises(TraceValidationError, match="sim 1"):
            pack_traces([good, bad])
        # escape hatch for deliberately pathological traces
        pack = pack_traces([good, bad], validate=False)
        assert pack.n_sims == 2
