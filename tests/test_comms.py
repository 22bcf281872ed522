"""Collective/ICI traffic analyzer (analysis/comms.py) + the two
round-22 audit rules.

Four layers under test: the extractor/pricer itself (hand-built
shard_map programs per collective kind with EXACT byte/hop
expectations — the ring model's semantics are pinned), phase
attribution on the real per-phase-gated 2D campaign (each px gather
lands on its protocol phase), the lints (the known-bad legacy
unpacked-exchange fixture trips gspmd-insertion naming the phase; the
partial-axis-psum fixture trips replication-drift naming the leak; the
registered mesh programs pass both), and the single-device identity
(every px exchange lowers to ZERO collective equations on a 1-device
tile axis — solo programs provably pay no fabric tax, asserted on the
jaxpr via the extractor)."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from graphite_tpu.analysis import comms, rules
from graphite_tpu.analysis.audit import (
    audit_program, default_programs, spec_from_sweep,
)
from graphite_tpu.analysis.cost import COMMS_METRICS, cost_report
from graphite_tpu.parallel.mesh import TILE_AXIS_2D, _shard_map
from graphite_tpu.parallel.px import SIM_AXIS, ParallelCtx

TILES = 8
DT = 4  # devices on the tile axis in the hand-built programs
TL = TILES // DT


def _mesh():
    return AbstractMesh((DT,), (TILE_AXIS_2D,))


def _lower(body, in_specs, out_specs, *args):
    fn = _shard_map(body, mesh=_mesh(), in_specs=in_specs,
                    out_specs=out_specs)
    return jax.make_jaxpr(fn)(*args)


def _extract(closed, phase_names=()):
    return comms.extract_collectives(
        closed, n_tiles=TILES, phase_names=phase_names,
        axis_env=comms.mesh_axis_sizes(closed))


@pytest.fixture(scope="module")
def mesh_specs():
    """Both registered mesh programs, lowered once per module."""
    return default_programs(
        TILES, names=("sweep-b4-2d", "gated-msi-2d"))


# ---------------------------------------------------------------------------
# extraction + ICI pricing: exact per-kind expectations
# ---------------------------------------------------------------------------


class TestExtraction:
    def test_all_gather_px_exchange(self):
        """A tiled full-axis int64 all_gather of [Tl, 3]: shard = 2*3*8
        = 48 B, ICI = (n-1) x shard = 144 B over n-1 = 3 hops, payload
        = the full [T, 3] buffer = 192 B, kind px-exchange."""
        def body(x):
            return jax.lax.all_gather(x, TILE_AXIS_2D, axis=0,
                                      tiled=True)

        closed = _lower(body, (P(TILE_AXIS_2D),), P(),
                        jax.ShapeDtypeStruct((TILES, 3), jnp.int64))
        (c,) = _extract(closed)
        assert c.primitive == "all_gather"
        assert c.axis_size == DT
        assert c.shard_bytes == TL * 3 * 8 == 48
        assert c.payload_bytes == TILES * 3 * 8 == 192
        assert c.ici_bytes == (DT - 1) * 48 == 144
        assert c.hops == DT - 1 == 3
        assert c.kind == comms.KIND_PX

    def test_psum_replication_reduction(self):
        """A full-axis psum of int64[8]: ring all-reduce pays
        2(n-1)/n x 64 B = 96 B over 3 hops; full-axis psum-likes are
        the declared replication reductions."""
        def body(x):
            return jax.lax.psum(x, TILE_AXIS_2D)

        closed = _lower(body, (P(TILE_AXIS_2D),), P(),
                        jax.ShapeDtypeStruct((TILES,), jnp.int64))
        (c,) = _extract(closed)
        assert c.primitive == "psum"
        assert c.shard_bytes == TL * 8 == 16
        assert c.ici_bytes == (2 * (DT - 1) * 16) // DT == 24
        assert c.hops == DT - 1
        assert c.kind == comms.KIND_REDUCTION

    def test_pmax_over_a_vmap_axis_is_no_collective(self):
        """What `vmap` leaves of a reduction over its OWN named axis (a
        campaign's sim axis, `ParallelCtx.any_sim`) is a `pmax` whose
        `axes` are positional: a reduce over an array axis on one
        device.  It is not listed, priced or counted — the psum over
        the mesh axis beside it in the same program is."""
        px = ParallelCtx(sim_axis=SIM_AXIS)

        def one(row):
            live = px.any_sim(jnp.any(row > 0))
            return jnp.where(live, jax.lax.psum(row, TILE_AXIS_2D), row)

        closed = _lower(jax.vmap(one, axis_name=SIM_AXIS),
                        (P(None, TILE_AXIS_2D),), P(),
                        jax.ShapeDtypeStruct((4, TILES), jnp.int64))
        pmaxes = [e for e in comms.iter_eqns_with_site(closed)
                  if e[1].primitive.name == "pmax"]
        assert [e.params["axes"] for _, e in pmaxes] == [(0,)]
        (c,) = _extract(closed)
        assert c.primitive == "psum" and c.axis_name == TILE_AXIS_2D
        assert c.kind == comms.KIND_REDUCTION

    def test_ppermute_ring_distance(self):
        """A ppermute shifting by 1 on a 4-ring moves its whole payload
        exactly 1 hop; the engine never emits one, so it is a stray."""
        perm = [(i, (i + 1) % DT) for i in range(DT)]

        def body(x):
            return jax.lax.ppermute(x, TILE_AXIS_2D, perm)

        closed = _lower(body, (P(TILE_AXIS_2D),), P(TILE_AXIS_2D),
                        jax.ShapeDtypeStruct((TILES,), jnp.int64))
        (c,) = _extract(closed)
        assert c.primitive == "ppermute"
        assert c.hops == 1
        assert c.ici_bytes == c.shard_bytes == TL * 8
        assert c.kind == comms.KIND_STRAY

    def test_ppermute_long_hop(self):
        """An exchange across the ring diameter (0 <-> 2 on a 4-ring)
        is 2 hops either way round."""
        perm = [(0, 2), (2, 0)]

        def body(x):
            return jax.lax.ppermute(x, TILE_AXIS_2D, perm)

        closed = _lower(body, (P(TILE_AXIS_2D),), P(TILE_AXIS_2D),
                        jax.ShapeDtypeStruct((TILES,), jnp.int64))
        (c,) = _extract(closed)
        assert c.hops == 2
        assert c.ici_bytes == 2 * c.shard_bytes

    def test_all_to_all_pricing(self):
        """all_to_all keeps 1/n of the shard local: (n-1)/n x shard
        crosses the fabric.  Never emitted by the engine -> stray."""
        def body(x):
            return jax.lax.all_to_all(x, TILE_AXIS_2D, split_axis=1,
                                      concat_axis=0, tiled=True)

        closed = _lower(body, (P(TILE_AXIS_2D),), P(TILE_AXIS_2D),
                        jax.ShapeDtypeStruct((TILES, DT), jnp.int64))
        (c,) = _extract(closed)
        assert c.primitive == "all_to_all"
        shard = TL * DT * 8
        assert c.shard_bytes == shard
        assert c.ici_bytes == ((DT - 1) * shard) // DT
        assert c.kind == comms.KIND_STRAY

    def test_grouped_psum_is_stray(self):
        """A partial-axis (grouped) psum is never a declared
        replication reduction: group size replaces n in the pricing and
        the kind is stray."""
        def body(x):
            return jax.lax.psum(x, TILE_AXIS_2D,
                                axis_index_groups=[[0, 1], [2, 3]])

        closed = _lower(body, (P(TILE_AXIS_2D),), P(TILE_AXIS_2D),
                        jax.ShapeDtypeStruct((TILES,), jnp.int64))
        (c,) = _extract(closed)
        assert c.axis_size == 2
        assert c.kind == comms.KIND_STRAY

    def test_uint8_all_gather_is_stray(self):
        """The px whitelist pins the PACKED exchange: every field rides
        the int64 descriptor.  A narrow per-field gather is exactly the
        GSPMD-cliff shape and must classify stray."""
        def body(x):
            return jax.lax.all_gather(x, TILE_AXIS_2D, axis=0,
                                      tiled=True)

        closed = _lower(body, (P(TILE_AXIS_2D),), P(),
                        jax.ShapeDtypeStruct((TILES,), jnp.uint8))
        (c,) = _extract(closed)
        assert c.kind == comms.KIND_STRAY


# ---------------------------------------------------------------------------
# single-device identity: zero collectives on a 1-device tile axis
# ---------------------------------------------------------------------------


class TestSingleDeviceIdentity:
    def test_ctx_not_sharded_on_one_device(self):
        assert not ParallelCtx(axis=TILE_AXIS_2D, n_dev=1).sharded
        assert ParallelCtx(axis=TILE_AXIS_2D, n_dev=2).sharded
        assert not ParallelCtx().sharded

    def test_px_exchange_identity_jaxpr(self):
        """ctx.ag(ctx.lo(x)) on a 1-device tile axis must lower to ZERO
        collective equations (extractor-asserted); the same program on
        2 devices emits exactly one packed all_gather."""
        def body_for(ctx):
            def body(x):
                return ctx.ag(ctx.lo(x))

            return body

        mesh1 = AbstractMesh((1,), (TILE_AXIS_2D,))
        ctx1 = ParallelCtx(axis=TILE_AXIS_2D, n_dev=1)
        fn1 = _shard_map(body_for(ctx1), mesh=mesh1,
                         in_specs=(P(),), out_specs=P())
        closed1 = jax.make_jaxpr(fn1)(
            jax.ShapeDtypeStruct((TILES, 2), jnp.int64))
        assert comms.extract_collectives(
            closed1, n_tiles=TILES,
            axis_env=comms.mesh_axis_sizes(closed1)) == []

        mesh2 = AbstractMesh((2,), (TILE_AXIS_2D,))
        ctx2 = ParallelCtx(axis=TILE_AXIS_2D, n_dev=2)
        fn2 = _shard_map(body_for(ctx2), mesh=mesh2,
                         in_specs=(P(),), out_specs=P())
        closed2 = jax.make_jaxpr(fn2)(
            jax.ShapeDtypeStruct((TILES, 2), jnp.int64))
        cs = comms.extract_collectives(
            closed2, n_tiles=TILES,
            axis_env=comms.mesh_axis_sizes(closed2))
        assert [c.kind for c in cs] == [comms.KIND_PX]

    def test_degenerate_tile_layout_lowers_no_collectives(self):
        """A (db, 1) campaign layout shards only the batch axis; the
        size-1 tile axis must cost nothing — the WHOLE lowered program
        carries zero collective equations."""
        from graphite_tpu.config import ConfigFile, SimConfig
        from graphite_tpu.sweep import SweepRunner
        from graphite_tpu.tools._template import config_text
        from graphite_tpu.trace import synthetic

        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, shared_mem=True, clock_scheme="lax_barrier")))
        traces = [synthetic.memory_stress_trace(
            TILES, n_accesses=16, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.5, seed=s)
            for s in (1, 2)]
        runner = SweepRunner(sc, traces, layout=(2, 1))
        spec = spec_from_sweep("b2x1", runner, 4096)
        assert comms.has_mesh_region(spec.closed)
        assert comms.extract_collectives(
            spec.closed, n_tiles=TILES,
            axis_env=comms.mesh_axis_sizes(spec.closed)) == []
        assert comms.collective_metrics(spec) == {
            "collectives_per_iter": 0, "ici_bytes_per_iter": 0}


# ---------------------------------------------------------------------------
# phase attribution on the real gated 2D campaign
# ---------------------------------------------------------------------------


class TestPhaseAttribution:
    def test_gated_2d_per_phase_counts(self, mesh_specs):
        """The per-phase-gated 2D program emits exactly one packed px
        exchange per exchanging phase — two ride the requester leg (the
        pre-cond working-set gather + the in-cond exchange), one each
        for home_evict, sharer and requester_fill — all px-exchange
        kind over the 2-device tile axis."""
        spec = next(s for s in mesh_specs if s.name == "gated-msi-2d")
        rep = comms.comms_report(spec)
        counts = {r.phase: r.collectives for r in rep.phase_rows()}
        assert counts == {"requester": 2, "home_evict": 1,
                          "sharer": 1, "requester_fill": 1}
        assert all(c.kind == comms.KIND_PX for c in rep.collectives)
        assert all(c.axis_size == 2 for c in rep.collectives)
        assert rep.collectives_per_iter == 5
        assert rep.ici_bytes_per_iter == sum(
            c.ici_bytes for c in rep.collectives) > 0

    def test_vmapped_2d_per_phase_counts(self, mesh_specs):
        """sweep-b4-2d's vmapped layout keeps its phase conds since
        ISSUE 36 (their predicates OR-ed over the cell's sims, the same
        on every device of the tile axis), so its five whitelisted px
        exchanges land on the phases they serve, as in the gated
        program of one sim a cell."""
        spec = next(s for s in mesh_specs if s.name == "sweep-b4-2d")
        rep = comms.comms_report(spec)
        counts = {r.phase: r.collectives for r in rep.phase_rows()}
        assert counts == {"requester": 2, "home_evict": 1,
                          "sharer": 1, "requester_fill": 1}
        assert rep.collectives_per_iter == 5
        assert all(c.kind == comms.KIND_PX for c in rep.collectives)


# ---------------------------------------------------------------------------
# the lints
# ---------------------------------------------------------------------------


class TestGspmdInsertionLint:
    def test_known_bad_fixture_fires_with_phase(self):
        """The legacy unpacked-exchange fixture (one narrow collective
        per field inside a real phase cond) must trip the lint with
        error severity, naming the collectives' protocol phase."""
        spec = comms.gspmd_insertion_fixture(TILES)
        fs = rules.gspmd_insertion(spec.closed, spec.n_tiles,
                                   phase_names=spec.phase_names)
        assert len(fs) == 2
        assert all(f.severity == rules.SEV_ERROR for f in fs)
        assert all("requester" in f.message for f in fs)
        assert all(f.data["kind"] == comms.KIND_STRAY for f in fs)

    def test_fixture_fails_only_gspmd_rule(self):
        """Under the full auditor the fixture's ONLY failing rule is
        gspmd-insertion — the self-test isolates the gate."""
        spec = comms.gspmd_insertion_fixture(TILES)
        results = audit_program(spec)
        failing = [r.rule for r in results if not r.ok]
        assert failing == ["gspmd-insertion"]

    def test_registered_mesh_programs_clean(self, mesh_specs):
        for spec in mesh_specs:
            assert rules.gspmd_insertion(
                spec.closed, spec.n_tiles,
                phase_names=spec.phase_names) == []

    def test_comms_fixture_cli_exits_nonzero(self, capsys):
        """CLI-level acceptance: `--comms-fixture` must exit nonzero
        naming the lint (and refuse a gate armed beside it)."""
        from graphite_tpu.tools.audit import main

        assert main(["--comms-fixture"]) == 1
        assert "gspmd-insertion" in capsys.readouterr().out
        with pytest.raises(SystemExit):
            main(["--comms-fixture", "--lock"])


class TestReplicationDriftLint:
    def test_partial_axis_psum_leak_fires(self):
        """A grouped psum feeding a declared-replicated output is the
        leak the rule exists for: error severity, the grouped psum
        named as the variance source."""
        spec = comms.replication_drift_fixture(TILES, leak=True)
        fs = rules.replication_drift(spec.closed)
        assert len(fs) == 1
        assert fs[0].severity == rules.SEV_ERROR
        assert any(lk["primitive"] == "psum"
                   for lk in fs[0].data["leaks"])

    def test_full_axis_psum_proves_uniform(self):
        spec = comms.replication_drift_fixture(TILES, leak=False)
        assert rules.replication_drift(spec.closed) == []

    def test_registered_mesh_programs_prove_uniform(self, mesh_specs):
        """The engine's replication contract holds on both registered
        mesh programs: every declared-replicated carry slot is provably
        uniform (and each program declares a non-trivial set of them)."""
        for spec in mesh_specs:
            assert rules.replication_drift(spec.closed) == []
            rows = comms.shard_map_uniformity(spec.closed)
            assert rows, spec.name
            assert any(r["declared_replicated"] for r in rows), spec.name


# ---------------------------------------------------------------------------
# budget metric wiring (cost.py)
# ---------------------------------------------------------------------------


class TestBudgetWiring:
    def test_mesh_program_metrics_present(self, mesh_specs):
        spec = next(s for s in mesh_specs if s.name == "gated-msi-2d")
        rep = cost_report(spec)
        m = rep.metrics()
        for k in COMMS_METRICS:
            assert k in m
        assert m["collectives_per_iter"] == 5
        assert m["ici_bytes_per_iter"] > 0

    def test_non_mesh_program_metrics_absent(self):
        """Non-mesh programs carry NO comms keys — the byte-identity
        guarantee for every pre-round-22 BUDGETS.json entry."""
        spec = default_programs(TILES, names=("gated-msi",))[0]
        assert not comms.has_mesh_region(spec.closed)
        assert comms.collective_metrics(spec) is None
        m = cost_report(spec).metrics()
        for k in COMMS_METRICS:
            assert k not in m
