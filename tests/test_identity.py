"""Program identity: canonical fingerprints, structural diffs, the
program registry + PROGRAMS.lock (analysis/identity.py, registry.py).

Four layers under test: the canonical form itself (alpha/object-renaming
invariance on retraced programs, sensitivity to one changed literal or
trip count with the divergent equation named), identity of the REAL
audited programs (two independent lowerings of the same config must
fingerprint identically — the acceptance claim bit-identity tests key
off; the intentionally perturbed lock fixture must produce a
phase-attributed diff, not just a failed hash), the registry
(PROGRAMS.lock round-trip, drift/geometry/knob-signature checks,
budget entries resolved through registry keys with stale fingerprints
erroring loudly), and the lower-once plumbing (audit + cost +
fingerprint share ONE tracing per program — `lower_count` is the
probe).
"""

import json

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr
import pytest

from graphite_tpu.analysis import cost, identity, registry
from graphite_tpu.analysis.audit import (
    DEFAULT_PROGRAM_NAMES, default_programs, gated_msi_simulator,
    spec_from_simulator,
)

TILES = 8


@pytest.fixture(scope="module")
def gated_spec():
    """The gated-MSI audited program, lowered once per module."""
    return default_programs(TILES, names=("gated-msi",))[0]


@pytest.fixture(scope="module")
def gated_spec_retraced():
    """A SECOND, independent lowering of the same config — different
    Simulator instance, different trace objects, same program."""
    return spec_from_simulator("gated-msi", gated_msi_simulator(TILES),
                               4096)


@pytest.fixture(scope="module")
def perturbed_spec():
    """The lock fixture: gated-MSI with one perturbed literal inside
    the requester phase cond (L2 data-access latency 8 -> 19)."""
    return registry.lock_regression_fixture(TILES)


# ---------------------------------------------------------------------------
# the canonical form: invariance + sensitivity on small programs
# ---------------------------------------------------------------------------


class TestFingerprint:
    def test_retrace_invariance(self):
        """Retracing a program through eval_jaxpr mints entirely fresh
        Var objects; the canonical numbering (first-appearance order
        per scope) must not see the difference."""
        def f(x):
            y = jnp.sin(x) * 2.0
            return jnp.where(x > 0, y, x).sum()

        c1 = jax.make_jaxpr(f)(jnp.ones(16))
        c2 = jax.make_jaxpr(
            lambda x: jax.core.eval_jaxpr(c1.jaxpr, c1.consts, x))(
            jnp.ones(16))
        assert c1.jaxpr.eqns[0].outvars[0] \
            is not c2.jaxpr.eqns[0].outvars[0]
        assert identity.fingerprint(c1) == identity.fingerprint(c2)
        assert identity.same_program(c1, c2)

    def test_literal_sensitivity_and_diff_names_eqn(self):
        c1 = jax.make_jaxpr(lambda x: jnp.sin(x) + 1.0)(jnp.ones(16))
        c2 = jax.make_jaxpr(lambda x: jnp.sin(x) + 2.0)(jnp.ones(16))
        assert identity.fingerprint(c1) != identity.fingerprint(c2)
        d = identity.structural_diff(c1, c2)
        assert d is not None and d.kind == "operands"
        assert "add" in d.site and "lit(1.0)" in d.detail \
            and "lit(2.0)" in d.detail

    def test_trip_count_sensitivity(self):
        def prog(n):
            def h(x):
                def step(c, _):
                    return c + 1.0, ()
                out, _ = jax.lax.scan(step, x, None, length=n)
                return out
            return jax.make_jaxpr(h)(jnp.ones(8))

        c10, c11 = prog(10), prog(11)
        assert identity.fingerprint(c10) != identity.fingerprint(c11)
        d = identity.structural_diff(c10, c11)
        assert d is not None and d.kind == "params"
        assert "length=10" in d.detail and "length=11" in d.detail

    def test_carried_aval_change_names_signature(self):
        """A widened while carry (the ballooned-buffer regression
        shape) is reported as a region-signature divergence with the
        aval sizes in the message."""
        def prog(n):
            def h(x):
                return jax.lax.while_loop(
                    lambda c: c.sum() < 10.0, lambda c: c + 1.0,
                    jnp.zeros(n) + x.sum())
            return jax.make_jaxpr(h)(jnp.ones(8))

        d = identity.structural_diff(prog(8), prog(1024))
        assert d is not None
        assert d.kind in ("signature", "operands", "outputs")
        assert "float64[8]" in str(d) and "float64[1024]" in str(d)

    def test_diff_none_on_identical(self):
        c = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones(4))
        assert identity.structural_diff(c, c) is None
        assert identity.diff_or_none(c, c) is None

    def test_fingerprint_scheme_prefix(self):
        c = jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones(4))
        fp = identity.fingerprint(c)
        assert fp.startswith(identity.FINGERPRINT_SCHEME + ":")
        assert len(fp.split(":", 1)[1]) == 64

    def test_canonical_lines_are_var_name_free(self):
        """The canonical stream numbers variables by first appearance
        (v0, v1, ...) — jaxpr Var spellings never leak in."""
        c = jax.make_jaxpr(lambda x: jnp.sin(x) + x)(jnp.ones(4))
        lines = identity.canonical_lines(c)
        assert any("v0:" in ln for ln in lines)
        assert all("0x" not in ln for ln in lines)

    def test_set_params_are_hash_seed_free(self):
        """A set-valued param (shard_map's `manual_axes`) renders in
        sorted order: set iteration follows the per-process string hash
        seed, which would make the fingerprint differ between
        processes — and the store is keyed by it."""
        tok = identity._norm_param(frozenset({"tile", "batch"}), None)
        assert tok == "{'batch','tile'}"


# ---------------------------------------------------------------------------
# eqn-count divergences carry the containing phase (round-20 fix)
# ---------------------------------------------------------------------------


PHASE_NAMES = ("requester", "home_evict", "home_start", "sharer",
               "home_finish", "requester_fill")
PC_TILES = 4


class TestEqnCountPhaseAttribution:
    def test_deep_eqn_count_divergence_names_phase(self):
        """An extra trailing equation deep inside a phase cond arm (a
        nested jit region, mimicking the engine's lowering shape) is
        reported as eqn-count WITH the phase whose gating cond encloses
        it — here the third phase cond in program order."""
        def mk(extra):
            def phase(k, x, m, extra_here):
                def inner(v):
                    s = jnp.sum(v * (k + 1.0))
                    if extra_here:
                        s = s * 0.5
                    return s

                def t_arm(x, m):
                    s = jax.jit(inner)(x)
                    return (m + jnp.uint8(1),
                            jnp.int32(k)
                            + jnp.asarray(s, jnp.int32) * 0)

                def f_arm(x, m):
                    return (m, jnp.int32(0))
                return jax.lax.cond(x[0] > k, t_arm, f_arm, x, m)

            def body(c):
                x, m, i = c
                for k in range(4):
                    m, _p = phase(k, x, m, extra and k == 2)
                return (x * 0.99, m, i + 1)

            def fn(x, m):
                return jax.lax.while_loop(
                    lambda c: c[2] < 3, body, (x, m, jnp.int32(0)))
            return jax.make_jaxpr(fn)(
                jnp.ones((8,)),
                jnp.zeros((PC_TILES, PC_TILES), jnp.uint8))

        d = identity.structural_diff(mk(False), mk(True),
                                     n_tiles=PC_TILES,
                                     phase_names=PHASE_NAMES)
        assert d is not None and d.kind == "eqn-count"
        assert d.phase == "home_start"
        assert "cond/branches[1]" in d.site
        assert "extra equation" in d.detail

    def test_subprogram_count_divergence_names_owning_phase(self):
        """The round-20 fix proper: a phase cond whose BRANCH LIST
        changed length (the sub-jaxpr count divergence) must be
        attributed to that cond's OWN phase and reported as eqn-count
        — before the fix it reported kind 'params' with the ENCLOSING
        phase (None at top level), losing the attribution."""
        def t_arm(x, m):
            return (m + jnp.uint8(1), jnp.int32(1))

        def f_arm(x, m):
            return (m, jnp.int32(0))

        def fn(x, m):
            return jax.lax.cond(x[0] > 0, t_arm, f_arm, x, m)

        c = jax.make_jaxpr(fn)(
            jnp.ones((8,)),
            jnp.zeros((PC_TILES, PC_TILES), jnp.uint8))
        j = c.jaxpr
        k = next(i for i, e in enumerate(j.eqns)
                 if e.primitive.name == "cond")
        eqn = j.eqns[k]
        br = tuple(eqn.params["branches"])
        grown = j.replace(eqns=[
            e if i != k else eqn.replace(
                params={**eqn.params, "branches": br + (br[0],)})
            for i, e in enumerate(j.eqns)])
        c2 = ClosedJaxpr(grown, c.consts)
        d = identity.structural_diff(c, c2, n_tiles=PC_TILES,
                                     phase_names=PHASE_NAMES)
        assert d is not None and d.kind == "eqn-count"
        assert d.phase == "requester"
        assert "2 sub-program(s) in A but 3 in B" in d.detail


# ---------------------------------------------------------------------------
# real-program identity: the acceptance claims
# ---------------------------------------------------------------------------


class TestRealProgramIdentity:
    def test_two_independent_lowerings_fingerprint_equal(
            self, gated_spec, gated_spec_retraced):
        """Acceptance: fingerprints are stable across two independent
        traces of the same config."""
        assert identity.fingerprint(gated_spec.closed) \
            == identity.fingerprint(gated_spec_retraced.closed)

    def test_perturbed_program_diff_is_phase_attributed(
            self, gated_spec, perturbed_spec):
        """Acceptance: the lock fixture's diff names the first
        divergent equation AND its protocol phase — "requester ...
        mul lit(8) -> lit(19)", not "hash changed"."""
        assert identity.fingerprint(gated_spec.closed) \
            != identity.fingerprint(perturbed_spec.closed)
        d = identity.diff_or_none(
            gated_spec.closed, perturbed_spec.closed,
            n_tiles=gated_spec.n_tiles,
            phase_names=gated_spec.phase_names)
        assert d is not None
        assert d.phase == "requester"
        assert d.kind == "operands" and "mul" in d.site
        assert "lit(8)" in d.detail and "lit(19)" in d.detail


# ---------------------------------------------------------------------------
# the registry + PROGRAMS.lock
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_record_round_trip(self, gated_spec, tmp_path):
        rec = registry.record_from_spec(gated_spec)
        p = str(tmp_path / "lock.json")
        registry.save_lock([rec], p)
        loaded = registry.load_lock(p)
        assert loaded["gated-msi"] == rec
        assert registry.check_lock([gated_spec], loaded) == []

    def test_save_lock_merges_subset_runs(self, gated_spec, tmp_path):
        p = str(tmp_path / "lock.json")
        other = registry.ProgramRecord("other", "gfp1:" + "a" * 64, 8)
        registry.save_lock([other], p)
        registry.save_lock([registry.record_from_spec(gated_spec)], p)
        loaded = registry.load_lock(p)
        assert set(loaded) == {"other", "gated-msi"}

    def test_drift_geometry_and_knob_checks(self, gated_spec):
        rec = registry.record_from_spec(gated_spec)
        import dataclasses

        drifted = {"gated-msi": dataclasses.replace(
            rec, fingerprint="gfp1:" + "0" * 64)}
        fs = registry.check_lock([gated_spec], drifted)
        assert len(fs) == 1 and "drifted" in fs[0].message

        wrong_tiles = {"gated-msi": dataclasses.replace(rec, tiles=16)}
        fs = registry.check_lock([gated_spec], wrong_tiles)
        assert len(fs) == 1 and "tiles" in fs[0].message

        wrong_knobs = {"gated-msi": dataclasses.replace(
            rec, knobs=("dram_latency_ns",))}
        fs = registry.check_lock([gated_spec], wrong_knobs)
        assert any("knob signature" in f.message for f in fs)

    def test_unregistered_and_stale_entries_error(self, gated_spec):
        fs = registry.check_lock([gated_spec], {})
        assert len(fs) == 1 and "not registered" in fs[0].message
        rec = registry.record_from_spec(gated_spec)
        stale = registry.ProgramRecord("ghost", "gfp1:" + "b" * 64, 8)
        fs = registry.check_lock(
            [gated_spec], {"gated-msi": rec, "ghost": stale},
            expect_complete=True)
        assert len(fs) == 1 and "ghost" in fs[0].message
        # without expect_complete a subset audit ignores the extras
        assert registry.check_lock(
            [gated_spec], {"gated-msi": rec, "ghost": stale}) == []

    def test_checked_in_lock_covers_all_default_programs(self):
        lock = registry.load_lock()
        assert set(DEFAULT_PROGRAM_NAMES) <= set(lock)
        for name in DEFAULT_PROGRAM_NAMES:
            assert lock[name].fingerprint.startswith("gfp1:")
            assert lock[name].tiles == TILES
        # the campaigns register their sweep-knob signature too
        assert lock["sweep-b4"].knobs is not None
        assert "dram_latency_ns" in lock["sweep-b4"].knobs


# ---------------------------------------------------------------------------
# budgets resolve THROUGH the registry
# ---------------------------------------------------------------------------


class TestLockBudgetConsistency:
    def test_checked_in_budgets_match_checked_in_lock(self):
        """CI-consistency acceptance: every BUDGETS.json entry records
        the fingerprint of the program it was measured at, and it
        matches the registered identity under the same key."""
        lock = registry.load_lock()
        budgets = cost.load_budgets()
        for name in DEFAULT_PROGRAM_NAMES:
            rec = lock[name]
            entry = budgets[rec.budget_key]
            assert entry.get("fingerprint") == rec.fingerprint, name

    def test_stale_fingerprint_budget_entry_errors(self, gated_spec):
        rep = cost.cost_report(gated_spec)
        rec = registry.record_from_spec(gated_spec)
        budgets = {"gated-msi": {
            "tiles": TILES, "measured": rep.metrics(),
            "ceiling": {k: v * 2 for k, v in rep.metrics().items()},
            "fingerprint": "gfp1:" + "0" * 64,
        }}
        fs = cost.check_budgets([rep], budgets,
                                registry={"gated-msi": rec})
        assert len(fs) == 1 and "STALE" in fs[0].message
        # matching fingerprint: same ceilings pass
        budgets["gated-msi"]["fingerprint"] = rec.fingerprint
        assert cost.check_budgets([rep], budgets,
                                  registry={"gated-msi": rec}) == []
        # a registered program whose entry has NO fingerprint cannot
        # be staleness-checked — loud error, not silent inheritance
        del budgets["gated-msi"]["fingerprint"]
        fs = cost.check_budgets([rep], budgets,
                                registry={"gated-msi": rec})
        assert len(fs) == 1 and "no fingerprint" in fs[0].message
        # without a registry (pre-round-11 path) it stays lenient
        assert cost.check_budgets([rep], budgets) == []

    def test_budget_key_resolves_renamed_program(self, gated_spec):
        """A registry rename keeps old ceilings reachable through
        budget_key — and the entry is still fingerprint-checked."""
        import dataclasses

        rep = cost.cost_report(gated_spec)
        rep = dataclasses.replace(rep, program="renamed-msi")
        rec = dataclasses.replace(
            registry.record_from_spec(gated_spec), name="renamed-msi",
            budget_key="gated-msi")
        budgets = {"gated-msi": {
            "tiles": TILES, "measured": rep.metrics(),
            "ceiling": {k: v * 2 for k, v in rep.metrics().items()},
            "fingerprint": rec.fingerprint,
        }}
        assert cost.check_budgets([rep], budgets,
                                  registry={"renamed-msi": rec}) == []

    def test_refresh_paths_respect_budget_key(self, gated_spec,
                                              tmp_path):
        """The rename workflow end-to-end: a hand-set budget_key
        survives a --lock-update refresh (record_from_spec only knows
        the name), and save_budgets writes the entry under the SAME
        key check_budget resolves — a refresh after a rename replaces
        the gated entry instead of orphaning a new-name copy."""
        import dataclasses

        lock_p = str(tmp_path / "lock.json")
        rec = dataclasses.replace(registry.record_from_spec(gated_spec),
                                  budget_key="legacy-key")
        registry.save_lock([rec], lock_p)
        registry.save_lock([registry.record_from_spec(gated_spec)],
                           lock_p)
        lock = registry.load_lock(lock_p)
        assert lock["gated-msi"].budget_key == "legacy-key"
        bud_p = str(tmp_path / "budgets.json")
        rep = cost.cost_report(gated_spec)
        cost.save_budgets(
            [rep], bud_p,
            fingerprints={"gated-msi": lock["gated-msi"].fingerprint},
            registry=lock)
        budgets = cost.load_budgets(bud_p)
        assert set(budgets) == {"legacy-key"}
        assert cost.check_budgets([rep], budgets, registry=lock) == []


# ---------------------------------------------------------------------------
# lower-once: one tracing serves audit + cost + fingerprint
# ---------------------------------------------------------------------------


class TestLowerOnce:
    def test_simulator_traces_once_across_consumers(self):
        """The round-11 bugfix: spec building, the cost model, the
        fingerprint and the registry record all consume ONE tracing —
        `lower_count` is the probe."""
        sim = gated_msi_simulator(TILES)
        assert sim.lower_count == 0
        spec = spec_from_simulator("gated-msi", sim, 4096)
        assert sim.lower_count == 1
        closed, paths = sim.lower(4096)          # cache hit
        assert closed is spec.closed
        cost.cost_report(spec)
        identity.fingerprint(spec.closed)
        registry.record_from_spec(spec)
        assert sim.lower_count == 1
        # a different static bound is a different program: new trace
        sim.lower(512)
        assert sim.lower_count == 2

    def test_attach_telemetry_invalidates_lowering_cache(self):
        from graphite_tpu.obs import TelemetrySpec

        sim = gated_msi_simulator(TILES)
        c1, _ = sim.lower(512)
        sim.attach_telemetry(TelemetrySpec(
            sample_interval_ps=1_000_000, n_samples=16))
        c2, _ = sim.lower(512)
        assert sim.lower_count == 2
        assert not identity.same_program(c1, c2)

    def test_sweep_runner_traces_once(self):
        from graphite_tpu.config import ConfigFile, SimConfig
        from graphite_tpu.sweep import SweepRunner
        from graphite_tpu.tools._template import config_text
        from graphite_tpu.trace import synthetic

        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, shared_mem=True, clock_scheme="lax_barrier")))
        traces = [synthetic.memory_stress_trace(
            TILES, n_accesses=8, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.5, seed=s)
            for s in (1, 2)]
        runner = SweepRunner(sc, traces, shard_batch=False)
        c1, _ = runner.lower(4096)
        c2, _ = runner.lower(4096)
        assert c1 is c2 and runner.lower_count == 1

    def test_attach_telemetry_invalidates_sweep_runner_caches(self):
        """attach_telemetry on the WRAPPED sim changes the program the
        campaign executes; a runner built earlier must drop its cached
        lowering (and jitted runner / broadcast states) or lower()
        certifies a different artifact than run() executes."""
        from graphite_tpu.config import ConfigFile, SimConfig
        from graphite_tpu.obs import TelemetrySpec
        from graphite_tpu.sweep import SweepRunner
        from graphite_tpu.tools._template import config_text
        from graphite_tpu.trace import synthetic

        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, shared_mem=True, clock_scheme="lax_barrier")))
        traces = [synthetic.memory_stress_trace(
            TILES, n_accesses=8, working_set_bytes=1 << 12,
            write_fraction=0.4, shared_fraction=0.5, seed=s)
            for s in (1, 2)]
        runner = SweepRunner(sc, traces, shard_batch=False)
        c1, _ = runner.lower(4096)
        runner.sim.attach_telemetry(TelemetrySpec(
            sample_interval_ps=1_000_000, n_samples=16))
        c2, _ = runner.lower(4096)
        assert runner.lower_count == 2
        assert not identity.same_program(c1, c2)


# ---------------------------------------------------------------------------
# CLI: --lock / --lock-update / --lock-fixture
# ---------------------------------------------------------------------------


class TestLockCLI:
    def test_lock_update_then_gate_round_trip(self, tmp_path):
        """--lock-update writes a lock --lock then passes against;
        tampering the registered fingerprint makes the SAME run exit
        nonzero (the gate is live, not decorative)."""
        from graphite_tpu.tools.audit import main

        p = str(tmp_path / "lock.json")
        assert main(["--programs", "gated-msi", "--lock-update",
                     "--lock-file", p]) == 0
        assert main(["--programs", "gated-msi", "--lock",
                     "--lock-file", p]) == 0
        data = json.load(open(p))
        data["gated-msi"]["fingerprint"] = "gfp1:" + "f" * 64
        json.dump(data, open(p, "w"))
        assert main(["--programs", "gated-msi", "--lock",
                     "--lock-file", p]) == 1

    def test_lock_update_refreshes_registry_for_combined_run(
            self, tmp_path):
        """--lock-update --budget in ONE invocation must gate budgets
        against the registry JUST written: ceilings recorded at a
        different fingerprint trip immediately, not only on the next
        plain --budget run."""
        from graphite_tpu.tools.audit import main

        lock_p = str(tmp_path / "lock.json")
        bud_p = str(tmp_path / "budgets.json")
        assert main(["--programs", "gated-msi",
                     "--lock-update", "--lock-file", lock_p,
                     "--budget-update", "--budgets-file", bud_p]) == 0
        data = json.load(open(bud_p))
        data["gated-msi"]["fingerprint"] = "gfp1:" + "0" * 64
        json.dump(data, open(bud_p, "w"))
        assert main(["--programs", "gated-msi",
                     "--lock-update", "--lock-file", lock_p,
                     "--budget", "--budgets-file", bud_p]) == 1

    def test_fixture_excludes_the_other_gate(self):
        """Each fixture self-tests ONE gate: arming the other alongside
        would let its finding carry the nonzero exit even when the gate
        under test is broken (a vacuously green CI self-test)."""
        from graphite_tpu.tools.audit import main

        for argv in (["--regression-fixture", "--lock"],
                     ["--lock-fixture", "--budget"]):
            with pytest.raises(SystemExit) as e:
                main(argv)
            assert e.value.code == 2

    def test_lock_fixture_cli_exits_nonzero(self, capsys):
        """CLI-level acceptance: `--lock-fixture` must exit nonzero
        against the real checked-in PROGRAMS.lock, and the emitted
        diff row must name the divergent equation and its phase."""
        from graphite_tpu.tools.audit import main

        assert main(["--lock-fixture"]) == 1
        rows = [json.loads(ln) for ln in
                capsys.readouterr().out.splitlines() if ln]
        diff = next(r for r in rows if r.get("lock_diff"))
        assert diff["phase"] == "requester"
        assert "mul" in diff["site"]
        lock_rows = [r for r in rows if r.get("rule") == "lock"]
        assert lock_rows and "requester" in lock_rows[0]["message"]
