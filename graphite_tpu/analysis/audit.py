"""The program auditor: lower a program, run every applicable lint.

`audit()` is the API the tests (`tests/test_analysis.py`) call;
`python -m graphite_tpu.tools.audit` is the CLI wrapper that emits the
report as JSON lines.  A ProgramSpec bundles one lowered program (a
ClosedJaxpr straight from `jax.make_jaxpr` — no compile needed, so the
auditor runs anywhere, including CPU-only CI) with the context the
rules need: which invars are absolute clocks (time-dtype taint
sources), which are sweep knobs (knob-fold), which aval signatures are
the big directory stores (cond-payload), and whether the program
believes it is phase-gated (vmap-gate).

The default program set mirrors the shapes every perf round is
measured on: the per-phase-GATED private-L2 engine, the UNGATED one,
the shared-L2 engine, the B=4 vmapped sweep campaign, the
telemetry-recording gated engine (round 9 — the timeline ring must
never ride a cond, and telemetry-off programs must carry no trace of
the recording machinery), and the combined sweep-B=4 + telemetry
campaign (round 10 — the composition of the two).
"""

from __future__ import annotations

import dataclasses
import re

from graphite_tpu.analysis import rules
from graphite_tpu.analysis.walk import invar_path_strings  # noqa: F401

# Invar leaves holding ABSOLUTE simulated times (taint sources for the
# time-dtype rule).  Everything matching carries int64 picosecond
# timestamps: running clocks, mailbox/protocol message arrival times,
# sync-object release/arrival/wake times, in-flight DRAM ready times.
# Deliberately NOT matched: *_stall_ps / acc_ps / *lat_ps (durations),
# dyn_ps (per-record costs), quantum/slack scalars.
CLOCK_LEAF_RE = re.compile(
    r"(clock_ps|time_ps|_time$|release_ps|arrival_ps|wake_ps|done_ps"
    r"|ready_ps|seq_ps)")

# Generic cond-payload ceiling: comfortably above every legitimate
# per-phase payload at audited shapes (mailbox matrices, net rings) and
# far below the multi-GB directory stores the rule exists to keep out
# of conds.  The CLI's --max-cond-bytes overrides it.
DEFAULT_MAX_COND_BYTES = 64 << 20


def clock_invar_indices(paths) -> "tuple[int, ...]":
    return tuple(i for i, p in enumerate(paths)
                 if CLOCK_LEAF_RE.search(p))


@dataclasses.dataclass
class ProgramSpec:
    """One lowered program plus the context its lints need."""

    name: str
    closed: object                    # ClosedJaxpr
    invar_paths: "list[str]"
    n_tiles: int
    expect_gated: bool = False
    n_phases: int = 6
    knob_invars: "dict | None" = None   # knob name -> invar indices
    forbidden_cond_avals: tuple = ()    # ((shape, dtype), ...)
    clock_invars: tuple = ()
    # round 9: telemetry-ON programs add the ring's [S, n_series] aval
    # to the cond-payload forbidden set; telemetry-OFF programs run the
    # telemetry-off rule (no telemetry invar, no ring-aval equation —
    # scanned against the canonical dense spec's ring sig)
    expect_telemetry: bool = False
    telemetry_sig: "tuple | None" = None   # ((S, n_series), dtype)
    # additional forbidden ring avals for telemetry-OFF programs
    # (round 14: the dense-plus-energy ring, one series wider — the
    # telemetry-off scan covers the energy series too)
    telemetry_extra_sigs: "tuple" = ()
    # round 16: the spatial profiler's [S, T, m] per-tile ring, policed
    # by the same machinery — profile-ON programs forbid the ring as a
    # cond payload; profile-OFF programs run the profile-off rule over
    # the canonical dense (and dense-plus-energy) per-tile ring sigs
    expect_profile: bool = False
    profile_sig: "tuple | None" = None     # ((S, T, m), dtype)
    profile_extra_sigs: "tuple" = ()
    # round 21: the latency-histogram bucket-count ring ([H, B]
    # aggregate or [T, H, B] per-tile int64) — hist-ON programs forbid
    # the ring as a cond payload; hist-OFF programs run the hist-off
    # rule over the canonical dense (and per-tile / dense-plus-energy)
    # ring sigs
    expect_hist: bool = False
    hist_sig: "tuple | None" = None        # ((H, B) | (T, H, B), dtype)
    hist_extra_sigs: "tuple" = ()
    # round 19: the runtime DVFS manager.  dvfs-ON programs carry the
    # per-domain operating point in the carry (SimState.dvfs_rt);
    # dvfs-OFF programs run the dvfs-off rule — no dvfs_rt invar may
    # survive in the lowering (the same None-adds-no-leaves contract as
    # telemetry/profile; the always-carried legacy `.dvfs.` table does
    # NOT match the `dvfs_rt` key)
    expect_dvfs: bool = False
    # round 10: the engine's protocol-phase names in phase-cond program
    # order, so the cost model (analysis/cost.py) can attribute the
    # per-iteration kernel proxy phase-by-phase
    phase_names: "tuple[str, ...]" = ()
    # round 11: vmapped campaign programs put the WHOLE program in the
    # scatter-determinism rule's scope (solo programs only police
    # shard_map interiors)
    batched: bool = False


def _mem_forbidden_avals(sim):
    """The big directory-store signatures of `sim`'s memory engine —
    the stores the round-6 delta plans keep out of every cond.

    Empty when the whole-engine mem_gate is ON: below its size ceiling
    the gate's lax.cond deliberately carries the ENTIRE memory state —
    directory included — and pays the double-buffer (that ceiling is
    the design; see EngineParams.mem_gate).  The contract "no cond
    output carries a directory store" is the BIG-state regime's
    (mem_gate off, per-phase conds the only gating).

    Signatures shared with a NON-directory state leaf are dropped: an
    aval match cannot tell the store apart from, say, a cache meta
    array of coincidentally equal geometry that legitimately rides the
    phase conds (the shl2 embedded-dir word shares the L2 meta's
    int64[T, S2, W2] aval BY CONSTRUCTION — its sharers rows are the
    observable proxy, detached and re-applied together with it by
    `_cond_dir`).  The phase-gating test picks collision-free geometry
    for the same reason."""
    import jax

    if sim.params.mem is None or sim.params.mem_gate:
        return ()
    if sim.params.mem.protocol.startswith("pr_l1_sh_l2"):
        from graphite_tpu.memory.engine_shl2 import dir_store_avals
    else:
        from graphite_tpu.memory.engine import dir_store_avals
    sigs = dir_store_avals(sim.state.mem)
    leaves, _ = jax.tree_util.tree_flatten_with_path(sim.state)
    non_dir = set()
    for p, leaf in leaves:
        path = jax.tree_util.keystr(p)
        if ".directory." not in path and ".dir." not in path \
                and hasattr(leaf, "shape"):
            non_dir.add((tuple(leaf.shape), str(leaf.dtype)))
    return tuple(s for s in sigs if s not in non_dir)


def _telemetry_fields(sim):
    """The telemetry policing shared by both spec builders:
    (extra forbidden cond avals, expect_telemetry, telemetry_sig).

    Telemetry-ON programs forbid the attached spec's actual ring as a
    cond payload (the [S, n] store would be double-buffered per
    iteration — the round-6 pathology the masked scatter-append
    avoids).  Telemetry-OFF programs get the canonical DENSE spec's
    ring sig (default S, every available series) — the shape an
    accidentally-hard-coded internal recorder would materialize, so
    the telemetry-off aval scan stays a live check instead of only
    policing carry invars — plus (round 14) the dense-plus-energy
    ring, one series wider, so the scan covers the opt-in `energy_pj`
    series too."""
    tel = sim.telemetry_spec
    if tel is not None:
        return (tel.buffer_sig(),), True, tel.buffer_sig(), ()
    from graphite_tpu.obs.telemetry import EnergyPrices, TelemetrySpec

    dense_sig = TelemetrySpec(sample_interval_ps=1).resolve(
        sim.params).buffer_sig()
    energy_sig = TelemetrySpec(
        sample_interval_ps=1,
        energy_prices=EnergyPrices()).resolve(sim.params).buffer_sig()
    return (), False, dense_sig, (energy_sig,)


def _profile_fields(sim):
    """The spatial-profiler policing shared by both spec builders:
    (extra forbidden cond avals, expect_profile, profile_sig,
    profile_extra_sigs) — the round-16 twin of `_telemetry_fields`.
    Profile-ON programs forbid the attached spec's actual [S, T, m]
    ring as a cond payload; profile-OFF programs get the canonical
    dense per-tile ring sig (default S, every available tile series)
    plus the dense-plus-energy variant, so the profile-off aval scan
    stays a live check."""
    prof = getattr(sim, "profile_spec", None)
    if prof is not None:
        return (prof.buffer_sig(),), True, prof.buffer_sig(), ()
    from graphite_tpu.obs.profile import ProfileSpec
    from graphite_tpu.obs.telemetry import EnergyPrices

    dense_sig = ProfileSpec(sample_interval_ps=1).resolve(
        sim.params).buffer_sig()
    energy_sig = ProfileSpec(
        sample_interval_ps=1,
        energy_prices=EnergyPrices()).resolve(sim.params).buffer_sig()
    return (), False, dense_sig, (energy_sig,)


def _hist_fields(sim):
    """The latency-histogram policing shared by both spec builders:
    (extra forbidden cond avals, expect_hist, hist_sig,
    hist_extra_sigs) — the round-21 twin of `_profile_fields`.
    Hist-ON programs forbid the attached spec's actual bucket-count
    ring as a cond payload; hist-OFF programs get the canonical dense
    aggregate [H, B] ring sig plus the per-tile [T, H, B] and
    dense-plus-energy variants, so the hist-off aval scan stays a live
    check for every recording layout."""
    hs = getattr(sim, "hist_spec", None)
    if hs is not None:
        return (hs.buffer_sig(),), True, hs.buffer_sig(), ()
    from graphite_tpu.obs.hist import HistSpec
    from graphite_tpu.obs.telemetry import EnergyPrices

    dense_sig = HistSpec().resolve(sim.params).buffer_sig()
    tile_sig = HistSpec(per_tile=True).resolve(sim.params).buffer_sig()
    energy_sig = HistSpec(
        energy_prices=EnergyPrices()).resolve(sim.params).buffer_sig()
    return (), False, dense_sig, (tile_sig, energy_sig)


def spec_from_simulator(name: str, sim,
                        max_quanta: int = 4096) -> ProgramSpec:
    """Lower a Simulator's single-device resident program into a spec."""
    from graphite_tpu.engine.simulator import mem_phase_names

    closed, paths = sim.lower(max_quanta)
    expect_gated = (sim.params.mem is not None
                    and bool(sim.params.mem.phase_gate))
    phase_names = (tuple(mem_phase_names(sim.params))
                   if sim.params.mem is not None else ())
    n_phases = len(phase_names) if phase_names else 6
    tel_forbidden, expect_tel, tel_sig, tel_extra = \
        _telemetry_fields(sim)
    prof_forbidden, expect_prof, prof_sig, prof_extra = \
        _profile_fields(sim)
    hist_forbidden, expect_hist, hist_sig, hist_extra = \
        _hist_fields(sim)
    return ProgramSpec(
        name=name, closed=closed, invar_paths=paths,
        n_tiles=sim.params.n_tiles, expect_gated=expect_gated,
        n_phases=n_phases,
        forbidden_cond_avals=(_mem_forbidden_avals(sim) + tel_forbidden
                              + prof_forbidden + hist_forbidden),
        clock_invars=clock_invar_indices(paths),
        expect_telemetry=expect_tel,
        telemetry_sig=tel_sig,
        telemetry_extra_sigs=tel_extra,
        expect_profile=expect_prof,
        profile_sig=prof_sig,
        profile_extra_sigs=prof_extra,
        expect_hist=expect_hist,
        hist_sig=hist_sig,
        hist_extra_sigs=hist_extra,
        expect_dvfs=getattr(sim, "dvfs_spec", None) is not None,
        phase_names=phase_names)


def spec_from_sweep(name: str, runner,
                    max_quanta: int = 4096) -> ProgramSpec:
    """Lower a SweepRunner's batched campaign program into a spec,
    mapping each sweep knob to its traced invar indices (knob-fold)."""
    from graphite_tpu.engine.simulator import mem_phase_names
    from graphite_tpu.sweep.knobs import KNOB_FIELDS

    closed, paths = runner.lower(max_quanta)
    knob_invars = {
        f: [i for i, p in enumerate(paths) if p.endswith("." + f)]
        for f in KNOB_FIELDS
    }
    if runner.knobs.dvfs_domain_mhz is not None:
        # the domain-frequency axis is a traced knob too: its invars
        # must stay live through the carried-frequency reads (knob-fold
        # proves a config that silently ignores the grid)
        from graphite_tpu.sweep.knobs import DVFS_KNOB_FIELD

        knob_invars[DVFS_KNOB_FIELD] = [
            i for i, p in enumerate(paths)
            if p.endswith("." + DVFS_KNOB_FIELD)]
    if runner.sim.quantum_ps is None:
        # unbounded clock schemes have no quantum for the knob to steer
        knob_invars.pop("quantum_ps", None)
    sim = runner.sim
    mp = sim.params.mem
    if mp is None:
        # memoryless campaigns never read the memory knobs by design
        # (Knobs.from_params zeroes them) — requiring them would fail
        # every healthy memoryless sweep
        from graphite_tpu.sweep.knobs import MEM_KNOB_FIELDS

        for f in MEM_KNOB_FIELDS:
            knob_invars.pop(f, None)
    elif len(set(mp.module_domains)) == 1:
        # single-DVFS-domain configs short-circuit every cross-domain
        # handoff to a Python 0 (MemParams.sync_cycles), so the sync
        # knob is structurally inert — not a folding bug.  Multi-domain
        # configs keep it in the required set.
        knob_invars.pop("sync_delay_cycles", None)
    expect_gated = (sim.params.mem is not None
                    and bool(sim.params.mem.phase_gate))
    phase_names = (tuple(mem_phase_names(sim.params))
                   if sim.params.mem is not None else ())
    n_phases = len(phase_names) if phase_names else 6
    tel_forbidden, expect_tel, tel_sig, tel_extra = \
        _telemetry_fields(sim)
    prof_forbidden, expect_prof, prof_sig, prof_extra = \
        _profile_fields(sim)
    hist_forbidden, expect_hist, hist_sig, hist_extra = \
        _hist_fields(sim)
    return ProgramSpec(
        name=name, closed=closed, invar_paths=paths,
        n_tiles=sim.params.n_tiles, expect_gated=expect_gated,
        n_phases=n_phases, knob_invars=knob_invars,
        forbidden_cond_avals=(_mem_forbidden_avals(sim) + tel_forbidden
                              + prof_forbidden + hist_forbidden),
        clock_invars=clock_invar_indices(paths),
        expect_telemetry=expect_tel,
        telemetry_sig=tel_sig,
        telemetry_extra_sigs=tel_extra,
        expect_profile=expect_prof,
        profile_sig=prof_sig,
        profile_extra_sigs=prof_extra,
        expect_hist=expect_hist,
        hist_sig=hist_sig,
        hist_extra_sigs=hist_extra,
        expect_dvfs=getattr(sim, "dvfs_spec", None) is not None,
        phase_names=phase_names,
        batched=not runner.shard_batch or runner._sims_per_dev > 1)


# ---------------------------------------------------------------------------
# default program set
# ---------------------------------------------------------------------------


DEFAULT_PROGRAM_NAMES = ("gated-msi", "ungated-msi", "shl2-mesi",
                         "sweep-b4", "gated-msi-tel", "sweep-b4-tel",
                         "sweep-b4-2d", "sweep-b4-dvfs",
                         "gated-msi-hist", "gated-msi-2d")

# cache/directory geometry chosen so the directory entry/sharers avals
# are UNIQUE in the program (same trick as the phase-gating test) — a
# cache meta array of coincidentally equal shape would make the
# cond-payload signature check blind to the store
AUDIT_GEOMETRY = """
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


def _audit_trace(tiles: int):
    from graphite_tpu.trace import synthetic

    return synthetic.memory_stress_trace(
        tiles, n_accesses=16, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=7)


def gated_msi_simulator(tiles: int = 8, extra_cfg: str = ""):
    """The audited gated-MSI Simulator, optionally with `extra_cfg` INI
    appended — the hook registry.lock_regression_fixture uses to lower
    the SAME program shape with one intentionally perturbed literal."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.tools._template import config_text

    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier")
        + AUDIT_GEOMETRY + extra_cfg))
    return Simulator(sc, _audit_trace(tiles), phase_gate=True,
                     mem_gate_bytes=0)


def default_programs(tiles: int = 8, max_quanta: int = 4096,
                     names=None) -> "list[ProgramSpec]":
    """The ten audited shapes: gated, ungated, shl2, sweep B=4, the
    telemetry-recording gated engine (round 9: the ring's aval joins
    the cond-payload forbidden set; telemetry-OFF programs additionally
    run the telemetry-off lint), the COMBINED sweep-B=4 + telemetry
    campaign (round 10: campaign timelines were previously only audited
    solo, so the [B, S, n_series] ring under vmap never met the
    cond-payload or knob-fold lints — the composition is audited now),
    and the 2D batch x tile sweep campaign (round 18: the same B=4
    sweep on a 2x2 Mesh(('batch','tile')) with the packed tile-axis
    exchange, lowered over a device-less AbstractMesh), and the
    runtime-DVFS sweep campaign (round 19: a genuinely two-domain
    config sweeping a dvfs_domain_mhz grid — the carried-frequency
    program where both the sync-delay knob and the frequency grid must
    prove live), plus the latency-histogram gated engine (round 21: the
    dense bucket-count ring joins the cond-payload forbidden set and
    the commit-site scatters meet every structural lint), and the
    per-phase-GATED 2D campaign (round 22: one sim per batch cell so
    the real phase conds survive next to the packed tile-axis exchange
    — the shape the comms analyzer attributes phase-by-phase).

    Small geometry on purpose — the lints are structural, so the
    8-tile lowering carries the same program shape the 1024-tile
    config-5 run compiles (the phase-gating test separately pins the
    1024-tile shape).  `names` restricts to a subset of
    DEFAULT_PROGRAM_NAMES (each lowering costs a few seconds of
    tracing)."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.sweep import SweepRunner
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace import synthetic

    if names is None:
        names = DEFAULT_PROGRAM_NAMES
    unknown = set(names) - set(DEFAULT_PROGRAM_NAMES)
    if unknown:
        raise ValueError(
            f"unknown program(s) {sorted(unknown)} "
            f"(available: {', '.join(DEFAULT_PROGRAM_NAMES)})")

    batch = _audit_trace(tiles)
    geometry = AUDIT_GEOMETRY
    sc = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax_barrier") + geometry))
    sc_shl2 = SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, protocol="pr_l1_sh_l2_mesi",
        clock_scheme="lax_barrier")))
    # mem_gate_bytes=0: phase conds are the ONLY gating — the config-5
    # big-state regime the round-6 contract exists for
    specs = []
    if "gated-msi" in names:
        specs.append(spec_from_simulator(
            "gated-msi", gated_msi_simulator(tiles), max_quanta))
    if "ungated-msi" in names:
        specs.append(spec_from_simulator("ungated-msi", Simulator(
            sc, batch, phase_gate=False, mem_gate_bytes=0), max_quanta))
    if "shl2-mesi" in names:
        specs.append(spec_from_simulator("shl2-mesi", Simulator(
            sc_shl2, batch, phase_gate=True, mem_gate_bytes=0),
            max_quanta))
    if "sweep-b4" in names or "sweep-b4-tel" in names \
            or "sweep-b4-2d" in names or "sweep-b4-dvfs" in names \
            or "gated-msi-2d" in names:
        # the sweep config splits the modules over TWO DVFS domains so
        # the sync_delay knob actually crosses a boundary — in a
        # single-domain config it is structurally inert (MemParams.
        # sync_cycles returns a Python 0) and spec_from_sweep would
        # drop it from the required set
        sc_sweep = SimConfig(ConfigFile.from_string(
            config_text(tiles, shared_mem=True,
                        clock_scheme="lax_barrier")
            + geometry + """
[dvfs]
technology_node = 22
max_frequency = 1.0
synchronization_delay = 2
[dvfs/domains]
domains = "<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE>, \
<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>"
"""))
        sweep_traces = [
            synthetic.memory_stress_trace(
                tiles, n_accesses=16, working_set_bytes=1 << 12,
                write_fraction=0.4, shared_fraction=0.5, seed=s)
            for s in (1, 2, 3, 4)
        ]
    if "sweep-b4" in names:
        runner = SweepRunner(sc_sweep, sweep_traces, shard_batch=False)
        specs.append(spec_from_sweep("sweep-b4", runner, max_quanta))
    if "gated-msi-tel" in names:
        from graphite_tpu.obs import TelemetrySpec

        specs.append(spec_from_simulator("gated-msi-tel", Simulator(
            sc, batch, phase_gate=True, mem_gate_bytes=0,
            telemetry=TelemetrySpec(sample_interval_ps=1_000_000,
                                    n_samples=32)), max_quanta))
    if "sweep-b4-tel" in names:
        from graphite_tpu.obs import TelemetrySpec

        # the combined campaign-timelines program: the [B, S, n_series]
        # ring must stay off every cond AND every knob must stay live
        # with the recording machinery in the loop body
        runner_tel = SweepRunner(
            sc_sweep, sweep_traces, shard_batch=False,
            telemetry=TelemetrySpec(sample_interval_ps=1_000_000,
                                    n_samples=32))
        specs.append(spec_from_sweep("sweep-b4-tel", runner_tel,
                                     max_quanta))
    if "sweep-b4-2d" in names:
        # the round-18 2D batch x tile campaign: the SAME B=4 sweep on
        # a 2x2 Mesh(('batch','tile')) — each device one tile block of
        # two sims, the packed per-phase exchange over the tile axis.
        # Lowered via a device-less AbstractMesh (SweepRunner.lower),
        # so the lints/cost/lock cover the composition on 1-device CI.
        runner_2d = SweepRunner(sc_sweep, sweep_traces, layout=(2, 2))
        specs.append(spec_from_sweep("sweep-b4-2d", runner_2d,
                                     max_quanta))
    if "gated-msi-2d" in names:
        # round 22: the per-phase-GATED 2D campaign — layout (4, 2)
        # puts ONE sim per batch cell, so the real lax.cond phase gates
        # survive (the vmapped layouts above trade them for masked
        # always-run phases) alongside the packed tile-axis exchange.
        # This is the registered shape the comms analyzer attributes
        # collective-by-collective to protocol phases: each phase's
        # px gather sits immediately before (or inside) its cond.
        runner_g2d = SweepRunner(sc_sweep, sweep_traces, layout=(4, 2),
                                 phase_gate=True, mem_gate_bytes=0)
        specs.append(spec_from_sweep("gated-msi-2d", runner_g2d,
                                     max_quanta))
    if "gated-msi-hist" in names:
        # the round-21 latency-histogram program: the dense bucket-count
        # ring in the carry — its [H, B] aval joins the cond-payload
        # forbidden set, and the commit-site scatters must stay
        # deterministic / host-sync-free like every other ring
        from graphite_tpu.obs import HistSpec

        specs.append(spec_from_simulator("gated-msi-hist", Simulator(
            sc, batch, phase_gate=True, mem_gate_bytes=0,
            hist=HistSpec()), max_quanta))
    if "sweep-b4-dvfs" in names:
        # the round-19 runtime-DVFS campaign: the SAME B=4 sweep with a
        # GENUINELY multi-domain [dvfs] table (note `domains =` under
        # [dvfs] itself — the sc_sweep block above nests it under
        # [dvfs/domains], where the parser files it as the unread key
        # `dvfs/domains/domains` and the config silently stays
        # single-domain, which is why sync_delay_cycles was popped from
        # its required knob set for ten rounds).  Here the two-domain
        # split is real, so knob-fold proves sync_delay_cycles AND the
        # dvfs_domain_mhz grid live through the carried-frequency reads.
        from graphite_tpu.dvfs import DvfsSpec

        sc_dvfs = SimConfig(ConfigFile.from_string(
            config_text(tiles, shared_mem=True,
                        clock_scheme="lax_barrier")
            + geometry + """
[general]
technology_node = 22
[dvfs]
max_frequency = 1.0
synchronization_delay = 2
domains = "<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE>, \
<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>"
"""))
        dvfs_points = [{"dvfs_domain_mhz": p} for p in
                       ((1000, 1000), (870, 1000), (750, 870),
                        (500, 630))]
        runner_dvfs = SweepRunner(sc_dvfs, sweep_traces, dvfs_points,
                                  shard_batch=False, dvfs=DvfsSpec())
        specs.append(spec_from_sweep("sweep-b4-dvfs", runner_dvfs,
                                     max_quanta))
    return specs


# ---------------------------------------------------------------------------
# audit driver
# ---------------------------------------------------------------------------

RULE_NAMES = ("cond-payload", "knob-fold", "time-dtype", "vmap-gate",
              "host-sync", "scatter-determinism", "write-race",
              "telemetry-off", "profile-off", "hist-off", "dvfs-off",
              "gspmd-insertion", "replication-drift")


@dataclasses.dataclass
class RuleResult:
    program: str
    rule: str
    findings: "list[rules.Finding]"

    @property
    def ok(self) -> bool:
        return not any(f.severity == rules.SEV_ERROR
                       for f in self.findings)

    def to_json(self) -> dict:
        return {"program": self.program, "rule": self.rule,
                "status": "pass" if not self.findings
                else ("fail" if not self.ok else "warn"),
                "findings": [f.to_json() for f in self.findings]}


@dataclasses.dataclass
class AuditReport:
    results: "list[RuleResult]"

    @property
    def findings(self) -> "list[rules.Finding]":
        return [f for r in self.results for f in r.findings]

    @property
    def errors(self) -> "list[rules.Finding]":
        return [f for f in self.findings
                if f.severity == rules.SEV_ERROR]

    @property
    def ok(self) -> bool:
        return not self.errors

    def programs(self) -> "list[str]":
        seen = []
        for r in self.results:
            if r.program not in seen:
                seen.append(r.program)
        return seen

    def summary_rows(self) -> "list[dict]":
        rows = []
        for prog in self.programs():
            rs = [r for r in self.results if r.program == prog]
            n_err = sum(1 for r in rs for f in r.findings
                        if f.severity == rules.SEV_ERROR)
            n_warn = sum(1 for r in rs for f in r.findings
                         if f.severity == rules.SEV_WARNING)
            rows.append({"program": prog, "summary": True,
                         "rules_run": len(rs), "errors": n_err,
                         "warnings": n_warn, "ok": n_err == 0})
        return rows


def audit_program(spec: ProgramSpec, *,
                  max_cond_bytes: "int | None" = DEFAULT_MAX_COND_BYTES,
                  ) -> "list[RuleResult]":
    """Run every applicable rule on one lowered program."""
    results = []

    def add(rule, findings):
        for f in findings:
            f.program = spec.name
        results.append(RuleResult(spec.name, rule, findings))

    add("cond-payload", rules.cond_payload(
        spec.closed, max_bytes=max_cond_bytes,
        forbidden=spec.forbidden_cond_avals))
    if spec.knob_invars is not None:
        add("knob-fold", rules.knob_fold(
            spec.closed, spec.knob_invars, spec.invar_paths))
    add("time-dtype", rules.time_dtype(
        spec.closed, spec.clock_invars, spec.invar_paths))
    add("vmap-gate", rules.vmap_gate(
        spec.closed, spec.n_tiles, spec.expect_gated,
        n_phases=spec.n_phases))
    add("host-sync", rules.host_sync(spec.closed))
    add("scatter-determinism", rules.scatter_determinism(
        spec.closed, batched=spec.batched))
    # the standing gate for the [T, k] mailbox compaction: no rewrite
    # may turn a req-lane or mailbox-matrix scatter into an
    # ordered-multi-writer one (analysis/protocol.py's model checker
    # supplies the reachable fan-in bounds; the gate itself is static)
    add("write-race", rules.write_race(spec.closed, spec.n_tiles))
    from graphite_tpu.analysis import comms
    if comms.has_mesh_region(spec.closed):
        # round 22: mesh programs additionally run the collective
        # lints — every collective must match the px packed-exchange
        # whitelist (the mesh.py GSPMD-cliff regression gate), and
        # every output declared replicated across the tile axis must
        # be provably uniform
        add("gspmd-insertion", rules.gspmd_insertion(
            spec.closed, spec.n_tiles, phase_names=spec.phase_names))
        add("replication-drift", rules.replication_drift(spec.closed))
    if not spec.expect_telemetry:
        # telemetry-OFF programs must carry no trace of the timeline
        # machinery (ON programs instead police the ring via the
        # cond-payload forbidden set, added by spec_from_*)
        add("telemetry-off", rules.telemetry_off(
            spec.closed, spec.invar_paths,
            ring_sigs=(((spec.telemetry_sig,)
                        if spec.telemetry_sig is not None else ())
                       + tuple(spec.telemetry_extra_sigs))))
    if not spec.expect_profile:
        # profile-OFF programs must carry no trace of the spatial
        # profiler — same rule, profile state key + [S, T, m] ring sigs
        add("profile-off", rules.telemetry_off(
            spec.closed, spec.invar_paths,
            ring_sigs=(((spec.profile_sig,)
                        if spec.profile_sig is not None else ())
                       + tuple(spec.profile_extra_sigs)),
            state_key="profile", rule="profile-off"))
    if not spec.expect_hist:
        # hist-OFF programs must carry no trace of the latency
        # histograms — same rule, hist state key + bucket-ring sigs
        add("hist-off", rules.telemetry_off(
            spec.closed, spec.invar_paths,
            ring_sigs=(((spec.hist_sig,)
                        if spec.hist_sig is not None else ())
                       + tuple(spec.hist_extra_sigs)),
            state_key="hist", rule="hist-off"))
    if not spec.expect_dvfs:
        # dvfs=None programs must carry no runtime-DVFS manager state:
        # no `dvfs_rt` invar may survive (the carried operating point
        # would change the lowering).  No ring sigs — the manager has
        # no ring; its state is a handful of [n_domains] vectors whose
        # avals are too generic to scan for.
        add("dvfs-off", rules.telemetry_off(
            spec.closed, spec.invar_paths, ring_sigs=(),
            state_key="dvfs_rt", rule="dvfs-off"))
    return results


def audit(specs: "list[ProgramSpec] | None" = None, *,
          tiles: int = 8,
          max_cond_bytes: "int | None" = DEFAULT_MAX_COND_BYTES,
          max_quanta: int = 4096) -> AuditReport:
    """Audit `specs` (default: the five default-config programs).

    Pure static analysis over `jax.make_jaxpr` output — no compile, no
    execution, runs on CPU.  `report.ok` is False iff any error-severity
    finding fired (warnings — e.g. vmap-gate — do not fail the audit)."""
    if specs is None:
        specs = default_programs(tiles, max_quanta)
    results = []
    for spec in specs:
        results.extend(audit_program(spec, max_cond_bytes=max_cond_bytes))
    return AuditReport(results)
