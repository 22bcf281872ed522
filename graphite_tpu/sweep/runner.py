"""Batched simulation campaigns: B simulations through ONE compiled step.

Graphite's whole reason to exist is simulation *throughput* — the
reference parallelizes ONE simulation across host machines because
architects run campaigns: design-space sweeps over timing parameters,
traces, and seeds.  The TPU port has the inverse opportunity: `vmap`
B independent simulations through ONE program, so that a campaign pays
one compile, one dispatch and one fetch a batch.  What it does NOT buy,
measured on the v5e (PERF.md, PR 31 / PR 32 / PR 34; `campaign64-dram`,
B = 4): a B-fold amortisation of the iteration.  A predicate batched by
`vmap` turns a `lax.cond` into both branches and a select, so a gate
holds under the batch only where its predicate is reduced over the sims
to a scalar.  The ENGINE's activity gates are (since PR 34: the sims
are mapped under a named axis and `engine/step.py` ORs each predicate
over it, `ParallelCtx.any_sim`): a batch runs the mailbox, NoC, barrier,
mutex/cond, join and DVFS blocks only in iterations where some sim of
it needs them.  The MEMORY engines' six phase conds, the home-activity
gate over the directory base and the staging flush follow the same rule
since PR 36; the whole-engine `mem_gate` cond stays off under a batch
(`_build_sim`; ROADMAP M3b, second half).

Mechanics:
 - traces pack to a common [B, T, L] layout (sweep/pack.py); `vmap` maps
   the device-side simulation loop (`engine/step.run_simulation`) over
   the sim axis;
 - timing knobs ride as a traced `[B]` Knobs pytree (sweep/knobs.py), so
   a grid of timing points — DRAM latency, directory access, hop
   latency, sync delay, quantum — shares the single compiled program
   with ZERO recompiles;
 - per-sim done/overflow/deadlock masks drive each sim's own while_loop
   condition: under vmap's batching rule a finished sim's carry is
   select-frozen, so every sim's final state is BIT-IDENTICAL to its own
   sequential run (pinned in tests/test_sweep.py) and the batch
   early-exits once the last live sim finishes;
 - results demux back into B independent SimResults (plus per-sim
   phase-skip counters and iteration counts).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from graphite_tpu.obs.scopes import tagged
from graphite_tpu.obs.trace import (
    NO_SPANS, PROGRAMS, RunSpans, SetupSpans, constructs,
)
from graphite_tpu.sweep.knobs import Knobs
from graphite_tpu.sweep.pack import PackedTraces, pack_traces


@dataclasses.dataclass
class SweepOutcome:
    """One campaign's demuxed outputs."""

    results: list                 # B SimResults (engine/simulator.py)
    knobs: "Knobs"                # the [B] knob batch that ran
    n_iterations: np.ndarray      # int64[B] subquantum iterations per sim
    n_quanta: np.ndarray          # int32[B]
    # per-sim counts of what the PROGRAM skipped (or None): under a sim
    # axis a gate's predicate is OR-ed over the batch, so a sim counts a
    # skip only where every sim of its batch had nothing to do — device
    # counters, not simulated statistics
    phase_skips: "list[dict] | None"
    seeds: "np.ndarray | None" = None  # per-sim trace seeds (pack metadata)
    # per-sim device-recorded timelines (obs.Timeline) when the campaign
    # ran with a TelemetrySpec: the batched [B, S, n_series] ring demuxed
    # sim-by-sim (each also rides its SimResults.telemetry)
    timelines: "list | None" = None
    # per-sim per-tile profiles (obs.TileProfile) when the campaign ran
    # with a ProfileSpec: the [B, S, T, m] ring demuxed sim-by-sim
    # (each also rides its SimResults.profile)
    profiles: "list | None" = None
    # per-sim latency histograms (obs.Hist) when the campaign ran with
    # a HistSpec: the [B, H, B'] (or [B, T, H, B']) bucket-count ring
    # demuxed sim-by-sim (each also rides its SimResults.hist)
    hists: "list | None" = None
    # False for unbounded clock schemes (lax/lax_p2p): there is no
    # quantum in the program, so reporting the knob would claim a value
    # that never entered it
    quantum_valid: bool = True
    # the device layout the campaign actually ran under (round 18):
    # "solo", "1d-batch(d=N)", "1d-tile(t=N)", or "2d(b=DB,t=DT)" —
    # reported per row so a result line names the program that made it
    layout: str = "solo"
    # per-sim {"base", "flush"} skip counts of the private-L2 engine's
    # home-activity gate (memory/engine.BASE_SKIP_NAMES), or None
    base_skips: "list[dict] | None" = None
    # int64[B]: of `n_iterations`, those in which the sim advanced
    # nothing (engine/step._quantum_loop) — one a quantum, plus what a
    # sim waited for the rest of its batch
    idle_iterations: "np.ndarray | None" = None
    # per-sim scalars of a power / DVFS target (`power_row`), or None
    power: "list[dict] | None" = None

    def json_rows(self) -> "list[dict]":
        """One JSON-able dict per sim (the CLI's output lines)."""
        rows = []
        for b, r in enumerate(self.results):
            point = self.knobs.point(b)
            if not self.quantum_valid:
                point.pop("quantum_ps", None)
            rows.append({
                "sim": b,
                **({"seed": int(self.seeds[b])}
                   if self.seeds is not None else {}),
                **point,
                "layout": self.layout,
                "completion_time_ns": r.completion_time_ps // 1000,
                "total_instructions": r.total_instructions,
                "n_quanta": int(self.n_quanta[b]),
                "n_iterations": int(self.n_iterations[b]),
                "func_errors": r.func_errors,
                **(self.power[b] if self.power is not None else {}),
            })
        return rows


def power_row(energy_pj, dvfs_counters, n_dvfs_sets: int,
              core_domain: int) -> dict:
    """One sim's energy and operating point as a result line's scalars,
    from `Simulator._power_host`'s pair.  `energy_pj_total`:
    `SimResults.energy_pj["total"]` summed over the tiles - the
    integrated energy (power/accounting.py), NOT the telemetry series
    `energy_pj` (ROADMAP D20).  `dvfs_transitions`: the trace's DVFS_SET
    records less the rejected ones.  `dvfs_level_mhz`: the CORE domain's
    frequency where every tile ended on the same one (a point of a V/f
    sweep), left out where tiles differ."""
    row = {}
    if energy_pj is not None:
        row["energy_pj_total"] = int(energy_pj["total"].sum())
    if dvfs_counters is not None:
        row["dvfs_transitions"] = n_dvfs_sets - int(
            dvfs_counters["errors"].sum())
        core = np.unique(dvfs_counters["freq_mhz"][:, core_domain])
        if len(core) == 1:
            row["dvfs_level_mhz"] = int(core[0])
    return row


def executable_text(program, inputs: tuple) -> str:
    """Optimized HLO text of a campaign program: a `jax.stages.Compiled`
    (AOT, store) gives its own; a jit that has run re-reads its own
    executable for `inputs` (abstract ones will do: no compile)."""
    if hasattr(program, "as_text"):
        return program.as_text()
    return program.lower(*inputs).compile().as_text()


def _divisors(n: int) -> "list[int]":
    return [d for d in range(1, int(n) + 1) if int(n) % d == 0]


class SweepRunner:
    """Run B same-geometry simulations as one batched compiled program.

    `traces`: a list of TraceBatch (or a PackedTraces).  `points`: knob
    override dicts (sweep/knobs.py KNOB_FIELDS); with one trace and K > 1
    points the trace is replicated across the grid.  Remaining kwargs
    reach the underlying Simulator construction (mailbox_depth,
    inner_block, phase_gate, telemetry, profile, ...); multi-chip tile
    sharding, streaming and host-barrier modes are out of scope for the
    batched program.  `telemetry=obs.TelemetrySpec(...)` records one
    device timeline PER SIM ([B, S, n_series] total), demuxed post-run
    into `SweepOutcome.timelines` / each result's `.telemetry`;
    `profile=obs.ProfileSpec(...)` likewise records one per-tile ring
    PER SIM ([B, S, T, m] total), demuxed into `SweepOutcome.profiles`
    / each result's `.profile` — under both vmap and batch shard_map;
    `dvfs=dvfs.DvfsSpec(...)` attaches the runtime DVFS manager to
    every sim, and a `dvfs_domain_mhz` knob axis then seeds each
    point's per-domain operating frequencies so ONE compiled program
    sweeps a whole domain-frequency grid (the race-to-idle study).
    `tracer=obs.Tracer(...)` attaches a tracer from the start: the
    set-up spans of construction and placement go to it (else to
    `obs.trace.SETUP`), and `run()` is traced as under `attach_tracer`.

    Four batching programs, chosen by `layout` (or the legacy
    `shard_batch` kwarg):
     - "solo": `vmap` over the sim axis (the default on one device):
       one program, B-wide arrays.  The engine's activity gates hold
       (their predicates are OR-ed over the sims); the memory engine's
       conds would become both-branch selects, so its phase and
       whole-engine gates are OFF by default (gating is mechanism, not
       policy — results are bit-identical either way; pass
       phase_gate=True to override).
     - "batch" (legacy `shard_batch=True`): batch-axis `shard_map` when
       several devices are visible and B divides evenly: each device
       runs B/ndev sims; with one sim per device the per-device program
       is the plain UNBATCHED engine — real lax.cond gating stays alive
       and sims run in parallel across devices.
     - "tile" / "2d" / an explicit `(batch_shards, tile_shards)` tuple:
       the round-18 `Mesh(('batch', 'tile'))` program — each device
       holds a TILE BLOCK of a SUBSET of sims.  The big per-tile arrays
       (cache meta, the directory + its staging rows, trace rows, the
       per-tile profile ring) are block-local on the tile axis and the
       round-12 packed per-phase exchange (one working-set gather + one
       merged scatter per iteration, parallel/px.py) runs over the tile
       axis only; batch cells never communicate.  This is the layout
       for sims whose per-sim residency bill exceeds ONE device's
       `hbm_budget_bytes`: the bill splits into per-device tile blocks
       (`device_breakdown()`).  Results are bit-identical to solo runs
       (`tests/test_mesh2d.py`).

    `layout=None` picks automatically from `residency_breakdown` + the
    device count: a campaign whose PER-SIM bill exceeds the per-device
    budget shards the tile axis (smallest tile_shards that fits, batch
    shards filling the remaining devices); otherwise the legacy choice
    (batch-axis shard_map when B divides the device count, else solo).
    The chosen layout is reported in `json_rows` ("layout" column) and
    `SweepOutcome.layout`, and `lower()` lowers the REAL composition
    (via a device-less AbstractMesh) so the audit lints, cost model and
    identity lock cover the 2D program on any host.

    `hbm_budget_bytes` (else `[general] hbm_budget_bytes`, 0 = off)
    arms the pre-compile residency fail-fast: the campaign's estimated
    footprint (B x state + resident traces + telemetry rings) above the
    budget raises `analysis.cost.ResidencyBudgetError` — with the
    per-consumer breakdown — before any tracing starts.  Under a
    tile-sharded layout the check is PER DEVICE (`device_breakdown`),
    which is exactly what lets a too-big-for-one-device sim run.
    """

    @constructs
    def __init__(self, config, traces, points: "list[dict] | None" = None,
                 *, mailbox_depth: "int | None" = None,
                 shard_batch: "bool | None" = None,
                 layout=None,
                 hbm_budget_bytes: "int | None" = None, tracer=None,
                 **sim_kwargs):
        from graphite_tpu.engine.simulator import Simulator, \
            auto_mailbox_depth

        for bad in ("mesh", "stream", "barrier_host", "donate"):
            # pop rather than test: an explicit falsy value (e.g.
            # barrier_host=False) matches our own construction and must
            # not collide with the kwargs passed below
            if sim_kwargs.pop(bad, None):
                raise ValueError(
                    f"SweepRunner does not support {bad}= (the batched "
                    "program is single-device and resident)")
        pack = traces if isinstance(traces, PackedTraces) \
            else pack_traces(list(traces))
        if points and pack.n_sims == 1 and len(points) > 1:
            pack = pack.replicate(len(points))
        if points is not None and len(points) != pack.n_sims:
            raise ValueError(
                f"{len(points)} knob points for {pack.n_sims} traces — "
                "counts must match (or pass one trace to replicate)")
        self.pack = pack
        B = pack.n_sims

        # every sim must build the SAME engine program: the memory
        # subsystem is built iff a trace touches memory, so mixed
        # memory/memoryless campaigns cannot share one lowering
        from graphite_tpu.trace.schema import FLAG_MEM0_VALID, \
            FLAG_MEM1_VALID
        mem_flags = FLAG_MEM0_VALID | FLAG_MEM1_VALID
        has_mem = [bool(np.any(pack.flags[b] & mem_flags))
                   for b in range(B)]
        if len(set(has_mem)) != 1:
            raise ValueError(
                "all sims in a sweep must agree on touching memory "
                f"(sims {[b for b in range(B) if has_mem[b] != has_mem[0]]}"
                " differ): the memory engine is part of the compiled "
                "program")

        if mailbox_depth is None:
            # one ring depth serves the whole batch (ring timing is
            # depth-invariant below overflow, so per-sim equality holds)
            mailbox_depth = max(auto_mailbox_depth(pack.sim(b))
                                for b in range(B))

        # device layout: solo vmap, batch-axis shard_map, or the 2D
        # batch x tile mesh (see class doc)
        n_dev = len(jax.devices())
        if layout is not None and shard_batch is not None:
            raise ValueError(
                "pass layout= OR the legacy shard_batch=, not both "
                "(shard_batch=True is layout='batch', False is 'solo')")
        if layout is None and shard_batch is not None:
            layout = "batch" if shard_batch else "solo"
        auto = layout is None
        self._n_dev = n_dev
        # a power target runs on one device (refused on a mesh, below):
        # left to itself the runner picks the layout that works
        power = bool(config.enable_power_modeling)
        if auto:
            # legacy auto guess; a budget-driven promotion to the 2D
            # layout happens below, once the sim's state bytes exist
            layout = ("batch" if n_dev > 1 and B % n_dev == 0
                      and not power else "solo")
        layout = self._normalize_layout(layout, B, n_dev)
        # host span tracing (`tracer=`, attach_tracer): of construction
        # and placement (obs/trace.py: SETUP_SPANS), which without one go
        # to the process-wide `obs.trace.SETUP`, and of run(), which
        # without one makes no span, no annotation and no device sync
        self.tracer = tracer
        self._user_gating = {
            k: sim_kwargs[k] for k in ("phase_gate", "mem_gate_bytes")
            if k in sim_kwargs}
        self._sim_ctor = (config, pack.sim(0), mailbox_depth,
                          dict(sim_kwargs))
        self._has_mem = bool(has_mem[0])
        self.sim = self._build_sim(layout)
        if self.sim.params.energy is not None and layout != "solo":
            # as `Simulator(mesh=...)` refuses it: `campaign_state_specs`
            # knows no `state.energy`, and no mesh layout (the batch-axis
            # one included) has run with the accumulators
            raise NotImplementedError(
                "[general] enable_power_modeling on a device mesh "
                f"(layout {self._layout_name(layout)}): the energy "
                "accumulators (EnergyState) have no shard spec yet; a "
                "power target is served by layout='solo' on one device")
        self.mailbox_depth = mailbox_depth
        base = Knobs.from_params(self.sim.params,
                                 self.sim.quantum_ps)
        points = points if points is not None else [{}] * B
        if self.sim.quantum_ps is None:
            # unbounded schemes (lax / lax_p2p) have no quantum for the
            # knob to steer — reject rather than silently ignore it
            bad_q = [i for i, p in enumerate(points) if "quantum_ps" in p]
            if bad_q:
                raise ValueError(
                    f"point(s) {bad_q} sweep quantum_ps but the clock "
                    "scheme has no lax_barrier quantum (the knob would "
                    "be reported yet never enter the program)")
        self.knobs = Knobs.stack(base, points)
        if self.knobs.dvfs_domain_mhz is not None:
            # the domain-frequency axis seeds the runtime DVFS carry, so
            # a DvfsSpec must be attached (it bakes the carried-frequency
            # reads into the program); validate the grid host-side — the
            # traced seed path clamps instead of raising
            if self.sim.dvfs_spec is None:
                raise ValueError(
                    "dvfs_domain_mhz knob points need dvfs=DvfsSpec(...) "
                    "on the campaign (the carried-frequency program is "
                    "opt-in; without it the knob would never enter the "
                    "lowering)")
            dvp = self.sim.params.dvfs
            grid = np.asarray(jax.device_get(self.knobs.dvfs_domain_mhz))
            if grid.shape[-1] != dvp.n_domains:
                raise ValueError(
                    f"dvfs_domain_mhz rows have {grid.shape[-1]} "
                    f"entries but the config defines {dvp.n_domains} "
                    "domain(s)")
            top = int(dvp.max_freq_mhz[0])
            if (grid <= 0).any() or (grid > top).any():
                raise ValueError(
                    "dvfs_domain_mhz points must be in (0, "
                    f"{top}] MHz (the V/f table's top level); got "
                    f"{sorted(set(grid.reshape(-1).tolist()) - set(range(1, top + 1)))}")
        if self.sim.quantum_ps is not None:
            q = np.asarray(jax.device_get(self.knobs.quantum_ps))
            if (q <= 0).any():
                raise ValueError(
                    f"quantum_ps knob points must be positive "
                    f"(sims {np.flatnonzero(q <= 0).tolist()}): the "
                    "boundary math divides by the quantum")
        # the last completed run()'s loop trip count (the max over the
        # batch: a finished sim's carry is frozen while the others go on;
        # per sim: `SweepOutcome.n_iterations`) and launch count, as
        # `Simulator` keeps them: one batched dispatch per run()
        self.last_n_iterations = 0
        self.last_run_dispatches = 0
        self._runner = None
        self._runner_max_quanta = None
        self._dtr = None      # device-resident [B, T, L] traces (cached)
        self._states0 = None  # broadcast [B, ...] initial states (cached)
        # lower-once plumbing (round 11): one tracing per max_quanta
        # serves audit + cost + fingerprint; lower_count is the probe.
        # _sim_lower_gen mirrors sim.lower_gen — attach_telemetry on
        # the wrapped sim changes the program AND initial state, so
        # every sim-derived cache here must drop (_sync_with_sim)
        self._lowered = {}
        self.lower_count = 0
        self._sim_lower_gen = self.sim.lower_gen
        # Pre-compile residency fail-fast (round 10): the campaign's HBM
        # bill is B x per-sim state + the resident [B, T, L] traces +
        # B telemetry rings — all known BEFORE tracing, so a sweep of
        # big sims with timelines refuses as a NAMED error here instead
        # of a device OOM minutes into compile.  Budget: kwarg, else
        # `[general] hbm_budget_bytes`, else 0 (disabled).
        if hbm_budget_bytes is None:
            hbm_budget_bytes = self.sim.config.cfg.get_int(
                "general/hbm_budget_bytes", 0)
        self.hbm_budget_bytes = int(hbm_budget_bytes)
        # Budget-driven layout promotion (round 18): a per-sim bill too
        # big for ONE device's budget is not a refusal anymore — shard
        # the tile axis (the smallest tile_shards whose per-device
        # block fits), batch shards filling the remaining devices.
        if auto and self.hbm_budget_bytes and n_dev > 1 and not power \
                and not isinstance(layout, tuple):
            per_sim = self._per_sim_bill()
            if per_sim > self.hbm_budget_bytes:
                promoted = self._auto_mesh_layout(
                    B, pack.n_tiles, n_dev,
                    budget=self.hbm_budget_bytes)
                if promoted is not None:
                    old_vmapped = self._sims_per_cell(layout) > 1
                    layout = promoted
                    if (self._sims_per_cell(layout) > 1) != old_vmapped \
                            and self._has_mem and not self._user_gating:
                        # the gating defaults follow the per-cell
                        # program shape (vmapped cells keep the
                        # whole-engine mem_gate off);
                        # rebuild the wrapped sim so the executed and
                        # certified program agree
                        self.sim = self._build_sim(layout)
                        self._sim_lower_gen = self.sim.lower_gen
        self.layout_spec = layout
        if self.sim.dvfs_spec is not None and isinstance(layout, tuple):
            raise ValueError(
                "the runtime DVFS manager does not support tile-sharded "
                "layouts: the governor and the chip-global election "
                "reduce over ALL tiles, which a tile shard cannot see "
                "(use layout='solo' or 'batch')")
        self.shard_batch = layout == "batch"
        self._sims_per_dev = self._sims_per_cell(layout)
        self.layout_name = self._layout_name(layout)
        if self.hbm_budget_bytes:
            from graphite_tpu.analysis.cost import (
                ResidencyBudgetError, format_breakdown,
            )

            if isinstance(layout, tuple):
                # tile-sharded layouts budget PER DEVICE: each device
                # holds (B/db) sims' tile blocks, which is exactly what
                # lets a too-big-for-one-device sim run at all
                bd = self.device_breakdown()
                if bd["total"] > self.hbm_budget_bytes:
                    raise ResidencyBudgetError(
                        f"per-device residency of the "
                        f"{self.layout_name} campaign layout exceeds "
                        f"hbm_budget_bytes={self.hbm_budget_bytes} (B="
                        f"{self.pack.n_sims}): "
                        + format_breakdown(bd)
                        + " per device — raise tile_shards, shrink the "
                        "batch, or raise `[general] hbm_budget_bytes`")
            else:
                breakdown = self.residency_breakdown()
                if breakdown["total"] > self.hbm_budget_bytes:
                    raise ResidencyBudgetError(
                        f"campaign residency exceeds hbm_budget_bytes="
                        f"{self.hbm_budget_bytes} before compile (B="
                        f"{self.pack.n_sims}): "
                        + format_breakdown(breakdown)
                        + " — shrink the batch, stream fewer consumers "
                        "(drop telemetry or shorten traces), raise "
                        "`[general] hbm_budget_bytes`, or shard the "
                        "mesh both ways (layout='2d' / layout=(batch_"
                        "shards, tile_shards): the 2D batch x tile "
                        "layout splits the bill into per-device tile "
                        "blocks)")

    # -- device layouts (round 18) ---------------------------------------

    def _normalize_layout(self, layout, B: int, n_dev: int):
        """Normalize a layout request to "solo" | "batch" | (db, dt)."""
        T = self.pack.n_tiles
        if isinstance(layout, str):
            name = layout.lower().replace("_", "-")
            if name == "solo":
                return "solo"
            if name in ("batch", "1d-batch"):
                if n_dev <= 1 or B % n_dev != 0:
                    raise ValueError(
                        f"layout 'batch' needs B ({B}) divisible by "
                        f"the device count ({n_dev})")
                return "batch"
            if name in ("tile", "1d-tile"):
                if n_dev <= 1:
                    raise ValueError(
                        "layout 'tile' needs more than one device "
                        "(force some with XLA_FLAGS=--xla_force_host_"
                        "platform_device_count=N on CPU)")
                return self._check_mesh_layout((1, n_dev), B, T)
            if name == "2d":
                got = self._auto_mesh_layout(B, T, n_dev, budget=None)
                if got is None:
                    raise ValueError(
                        f"no 2D layout fits: {n_dev} device(s), tile "
                        f"count {T}, B={B} — need a >1 tile divisor of "
                        "the device count (pass an explicit (batch_"
                        "shards, tile_shards) tuple to override)")
                return got
            raise ValueError(
                f"unknown layout {layout!r} (choose 'solo', 'batch', "
                "'tile', '2d', or an explicit (batch_shards, "
                "tile_shards) tuple)")
        if isinstance(layout, (tuple, list)) and len(layout) == 2:
            return self._check_mesh_layout(
                (int(layout[0]), int(layout[1])), B,
                self.pack.n_tiles)
        raise ValueError(
            f"unknown layout {layout!r} (choose 'solo', 'batch', "
            "'tile', '2d', or an explicit (batch_shards, tile_shards) "
            "tuple)")

    def _check_mesh_layout(self, layout, B: int, T: int):
        """Validate an explicit (db, dt) mesh layout.  Device
        availability is deliberately NOT checked here: lowering (audit,
        fingerprint, lock) uses a device-less AbstractMesh, so a 2D
        program is auditable on a 1-device host; `_get_runner` checks
        the real devices at execution time."""
        db, dt = layout
        if db < 1 or dt < 1:
            raise ValueError(
                f"layout shards must be positive (got {layout})")
        if B % db:
            raise ValueError(
                f"layout batch_shards={db} must divide B ({B})")
        if T % dt:
            raise ValueError(
                f"layout tile_shards={dt} must divide the tile count "
                f"({T})")
        return (db, dt)

    def _auto_mesh_layout(self, B: int, T: int, n_dev: int, *,
                          budget: "int | None"):
        """Pick a (db, dt) mesh layout.  With a `budget`, the smallest
        tile_shards whose per-device block fits, batch shards filling
        the remaining devices (largest divisor of B that fits); with
        budget=None (an explicit '2d' request), the smallest >1 tile
        split the geometry allows.  None when nothing fits."""
        # any tile divisor up to the device count is a candidate — dt
        # need not divide n_dev (the mesh uses db*dt of the devices;
        # idle devices beat a refusal), smallest split that fits wins
        for dt in range(2, n_dev + 1):
            if T % dt:
                continue
            db_max = n_dev // dt
            if budget is None:
                db = max(d for d in _divisors(B) if d <= db_max)
                return (db, dt)
            block = self._per_sim_bill(tile_shards=dt)
            cap = budget // max(block, 1)
            if cap < 1 or block > budget:
                continue
            db = max(d for d in _divisors(B) if d <= db_max)
            if B // db <= cap:
                return (db, dt)
        return None

    def _sims_per_cell(self, layout) -> int:
        B = self.pack.n_sims
        if layout == "batch":
            return B // self._n_dev_hint()
        if isinstance(layout, tuple):
            return B // layout[0]
        return B

    def _n_dev_hint(self) -> int:
        n = getattr(self, "_n_dev", None)
        return n if n else len(jax.devices())

    def _layout_name(self, layout) -> str:
        if layout == "solo":
            return "solo"
        if layout == "batch":
            return f"1d-batch(d={self._n_dev_hint()})"
        db, dt = layout
        if db == 1:
            return f"1d-tile(t={dt})"
        return f"2d(b={db},t={dt})"

    def _build_sim(self, layout):
        from graphite_tpu.engine.simulator import Simulator

        config, trace0, mbd, kwargs = self._sim_ctor
        kwargs = dict(kwargs)
        if self._sims_per_cell(layout) > 1 and self._has_mem:
            # the per-cell program is vmapped.  The memory engines'
            # phase conds and the home-activity gate reduce their
            # predicates over the sim axis, like the engine's own
            # activity gates (`_runner_fn`: over_sims), so `phase_gate`
            # keeps the Simulator's default.  The whole-engine
            # `mem_gate` cond does not: its predicate is per sim and its
            # outputs are the batch's stores, which a both-branch select
            # would double-buffer — default it OFF (explicit kwargs win)
            kwargs.setdefault("mem_gate_bytes", 0)
        return Simulator(config, trace0, mailbox_depth=mbd,
                         barrier_host=False, tracer=self.tracer, **kwargs)

    def _per_sim_bill(self, tile_shards: int = 1) -> int:
        """ONE sim's residency bill — whole (tile_shards=1) or its
        per-device tile block under a tile-sharded layout."""
        return self._device_bd(sims_per_shard=1,
                               tile_shards=tile_shards)["total"]

    def _device_bd(self, *, sims_per_shard: int,
                   tile_shards: int) -> "dict[str, int]":
        from graphite_tpu.analysis.cost import (
            device_residency_breakdown, trace_record_bytes,
        )

        state = self.sim.state
        if state.telemetry is not None:
            state = state.replace(telemetry=None)
        if state.profile is not None:
            state = state.replace(profile=None)
        if state.hist is not None:
            state = state.replace(hist=None)
        per_sim_trace = (self.pack.n_tiles * self.pack.length
                         * trace_record_bytes(self.pack.sim(0)))
        return device_residency_breakdown(
            state=state, sims_per_shard=sims_per_shard,
            tile_shards=tile_shards,
            per_sim_trace_bytes=per_sim_trace,
            telemetry_spec=self.sim.telemetry_spec,
            profile_spec=self.sim.profile_spec,
            hist_spec=self.sim.hist_spec)

    def device_breakdown(self) -> "dict[str, int]":
        """Per-DEVICE itemized residency of the chosen layout: each
        device holds (B / batch_shards) sims' tile blocks — the
        replicated control state in full, 1/tile_shards of the big
        per-tile arrays, trace rows and profile ring (the telemetry
        ring's scalar rows are replicated).  For solo this equals
        `residency_breakdown` modulo the packed-trace padding; for the
        batch layout it is the per-device share."""
        if isinstance(self.layout_spec, tuple):
            db, dt = self.layout_spec
        elif self.layout_spec == "batch":
            db, dt = self._n_dev_hint(), 1
        else:
            db, dt = 1, 1
        return self._device_bd(sims_per_shard=self.pack.n_sims // db,
                               tile_shards=dt)

    def residency_breakdown(self) -> "dict[str, int]":
        """Per-consumer HBM estimate of this campaign's resident layout
        (analysis/cost.residency_breakdown): B x per-sim state, the
        packed [B, T, L] traces, B telemetry rings.  The same itemized
        dict the pre-compile fail-fast prints."""
        from graphite_tpu.analysis.cost import residency_breakdown
        from graphite_tpu.sweep.pack import PackedTraces

        trace_arrays = {f: getattr(self.pack, f)
                        for f in PackedTraces._TRACE_FIELDS}
        # the rings are itemized as their own consumers — strip them
        # from the per-sim state so an attached spec is not counted twice
        state = self.sim.state
        if state.telemetry is not None:
            state = state.replace(telemetry=None)
        if state.profile is not None:
            state = state.replace(profile=None)
        if state.hist is not None:
            state = state.replace(hist=None)
        return residency_breakdown(
            state=state, trace=trace_arrays,
            batch=self.pack.n_sims,
            telemetry_spec=self.sim.telemetry_spec,
            profile_spec=self.sim.profile_spec,
            hist_spec=self.sim.hist_spec)

    @property
    def n_sims(self) -> int:
        return self.pack.n_sims

    def _runner_fn(self, max_quanta: int, abstract: bool = False):
        """The (unjitted) batched campaign function — `_get_runner`
        jits it; `lower()` hands it to `jax.make_jaxpr` for the
        program auditor.  `abstract=True` (lowering only) builds any
        mesh layout over a device-less AbstractMesh, so the 2D program
        is auditable/fingerprintable on hosts without the forced
        device platform."""
        from graphite_tpu.engine.step import run_simulation
        from graphite_tpu.parallel.px import IDENT, SIM_AXIS, ParallelCtx

        params = self.sim.params
        unbounded = self.sim.quantum_ps is None
        tel = self.sim.telemetry_spec
        prof = self.sim.profile_spec
        hs = self.sim.hist_spec
        dv = self.sim.dvfs_spec

        def one(state, trace, kn, px=IDENT):
            q = None if unbounded else kn.quantum_ps
            if dv is not None and kn.dvfs_domain_mhz is not None:
                # per-point operating seed: rebuild the DVFS carry from
                # this row's [n_domains] frequencies (AUTO voltage) and
                # re-broadcast the CORE domain into the tile clocks, so
                # one compiled program serves the whole frequency grid
                from graphite_tpu.dvfs.runtime import (
                    core_freq_tiles, init_dvfs_rt,
                )

                rt = init_dvfs_rt(params.dvfs, dv,
                                  domain_mhz=kn.dvfs_domain_mhz)
                state = state.replace(
                    dvfs_rt=rt,
                    core=state.core.replace(freq_mhz=core_freq_tiles(
                        params.dvfs, rt, state.core.freq_mhz)),
                    dvfs=state.dvfs.replace(
                        freq_mhz=jnp.broadcast_to(
                            rt.domain_mhz[None],
                            state.dvfs.freq_mhz.shape),
                        voltage_mv=jnp.broadcast_to(
                            rt.domain_mv[None],
                            state.dvfs.voltage_mv.shape)))
            return run_simulation(params, trace, state, q, max_quanta,
                                  knobs=kn, telemetry=tel, profile=prof,
                                  dvfs=dv, hist=hs, px=px)

        def over_sims(px=IDENT):
            # `one` over the sims of a cell, under a NAMED axis: the
            # engine's activity gates OR their predicates over it
            # (px.any_sim), so they stay scalar and the conds stay conds
            pxs = dataclasses.replace(px, sim_axis=SIM_AXIS)
            return jax.vmap(lambda s, t, k: one(s, t, k, pxs),
                            axis_name=SIM_AXIS)

        if isinstance(self.layout_spec, tuple):
            # the 2D batch x tile mesh: each device holds a tile block
            # of a subset of sims; the packed per-phase exchange runs
            # over the tile axis only (parallel/mesh.py round 18)
            from jax.sharding import PartitionSpec as P

            from graphite_tpu.parallel.mesh import (
                TILE_AXIS_2D, _shard_map, campaign_state_specs,
                campaign_trace_specs, make_batch_tile_mesh,
            )
            db, dt = self.layout_spec
            px = ParallelCtx(axis=TILE_AXIS_2D, n_dev=dt)
            mesh = make_batch_tile_mesh(db, dt, abstract=abstract)
            state_specs = campaign_state_specs(self.sim.state)
            trace_specs = campaign_trace_specs(self.sim.device_trace)
            knob_specs = jax.tree.map(lambda _: P("batch"), self.knobs)
            Bl = self.pack.n_sims // db

            def per_cell(state, trace, kn):
                if Bl == 1:
                    # one sim's tile blocks per batch cell: strip the
                    # [1] batch dim and run the plain engine under the
                    # tile exchange — real lax.cond gating stays alive
                    sq = jax.tree_util.tree_map
                    out = one(*(sq(lambda x: x[0], t)
                                for t in (state, trace, kn)), px)
                    return sq(lambda x: x[None], out)
                return over_sims(px)(state, trace, kn)

            return _shard_map(
                per_cell, mesh=mesh,
                in_specs=(state_specs, trace_specs, knob_specs),
                out_specs=(state_specs, P("batch"), P("batch"),
                           P("batch"), P("batch")))

        if not self.shard_batch:
            return over_sims()

        from jax.sharding import Mesh, PartitionSpec as P

        from graphite_tpu.parallel.mesh import _shard_map

        K = self._sims_per_dev
        mesh = Mesh(np.array(jax.devices()), ("b",))

        def per_device(state, trace, kn):
            if K > 1:
                return over_sims()(state, trace, kn)
            # one sim per device: strip the [1] batch dim and run
            # the plain UNBATCHED program — real lax.cond gating,
            # bit-identical to a sequential Simulator run
            squeeze = jax.tree_util.tree_map
            out = one(*(squeeze(lambda x: x[0],
                                t) for t in (state, trace, kn)))
            return squeeze(lambda x: x[None], out)

        return _shard_map(per_device, mesh=mesh,
                          in_specs=(P("b"), P("b"), P("b")),
                          out_specs=P("b"))

    def _sync_with_sim(self):
        """Drop caches derived from the wrapped sim's program when its
        identity changed (attach_telemetry after this runner was built):
        the lowering, the jitted runner, and the broadcast initial
        states all bake the telemetry spec/ring in, and serving stale
        ones would certify or execute a different artifact than the
        sim describes."""
        if self._sim_lower_gen != self.sim.lower_gen:
            self._sim_lower_gen = self.sim.lower_gen
            self._lowered = {}
            self._runner = None
            self._runner_max_quanta = None
            self._states0 = None
            self._dtr = None

    def _get_runner(self, max_quanta: int):
        self._sync_with_sim()
        if self._runner is None or self._runner_max_quanta != max_quanta:
            fn = self._runner_fn(max_quanta)

            def campaign(states, traces, knobs):
                return fn(states, traces, knobs)

            # the scope registry's tag in the module's name: a cached
            # executable cannot be served without its scopes (obs/scopes)
            self._runner = jax.jit(tagged(campaign))
            self._runner_max_quanta = max_quanta
        return self._runner

    def attach_tracer(self, tracer) -> None:
        """Attach (or, with None, detach) an `obs.Tracer`: every later
        `run()` records one `run-<n>` trace (or the caller's `trace_id`)
        of `obs.trace.RUN_SPANS`, each also a `gt:<name>`
        TraceAnnotation, exactly as `Simulator.attach_tracer` (`tracer=`
        at construction attaches it from the start).  Host side
        only; with a tracer run() adds ONE `block_until_ready` (the
        `wait` span)."""
        self.tracer = tracer

    def _spans(self, trace_id=None):
        if self.tracer is None:
            return NO_SPANS
        return RunSpans(self.tracer, trace_id)

    def compiled_text(self, max_quanta: int = 1_000_000) -> str:
        """Optimized HLO text of the batched program `run()` dispatches,
        each instruction with its `op_name` path (`obs/scopes.py`)."""
        return executable_text(self._get_runner(max_quanta),
                               self.abstract_inputs())

    def abstract_inputs(self) -> tuple:
        """(states, traces, knobs) as `run()` passes them, the two big
        ones as shapes only: enough to trace, lower or look up the
        program, with nothing placed on the device."""
        from graphite_tpu.engine.state import DeviceTrace
        from graphite_tpu.sweep.pack import PackedTraces

        B = self.pack.n_sims
        states_abs = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((B,) + jnp.shape(x),
                                           jnp.result_type(x)),
            self.sim.state)
        dtr_abs = DeviceTrace(**{
            f: jax.ShapeDtypeStruct(getattr(self.pack, f).shape,
                                    getattr(self.pack, f).dtype)
            for f in PackedTraces._TRACE_FIELDS})
        return states_abs, dtr_abs, self.knobs

    def _batched_inputs(self):
        """The [B, ...] initial states and [B, T, L] device traces,
        built once and cached so repeat run() calls (timed benchmark
        loops) measure the program, not a host->device re-upload.
        Building them is the set-up span `place` (its `programs`: what
        JAX compiled or loaded for it); with a tracer it ends in one
        `block_until_ready`, so that the span holds the device's part."""
        from graphite_tpu.engine.simulator import tree_bytes

        self._sync_with_sim()
        if self._states0 is None:
            B = self.pack.n_sims
            span = SetupSpans(self.tracer)
            with span("place", sims=B) as made:
                before = PROGRAMS.snapshot()
                self._states0 = jax.tree_util.tree_map(
                    lambda x: jnp.broadcast_to(x[None], (B,) + x.shape),
                    self.sim.state)
                self._dtr = self.pack.device_traces()
                if span.on:
                    jax.block_until_ready((self._states0, self._dtr))
                cost = PROGRAMS.since(before)
                made.attrs.update(
                    bytes=tree_bytes((self._states0, self._dtr)),
                    programs=cost["programs_compiled"]
                    + cost["programs_loaded"])
        return self._states0, self._dtr

    def lower(self, max_quanta: int = 4096):
        """The batched campaign program as a ClosedJaxpr plus its flat
        invar paths (states first, then traces, then knob leaves) — the
        program auditor's input (analysis/audit.py; the knob-fold rule
        maps knob names to invars via the paths).

        Pure tracing over abstract inputs: make_jaxpr only needs avals,
        so audit-only callers never pay the [B, ...] state broadcast or
        the [B, T, L] trace upload run() caches for execution.
        Lower-once: cached per max_quanta, so audit + cost +
        fingerprint share one tracing (`lower_count` is the probe), and
        on one device the jit shares it too."""
        from graphite_tpu.analysis.walk import invar_path_strings

        self._sync_with_sim()
        hit = self._lowered.get(max_quanta)
        if hit is not None:
            return hit
        inputs = self.abstract_inputs()
        jitted = None if isinstance(self.layout_spec, tuple) \
            else self._get_runner(max_quanta)
        if hasattr(jitted, "trace"):
            # the jit's OWN trace (what `make_jaxpr` would return): a
            # run() after this dispatches without tracing the program a
            # second time (19.6 s at 64 tiles on the v5e's host, PR 31)
            closed = jitted.trace(*inputs).jaxpr
        else:
            # a mesh layout lowers over a device-less AbstractMesh; an
            # injected executable (AOT / cache hit) has no trace to share
            closed = jax.make_jaxpr(self._runner_fn(
                max_quanta, abstract=True))(*inputs)
        self.lower_count += 1
        hit = (closed, invar_path_strings(inputs))
        self._lowered[max_quanta] = hit
        return hit

    def run(self, max_quanta: int = 1_000_000,
            trace_id: "str | None" = None) -> SweepOutcome:
        """Run the batch: ONE dispatch, one batched fetch, B results.
        With a tracer attached (`attach_tracer`) the call records `run`
        > `dispatch` > `wait` > `fetch` > `results` (obs/trace.py:
        RUN_SPANS) under `run-<n>` or the caller's `trace_id`."""
        span = self._spans(trace_id)
        with span("run", call="sweep"):
            return self._run(max_quanta, span)

    def _run(self, max_quanta: int, span) -> SweepOutcome:
        from graphite_tpu.engine.simulator import (
            DeadlockError, MailboxOverflowError, Simulator,
        )

        # B identical initial states (same config/geometry -> same init)
        states0, dtr = self._batched_inputs()
        with span("dispatch", parent="run"):
            state, nq_d, deadlock_d, iters_d, idle_d = self._get_runner(
                max_quanta)(states0, dtr, self.knobs)
        if span.on:
            with span("wait", parent="dispatch"):
                jax.block_until_ready((nq_d, deadlock_d, iters_d))
        net_part, mem_part, ioc_part, tel_part, prof_part, hist_part = \
            Simulator._result_parts(state)
        skips_d = None if state.mem is None else (
            state.mem.phase_skips, getattr(state.mem, "base_skips", None))
        # ONE batched device->host fetch: control flags, every summary
        # counter, the rings and the gates' skip counts
        with span("fetch", parent="wait"):
            (nq, deadlock, overflow, done, core_h, net_h, mem_h, ioc_h,
             tel_h, prof_h, hist_h, iters, idle, skips_h,
             power_h) = jax.device_get((
                nq_d, deadlock_d, state.net.overflow, state.done,
                state.core, net_part, mem_part, ioc_part, tel_part,
                prof_part, hist_part, iters_d, idle_d, skips_d,
                self.sim._power_part(state)))
        if overflow.any():
            raise MailboxOverflowError(
                f"mailbox ring overflow in sim(s) "
                f"{np.flatnonzero(overflow).tolist()}; re-run with a "
                "larger mailbox_depth")
        if deadlock.any():
            raise DeadlockError(
                f"no progress across a quantum in sim(s) "
                f"{np.flatnonzero(deadlock).tolist()}")
        undone = ~done.all(axis=1)
        if undone.any():
            raise RuntimeError(
                f"sim(s) {np.flatnonzero(undone).tolist()} exceeded "
                f"max_quanta={max_quanta}")
        # self.sim.state keeps the PRISTINE initial state: repeat run()
        # calls (timed benchmark loops) restart the campaign from zero
        self.last_n_iterations = int(np.max(iters))
        self.last_run_dispatches = 1
        with span("results", parent="fetch"):
            return self._outcome(nq, iters, idle, core_h, net_h, mem_h,
                                 ioc_h, tel_h, prof_h, hist_h, skips_h,
                                 power_h, span)

    def _outcome(self, nq, iters, idle, core_h, net_h, mem_h, ioc_h, tel_h,
                 prof_h, hist_h, skips_h, power_h=None,
                 span=NO_SPANS) -> SweepOutcome:
        """Demux the fetched host arrays into B SimResults."""
        B = self.pack.n_sims

        def row(tree, b):
            return jax.tree_util.tree_map(lambda x: x[b], tree)

        timelines = None
        if self.sim.telemetry_spec is not None and tel_h is not None:
            from graphite_tpu.obs.telemetry import Timeline

            # the whole [B, S, n_series] ring rode the ONE batched fetch
            # above; demux sim-by-sim host-side (shard_map campaigns
            # gather per-device buffers through the out_specs, so the
            # same demux serves both batching programs)
            buf_h, count_h = np.asarray(tel_h[0]), np.asarray(tel_h[1])
            timelines = [
                Timeline.from_host_state(self.sim.telemetry_spec,
                                         buf_h[b], int(count_h[b]))
                for b in range(B)
            ]
        profiles = None
        if self.sim.profile_spec is not None and prof_h is not None:
            from graphite_tpu.obs.profile import demux_profiles

            # the [B, S, T, m] ring rode the same ONE batched fetch;
            # the demux serves vmap and batch-shard_map campaigns alike
            profiles = demux_profiles(self.sim.profile_spec, prof_h)
        hists = None
        if self.sim.hist_spec is not None and hist_h is not None:
            from graphite_tpu.obs.hist import demux_hists

            # the [B, (T,) H, B'] count ring rode the same ONE batched
            # fetch; the demux serves vmap and shard_map campaigns alike
            hists = demux_hists(self.sim.hist_spec, hist_h)
        rows = [(row(core_h, b), row(net_h, b),
                 None if mem_h is None else row(mem_h, b))
                for b in range(B)]
        powers, power = [None] * B, None
        if power_h is not None:
            from graphite_tpu.trace.schema import Op

            # each sim's V/f table, its energy closed on the host by the
            # integers a solo run closes with (`_power_host`), and the
            # scalars of the two a result line carries
            with span("power_demux", parent="results", sims=B):
                powers = [self.sim._power_host(row(power_h, b), *rows[b])
                          for b in range(B)]
                sets = (self.pack.op == int(Op.DVFS_SET)).sum(axis=(1, 2))
                power = [power_row(*powers[b], int(sets[b]),
                                   self.sim.params.dvfs.core_domain)
                         for b in range(B)]
        results = [
            self.sim._results_host(
                *rows[b], int(nq[b]),
                None if ioc_h is None else row(ioc_h, b),
                telemetry=None if timelines is None else timelines[b],
                profile=None if profiles is None else profiles[b],
                hist=None if hists is None else hists[b],
                power=powers[b])
            for b in range(B)
        ]
        phase_skips = base_skips = None
        if skips_h is not None:
            from graphite_tpu.engine.simulator import mem_phase_names
            from graphite_tpu.memory.engine import BASE_SKIP_NAMES

            def named(names, rows):
                return None if rows is None else [
                    {n: int(v) for n, v in zip(names, rows[b].tolist())}
                    for b in range(B)]

            phase_skips = named(mem_phase_names(self.sim.params),
                                skips_h[0])
            base_skips = named(BASE_SKIP_NAMES, skips_h[1])
        return SweepOutcome(results=results, knobs=self.knobs,
                            n_iterations=np.asarray(iters),
                            n_quanta=np.asarray(nq),
                            phase_skips=phase_skips,
                            base_skips=base_skips,
                            idle_iterations=np.asarray(idle),
                            power=power,
                            seeds=self.pack.seeds,
                            quantum_valid=self.sim.quantum_ps is not None,
                            timelines=timelines,
                            profiles=profiles,
                            hists=hists,
                            layout=self.layout_name)
