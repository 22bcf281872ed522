"""Device-resident telemetry timelines: in-sim sampled metrics, no host sync.

Graphite's statistics thread wakes at every barrier quantum that crosses
the sampling interval and appends time-series records to trace files
(`statistics_thread.h:8-28`, knobs `carbon_sim.cfg:394-411`).  The port's
chunked equivalent (`system/statistics.py`) chops the one-compiled-region
simulation into host-driven chunks — one host<->device round trip PER
SAMPLE, the dispatch tail rounds 6 and 7 fought to remove.

This module records the timeline ON DEVICE instead: a preallocated ring
buffer `int64[S, n_series]` rides the simulation carry
(`engine/state.SimState.telemetry`), and the outer quantum loop
(`engine/step.run_simulation` and the `barrier_host_batch` dispatch path)
appends one row whenever simulated time crosses the next
`sample_interval_ps` boundary — the same barrier-quantum sampling points
the reference uses.  No callbacks, no infeed: the program still passes the
host-sync audit lint, and the host reads the whole timeline back in the
one post-run fetch it already pays.

Series are drawn from state already in the carry (cheap scalar
reductions): per-phase gate-skip deltas, memory-counter deltas (misses,
invalidations, evictions), USER-net packet injection, per-tile clock
spread (min/max/mean), zero-progress stall quanta, and iteration/quantum
counts.  `telemetry=None` (the default everywhere) constant-folds the
recording away to a bit-identical program — the same contract as the
round-7 `knobs=None`, jaxpr-asserted in tests and enforced by the
`telemetry-off` audit lint.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

I64 = jnp.int64
_BIG = 2**62

# Series that record the sampled LEVEL; everything else records the
# since-last-sample DELTA of a monotone cumulative counter (the delta is
# computed on device against the `prev` snapshot in TelemetryState, so
# ring wraparound never corrupts differencing).
LEVEL_SERIES = ("time_ps", "clock_min_ps", "clock_max_ps", "clock_mean_ps")

# Always-available series (state the core carry already holds).
CORE_SERIES = (
    "time_ps",        # laggard non-done clock (max clock once all done)
    "quanta",         # outer-loop quanta since last sample
    "iterations",     # subquantum engine iterations since last sample
    "stall_quanta",   # zero-progress quanta (boundary jumps / barrier stalls)
    "instructions",   # committed instructions (all tiles)
    "packets_sent",   # USER-net packet injection (all tiles)
    "sync_stall_ps",  # barrier/mutex/cond stall time (all tiles)
    "clock_min_ps",
    "clock_max_ps",
    "clock_mean_ps",
)

# Memory-engine counter series (require EngineParams.mem); the per-phase
# gate-skip series ride alongside, named skip_<phase> off the engines'
# own `mem_phase_names` (one source of truth — no parallel name list).
MEM_SERIES = ("l2_misses", "invalidations", "evictions")

# Energy series (round 14): cumulative picojoules priced from the event
# counters already in the carry.  Opt-in via TelemetrySpec.energy_prices
# — never part of the default dense selection, so every pre-round-14
# program (and its locked fingerprint/budget) is untouched.
ENERGY_SERIES = ("energy_pj",)

SKIP_PREFIX = "skip_"


def counts_the_program(series: str) -> bool:
    """Whether `series` counts what the PROGRAM ran rather than the sim:
    the gates' skips and the iterations.  Under a campaign's sim axis
    the gates and the block's exit are keyed on the OR over the batch
    (`ParallelCtx.any_sim`), so no solo run is these series' oracle."""
    return series == "iterations" or series.startswith(SKIP_PREFIX)


@dataclasses.dataclass(frozen=True)
class EnergyPrices:
    """Per-event energy prices in integer picojoules — the static
    constants the `energy_pj` series folds into the compiled step.

    Each field prices one counter class the simulation carry already
    holds (MemCounters + instruction/packet counts), so the cumulative
    energy is a handful of multiply-adds over scalar reductions — a
    masked add-a-delta ring row like every other series, never a cond
    payload.  Integer pJ keeps the series int64-exact (hand-steppable
    oracle, bit-stable across platforms); sub-pJ events round at price
    construction, not per sample.

    `from_power_model` derives the prices from the McPAT/DSENT native
    energy library (`power/interface.py`) at a given technology node —
    the same per-event model `TileEnergyMonitor` charges post-run, now
    feeding a live device timeline.  Explicit field values keep tests
    (and air-gapped runs) independent of the native build.
    """

    instruction_pj: int = 0   # core front-end+bypass per committed instr
    l1i_access_pj: int = 0    # per L1-I lookup (hits + misses)
    l1d_access_pj: int = 0    # per L1-D access (read/write, hit/miss)
    l2_access_pj: int = 0     # per L2 lookup (hits + misses)
    l2_miss_pj: int = 0       # additional per L2 miss (tag + refill)
    invalidation_pj: int = 0  # per INV_REQ served with a valid line
    eviction_pj: int = 0      # per L2 eviction writeback
    dram_access_pj: int = 0   # per DRAM line read/write
    packet_pj: int = 0        # per USER-net packet injected (router+link)

    # fields that price MemCounters events — a memoryless program cannot
    # record them, so resolve() rejects nonzero mem prices there
    MEM_FIELDS = ("l1i_access_pj", "l1d_access_pj", "l2_access_pj",
                  "l2_miss_pj", "invalidation_pj", "eviction_pj",
                  "dram_access_pj")

    def __post_init__(self):
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if int(v) != v or int(v) < 0:
                raise ValueError(
                    f"EnergyPrices.{f.name} must be a non-negative "
                    f"integer picojoule price, got {v!r}")
            object.__setattr__(self, f.name, int(v))

    def needs_mem(self) -> bool:
        return any(getattr(self, f) for f in self.MEM_FIELDS)

    @classmethod
    def from_power_model(cls, node_nm: int = 45, *, voltage: float = 1.0,
                         line_bytes: int = 64,
                         l1_bytes: int = 32 * 1024, l1_assoc: int = 4,
                         l2_bytes: int = 512 * 1024, l2_assoc: int = 8
                         ) -> "EnergyPrices":
        """Price the events through the native McPAT/DSENT model
        (builds `native/libgraphite_energy.so` on first use)."""
        from graphite_tpu.power.interface import (
            DSENTInterface, McPATCacheInterface, McPATCoreInterface,
            load_native,
        )

        def pj(joules: float) -> int:
            return int(round(joules * 1e12))

        core = McPATCoreInterface(node_nm)
        l1 = McPATCacheInterface(node_nm, l1_bytes, l1_assoc, line_bytes)
        l2 = McPATCacheInterface(node_nm, l2_bytes, l2_assoc, line_bytes)
        noc = DSENTInterface(node_nm)
        l1o = l1.at_voltage(voltage)
        l2o = l2.at_voltage(voltage)
        return cls(
            instruction_pj=pj(core.dynamic_energy_j(
                voltage, instructions=1)),
            l1i_access_pj=pj(l1o.read_energy_j),
            l1d_access_pj=pj((l1o.read_energy_j + l1o.write_energy_j) / 2),
            l2_access_pj=pj(l2o.read_energy_j),
            l2_miss_pj=pj(l2o.tag_energy_j + l2o.write_energy_j),
            invalidation_pj=pj(l2o.tag_energy_j),
            eviction_pj=pj(l2o.write_energy_j),
            dram_access_pj=pj(load_native().dram_access_energy_j(
                node_nm, line_bytes)),
            packet_pj=pj(noc.router_dynamic_energy_j(voltage, 1)
                         + noc.link_dynamic_energy_j(voltage, 1)),
        )


def tile_energy_pj(ep: EnergyPrices, state, dvfs=None) -> jax.Array:
    """Cumulative per-tile event energy int64[T] — THE definition of
    the energy ladder, shared by the scalar `energy_pj` series (which
    reduces it with jnp.sum) and the round-16 per-tile profile series
    (which records it as-is), so the per-tile column sums over T to
    the scalar column exactly and a new price term cannot land in one
    ring but not the other.  Integer pJ prices fold as literals into a
    few multiply-adds; zero-priced terms add no ops at all.

    With `dvfs` (a `models.dvfs.DvfsParams`) and a runtime DVFS carry
    attached (`SimState.dvfs_rt`), each term is scaled by its module's
    domain V²·f factor (Q16 integer, level 0 = the prices' reference
    point): events-to-date priced at the domain's CURRENT operating
    point — exact whenever the domain's frequency is constant over the
    measurement window (the campaign case), an at-current-point
    approximation across in-window transitions.  `dvfs=None` (the
    default) traces the identical jaxpr as before round 19."""
    core = state.core
    T = core.clock_ps.shape[0]
    if dvfs is not None and getattr(state, "dvfs_rt", None) is not None:
        from graphite_tpu.dvfs.levels import energy_scale_q16

        rt = state.dvfs_rt
        sc = energy_scale_q16(dvfs, rt.domain_mhz, rt.domain_mv)
        dom = dvfs.module_domains

        def _at_point(val, module):
            return (val * sc[dom[module]]) >> 16
    else:
        def _at_point(val, module):
            return val
    # term -> models.dvfs.DVFS_MODULES index (CORE, L1_ICACHE, L1_DCACHE,
    # L2_CACHE, DIRECTORY, NETWORK_USER, NETWORK_MEMORY)
    e = jnp.zeros((T,), I64)
    if ep.instruction_pj:
        e = e + _at_point(core.instruction_count * ep.instruction_pj, 0)
    if ep.packet_pj:
        e = e + _at_point(state.net.packets_sent * ep.packet_pj, 5)
    if state.mem is not None:
        mc = state.mem.counters
        terms = (
            (ep.l1i_access_pj, 1, (mc.l1i_hits, mc.l1i_misses)),
            (ep.l1d_access_pj, 2, (mc.l1d_read_hits, mc.l1d_read_misses,
                                   mc.l1d_write_hits, mc.l1d_write_misses)),
            (ep.l2_access_pj, 3, (mc.l2_hits, mc.l2_misses)),
            (ep.l2_miss_pj, 3, (mc.l2_misses,)),
            (ep.invalidation_pj, 4, (mc.invalidations,)),
            (ep.eviction_pj, 3, (mc.evictions,)),
            (ep.dram_access_pj, 6, (mc.dram_reads, mc.dram_writes)),
        )
        for price, module, arrs in terms:
            if price:
                n = arrs[0]
                for a in arrs[1:]:
                    n = n + a
                e = e + _at_point(n * price, module)
    elif ep.needs_mem():
        raise ValueError(
            "energy_prices price memory events but this program has no "
            "memory subsystem")
    return e


def available_series(params) -> "tuple[str, ...]":
    """Every series the given EngineParams can record."""
    out = CORE_SERIES
    if params.mem is not None:
        from graphite_tpu.engine.simulator import mem_phase_names

        out = out + MEM_SERIES + tuple(
            SKIP_PREFIX + n for n in mem_phase_names(params))
    return out


@dataclasses.dataclass(frozen=True)
class TelemetrySpec:
    """What to record: sampling interval, ring depth S, series selection.

    `series=None` selects every series the engine parameters support
    (the dense spec).  `resolve(params)` validates the selection against
    the program and returns a spec with a concrete ordered tuple —
    `time_ps` always first (the demux key) — which is what the engine
    and the demux consume.

    `energy_prices` (an `EnergyPrices`) makes the `energy_pj` series
    available: cumulative event energy priced from the carry's own
    counters.  It is opt-in — with `energy_prices=None` (the default)
    `energy_pj` is neither offered nor selected, so the dense spec (and
    every locked pre-round-14 program) is unchanged.
    """

    sample_interval_ps: int
    n_samples: int = 256
    series: "tuple[str, ...] | None" = None
    # filled by resolve(): the engine's protocol phase names in skip-
    # vector order (`mem_phase_names` — the one source of truth), so a
    # SUBSET of skip_* series still indexes the right phase_skips slot
    phase_names: "tuple[str, ...]" = ()
    # per-event pJ prices enabling the energy_pj series (round 14)
    energy_prices: "EnergyPrices | None" = None

    def __post_init__(self):
        if int(self.sample_interval_ps) <= 0:
            raise ValueError("sample_interval_ps must be positive")
        if int(self.n_samples) <= 0:
            raise ValueError("n_samples must be positive")
        if self.series is not None:
            object.__setattr__(self, "series", tuple(self.series))

    @property
    def resolved(self) -> bool:
        return self.series is not None

    def resolve(self, params) -> "TelemetrySpec":
        avail = available_series(params)
        if self.energy_prices is not None:
            if params.mem is None and self.energy_prices.needs_mem():
                raise ValueError(
                    "energy_prices set nonzero memory-event prices but "
                    "this program has no memory subsystem (only "
                    "instruction_pj/packet_pj apply to memoryless "
                    "traces)")
            avail = avail + ENERGY_SERIES
        elif self.series is not None \
                and any(s in ENERGY_SERIES for s in self.series):
            raise ValueError(
                "the energy_pj series needs TelemetrySpec.energy_prices "
                "(an obs.EnergyPrices — explicit pJ fields or "
                "EnergyPrices.from_power_model)")
        if self.series is None:
            sel = avail
        else:
            unknown = [s for s in self.series if s not in avail]
            if unknown:
                raise ValueError(
                    f"unknown/unavailable telemetry series {unknown} "
                    f"(this program offers: {', '.join(avail)})")
            # time_ps leads (demux/report key); preserve the caller's
            # order otherwise, deduplicated
            seen = []
            for s in ("time_ps",) + tuple(self.series):
                if s not in seen:
                    seen.append(s)
            sel = tuple(seen)
        phase_names = ()
        if params.mem is not None:
            from graphite_tpu.engine.simulator import mem_phase_names

            phase_names = tuple(mem_phase_names(params))
        return dataclasses.replace(self, series=sel,
                                   phase_names=phase_names)

    @property
    def n_series(self) -> int:
        if self.series is None:
            raise ValueError("spec is unresolved (call resolve(params))")
        return len(self.series)

    def buffer_sig(self) -> "tuple[tuple, str]":
        """The ring buffer's aval signature ((S, n_series), dtype) — what
        the audit lints match (cond-payload forbidden set when telemetry
        is ON; the telemetry-off rule when it must be absent)."""
        return ((int(self.n_samples), self.n_series), "int64")

    def ring_bytes(self) -> int:
        """Per-sim device residency of this spec's TelemetryState: the
        [S, n_series] ring + the prev snapshot + the five scalar
        cursors, all int64.  The ONE size model the residency budget
        consumes (analysis/cost.residency_breakdown) — a campaign pays
        B x this, which is why `attach_telemetry` refuses layouts that
        cannot afford the ring."""
        (S, n), dtype = self.buffer_sig()
        item = np.dtype(dtype).itemsize
        return S * n * item + n * item + 5 * item

    def delta_mask(self) -> np.ndarray:
        """bool[n_series]: True where the series records a delta."""
        return np.array([s not in LEVEL_SERIES for s in self.series],
                        dtype=bool)


@struct.dataclass
class TelemetryState:
    """The device-resident recording state (rides SimState.telemetry).

    `buf` is the [S, n_series] ring; `count` the total samples taken
    (including overwritten ones — `count % S` is the next write slot);
    `prev` the cumulative snapshot at the last sample (delta baseline);
    `next_ps` the next simulated-time sample boundary.  `quanta`,
    `iters`, `stall_quanta` are cumulative loop counters the outer loop
    feeds the tick (they are series sources, not engine state)."""

    buf: jax.Array          # int64[S, n_series]
    prev: jax.Array         # int64[n_series]
    count: jax.Array        # int32[]
    next_ps: jax.Array      # int64[]
    quanta: jax.Array       # int64[]
    iters: jax.Array        # int64[]
    stall_quanta: jax.Array  # int64[]


def init_telemetry(spec: TelemetrySpec) -> TelemetryState:
    if not spec.resolved:
        raise ValueError("init_telemetry needs a resolved TelemetrySpec")
    n = spec.n_series
    return TelemetryState(
        buf=jnp.zeros((int(spec.n_samples), n), I64),
        prev=jnp.zeros((n,), I64),
        count=jnp.zeros((), jnp.int32),
        next_ps=jnp.asarray(int(spec.sample_interval_ps), I64),
        quanta=jnp.zeros((), I64),
        iters=jnp.zeros((), I64),
        stall_quanta=jnp.zeros((), I64),
    )


def _series_values(spec: TelemetrySpec, state, ts: TelemetryState,
                   sim_time: jax.Array, dvfs=None) -> jax.Array:
    """The CUMULATIVE value of every selected series, int64[n_series].
    Delta series are differenced against `ts.prev` by the tick."""
    core = state.core
    clocks = core.clock_ps
    T = clocks.shape[0]
    vals = {}
    sel = set(spec.series)
    if "time_ps" in sel:
        vals["time_ps"] = sim_time
    if "quanta" in sel:
        vals["quanta"] = ts.quanta
    if "iterations" in sel:
        vals["iterations"] = ts.iters
    if "stall_quanta" in sel:
        vals["stall_quanta"] = ts.stall_quanta
    if "instructions" in sel:
        vals["instructions"] = jnp.sum(core.instruction_count)
    if "packets_sent" in sel:
        vals["packets_sent"] = jnp.sum(state.net.packets_sent)
    if "sync_stall_ps" in sel:
        vals["sync_stall_ps"] = jnp.sum(core.sync_stall_ps)
    if "clock_min_ps" in sel:
        vals["clock_min_ps"] = jnp.min(clocks)
    if "clock_max_ps" in sel:
        vals["clock_max_ps"] = jnp.max(clocks)
    if "clock_mean_ps" in sel:
        vals["clock_mean_ps"] = jnp.sum(clocks) // T
    if state.mem is not None:
        mc = state.mem.counters
        if "l2_misses" in sel:
            vals["l2_misses"] = jnp.sum(mc.l2_misses)
        if "invalidations" in sel:
            vals["invalidations"] = jnp.sum(mc.invalidations)
        if "evictions" in sel:
            vals["evictions"] = jnp.sum(mc.evictions)
    if "energy_pj" in sel:
        ep = spec.energy_prices
        if ep is None:
            raise ValueError("energy_pj selected without energy_prices")
        vals["energy_pj"] = jnp.sum(tile_energy_pj(ep, state, dvfs))
    skip_names = [s for s in spec.series if s.startswith(SKIP_PREFIX)]
    if skip_names:
        if state.mem is None:
            raise ValueError("skip_* series need the memory subsystem")
        # spec.phase_names carries the engine's `mem_phase_names` order,
        # so even a SUBSET of skip_* series indexes its true slot
        for s in skip_names:
            idx = spec.phase_names.index(s[len(SKIP_PREFIX):])
            vals[s] = state.mem.phase_skips[idx]
    missing = [s for s in spec.series if s not in vals]
    if missing:
        raise ValueError(f"series {missing} unavailable in this program")
    return jnp.stack([vals[s].astype(I64) for s in spec.series])


def telemetry_tick(spec: TelemetrySpec, state, *,
                   progress: jax.Array, blk_iters: jax.Array,
                   dvfs=None) -> TelemetryState:
    """One outer-loop quantum's telemetry update (device-side, traced).

    Advances the cumulative loop counters, then — when simulated time
    (the laggard non-done clock; max clock once all tiles are done)
    crossed `next_ps`, or on the completing quantum — appends one row to
    the ring.  The row store is a MASKED add-a-delta scatter, never a
    lax.cond: the `[S, n_series]` buffer must not ride any cond output
    (the cond-payload audit rule forbids its aval), and the row itself
    is ~a dozen scalar reductions — noise next to a quantum.
    """
    ts = state.telemetry
    if ts is None:
        raise ValueError(
            "telemetry spec given but SimState.telemetry is None "
            "(init the state with obs.init_telemetry)")
    done = state.done
    clocks = state.core.clock_ps
    all_done = jnp.all(done)
    pending_min = jnp.min(jnp.where(~done, clocks, jnp.asarray(_BIG, I64)))
    sim_time = jnp.where(all_done, jnp.max(clocks), pending_min)

    zero = (progress == 0) & jnp.any(~done)
    ts = ts.replace(
        quanta=ts.quanta + 1,
        iters=ts.iters + blk_iters.astype(I64),
        stall_quanta=ts.stall_quanta + zero.astype(I64),
    )

    cur = _series_values(spec, state, ts, sim_time, dvfs)
    # the completing quantum records a final row (the chunked sampler's
    # sample-at-done), making the last cumulative state always visible
    do = (sim_time >= ts.next_ps) | all_done
    row = jnp.where(jnp.asarray(spec.delta_mask()), cur - ts.prev, cur)
    S = int(spec.n_samples)
    slot = (ts.count % S).astype(jnp.int32)
    # add-a-delta under mask: the scatter is the ring's only use, so XLA
    # updates the loop-carried buffer in place (no per-quantum copy)
    buf = ts.buf.at[slot].add(jnp.where(do, row - ts.buf[slot], 0))
    interval = jnp.asarray(int(spec.sample_interval_ps), I64)
    return ts.replace(
        buf=buf,
        prev=jnp.where(do, cur, ts.prev),
        count=ts.count + do.astype(jnp.int32),
        next_ps=jnp.where(do, (sim_time // interval + 1) * interval,
                          ts.next_ps),
    )


# ---------------------------------------------------------------------------
# host-side timeline (post-run demux)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Timeline:
    """One sim's recorded timeline, demuxed to chronological host rows.

    `data[i, j]` is sample i of series `series[j]`; delta series hold
    since-previous-sample deltas, level series sampled values.  When the
    run took more than S samples the ring wrapped: `data` holds the LAST
    S samples and `n_total` the true count (`wrapped` flags the loss)."""

    series: "tuple[str, ...]"
    data: np.ndarray          # int64[n_recorded, n_series]
    n_total: int
    sample_interval_ps: int
    wrapped: bool = False

    @classmethod
    def from_host_state(cls, spec: TelemetrySpec, buf: np.ndarray,
                        count: int) -> "Timeline":
        S = int(spec.n_samples)
        count = int(count)
        buf = np.asarray(buf)
        if count <= S:
            data = buf[:count].copy()
            wrapped = False
        else:
            slot = count % S
            data = np.concatenate([buf[slot:], buf[:slot]], axis=0)
            wrapped = True
        return cls(series=tuple(spec.series), data=data, n_total=count,
                   sample_interval_ps=int(spec.sample_interval_ps),
                   wrapped=wrapped)

    def __len__(self) -> int:
        return self.data.shape[0]

    def col(self, name: str) -> np.ndarray:
        return self.data[:, self.series.index(name)]

    @property
    def time_ns(self) -> np.ndarray:
        return self.col("time_ps") // 1000

    def summary(self) -> dict:
        """Timeline-derived scalars for bench/CI JSON: peak USER-net
        injection rate (packets per ns per tile-count-free total) and
        the mean per-tile clock spread, plus bookkeeping."""
        out = {
            "samples": int(len(self)),
            "samples_total": int(self.n_total),
            "wrapped": bool(self.wrapped),
        }
        if len(self) == 0:
            return out
        t_ns = self.time_ns.astype(np.int64)
        dt_ns = np.maximum(np.diff(np.concatenate([[0], t_ns])), 1)
        # wrapped ring: the first retained sample's baseline timestamp
        # was overwritten, so its interval (and any rate computed from
        # it) is unknowable — exclude it from the rate statistics
        rate_sl = slice(1, None) if self.wrapped else slice(None)
        if "packets_sent" in self.series:
            rate = (self.col("packets_sent") / dt_ns)[rate_sl]
            if rate.size:
                out["peak_injection_per_ns"] = float(rate.max())
                out["mean_injection_per_ns"] = float(rate.mean())
        if ("clock_max_ps" in self.series
                and "clock_min_ps" in self.series):
            spread = self.col("clock_max_ps") - self.col("clock_min_ps")
            out["mean_clock_spread_ps"] = float(spread.mean())
            out["max_clock_spread_ps"] = int(spread.max())
        if "stall_quanta" in self.series:
            out["stall_quanta_total"] = int(self.col("stall_quanta").sum())
        out["peaks"] = self.peaks()
        return out

    def peaks(self) -> dict:
        """Per-series maximum with its SAMPLE INDEX and time — so a
        spike is nameable ("l2_misses peaked at sample 17, t=42us")
        instead of only sized.  Clock levels are reported as their
        spread's peak (the raw max of a monotone clock is always the
        last sample, which names nothing)."""
        out = {}
        if len(self) == 0:
            return out
        t_ns = self.time_ns
        base = self.n_total - len(self)

        def peak(name, values):
            i = int(np.argmax(values))
            out[name] = {"max": int(values[i]),
                         "sample": int(base + i),
                         "time_ns": int(t_ns[i])}

        for s in self.series:
            if s == "time_ps" or s in LEVEL_SERIES:
                continue
            peak(s, self.col(s))
        if ("clock_max_ps" in self.series
                and "clock_min_ps" in self.series):
            peak("clock_spread_ps",
                 self.col("clock_max_ps") - self.col("clock_min_ps"))
        return out

    def json_rows(self) -> "list[dict]":
        """One JSON-able dict per sample (tools/report.py output)."""
        rows = []
        for i in range(len(self)):
            row = {"sample": int(self.n_total - len(self) + i),
                   "time_ns": int(self.time_ns[i])}
            for j, s in enumerate(self.series):
                if s == "time_ps":
                    continue
                row[s] = int(self.data[i, j])
            rows.append(row)
        return rows

    def save(self, path: str) -> None:
        np.savez(path, data=self.data,
                 series=np.array(self.series),
                 n_total=self.n_total,
                 sample_interval_ps=self.sample_interval_ps,
                 wrapped=self.wrapped)

    @classmethod
    def load(cls, path: str) -> "Timeline":
        z = np.load(path, allow_pickle=False)
        return cls(series=tuple(str(s) for s in z["series"]),
                   data=np.asarray(z["data"]),
                   n_total=int(z["n_total"]),
                   sample_interval_ps=int(z["sample_interval_ps"]),
                   wrapped=bool(z["wrapped"]))


def timeline_from_state(spec: TelemetrySpec, tstate) -> Timeline:
    """Fetch + demux one sim's TelemetryState (device or host pytree)."""
    buf, count = jax.device_get((tstate.buf, tstate.count))
    return Timeline.from_host_state(spec, np.asarray(buf), int(count))


def demux_timelines(spec: TelemetrySpec, tstate) -> "list[Timeline]":
    """Demux a batched [B, ...] TelemetryState (vmapped campaign or the
    batch-axis shard_map gather) into B per-sim Timelines."""
    buf, count = jax.device_get((tstate.buf, tstate.count))
    buf = np.asarray(buf)
    count = np.asarray(count)
    return [Timeline.from_host_state(spec, buf[b], int(count[b]))
            for b in range(buf.shape[0])]
