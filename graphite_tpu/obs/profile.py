"""Device-resident per-tile profile rings: the spatial profiler.

Graphite's statistics subsystem dumps PER-TILE counters (core, cache,
network, power) at simulation end — `tile.cc:105-123` outputSummary per
tile — and that spatial view is how the HPCA'10 evaluation localizes
hotspots and how the TR-09 clock-skew study characterizes per-tile skew
under the lax schemes.  The round-9 telemetry ring (`obs/telemetry.py`)
records only fleet aggregates (summed counters, clock min/max/mean), so
it can say *that* traffic spiked but not *where*, and *that* clocks
spread but not *which tile is the straggler*.

This module records the spatial dimension: a second device-resident
ring `int64[S, T, m]` rides the simulation carry
(`engine/state.SimState.profile`) next to the scalar ring, sampled on
the SAME simulated-time boundaries (one boundary test per quantum, one
masked add-a-delta row scatter per ring, zero host sync — the program
still passes the host-sync audit lint).  Series are per-tile `[T]`
lanes the carry already holds: clock skew vs the laggard, committed
instructions and trace records, sync/recv stall time, per-tile cache
access/miss and directory-op deltas, USER-net packets in/out, and the
opt-in per-tile `energy_pj` priced through the same `EnergyPrices`
table the scalar series uses.

Cross-ring consistency is free by construction and asserted by
`tests/test_profile.py::TestRecording`: a delta series shared with the
scalar ring sums over T to exactly the scalar column, and
`max(clock_skew_ps) + clock_min_ps == clock_max_ps` sample for sample.

`profile=None` (the default everywhere) constant-folds the recording
away to a bit-identical program — the same contract as `telemetry=None`
(round 9) and `knobs=None` (round 7), jaxpr-asserted in
tests/test_profile.py and enforced by the `profile-off` audit lint.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from graphite_tpu.obs.telemetry import EnergyPrices, tile_energy_pj

I64 = jnp.int64
_BIG = 2**62

# Series that record the sampled LEVEL; everything else records the
# since-last-sample DELTA of a monotone cumulative per-tile counter
# (differenced on device against the `prev` snapshot in ProfileState,
# so ring wraparound never corrupts — exactly the round-9 discipline).
PROFILE_LEVEL_SERIES = ("clock_skew_ps", "freq_mhz")

# Always-available per-tile series (state the core carry already holds
# as [T] lanes).  Names shared with the scalar telemetry ring
# (instructions, sync_stall_ps, packets_sent, ...) sum over T to the
# scalar series — the cross-ring invariant tests/test_profile.py asserts.
PROFILE_CORE_SERIES = (
    "clock_skew_ps",     # tile clock minus the fleet-minimum clock
    "instructions",      # committed instructions, this tile
    "records",           # committed trace records (per-tile progress)
    "sync_stall_ps",     # barrier/mutex/cond stall time, this tile
    "recv_stall_ps",     # blocking-recv stall time, this tile
    "packets_sent",      # USER-net injections, this tile
    "packets_received",  # USER-net receives, this tile
)

# Memory-engine per-tile counter series (require EngineParams.mem).
PROFILE_MEM_SERIES = (
    "l1d_accesses",      # L1-D lookups (read+write, hit+miss)
    "l1d_misses",
    "l2_accesses",       # L2 lookups (hits + misses)
    "l2_misses",
    "dir_accesses",      # directory operations homed at this tile
    "invalidations",
    "evictions",
)

# Per-tile energy (opt-in via ProfileSpec.energy_prices, like round 14's
# scalar series — never part of the dense default, so locked programs
# are untouched).
PROFILE_ENERGY_SERIES = ("energy_pj",)

# Per-tile operating frequency (round 19, opt-in via ProfileSpec.dvfs —
# same never-in-the-dense-default rule, so locked programs with
# series=None resolve unchanged).  A LEVEL series: the sampled MHz, not
# a delta.
PROFILE_DVFS_SERIES = ("freq_mhz",)


def available_tile_series(params) -> "tuple[str, ...]":
    """Every per-tile series the given EngineParams can record."""
    out = PROFILE_CORE_SERIES
    if params.mem is not None:
        out = out + PROFILE_MEM_SERIES
    return out


@dataclasses.dataclass(frozen=True)
class ProfileSpec:
    """What to record per tile: sampling interval, ring depth S, series.

    Mirrors `TelemetrySpec` deliberately — same interval/S fields, same
    resolve-against-the-program flow, same opt-in `energy_prices` — so
    a job carrying both specs samples both rings on one shared cursor
    schedule (the boundary test is identical arithmetic; give both
    specs the same `sample_interval_ps` and the rows align one-to-one,
    which is what makes the cross-ring sum invariant assertable).

    `series=None` selects every per-tile series the engine parameters
    support (the dense spec).  `resolve(params)` validates the
    selection and fills `n_tiles` — `ring_bytes()` and `buffer_sig()`
    need the resolved spec.
    """

    sample_interval_ps: int
    n_samples: int = 256
    series: "tuple[str, ...] | None" = None
    # per-event pJ prices enabling the per-tile energy_pj series
    energy_prices: "EnergyPrices | None" = None
    # True makes the per-tile freq_mhz series available (round 19 —
    # pair with a Simulator dvfs= spec to watch transitions; the core
    # carry always holds the [T] frequency, so the flag only gates the
    # series offering, keeping series=None resolutions unchanged)
    dvfs: bool = False
    # filled by resolve(): the program's tile count (the ring's T axis)
    n_tiles: int = 0

    def __post_init__(self):
        if int(self.sample_interval_ps) <= 0:
            raise ValueError("sample_interval_ps must be positive")
        if int(self.n_samples) <= 0:
            raise ValueError("n_samples must be positive")
        if self.series is not None:
            object.__setattr__(self, "series", tuple(self.series))

    @property
    def resolved(self) -> bool:
        return self.series is not None and self.n_tiles > 0

    def resolve(self, params) -> "ProfileSpec":
        avail = available_tile_series(params)
        if self.energy_prices is not None:
            if params.mem is None and self.energy_prices.needs_mem():
                raise ValueError(
                    "energy_prices set nonzero memory-event prices but "
                    "this program has no memory subsystem (only "
                    "instruction_pj/packet_pj apply to memoryless "
                    "traces)")
            avail = avail + PROFILE_ENERGY_SERIES
        elif self.series is not None \
                and any(s in PROFILE_ENERGY_SERIES for s in self.series):
            raise ValueError(
                "the per-tile energy_pj series needs "
                "ProfileSpec.energy_prices (an obs.EnergyPrices)")
        if self.dvfs:
            avail = avail + PROFILE_DVFS_SERIES
        elif self.series is not None \
                and any(s in PROFILE_DVFS_SERIES for s in self.series):
            raise ValueError(
                "the per-tile freq_mhz series needs ProfileSpec.dvfs=True")
        if self.series is None:
            sel = avail
        else:
            unknown = [s for s in self.series if s not in avail]
            if unknown:
                raise ValueError(
                    f"unknown/unavailable profile series {unknown} "
                    f"(this program offers: {', '.join(avail)})")
            seen = []
            for s in self.series:
                if s not in seen:
                    seen.append(s)
            sel = tuple(seen)
        return dataclasses.replace(self, series=sel,
                                   n_tiles=int(params.n_tiles))

    @property
    def n_series(self) -> int:
        if self.series is None:
            raise ValueError("spec is unresolved (call resolve(params))")
        return len(self.series)

    def buffer_sig(self) -> "tuple[tuple, str]":
        """The profile ring's aval signature ((S, T, m), dtype) — what
        the audit lints match (cond-payload forbidden set when the
        profile is ON; the profile-off rule when it must be absent).
        The [S] times ring is deliberately NOT a lint signature: a
        length-S int64 vector is far too generic an aval to forbid."""
        if not self.resolved:
            raise ValueError("buffer_sig needs a resolved ProfileSpec")
        return ((int(self.n_samples), int(self.n_tiles), self.n_series),
                "int64")

    def ring_bytes(self, tile_shards: int = 1) -> int:
        """Per-sim device residency of this spec's ProfileState: the
        [S, T, m] ring + the [T, m] prev snapshot + the [S] times ring
        + the two scalar cursors, all int64.  The ONE size model the
        residency budget and the admission bill consume
        (analysis/cost.residency_breakdown) — a campaign pays B x this,
        and the T factor is why a 1024-tile dense profile is priced,
        not assumed.

        `tile_shards` (round 18): per-DEVICE bytes under a tile-sharded
        2D campaign layout — the [S, T, m] ring and the [T, m] prev
        snapshot shard their tile axis with the directory (each device
        holds T/tile_shards rows), while the [S] times ring and the
        cursors stay replicated."""
        (S, T, m), dtype = self.buffer_sig()
        item = np.dtype(dtype).itemsize
        ts = max(int(tile_shards), 1)
        if T % ts:
            raise ValueError(
                f"tile count {T} not divisible by tile_shards={ts}")
        Tl = T // ts
        return (S * Tl * m + Tl * m + S + 2) * item

    def delta_mask(self) -> np.ndarray:
        """bool[n_series]: True where the series records a delta."""
        return np.array([s not in PROFILE_LEVEL_SERIES
                         for s in self.series], dtype=bool)


@struct.dataclass
class ProfileState:
    """The device-resident per-tile recording state (rides
    SimState.profile).

    `buf` is the [S, T, m] ring; `times` the [S] sample-time ring
    (simulated picoseconds — the host demux key, since per-tile rows
    have no scalar time column of their own); `prev` the cumulative
    [T, m] snapshot at the last sample; `count` the total samples taken
    (`count % S` is the next write slot); `next_ps` the next
    simulated-time sample boundary."""

    buf: jax.Array       # int64[S, T, m]
    times: jax.Array     # int64[S]
    prev: jax.Array      # int64[T, m]
    count: jax.Array     # int32[]
    next_ps: jax.Array   # int64[]


def init_profile(spec: ProfileSpec) -> ProfileState:
    if not spec.resolved:
        raise ValueError("init_profile needs a resolved ProfileSpec")
    S, T, m = spec.buffer_sig()[0]
    return ProfileState(
        buf=jnp.zeros((S, T, m), I64),
        times=jnp.zeros((S,), I64),
        prev=jnp.zeros((T, m), I64),
        count=jnp.zeros((), jnp.int32),
        next_ps=jnp.asarray(int(spec.sample_interval_ps), I64),
    )


def _tile_series_values(spec: ProfileSpec, state, dvfs=None) -> jax.Array:
    """The CUMULATIVE value of every selected series, int64[T, m].
    Delta series are differenced against `ProfileState.prev` by the
    tick."""
    core = state.core
    clocks = core.clock_ps
    vals = {}
    sel = set(spec.series)
    if "freq_mhz" in sel:
        vals["freq_mhz"] = core.freq_mhz.astype(I64)
    if "clock_skew_ps" in sel:
        # skew vs the laggard: the same jnp.min baseline the scalar
        # ring's clock_min_ps level records, so max-over-tiles of this
        # column plus clock_min_ps reconstructs clock_max_ps exactly
        vals["clock_skew_ps"] = clocks - jnp.min(clocks)
    if "instructions" in sel:
        vals["instructions"] = core.instruction_count
    if "records" in sel:
        vals["records"] = core.idx.astype(I64)
    if "sync_stall_ps" in sel:
        vals["sync_stall_ps"] = core.sync_stall_ps
    if "recv_stall_ps" in sel:
        vals["recv_stall_ps"] = core.recv_stall_ps
    if "packets_sent" in sel:
        vals["packets_sent"] = state.net.packets_sent
    if "packets_received" in sel:
        vals["packets_received"] = state.net.packets_received
    if sel & set(PROFILE_MEM_SERIES):
        if state.mem is None:
            raise ValueError("memory profile series need the memory "
                             "subsystem")
        mc = state.mem.counters
        if "l1d_accesses" in sel:
            vals["l1d_accesses"] = (mc.l1d_read_hits + mc.l1d_read_misses
                                    + mc.l1d_write_hits
                                    + mc.l1d_write_misses)
        if "l1d_misses" in sel:
            vals["l1d_misses"] = mc.l1d_read_misses + mc.l1d_write_misses
        if "l2_accesses" in sel:
            vals["l2_accesses"] = mc.l2_hits + mc.l2_misses
        if "l2_misses" in sel:
            vals["l2_misses"] = mc.l2_misses
        if "dir_accesses" in sel:
            vals["dir_accesses"] = mc.dir_accesses
        if "invalidations" in sel:
            vals["invalidations"] = mc.invalidations
        if "evictions" in sel:
            vals["evictions"] = mc.evictions
    if "energy_pj" in sel:
        ep = spec.energy_prices
        if ep is None:
            raise ValueError("energy_pj selected without energy_prices")
        # the ONE energy ladder (obs/telemetry.tile_energy_pj): the
        # scalar series is jnp.sum of exactly this vector
        vals["energy_pj"] = tile_energy_pj(ep, state, dvfs)
    missing = [s for s in spec.series if s not in vals]
    if missing:
        raise ValueError(f"series {missing} unavailable in this program")
    return jnp.stack([vals[s].astype(I64) for s in spec.series], axis=1)


def profile_tick(spec: ProfileSpec, state, px=None, dvfs=None
                 ) -> ProfileState:
    """One outer-loop quantum's profile update (device-side, traced).

    The boundary test is the SAME arithmetic as `telemetry_tick` —
    simulated time (the laggard non-done clock; max clock once all done)
    crossed `next_ps`, or the completing quantum — so when both rings
    ride one carry with equal intervals, XLA CSEs the shared scalar
    reductions and the two row appends cost one boundary test.  The row
    store is a MASKED add-a-delta scatter, never a lax.cond: the
    [S, T, m] buffer must not ride any cond output (it joins the
    cond-payload forbidden set), and the row itself is a handful of
    [T]-lane reads — noise next to a quantum.

    Under a tile-sharded `px` (the round-18 2D batch x tile campaign)
    the ring's tile axis shards with the directory: `ps.buf` is this
    device's [S, Tl, m] block and `ps.prev` its [Tl, m] snapshot, so
    the full [T, m] row — computed from replicated carry state — is
    sliced to the local lanes before the append (the cursors and the
    [S] times ring stay replicated).  The reassembled-on-fetch ring is
    bit-identical to the solo recording by construction.
    """
    ps = state.profile
    if ps is None:
        raise ValueError(
            "profile spec given but SimState.profile is None "
            "(init the state with obs.init_profile)")
    done = state.done
    clocks = state.core.clock_ps
    all_done = jnp.all(done)
    pending_min = jnp.min(jnp.where(~done, clocks,
                                    jnp.asarray(_BIG, I64)))
    sim_time = jnp.where(all_done, jnp.max(clocks), pending_min)

    cur = _tile_series_values(spec, state, dvfs)           # [T, m]
    if px is not None and px.sharded:
        cur = px.lo(cur)                                   # [Tl, m]
    do = (sim_time >= ps.next_ps) | all_done
    mask = jnp.asarray(spec.delta_mask())                  # [m]
    row = jnp.where(mask[None, :], cur - ps.prev, cur)
    S = int(spec.n_samples)
    slot = (ps.count % S).astype(jnp.int32)
    # add-a-delta under mask: the scatter is the ring's only use, so
    # XLA updates the loop-carried buffer in place (no per-quantum copy)
    buf = ps.buf.at[slot].add(jnp.where(do, row - ps.buf[slot], 0))
    times = ps.times.at[slot].add(
        jnp.where(do, sim_time - ps.times[slot], 0))
    interval = jnp.asarray(int(spec.sample_interval_ps), I64)
    return ps.replace(
        buf=buf,
        times=times,
        prev=jnp.where(do, cur, ps.prev),
        count=ps.count + do.astype(jnp.int32),
        next_ps=jnp.where(do, (sim_time // interval + 1) * interval,
                          ps.next_ps),
    )


# ---------------------------------------------------------------------------
# host-side per-tile profile (post-run demux)
# ---------------------------------------------------------------------------


def grid_shape(n_tiles: int) -> "tuple[int, int]":
    """(rows, cols) of the near-square tile grid heatmaps render —
    matches the emesh topology convention (width = ceil(sqrt(T)))."""
    cols = int(np.ceil(np.sqrt(max(int(n_tiles), 1))))
    rows = int(np.ceil(int(n_tiles) / cols))
    return rows, cols


def gini(values) -> float:
    """Gini coefficient of a non-negative per-tile distribution — the
    traffic-imbalance scalar the straggler summary reports (0 = fully
    balanced, -> 1 = one tile carries everything)."""
    x = np.sort(np.asarray(values, dtype=np.float64))
    n = x.size
    total = x.sum()
    if n == 0 or total == 0:
        return 0.0
    # mean absolute difference via the sorted-rank identity
    ranks = np.arange(1, n + 1)
    return float((2 * (ranks * x).sum() / (n * total)) - (n + 1) / n)


@dataclasses.dataclass
class TileProfile:
    """One sim's recorded per-tile profile, demuxed to chronological
    host rows.

    `data[i, t, j]` is sample i, tile t of series `series[j]`; delta
    series hold since-previous-sample deltas, level series sampled
    values.  `times_ps[i]` is sample i's simulated time.  When the run
    took more than S samples the ring wrapped: `data` holds the LAST S
    samples and `n_total` the true count (`wrapped` flags the loss)."""

    series: "tuple[str, ...]"
    data: np.ndarray          # int64[n_recorded, T, n_series]
    times_ps: np.ndarray      # int64[n_recorded]
    n_total: int
    sample_interval_ps: int
    wrapped: bool = False

    @classmethod
    def from_host_state(cls, spec: ProfileSpec, buf: np.ndarray,
                        times: np.ndarray, count: int) -> "TileProfile":
        S = int(spec.n_samples)
        count = int(count)
        buf = np.asarray(buf)
        times = np.asarray(times)
        if count <= S:
            data = buf[:count].copy()
            tp = times[:count].copy()
            wrapped = False
        else:
            slot = count % S
            data = np.concatenate([buf[slot:], buf[:slot]], axis=0)
            tp = np.concatenate([times[slot:], times[:slot]], axis=0)
            wrapped = True
        return cls(series=tuple(spec.series), data=data, times_ps=tp,
                   n_total=count,
                   sample_interval_ps=int(spec.sample_interval_ps),
                   wrapped=wrapped)

    def __len__(self) -> int:
        return self.data.shape[0]

    @property
    def n_tiles(self) -> int:
        return self.data.shape[1]

    def col(self, name: str) -> np.ndarray:
        """int64[n_recorded, T] — one series across all samples."""
        return self.data[:, :, self.series.index(name)]

    @property
    def time_ns(self) -> np.ndarray:
        return self.times_ps // 1000

    def tile_slice(self, name: str, sample: "int | str" = "total"
                   ) -> np.ndarray:
        """One [T] vector of series `name`: sample index (negative from
        the end), "last", or "total" (delta series sum over samples;
        level series take the last sample — a level has no meaningful
        sum)."""
        col = self.col(name)
        if isinstance(sample, str):
            if sample == "last":
                return col[-1]
            if sample == "total":
                if name in PROFILE_LEVEL_SERIES:
                    return col[-1]
                return col.sum(axis=0)
            raise ValueError(
                f"sample must be an index, 'last', or 'total' "
                f"(got {sample!r})")
        return col[int(sample)]

    def summary(self) -> dict:
        """Straggler/imbalance scalars for bench/CI JSON: per-tile skew
        distribution (max/mean over the whole run, leader + straggler
        tile ids) and traffic concentration (Gini + hottest tile)."""
        out = {
            "samples": int(len(self)),
            "samples_total": int(self.n_total),
            "wrapped": bool(self.wrapped),
            "n_tiles": int(self.n_tiles),
        }
        if len(self) == 0:
            return out
        if "clock_skew_ps" in self.series:
            skew = self.col("clock_skew_ps")
            mean_by_tile = skew.mean(axis=0)
            out["max_skew_ps"] = int(skew.max())
            out["mean_skew_ps"] = float(skew.mean())
            # the laggard everyone waits for has skew ~0; the leader
            # runs furthest ahead of it
            out["straggler_tile"] = int(mean_by_tile.argmin())
            out["leader_tile"] = int(mean_by_tile.argmax())
        for name, key in (("packets_sent", "traffic"),
                          ("l2_misses", "miss")):
            if name in self.series:
                totals = self.tile_slice(name, "total")
                out[f"{key}_gini"] = round(gini(totals), 6)
                out[f"hot_{key}_tile"] = int(totals.argmax())
                out[f"hot_{key}_total"] = int(totals.max())
        return out

    def json_rows(self, series=None, sample: "int | str | None" = None
                  ) -> "list[dict]":
        """One JSON-able dict per (sample, series) with the full [T]
        tile vector — the heatmap CLI's machine rows.  `sample`
        restricts to one time slice ("total"/"last"/index); None emits
        every recorded sample."""
        names = tuple(series) if series else self.series
        rows = []
        if sample is not None:
            for s in names:
                rows.append({"sample": sample
                             if isinstance(sample, str) else int(sample),
                             "series": s,
                             "tiles": [int(v) for v in
                                       self.tile_slice(s, sample)]})
            return rows
        for i in range(len(self)):
            base = int(self.n_total - len(self) + i)
            for s in names:
                j = self.series.index(s)
                rows.append({"sample": base,
                             "time_ns": int(self.time_ns[i]),
                             "series": s,
                             "tiles": [int(v)
                                       for v in self.data[i, :, j]]})
        return rows

    def save(self, path: str) -> None:
        np.savez(path, data=self.data, times_ps=self.times_ps,
                 series=np.array(self.series),
                 n_total=self.n_total,
                 sample_interval_ps=self.sample_interval_ps,
                 wrapped=self.wrapped)

    @classmethod
    def load(cls, path: str) -> "TileProfile":
        z = np.load(path, allow_pickle=False)
        return cls(series=tuple(str(s) for s in z["series"]),
                   data=np.asarray(z["data"]),
                   times_ps=np.asarray(z["times_ps"]),
                   n_total=int(z["n_total"]),
                   sample_interval_ps=int(z["sample_interval_ps"]),
                   wrapped=bool(z["wrapped"]))


def profile_from_state(spec: ProfileSpec, pstate) -> TileProfile:
    """Fetch + demux one sim's ProfileState (device or host pytree)."""
    buf, times, count = jax.device_get(
        (pstate.buf, pstate.times, pstate.count))
    return TileProfile.from_host_state(spec, np.asarray(buf),
                                       np.asarray(times), int(count))


def demux_profiles(spec: ProfileSpec, pstate) -> "list[TileProfile]":
    """Demux a batched [B, ...] ProfileState (vmapped campaign or the
    batch-axis shard_map gather) into B per-sim TileProfiles.

    `pstate` may also be the already-fetched (buf, times, count) host
    triple — SweepRunner passes the arrays from its ONE batched
    device→host fetch, so this is the single demux implementation
    every campaign path shares."""
    parts = (tuple(pstate) if isinstance(pstate, (tuple, list))
             else (pstate.buf, pstate.times, pstate.count))
    buf, times, count = (np.asarray(x)
                         for x in jax.device_get(parts))
    return [TileProfile.from_host_state(spec, buf[b], times[b],
                                        int(count[b]))
            for b in range(buf.shape[0])]
