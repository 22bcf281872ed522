"""Device-resident latency histograms: the distribution instrument.

Graphite's value as a simulator is the timing DISTRIBUTIONS it reports —
per-access miss latency, network delay, sync stall breakdowns
(`tile.cc:105-123` outputSummary) — and the TR-09 four-scheme clock study
compares distributions of skew, not just means.  The repo's first two
rings record cumulative counters (round 9, `obs/telemetry.py`) and
time-sampled per-tile series (round 16, `obs/profile.py`); every
per-event latency the engines already compute in-carry (`acc_ps`,
`slot_lat_ps` in `memory/engine.py`, the recv/barrier/mutex wait times in
`engine/step.py`) was folded into a sum and thrown away — no p50/p99, no
tail, no per-scheme distribution diff was observable.

This module records the distribution dimension: a third device-resident
ring of int64 bucket counts rides the simulation carry
(`engine/state.SimState.hist`), accumulated by masked scatter-add at
EVENT COMPLETION (the commit site in `engine/step.py`, not on sampling
boundaries) with zero host sync — the program still passes the
host-sync audit lint.  Sources are values the carry already holds:

 - per-slot memory latency at record commit (`slot_lat_ps[T, 3]` —
   icache slot -> `l1i_lat_ps`, mem slots -> `l1d_lat_ps`);
 - per-miss service time (`miss_lat_ps`): the requester's phase-6
   reply fill (`memory/engine.MemStepOut.fill_now` / `fill_lat_ps` —
   a per-call event, because a whole miss can start AND fill within
   one engine call);
 - USER-net packet latency at receive (`net_lat_ps`);
 - blocking-recv and sync stall durations (`recv_stall_ps`,
   `sync_stall_ps`) exactly where the scalar counters charge them;
 - per-boundary `clock_skew_ps` (every tile, every quantum — the
   four-scheme study's accuracy instrument) and opt-in per-boundary
   `energy_pj` deltas priced through the shared `EnergyPrices` ladder.

Every histogram total is CONSERVED against the matching cumulative
counter (`conservation_totals`): the recording masks are bit-identical
to the counter increments in `engine/step.py`, so on a completed run
with constant `models_enabled` the total count equals the counter —
the distribution analogue of round-16's cross-ring sum invariant,
asserted by tests/test_hist.py.

`hist=None` (the default everywhere) constant-folds the recording away
to a bit-identical program — the same contract as `telemetry=None`
(round 9) and `profile=None` (round 16), jaxpr-asserted in
tests/test_hist.py and enforced by the `hist-off` audit lint.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from graphite_tpu.obs.metrics import bucket_quantile
from graphite_tpu.obs.telemetry import EnergyPrices, tile_energy_pj

I64 = jnp.int64

# Commit-site sources every program offers: recorded at the engine/step
# commit site under EXACTLY the masks the cumulative counters use
# (net_lat_ps <-> packets_received, recv_stall_ps <-> recv_instructions,
# sync_stall_ps <-> sync_instructions).
HIST_CORE_SOURCES = (
    "net_lat_ps",      # USER-net packet latency, at receive
    "recv_stall_ps",   # blocking-recv wait, charged receives only
    "sync_stall_ps",   # barrier/mutex/bsync/cjoin wait, charged syncs
)

# Memory-engine sources (require EngineParams.mem).  The slot latencies
# sample at record commit (one sample per present slot); the miss
# service time samples at the requester's reply-fill transition.
HIST_MEM_SOURCES = (
    "l1i_lat_ps",      # icache slot latency per committed record
    "l1d_lat_ps",      # L1-D slot latency per access (mem0 + mem1)
    "miss_lat_ps",     # full miss service time (phase-6 reply fill)
)

# Boundary sources: sampled for EVERY tile at EVERY executed quantum
# (unlike the interval-gated rings — skew is the four-scheme study's
# instrument, so each quantum is one observation of the whole fleet).
HIST_BOUNDARY_SOURCES = (
    "clock_skew_ps",   # tile clock minus the fleet-minimum clock
)

# Opt-in per-boundary per-tile energy delta (needs
# HistSpec.energy_prices — never part of the dense default, so locked
# programs are untouched).
HIST_ENERGY_SOURCES = ("energy_pj",)


def available_hist_sources(params) -> "tuple[str, ...]":
    """Every histogram source the given EngineParams can record
    (energy_pj joins only through HistSpec.energy_prices)."""
    out = HIST_CORE_SOURCES
    if params.mem is not None:
        out = out + HIST_MEM_SOURCES
    return out + HIST_BOUNDARY_SOURCES


@dataclasses.dataclass(frozen=True)
class HistSpec:
    """What to bucket: source selection, bucket edges, per-tile switch.

    `sources=None` selects every source the engine parameters support
    (the dense spec).  Buckets come from `edges` — an explicit strictly
    ascending tuple of non-negative ints — or, when None, the log2
    ladder `1, 2, 4, ..., 2**(log2_buckets - 2)` (so `log2_buckets`
    buckets total including the underflow-at-0 and overflow buckets).
    A value lands in the first bucket whose upper edge exceeds it;
    values at or past the last edge land in the overflow bucket.

    `per_tile=True` keeps one [H, B] plane per tile (int64[T, H, B],
    tile axis sharded with the directory under the 2D campaign mesh);
    the default aggregates the fleet into one int64[H, B] ring.

    `resolve(params)` validates the selection and fills `n_tiles` —
    `ring_bytes()` and `buffer_sig()` need the resolved spec.
    """

    sources: "tuple[str, ...] | None" = None
    edges: "tuple[int, ...] | None" = None
    log2_buckets: int = 32
    per_tile: bool = False
    # per-event pJ prices enabling the per-boundary energy_pj source
    energy_prices: "EnergyPrices | None" = None
    # filled by resolve(): the program's tile count
    n_tiles: int = 0

    def __post_init__(self):
        if self.sources is not None:
            object.__setattr__(self, "sources", tuple(self.sources))
        if self.edges is not None:
            e = tuple(int(v) for v in self.edges)
            if len(e) == 0:
                raise ValueError("edges must be non-empty when given")
            if any(v < 0 for v in e):
                raise ValueError("edges must be non-negative")
            if any(b <= a for a, b in zip(e, e[1:])):
                raise ValueError("edges must be strictly ascending")
            object.__setattr__(self, "edges", e)
        elif int(self.log2_buckets) < 2:
            raise ValueError("log2_buckets must be >= 2")

    @property
    def resolved(self) -> bool:
        return self.sources is not None and self.n_tiles > 0

    def resolve(self, params) -> "HistSpec":
        avail = available_hist_sources(params)
        if self.energy_prices is not None:
            if params.mem is None and self.energy_prices.needs_mem():
                raise ValueError(
                    "energy_prices set nonzero memory-event prices but "
                    "this program has no memory subsystem (only "
                    "instruction_pj/packet_pj apply to memoryless "
                    "traces)")
            avail = avail + HIST_ENERGY_SOURCES
        elif self.sources is not None \
                and any(s in HIST_ENERGY_SOURCES for s in self.sources):
            raise ValueError(
                "the energy_pj histogram needs HistSpec.energy_prices "
                "(an obs.EnergyPrices)")
        if self.sources is None:
            sel = avail
        else:
            unknown = [s for s in self.sources if s not in avail]
            if unknown:
                raise ValueError(
                    f"unknown/unavailable hist sources {unknown} "
                    f"(this program offers: {', '.join(avail)})")
            seen = []
            for s in self.sources:
                if s not in seen:
                    seen.append(s)
            sel = tuple(seen)
        return dataclasses.replace(self, sources=sel,
                                   n_tiles=int(params.n_tiles))

    @property
    def n_sources(self) -> int:
        if self.sources is None:
            raise ValueError("spec is unresolved (call resolve(params))")
        return len(self.sources)

    def bucket_edges(self) -> np.ndarray:
        """int64[E]: the bucket upper edges (explicit, or the log2
        ladder).  B = E + 1 buckets: index searchsorted(edges, v,
        'right') — below edges[0] is bucket 0, at/past edges[-1] the
        overflow bucket E."""
        if self.edges is not None:
            return np.asarray(self.edges, np.int64)
        return np.asarray([2 ** k for k in
                           range(int(self.log2_buckets) - 1)], np.int64)

    @property
    def n_buckets(self) -> int:
        return int(self.bucket_edges().shape[0]) + 1

    def buffer_sig(self) -> "tuple[tuple, str]":
        """The hist ring's aval signature ((T, H, B) per-tile or (H, B)
        aggregate, int64) — what the audit lints match (cond-payload
        forbidden set when the hist is ON; the hist-off rule when it
        must be absent)."""
        if not self.resolved:
            raise ValueError("buffer_sig needs a resolved HistSpec")
        shape = (self.n_sources, self.n_buckets)
        if self.per_tile:
            shape = (int(self.n_tiles),) + shape
        return (shape, "int64")

    def ring_bytes(self, tile_shards: int = 1) -> int:
        """Per-sim device residency of this spec's HistState: the
        bucket-count buffer + the boundaries scalar + (opt-in) the [T]
        prev-energy snapshot, all int64.  The ONE size model the
        residency budget and the admission bill consume
        (analysis/cost.residency_breakdown).

        `tile_shards` (round 18): per-DEVICE bytes under a tile-sharded
        2D campaign layout — a per-tile ring shards its tile axis with
        the directory; the aggregate ring, the boundaries cursor, and
        the prev-energy snapshot stay replicated."""
        shape, dtype = self.buffer_sig()
        item = np.dtype(dtype).itemsize
        ts = max(int(tile_shards), 1)
        if self.per_tile:
            T, H, B = shape
            if T % ts:
                raise ValueError(
                    f"tile count {T} not divisible by tile_shards={ts}")
            elems = (T // ts) * H * B
        else:
            elems = int(np.prod(shape))
        extra = (int(self.n_tiles)
                 if self.sources is not None
                 and any(s in HIST_ENERGY_SOURCES for s in self.sources)
                 else 0)
        return (elems + 1 + extra) * item


@struct.dataclass
class HistState:
    """The device-resident bucket-count state (rides SimState.hist).

    `buf` is the int64[H, B] (aggregate) or int64[T, H, B] (per-tile)
    bucket-count ring; `boundaries` counts executed quanta (one
    fleet-wide skew/energy observation each — the conservation
    denominator for the boundary sources); `prev_energy` is the [T]
    cumulative-pJ snapshot at the last boundary (present only when the
    energy_pj source is selected — the off spec carries no leaf)."""

    buf: jax.Array           # int64[H, B] | int64[T, H, B]
    boundaries: jax.Array    # int64[]
    prev_energy: "jax.Array | None" = None   # int64[T] | None


def init_hist(spec: HistSpec) -> HistState:
    if not spec.resolved:
        raise ValueError("init_hist needs a resolved HistSpec")
    shape, _ = spec.buffer_sig()
    prev = None
    if any(s in HIST_ENERGY_SOURCES for s in spec.sources):
        prev = jnp.zeros((int(spec.n_tiles),), I64)
    return HistState(buf=jnp.zeros(shape, I64),
                     boundaries=jnp.zeros((), I64),
                     prev_energy=prev)


def _bucketize(spec: HistSpec, values: jax.Array) -> jax.Array:
    """int32[T] bucket index per lane: first bucket whose upper edge
    exceeds the value (overflow bucket at/past the last edge)."""
    edges = jnp.asarray(spec.bucket_edges())
    return jnp.searchsorted(edges, values.astype(I64),
                            side="right").astype(jnp.int32)


def _scatter(spec: HistSpec, buf: jax.Array, h: int, mask: jax.Array,
             values: jax.Array, px=None) -> jax.Array:
    """Masked scatter-add of one event batch into source row `h`.

    Masked-off lanes still index a bucket but add 0 — the add-a-delta
    discipline, so the scatter is the buffer's only use and XLA updates
    the loop-carried ring in place.  Under a tile-sharded px the
    per-tile ring holds only this device's [Tl, H, B] block: the
    replicated [T] masks/values are lo()'d to the local lanes."""
    bucket = _bucketize(spec, values)
    if spec.per_tile:
        if px is not None and px.sharded:
            mask, bucket = px.lo((mask, bucket))
        rows = jnp.arange(bucket.shape[0], dtype=jnp.int32)
        return buf.at[rows, h, bucket].add(mask.astype(I64))
    return buf.at[h, bucket].add(mask.astype(I64))


def hist_commit_update(spec: HistSpec, hs: HistState, *,
                       advance, enabled,
                       recv_now, recv_lat_ps, recv_charged, recv_wait_ps,
                       sync_charged, sync_wait_ps,
                       present=None, slot_lat_ps=None,
                       miss_now=None, miss_lat_ps=None,
                       px=None) -> HistState:
    """One subquantum iteration's commit-site histogram update.

    Called from the `engine/step.py` commit site (after the charged
    masks are final) under a Python-level `hist is not None` gate, so
    the off program lowers byte-identically.  The masks are the SAME
    expressions the cumulative counters add (`conservation_totals`
    documents each pairing); the memory arguments are None exactly when
    the program has no memory subsystem (resolve() already refused
    memory sources then)."""
    if hs is None:
        raise ValueError(
            "hist spec given but SimState.hist is None "
            "(init the state with obs.init_hist)")
    buf = hs.buf
    sel = spec.sources
    if "net_lat_ps" in sel:
        # every receive, enabled or not — packets_received counts them all
        buf = _scatter(spec, buf, sel.index("net_lat_ps"),
                       recv_now, recv_lat_ps.astype(I64), px=px)
    if "recv_stall_ps" in sel:
        buf = _scatter(spec, buf, sel.index("recv_stall_ps"),
                       recv_charged, recv_wait_ps, px=px)
    if "sync_stall_ps" in sel:
        buf = _scatter(spec, buf, sel.index("sync_stall_ps"),
                       sync_charged, sync_wait_ps, px=px)
    if "l1i_lat_ps" in sel:
        # icache slot presence is already enabled-gated (slots_present)
        buf = _scatter(spec, buf, sel.index("l1i_lat_ps"),
                       advance & present[:, 0] & enabled,
                       slot_lat_ps[:, 0], px=px)
    if "l1d_lat_ps" in sel:
        h = sel.index("l1d_lat_ps")
        for s in (1, 2):
            buf = _scatter(spec, buf, h,
                           advance & present[:, s] & enabled,
                           slot_lat_ps[:, s], px=px)
    if "miss_lat_ps" in sel:
        buf = _scatter(spec, buf, sel.index("miss_lat_ps"),
                       miss_now & enabled, miss_lat_ps, px=px)
    return hs.replace(buf=buf)


def hist_boundary_tick(spec: HistSpec, state, px=None, dvfs=None
                       ) -> HistState:
    """One outer-loop quantum's boundary-source update (device-side,
    traced).  Unlike the interval-gated telemetry/profile ticks this
    samples EVERY executed quantum: each quantum is one observation of
    the whole fleet's skew (and energy delta), and `boundaries` is the
    conservation denominator (`total == boundaries * T`)."""
    hs = state.hist
    if hs is None:
        raise ValueError(
            "hist spec given but SimState.hist is None "
            "(init the state with obs.init_hist)")
    buf = hs.buf
    sel = spec.sources
    T = int(spec.n_tiles)
    ones = jnp.ones((T,), jnp.bool_)
    if "clock_skew_ps" in sel:
        clocks = state.core.clock_ps
        skew = clocks - jnp.min(clocks)
        buf = _scatter(spec, buf, sel.index("clock_skew_ps"),
                       ones, skew, px=px)
    prev = hs.prev_energy
    if "energy_pj" in sel:
        # delta on the full replicated [T] vector; the scatter lo()s it
        cur = tile_energy_pj(spec.energy_prices, state, dvfs)
        buf = _scatter(spec, buf, sel.index("energy_pj"),
                       ones, cur - hs.prev_energy, px=px)
        prev = cur
    return hs.replace(buf=buf, boundaries=hs.boundaries + 1,
                      prev_energy=prev)


# ---------------------------------------------------------------------------
# host-side histogram (post-run fetch)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Hist:
    """One sim's recorded histograms on the host.

    `counts[h, b]` (aggregate) or `counts[t, h, b]` (per-tile) is the
    event count of source `sources[h]` in bucket b; `edges[b]` is
    bucket b's upper edge (the overflow bucket has none).  Quantiles
    use the ONE shared definition (`obs.metrics.bucket_quantile`):
    first bucket edge whose cumulative count reaches ceil(q * n),
    saturating at the last edge for the overflow bucket."""

    sources: "tuple[str, ...]"
    edges: np.ndarray         # int64[B - 1]
    counts: np.ndarray        # int64[H, B] | int64[T, H, B]
    boundaries: int

    @property
    def per_tile(self) -> bool:
        return self.counts.ndim == 3

    @property
    def n_tiles(self) -> int:
        return self.counts.shape[0] if self.per_tile else 1

    def counts_for(self, source: str, tile: "int | None" = None
                   ) -> np.ndarray:
        """int64[B] — one source's buckets (fleet-summed, or one
        tile's plane when `tile` is given on a per-tile recording)."""
        h = self.sources.index(source)
        if not self.per_tile:
            if tile is not None:
                raise ValueError("tile= needs a per_tile recording")
            return self.counts[h]
        if tile is not None:
            return self.counts[int(tile), h]
        return self.counts[:, h].sum(axis=0)

    def total(self, source: str) -> int:
        return int(self.counts_for(source).sum())

    def totals(self) -> "dict[str, int]":
        return {s: self.total(s) for s in self.sources}

    def quantile(self, source: str, q: float,
                 tile: "int | None" = None) -> int:
        counts = self.counts_for(source, tile)
        bounds = [int(e) for e in self.edges]
        return int(bucket_quantile([int(c) for c in counts], bounds, q,
                                   overflow=bounds[-1]))

    def summary(self) -> dict:
        """Per-source count + p50/p95/p99 scalars for bench/CI JSON."""
        out = {"boundaries": int(self.boundaries),
               "per_tile": bool(self.per_tile)}
        for s in self.sources:
            out[f"{s}_count"] = self.total(s)
            for q in (0.5, 0.95, 0.99):
                out[f"{s}_p{int(q * 100)}"] = self.quantile(s, q)
        return out

    def save(self, path: str) -> None:
        np.savez(path, counts=self.counts, edges=self.edges,
                 sources=np.array(self.sources),
                 boundaries=self.boundaries)

    @classmethod
    def load(cls, path: str) -> "Hist":
        z = np.load(path, allow_pickle=False)
        return cls(sources=tuple(str(s) for s in z["sources"]),
                   edges=np.asarray(z["edges"]),
                   counts=np.asarray(z["counts"]),
                   boundaries=int(z["boundaries"]))


def hist_from_state(spec: HistSpec, hstate) -> Hist:
    """Fetch one sim's HistState (device or host pytree) into a Hist."""
    buf, boundaries = jax.device_get((hstate.buf, hstate.boundaries))
    return Hist(sources=tuple(spec.sources),
                edges=spec.bucket_edges(),
                counts=np.asarray(buf), boundaries=int(boundaries))


def demux_hists(spec: HistSpec, hstate) -> "list[Hist]":
    """Demux a batched [B, ...] HistState (vmapped campaign or the
    batch-axis shard_map gather) into B per-sim Hists.

    `hstate` may also be the already-fetched (buf, boundaries) host
    pair — SweepRunner passes the arrays from its ONE batched
    device->host fetch, so this is the single demux implementation
    every campaign path shares."""
    parts = (tuple(hstate) if isinstance(hstate, (tuple, list))
             else (hstate.buf, hstate.boundaries))
    buf, boundaries = (np.asarray(x) for x in jax.device_get(parts))
    return [Hist(sources=tuple(spec.sources), edges=spec.bucket_edges(),
                 counts=buf[b], boundaries=int(boundaries[b]))
            for b in range(buf.shape[0])]


def conservation_totals(hist: Hist, results, *,
                        protocol: "str | None" = None
                        ) -> "dict[str, tuple[int, int]]":
    """source -> (histogram total, the cumulative total it must
    bit-equal) — the conservation cross-check.

    Exact on COMPLETED runs with constant `models_enabled`, because the
    recording masks are the counter-increment masks:

      net_lat_ps     <-> packets_received      (every receive)
      recv_stall_ps  <-> recv_instructions     (charged receives)
      sync_stall_ps  <-> sync_instructions     (charged syncs)
      l1i_lat_ps     <-> l1i_hits + l1i_misses (one lookup per record)
      l1d_lat_ps     <-> all four l1d counters (one lookup per slot)
      miss_lat_ps    <-> l2_misses (private-L2 MSI) or the three L1
                         miss counters (pr_l1_sh_l2 — every L1 miss
                         goes remote)
      clock_skew_ps  <-> boundaries * T        (fleet sample/quantum)
      energy_pj      <-> boundaries * T
    """
    out = {}
    mc = results.mem_counters
    for s in hist.sources:
        if s == "net_lat_ps":
            want = int(np.sum(results.packets_received))
        elif s == "recv_stall_ps":
            want = int(np.sum(results.recv_instructions))
        elif s == "sync_stall_ps":
            want = int(np.sum(results.sync_instructions))
        elif s == "l1i_lat_ps":
            want = int(np.sum(mc["l1i_hits"]) + np.sum(mc["l1i_misses"]))
        elif s == "l1d_lat_ps":
            want = int(np.sum(mc["l1d_read_hits"])
                       + np.sum(mc["l1d_read_misses"])
                       + np.sum(mc["l1d_write_hits"])
                       + np.sum(mc["l1d_write_misses"]))
        elif s == "miss_lat_ps":
            if protocol is not None and protocol.startswith("pr_l1_sh_l2"):
                want = int(np.sum(mc["l1i_misses"])
                           + np.sum(mc["l1d_read_misses"])
                           + np.sum(mc["l1d_write_misses"]))
            else:
                want = int(np.sum(mc["l2_misses"]))
        elif s in ("clock_skew_ps", "energy_pj"):
            want = int(hist.boundaries) * int(results.n_tiles)
        else:
            continue
        out[s] = (hist.total(s), want)
    return out
