"""Observability: device telemetry timelines + host spans and metrics.

Device side (round 9 + 14):

  TelemetrySpec      — what to record (interval, ring depth S, series)
  EnergyPrices       — per-event pJ prices enabling the energy_pj series
  TelemetryState     — the [S, n_series] ring riding SimState.telemetry
  telemetry_tick     — the outer quantum loop's per-quantum update
  Timeline           — one sim's demuxed chronological host rows
  demux_timelines    — [B, ...] campaign state -> B Timelines

    spec = TelemetrySpec(sample_interval_ps=10_000_000)   # 10 us
    sim = Simulator(cfg, batch, telemetry=spec)
    res = sim.run()
    res.telemetry.summary()   # peak injection, clock spread, ...

`telemetry=None` (the default) lowers to a bit-identical program —
jaxpr-asserted in tests/test_telemetry.py and enforced by the
`telemetry-off` audit lint (`python -m graphite_tpu.tools.audit`).
`energy_prices` is opt-in, so the dense default selection (and every
locked program fingerprint) is unchanged by the energy series.

Spatial profiler (round 16):

  ProfileSpec        — what to record PER TILE (interval, S, series)
  ProfileState       — the [S, T, m] ring riding SimState.profile
  profile_tick       — the outer quantum loop's per-tile row append
  TileProfile        — one sim's demuxed per-tile host rows (heatmap
                       input; `tools/report.py --heatmap`)
  demux_profiles     — [B, ...] campaign state -> B TileProfiles

    prof = ProfileSpec(sample_interval_ps=10_000_000)
    sim = Simulator(cfg, batch, profile=prof)
    res = sim.run()
    res.profile.summary()   # max/mean skew, straggler tile, Gini

`profile=None` (the default) lowers the same bit-identical program —
enforced by the `profile-off` audit lint.

Latency histograms (round 21):

  HistSpec           — what to bucket (sources, edges, per_tile)
  HistState          — the int64 [H, B] / [T, H, B] bucket-count ring
                       riding SimState.hist
  hist_commit_update — the commit site's masked scatter-add
  hist_boundary_tick — the outer loop's per-quantum skew/energy sample
  Hist               — one sim's fetched counts (+ deterministic
                       p50/p95/p99 via the shared bucket_quantile)
  demux_hists        — [B, ...] campaign state -> B Hists
  conservation_totals — histogram total vs matching cumulative counter

    hist = HistSpec()                 # dense: every available source
    sim = Simulator(cfg, batch, hist=hist)
    res = sim.run()
    res.hist.quantile("miss_lat_ps", 0.99)

`hist=None` (the default) lowers the same bit-identical program —
enforced by the `hist-off` audit lint.

Host side (round 14, consumed by serve/service.py):

  MetricsRegistry    — counters / gauges / fixed-bucket histograms with
                       deterministic p50/p90/p99, Prometheus text +
                       JSON snapshot exporters, a sampled timeline
  Tracer / Span      — job-lifecycle span tracing (submit → ... → emit)
                       with JSON-lines export and terminal-completeness
                       checking

Both take an injectable monotonic clock, so tests pin exact latencies
on a fake clock; neither ever touches a traced program (tracing on/off
serve results are bit-equal: `tests/test_obs_service.py`).
"""

from graphite_tpu.obs.hist import (  # noqa: F401
    HIST_BOUNDARY_SOURCES, HIST_CORE_SOURCES, HIST_ENERGY_SOURCES,
    HIST_MEM_SOURCES, Hist, HistSpec, HistState, available_hist_sources,
    conservation_totals, demux_hists, hist_boundary_tick,
    hist_commit_update, hist_from_state, init_hist,
)
from graphite_tpu.obs.metrics import (  # noqa: F401
    Counter, DEFAULT_COUNT_BUCKETS, DEFAULT_LATENCY_BUCKETS, Gauge,
    Histogram, MetricsError, MetricsRegistry, RATIO_BUCKETS,
    bucket_quantile, parse_exposition,
)
from graphite_tpu.obs.telemetry import (  # noqa: F401
    CORE_SERIES, ENERGY_SERIES, EnergyPrices, LEVEL_SERIES, MEM_SERIES,
    SKIP_PREFIX, Timeline, TelemetrySpec, TelemetryState,
    available_series, demux_timelines, init_telemetry, telemetry_tick,
    timeline_from_state,
)
from graphite_tpu.obs.profile import (  # noqa: F401
    PROFILE_CORE_SERIES, PROFILE_ENERGY_SERIES, PROFILE_LEVEL_SERIES,
    PROFILE_MEM_SERIES, ProfileSpec, ProfileState, TileProfile,
    available_tile_series, demux_profiles, gini, grid_shape,
    init_profile, profile_from_state, profile_tick,
)
from graphite_tpu.obs.trace import (  # noqa: F401
    JOB_SPANS, RUN_SPANS, Span, TERMINAL_SPANS, Tracer, job_breakdown,
    load_jsonl,
)

__all__ = [
    "CORE_SERIES",
    "Counter",
    "DEFAULT_COUNT_BUCKETS",
    "DEFAULT_LATENCY_BUCKETS",
    "ENERGY_SERIES",
    "EnergyPrices",
    "Gauge",
    "HIST_BOUNDARY_SOURCES",
    "HIST_CORE_SOURCES",
    "HIST_ENERGY_SOURCES",
    "HIST_MEM_SOURCES",
    "Hist",
    "HistSpec",
    "HistState",
    "Histogram",
    "JOB_SPANS",
    "RUN_SPANS",
    "LEVEL_SERIES",
    "MEM_SERIES",
    "MetricsError",
    "MetricsRegistry",
    "PROFILE_CORE_SERIES",
    "PROFILE_ENERGY_SERIES",
    "PROFILE_LEVEL_SERIES",
    "PROFILE_MEM_SERIES",
    "ProfileSpec",
    "ProfileState",
    "RATIO_BUCKETS",
    "SKIP_PREFIX",
    "Span",
    "TERMINAL_SPANS",
    "Timeline",
    "TelemetrySpec",
    "TelemetryState",
    "TileProfile",
    "Tracer",
    "available_hist_sources",
    "available_series",
    "available_tile_series",
    "bucket_quantile",
    "conservation_totals",
    "demux_hists",
    "demux_profiles",
    "demux_timelines",
    "gini",
    "grid_shape",
    "hist_boundary_tick",
    "hist_commit_update",
    "hist_from_state",
    "init_hist",
    "init_profile",
    "init_telemetry",
    "job_breakdown",
    "load_jsonl",
    "parse_exposition",
    "profile_from_state",
    "profile_tick",
    "telemetry_tick",
    "timeline_from_state",
]
