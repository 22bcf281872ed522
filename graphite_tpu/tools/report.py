"""Observability reporter: timelines, profiles, span traces, metrics.

Input kinds, one renderer:

  positional .npz   device telemetry timelines (`obs.Timeline.save`) —
                    a solo run, or several files as one campaign;
  --heatmap         positional .npz files are per-tile PROFILES
                    (`obs.TileProfile.save`): renders each selected
                    series as a tile-grid heatmap (aligned ASCII shade
                    digits for the terminal; JSON rows carrying the
                    full [T] vector) plus the straggler/imbalance
                    summary (max/mean skew, leader/straggler tile,
                    traffic Gini).  `--slice total|last|<idx>` picks
                    the time slice; `--series a,b` restricts series;
  --spans FILE      job/batch lifecycle spans saved as JSON-lines by
                    `tools/serve.py --trace-out` — renders one aligned
                    latency-breakdown row per job (submit, queue dwell,
                    execute, ... in microseconds) plus the batch
                    execution table (class, occupancy, cache hit,
                    compile time);
  --trade-curve FILE
                    the same span JSON-lines rendered as the latency/
                    occupancy trade curve: one scatter row per job
                    (queue_dwell_us vs its batch's occupancy) plus
                    occupancy-bucketed dwell aggregates — the
                    measurement half of latency-aware batching.  When
                    the file holds per-job RESULT rows that carry an
                    energy + `completion_time_ns` (a V/f campaign),
                    the same flag renders the energy-vs-wall trade
                    instead: one scatter row per operating point
                    (wall, energy, EDP) plus the Pareto frontier.  The
                    energy is a power target's integrated total
                    (`energy_pj_total`) where the row has it, else
                    the telemetry series' sum (`energy_pj`);
  --metrics FILE    a Prometheus text exposition written by
                    `tools/serve.py --metrics-out` — renders counters/
                    gauges and histogram summaries (count, sum,
                    p50/p90/p99 from the cumulative buckets);
  --perfetto OUT.json
                    unified Chrome-trace export (round 21): merges the
                    span JSONL (`--spans`, host-time track), telemetry
                    timelines (positional .npz), per-tile profiles
                    (`--profile-npz`) and latency histograms
                    (`--hist`, `obs.Hist.save` / `tools/serve.py
                    --hist-out`) into ONE trace with separate
                    host-time and sim-time clock tracks — open in the
                    Perfetto UI or chrome://tracing.

Output (stdout):

  --format json   machine rows (one JSON line per sample / job / metric)
                  — the shape the CI artifacts consume;
  --format text   aligned-text tables;
  --summary       summaries only (timeline/heatmap modes).  Timeline
                  summaries carry per-series `peaks` (max + argmax
                  sample/time), so stragglers and spikes are nameable
                  from scalar timelines too.

Usage:
  python -m graphite_tpu.tools.report run.npz [sim0.npz sim1.npz ...]
                                      [--format json|text] [--summary]
  python -m graphite_tpu.tools.report --heatmap prof.npz --slice total
  python -m graphite_tpu.tools.report --spans spans.jsonl --format text
  python -m graphite_tpu.tools.report --trade-curve spans.jsonl
  python -m graphite_tpu.tools.report --metrics metrics.prom
  python -m graphite_tpu.tools.report --perfetto trace.json \
      --spans spans.jsonl --hist hists/*.npz run.npz
"""

from __future__ import annotations

import argparse
import json
import math
import sys


def _align(cols: "list[str]", rows: "list[list[str]]") -> "list[str]":
    widths = [max(len(c), *(len(r[i]) for r in rows)) if rows else len(c)
              for i, c in enumerate(cols)]
    lines = ["  ".join(c.rjust(w) for c, w in zip(cols, widths))]
    for r in rows:
        lines.append("  ".join(c.rjust(w) for c, w in zip(r, widths)))
    return lines


def _text_table(tl) -> "list[str]":
    """Aligned rows: sample index + time_ns + every non-time series."""
    cols = ["sample", "time_ns"] + [s for s in tl.series
                                    if s != "time_ps"]
    rows = [[str(r["sample"]), str(r["time_ns"])]
            + [str(r[s]) for s in cols[2:]] for r in tl.json_rows()]
    return _align(cols, rows)


def render_spans(path: str, fmt: str) -> "list[str]":
    """Span JSON-lines -> per-job latency breakdown + batch table + the
    set-up table (top-level set-up spans, the program ledger's totals)."""
    from graphite_tpu.obs.trace import (
        BATCH_SPANS, BATCH_TRACE_PREFIX, JOB_SPANS, RUN_SPANS,
        job_breakdown, load_jsonl, setup_breakdown,
    )

    rows = load_jsonl(path)
    jobs = sorted(job_breakdown(rows), key=lambda r: r["job"])
    setup, programs = setup_breakdown(rows)
    if fmt == "json":
        out = [json.dumps(r) for r in jobs]
        for r in rows:
            if r["trace"].startswith(BATCH_TRACE_PREFIX) \
                    and r["span"] == "batch":
                out.append(json.dumps(r))
        out += [json.dumps({"setup": r}) for r in setup]
        if programs:
            out.append(json.dumps({"programs": programs}))
        return out
    # aligned per-job table: lifecycle spans in canonical order, then
    # any extra recorded spans (split/retry/...), then status/total
    span_cols = [s + "_us" for s in JOB_SPANS + RUN_SPANS]
    extra = sorted({k for r in jobs for k in r
                    if k.endswith("_us") and k != "total_us"
                    and k not in span_cols})
    span_cols = [c for c in span_cols if any(c in r for r in jobs)]
    span_cols += [c for c in extra if c not in span_cols]
    cols = ["job"] + span_cols + ["total_us", "status"]
    body = [[str(r.get(c, "-")) for c in cols] for r in jobs]
    lines = _align(cols, body)
    batches = [r for r in rows
               if r["trace"].startswith(BATCH_TRACE_PREFIX)
               and r["span"] == "batch"]
    if batches:
        # what each batch paid around its run (BATCH_SPANS) and inside
        # it (the runner's RUN_SPANS, recorded under the batch's trace)
        inside = {}
        for r in rows:
            if r["trace"].startswith(BATCH_TRACE_PREFIX) \
                    and r["span"] in BATCH_SPANS + RUN_SPANS:
                per = inside.setdefault(r["trace"], {})
                per[r["span"]] = per.get(r["span"], 0) + r["dur_us"]
        parts = [n for n in BATCH_SPANS + RUN_SPANS
                 if any(n in per for per in inside.values())]
        bcols = ["batch", "class", "n_jobs", "capacity", "occupancy",
                 "cache_hit", "compile_s"] \
            + [n + "_us" for n in parts] + ["dur_us", "ok"]
        brows = [[str(r["trace"]), str(r.get("class", "-")),
                  str(r.get("n_jobs", "-")), str(r.get("capacity", "-")),
                  str(r.get("occupancy", "-")),
                  str(r.get("cache_hit", "-")),
                  str(r.get("compile_s", "-"))]
                 + [str(inside.get(r["trace"], {}).get(n, "-"))
                    for n in parts]
                 + [str(r["dur_us"]), str(r.get("ok", "-"))]
                 for r in batches]
        lines.append("")
        lines.extend(_align(bcols, brows))
    if setup:
        # what came before the first run (obs/trace.py: SETUP_SPANS):
        # each top-level span with its self time, then the program
        # ledger's totals
        lines.append("")
        lines.extend(_align(
            ["setup", "trace", "count", "start_us", "dur_us", "self_us"],
            [[r["span"], r["trace"], str(r["count"]), str(r["start_us"]),
              str(r["dur_us"]), str(r["self_us"])] for r in setup]))
        lines.append("")
        lines.extend(_align(
            ["programs", "count", "total_us"],
            [[name, str(n), str(us)]
             for name, (n, us) in sorted(programs.items())]))
    return lines


_SHADES = "0123456789"


def heatmap_lines(prof, *, series=None,
                  sample: "int | str" = "total") -> "list[str]":
    """ASCII tile-grid heatmaps of one TileProfile: per selected
    series, the near-square emesh grid with each tile's value scaled
    to a shade digit 0-9 (0 = the slice minimum, 9 = the maximum; a
    flat slice renders all zeros), plus the min/max legend.  Aligned,
    deterministic — the golden-render shape the tests pin."""
    from graphite_tpu.obs.profile import grid_shape

    names = tuple(series) if series else prof.series
    rows_n, cols_n = grid_shape(prof.n_tiles)
    out = []
    for s in names:
        vec = prof.tile_slice(s, sample)
        lo, hi = int(vec.min()), int(vec.max())
        span = hi - lo
        out.append(f"-- {s} [slice {sample}] min {lo} max {hi} "
                   f"(0='{_SHADES[0]}' .. 9='{_SHADES[-1]}')")
        for r in range(rows_n):
            cells = []
            for c in range(cols_n):
                t = r * cols_n + c
                if t >= prof.n_tiles:
                    cells.append(" ")
                    continue
                v = int(vec[t])
                shade = 0 if span == 0 else (9 * (v - lo)) // span
                cells.append(_SHADES[shade])
            out.append(" ".join(cells).rstrip())
    return out


def render_heatmap(paths, fmt: str, *, series=None,
                   sample: "int | str" = "total",
                   summary_only: bool = False) -> "list[str]":
    """Per-tile profile .npz file(s) -> heatmaps + straggler summary."""
    from graphite_tpu.obs.profile import TileProfile

    lines = []
    for b, path in enumerate(paths):
        prof = TileProfile.load(path)
        names = tuple(series) if series else prof.series
        unknown = [s for s in names if s not in prof.series]
        if unknown:
            raise SystemExit(
                f"{path}: unknown series {unknown} "
                f"(recorded: {', '.join(prof.series)})")
        if len(prof) == 0:
            raise SystemExit(f"{path}: profile holds no recorded "
                             "samples — nothing to render")
        if isinstance(sample, int) \
                and not -len(prof) <= sample < len(prof):
            raise SystemExit(
                f"{path}: --slice {sample} out of range (profile "
                f"holds {len(prof)} recorded sample(s))")
        summary = {"sim": b, "file": path,
                   "sample_interval_ps": prof.sample_interval_ps,
                   **prof.summary()}
        if fmt == "json":
            if not summary_only:
                lines.extend(json.dumps({"sim": b, **row})
                             for row in prof.json_rows(
                                 series=names, sample=sample))
            lines.append(json.dumps(summary))
            continue
        lines.append(
            f"== sim {b}: {path} ({prof.n_tiles} tiles, "
            f"{len(prof)} of {prof.n_total} samples"
            + (", ring WRAPPED" if prof.wrapped else "") + ")")
        if not summary_only:
            lines.extend(heatmap_lines(prof, series=names,
                                       sample=sample))
        for k, v in summary.items():
            if k not in ("sim", "file"):
                lines.append(f"  {k:22} {v}")
    return lines


def trade_curve_rows(rows: "list[dict]") -> "tuple[list, list]":
    """Span rows -> (per-job scatter rows, occupancy-bucket aggregate
    rows) of the latency/occupancy trade: each job's queue dwell
    against the occupancy of the batch that ran it — the measurement
    the round-14 `queue_dwell_seconds` histogram and `batch_occupancy`
    series exist to feed (the scale-out item's dwell-knob evidence)."""
    from graphite_tpu.obs.trace import BATCH_TRACE_PREFIX

    occ_by_batch = {}
    for r in rows:
        if r["trace"].startswith(BATCH_TRACE_PREFIX) \
                and r["span"] == "batch" and "occupancy" in r:
            occ_by_batch[r["trace"]] = r
    scatter = []
    for r in rows:
        if r["span"] != "queue" or "batch" not in r:
            continue
        b = occ_by_batch.get(f"batch-{r['batch']}")
        if b is None:
            continue
        scatter.append({
            "job": r["trace"], "batch": int(r["batch"]),
            "queue_dwell_us": int(r["dur_us"]),
            "occupancy": float(b["occupancy"]),
            "n_jobs": b.get("n_jobs"),
            "capacity": b.get("capacity"),
            "execute_us": int(b["dur_us"]),
        })
    buckets: "dict[float, list]" = {}
    for s in scatter:
        # bucket occupancy to one decimal: the curve's x grid
        buckets.setdefault(round(s["occupancy"], 1), []).append(s)
    curve = []
    for occ in sorted(buckets):
        group = buckets[occ]
        dwells = sorted(g["queue_dwell_us"] for g in group)
        curve.append({
            "curve": True, "occupancy_bucket": occ,
            "jobs": len(group),
            "mean_dwell_us": int(sum(dwells) / len(dwells)),
            "max_dwell_us": int(dwells[-1]),
            "mean_execute_us": int(sum(g["execute_us"] for g in group)
                                   / len(group)),
        })
    return scatter, curve


def _row_energy_pj(r: dict):
    """A result row's energy: the integrated total of a power target
    (`energy_pj_total`: `SimResults.energy_pj`, closed interval by
    interval) where the job has it, else the telemetry series' sum
    (`energy_pj`); None where it has neither."""
    return r.get("energy_pj_total", r.get("energy_pj"))


def energy_trade_rows(rows: "list[dict]") -> "tuple[list, list]":
    """Per-job result rows (tools/serve.py or tools/sweep.py output
    lines, or any JSON lines carrying `energy_pj_total` or `energy_pj`,
    + `completion_time_ns`) -> (per-config scatter rows, Pareto frontier
    rows) of the energy-vs-wall trade — the V/f campaign's headline
    curve.  Each scatter row carries the operating point (the job's
    `dvfs_level_mhz`, the `dvfs_domain_mhz` knob, when present), the
    simulated wall, the energy (`_row_energy_pj`), and their product
    (EDP, pJ·ns).  A point is on the frontier when no other point is
    at least as good on BOTH axes and better on one."""
    scatter = []
    for r in rows:
        if _row_energy_pj(r) is None or "completion_time_ns" not in r:
            continue
        s = {"job": r.get("job", r.get("sim")),
             "wall_ns": int(r["completion_time_ns"]),
             "energy_pj": int(_row_energy_pj(r))}
        if "dvfs_level_mhz" in r:
            s["dvfs_level_mhz"] = int(r["dvfs_level_mhz"])
        if "dvfs_domain_mhz" in r:
            s["dvfs_domain_mhz"] = tuple(
                int(x) for x in r["dvfs_domain_mhz"]) \
                if isinstance(r["dvfs_domain_mhz"], (tuple, list)) \
                else int(r["dvfs_domain_mhz"])
        s["edp_pj_ns"] = s["wall_ns"] * s["energy_pj"]
        scatter.append(s)
    scatter.sort(key=lambda s: (s["wall_ns"], s["energy_pj"]))
    frontier = []
    for s in scatter:
        dominated = any(
            o is not s
            and o["wall_ns"] <= s["wall_ns"]
            and o["energy_pj"] <= s["energy_pj"]
            and (o["wall_ns"] < s["wall_ns"]
                 or o["energy_pj"] < s["energy_pj"])
            for o in scatter)
        if not dominated:
            frontier.append({**s, "pareto": True})
    return scatter, frontier


def render_trade_curve(path: str, fmt: str) -> "list[str]":
    from graphite_tpu.obs.trace import load_jsonl

    rows = load_jsonl(path)
    if any(_row_energy_pj(r) is not None and "completion_time_ns" in r
           for r in rows):
        # energy-vs-wall mode: per-job result rows from a DVFS campaign
        scatter, frontier = energy_trade_rows(rows)
        if fmt == "json":
            return [json.dumps(r) for r in scatter + frontier]
        cols = ["job", "dvfs_level_mhz", "dvfs_domain_mhz", "wall_ns",
                "energy_pj", "edp_pj_ns"]
        frontier_keys = {(f["wall_ns"], f["energy_pj"], f["job"])
                         for f in frontier}
        body = [[str(r.get(c, "-")) for c in cols]
                + ["*" if (r["wall_ns"], r["energy_pj"],
                           r["job"]) in frontier_keys else ""]
                for r in scatter]
        return _align(cols + ["pareto"], body)
    scatter, curve = trade_curve_rows(rows)
    if fmt == "json":
        return [json.dumps(r) for r in scatter + curve]
    cols = ["job", "batch", "queue_dwell_us", "occupancy", "n_jobs",
            "capacity", "execute_us"]
    lines = _align(cols, [[str(r.get(c, "-")) for c in cols]
                          for r in scatter])
    if curve:
        ccols = ["occupancy_bucket", "jobs", "mean_dwell_us",
                 "max_dwell_us", "mean_execute_us"]
        lines.append("")
        lines.extend(_align(ccols, [[str(r[c]) for c in ccols]
                                    for r in curve]))
    return lines


def _hist_quantile(buckets: "dict[str, int]", count: int,
                   q: float) -> str:
    """Quantile from cumulative `le -> count` buckets (the same
    first-bucket-reaching-rank rule obs.metrics.Histogram uses; the
    +Inf tail renders as '>LAST' since the text format cannot carry
    the true max)."""
    if count == 0:
        return "0"
    rank = math.ceil(q * count)
    finite = [(le, c) for le, c in buckets.items() if le != "+Inf"]
    for le, cum in finite:
        if cum >= rank:
            return le
    return f">{finite[-1][0]}" if finite else "inf"


HOST_PID = 1   # serve lifecycle spans (tracer clock, ts in us)
SIM_PID = 2    # device rings: telemetry/profile counters + histograms
               # (simulated time, ts in ns)


def perfetto_events(*, spans: "str | None" = None, timelines=(),
                    profiles=(), hists=()) -> "list[dict]":
    """One Chrome-trace event list from every observability artifact.

    Two clock tracks, kept as separate trace processes because their
    clocks never align: HOST_PID carries the serve span JSONL
    (`tools/serve.py --trace-out`, ts = tracer microseconds) as 'X'
    complete events, SIM_PID carries the device rings in SIMULATED
    time — telemetry and per-tile profile samples as 'C' counter
    tracks (ts = sim ns), and each latency histogram as one instant
    event whose args hold the deterministic count/p50/p95/p99 summary
    (`obs.Hist.summary` — the shared bucket_quantile definition).
    Events are sorted (pid, ts), so every track's stamps are monotone
    — the invariant `tests/test_hist.py::TestPerfetto` asserts."""
    events = [
        {"ph": "M", "pid": HOST_PID, "tid": 0, "ts": 0,
         "name": "process_name",
         "args": {"name": "host-time (serve spans, us)"}},
        {"ph": "M", "pid": SIM_PID, "tid": 0, "ts": 0,
         "name": "process_name",
         "args": {"name": "sim-time (device rings, ns)"}},
    ]
    if spans:
        from graphite_tpu.obs.trace import load_jsonl

        for r in load_jsonl(spans):
            ev = {"name": r["span"], "cat": "serve", "ph": "X",
                  "pid": HOST_PID, "tid": r["trace"],
                  "ts": int(r["start_us"]),
                  "dur": int(r["dur_us"])}
            extra = {k: v for k, v in r.items()
                     if k not in ("trace", "span", "start_us",
                                  "dur_us")}
            if extra:
                ev["args"] = extra
            events.append(ev)
    if timelines:
        from graphite_tpu.obs import Timeline

        for b, path in enumerate(timelines):
            tl = Timeline.load(path)
            for row in tl.json_rows():
                for s in tl.series:
                    if s == "time_ps":
                        continue
                    events.append({
                        "name": f"tl{b}.{s}", "cat": "telemetry",
                        "ph": "C", "pid": SIM_PID, "tid": 0,
                        "ts": int(row["time_ns"]),
                        "args": {"value": int(row[s])}})
    if profiles:
        from graphite_tpu.obs.profile import TileProfile

        for b, path in enumerate(profiles):
            prof = TileProfile.load(path)
            times = prof.time_ns
            for s in prof.series:
                col = prof.col(s)       # [S, T]
                for i in range(len(prof)):
                    # one stacked counter track per series: every
                    # tile's value rides the same event's args
                    events.append({
                        "name": f"prof{b}.{s}", "cat": "profile",
                        "ph": "C", "pid": SIM_PID, "tid": 0,
                        "ts": int(times[i]),
                        "args": {f"t{t}": int(col[i, t])
                                 for t in range(prof.n_tiles)}})
    if hists:
        from graphite_tpu.obs.hist import Hist

        for b, path in enumerate(hists):
            h = Hist.load(path)
            for s in h.sources:
                events.append({
                    "name": f"hist{b}.{s}", "cat": "hist", "ph": "i",
                    "pid": SIM_PID, "tid": 0, "ts": 0, "s": "g",
                    "args": {"count": h.total(s),
                             "p50": h.quantile(s, 0.5),
                             "p95": h.quantile(s, 0.95),
                             "p99": h.quantile(s, 0.99),
                             "file": path}})
    # metadata first, then every track's stamps monotone within its pid
    events.sort(key=lambda e: (e["ph"] != "M", e["pid"], e["ts"]))
    return events


def write_perfetto(out_path: str, **kw) -> int:
    """Write the unified Chrome trace (load in Perfetto UI /
    chrome://tracing); returns the event count."""
    events = perfetto_events(**kw)
    with open(out_path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ns"},
                  fh)
    return len(events)


def render_metrics(path: str, fmt: str) -> "list[str]":
    """Prometheus text dump -> aligned metric summaries."""
    from graphite_tpu.obs.metrics import parse_exposition

    with open(path) as fh:
        parsed = parse_exposition(fh.read())
    if fmt == "json":
        return [json.dumps({"metric": name, **m})
                for name, m in parsed.items()]
    cols = ["metric", "type", "value", "count", "sum", "p50", "p90",
            "p99"]
    rows = []
    for name, m in parsed.items():
        if m["type"] == "histogram":
            n = m["count"]
            rows.append([name, "histogram", "-", str(n),
                         str(round(m["sum"], 6))]
                        + [_hist_quantile(m["buckets"], n, q)
                           for q in (0.5, 0.9, 0.99)])
        else:
            v = m.get("value", 0)
            v = int(v) if float(v).is_integer() else round(v, 6)
            rows.append([name, m["type"], str(v), "-", "-", "-", "-",
                         "-"])
    return _align(cols, rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render telemetry timelines, span traces, and "
        "metrics dumps")
    ap.add_argument("files", nargs="*",
                    help=".npz timeline file(s) (obs.Timeline.save) — "
                    "or, with --heatmap, per-tile profile file(s) "
                    "(obs.TileProfile.save); several files render as "
                    "one campaign, sim-indexed in argument order")
    ap.add_argument("--heatmap", action="store_true",
                    help="treat the positional .npz files as per-tile "
                    "profiles and render tile-grid heatmaps + the "
                    "straggler/imbalance summary")
    ap.add_argument("--slice", default=None, metavar="WHICH",
                    help="heatmap time slice: 'total' (the default; "
                    "delta series sum over samples, levels take the "
                    "last), 'last', or a sample index (negative from "
                    "the end)")
    ap.add_argument("--series", metavar="A,B,...",
                    help="restrict heatmaps to these series")
    ap.add_argument("--spans", metavar="FILE",
                    help="render a span JSON-lines file "
                    "(tools/serve.py --trace-out) as a per-job latency "
                    "breakdown + batch table")
    ap.add_argument("--trade-curve", metavar="FILE",
                    help="render a span JSON-lines file as the "
                    "latency/occupancy trade curve (per-job queue "
                    "dwell vs batch occupancy + bucketed aggregates); "
                    "per-job result rows with energy_pj_total (a "
                    "power target's integrated energy) or energy_pj "
                    "(the telemetry series) render as the "
                    "energy-vs-wall trade + Pareto frontier instead")
    ap.add_argument("--metrics", metavar="FILE",
                    help="render a Prometheus text exposition "
                    "(tools/serve.py --metrics-out) as metric "
                    "summaries")
    ap.add_argument("--perfetto", metavar="OUT.json",
                    help="write one unified Chrome-trace JSON merging "
                    "every given artifact: --spans JSONL (host-time "
                    "track), positional telemetry .npz + --profile-npz "
                    "+ --hist .npz files (sim-time track); open in "
                    "the Perfetto UI or chrome://tracing")
    ap.add_argument("--hist", metavar="FILE", nargs="+", default=(),
                    help="latency-histogram .npz file(s) "
                    "(obs.Hist.save / tools/serve.py --hist-out) to "
                    "fold into the --perfetto export")
    ap.add_argument("--profile-npz", metavar="FILE", nargs="+",
                    default=(),
                    help="per-tile profile .npz file(s) to fold into "
                    "the --perfetto export as stacked counter tracks")
    ap.add_argument("--format", choices=("json", "text"), default="json")
    ap.add_argument("--summary", action="store_true",
                    help="emit per-timeline/profile summaries only "
                    "(peak injection rate, clock spread + per-series "
                    "peaks, skew/Gini stragglers, ...)")
    args = ap.parse_args(argv)

    if args.perfetto:
        if args.metrics or args.trade_curve or args.heatmap:
            ap.error("--perfetto combines positional timeline .npz, "
                     "--spans, --profile-npz and --hist only")
        if not (args.files or args.spans or args.hist
                or args.profile_npz):
            ap.error("--perfetto needs at least one input artifact "
                     "(timeline .npz, --spans, --profile-npz, --hist)")
    elif args.hist or args.profile_npz:
        ap.error("--hist/--profile-npz apply to --perfetto mode only")
    else:
        modes = sum((bool(args.files), bool(args.spans),
                     bool(args.metrics), bool(args.trade_curve)))
        if modes != 1:
            ap.error("give exactly one input: timeline/profile .npz "
                     "file(s), --spans FILE, --trade-curve FILE, or "
                     "--metrics FILE")
    if args.heatmap and not args.files:
        ap.error("--heatmap needs positional profile .npz file(s)")
    if not args.heatmap and (args.slice is not None or args.series):
        ap.error("--slice/--series apply to --heatmap mode only")

    # pure host-side post-processing — never touch a chip
    import os

    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    if args.perfetto:
        n = write_perfetto(args.perfetto, spans=args.spans,
                           timelines=args.files,
                           profiles=args.profile_npz, hists=args.hist)
        print(json.dumps({"perfetto": args.perfetto, "events": n}))
        return 0

    if args.spans:
        for line in render_spans(args.spans, args.format):
            print(line)
        return 0
    if args.trade_curve:
        for line in render_trade_curve(args.trade_curve, args.format):
            print(line)
        return 0
    if args.metrics:
        for line in render_metrics(args.metrics, args.format):
            print(line)
        return 0
    if args.heatmap:
        sl = args.slice if args.slice is not None else "total"
        if sl not in ("total", "last"):
            try:
                sl = int(sl)
            except ValueError:
                ap.error("--slice must be 'total', 'last', or an "
                         "integer sample index")
        names = (tuple(s.strip() for s in args.series.split(",")
                       if s.strip()) if args.series else None)
        for line in render_heatmap(args.files, args.format,
                                   series=names, sample=sl,
                                   summary_only=args.summary):
            print(line)
        return 0

    from graphite_tpu.obs import Timeline

    for b, path in enumerate(args.files):
        tl = Timeline.load(path)
        summary = {"sim": b, "file": path,
                   "sample_interval_ps": tl.sample_interval_ps,
                   **tl.summary()}
        if args.format == "json":
            if not args.summary:
                for row in tl.json_rows():
                    print(json.dumps({"sim": b, **row}))
            print(json.dumps(summary))
        else:
            print(f"== sim {b}: {path} "
                  f"(interval {tl.sample_interval_ps} ps, "
                  f"{len(tl)} of {tl.n_total} samples"
                  + (", ring WRAPPED" if tl.wrapped else "") + ")")
            if not args.summary:
                for line in _text_table(tl):
                    print(line)
            for k, v in summary.items():
                if k in ("sim", "file"):
                    continue
                if k == "peaks":
                    # per-series argmax rows: spikes are nameable by
                    # sample/time, not only sized
                    for s, p in v.items():
                        print(f"  peak {s:22} {p['max']} at sample "
                              f"{p['sample']} (t={p['time_ns']} ns)")
                    continue
                print(f"  {k:28} {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
