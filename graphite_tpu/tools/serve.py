"""Campaign-service CLI: JSON-lines jobs in -> JSON-lines results out.

The front-end wrapper over `serve.CampaignService`: each input line is
one job spec, each output line one result envelope (emitted as its
batch completes — the stream a long-running caller tails), plus one
trailing summary line with the service counters (queue depth, batch
occupancy, cache hit rate, compile count, jobs/s).

Job-spec line schema (all fields except `id` optional):

  {"id": "j0",                     // job id echoed into the result
   "workload": "memstress",        // memstress | a trace/benchmarks name
   "tiles": 16, "seed": 7,
   "accesses": 24,                 // memstress accesses per tile
   "protocol": "pr_l1_pr_l2_dram_directory_msi",
   "network": "emesh_hop_counter",
   "knobs": {"dram_latency_ns": 120, ...},   // traced sweep knobs
   "clock_scheme": "lax_barrier",  // lax_barrier | lax | lax_p2p
   "telemetry": {"sample_interval_ps": 1000000, "n_samples": 64,
                 // optional energy_pj series: explicit pJ prices, or
                 // {"node_nm": 45} to price via the native power model
                 "energy": {"instruction_pj": 2, "l2_miss_pj": 120}},
   "profile": {"sample_interval_ps": 1000000, "n_samples": 64,
               // optional "series": [...], "energy": {...} — the
               // per-tile spatial profiler ring (obs.ProfileSpec);
               // render results with tools/report.py --heatmap
               "series": ["clock_skew_ps", "l2_misses"]},
   "hist": {"log2_buckets": 32,    // device-resident latency histograms
            // optional "sources": [...], explicit "edges": [...],
            // "per_tile": true, "energy": {...} (obs.HistSpec);
            // persist counts with --hist-out DIR
            "sources": ["miss_lat_ps", "net_lat_ps"]}}

Usage:
  python -m graphite_tpu.tools.serve --jobs jobs.jsonl --budget-bytes 2e9
  cat jobs.jsonl | python -m graphite_tpu.tools.serve --batch-size 8
  python -m graphite_tpu.tools.serve --dryrun    # tiny CPU smoke, no input
  python -m graphite_tpu.tools.serve --jobs jobs.jsonl --store /shared/aot
      # fleet mode (round 17): executables deserialize from / serialize
      # into the shared store, warm-starting from compatible entries —
      # each program class compiles once per FLEET; the summary line's
      # store_hits / store_fills / compile_count report the split
      # (maintain the store with tools/store.py ls|verify|gc|evict)

`--dryrun` pins JAX to CPU and serves a built-in mixed-geometry,
mixed-knob demo job set — the smoke shape
`tests/test_serve.py::TestServiceEndToEnd` also exercises.

Observability (round 14): `--trace-out spans.jsonl` records every
job's lifecycle spans (submit → validate → admit → queue dwell →
execute → emit) plus per-batch execution spans and writes them as
JSON-lines (`tools/report.py --spans` renders the per-job latency
breakdown); `--metrics-out metrics.prom` dumps the service's metrics
registry in Prometheus text format (`tools/report.py --metrics`
renders it); the trailing summary line always embeds the JSON metrics
snapshot under "metrics" alongside the round-13 counter keys.

Energy in the result lines: where a job's target models power (`[dvfs]`
+ `[general] enable_power_modeling` in its config text) its line
carries `energy_pj_total` (the integrated `SimResults.energy_pj["total"]`
summed over the tiles - NOT the telemetry series' `energy_pj`, which is
priced per sample), `dvfs_transitions` and, where the whole job ended on
one CORE frequency, `dvfs_level_mhz`; `tools/report.py --trade-curve`
plots a job by the integrated total where it has one.  Such a config
text is a program class of its own (the class key digests the text).  A
job-spec line builds no such target: it is served from code, as a `Job`
whose `config` is the power target's (benchmark/drivers/
campaign_vf_closed.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


DRYRUN_JOBS = [
    {"id": "d0", "tiles": 4, "seed": 1, "accesses": 10},
    {"id": "d1", "tiles": 4, "seed": 2, "accesses": 10,
     "knobs": {"dram_latency_ns": 150}},
    {"id": "d2", "tiles": 4, "seed": 3, "accesses": 10},
    {"id": "d3", "tiles": 8, "seed": 4, "accesses": 10},
    {"id": "d4", "tiles": 4, "seed": 5, "accesses": 10,
     "knobs": {"hop_latency_cycles": 3}},
    {"id": "d5", "tiles": 4, "seed": 6, "accesses": 10,
     "telemetry": {"sample_interval_ps": 1_000_000, "n_samples": 16,
                   "energy": {"instruction_pj": 2, "l2_miss_pj": 120,
                              "dram_access_pj": 500}}},
    {"id": "d6", "tiles": 4, "seed": 7, "accesses": 10,
     "profile": {"sample_interval_ps": 1_000_000, "n_samples": 16}},
    {"id": "d7", "tiles": 4, "seed": 8, "accesses": 10,
     "hist": {"log2_buckets": 24}},
]


def build_job(spec: dict, config_cache: dict):
    """One input line -> a serve.Job (config objects cached per
    geometry/protocol/network so same-shaped jobs share a digest-equal
    config and co-batch)."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.obs import TelemetrySpec
    from graphite_tpu.serve import Job
    from graphite_tpu.tools._template import config_text
    from graphite_tpu.trace import synthetic

    if "id" not in spec:
        raise ValueError("job spec needs an \"id\" field")
    tiles = int(spec.get("tiles", 16))
    workload = spec.get("workload", "memstress")
    seed = int(spec.get("seed", 7))
    protocol = spec.get("protocol", "pr_l1_pr_l2_dram_directory_msi")
    network = spec.get("network", "emesh_hop_counter")
    shared = workload == "memstress"
    ckey = (tiles, protocol, network, shared)
    sc = config_cache.get(ckey)
    if sc is None:
        sc = SimConfig(ConfigFile.from_string(config_text(
            tiles, shared_mem=shared, protocol=protocol,
            network=network, clock_scheme="lax_barrier")))
        config_cache[ckey] = sc
    if workload == "memstress":
        trace = synthetic.memory_stress_trace(
            tiles, n_accesses=int(spec.get("accesses", 24)),
            working_set_bytes=1 << 13, write_fraction=0.4,
            shared_fraction=0.5, seed=seed)
    else:
        from graphite_tpu.trace.benchmarks import BENCHMARKS

        if workload not in BENCHMARKS:
            raise ValueError(
                f"unknown workload {workload!r} (memstress or: "
                f"{', '.join(sorted(BENCHMARKS))})")
        trace = BENCHMARKS[workload](tiles)
    def _prices(t, what):
        if not t.get("energy"):
            return None
        from graphite_tpu.obs import EnergyPrices

        e = t["energy"]
        if not isinstance(e, dict):
            raise ValueError(
                f"{what}.energy must be a dict of pJ prices or "
                '{"node_nm": N} for the native power model')
        if "node_nm" in e:
            return EnergyPrices.from_power_model(
                int(e["node_nm"]), voltage=float(e.get("voltage", 1.0)))
        return EnergyPrices(**e)

    telemetry = None
    if spec.get("telemetry"):
        t = spec["telemetry"]
        telemetry = TelemetrySpec(
            sample_interval_ps=int(t["sample_interval_ps"]),
            n_samples=int(t.get("n_samples", 256)),
            series=tuple(t["series"]) if t.get("series") else None,
            energy_prices=_prices(t, "telemetry"))
    profile = None
    if spec.get("profile"):
        from graphite_tpu.obs import ProfileSpec

        p = spec["profile"]
        profile = ProfileSpec(
            sample_interval_ps=int(p["sample_interval_ps"]),
            n_samples=int(p.get("n_samples", 256)),
            series=tuple(p["series"]) if p.get("series") else None,
            energy_prices=_prices(p, "profile"))
    hist = None
    if spec.get("hist"):
        from graphite_tpu.obs import HistSpec

        h = spec["hist"]
        hist = HistSpec(
            sources=tuple(h["sources"]) if h.get("sources") else None,
            edges=tuple(h["edges"]) if h.get("edges") else None,
            log2_buckets=int(h.get("log2_buckets", 32)),
            per_tile=bool(h.get("per_tile", False)),
            energy_prices=_prices(h, "hist"))
    return Job(job_id=str(spec["id"]), config=sc, trace=trace,
               knobs=dict(spec.get("knobs", {})), telemetry=telemetry,
               profile=profile, hist=hist, seed=seed,
               clock_scheme=spec.get("clock_scheme"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="campaign service: JSON-lines jobs in, JSON-lines "
        "results out (a power target's lines carry energy_pj_total, "
        "dvfs_transitions, dvfs_level_mhz; it is a program class of its "
        "own)")
    ap.add_argument("--jobs", help="job-spec JSON-lines file (default: "
                    "stdin)")
    ap.add_argument("--budget-bytes", type=float, default=0,
                    help="per-device hbm_budget_bytes admission budget "
                    "(0 = off)")
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--cache-bytes", type=float, default=0,
                    help="program-cache eviction budget (0 = unbounded)")
    ap.add_argument("--max-pending", type=int, default=1024)
    ap.add_argument("--max-quanta", type=int, default=1_000_000)
    ap.add_argument("--n-devices", default="1",
                    help="devices admission may bin-pack a too-big-"
                    "for-one-device sim across (the 2D batch x tile "
                    "layout); an integer or 'auto' (visible device "
                    "count).  Default 1 = round-13 single-device "
                    "admission")
    ap.add_argument("--verify-hits", action="store_true",
                    help="re-lower every cache hit and re-prove "
                    "fingerprint equality (retrace, never recompile)")
    ap.add_argument("--store", metavar="DIR",
                    help="persistent AOT program store directory "
                    "(created if absent, shared across a fleet of "
                    "serve processes): compiled executables are "
                    "deserialized from / serialized into it, and the "
                    "service warm-starts from compatible entries "
                    "(maintain with tools/store.py)")
    ap.add_argument("--warm-limit", type=int, default=None,
                    metavar="N",
                    help="stage at most N most-recently-used store "
                    "entries at startup (default: every compatible "
                    "entry; unstaged classes still store-hit lazily)")
    ap.add_argument("--max-dwell-s", type=float, default=0.0,
                    help="let an under-full batch wait up to this long "
                    "for its class to fill before forming (latency/"
                    "occupancy trade; 0 = run immediately)")
    ap.add_argument("--trace-out", metavar="FILE",
                    help="enable span tracing and write job/batch "
                    "lifecycle spans as JSON-lines on exit "
                    "(render: tools/report.py --spans FILE)")
    ap.add_argument("--profile-out", metavar="DIR",
                    help="save each job's per-tile profile as "
                    "DIR/<job_id>.npz (obs.TileProfile.save; the "
                    "result line gains \"profile_file\"; render: "
                    "tools/report.py --heatmap DIR/*.npz)")
    ap.add_argument("--hist-out", metavar="DIR",
                    help="save each job's latency histograms as "
                    "DIR/<job_id>.npz (obs.Hist.save; the result line "
                    "gains \"hist_file\"; merge into a Chrome trace: "
                    "tools/report.py --perfetto out.json --hist "
                    "DIR/*.npz)")
    ap.add_argument("--metrics-out", metavar="FILE",
                    help="write the metrics registry as Prometheus "
                    "text exposition on exit "
                    "(render: tools/report.py --metrics FILE)")
    ap.add_argument("--dryrun", action="store_true",
                    help="CPU smoke: force JAX_PLATFORMS=cpu and serve "
                    "a built-in mixed demo job set")
    args = ap.parse_args(argv)

    if args.dryrun:
        # must land before jax initializes its backends
        os.environ["JAX_PLATFORMS"] = "cpu"

    if args.store is not None:
        # a clean refusal beats a traceback deep inside the store: a
        # path that EXISTS but is not a directory can never hold the
        # entries/ layout (a missing path is first boot — create it)
        if os.path.exists(args.store) and not os.path.isdir(args.store):
            print(f"error: --store {args.store!r} exists and is not a "
                  "directory", file=sys.stderr)
            return 2
        os.makedirs(args.store, exist_ok=True)

    import graphite_tpu  # noqa: F401  (x64)

    from graphite_tpu.analysis.cost import ResidencyBudgetError
    from graphite_tpu.serve import CampaignService, QueueFullError
    from graphite_tpu.trace.validate import TraceValidationError

    failures = 0
    if args.dryrun:
        specs = list(DRYRUN_JOBS)
    else:
        fh = open(args.jobs) if args.jobs else sys.stdin
        specs = []
        for lineno, line in enumerate(fh, 1):
            if not line.strip() or line.lstrip().startswith("#"):
                continue
            try:
                specs.append(json.loads(line))
            except ValueError as e:
                # one bad line rejects that line, never the stream
                failures += 1
                print(json.dumps({"line": lineno, "status": "rejected",
                                  "error": f"bad JSON: {e}"}))
        if args.jobs:
            fh.close()

    if args.n_devices != "auto":
        try:
            args.n_devices = int(args.n_devices)
        except ValueError:
            raise SystemExit(
                f"--n-devices must be an integer or 'auto' "
                f"(got {args.n_devices!r})")
    service = CampaignService(
        hbm_budget_bytes=int(args.budget_bytes),
        batch_size=args.batch_size,
        cache_bytes=int(args.cache_bytes),
        max_pending=args.max_pending,
        max_quanta=args.max_quanta,
        verify_hits=args.verify_hits,
        n_devices=args.n_devices,
        tracing=bool(args.trace_out),
        store=args.store,
        max_dwell_s=args.max_dwell_s)
    n_warm = service.warm_start(limit=args.warm_limit)
    if n_warm:
        print(json.dumps({"warm_start": n_warm,
                          "store": args.store}), flush=True)

    config_cache: dict = {}
    t0 = time.perf_counter()

    n_reported = 0      # batch ids count up from 0

    def emit_batches():
        """One line per batch not yet reported: what JAX traced, lowered,
        compiled or loaded for it (the program ledger's delta,
        obs/trace.py) beside the service's own compile count — which
        batch recompiled, and what."""
        nonlocal n_reported
        new = []
        for b in reversed(service.batch_log):
            if b.batch_id < n_reported:
                break
            new.append(b)
        for b in reversed(new):
            n_reported = b.batch_id + 1
            print(json.dumps({
                "batch": b.batch_id, "class": b.class_name, "ok": b.ok,
                "cache_hit": b.cache_hit,
                "compile_count": service.counters["compile_count"],
                "programs": None if b.programs is None else {
                    k: round(v, 6) for k, v in b.programs.items()}}),
                flush=True)

    def emit(res):
        """One result line; --profile-out persists the per-tile ring
        (the envelope only carries a sample count) and names the file
        in the line so the heatmap render is one copy-paste away."""
        emit_batches()
        row = res.to_json()
        if args.profile_out and res.profile is not None:
            os.makedirs(args.profile_out, exist_ok=True)
            path = os.path.join(args.profile_out, f"{res.job_id}.npz")
            res.profile.save(path)
            row["profile_file"] = path
        if args.hist_out and res.hist is not None:
            os.makedirs(args.hist_out, exist_ok=True)
            path = os.path.join(args.hist_out, f"{res.job_id}.npz")
            res.hist.save(path)
            row["hist_file"] = path
        print(json.dumps(row), flush=True)

    # submit with per-job drain on backpressure: a full queue runs a
    # batch (streaming its results) instead of dropping the job
    for spec in specs:
        try:
            job = build_job(spec, config_cache)
        except (ValueError, KeyError) as e:
            failures += 1
            print(json.dumps({"job": spec.get("id"), "status": "rejected",
                              "error": f"bad spec: {e}"}))
            continue
        while True:
            try:
                service.submit(job)
                break
            except QueueFullError:
                # drain through the dwell policy first (it runs a
                # FULL class while an under-full head ages), forcing
                # only when every class is under-full and held — the
                # queue must shrink for the submit to retry
                ran = False
                for res in service.step():
                    ran = True
                    emit(res)
                if not ran:
                    for res in service.step(force=True):
                        emit(res)
            except (ResidencyBudgetError, TraceValidationError,
                    ValueError) as e:
                failures += 1
                print(json.dumps({"job": job.job_id,
                                  "status": "rejected",
                                  "error": str(e)}))
                break
        if args.max_dwell_s > 0:
            # streaming dwell: run whatever the policy considers
            # ready NOW (a full class, or a head past its window),
            # holding under-full batches for later arrivals — the
            # latency/occupancy dial acting mid-stream, not only at
            # backpressure; with the default 0 the round-13
            # submit-everything-then-drain flow is untouched
            for res in service.step():
                emit(res)
    # input is exhausted: no job can ever fill an under-full batch, so
    # force past any dwell hold instead of sleeping out the window
    for res in service.drain(force=True):
        emit(res)
    counters = service.counters
    failures += counters["failed"]
    if args.trace_out:
        n_spans = service.export_spans(args.trace_out)
        print(json.dumps({"trace_out": args.trace_out,
                          "spans": n_spans}), flush=True)
    if args.metrics_out:
        with open(args.metrics_out, "w") as fh:
            fh.write(service.metrics.exposition())

    def _round(v):
        if isinstance(v, float):
            return round(v, 6)
        if isinstance(v, dict):
            return {k: _round(x) for k, x in v.items()}
        return v

    print(json.dumps({
        "summary": True,
        "wall_s": round(time.perf_counter() - t0, 3),
        **{k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in counters.items()},
        # the registry's JSON snapshot rides the summary line — one
        # artifact holds both the compatibility counters and the
        # histogram summaries (count/sum/p50/p90/p99)
        "metrics": _round(service.metrics.snapshot()),
        "dryrun": bool(args.dryrun),
    }))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
