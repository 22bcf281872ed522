"""campaign_closed: one architect's sweep, served: grids of jobs pushed
through `CampaignService`, closed loop, one grid outstanding.

A grid is `streams` traces x the traffic's DRAM latencies, submitted
stream-major (with `batch_size` = the number of latencies, each batch is
one stream at every latency) and drained; the next grid is submitted only
when the last envelope of this one has come back, and only if the median
grid so far fits the time left.  No arrival schedule, no think time: the
load is 100% of what the service sustains.  The run's streams are FIXED
(`streams`, of the `pool` of generator seeds the reference covers), and
`--seed` is recorded, echoed into each `Job.seed` and draws nothing, as in
the solo cells: a batch runs until its slowest job is done, the pool's
streams take 1,280 to 1,344 iterations a batch, and so the rate over the
six pairs of the pool spreads by 1.7% against the 1% a cell may have
(measured, PERF.md).  `correct` is decided by STORED digests:
`references/<config>.json` holds one SHA-256 of every statistic per
(stream, latency), made on the CPU by plain `Simulator.run()` with the
latency in the config text (`make_reference_campaign.py`), independent of
`serve/`, `sweep/`, `vmap` and the knob operands - and NOT of the engine:
the golden interpreter models no iocoom, and on free-running tiles that
share lines it resolves same-line races in another (equally valid) order,
so it cannot give exact digests for this traffic.  What holds the served,
un-gated program to the golden interpreter is
`tests/test_campaign_golden.py` (PERF.md section 2).  Every envelope of
the window is compared; nothing is simulated or compiled after the window.

Set-up serves ONE grid of SHORT jobs (each of the run's streams cut to
`trace_n_accesses` a tile and padded to the window's trace length: the
window's class and shapes, so it compiles the class's one program, or
loads it, and a full-length grid would only add its 15 s to every run);
the span `warmup` lies around it.  Every grid is served by a daemon worker
thread and awaited with a deadline (`deadline_x` times the first grid's
time, at least `deadline_min_s`): a worker's exception or a grid past its
deadline is a failed grid that ends the window, never a wait.

The end-to-end rate is the trace records of the jobs drained OK over the
WHOLE window, as in the solo cells: padded slots and replicas count
nothing.  The traced slice is ONE batch through the service - the program
the window drove, its whole dispatch, nothing compiled - of SHORT jobs:
the first stream cut to `trace_n_accesses` a tile and padded to the
window's trace length (the same class, so the same executable), at every
latency.  A batch is one dispatch and cannot be cut, and a batch of the
window's jobs is ~5 million device events (3,700 operations an iteration:
170 s to take and 200 s to reduce, measured, PERF.md); with every gate
off an iteration does the same work whatever the trace holds, so the
short batch shows the same operations in the same shares, but a far
larger share of host and idle time than the window's batches have: the
cell therefore reports NO `device_idle_share` (the slice's 42% is not the
window's 4%, PERF.md), and `batch_host_ms` / `batch_execute_ms` are the
window's own.  A window of two grids has no tail either: 16 jobs, whose
95th percentile is their maximum and reads the grid's structure (a job of
the second batch waits for the first), so no p95 is reported.
`ctx.own["sim"]` is the service's handle to that resident program (`CampaignService.resident_program`) where the
program has it; on a program without it the scope metrics are left out
and nothing is compiled or lowered in their stead.

**Where this departs from the worked example in `benchmark/README.md`.**
(1) `judge` compares stored digests of a fixed pool of streams instead of
running plain `Simulator.run()` on the CPU backend after the window: the
solo program bakes its trace in, so streams drawn from `--seed` would be
several cold compiles inside the timed process (the cell was refused
once for running past its time limit, ledger PR 30); the stored digests
are exact, cover every envelope instead of a sample, and cost nothing at
run time.  (2) The rate is `sim_records_per_s`, the solo cells' metric
with the solo cells' definition and bound, not a `campaign_records_per_s`
of its own: what a campaign user pays for is the same trace records per
wall second, and one metric lets the cells be read side by side; its
spread here is measured against half that bound before the cell is
admitted (PERF.md).
"""

import queue
import statistics as st
import threading
import traceback

from lib import checks, digest, served, target
from lib.clock import now


def job_key(stream: int, latency_ns: int) -> str:
    """The name of one (stream, latency) of the pool, in the reference."""
    return f"s{stream}-L{latency_ns}"


def pool_trace(config: dict, stream: int):
    """One stream of the pool: the configuration's trace (the traffic's
    generator at its geometry), seeded with the stream's seed."""
    trace = {**config["trace"],
             "kwargs": {**config["trace"]["kwargs"], "seed": stream}}
    return target.build_trace({"trace": trace})


def check_generator(config: dict, traffic: dict) -> None:
    """The configuration's canonical trace IS the traffic's generator at
    the pool's first seed: one of the two files cannot drift alone."""
    gen = traffic["generator"]
    want = {**gen["kwargs"], "seed": traffic["pool"][0],
            "n_tiles": config["config_text"]["tiles"]}
    got = config["trace"]
    if got["function"] != gen["function"] or got["kwargs"] != want:
        raise SystemExit(
            f"benchmark: the configuration's trace {got} is not the "
            f"traffic's generator {gen} at seed {traffic['pool'][0]}")


class _Worker:
    """One daemon thread that serves grids; the caller waits for each
    with a deadline.  A thread stuck in a device call cannot be stopped:
    being a daemon it does not keep the process from ending."""

    def __init__(self):
        self._todo = queue.Queue()
        self._done = queue.Queue()
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            fn = self._todo.get()
            try:
                self._done.put((fn(), None))
            except BaseException:               # noqa: BLE001 - reported
                self._done.put((None, traceback.format_exc()))

    def call(self, fn, deadline_s: float):
        """(result, error text or None); past the deadline the result is
        given up and the error says so."""
        self._todo.put(fn)
        try:
            return self._done.get(timeout=deadline_s)
        except queue.Empty:
            return None, f"no result within the deadline of " \
                         f"{deadline_s:.1f} s"


def _grid_jobs(ctx, traces: dict) -> list:
    """[(Job, stream, latency)] of one grid over the streams of `traces`
    ({stream: TraceBatch}), stream-major."""
    from graphite_tpu.serve.job import Job

    own = ctx.own
    out = []
    for s, trace in traces.items():
        for lat in ctx.traffic["dram_latency_ns"]:
            own["n_jobs_made"] += 1
            out.append((Job(
                job_id=f"j{own['n_jobs_made']}-{job_key(s, lat)}",
                config=own["sim_config"], trace=trace,
                knobs={"dram_latency_ns": lat}, seed=ctx.seed), s, lat))
    return out


def _serve(ctx, traces: dict, deadline_s: float
           ) -> "tuple[list, str | None]":
    """Submit one grid over `traces` and drain it.  ([record per job],
    error or None); a record is {key, stream, latency, t_submit,
    t_envelope, envelopes} and a job whose envelope did not come back
    has no envelope."""
    svc = ctx.own["svc"]
    jobs = _grid_jobs(ctx, traces)
    records = {j.job_id: {"key": job_key(s, lat), "stream": s,
                          "latency": lat, "t_submit": None,
                          "t_envelope": None, "envelopes": []}
               for j, s, lat in jobs}

    def grid():
        for j, _, _ in jobs:
            records[j.job_id]["t_submit"] = now()
            svc.submit(j)
        for env in svc.drain(force=True):
            rec = records.get(env.job_id)
            if rec is None:
                raise RuntimeError(f"an envelope for {env.job_id!r}, "
                                   f"which this grid did not submit")
            rec["t_envelope"] = now()
            rec["envelopes"].append(env)

    _, err = ctx.own["worker"].call(grid, deadline_s)
    return list(records.values()), err


def _not_ok(records: list) -> list:
    """Keys of the job records without exactly one ok envelope."""
    return [r["key"] for r in records if len(r["envelopes"]) != 1
            or r["envelopes"][0].status != "ok"]


def _short_traces(ctx) -> dict:
    """{stream: its trace cut to `trace_n_accesses` a tile and padded
    with NOPs to the window's trace length} for the run's streams: the
    service puts them in the window's class, so they run the window's
    executable."""
    from graphite_tpu.sweep.pack import pack_traces

    short = dict(ctx.config)
    short["trace"] = {**short["trace"], "kwargs": {
        **short["trace"]["kwargs"],
        "n_accesses": ctx.traffic["trace_n_accesses"]}}
    return {s: pack_traces([pool_trace(short, s)], validate=False,
                           pad_length=full.length).sim(0)
            for s, full in ctx.own["traces"].items()}


def _deadline_s(ctx) -> float:
    t = ctx.traffic
    return max(t["deadline_x"] * ctx.own["first_grid_s"],
               t["deadline_min_s"])


def setup(ctx) -> None:
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.serve.service import CampaignService

    own, t = ctx.own, ctx.traffic
    with ctx.spans.span("build_target"):
        check_generator(ctx.config, t)
        own["sim_config"] = target.build_sim_config(ctx.config)
        own["streams"] = tuple(t["streams"])
        if not set(own["streams"]) <= set(t["pool"]):
            raise SystemExit(f"benchmark: streams {t['streams']} are not "
                             f"of the pool {t['pool']}")
        own["traces"] = {s: pool_trace(ctx.config, s)
                         for s in own["streams"]}
        own["trace_records"] = {s: checks.trace_records(b)
                                for s, b in own["traces"].items()}
        own["trace_instructions"] = {s: checks.trace_instructions(b)
                                     for s, b in own["traces"].items()}
        own["short_traces"] = _short_traces(ctx)
        # the target as built, held against the configuration's `expect`
        target.check_expectations(ctx.config, Simulator(
            own["sim_config"], own["traces"][own["streams"][0]],
            **ctx.config["simulator"]))
    print(f"streams {list(own['streams'])} of the pool {t['pool']} "
          f"(--seed {ctx.seed} is recorded and draws nothing); records "
          f"per job {sorted(own['trace_records'].values())}")
    own["svc"] = CampaignService(**t["service"], tracing=True)
    own["worker"] = _Worker()
    own["n_jobs_made"] = 0
    with ctx.spans.span("warmup"):
        t0 = now()
        records, err = _serve(ctx, own["short_traces"],
                              t["first_grid_limit_s"])
        own["first_grid_s"] = now() - t0
    bad = _not_ok(records)
    if err or bad:
        raise SystemExit(f"benchmark: the first grid failed "
                         f"({bad or ''}): {err}")
    # the handle to the resident program, where the program has one
    handle = getattr(own["svc"], "resident_program", None)
    own["sim"] = handle() if handle else None
    print(f"first grid (short jobs): {len(records)} jobs in "
          f"{own['first_grid_s']:.3f} s; "
          f"program handle: "
          f"{'yes' if own['sim'] is not None else 'none (no scope metric)'}")


def window(ctx) -> None:
    own = ctx.own
    deadline_s = _deadline_s(ctx)
    t0 = now()
    end = t0 + ctx.seconds
    while True:
        g0 = now()
        records, err = _serve(ctx, own["traces"], deadline_s)
        wall = now() - g0
        ctx.attempted += len(records)
        ctx.readings.append({"t0": g0, "wall_s": wall, "jobs": records,
                             "error": err})
        if err:
            # a failed grid ends the window: the service may be stuck
            ctx.raised += 1
            print(f"grid {len(ctx.readings)} failed after {wall:.3f} s:\n"
                  f"{err}")
            break
        walls = [r["wall_s"] for r in ctx.readings]
        if now() + st.median(walls) > end:
            break
    ctx.window_s = now() - t0


def end_to_end(ctx) -> dict:
    grids = [g for g in ctx.readings if not g["error"]]
    if not grids:
        return {}
    walls = sorted(g["wall_s"] for g in grids)
    ok = served.ok_jobs(ctx)
    print(f"grids: {len(grids)} drained of {len(ctx.readings)} started in "
          f"{ctx.window_s:.3f} s, {sum(walls):.3f} s of it inside a grid; "
          f"grid min {walls[0]:.6f} median {st.median(walls):.6f} max "
          f"{walls[-1]:.6f} s; jobs drained ok {len(ok)} of "
          f"{ctx.attempted}")
    start = min(g["t0"] for g in grids)
    slow = sorted(grids, key=lambda g: -g["wall_s"])[:3]
    print("slowest grids (s into the window: wall s): " + ", ".join(
        f"{g['t0'] - start:.1f}: {g['wall_s']:.4f}" for g in slow))
    trips = {}
    for j in ok:
        trips[j["stream"]] = max(trips.get(j["stream"], 0),
                                 int(j["envelopes"][0].n_iterations))
    print(f"engine iterations of a batch (the loop's trip count: the "
          f"slowest of its jobs), by stream: {trips}")
    # no metric: a window of this few jobs has no tail (module docstring)
    waits = sorted(j["t_envelope"] - j["t_submit"] for j in ok)
    if waits:
        print(f"submit -> envelope over {len(waits)} jobs: min "
              f"{waits[0]:.3f} median {st.median(waits):.3f} max "
              f"{waits[-1]:.3f} s")
    records = sum(ctx.own["trace_records"][j["stream"]] for j in ok)
    return {"sim_records_per_s": records / ctx.window_s}


def judge(ctx, out=print) -> "tuple[bool, int]":
    """(correct, failed jobs): every job of the window against the
    configuration's guarantees and the stored digest of its (stream,
    latency).  Every limit is 0."""
    ref = ctx.reference["jobs"]
    jobs = [j for g in ctx.readings for j in g["jobs"]]
    lost = [j for j in jobs if not j["envelopes"]]
    twice = [j for j in jobs if len(j["envelopes"]) > 1]
    worst = {"func_errors": 0, "tiles_whose_clock_did_not_advance": 0,
             "total_instructions_minus_trace_count": 0}
    first, n_failed, n_compared = {}, len(lost), 0
    not_ok, off_ref, off_first = [], [], []
    for j in jobs:
        if not j["envelopes"]:
            continue
        env = j["envelopes"][0]
        bad = len(j["envelopes"]) > 1
        if env.status != "ok" or env.results is None:
            not_ok.append(j["key"])
            n_failed += 1
            continue
        nums = checks.check_reading(
            env.results, ctx.own["trace_instructions"][j["stream"]])
        for k, v in nums.items():
            if abs(v) > abs(worst[k]):
                worst[k] = v
        hs = digest.hashes(digest.statistics(env.results))
        n_compared += 1
        if digest.combined(hs) != ref.get(j["key"]):
            off_ref.append(j["key"])
            bad = True
        if first.setdefault(j["key"], hs) != hs:
            off_first.append(j["key"])
            bad = True
        n_failed += bool(bad or any(nums.values()))
    out(f"check grids that failed or ran past their deadline: "
        f"{ctx.raised} (limit 0)")
    out(f"check jobs submitted whose envelope did not come back: "
        f"{len(lost)} of {len(jobs)} (limit 0)")
    out(f"check jobs drained more than once: {len(twice)} (limit 0)")
    out(f"check envelopes with a status other than ok: {len(not_ok)} "
        f"{sorted(set(not_ok))[:4]} (limit 0)")
    for k, v in worst.items():
        out(f"check {k}, worst job: {v} (limit 0)")
    out(f"check repeats of a job differing from its first envelope: "
        f"{len(off_first)} {sorted(set(off_first))[:4]} (limit 0)")
    out(f"check envelopes whose digest differs from the stored digest of "
        f"their (stream, latency) ({ctx.reference['origin']}, "
        f"{len(ref)} digests): {len(off_ref)} of {n_compared} compared "
        f"{sorted(set(off_ref))[:4]} (limit 0)")
    correct = n_compared > 0 and n_failed == 0 and not ctx.raised \
        and not twice
    return correct, n_failed


def traced_slice(ctx, tracing) -> None:
    """What the profiler sees: ONE batch through the service - the
    program the window drove, its whole dispatch (a part of it would be
    another program), with what the service does around it - of the
    first stream cut short, at every latency (the module's docstring says
    why).  Nothing compiles."""
    own = ctx.own
    first = own["streams"][0]
    with tracing():
        with ctx.spans.span("run"):
            t0 = now()
            records, err = _serve(ctx, {first: own["short_traces"][first]},
                                  _deadline_s(ctx))
            wall = now() - t0
    bad = _not_ok(records)
    if err or bad:
        raise SystemExit(f"benchmark: the traced batch failed "
                         f"({bad or ''}): {err}")
    own["traced"] = (f"one batch of the first stream cut to "
                     f"{ctx.traffic['trace_n_accesses']} accesses a tile "
                     f"(not a batch of the window's jobs)")
    # a whole `SweepRunner.run()`: its `fetch` + `results` spans are what
    # every batch of the class pays (B results, whatever the jobs' length)
    own["traced_whole_run"] = True
    iters = max(r["envelopes"][0].n_iterations for r in records)
    print(f"traced slice: {own['traced']}, {len(records)} jobs, {iters} "
          f"engine iterations in {wall:.3f} s under the profiler "
          f"({1e3 * wall / max(1, iters):.3f} ms each)")
