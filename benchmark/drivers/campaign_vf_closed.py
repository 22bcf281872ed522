"""campaign_vf_closed: an architect's V/f sweep, served: grids of jobs
pushed through `CampaignService`, closed loop, one grid outstanding.  The
worked example of a SECOND served cell: what it shares with
`campaign_closed.py` it imports from there, and what is here is what a
swept axis that is not a traced knob forces.

A grid is `streams` canneal inputs x the traffic's `levels` (rows of the
22 nm V/f table): `len(streams) * len(levels)` jobs, submitted
stream-major and level-descending in frequency, and drained; the next grid
is submitted only when the last envelope of this one has come back, and
only if the median grid so far fits the time left.  No arrival schedule,
no think time: the load is 100% of what the service sustains.  The
streams are FIXED and `--seed` is recorded, echoed into each `Job.seed`
and draws nothing, as in `campaign64-dram`.

**What differs from `campaign_closed.py`.**
(1) The swept axis lives in the TRACE, not in a knob: a job's level is
the frequency its tiles ask for by `DVFS_SET` at the start of every
temperature step (`canneal_trace(dvfs_schedule="level-<k>")`), so a grid
holds one trace per (stream, level), every job's knobs are empty, and all
of them are one program class (the class key digests the config text and
buckets the trace's length; a level changes neither).  `_grid_jobs`,
`_serve` and `window` are therefore this file's own - `campaign_closed`'s
build one job per (stream, knob value) from one trace per stream - and the
rest (`_Worker`, `_not_ok`, `_deadline_s`, `end_to_end`, the base of
`judge`) is imported.
(2) Jobs of one batch do NOT end together.  `batch_size` is 4 and a grid
is 6 levels a stream, so batches mix levels (1000/870/750/630 MHz;
500/370 of one stream with 1000/870 of the next; 750/630/500/370): a
batch runs to its slowest job and the faster lanes idle
(`served_lane_idle_share`).  Nothing here sorts jobs by expected length:
that is a later optimisation's, and this cell is what it is judged on.
(3) `correct` holds every envelope to TWO references of
`references/<config>.json`.  (a) The INDEPENDENT one, origin `golden`:
`golden/interpreter.py: run_golden` (serial, one record at a time;
nothing of `engine/`, `sweep/`, `serve/`, `vmap`) on the job's own trace
at the cell's own 256 tiles, kept per (stream, level) as the numbers of
the configuration's `golden_envelope.statistics` - clocks, barrier waits,
misses, invalidations, DRAM reads, total energy by sum and by the worst
tile, and EVERY `energy_pj` component summed.  canneal's swaps race and
golden and engine take different valid orders (BASELINE.md), so the
comparison is an envelope: |served - golden| / golden inside each
statistic's `limit_pct`, which lies between the engine's largest reading
over the 12 jobs and the reading of the configuration's `control` (the
shipped single domain) or, for the dynamic components, of the table's
neighbouring row.  A wrong V squared, energy close or `DVFS_SET` timing
in the ENGINE falls outside; the control is outside in 10 of the 12 jobs.
(b) The stored digest of the job's (stream, level), origin
`cpu-backend`: one SHA-256 over every statistic of `SimResults`, made
on the CPU by plain solo `Simulator.run()` (`make_reference_vf.py`) -
the bit-exact check on the lowering, on `serve/`, `sweep/` and `vmap`.
Besides: `dvfs_counters.errors` 0 (no request rejected) and `energy_pj`
present.  Every limit but the envelope's is 0.

Set-up serves ONE grid of SHORT jobs and the traced slice ONE batch of
them: each stream cut to `trace_temperature_steps` temperature step(s)
and padded with NOPs to the window's trace length - the window's class,
so the window's executable; a batch of the window's jobs is millions of
device events.  The short batch shows the program's operations, not the
window's shares of them: it holds one step's swaps and one barrier, so
the shares that follow the trace's mix (the memory phases against the
core block, the staged landings' share of an iteration, the idle share)
are the slice's own.  The cell reports no `device_idle_share`, and
`batch_host_ms` / `batch_execute_ms` / `power_demux_ms` are the window's.
"""

import statistics as st

from lib import checks, served, target
from lib.clock import now

from probe_golden_hbh import envelope, numbers

from drivers.campaign_closed import (     # noqa: F401  (end_to_end: run.py)
    _Worker, _deadline_s, _not_ok, end_to_end, judge as _base_judge,
)


def job_key(stream: int, mhz: int) -> str:
    """The name of one (stream, level) of the pool, in the reference."""
    return f"s{stream}-f{mhz}"


def level_mhz(level: int) -> int:
    """The frequency the schedule `level-<k>` asks for."""
    from graphite_tpu.trace.benchmarks import DVFS_SCHEDULES

    return DVFS_SCHEDULES[f"level-{level}"](0, 0)


def pool_trace(config: dict, job: "tuple[int, int]"):
    """One job of the pool: the configuration's trace (the traffic's
    generator at its geometry) at the (stream seed, level) `job`."""
    stream, level = job
    trace = {**config["trace"], "kwargs": {
        **config["trace"]["kwargs"], "seed": stream,
        "dvfs_schedule": f"level-{level}"}}
    return target.build_trace({"trace": trace})


def check_generator(config: dict, traffic: dict) -> None:
    """The configuration's canonical trace IS the traffic's generator at
    the pool's first stream and first level."""
    gen = traffic["generator"]
    want = {**gen["kwargs"], "seed": traffic["pool"][0],
            "dvfs_schedule": f"level-{traffic['levels'][0]}",
            "n_tiles": config["config_text"]["tiles"]}
    got = config["trace"]
    if got["function"] != gen["function"] or got["kwargs"] != want:
        raise SystemExit(
            f"benchmark: the configuration's trace {got} is not the "
            f"traffic's generator {gen} at seed {traffic['pool'][0]}, "
            f"level {traffic['levels'][0]}")


def _grid_jobs(ctx, traces: dict) -> list:
    """[(Job, its record)] of one grid over `traces` ({(stream, level):
    TraceBatch}), in the dict's order: stream-major, levels as the
    traffic lists them.  A record is `campaign_closed._serve`'s, and
    names the job's `level` and `mhz` besides."""
    from graphite_tpu.serve.job import Job

    own = ctx.own
    out = []
    for (s, k), trace in traces.items():
        own["n_jobs_made"] += 1
        mhz = level_mhz(k)
        key = job_key(s, mhz)
        out.append((Job(job_id=f"j{own['n_jobs_made']}-{key}",
                        config=own["sim_config"], trace=trace,
                        seed=ctx.seed),
                    {"key": key, "stream": s, "level": k, "mhz": mhz,
                     "t_submit": None, "t_envelope": None,
                     "envelopes": []}))
    return out


def _serve(ctx, traces: dict, deadline_s: float
           ) -> "tuple[list, str | None]":
    """Submit one grid over `traces` and drain it.  ([record per job],
    error or None), as `campaign_closed._serve`."""
    svc = ctx.own["svc"]
    jobs = _grid_jobs(ctx, traces)
    records = {j.job_id: rec for j, rec in jobs}

    def grid():
        for j, rec in jobs:
            rec["t_submit"] = now()
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


def _short_traces(ctx) -> dict:
    """{(stream, level): its trace cut to `trace_temperature_steps` and
    padded with NOPs to the window's trace length}: the service puts them
    in the window's class, so they run the window's executable."""
    from graphite_tpu.sweep.pack import pack_traces

    short = dict(ctx.config)
    short["trace"] = {**short["trace"], "kwargs": {
        **short["trace"]["kwargs"],
        "temperature_steps": ctx.traffic["trace_temperature_steps"]}}
    return {job: pack_traces([pool_trace(short, job)], validate=False,
                             pad_length=full.length).sim(0)
            for job, full in ctx.own["traces"].items()}


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
        own["traces"] = {(s, k): pool_trace(ctx.config, (s, k))
                         for s in own["streams"] for k in t["levels"]}
        # a level changes `aux1` of the DVFS_SET records and nothing
        # else: one count a stream
        own["trace_records"] = {}
        own["trace_instructions"] = {}
        for (s, k), b in own["traces"].items():
            n, m = checks.trace_records(b), checks.trace_instructions(b)
            if own["trace_records"].setdefault(s, n) != n \
                    or own["trace_instructions"].setdefault(s, m) != m:
                raise SystemExit(f"benchmark: stream {s} has another "
                                 f"record count at level {k}")
        own["short_traces"] = _short_traces(ctx)
        first = (own["streams"][0], t["levels"][0])
        # the target as built, held against the configuration's `expect`
        target.check_expectations(ctx.config, Simulator(
            own["sim_config"], own["traces"][first],
            **ctx.config["simulator"]))
    print(f"streams {list(own['streams'])} of the pool {t['pool']} x "
          f"levels {[level_mhz(k) for k in t['levels']]} MHz "
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
    handle = getattr(own["svc"], "resident_program", None)
    own["sim"] = handle() if handle else None
    print(f"first grid (short jobs): {len(records)} jobs in "
          f"{own['first_grid_s']:.3f} s; program handle: "
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
        if now() + st.median(r["wall_s"] for r in ctx.readings) > end:
            break
    ctx.window_s = now() - t0


def judge(ctx, out=print) -> "tuple[bool, int]":
    """(correct, failed jobs): `campaign_closed.judge` (the four
    engine-independent checks, one ok envelope a job, repeats
    bit-identical, the stored `cpu-backend` digest of the job's (stream,
    level)), and of every ok envelope that no DVFS_SET was rejected,
    that the energy is there, and that every statistic of the
    configuration's `golden_envelope` lies inside its limit of the
    stored GOLDEN number of the job's (stream, level)."""
    correct, n_failed = _base_judge(ctx, out)
    env = ctx.config["golden_envelope"]["statistics"]
    gold = ctx.reference["golden"]
    rejected, no_energy, outside = [], [], []
    worst = dict.fromkeys(env, 0.0)
    for j in served.ok_jobs(ctx):
        res = j["envelopes"][0].results
        counters = getattr(res, "dvfs_counters", None)
        if counters is None or int(counters["errors"].sum()):
            rejected.append(j["key"])
        if not getattr(res, "energy_pj", None):
            no_energy.append(j["key"])
        rows = envelope(gold["jobs"][j["key"]], numbers(res, env), env)
        for k, pct, _, _ in rows:
            worst[k] = max(worst[k], pct)
        if any(r[3] for r in rows):
            outside.append(j["key"])
    out(f"check envelopes with a rejected DVFS_SET (or no "
        f"dvfs_counters): {len(rejected)} {sorted(set(rejected))[:4]} "
        f"(limit 0)")
    out(f"check envelopes without energy_pj: {len(no_energy)} "
        f"{sorted(set(no_energy))[:4]} (limit 0)")
    for k, pct in worst.items():
        out(f"check {k} against the golden's, worst envelope: {pct:.4f}% "
            f"(limit {env[k]['limit_pct']}%)")
    out(f"check envelopes outside the golden envelope of their (stream, "
        f"level) ({gold['origin']}, {len(gold['jobs'])} jobs x {len(env)} "
        f"statistics): {len(outside)} {sorted(set(outside))[:4]} (limit 0)")
    bad = set(rejected) | set(no_energy) | set(outside)
    return correct and not bad, max(n_failed, len(bad))


def traced_slice(ctx, tracing) -> None:
    """What the profiler sees: ONE batch through the service - the
    program the window drove, its whole dispatch, nothing compiled - of
    the first stream's SHORT jobs at the first `batch_size` levels (the
    window's first batch, cut short: the module's docstring says what
    the slice cannot show)."""
    own, t = ctx.own, ctx.traffic
    first = [(own["streams"][0], k)
             for k in t["levels"][:t["service"]["batch_size"]]]
    with tracing():
        with ctx.spans.span("run"):
            t0 = now()
            records, err = _serve(
                ctx, {job: own["short_traces"][job] for job in first},
                _deadline_s(ctx))
            wall = now() - t0
    bad = _not_ok(records)
    if err or bad:
        raise SystemExit(f"benchmark: the traced batch failed "
                         f"({bad or ''}): {err}")
    own["traced"] = (f"one batch of the first stream cut to "
                     f"{t['trace_temperature_steps']} temperature step(s) "
                     f"at {[r['mhz'] for r in records]} MHz (not a batch "
                     f"of the window's jobs)")
    # a whole `SweepRunner.run()`: its `fetch` + `results` spans are what
    # every batch of the class pays (B results, whatever the jobs' length)
    own["traced_whole_run"] = True
    iters = max(r["envelopes"][0].n_iterations for r in records)
    print(f"traced slice: {own['traced']}, {len(records)} jobs, {iters} "
          f"engine iterations in {wall:.3f} s under the profiler "
          f"({1e3 * wall / max(1, iters):.3f} ms each)")
