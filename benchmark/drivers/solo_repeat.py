"""solo_repeat: one target, simulated again and again, one at a time.

Set-up builds the configuration's target, places its trace and its
initial state on the device once and calls `warmup()` (compile, or load
from the cache).  A reading restores the initial state (the device arrays
set-up made: `run()` does not consume them) and calls `run()`, through the
results fetch.  Readings go back to back until the window is over; a new
one starts only if the median reading so far fits in the time left.  At
least one is always made, and the one in flight always finishes.

The end-to-end rate is the trace records of the completed readings over
ALL the time of the window, and the window holds nothing but readings:
what lies between them is reported (`outside_run_share`) and is part of
the rate.

The FFT skeleton is a pure function of (tiles, points) and the resident
program closes over its trace, so `--seed` draws nothing here: it is
recorded, and the statistics must equal the reference for every seed.
"""

import statistics as st
import sys
import traceback

from lib import checks, target
from lib.clock import now, timed


def setup(ctx) -> None:
    import jax

    from graphite_tpu.engine.simulator import Simulator

    own = ctx.own
    with ctx.spans.span("build_target"):
        own["sim_config"] = target.build_sim_config(ctx.config)
        own["batch"] = target.build_trace(ctx.config)
        own["trace_instructions"] = checks.trace_instructions(own["batch"])
        own["trace_records"] = checks.trace_records(own["batch"])
    with ctx.spans.span("place_state"):
        sim = Simulator(own["sim_config"], own["batch"],
                        **ctx.config["simulator"])
        target.check_expectations(ctx.config, sim)
        jax.block_until_ready((sim.state, sim.device_trace))
    with ctx.spans.span("warmup"):
        sim.warmup()
    own["sim"] = sim
    own["initial_state"] = sim.state


def _from_the_start(ctx):
    """The target's simulator, back at its initial state."""
    sim = ctx.own["sim"]
    sim.state = ctx.own["initial_state"]
    return sim


def _one_reading(ctx) -> None:
    ctx.attempted += 1
    t0 = now()
    try:
        with ctx.spans.span("run"):
            sim = _from_the_start(ctx)
            res, wall = timed(sim.run)
        skips = sim.last_phase_skips
    except Exception:
        # a deadlock, a mailbox overflow, a failed dispatch: the reading
        # counts as failed and the window goes on
        ctx.raised += 1
        print(f"reading {ctx.attempted} raised:")
        traceback.print_exc(file=sys.stdout)
        return
    ctx.readings.append({
        "t0": t0, "wall_s": wall, "results": res,
        "records": ctx.own["trace_records"],
        "iterations": int(sim.last_n_iterations),
        "n_quanta": int(res.n_quanta),
        "phase_skips": None if skips is None else dict(skips),
    })


def window(ctx) -> None:
    t0 = now()
    end = t0 + ctx.seconds
    while True:
        _one_reading(ctx)
        walls = [r["wall_s"] for r in ctx.readings]
        if ctx.raised > 3 or (not walls and ctx.raised):
            break
        if now() + (st.median(walls) if walls else 0.0) > end:
            break
    ctx.window_s = now() - t0


def end_to_end(ctx) -> dict:
    walls = sorted(r["wall_s"] for r in ctx.readings)
    if not walls:
        return {}
    n = len(walls)
    print(f"readings: {n} completed of {ctx.attempted} started in "
          f"{ctx.window_s:.3f} s, {sum(walls):.3f} s of it inside run(); "
          f"run() min {walls[0]:.6f} median {st.median(walls):.6f} max "
          f"{walls[-1]:.6f} s")
    # a stalled reading counts in the rate; say where in the window it lay
    start = min(r["t0"] for r in ctx.readings)
    slow = sorted(ctx.readings, key=lambda r: -r["wall_s"])[:3]
    print("slowest readings (s into the window: wall s): " + ", ".join(
        f"{r['t0'] - start:.1f}: {r['wall_s']:.4f}" for r in slow))
    p95 = walls[max(0, -(-95 * n // 100) - 1)]     # nearest rank
    return {
        "sim_records_per_s":
            sum(r["records"] for r in ctx.readings) / ctx.window_s,
        "run_wall_p95_s": p95,
    }


def judge(ctx) -> "tuple[bool, int]":
    return checks.judge([r["results"] for r in ctx.readings], ctx.raised,
                        ctx.own["trace_instructions"], ctx.reference)


def traced_slice(ctx, tracing) -> None:
    """What the profiler sees: the program the window drove, never
    another.  A single-region target's `run()` is ONE dispatch of one
    program (a part of it would be a different program, compiled anew):
    the whole `run()` is traced.  A host-driven target (`barrier_host`)
    dispatches the same program with a budget of quanta: `trace_quanta`
    quanta by `run_chunk()` are traced, after `trace_skip_quanta` quanta
    outside the trace (a whole 1024-tile run is millions of events)."""
    sim = _from_the_start(ctx)
    whole = not sim.barrier_host
    if whole:
        with tracing():
            with ctx.spans.span("run"):
                _, wall = timed(sim.run)
        ctx.own["traced"] = "one whole run()"
    else:
        skip, n = ctx.traffic["trace_skip_quanta"], ctx.traffic["trace_quanta"]
        sim.run_chunk(skip)
        with tracing():
            with ctx.spans.span("run"):
                _, wall = timed(lambda: sim.run_chunk(n))
        ctx.own["traced"] = (f"run_chunk({n}) after {skip} quanta "
                             f"(not a whole run)")
    ctx.own["traced_whole_run"] = whole
    iters = int(sim.last_n_iterations)
    print(f"traced slice: {ctx.own['traced']}, {iters} engine iterations "
          f"in {wall:.3f} s under the profiler "
          f"({1e3 * wall / max(1, iters):.3f} ms each)")
