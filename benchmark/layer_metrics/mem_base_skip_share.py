"""Share of a whole run's engine iterations whose consolidated base (the
directory working-set gather and the merged scatter) the home-activity
gate skipped: `Simulator.last_base_skips["base"]` over
`last_n_iterations`, in percent.

The counter is carried in the simulated state and counts everything that
state has run, and the driver keeps no reading of it, so it is read here,
after the window.  A whole traced `run()` leaves the state of exactly one
run.  A traced slice of a host-driven target leaves a part-run state, and
a run driven in chunks places its quanta (and so counts its iterations)
differently from the window's readings: there one more whole `run()` is
made from the initial state, untraced.  A program with no such counter
(the parent's) reads nothing."""


def read(ctx):
    sim = ctx.own.get("sim")
    if getattr(sim, "last_base_skips", None) is None:
        return None
    if not ctx.own.get("traced_whole_run"):
        sim.state = ctx.own["initial_state"]
        sim.run()
    share = sim.last_base_skips["base"] / int(sim.last_n_iterations)
    if share > 1.0:
        raise AssertionError(f"base skip share {share} over 1")
    return 100.0 * share
