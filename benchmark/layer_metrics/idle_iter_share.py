"""Share of a whole run's engine iterations in which nothing advanced:
`Simulator.last_idle_iterations` over `last_n_iterations`, in percent.
An idle iteration is how the quantum loop learns that a quantum is over
(`engine/step._quantum_loop`): one a quantum where the loop stops at it,
the rest of its block and a whole block more where it runs whole blocks.

Both counters are of the simulator's last call, and the driver keeps no
reading of the first, so it is read here, after the window.  A whole
traced `run()` is such a call; after a traced slice of a host-driven
target one more whole `run()` is made from the initial state, untraced
(as `mem_base_skip_share` does).  A program with no such counter (the
parent's) reads nothing."""


def read(ctx):
    sim = ctx.own.get("sim")
    if getattr(sim, "last_idle_iterations", None) is None:
        return None
    if not ctx.own.get("traced_whole_run"):
        sim.state = ctx.own["initial_state"]
        sim.run()
    share = int(sim.last_idle_iterations) / int(sim.last_n_iterations)
    if share > 1.0:
        raise AssertionError(f"idle iteration share {share} over 1")
    return 100.0 * share
