"""Share of a whole run's inner blocks whose staged directory writes the
home-activity gate did not flush: `Simulator.last_base_skips["flush"]`
over `last_n_iterations / inner_block`, in percent.  A block is skipped
only if every one of its iterations skipped the base
(`mem_base_skip_share`), so this is at most that share's blocks.

Like `mem_base_skip_share` the counter lives in the simulated state and
counts everything that state has run, so it is read from the state of ONE
whole `run()`: the traced one of a single-region target; else the state a
finished run left (every tile done: in this harness only a whole `run()`
ends a state, `mem_base_skip_share`'s own among them), else one more
`run()` from the initial state, untraced.  A program without the counter
reads nothing."""


import numpy as np


def read(ctx):
    sim = ctx.own.get("sim")
    skips = getattr(sim, "last_base_skips", None)
    if not skips or "flush" not in skips:
        return None
    if not (ctx.own.get("traced_whole_run")
            or bool(np.asarray(sim.state.done).all())):
        sim.state = ctx.own["initial_state"]
        sim.run()
    blocks = int(sim.last_n_iterations) // sim.params.inner_block
    if not blocks:
        return None
    share = sim.last_base_skips["flush"] / blocks
    if share > 1.0:
        raise AssertionError(f"flush skip share {share} over 1")
    return 100.0 * share
