"""Share of the memory engine's phases that the served program's gates
skipped over the window's jobs: summed `JobResult.phase_skips` of the
ok envelopes / (phases x their `n_iterations`), in percent.  Under the
batch a gate's predicate is OR-ed over the sims, so a job counts a skip
only where no job of its batch had work for the phase.  A device
counter; None where the envelope carries no such counter (a program
from before PR 36)."""

from lib import served


def read(ctx):
    skipped = phases = 0
    for j in served.ok_jobs(ctx):
        env = j["envelopes"][0]
        skips = getattr(env, "phase_skips", None)
        if not skips or not env.n_iterations:
            continue
        skipped += sum(skips.values())
        phases += len(skips) * int(env.n_iterations)
    if not phases:
        return None
    share = skipped / phases
    if share > 1.0:
        raise AssertionError(f"served phase skip share {share} over 1")
    return 100.0 * share
