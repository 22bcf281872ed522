"""Share of a served batch's lane-iterations in which the lane's job
advanced nothing, over the window's ok jobs, in percent: for each job the
iterations its own loop counted idle (`JobResult.idle_iterations`: one a
quantum by the loop's nature, and every iteration of a quantum that
another job of the batch still worked in) plus the iterations its lane
sat finished while the batch ran on to its slowest job (the batch's trip
count, the largest `n_iterations` among its envelopes, less the job's own:
a finished job's counters freeze with its carry) / the batch's trip count
x its lanes.  What a batch loses to its slowest level: in a V/f sweep a
370 MHz job is ~2.7x a 1000 MHz one in simulated time, and the batches mix
levels.  Device counters; they repeat exactly for the same jobs.  A padded
slot (a replica whose result is dropped) counts as a lane that is wholly
idle.  None where the envelopes carry no `idle_iterations` (a program
from before PR 41)."""

from lib import served


def read(ctx):
    batches = {}
    for j in served.ok_jobs(ctx):
        env = j["envelopes"][0]
        if getattr(env, "idle_iterations", None) is None:
            return None
        batches.setdefault(env.batch_id, []).append(env)
    caps = {b.batch_id: b.batch_cap for b in served.batch_reports(ctx)}
    waited = finished = lanes = 0
    for bid, envs in batches.items():
        trip = max(int(e.n_iterations) for e in envs)
        cap = caps.get(bid, len(envs))
        waited += sum(int(e.idle_iterations) for e in envs)
        finished += sum(trip - int(e.n_iterations) for e in envs) \
            + trip * (cap - len(envs))
        lanes += trip * cap
    if not lanes:
        return None
    share = (waited + finished) / lanes
    if share > 1.0:
        raise AssertionError(f"served lane idle share {share} over 1")
    print(f"lane-iterations of {len(batches)} batches: {lanes}; idle "
          f"inside a job's own run {waited} ({100.0 * waited / lanes:.2f}"
          f"%), after it finished {finished} "
          f"({100.0 * finished / lanes:.2f}%)")
    return 100.0 * share
