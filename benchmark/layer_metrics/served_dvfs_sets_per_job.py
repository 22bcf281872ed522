"""DVFS_SET requests that took effect in a job, the mean over the
window's ok jobs: `JobResult.dvfs_transitions`, the job's DVFS_SET
records less `dvfs_counters["errors"]` summed over its tiles.  A constant
of the traffic - tiles x temperature steps where nothing is dropped
(`canneal-dvfs-256-vfsweep`: 256 tiles at every step) -, held by `correct`
through the stored digests and its own check of `errors`; it says that
the arm the cell exists for was taken in every job, and must never move
under a speed PR.  Printed beside it: each level's summed energy and
completion time over the window's jobs (the sweep's curve).  None where
the envelopes carry no `dvfs_transitions` (a program from before PR 51)."""

from lib import served


def read(ctx):
    jobs = served.ok_jobs(ctx)
    sets = [getattr(j["envelopes"][0], "dvfs_transitions", None)
            for j in jobs]
    if not jobs or any(s is None for s in sets):
        return None
    curve = {}
    for j in jobs:
        env = j["envelopes"][0]
        curve[(j["stream"], env.dvfs_level_mhz)] = (
            env.energy_pj_total, env.results.completion_time_ps)
    for (stream, mhz), (pj, ps) in sorted(
            curve.items(), key=lambda kv: (kv[0][0], -(kv[0][1] or 0))):
        print(f"stream {stream} at {mhz} MHz: energy {pj} pJ, "
              f"completion {ps} ps")
    return sum(sets) / len(sets)
