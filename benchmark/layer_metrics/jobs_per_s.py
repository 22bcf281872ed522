"""Jobs drained ok per second of the whole window (host clock): the
service's throughput in what its users submit."""

from lib import served


def read(ctx):
    jobs = served.ok_jobs(ctx)
    if not jobs or not ctx.window_s:
        return None
    return len(jobs) / ctx.window_s
