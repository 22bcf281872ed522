"""Host wall of the median `run()` (through the results fetch) over its
engine iterations, in ms."""

import statistics


def read(ctx):
    rs = [r for r in ctx.readings if r["iterations"]]
    if not rs:
        return None
    wall = statistics.median(r["wall_s"] for r in rs)
    return 1e3 * wall / rs[0]["iterations"]
