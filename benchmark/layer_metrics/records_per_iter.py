"""Trace records retired per engine iteration: the trace's own record
count over `last_n_iterations` (a device counter fetched with the
results).  An iteration advances every tile by one record at most, so
this is at most the number of tiles: the share of lanes that did work.
Repeats exactly.  run()-only rate = records_per_iter / wall_per_iter."""


def read(ctx):
    rs = [r for r in ctx.readings if r["iterations"]]
    if not rs:
        return None
    per_iter = rs[0]["records"] / rs[0]["iterations"]
    if per_iter > ctx.own["batch"].n_tiles:
        raise AssertionError(f"{per_iter} records per iteration, over the "
                             f"number of tiles")
    return per_iter
