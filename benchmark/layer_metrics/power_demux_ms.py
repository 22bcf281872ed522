"""Mean `power_demux` span of the window's batches, in ms: inside the
runner's `results` (`SweepRunner._outcome`), every job's V/f table rowed
out of the batched fetch and its energy interval closed on the host in
the integers a solo run closes with (`Simulator._power_host`), and the
scalars an envelope carries made of them (`energy_pj_total`,
`dvfs_transitions`, `dvfs_level_mhz`).  A part of `batch_execute_ms`.
None where the program records no such span (a target without power or
DVFS; a program from before PR 51)."""

from lib import served


def read(ctx):
    return served.batch_span_ms(ctx, "power_demux")
