"""Share of the window that lay outside any `run()`: what the driver
does between readings (restoring the initial state, keeping the
results).  The end-to-end rate is over the whole window, so this is the
part of it that `wall_per_iter_ms` does not see; it should stay near 0."""


def read(ctx):
    if not ctx.readings or not ctx.window_s:
        return None
    share = 1.0 - sum(r["wall_s"] for r in ctx.readings) / ctx.window_s
    if not -1e-9 <= share <= 1.0:
        raise AssertionError(f"{share} of the window outside run()")
    return 100.0 * max(share, 0.0)
