"""Share of the memory engine's phases that its gates skipped:
`sum(last_phase_skips) / (phases x last_n_iterations)`, in percent."""


def read(ctx):
    rs = [r for r in ctx.readings if r["phase_skips"] and r["iterations"]]
    if not rs:
        return None
    skips = rs[0]["phase_skips"]
    share = sum(skips.values()) / (len(skips) * rs[0]["iterations"])
    if share > 1.0:
        raise AssertionError(f"phase skip share {share} over 1")
    return 100.0 * share
