"""Real jobs / slots over the window's batches, in percent (the
service's batch log): a padded slot runs a replica whose result is
dropped."""

from lib import served


def read(ctx):
    reports = served.batch_reports(ctx)
    slots = sum(b.batch_cap for b in reports)
    if not slots:
        return None
    share = sum(b.n_jobs for b in reports) / slots
    if share > 1.0:
        raise AssertionError(f"batch occupancy {share} over 1")
    return 100.0 * share
