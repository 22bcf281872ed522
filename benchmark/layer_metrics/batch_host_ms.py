"""Mean host time a batch of the window spends OUTSIDE `execute`, in ms:
the service's spans `pack` (traces to one layout) + `build` (a fresh
runner and Simulator, inputs placed on the device) + `cache` (program
resolve) + `demux` (envelopes).  What a job pays around its run."""

from lib import served


def read(ctx):
    return served.batch_span_ms(ctx, "pack", "build", "cache", "demux")
