"""Mean host time a batch of the window spends placing its inputs, in
ms: the program's span `place` inside the service's `build`
(`SweepRunner._batched_inputs`: the [B, ...] states and [B, T, L] traces
onto the device, through one block_until_ready since the service keeps a
tracer).  The part of `batch_host_ms` that is not construction."""

from lib import served


def read(ctx):
    return served.batch_span_ms(ctx, "place")
