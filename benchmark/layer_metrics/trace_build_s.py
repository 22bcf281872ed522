"""Seconds making the trace in set-up: the program's spans `build_trace`
(a generator of `graphite_tpu/trace/`, on the host) + `encode_trace` (the
batch to device arrays), less what JAX compiled inside them
(lib/setup_trace.py: exclusive time)."""

from lib import setup_trace


def read(ctx):
    return setup_trace.seconds(ctx, "build_trace", "encode_trace")
