"""Seconds making and placing the initial state in set-up: the program's
spans `init_state` + `place` (a mesh placement, or a sweep's [B, ...]
batch), less the JAX traces, lowerings and compiles inside them, which
`lower_s` and `program_*_s` carry (lib/setup_trace.py: exclusive time).
HOST time where no tracer is given: what the device still owes when
`construct` returns lands in the caller's block_until_ready."""

from lib import setup_trace


def read(ctx):
    return setup_trace.seconds(ctx, "init_state", "place")
