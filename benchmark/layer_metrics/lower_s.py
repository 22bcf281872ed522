"""Seconds in set-up tracing jitted functions to jaxprs and lowering
them to MLIR: the program ledger's spans `jax_trace` (outermost traces:
helpers traced on the way are inside them) + `jax_lower`
(lib/setup_trace.py)."""

from lib import setup_trace


def read(ctx):
    return setup_trace.seconds(ctx, "jax_trace", "jax_lower")
