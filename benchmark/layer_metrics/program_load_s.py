"""Seconds in set-up reading executables back from the persistent
compile cache: the program ledger's `jax_compile` spans with `cache_hit`
true (lib/setup_trace.py).  0 on a cold cache."""

from lib import setup_trace


def read(ctx):
    return setup_trace.seconds(ctx, setup_trace.LOADED)
