"""Seconds in set-up inside backend compiles: the program ledger's
`jax_compile` spans with `cache_hit` false (lib/setup_trace.py).  Not 0 on
a warm cache: a program that compiles faster than the persistent cache's
minimum is compiled again in every process."""

from lib import setup_trace


def read(ctx):
    return setup_trace.seconds(ctx, setup_trace.COMPILED)
