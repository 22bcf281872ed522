"""Backend compiles in set-up: the program ledger's `jax_compile` spans
not preceded on their thread by a persistent-cache hit (the small programs
on a warm cache, every program cold: what tells a warm `setup_s` from a
first one).  `programs_loaded` is printed beside it
(lib/setup_trace.py)."""

from lib import setup_trace


def read(ctx):
    red = setup_trace.get(ctx)
    return None if red is None else red["count"].get(setup_trace.COMPILED, 0)
