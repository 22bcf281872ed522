"""Seconds of set-up inside ANY span of the program: the union of its
set-up spans from `import` to the end of `warmup` (a served cell: of its
first grid's batches).  `setup_s` less this is what still lies outside
the program: the interpreter's start, the backend client's, the
benchmark's own loading and its block_until_ready (lib/setup_trace.py)."""

from lib import setup_trace


def read(ctx):
    red = setup_trace.get(ctx)
    return None if red is None else red["traced_s"]
