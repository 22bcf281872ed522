"""Seconds importing the package, jax with it: the program's set-up span
`import` (`graphite_tpu/__init__.py`, first line to last), from
lib/setup_trace.py."""

from lib import setup_trace


def read(ctx):
    return setup_trace.seconds(ctx, "import")
