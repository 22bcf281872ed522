"""Taking a profiler trace of a slice, and reducing it in this process:
numbers come back, never the trace."""

import contextlib
import os
import shutil

from . import xplane
from .paths import ROOT

# inside the checkout (git-ignored); emptied before and after each trace
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


@contextlib.contextmanager
def tracing():
    import jax

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # host spans come from TraceAnnotation
    opts.enable_hlo_proto = False       # the 1024-tile program is 240 MB
    jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def reduce_last() -> dict:
    """The reduction of the trace `tracing()` just took; the trace goes."""
    try:
        return xplane.reduce(xplane.load(TRACE_DIR))
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
