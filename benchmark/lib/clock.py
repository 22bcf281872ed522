"""The host clock, and host spans that also land in the profiler's trace."""

import contextlib
import time

now = time.perf_counter


def timed(fn):
    """(result, wall seconds).  `fn` must block on its own result:
    `Simulator.run()` fetches its statistics to the host, `warmup()` ends
    in block_until_ready."""
    t0 = now()
    out = fn()
    return out, now() - t0


class Spans:
    """Named host spans, kept in memory: [(name, start, end)] on the host
    clock.  Each is also a `jax.profiler.TraceAnnotation`, so in a traced
    run the same span lies on the profiler's clock beside the device's
    operations."""

    PREFIX = "bench:"

    def __init__(self):
        self.spans = []

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = now()
        with jax.profiler.TraceAnnotation(self.PREFIX + name):
            try:
                yield
            finally:
                self.spans.append((name, t0, now()))

    def total(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name)
