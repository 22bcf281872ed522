"""Host-side metrics registry: counters, gauges, fixed-bucket histograms.

The round-9 telemetry rings (`obs/telemetry.py`) instrument the *device*
program; this module instruments the *host* serving path.  The campaign
service (`serve/service.py`) holds one `MetricsRegistry` and replaces the
round-13 ad-hoc counter arithmetic with named instruments: queue dwell,
admission latency, batch-form latency, execute latency, compile time and
split depth become fixed-bucket histograms with deterministic
p50/p90/p99 summaries; the accounting identities (submitted ==
completed + failed, cache hits vs compiles) stay plain counters.

Design points:

 - **Injectable clock.**  The registry (and `obs/trace.py`'s tracer)
   takes a `clock` callable returning monotonic seconds; production uses
   `time.monotonic`, tests a fake clock — so dwell/latency histograms
   are *exact* under test, not approximately-timed.
 - **Deterministic quantiles.**  `Histogram.quantile(q)` returns the
   upper bound of the first bucket whose cumulative count reaches
   `ceil(q * count)` (the Prometheus convention without interpolation),
   and the true max for observations past the last finite bucket.  No
   estimation ambiguity: a hand-built observation set has one right
   answer, which the tests pin.
 - **Two exporters.**  `exposition()` renders the Prometheus text
   format (`# TYPE` comments, `_bucket{le=...}`/`_sum`/`_count` rows);
   `snapshot()` returns the JSON-able dict the CLI summary line embeds.
   `parse_exposition()` round-trips the text back into snapshot form —
   exporter output is CI-checkable, not write-only.
 - **Periodic timeline.**  `sample()` appends a timestamped snapshot
   row to a bounded deque — the service samples after every batch, so
   `tools/report.py --metrics` can render the service's counters as a
   time series, not just a final total.

Everything here is plain host Python: nothing touches a traced program,
so the registry can never perturb device results (the tracing-on/off
bit-equality contract rides on that).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import math
import time

INF = float("inf")

# Default latency buckets (seconds): 1 us .. ~100 s, 4 per decade.
DEFAULT_LATENCY_BUCKETS = tuple(
    round(10.0 ** (e / 4.0), 9) for e in range(-24, 9))
# Default count buckets (splits, attempts, depths): small exact ints.
DEFAULT_COUNT_BUCKETS = (0, 1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64)
# Occupancy / ratio buckets: exact eighths of [0, 1].
RATIO_BUCKETS = tuple(i / 8 for i in range(9))

SUMMARY_QUANTILES = (0.5, 0.9, 0.99)


class MetricsError(ValueError):
    """Registry misuse: name collision across types, unknown metric."""


def bucket_quantile(counts, bounds, q, *, overflow):
    """THE deterministic bucket-quantile definition, shared by host
    histograms (`Histogram.quantile`) and device histograms
    (`obs/hist.Hist.quantile`): the upper bound of the first bucket
    whose cumulative count reaches `ceil(q * total)`; observations in
    the trailing overflow bucket (counts has one more entry than
    bounds) resolve to `overflow`.  Empty -> 0.0."""
    if not 0.0 < q <= 1.0:
        raise MetricsError(f"quantile {q} outside (0, 1]")
    total = sum(counts)
    if total == 0:
        return 0.0
    rank = math.ceil(q * total)
    cum = 0
    for i, b in enumerate(bounds):
        cum += counts[i]
        if cum >= rank:
            return b
    return overflow


@dataclasses.dataclass
class Counter:
    """Monotone cumulative counter (float-valued so wall-clock sums can
    ride the same instrument)."""

    name: str
    help: str = ""
    value: float = 0.0

    def inc(self, n: float = 1.0) -> None:
        if n < 0:
            raise MetricsError(f"counter {self.name!r} cannot decrease")
        self.value += n

    def to_snapshot(self):
        v = self.value
        return int(v) if float(v).is_integer() else v


@dataclasses.dataclass
class Gauge:
    """Point-in-time value (queue depth, cache bytes)."""

    name: str
    help: str = ""
    value: float = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def to_snapshot(self):
        v = self.value
        return int(v) if float(v).is_integer() else v


class Histogram:
    """Fixed-bucket histogram with deterministic quantile summaries.

    `buckets` are finite upper bounds (ascending); an implicit +Inf
    bucket catches the tail.  `observe(v)` increments the first bucket
    with `v <= bound`.  `quantile(q)` (q in (0, 1]) returns the upper
    bound of the first bucket whose cumulative count reaches
    `ceil(q * count)`; observations that landed in the +Inf bucket
    resolve to the true maximum seen (tracked exactly).  An empty
    histogram's quantile is 0.0.
    """

    def __init__(self, name: str, help: str = "",
                 buckets=DEFAULT_LATENCY_BUCKETS):
        bounds = tuple(float(b) for b in buckets)
        if not bounds or any(b2 <= b1 for b1, b2
                             in zip(bounds, bounds[1:])):
            raise MetricsError(
                f"histogram {name!r} needs ascending finite buckets")
        if math.isinf(bounds[-1]):
            raise MetricsError(
                f"histogram {name!r}: +Inf bucket is implicit")
        self.name = name
        self.help = help
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)   # last = +Inf bucket
        self.sum = 0.0
        self.count = 0
        self._min = INF
        self._max = -INF

    def observe(self, v: float) -> None:
        v = float(v)
        for i, b in enumerate(self.bounds):
            if v <= b:
                self.counts[i] += 1
                break
        else:
            self.counts[-1] += 1
        self.sum += v
        self.count += 1
        self._min = min(self._min, v)
        self._max = max(self._max, v)

    @property
    def min(self) -> float:
        return 0.0 if self.count == 0 else self._min

    @property
    def max(self) -> float:
        return 0.0 if self.count == 0 else self._max

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    def quantile(self, q: float) -> float:
        return bucket_quantile(self.counts, self.bounds, q,
                               overflow=self.max)

    def to_snapshot(self) -> dict:
        out = {"count": self.count, "sum": self.sum,
               "min": self.min, "max": self.max, "mean": self.mean}
        for q in SUMMARY_QUANTILES:
            out[f"p{int(q * 100)}"] = self.quantile(q)
        return out


class MetricsRegistry:
    """Named instruments + exporters + a bounded snapshot timeline.

    `counter/gauge/histogram` are get-or-create (idempotent by name);
    re-registering a name as a different type is an error — one
    definition of each rate, by construction.
    """

    def __init__(self, *, clock=time.monotonic, max_timeline: int = 4096):
        self.clock = clock
        self._metrics: "collections.OrderedDict[str, object]" = \
            collections.OrderedDict()
        self.timeline: "collections.deque[dict]" = collections.deque(
            maxlen=int(max_timeline))

    def _get(self, name: str, typ, factory):
        m = self._metrics.get(name)
        if m is None:
            m = factory()
            self._metrics[name] = m
        elif not isinstance(m, typ):
            raise MetricsError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, not {typ.__name__}")
        return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(name, Counter, lambda: Counter(name, help))

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(name, Gauge, lambda: Gauge(name, help))

    def histogram(self, name: str, help: str = "",
                  buckets=DEFAULT_LATENCY_BUCKETS) -> Histogram:
        h = self._get(name, Histogram,
                      lambda: Histogram(name, help, buckets))
        if h.bounds != tuple(float(b) for b in buckets):
            # same failure mode as a cross-type collision: two sites
            # disagreeing on the layout must fail fast, not silently
            # observe into the wrong buckets
            raise MetricsError(
                f"histogram {name!r} already registered with buckets "
                f"{h.bounds}, not {tuple(buckets)}")
        return h

    def __contains__(self, name: str) -> bool:
        return name in self._metrics

    def __getitem__(self, name: str):
        try:
            return self._metrics[name]
        except KeyError:
            raise MetricsError(f"unknown metric {name!r}") from None

    def names(self) -> "list[str]":
        return list(self._metrics)

    # -- exporters -------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able view of every instrument (histograms summarized)."""
        return {name: m.to_snapshot()
                for name, m in self._metrics.items()}

    def sample(self) -> dict:
        """Append one timestamped snapshot row to the timeline."""
        row = {"t_s": float(self.clock()), **self.snapshot()}
        self.timeline.append(row)
        return row

    def timeline_jsonl(self) -> str:
        return "\n".join(json.dumps(row) for row in self.timeline)

    def exposition(self) -> str:
        """Prometheus text exposition of the current state."""
        lines = []
        for name, m in self._metrics.items():
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            if isinstance(m, Counter):
                lines.append(f"# TYPE {name} counter")
                lines.append(f"{name} {_fmt(m.value)}")
            elif isinstance(m, Gauge):
                lines.append(f"# TYPE {name} gauge")
                lines.append(f"{name} {_fmt(m.value)}")
            else:
                lines.append(f"# TYPE {name} histogram")
                cum = 0
                for b, c in zip(m.bounds, m.counts):
                    cum += c
                    lines.append(
                        f'{name}_bucket{{le="{_fmt(b)}"}} {cum}')
                lines.append(
                    f'{name}_bucket{{le="+Inf"}} {m.count}')
                lines.append(f"{name}_sum {_fmt(m.sum)}")
                lines.append(f"{name}_count {m.count}")
        return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    v = float(v)
    return str(int(v)) if v.is_integer() else repr(v)


def parse_exposition(text: str) -> dict:
    """Parse a `MetricsRegistry.exposition()` dump back into
    `{name: {"type": ..., "value"/...}}` — the round-trip check the
    tests (`tests/test_obs_service.py`) run on exporter output.  Histograms
    come back with their per-bucket cumulative counts, sum and count, so a
    registry rebuilt from the text proves the dump lossless (up to the
    +Inf tail's true max, which the text format cannot carry)."""
    out: dict = {}
    types: dict = {}
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(None, 3)
            types[name] = typ
            out[name] = {"type": typ}
            if typ == "histogram":
                out[name].update({"buckets": {}, "sum": 0.0, "count": 0})
            continue
        if line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        name = name.strip()
        value = float(value)
        base, label = name, None
        if "{" in name:
            base, _, rest = name.partition("{")
            label = rest.rstrip("}")
        if base.endswith("_bucket") and label and label.startswith("le="):
            hname = base[: -len("_bucket")]
            le = label[len('le="'):].rstrip('"')
            out[hname]["buckets"][le] = int(value)
        elif base.endswith("_sum") and base[: -len("_sum")] in types:
            out[base[: -len("_sum")]]["sum"] = value
        elif base.endswith("_count") and base[: -len("_count")] in types:
            out[base[: -len("_count")]]["count"] = int(value)
        elif base in types:
            out[base]["value"] = value
        else:
            raise MetricsError(
                f"exposition line names unknown metric: {raw!r}")
    return out
