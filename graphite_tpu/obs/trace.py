"""Job-lifecycle span tracing for the campaign service.

One `Span` is a named host-side interval with attributes; one *trace*
is the set of spans sharing a `trace_id` — a job id for job lifecycles
(submit → validate → admit/reject → queue dwell → execute → emit), or
`batch-<n>` for batch execution spans (class key, capacity, occupancy,
cache hit/miss, compile time).  Together they answer "where did this
job's wall time go" with one artifact: host phases from the spans,
device time from the telemetry timeline the emit span references.

Contracts:

 - **Injectable clock** (same as `obs/metrics.py`): the tracer reads
   monotonic seconds from a caller-supplied callable, so tests drive a
   fake clock and assert exact span durations.
 - **Terminal completeness.**  Every job trace must end in exactly one
   terminal span (`emit`, `reject`, or `failed`).  `missing_terminal()`
   names the jobs that don't — the regress rung's span-set-complete
   check.
 - **JSON-lines export.**  `export_jsonl()` writes one span per line
   (`tools/serve.py --trace-out`); `load_jsonl()` reads it back for
   `tools/report.py --spans`.  Timestamps export as integer
   microseconds relative to the tracer's epoch (the first clock read),
   so files are stable and diffable under a fake clock.
 - **Bounded retention**: the span deque keeps the newest `max_spans`
   (a persistent service must not grow without bound); the export
   carries whatever is retained.

Tracing is strictly host-side observability: no traced program ever
sees the tracer, so serve results are bit-equal with tracing on or off
(regress-pinned).

The same tracer follows `Simulator`'s drive loop (`attach_tracer`): one
`run-<n>` trace per `run()` / `run_chunk()` / `run_streamed()` call with
the spans of `RUN_SPANS` (+ `refill` when streaming), each also a
`jax.profiler.TraceAnnotation("gt:<name>")` so that under a profiler
trace they lie on the device trace's clock — see `RunSpans`.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import time

# Span names in job-lifecycle order (report tables render this order).
JOB_SPANS = ("submit", "validate", "admit", "queue", "execute", "job",
             "emit")
# What a batch pays around its run, in order (`serve/service.py`): traces
# packed to one layout; a fresh runner + Simulator built and its inputs
# placed; the compiled-program cache resolved; the program run (enclosing
# the runner's RUN_SPANS); results demuxed into envelopes.
BATCH_SPANS = ("pack", "build", "cache", "execute", "demux")
# Terminal span names: every submitted job's trace ends in exactly one.
TERMINAL_SPANS = ("emit", "reject", "failed")
# Simulator drive-loop spans, in the order one dispatch goes through them:
# `run` encloses the call; `dispatch` is the call into the compiled runner
# until it returns its futures; `wait` blocks on the control scalars (made
# only when a tracer is attached); `fetch` is the device_get; `results`
# assembles SimResults on the host.
RUN_SPANS = ("run", "dispatch", "wait", "fetch", "results")

BATCH_TRACE_PREFIX = "batch-"
RUN_TRACE_PREFIX = "run-"
ANNOTATION_PREFIX = "gt:"


@dataclasses.dataclass
class Span:
    """One named host-side interval within a trace."""

    trace_id: str
    name: str
    t_start: float               # tracer-clock seconds
    t_end: "float | None" = None
    attrs: dict = dataclasses.field(default_factory=dict)

    @property
    def dur_s(self) -> float:
        return 0.0 if self.t_end is None else self.t_end - self.t_start

    @property
    def open(self) -> bool:
        return self.t_end is None


class Tracer:
    """Collects spans against an injectable monotonic clock."""

    def __init__(self, *, clock=time.monotonic, max_spans: int = 65536):
        self.clock = clock
        self.spans: "collections.deque[Span]" = collections.deque(
            maxlen=int(max_spans))
        self._epoch: "float | None" = None
        self._n_run_traces = 0

    def new_run_id(self) -> str:
        """The next `run-<n>` trace id (n counts this tracer's runs)."""
        tid = f"{RUN_TRACE_PREFIX}{self._n_run_traces}"
        self._n_run_traces += 1
        return tid

    def _now(self) -> float:
        t = float(self.clock())
        if self._epoch is None:
            self._epoch = t
        return t

    # -- recording -------------------------------------------------------

    def begin(self, trace_id: str, name: str, **attrs) -> Span:
        """Open a span (not yet retained — `end()` appends it)."""
        return Span(trace_id=str(trace_id), name=str(name),
                    t_start=self._now(), attrs=dict(attrs))

    def end(self, span: Span, **attrs) -> Span:
        span.t_end = self._now()
        span.attrs.update(attrs)
        self.spans.append(span)
        return span

    @contextlib.contextmanager
    def span(self, trace_id: str, name: str, **attrs):
        s = self.begin(trace_id, name, **attrs)
        try:
            yield s
        finally:
            self.end(s)

    def event(self, trace_id: str, name: str, **attrs) -> Span:
        """Zero-duration span (backpressure, retry, ...)."""
        return self.end(self.begin(trace_id, name, **attrs))

    def record(self, trace_id: str, name: str, t_start: float,
               t_end: float, **attrs) -> Span:
        """Append a span whose interval was measured elsewhere (e.g.
        queue dwell, reconstructed from the enqueue timestamp when the
        batch forms)."""
        self._now()   # pin the epoch even if this is the first record
        s = Span(trace_id=str(trace_id), name=str(name),
                 t_start=float(t_start), t_end=float(t_end),
                 attrs=dict(attrs))
        self.spans.append(s)
        return s

    # -- queries ---------------------------------------------------------

    def trace(self, trace_id: str) -> "list[Span]":
        return [s for s in self.spans if s.trace_id == str(trace_id)]

    def trace_ids(self) -> "list[str]":
        seen: "dict[str, None]" = {}
        for s in self.spans:
            seen.setdefault(s.trace_id, None)
        return list(seen)

    def missing_terminal(self, trace_ids) -> "list[str]":
        """The given traces that lack a terminal span — must be empty
        for every submitted job id once the service drained (the
        regress rung-9 completeness check)."""
        done = {s.trace_id for s in self.spans
                if s.name in TERMINAL_SPANS}
        return [str(t) for t in trace_ids if str(t) not in done]

    # -- export ----------------------------------------------------------

    def to_rows(self) -> "list[dict]":
        epoch = self._epoch or 0.0
        rows = []
        for s in self.spans:
            rows.append({
                "trace": s.trace_id,
                "span": s.name,
                "start_us": int(round((s.t_start - epoch) * 1e6)),
                "dur_us": int(round(s.dur_s * 1e6)),
                **s.attrs,
            })
        return rows

    def export_jsonl(self, path_or_file) -> int:
        """Write one JSON line per retained span; returns the count."""
        rows = self.to_rows()
        if hasattr(path_or_file, "write"):
            for row in rows:
                path_or_file.write(json.dumps(row) + "\n")
        else:
            with open(path_or_file, "w") as fh:
                for row in rows:
                    fh.write(json.dumps(row) + "\n")
        return len(rows)


class RunSpans:
    """The spans of ONE drive-loop call: `spans(name, parent=..., **attrs)`
    is a context manager that records a `Tracer` span under the call's
    trace id and enters `jax.profiler.TraceAnnotation("gt:" + name)`.
    `attrs["parent"]` names the span that caused this one.  `.on` tells
    the drive loop whether to make the tracer-only `wait`."""

    on = True

    def __init__(self, tracer: Tracer, trace_id: "str | None" = None):
        self.tracer = tracer
        self.trace_id = (tracer.new_run_id() if trace_id is None
                         else str(trace_id))

    @contextlib.contextmanager
    def __call__(self, name: str, **attrs):
        import jax

        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            with self.tracer.span(self.trace_id, name, **attrs) as s:
                yield s


class _NoSpans:
    """`RunSpans` with no tracer attached: every span is the one shared
    null context (yields None), nothing is created or recorded."""

    on = False
    _null = contextlib.nullcontext()

    def __call__(self, name: str, **attrs):
        return self._null


NO_SPANS = _NoSpans()


def load_jsonl(path_or_file) -> "list[dict]":
    """Read spans back from a `export_jsonl` file (report input)."""
    if hasattr(path_or_file, "read"):
        lines = path_or_file.read().splitlines()
    else:
        with open(path_or_file) as fh:
            lines = fh.read().splitlines()
    rows = []
    for ln in lines:
        ln = ln.strip()
        if ln:
            rows.append(json.loads(ln))
    return rows


def job_breakdown(rows: "list[dict]") -> "list[dict]":
    """Fold exported span rows into one latency-breakdown row per job
    trace: `{job, <span>_us..., total_us, status, **terminal attrs}`.
    Batch traces (`batch-*`) are excluded — `tools/report.py --spans`
    renders them separately."""
    by_job: "dict[str, dict]" = {}
    for r in rows:
        tid = r["trace"]
        if tid.startswith(BATCH_TRACE_PREFIX):
            continue
        row = by_job.setdefault(tid, {"job": tid, "status": None})
        name = r["span"]
        # repeated spans (retries) accumulate duration
        row[name + "_us"] = row.get(name + "_us", 0) + r["dur_us"]
        if name in TERMINAL_SPANS:
            row["status"] = name
            for k, v in r.items():
                if k not in ("trace", "span", "start_us", "dur_us"):
                    row.setdefault(k, v)
    for row in by_job.values():
        if row["job"].startswith(RUN_TRACE_PREFIX):
            # a drive-loop trace: `run` encloses its other spans
            row["total_us"] = row.get("run_us", 0)
            continue
        if "job_us" in row:
            # `job` is submit -> envelope: it encloses every other span
            row["total_us"] = row["job_us"]
            continue
        row["total_us"] = sum(v for k, v in row.items()
                              if isinstance(v, int) and k.endswith("_us"))
    return list(by_job.values())
