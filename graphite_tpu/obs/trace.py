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
   names the jobs that don't — the span-set-complete check of
   `tests/test_obs_service.py` and `tests/test_campaign_cell.py`.
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
(`tests/test_obs_service.py::TestEndToEnd`).

The same tracer follows `Simulator`'s drive loop (`attach_tracer`): one
`run-<n>` trace per `run()` / `run_chunk()` / `run_streamed()` call with
the spans of `RUN_SPANS` (+ `refill` when streaming), each also a
`jax.profiler.TraceAnnotation("gt:<name>")` so that under a profiler
trace they lie on the device trace's clock — see `RunSpans`.

Set-up accounts for itself the same way (`SETUP_SPANS`, `SetupSpans`):
importing the package, building a trace, constructing a `Simulator` or
a `SweepRunner`, placing state and `warmup()` each record a span where
the work happens, and the program ledger (`ProgramLedger`, ONE
`jax.monitoring` listener) hangs JAX's own trace / lower / compile
events under the innermost of them.  With no tracer given they land in
the process-wide, bounded `SETUP` tracer on `time.perf_counter`: a dozen
clock reads per constructed object, nothing per run.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import json
import threading
import time

import jax

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
# assembles SimResults on the host.  A sweep of a power / DVFS target adds
# `power_demux` inside `results` (`SweepRunner._outcome`: every sim's V/f
# table rowed and its energy closed).
RUN_SPANS = ("run", "dispatch", "wait", "fetch", "results")
# What happens before the first run, each where the work is done:
# `import` (the package, jax with it); `build_trace` (a generator of
# `graphite_tpu/trace/`); `construct` (all of `Simulator.__init__` /
# `SweepRunner.__init__`) over `init_state`, `encode_trace` (the trace to
# device arrays) and `place` (state and traces onto a mesh, or the [B, ...]
# batch of a sweep); `warmup` (all of `Simulator.warmup()`) over
# `first_dispatch` (the call into the runner through block_until_ready).
# The last three are the program ledger's: one per outermost JAX trace,
# per lowering, per backend compile or cache load.
SETUP_SPANS = ("import", "build_trace", "construct", "init_state",
               "encode_trace", "place", "warmup", "first_dispatch",
               "jax_trace", "jax_lower", "jax_compile")

BATCH_TRACE_PREFIX = "batch-"
RUN_TRACE_PREFIX = "run-"
SETUP_TRACE_ID = "setup"
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
        for every submitted job id once the service drained
        (`tests/test_obs_service.py`'s completeness check)."""
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
        with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
            with self.tracer.span(self.trace_id, name, **attrs) as s:
                yield s


# The set-up spans open on this thread, innermost last: [(maker, name)].
# Thread-local because a service's batches are built on a worker thread.
_OPEN = threading.local()


def _open_spans() -> list:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


class SetupSpans(RunSpans):
    """`RunSpans` for what comes before a run (`SETUP_SPANS`; a service's
    batch spans are made by it too, so that they can be parents).

    With the caller's `tracer` a span is a tracer row and a `gt:<name>`
    annotation, under `trace_id`, else under the trace of the set-up span
    already open on this thread in the same tracer (a runner built inside
    a batch's `build` joins `batch-<n>`), else under `setup`.  With none
    it is a row of the process-wide `SETUP` tracer and no annotation, and
    `.on` is False: the caller then adds no device sync of its own.
    `parent` defaults to the innermost set-up span open on this thread,
    which is also where the program ledger hangs JAX's events."""

    def __init__(self, tracer: "Tracer | None" = None,
                 trace_id: "str | None" = None):
        self.on = tracer is not None
        self.tracer = SETUP if tracer is None else tracer
        if trace_id is None:
            outer = _open_spans()
            joins = outer and outer[-1][0].tracer is self.tracer
            trace_id = outer[-1][0].trace_id if joins else SETUP_TRACE_ID
        self.trace_id = str(trace_id)

    @contextlib.contextmanager
    def __call__(self, name: str, **attrs):
        stack = _open_spans()
        if stack:
            attrs.setdefault("parent", stack[-1][1])
        note = (jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
                if self.on else contextlib.nullcontext())
        with note, self.tracer.span(self.trace_id, name, **attrs) as s:
            stack.append((self, name))
            try:
                yield s
            finally:
                PROGRAMS.flush()
                stack.pop()


def constructs(init):
    """Decorator of an `__init__` that takes `tracer=`: the whole call is
    the set-up span `construct` (its self time is configuration and
    sizing), in the caller's tracer where one is given."""

    @functools.wraps(init)
    def construct(self, *args, tracer=None, **kwargs):
        with SetupSpans(tracer)("construct", of=type(self).__name__):
            init(self, *args, tracer=tracer, **kwargs)

    return construct


class _NoSpans:
    """`RunSpans` with no tracer attached: every span is the one shared
    null context (yields None), nothing is created or recorded."""

    on = False
    _null = contextlib.nullcontext()

    def __call__(self, name: str, **attrs):
        return self._null


NO_SPANS = _NoSpans()

# Set-up spans of callers that gave no tracer: always on, the newest 4096
# kept, on the clock a benchmark's host spans use.
SETUP = Tracer(clock=time.perf_counter, max_spans=4096)

# JAX's duration events (jax 0.9.0: `_src/dispatch.py`, `_src/compiler.py`)
# -> what the ledger makes of them.  The retrieval time is recorded only
# beside `/jax/compilation_cache/cache_hits`, inside the backend-compile
# event that then ends: it marks that event as a load.
_JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "hit",
}


class ProgramLedger:
    """What JAX traced, lowered, compiled or loaded in this process, and
    under which set-up span: the answer to "which step recompiled".

    `on_event` is ONE `jax.monitoring` duration listener.  It keeps the
    process counters `programs_traced` / `_lowered` / `_compiled` (a
    backend compile: no cache hit before it on its thread — a miss, or a
    program the persistent cache is not asked about) / `_loaded` (a hit:
    an executable read back) with their seconds, and records each event
    as a span `jax_trace` / `jax_lower` / `jax_compile` (attr `cache_hit`)
    of the event's duration ending now, with the `fun_name` JAX passes
    (all three events carry one), under the innermost set-up span open on
    the thread (`parent`; its tracer and trace), else in `SETUP`.

    A jitted helper traced on the way through an outer trace reports an
    event of its own, inside the outer one's: such traces are folded into
    the outermost (attr `nested` counts them), which is known only when
    the thread's next lowering, compile, span end or `snapshot()` comes,
    so a `jax_trace` span is recorded then."""

    # what happened to a program -> (its count, its seconds)
    _KEYS = {"traced": ("programs_traced", "trace_s"),
             "lowered": ("programs_lowered", "lower_s"),
             "compiled": ("programs_compiled", "compile_s"),
             "loaded": ("programs_loaded", "load_s")}
    COUNTERS = tuple(k for pair in _KEYS.values() for k in pair)

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self.COUNTERS, 0)
        self._thread = threading.local()

    def _pending(self) -> list:
        """This thread's traces not yet known to be outermost:
        [(start on the ledger's clock, span, its tracer)]."""
        try:
            return self._thread.pending
        except AttributeError:
            self._thread.pending = []
            return self._thread.pending

    def _count(self, what: str, secs: float) -> None:
        n, s = self._KEYS[what]
        with self._lock:
            self._counts[n] += 1
            self._counts[s] += secs

    def flush(self) -> None:
        """Record this thread's finished outermost traces."""
        pending = self._pending()
        for _, span, tracer in pending:
            tracer.spans.append(span)
            self._count("traced", span.dur_s)
        pending.clear()

    def on_event(self, event: str, duration: float, **kwargs) -> None:
        kind = _JAX_EVENTS.get(event)
        if kind is None:
            return
        if kind == "hit":
            self._thread.hit = True
            return
        stack = _open_spans()
        maker, parent = stack[-1] if stack else (None, None)
        tracer = SETUP if maker is None else maker.tracer
        end = tracer._now()
        attrs = {"fun_name": str(kwargs.get("fun_name", ""))}
        if parent is not None:
            attrs["parent"] = parent
        span = Span(SETUP_TRACE_ID if maker is None else maker.trace_id,
                    "jax_" + kind, end - duration, end, attrs)
        pending = self._pending()
        if kind == "trace":
            start = self._clock() - duration
            nested = 0
            while pending and pending[-1][0] >= start:
                nested += 1 + pending.pop()[1].attrs["nested"]
            attrs["nested"] = nested
            pending.append((start, span, tracer))
            return
        self.flush()
        if kind == "compile":
            hit = getattr(self._thread, "hit", False)
            self._thread.hit = False
            attrs["cache_hit"] = hit
            self._count("loaded" if hit else "compiled", duration)
        else:
            self._count("lowered", duration)
        tracer.spans.append(span)

    def snapshot(self) -> dict:
        """The counters now."""
        self.flush()
        with self._lock:
            return dict(self._counts)

    def since(self, before: dict) -> dict:
        """What a step cost: the counters now less an earlier snapshot."""
        return {k: v - before[k] for k, v in self.snapshot().items()}


PROGRAMS = ProgramLedger()
jax.monitoring.register_event_duration_secs_listener(PROGRAMS.on_event)


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
    Batch traces (`batch-*`) and the set-up trace (`setup`) are excluded
    — `tools/report.py --spans` renders them separately."""
    by_job: "dict[str, dict]" = {}
    for r in rows:
        tid = r["trace"]
        if tid.startswith(BATCH_TRACE_PREFIX) or tid == SETUP_TRACE_ID:
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


LEDGER_SPANS = ("jax_trace", "jax_lower", "jax_compile")


def _union_us(intervals) -> int:
    """Length of the union of (start, end) intervals."""
    total, edge = 0, None
    for a, b in sorted(intervals):
        if edge is None or a > edge:
            total, edge = total + b - a, b
        elif b > edge:
            total, edge = total + b - edge, b
    return total


def setup_breakdown(rows: "list[dict]") -> "tuple[list[dict], dict]":
    """Fold exported span rows into the set-up table `tools/report.py
    --spans` renders: (one row per top-level set-up span, the program
    ledger's totals).  A top-level span is one of `SETUP_SPANS` with no
    `parent`; its `self_us` is its duration minus the union of the
    set-up spans of its trace that lie inside it.  The ledger's own spans
    outside every set-up span (a compile in the middle of a run) fold
    into one row a name.  Totals: {name: [count, summed us]}, a
    `jax_compile` counted as `programs_loaded` where it was a cache hit,
    else as `programs_compiled`."""
    setup = [r for r in rows if r["span"] in SETUP_SPANS]
    table, orphans, totals = [], {}, {}
    for r in setup:
        end = r["start_us"] + r["dur_us"]
        if r["span"] in LEDGER_SPANS:
            name = r["span"] if r["span"] != "jax_compile" else (
                "programs_loaded" if r.get("cache_hit")
                else "programs_compiled")
            n_us = totals.setdefault(name, [0, 0])
            n_us[0] += 1
            n_us[1] += r["dur_us"]
        if "parent" in r:
            continue
        if r["span"] in LEDGER_SPANS:
            row = orphans.setdefault((r["trace"], r["span"]), {
                "trace": r["trace"], "span": r["span"], "count": 0,
                "start_us": r["start_us"], "dur_us": 0, "self_us": 0})
            row["count"] += 1
            row["dur_us"] += r["dur_us"]
            row["self_us"] += r["dur_us"]
            continue
        inside = [(c["start_us"], c["start_us"] + c["dur_us"])
                  for c in setup if c is not r
                  and c["trace"] == r["trace"]
                  and r["start_us"] <= c["start_us"]
                  and c["start_us"] + c["dur_us"] <= end]
        table.append({"trace": r["trace"], "span": r["span"], "count": 1,
                      "start_us": r["start_us"], "dur_us": r["dur_us"],
                      "self_us": r["dur_us"] - _union_us(inside)})
    table += orphans.values()
    return sorted(table, key=lambda t: t["start_us"]), totals
