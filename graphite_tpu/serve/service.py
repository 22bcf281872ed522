"""The campaign service: admission-controlled job batching over a
fingerprint-keyed compiled-program cache.

This is the piece that *serves* every amortization primitive the repo
already has: jobs (`serve/job.py`) are validated up front, bin-packed
into same-program batches by the admission controller
(`serve/admission.py` — `residency_breakdown` arithmetic against a
per-device `hbm_budget_bytes`), executed as vmapped `SweepRunner`
campaigns through the LRU compiled-program cache (`serve/cache.py` —
keyed by program class, proven by `analysis/identity` fingerprints
resolved through an `analysis/registry`-style record set), and demuxed
back into per-job `SimResults` + telemetry envelopes as each batch
completes.

Graceful degradation is structural, not best-effort:

 - a job that can never fit the budget is rejected at submit with the
   itemized breakdown; a full queue raises backpressure;
 - batches are padded to the class's FIXED capacity (replicating the
   first job — semantically a re-run, so padding adds no new failure
   modes) so every batch of a class reuses ONE compiled shape; the
   padded tail is masked out of the result stream;
 - a failed batch (deadlock, mailbox overflow, max_quanta timeout)
   SPLITS in half and re-enqueues at the front of its class FIFO —
   halving isolates the offending job in log2(B) steps instead of
   poisoning the queue; a job that fails ALONE is retried up to
   `max_attempts` and then reported as a failed envelope.  Every
   failure increments each member's attempt counter, so the
   split/retry recursion provably terminates.

The bit-exact sequential path (`Simulator.run`) remains the equivalence
oracle: `tests/test_serve.py::TestServiceEndToEnd` replays a mixed-
geometry job set both ways and requires identical results + telemetry.

Observability (round 14) is built in, not bolted on: every rate the
service reports is ONE instrument in an `obs.MetricsRegistry` (queue
dwell, admission/batch-form/execute latency, compile time, split depth
and batch occupancy are fixed-bucket histograms; the accounting
identities are counters), `counters` is a compatibility view over that
registry, and — when constructed with `tracing=` — every job gets a
lifecycle span trace (submit → validate → admit/reject → queue dwell →
execute → emit/failed, and `job`: submit → envelope) and every batch an
execution span carrying the class, capacity, occupancy, cache hit,
compile time and residency, over the spans of what a batch pays around
its run: `pack` (traces to one [B, T, L] layout), `build` (a fresh
`SweepRunner` + `Simulator`, the inputs placed on the device), `cache`,
`execute` (enclosing the runner's own `run` > `dispatch` > `wait` >
`fetch` > `results`) and `demux`, each also a `gt:<name>`
TraceAnnotation so that under a profiler they lie on the device's
clock.
Both ride an injectable monotonic clock (`clock=`) so tests pin exact
latencies; neither ever touches a traced program, so serve results are
bit-equal with tracing on or off (`tests/test_obs_service.py::
TestEndToEnd`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

from graphite_tpu.obs.metrics import (
    DEFAULT_COUNT_BUCKETS, MetricsRegistry, RATIO_BUCKETS,
)
from graphite_tpu.obs.trace import NO_SPANS, PROGRAMS, SetupSpans, Tracer
from graphite_tpu.serve.admission import AdmissionController, JobClass, \
    Pending, QueueFullError
from graphite_tpu.serve.cache import CacheEntry, ProgramCache, \
    ProgramCacheError, ResidentProgram
from graphite_tpu.serve.job import (
    Job, JobResult, STATUS_FAILED, STATUS_OK,
)


@dataclasses.dataclass
class BatchReport:
    """One executed (or failed) batch's bookkeeping row."""

    batch_id: int
    class_name: str
    n_tiles: int
    job_ids: "list[str]"
    n_jobs: int                # real jobs (pre-padding)
    batch_cap: int             # the padded B the program ran at
    occupancy: float           # n_jobs / batch_cap
    residency_total: int       # the admitted layout's residency bill
    cache_hit: bool
    ok: bool
    wall_s: float
    error: "str | None" = None
    # round 17: the in-memory miss was served by the persistent AOT
    # program store (deserialize, not compile)
    store_hit: bool = False
    # round 18: the device layout the batch ran under ("solo",
    # "1d-batch(d=N)", "2d(b=DB,t=DT)", ...)
    layout: str = "solo"
    # the program ledger's delta over the batch (obs/trace.py:
    # ProgramLedger.COUNTERS): what JAX traced, lowered, compiled or
    # loaded for it — "which step recompiled"; None on a batch that
    # failed before its run ended
    programs: "dict | None" = None


class CampaignService:
    """Persistent front end: submit jobs, drain result envelopes.

    `hbm_budget_bytes`: per-device admission budget (0 = off);
    `batch_size`: max sims per campaign batch (the class capacity is
    `min(batch_size, budget // per_sim_bytes)`); `n_devices` (round
    18): devices admission may bin-pack a too-big-for-one-device sim
    across — such jobs are served under the 2D batch x tile mesh
    layout (per-device tile blocks proven <= the budget) instead of
    rejected; "auto" reads the visible device count, the default 1
    keeps round-13 admission exactly; `cache_bytes`: program
    cache budget for byte-accounted LRU eviction (0 = unbounded);
    `max_pending`: queue depth before submit raises backpressure;
    `max_attempts`: per-job failure budget across splits/retries;
    `max_quanta`: the batch programs' quantum bound (part of the
    compiled program, hence of the cache key); `verify_hits`: re-lower
    every cache hit and re-prove fingerprint equality (a retrace, never
    a recompile — the belt-and-braces mode `tests/test_serve.py` runs);
    `validate`: run `trace/validate.py` on every submitted trace;
    `max_history`: newest result envelopes / batch reports retained on
    the service (`results` / `batch_log`) — streaming consumers use
    `drain()`; counters stay exact regardless.

    `store` (round 17): a `store.ProgramStore` (or a directory path)
    layered UNDER the in-memory cache as its miss/fill backend — an
    in-memory miss deserializes the fingerprint-keyed on-disk
    executable instead of compiling (store hit: retrace + deserialize,
    zero compiles), and a fresh compile is serialized back (store
    fill), so a fleet of processes sharing one store dir compiles each
    program class once per FLEET.  `warm_start()` pre-deserializes
    compatible entries at startup.  `max_dwell_s` (round 17): let an
    under-full batch wait up to this long for its class to fill before
    forming — the latency/occupancy dial the round-14
    `queue_dwell_seconds` histogram measures; 0 (default) keeps the
    wait-for-nothing scheduler bit-identically.

    Observability: `metrics` (an `obs.MetricsRegistry`) is always live
    — it IS the service bookkeeping, not a copy of it; `tracing=True`
    (or a caller-owned `obs.Tracer`) records job-lifecycle + batch
    spans, exported via `export_spans()` / `tools/serve.py
    --trace-out`; `clock` injects the monotonic time source both read
    (default `time.monotonic` — tests pass a fake clock and get exact
    dwell/latency histograms).
    """

    def __init__(self, *, hbm_budget_bytes: int = 0, batch_size: int = 4,
                 cache_bytes: int = 0, max_pending: int = 1024,
                 max_attempts: int = 3, max_quanta: int = 1_000_000,
                 verify_hits: bool = False, validate: bool = True,
                 shard_batch: "bool | None" = False,
                 n_devices: "int | str" = 1,
                 max_history: int = 4096,
                 tracing: "bool | Tracer" = False,
                 clock=None,
                 store: "object | str | None" = None,
                 max_dwell_s: float = 0.0):
        import collections

        # round 18: devices the admission controller may bin-pack a
        # too-big-for-one-device sim across (the 2D batch x tile
        # layout).  "auto" reads the visible device count; the default
        # 1 keeps round-13 single-device admission bit-identically.
        import jax

        if n_devices == "auto":
            n_devices = len(jax.devices())
        self.n_devices = max(int(n_devices), 1)
        if self.n_devices > len(jax.devices()):
            # fail at construction, not mid-drain: a 2D class planned
            # for more devices than exist would otherwise crash the
            # serve loop at execute (mesh construction), stranding
            # admitted jobs without terminal envelopes
            raise ValueError(
                f"n_devices={self.n_devices} exceeds the "
                f"{len(jax.devices())} visible device(s) — the service "
                "executes locally; force more host devices with "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                "on CPU, or pass 'auto'")
        self.admission = AdmissionController(
            hbm_budget_bytes=hbm_budget_bytes, batch_size=batch_size,
            max_pending=max_pending, n_devices=self.n_devices)
        self.cache = ProgramCache(cache_bytes)
        self.registry: "dict[str, object]" = {}   # name -> ProgramRecord
        self.hbm_budget_bytes = int(hbm_budget_bytes)
        self.max_attempts = int(max_attempts)
        self.max_quanta = int(max_quanta)
        self.verify_hits = bool(verify_hits)
        self.validate = bool(validate)
        self.shard_batch = shard_batch
        if isinstance(tracing, Tracer):
            # ONE timebase: reconstructed spans (queue dwell, execute)
            # are recorded with service-clock timestamps, so a caller-
            # owned tracer must share it.  An explicit `clock=` is
            # adopted by both; otherwise the service adopts the
            # tracer's clock.
            self.tracer: "Tracer | None" = tracing
            if clock is not None:
                self._clock = clock
                tracing.clock = clock
            else:
                self._clock = tracing.clock
        else:
            self._clock = clock if clock is not None else time.monotonic
            self.tracer = Tracer(clock=self._clock) if tracing else None
        # retention is BOUNDED (`max_history` newest entries): envelopes
        # stream out through drain(); keeping every SimResults +
        # BatchReport forever would grow a persistent service without
        # bound.  Counters stay exact over all time (the registry's
        # instruments are running sums, and the metrics timeline /
        # tracer spans are bounded deques of their own).
        self.batch_log: "collections.deque[BatchReport]" = \
            collections.deque(maxlen=int(max_history))
        self._completed: "collections.deque[JobResult]" = \
            collections.deque(maxlen=int(max_history))
        self._next_batch_id = 0
        self._last_residency = 0
        self._last_cache_hit = False
        self._last_compile_s = 0.0
        self._last_layout = "solo"
        self._last_programs: "dict | None" = None
        # the cache entry the last batch dispatched (`resident_program`)
        self._last_entry: "CacheEntry | None" = None
        # persistent AOT program store (round 17): the in-memory
        # cache's miss/fill backend — a fleet of service processes
        # sharing one store dir compiles each class once per FLEET
        if isinstance(store, str):
            from graphite_tpu.store import ProgramStore

            store = ProgramStore(store)
        self.store = store
        # fingerprint-keyed staging area `warm_start()` fills from
        # disk: (fingerprint, B) -> (executable, manifest, deserialize_s)
        self._warm: dict = {}
        self._last_store_hit = False
        self._last_deserialize_s = 0.0
        # latency-aware batching: an under-full batch may wait up to
        # `max_dwell_s` for the class to fill before forming (0 = the
        # round-13 wait-for-nothing scheduler, bit-identically);
        # `_dwell_wait_s` reports the remaining wait after a step that
        # chose to hold
        self.max_dwell_s = float(max_dwell_s)
        self._dwell_wait_s = 0.0
        self.metrics = MetricsRegistry(clock=self._clock,
                                       max_timeline=int(max_history))
        self._init_metrics()

    def _init_metrics(self) -> None:
        """Register every instrument up front (one definition of each
        rate; the exposition shows zeros instead of omitting series)."""
        m = self.metrics
        self._m = {
            "submitted": m.counter(
                "jobs_submitted_total", "jobs accepted into the queue"),
            "completed": m.counter(
                "jobs_completed_total", "ok envelopes emitted"),
            "failed": m.counter(
                "jobs_failed_total", "failed envelopes emitted"),
            "rejected": m.counter(
                "jobs_rejected_total", "jobs refused at submit"),
            "backpressure": m.counter(
                "backpressure_total", "submits refused by a full queue"),
            "batches": m.counter("batches_total", "batches executed"),
            "padded_slots": m.counter(
                "padded_slots_total", "batch slots filled with a replica "
                "of the batch's first job (capacity - real jobs)"),
            "splits": m.counter(
                "splits_total", "failed batches split in half"),
            "retries": m.counter(
                "retries_total", "batch/job re-executions"),
            "cache_hits": m.counter(
                "cache_hits_total", "program-cache hits"),
            "compiles": m.counter(
                "compiles_total", "program-cache miss compiles"),
            "execute_wall": m.counter(
                "execute_wall_seconds", "wall seconds inside batch "
                "execution (jobs_per_s denominator)"),
            "store_hits": m.counter(
                "store_hits_total", "program-store hits (executable "
                "deserialized instead of compiled)"),
            "store_misses": m.counter(
                "store_misses_total", "program-store misses (store "
                "attached, fresh compile paid)"),
            "store_fills": m.counter(
                "store_fills_total", "executables serialized into the "
                "program store"),
            "store_fill_errors": m.counter(
                "store_fill_errors_total", "store writes that failed "
                "(disk/serialization; the batch still served)"),
            "store_integrity": m.counter(
                "store_integrity_total", "store entries quarantined at "
                "load (checksum/truncation/version/fingerprint/"
                "deserialize)"),
        }
        self._g = {
            "queue_depth": m.gauge("queue_depth", "pending jobs"),
            "cache_entries": m.gauge("cache_entries",
                                     "compiled programs cached"),
            "cache_bytes": m.gauge("cache_bytes",
                                   "program-cache residency bytes"),
        }
        self._h = {
            "admission": m.histogram(
                "admission_seconds",
                "submit latency (validate + classify + enqueue)"),
            "dwell": m.histogram(
                "queue_dwell_seconds",
                "enqueue to batch-form wait per job"),
            "batch_form": m.histogram(
                "batch_form_seconds", "queue pop + batch assembly"),
            "execute": m.histogram(
                "execute_seconds", "batch execution wall time"),
            "compile": m.histogram(
                "compile_seconds", "program lower+compile on cache miss"),
            "occupancy": m.histogram(
                "batch_occupancy", "real jobs / batch capacity",
                buckets=RATIO_BUCKETS),
            "split_depth": m.histogram(
                "split_depth", "attempts consumed per terminal job",
                buckets=DEFAULT_COUNT_BUCKETS),
            "store_deserialize": m.histogram(
                "store_deserialize_seconds",
                "store-hit payload load+deserialize time"),
            "store_fill": m.histogram(
                "store_fill_seconds",
                "store-miss serialize+write time"),
        }

    def _span(self, trace_id: str, name: str, **attrs):
        if self.tracer is None:
            return contextlib.nullcontext(None)
        return self.tracer.span(trace_id, name, **attrs)

    def _batch_spans(self, batch_id: int):
        """The span maker of one batch (`SetupSpans` under `batch-<n>`:
        tracer row + `gt:<name>` annotation, and a parent to the set-up
        spans and the program ledger's inside it), or the null one."""
        if self.tracer is None:
            return NO_SPANS
        return SetupSpans(self.tracer, f"batch-{batch_id}")

    def resident_program(self) -> "ResidentProgram | None":
        """A handle to the cached program the last batch dispatched, or
        None before any batch has run."""
        if self._last_entry is None:
            return None
        return ResidentProgram(self._last_entry)

    def export_spans(self, path_or_file) -> int:
        """Write the retained spans as JSON-lines (the `--trace-out`
        artifact); returns the span count, 0 when tracing is off."""
        if self.tracer is None:
            return 0
        return self.tracer.export_jsonl(path_or_file)

    # -- submission ------------------------------------------------------

    def submit(self, job: Job) -> int:
        """Validate and queue one job; returns its submission sequence
        number.  Raises `TraceValidationError`/`ValueError` on a
        malformed job, `analysis.cost.ResidencyBudgetError` (with
        `.breakdown`) on a job that can never fit, `QueueFullError`
        under backpressure."""
        t0 = self._clock()
        jid = job.job_id
        try:
            with self._span(jid, "submit"):
                with self._span(jid, "validate"):
                    job.validate(validate_trace=self.validate)
                with self._span(jid, "admit"):
                    cls, pending = self.admission.admit(job)
        except QueueFullError:
            # backpressure is NOT a rejection: the job is fine, the
            # queue is full — the caller drains and resubmits, and the
            # later successful submit must keep the accounting identity
            # submitted == completed + failed (+ rejected never counts
            # a job that eventually ran)
            self._m["backpressure"].inc()
            if self.tracer is not None:
                self.tracer.event(jid, "backpressure")
            raise
        except Exception as e:
            self._m["rejected"].inc()
            if self.tracer is not None:
                # terminal span: a rejected job's lifecycle ends here
                self.tracer.event(
                    jid, "reject", error=f"{type(e).__name__}: {e}")
            raise
        now = self._clock()
        self._h["admission"].observe(now - t0)
        pending.enqueue_ts = now
        pending.submit_ts = t0
        self._m["submitted"].inc()
        self._g["queue_depth"].set(self.admission.queue_depth)
        return pending.seq

    @property
    def queue_depth(self) -> int:
        return self.admission.queue_depth

    # -- scheduling ------------------------------------------------------

    def step(self, *, force: bool = False) -> "list[JobResult]":
        """Form and run ONE batch (the oldest-head class); returns the
        envelopes it completed (empty when a failed batch split and
        re-enqueued, when the queue is idle, or when the dwell policy
        chose to wait).

        With `max_dwell_s > 0` an UNDER-FULL batch holds until its
        head job has dwelled `max_dwell_s` (trading latency for
        occupancy the way inference servers do — the trade the
        round-14 `queue_dwell_seconds` x `batch_occupancy` instruments
        measure); a full batch, or a requeued split/retry batch, never
        waits.  `force=True` overrides the hold (the drain-to-idle
        paths use it so a waiting scheduler cannot spin)."""
        t0 = self._clock()
        self._dwell_wait_s = 0.0
        from_cls = None
        if self.max_dwell_s > 0 and not force:
            peek = self.admission.peek_batch()
            if peek is not None:
                cls, n, head, preformed = peek
                if (not preformed and n < cls.batch_cap
                        and head.enqueue_ts is not None):
                    dwelled = t0 - head.enqueue_ts
                    if dwelled < self.max_dwell_s:
                        # the oldest head is held — but a FULL batch of
                        # another class never waits: run it now, the
                        # held head keeps aging for free
                        from_cls = self.admission.full_class()
                        if from_cls is None:
                            self._dwell_wait_s = \
                                self.max_dwell_s - dwelled
                            return []
        nxt = self.admission.next_batch(from_cls)
        if nxt is None:
            return []
        cls, pendings = nxt
        self._h["batch_form"].observe(self._clock() - t0)
        return self._run_batch(cls, pendings)

    def drain(self, *, force: bool = False):
        """Generator: run batches until the queue is idle, yielding
        result envelopes as each batch completes (the streaming read
        the CLI prints line-by-line).  Dwell-aware: a held under-full
        batch sleeps out its window on the real clock; under an
        injected clock that does not advance on its own, the batch is
        forced instead — drain always terminates.  `force=True` skips
        every dwell hold outright: when the caller KNOWS no new job
        can arrive (input exhausted, shutdown), waiting buys nothing
        but latency."""
        while self.admission.queue_depth:
            got = False
            for res in self.step(force=force):
                got = True
                yield res
            if got or not self._dwell_wait_s:
                continue
            # sleep a slice of the window (never a busy spin), then
            # check whether the clock moved: any real clock
            # (monotonic/time/perf_counter) or auto-advancing test
            # clock ages the held head on its own and the loop simply
            # re-steps; a FROZEN injected clock can never age it past
            # the dwell window, so the batch is forced instead of
            # spinning forever
            before = self._clock()
            time.sleep(min(self._dwell_wait_s, 0.02))
            if self._clock() == before:
                for res in self.step(force=True):
                    yield res

    def run_all(self) -> "list[JobResult]":
        # synchronous: nothing can arrive while we run, so a dwell
        # hold could only add latency — force past it
        return list(self.drain(force=True))

    @property
    def results(self) -> "list[JobResult]":
        """Every envelope completed so far (streaming callers use
        `drain()` instead)."""
        return list(self._completed)

    # -- batch execution -------------------------------------------------

    def _run_batch(self, cls: JobClass,
                   pendings: "list[Pending]") -> "list[JobResult]":
        from graphite_tpu.engine.simulator import (
            DeadlockError, MailboxOverflowError,
        )

        self._m["batches"].inc()
        batch_id = self._next_batch_id
        self._next_batch_id += 1
        btid = f"batch-{batch_id}"
        t0 = self._clock()
        # queue dwell ends when the batch forms: one histogram
        # observation per member, one reconstructed `queue` span per
        # job (requeued members' clocks restarted at requeue time, so
        # a split's second wait is a second observation, not a longer
        # first one)
        for p in pendings:
            if p.enqueue_ts is not None:
                p.dwell_s = t0 - p.enqueue_ts
                self._h["dwell"].observe(p.dwell_s)
                if self.tracer is not None:
                    self.tracer.record(p.job.job_id, "queue",
                                       p.enqueue_ts, t0, batch=batch_id)
        try:
            results = self._execute(cls, pendings, batch_id)
        except ProgramCacheError as e:
            # identity failures are NOT load: retrying cannot make a
            # mismatched artifact provable — surface them.  The popped
            # jobs still get failed envelopes first, so the accounting
            # (submitted == completed + failed + rejected) survives the
            # raise and no admitted work silently vanishes
            for p in pendings:
                p.attempts += 1
                self._completed.append(JobResult(
                    job_id=p.job.job_id, status=STATUS_FAILED,
                    error=f"ProgramCacheError: {e}", batch_id=batch_id,
                    attempts=p.attempts, seed=p.job.seed))
                self._m["failed"].inc()
                self._h["split_depth"].observe(p.attempts)
                if self.tracer is not None:
                    self.tracer.event(
                        p.job.job_id, "failed", batch=batch_id,
                        attempts=p.attempts,
                        error=f"ProgramCacheError: {e}")
            raise
        except (DeadlockError, MailboxOverflowError, RuntimeError) as e:
            wall = self._clock() - t0
            self._finish_batch_metrics(wall)
            return self._handle_failure(cls, pendings, batch_id, e,
                                        t0, wall)
        wall = self._clock() - t0
        self._finish_batch_metrics(wall)
        occupancy = len(pendings) / cls.batch_cap
        self._h["occupancy"].observe(occupancy)
        self.batch_log.append(BatchReport(
            batch_id=batch_id, class_name=self._class_name(cls),
            n_tiles=cls.n_tiles,
            job_ids=[p.job.job_id for p in pendings],
            n_jobs=len(pendings), batch_cap=cls.batch_cap,
            occupancy=occupancy,
            residency_total=self._last_residency,
            cache_hit=self._last_cache_hit,
            store_hit=self._last_store_hit, ok=True, wall_s=wall,
            layout=self._last_layout, programs=self._last_programs))
        if self.tracer is not None:
            self.tracer.record(
                btid, "batch", t0, t0 + wall,
                **self._batch_attrs(cls, pendings, ok=True))
            for p, res in zip(pendings, results):
                # terminal emit span; `telemetry_samples` references
                # the demuxed device timeline riding the envelope
                attrs = {"batch": batch_id, "attempts": res.attempts}
                if res.telemetry is not None:
                    attrs["telemetry_samples"] = len(res.telemetry)
                if res.profile is not None:
                    # the emit span links to the per-tile profile the
                    # way it links to the scalar timeline
                    attrs["profile_samples"] = len(res.profile)
                if res.hist is not None:
                    attrs["hist_events"] = int(sum(
                        res.hist.total(s) for s in res.hist.sources))
                self._job_span(p, batch_id)
                self.tracer.event(p.job.job_id, "emit", **attrs)
        for p, res in zip(pendings, results):
            self._h["split_depth"].observe(res.attempts)
            if self.tracer is not None:
                res.timings = {"queue_dwell_s": round(p.dwell_s, 6),
                               "batch_execute_s": round(wall, 6)}
        self._completed.extend(results)
        self._m["completed"].inc(len(results))
        return results

    def _job_span(self, p: Pending, batch_id: int) -> None:
        """`job`: first submit -> terminal envelope, over every queue
        dwell, split and retry between them (reconstructed, like
        `queue`: a row, no annotation)."""
        if p.submit_ts is not None:
            self.tracer.record(p.job.job_id, "job", p.submit_ts,
                               self._clock(), batch=batch_id)

    def _finish_batch_metrics(self, wall: float) -> None:
        self._m["execute_wall"].inc(wall)
        self._h["execute"].observe(wall)
        self._g["queue_depth"].set(self.admission.queue_depth)
        self._g["cache_entries"].set(len(self.cache))
        self._g["cache_bytes"].set(self.cache.total_bytes)
        # one periodic metrics-timeline row per executed batch — the
        # time series tools/report.py --metrics renders
        self.metrics.sample()

    def _batch_attrs(self, cls: JobClass, pendings, *, ok: bool,
                     error: "str | None" = None) -> dict:
        attrs = {
            "class": self._class_name(cls),
            "n_tiles": cls.n_tiles,
            "capacity": cls.batch_cap,
            "n_jobs": len(pendings),
            "occupancy": round(len(pendings) / cls.batch_cap, 6),
            "cache_hit": self._last_cache_hit,
            "store_hit": self._last_store_hit,
            "compile_s": round(self._last_compile_s, 6),
            "deserialize_s": round(self._last_deserialize_s, 6),
            "residency_bytes": self._last_residency,
            "layout": self._last_layout,
            "jobs": [p.job.job_id for p in pendings],
            "ok": ok,
        }
        if error is not None:
            attrs["error"] = error
        return attrs

    def _handle_failure(self, cls, pendings, batch_id, exc, t0, wall
                        ) -> "list[JobResult]":
        """Split-and-requeue (n > 1) or retry/fail (n == 1); every
        member's attempt counter moves, so the recursion terminates."""
        msg = f"{type(exc).__name__}: {exc}"
        self.batch_log.append(BatchReport(
            batch_id=batch_id, class_name=self._class_name(cls),
            n_tiles=cls.n_tiles,
            job_ids=[p.job.job_id for p in pendings],
            n_jobs=len(pendings), batch_cap=cls.batch_cap,
            occupancy=len(pendings) / cls.batch_cap,
            residency_total=self._last_residency,
            cache_hit=self._last_cache_hit,
            store_hit=self._last_store_hit,
            ok=False, wall_s=wall, error=msg,
            layout=self._last_layout))
        if self.tracer is not None:
            # the span covers the REAL execute window (t0, t0+wall) —
            # clock reads after it (metrics sampling) must not shift it
            self.tracer.record(
                f"batch-{batch_id}", "batch", t0, t0 + wall,
                **self._batch_attrs(cls, pendings, ok=False, error=msg))
        now = self._clock()
        for p in pendings:
            p.attempts += 1
            # a requeued member's dwell clock restarts: its second wait
            # is a second histogram observation, not a longer first one
            p.enqueue_ts = now
        if len(pendings) > 1:
            # halving isolates the offender in ~log2(B) steps; the
            # halves requeue as PRE-FORMED batches (head of the ready
            # line, first half first) so they re-run at their reduced
            # size — and still pad to the class capacity, so every
            # retry is a cache hit on the one compiled program
            mid = (len(pendings) + 1) // 2
            self.admission.requeue_batch(cls, pendings[mid:])
            self.admission.requeue_batch(cls, pendings[:mid])
            self._m["splits"].inc()
            self._m["retries"].inc()
            if self.tracer is not None:
                for p in pendings:
                    self.tracer.event(p.job.job_id, "split",
                                      batch=batch_id, error=msg)
            return []
        p = pendings[0]
        if p.attempts >= self.max_attempts:
            res = JobResult(job_id=p.job.job_id, status=STATUS_FAILED,
                            error=msg, batch_id=batch_id,
                            attempts=p.attempts, seed=p.job.seed)
            self._completed.append(res)
            self._m["failed"].inc()
            self._h["split_depth"].observe(p.attempts)
            if self.tracer is not None:
                self._job_span(p, batch_id)
                self.tracer.event(p.job.job_id, "failed",
                                  batch=batch_id, attempts=p.attempts,
                                  error=msg)
            return [res]
        self.admission.requeue_batch(cls, [p])
        self._m["retries"].inc()
        if self.tracer is not None:
            self.tracer.event(p.job.job_id, "retry", batch=batch_id,
                              attempts=p.attempts, error=msg)
        return []

    def _class_name(self, cls: JobClass) -> str:
        import hashlib

        digest = cls.key[0][:8]
        tel = "-tel" if cls.telemetry is not None else ""
        tel += "-prof" if cls.profile is not None else ""
        tel += "-dvfs" if getattr(cls, "dvfs", None) is not None else ""
        tel += "-hist" if getattr(cls, "hist", None) is not None else ""
        # round 18: 2D classes carry their mesh in the name — the
        # layout tag is in the key (injective hash below), but a
        # readable "-2d2x2" names the program a human greps for
        mesh = (f"-2d{cls.batch_shards}x{cls.tile_shards}"
                if getattr(cls, "tile_shards", 1) > 1 else "")
        # the key hash keeps the name INJECTIVE over class keys: the
        # readable fields alone miss key components (mem-ness,
        # telemetry spec details), and two distinct classes colliding
        # on one registry name would read as an identity violation
        khash = hashlib.sha256(repr(cls.key).encode()).hexdigest()[:8]
        return (f"serve-{digest}-t{cls.n_tiles}-b{cls.batch_cap}"
                f"-l{cls.pad_length}-d{cls.mailbox_depth}{tel}{mesh}"
                f"-k{khash}")

    def _execute(self, cls: JobClass, pendings: "list[Pending]",
                 batch_id: int) -> "list[JobResult]":
        """Pack, cache-resolve, run, and demux one batch.  Raises the
        engine's own failure types on a bad batch — `_run_batch` owns
        the split/retry policy."""
        from graphite_tpu.sweep.pack import pack_traces
        from graphite_tpu.sweep.runner import SweepRunner

        jobs = [p.job for p in pendings]
        n, B = len(jobs), cls.batch_cap
        btid = f"batch-{batch_id}"
        self._m["padded_slots"].inc(B - n)
        # per-batch stats reset FIRST: a failure before they are
        # recomputed must not report the previous batch's numbers
        self._last_residency = 0
        self._last_cache_hit = False
        self._last_compile_s = 0.0
        self._last_store_hit = False
        self._last_deserialize_s = 0.0
        self._last_layout = "solo"
        self._last_programs = None
        # pad to the class's FIXED capacity with replicas of job 0 so
        # every batch of this class shares one [B, T, L] program shape;
        # the replicas' rows are dropped below (the tail mask)
        traces = [j.trace for j in jobs] + [jobs[0].trace] * (B - n)
        points = [dict(j.knobs) for j in jobs] \
            + [dict(jobs[0].knobs)] * (B - n)
        if getattr(cls, "dvfs", None) is not None:
            from graphite_tpu.sweep.knobs import DVFS_KNOB_FIELD

            if any(DVFS_KNOB_FIELD in p for p in points):
                # jobs of one DVFS class co-batch whether or not they
                # sweep the operating point; absent points run at the
                # config's default domain frequencies
                default = tuple(int(f)
                                for f in cls.params.dvfs.domain_freq_mhz)
                for p in points:
                    p.setdefault(DVFS_KNOB_FIELD, default)
        span = self._batch_spans(batch_id)
        with span("pack", batch=batch_id):
            pack = pack_traces(traces, validate=False,
                               pad_length=cls.pad_length)
        # the budget is passed as an INT always: 0 explicitly disables
        # the runner's fail-fast (None would fall back to the config's
        # own `[general] hbm_budget_bytes`, refusing batches the
        # service-level admission never checked against)
        # round 18: a 2D class runs the Mesh(('batch','tile')) program
        # its admission plan sized — the layout is part of the class
        # key, so every batch of the class lowers the same artifact
        if getattr(cls, "tile_shards", 1) > 1:
            layout_kw = {"layout": (cls.batch_shards, cls.tile_shards)}
        else:
            layout_kw = {"shard_batch": self.shard_batch}
        programs0 = PROGRAMS.snapshot()
        with span("build", batch=batch_id):
            # what every batch pays before its program can run: a fresh
            # runner (and the Simulator inside it: `construct`), and the
            # [B, ...] initial states and [B, T, L] traces placed on the
            # device (`place`)
            runner = SweepRunner(
                cls.config, pack, points,
                mailbox_depth=cls.mailbox_depth,
                hbm_budget_bytes=self.hbm_budget_bytes,
                telemetry=cls.telemetry,
                profile=cls.profile, dvfs=cls.dvfs,
                hist=getattr(cls, "hist", None), tracer=self.tracer,
                **layout_kw)
            runner._batched_inputs()
        self._last_layout = runner.layout_name
        self._last_residency = int(
            runner.residency_breakdown()["total"])
        # the budget is PER DEVICE: a 2D batch's whole-campaign bill
        # legitimately exceeds it — its per-device tile blocks may not
        admitted = (int(runner.device_breakdown()["total"])
                    if getattr(cls, "tile_shards", 1) > 1
                    else self._last_residency)
        if self.hbm_budget_bytes \
                and admitted > self.hbm_budget_bytes:
            # unreachable by construction (admission sized batch_cap
            # from the same arithmetic and the runner's own fail-fast
            # already re-checked) — a trip here is a real bug, not load
            raise AssertionError(
                f"admitted batch per-device residency {admitted} "
                f"exceeds hbm_budget_bytes={self.hbm_budget_bytes}")
        with span("cache", batch=batch_id) as cspan:
            before = PROGRAMS.snapshot()
            entry = self._resolve_program(cls, runner, B)
            if cspan is not None:
                cost = PROGRAMS.since(before)
                cspan.attrs.update(hit=self._last_cache_hit,
                                   compile_s=round(
                                       self._last_compile_s, 6),
                                   store_hit=self._last_store_hit,
                                   deserialize_s=round(
                                       self._last_deserialize_s, 6),
                                   programs_compiled=cost[
                                       "programs_compiled"],
                                   programs_loaded=cost[
                                       "programs_loaded"],
                                   jax_compile_s=round(
                                       cost["compile_s"]
                                       + cost["load_s"], 6))
        # the program's dispatches are followed by the tracer attached
        # to its handle (`resident_program().attach_tracer`, a `run-<n>`
        # trace of its own), else by the service's, inside the batch's
        own = entry.tracer is None
        runner.attach_tracer(self.tracer if own else entry.tracer)
        t_exec = self._clock()
        with span("execute", batch=batch_id,
                  cache_hit=self._last_cache_hit):
            out = runner.run(max_quanta=self.max_quanta,
                             trace_id=btid if own else None)
        entry.inputs = runner.abstract_inputs()
        entry.last_n_iterations = runner.last_n_iterations
        entry.last_run_dispatches = runner.last_run_dispatches
        self._last_entry = entry
        # what JAX traced, lowered, compiled or loaded for this batch,
        # the lazy compile inside `execute` included (`BatchReport`)
        self._last_programs = PROGRAMS.since(programs0)
        t_done = self._clock()
        if self.tracer is not None:
            # one execute span per member too, so a job trace alone
            # carries its full host timeline
            for p in pendings:
                self.tracer.record(p.job.job_id, "execute",
                                   t_exec, t_done, batch=batch_id)
        with span("demux", batch=batch_id):
            results = []
            for b in range(n):  # the padded tail [n:B] never leaves here
                p = pendings[b]
                tl = None if out.timelines is None else out.timelines[b]
                pf = None if out.profiles is None else out.profiles[b]
                hf = (None if getattr(out, "hists", None) is None
                      else out.hists[b])
                ps = None if out.phase_skips is None else out.phase_skips[b]
                bs = None if out.base_skips is None else out.base_skips[b]
                results.append(JobResult(
                    job_id=p.job.job_id, status=STATUS_OK,
                    results=out.results[b], telemetry=tl, profile=pf,
                    hist=hf,
                    batch_id=batch_id, attempts=p.attempts + 1,
                    seed=p.job.seed, knob_point=dict(p.job.knobs),
                    n_quanta=int(out.n_quanta[b]),
                    n_iterations=int(out.n_iterations[b]),
                    idle_iterations=int(out.idle_iterations[b]),
                    phase_skips=ps, base_skips=bs,
                    **(out.power[b] if out.power is not None else {})))
        return results

    # -- program cache ---------------------------------------------------

    def _resolve_program(self, cls: JobClass, runner, B: int
                         ) -> CacheEntry:
        """Serve the batch through the compiled-program cache.

        MISS: lower the campaign, fingerprint it
        (`analysis/identity.fingerprint`), resolve the name through the
        service registry (a registry-mismatched fingerprint at insert
        time errors LOUDLY — `ProgramCacheError`), register + insert,
        and hand the runner its own fresh jit (the one compile).
        HIT: resolve the stored record through the registry, optionally
        re-lower and re-prove fingerprint equality (`verify_hits` — a
        retrace, never a recompile), and inject the cached jitted
        callable into the fresh runner, so the batch executes the
        PROVABLY-same compiled artifact with zero new compiles."""
        from graphite_tpu.analysis.identity import fingerprint
        from graphite_tpu.analysis.registry import ProgramRecord

        name = self._class_name(cls)
        key = cls.key + (B, self.max_quanta)
        shape_sig = (B, cls.n_tiles, cls.pad_length)
        entry = self.cache.get(key, shape_sig)
        if entry is not None:
            reg = self.registry.get(entry.name)
            if reg is None or reg.fingerprint != entry.record.fingerprint:
                raise ProgramCacheError(
                    f"cache entry {entry.name!r} no longer resolves "
                    "through the registry — refusing to serve an "
                    "unprovable artifact")
            if self.verify_hits:
                closed, _ = runner.lower(self.max_quanta)
                fp = fingerprint(closed)
                if fp != entry.record.fingerprint:
                    raise ProgramCacheError(
                        f"cache hit verification failed for "
                        f"{entry.name!r}: this batch lowers to "
                        f"{fp[:24]}... but the cached program is "
                        f"{entry.record.fingerprint[:24]}... — the "
                        "class key admitted a different program")
            runner._runner = entry.jitted
            runner._runner_max_quanta = entry.max_quanta
            self._m["cache_hits"].inc()
            self._last_cache_hit = True
            # a hit still knows what its program cost to build
            self._last_compile_s = entry.compile_s
            return entry
        self._last_cache_hit = False
        t_compile = self._clock()
        closed, _ = runner.lower(self.max_quanta)
        fp = fingerprint(closed)
        record = ProgramRecord(name=name, fingerprint=fp,
                               tiles=cls.n_tiles)
        reg = self.registry.get(name)
        if reg is not None and reg.fingerprint != fp:
            raise ProgramCacheError(
                f"program {name!r} lowered to fingerprint {fp[:24]}... "
                f"but is registered as {reg.fingerprint[:24]}... — "
                "refusing the insert: the same class key must not "
                "silently serve two different artifacts")
        self.registry[name] = record
        if self.store is not None:
            # STORE HIT: another fleet process (or a prior life of this
            # one) already compiled this exact program — deserialize
            # its executable and inject it, zero compiles.  The
            # fingerprint we just lowered IS the store key, so every
            # store hit is identity-proven by retrace (the same proof
            # `verify_hits` buys for in-memory hits).
            t_probe = self._clock()
            entry = self._store_resolve(runner, record, B, shape_sig)
            if entry is not None:
                self.cache.put(key, entry, expect_fingerprint=fp)
                return entry
            # the disk probe (possibly a multi-MB read + sha256 + a
            # quarantine rename) is not compile time: keep it out of
            # compile_seconds and the compile_s the manifest persists
            t_compile += self._clock() - t_probe
            # STORE MISS: compile AOT against the real device inputs
            # (the jit path compiles lazily inside run(), which cannot
            # be serialized), fill the store, serve the batch
            from graphite_tpu.store.aot import aot_compile_runner

            compiled = aot_compile_runner(runner, self.max_quanta)
            self._last_compile_s = self._clock() - t_compile
            self._m["store_misses"].inc()
            jitted = compiled
        else:
            jitted = runner._get_runner(self.max_quanta)
            self._last_compile_s = self._clock() - t_compile
        self._h["compile"].observe(self._last_compile_s)
        entry = CacheEntry(
            name=name, record=record, jitted=jitted,
            max_quanta=self.max_quanta,
            nbytes=self._last_residency, shape_sig=shape_sig,
            compile_s=self._last_compile_s)
        self.cache.put(key, entry, expect_fingerprint=fp)
        self._m["compiles"].inc()
        if self.store is not None:
            self._store_fill(entry, B, jitted)
        return entry

    def _store_resolve(self, runner, record, B: int, shape_sig
                       ) -> "CacheEntry | None":
        """Serve an in-memory miss from the persistent store when it
        can prove the artifact: `warm_start()`-staged executables
        first, then a disk load.  An integrity failure quarantines the
        entry, counts, and returns None (fall through to compile) —
        never a crash, never a silently wrong program."""
        from graphite_tpu.store import (
            StoreError, StoreIntegrityError, StoreKey,
        )
        from graphite_tpu.store.aot import runtime_env

        fp = record.fingerprint
        staged = self._warm.pop((fp, B), None)
        if staged is not None:
            fnc, man, des_s = staged
        else:
            skey = StoreKey(fp, B, self.max_quanta, runtime_env())
            t0 = self._clock()
            try:
                got = self.store.load_executable(
                    skey, expect_fingerprint=fp)
            except StoreIntegrityError:
                self._m["store_integrity"].inc()
                return None
            except (StoreError, OSError):
                # store unreachable (read-only mount, deleted locks/,
                # disk error): an availability loss, not a
                # correctness one — fall back to a local compile,
                # never a crash
                return None
            if got is None:
                return None
            fnc, man = got
            des_s = self._clock() - t0
        runner._runner = fnc
        runner._runner_max_quanta = self.max_quanta
        self._m["store_hits"].inc()
        self._h["store_deserialize"].observe(des_s)
        self._last_store_hit = True
        self._last_deserialize_s = des_s
        # what the ORIGINAL fleet miss paid to build this program —
        # the round-14 "a hit still knows its build cost" contract,
        # now surviving process death via the manifest
        try:
            self._last_compile_s = float(man.get("compile_s", 0.0))
        except (TypeError, ValueError):
            self._last_compile_s = 0.0
        return CacheEntry(
            name=record.name, record=record, jitted=fnc,
            max_quanta=self.max_quanta, nbytes=self._last_residency,
            shape_sig=shape_sig, compile_s=self._last_compile_s,
            source="store", deserialize_s=des_s)

    def _store_fill(self, entry: CacheEntry, B: int, compiled) -> None:
        """Serialize + publish the fresh executable (atomic, locked).
        A fill failure is an availability loss, not a correctness one:
        counted, never raised into the batch — the compiled program
        still serves this process."""
        from graphite_tpu.store import StoreKey
        from graphite_tpu.store.aot import runtime_env

        t0 = self._clock()
        try:
            skey = StoreKey(entry.record.fingerprint, B,
                            self.max_quanta, runtime_env())
            self.store.save_executable(skey, compiled, manifest={
                "name": entry.name,
                "shape_sig": list(entry.shape_sig),
                "nbytes": int(entry.nbytes),
                "compile_s": round(float(entry.compile_s), 6),
                "record": {"name": entry.record.name,
                           **entry.record.to_json()},
            })
        except Exception:    # noqa: BLE001 — the batch must serve:
            # serialize/pickle/disk failures of EVERY flavor are an
            # availability loss for the FLEET, never a correctness
            # loss for this batch (StoreError, PicklingError, OSError,
            # backend serialization RuntimeErrors, ...)
            self._m["store_fill_errors"].inc()
            return
        self._m["store_fills"].inc()
        self._h["store_fill"].observe(self._clock() - t0)

    def warm_start(self, limit: "int | None" = None) -> int:
        """Pre-populate from the persistent store: deserialize entries
        compatible with this process (same runtime environment, same
        `max_quanta`) into a fingerprint-keyed staging area, so the
        first job of each stored class pays its deserialize at STARTUP
        and zero compiles at serve time.  Returns the number of
        programs staged; 0 without a store.  Integrity failures
        quarantine + count and skip the entry, exactly like the lazy
        load path.

        Staged executables live on the host/devices until a job of
        their class pops them, so startup wall time and memory scale
        with what is staged — `limit` bounds that to the N
        most-recently-used entries (a fleet store can hold far more
        classes than one process will ever serve; an unstaged class
        still store-hits lazily on its first job).  None stages every
        compatible entry."""
        if self.store is None:
            return 0
        from graphite_tpu.store import (
            StoreError, StoreIntegrityError, StoreKey,
        )
        from graphite_tpu.store.aot import runtime_env

        env = runtime_env()
        n = 0
        try:
            rows = self.store.entries()
        except OSError:
            return 0    # store unreachable: cold start, not a crash
        # entries() sorts oldest-used first; stage MRU-first so a
        # `limit` keeps the entries most likely to serve soon
        for row in reversed(rows):
            if limit is not None and n >= limit:
                break
            man = row["manifest"]
            if man is None:
                continue
            try:
                fp = str(man["fingerprint"])
                batch = int(man["batch"])
                ok = (int(man["max_quanta"]) == self.max_quanta
                      and tuple(man["env"]) == env)
            except (KeyError, TypeError, ValueError):
                continue
            if not ok or (fp, batch) in self._warm:
                continue
            skey = StoreKey(fp, batch, self.max_quanta, env)
            t0 = self._clock()
            try:
                got = self.store.load_executable(
                    skey, expect_fingerprint=fp)
            except StoreIntegrityError:
                self._m["store_integrity"].inc()
                continue
            except (StoreError, OSError):
                continue    # unreachable entry: serve cold instead
            if got is None:
                continue
            fnc, man2 = got
            self._warm[(fp, batch)] = (fnc, man2, self._clock() - t0)
            n += 1
        return n

    # -- observability ---------------------------------------------------

    @property
    def counters(self) -> dict:
        """Service counters: queue depth, batch occupancy, cache hit
        rate, compile count, jobs/s — the inference-stack dashboard.

        This is a COMPATIBILITY VIEW over `self.metrics` (the one
        definition of each rate lives in the registry): the round-13
        dict keys are preserved for `tools/serve.py` summary output and
        existing tests, each derived from exactly one instrument."""
        m = self._m
        hits = int(m["cache_hits"].value)
        compiles = int(m["compiles"].value)
        # store hits are neither an in-memory hit nor a compile, but
        # they ARE resolved batches — the rate's denominator counts
        # every resolution so a warm-started fleet member reads an
        # honest in-memory hit fraction
        store_hits = int(m["store_hits"].value)
        occ = self._h["occupancy"]
        wall = m["execute_wall"].value
        completed = int(m["completed"].value)
        return {
            "submitted": int(m["submitted"].value),
            "completed": completed,
            "failed": int(m["failed"].value),
            "rejected": int(m["rejected"].value),
            "backpressure": int(m["backpressure"].value),
            "batches": int(m["batches"].value),
            "padded_slots": int(m["padded_slots"].value),
            "splits": int(m["splits"].value),
            "retries": int(m["retries"].value),
            "cache_hits": hits,
            "compile_count": compiles,
            "queue_depth": self.admission.queue_depth,
            "mean_batch_occupancy": occ.mean,
            "cache_hit_rate": (hits / (hits + compiles + store_hits)
                               if hits + compiles + store_hits
                               else 0.0),
            "cache_entries": len(self.cache),
            "cache_bytes": self.cache.total_bytes,
            "cache_evictions": self.cache.evictions,
            "store_hits": store_hits,
            "store_misses": int(m["store_misses"].value),
            "store_fills": int(m["store_fills"].value),
            "store_integrity": int(m["store_integrity"].value),
            "jobs_per_s": completed / wall if wall > 0 else 0.0,
        }
