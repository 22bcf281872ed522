"""Admission control: budget bin-packing + FIFO queueing for the service.

The admission controller answers three questions per submitted job,
entirely from host-side arithmetic (no tracing, no compile):

 - *which program class* does it belong to?  Jobs co-batch only when
   they provably share one compiled program: same config digest, same
   tile count, same memory-ness, same telemetry spec, same per-tile
   profile spec, the same runtime-DVFS spec (the carried-frequency
   reads are baked into the program — differing domain configurations
   never co-batch, while `dvfs_domain_mhz` knob points of ONE spec
   do), the same latency-histogram spec (round 21 — the int64 bucket
   ring is baked into the program too), the same
   bucketed mailbox depth / trace length (lengths and depths round up
   to powers of two so successive batches share one [B, T, L] shape —
   and therefore one program-cache entry), and — round 18 — the same
   DEVICE LAYOUT axis: a job served under the 2D batch x tile mesh
   lowers a different program than a solo job, so 1D and 2D jobs never
   co-batch (the layout tag is the key's last element);

 - *can it ever fit*?  The per-sim residency bill — state pytree +
   padded trace rows + telemetry ring, the exact consumers
   `analysis/cost.residency_breakdown` itemizes — is compared against
   `hbm_budget_bytes`.  A job whose B=1 bill exceeds ONE device's
   budget is no longer bounced (round 18): with `n_devices` > 1 the
   bill is split into per-device TILE BLOCKS
   (`analysis/cost.device_residency_breakdown` — the big per-tile
   arrays, trace rows and profile ring shard with the directory) and
   the job is admitted under the smallest tile split whose per-device
   block fits.  Only a job too big even when split over EVERY device
   is rejected — immediately, with the itemized per-device breakdown
   (`ResidencyBudgetError`, the round-10 refusal type);

 - *how many co-batch*?  Every campaign consumer scales linearly in B,
   so a solo class's batch capacity is `budget // per_sim_total`,
   clamped to the service's `batch_size`.  A 2D class accounts
   DEVICES x budget instead of one budget: with batch_shards
   devices on the batch axis, capacity is `batch_shards x (budget //
   per_device_block)` (then rounded to a batch_shards multiple so the
   mesh divides evenly).  No admitted batch's per-device
   residency can exceed the budget by construction (and the
   SweepRunner's own pre-compile fail-fast re-proves it).

Jobs that fit but not *now* wait in per-class FIFO queues under a
global `max_pending` bound — when the queue is full, `admit` raises
`QueueFullError` (backpressure: the caller must drain results before
submitting more).  `next_batch` serves the class whose HEAD job is
globally oldest, so no class starves behind a busier one (FIFO
fairness across classes, strict FIFO within one).
"""

from __future__ import annotations

import collections
import dataclasses

from graphite_tpu.serve.job import Job, config_digest


class QueueFullError(RuntimeError):
    """Backpressure: the pending queue is at `max_pending`."""


def _pow2_bucket(n: int, lo: int) -> int:
    """Smallest power of two >= max(n, lo)."""
    n = max(int(n), int(lo))
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass
class Pending:
    """One queued job plus its service bookkeeping."""

    job: Job
    seq: int           # global submission order (FIFO fairness key)
    attempts: int = 0  # failed executions so far (split/retry budget)
    # observability (round 14): service-clock timestamp of the LAST
    # enqueue (submit or requeue — the queue-dwell histogram's start;
    # None until the service stamps it), and the dwell the most recent
    # batch-form measured from it
    enqueue_ts: "float | None" = None
    dwell_s: float = 0.0
    # service-clock timestamp of the FIRST enqueue (the `job` span's
    # start: submit -> envelope, across splits and retries)
    submit_ts: "float | None" = None


@dataclasses.dataclass
class JobMeasure:
    """One class's probe measurements: the engine params, resolved
    ring specs, and the residency byte counts the layout planner and
    the class capacity arithmetic both consume.  The probe Simulator
    itself is dropped immediately (its state pytree is real device
    memory — retaining one per class forever would be exactly the
    residency the controller polices)."""

    params: object
    telemetry: object          # resolved TelemetrySpec | None
    profile: object            # resolved ProfileSpec | None
    hist: object               # resolved HistSpec | None
    pad_length: int
    per_sim_bytes: "dict[str, int]"    # whole-sim consumers (dt=1)
    state_replicated: int      # control state every tile shard holds
    state_tile_local: int      # big per-tile arrays (shard with dt)

    @property
    def per_sim_total(self) -> int:
        return sum(self.per_sim_bytes.values())

    def device_block(self, tile_shards: int = 1,
                     sims: int = 1) -> "dict[str, int]":
        """Itemized PER-DEVICE bill of `sims` sims' tile blocks under
        a `tile_shards`-way tile split — delegates to THE per-device
        arithmetic (`analysis/cost.device_residency_breakdown`) with
        the probe's retained byte counts, so the admission bill and
        the runner's fail-fast can never desynchronize."""
        from graphite_tpu.analysis.cost import device_residency_breakdown

        return device_residency_breakdown(
            state_split={"replicated": self.state_replicated,
                         "tile_local": self.state_tile_local},
            sims_per_shard=sims, tile_shards=tile_shards,
            per_sim_trace_bytes=self.per_sim_bytes["trace"],
            telemetry_spec=self.telemetry,
            profile_spec=self.profile,
            hist_spec=self.hist)


def measure_job(job: Job, *, mailbox_depth: int,
                pad_length: int) -> JobMeasure:
    """Build the probe Simulator exactly the way the batch runner will
    build its per-sim program (same config, same mailbox depth), read
    the byte counts, drop the probe."""
    from graphite_tpu.analysis.cost import trace_record_bytes, tree_bytes
    from graphite_tpu.engine.simulator import Simulator
    from graphite_tpu.parallel.mesh import shard_split_bytes

    probe = Simulator(job.resolved_config(), job.trace,
                      mailbox_depth=int(mailbox_depth),
                      barrier_host=False)
    params = probe.params
    telemetry = (job.telemetry.resolve(params)
                 if job.telemetry is not None else None)
    # the per-tile profile ring joins the admission bill the same way
    # (obs.ProfileSpec.ring_bytes — the one size model); its T factor
    # is what makes a dense big-tile profile pay its way through the
    # budget instead of OOMing a compiled batch
    profile = (job.profile.resolve(params)
               if job.profile is not None else None)
    # the int64 bucket ring joins the bill through the same size model
    # (obs.HistSpec.ring_bytes) — a dense per-tile recording pays its
    # way through the budget like the profile ring does
    hist = (job.hist.resolve(params)
            if job.hist is not None else None)
    per_sim = {
        "state": int(tree_bytes(probe.state)),
        "trace": (params.n_tiles * int(pad_length)
                  * trace_record_bytes(job.trace)),
    }
    if telemetry is not None:
        per_sim["telemetry"] = int(telemetry.ring_bytes())
    if profile is not None:
        per_sim["profile"] = int(profile.ring_bytes())
    if hist is not None:
        per_sim["hist"] = int(hist.ring_bytes())
    split = shard_split_bytes(probe.state)
    return JobMeasure(params=params, telemetry=telemetry,
                      profile=profile, hist=hist,
                      pad_length=int(pad_length),
                      per_sim_bytes=per_sim,
                      state_replicated=int(split["replicated"]),
                      state_tile_local=int(split["tile_local"]))


def plan_layout(measure: JobMeasure, *, hbm_budget_bytes: int,
                batch_size: int, n_devices: int) -> dict:
    """The class's device layout + batch capacity, from arithmetic the
    measure already holds.

    Solo (tag ('solo',)) when the budget is off or one sim fits one
    device: capacity = budget // per_sim (the round-13 rule).  When a
    sim alone exceeds the budget and devices exist, the smallest tile
    split whose per-device block fits wins (tag ('2d', db, dt)):
    batch_shards devices on the batch axis each run cap//db sims'
    blocks, so capacity accounts DEVICES x budget.  Tag ('never',)
    when even the maximal split exceeds the budget — the only
    remaining rejection."""
    budget = int(hbm_budget_bytes)
    batch_size = int(batch_size)
    n_dev = max(int(n_devices), 1)
    if not budget:
        return {"tag": ("solo",), "batch_shards": 1, "tile_shards": 1,
                "batch_cap": batch_size}
    if measure.per_sim_total <= budget:
        return {"tag": ("solo",), "batch_shards": 1, "tile_shards": 1,
                "batch_cap": min(batch_size,
                                 budget // max(measure.per_sim_total,
                                               1))}
    T = int(measure.params.n_tiles)
    best_bd = measure.device_block(1)
    # any tile divisor up to the device count is a candidate — dt need
    # not divide n_devices (the mesh simply uses db*dt of them; idle
    # devices beat a rejection), smallest split that fits wins
    for dt in range(2, n_dev + 1):
        if T % dt:
            continue
        bd = measure.device_block(dt)
        if bd["total"] < best_bd["total"]:
            best_bd = bd
        if bd["total"] > budget:
            continue
        cap_per_shard = budget // bd["total"]
        db = n_dev // dt
        cap = min(batch_size, db * cap_per_shard)
        if cap < 1:
            continue
        if cap < db:
            # fewer sims than batch shards: shrink the batch axis
            db = cap
        else:
            cap -= cap % db
        return {"tag": ("2d", db, dt), "batch_shards": db,
                "tile_shards": dt, "batch_cap": cap}
    return {"tag": ("never",), "batch_shards": 1, "tile_shards": 1,
            "batch_cap": 0, "best_breakdown": best_bd}


class JobClass:
    """One program class: jobs that provably share a compiled program.

    A probe Simulator is built once (never run) to read the engine
    params and the per-sim state bytes, then dropped; the class keeps
    the per-sim residency bill, the device layout + batch capacity the
    budget allows, and the class FIFO.
    """

    def __init__(self, key: tuple, job: Job, *, mailbox_depth: int,
                 pad_length: int, hbm_budget_bytes: int, batch_size: int,
                 n_devices: int = 1, measure: "JobMeasure | None" = None):
        self.key = key
        self.config = job.resolved_config()
        self.dvfs = job.dvfs
        self.mailbox_depth = int(mailbox_depth)
        self.pad_length = int(pad_length)
        self.fifo: "collections.deque[Pending]" = collections.deque()
        if measure is None:
            measure = measure_job(job, mailbox_depth=self.mailbox_depth,
                                  pad_length=self.pad_length)
        self.measure = measure
        self.params = measure.params
        self.telemetry = measure.telemetry
        self.profile = measure.profile
        self.hist = measure.hist
        self.per_sim_bytes = dict(measure.per_sim_bytes)
        self.per_sim_total = measure.per_sim_total
        plan = plan_layout(measure, hbm_budget_bytes=hbm_budget_bytes,
                           batch_size=batch_size, n_devices=n_devices)
        self.layout_tag = plan["tag"]
        self.batch_shards = int(plan["batch_shards"])
        self.tile_shards = int(plan["tile_shards"])
        self.batch_cap = int(plan["batch_cap"])
        self.best_breakdown = plan.get("best_breakdown")

    @property
    def n_tiles(self) -> int:
        return int(self.params.n_tiles)

    @property
    def sharded(self) -> bool:
        """True when this class runs under the 2D batch x tile mesh."""
        return self.tile_shards > 1

    def breakdown(self, batch: int = 1) -> "dict[str, int]":
        """The itemized residency bill for a `batch`-wide campaign of
        this class — consumer-for-consumer the dict
        `SweepRunner.residency_breakdown` computes for the real batch
        (every consumer scales linearly in B)."""
        out = {k: v * int(batch) for k, v in self.per_sim_bytes.items()}
        out["total"] = sum(out.values())
        return out

    def device_breakdown(self, batch: "int | None" = None
                         ) -> "dict[str, int]":
        """The itemized PER-DEVICE bill of a `batch`-wide campaign
        (default: the class capacity) under this class's layout — the
        bill the 2D admission proves <= hbm_budget_bytes."""
        batch = self.batch_cap if batch is None else int(batch)
        db = max(self.batch_shards, 1)
        sims = max((batch + db - 1) // db, 1) if batch else 0
        return self.measure.device_block(self.tile_shards, sims=sims)


class AdmissionController:
    """Classify, budget-check, and queue jobs; form FIFO-fair batches."""

    def __init__(self, *, hbm_budget_bytes: int = 0, batch_size: int = 4,
                 max_pending: int = 1024, n_devices: int = 1):
        if int(batch_size) < 1:
            raise ValueError("batch_size must be >= 1")
        self.hbm_budget_bytes = int(hbm_budget_bytes)
        self.batch_size = int(batch_size)
        self.max_pending = int(max_pending)
        # round 18: devices the service may spread a class over — a
        # per-sim bill above ONE device's budget bin-packs ACROSS them
        # (the 2D batch x tile layout) instead of bouncing.  Default 1
        # keeps the round-13 single-device admission bit-identically.
        self.n_devices = max(int(n_devices), 1)
        self.classes: "dict[tuple, JobClass]" = {}
        # probe measurements + layout plans memoized per BASE key (the
        # key minus its layout element): the layout axis is derived
        # from the measurement, and re-probing per submit would build a
        # device-state pytree per job
        self._measures: "dict[tuple, JobMeasure]" = {}
        # pre-formed batches (split/retry requeues) served before any
        # new batch forms — without this, a split's halves would simply
        # re-coalesce into the failing batch on the next pop
        self._ready: "collections.deque[tuple]" = collections.deque()
        self._seq = 0
        self._depth = 0

    @property
    def queue_depth(self) -> int:
        return self._depth

    def class_key(self, job: Job) -> tuple:
        """The program-class key: everything that changes the compiled
        artifact and is knowable without tracing.  Traced knobs are
        deliberately absent (they share the program — that is the whole
        round-7 point); the cache's fingerprint check is the proof the
        key was sufficient."""
        from graphite_tpu.engine.simulator import auto_mailbox_depth

        depth = _pow2_bucket(auto_mailbox_depth(job.trace), 2)
        length = _pow2_bucket(job.trace.length, 16)
        tel = job.telemetry
        # energy_prices is part of the key: the pJ prices fold into the
        # compiled step as literals, so two jobs differing only in
        # prices lower different programs and must never co-batch
        tel_key = None if tel is None else (
            int(tel.sample_interval_ps), int(tel.n_samples), tel.series,
            tel.energy_prices)
        prof = job.profile
        # the profile spec is part of the key for the same reason: the
        # [S, T, m] ring (and its series selection / prices) is baked
        # into the lowering, so differing specs never co-batch
        prof_key = None if prof is None else (
            int(prof.sample_interval_ps), int(prof.n_samples),
            prof.series, prof.energy_prices)
        # the runtime-DVFS spec splits classes the same way: a DvfsSpec
        # (frozen, hashable) bakes the carried-frequency reads and the
        # governor into the lowering; dvfs=None jobs keep the historical
        # program.  The per-point dvfs_domain_mhz knob is absent here on
        # purpose — points of one spec share the compiled program.
        hs = job.hist
        # the hist spec splits classes too: the int64 bucket ring (its
        # edges, source selection, per-tile switch and prices) is baked
        # into the lowering; hist=None jobs keep the historical program
        hist_key = None if hs is None else (
            hs.sources, hs.edges, int(hs.log2_buckets),
            bool(hs.per_tile), hs.energy_prices)
        base = (config_digest(job.resolved_config()), job.n_tiles,
                job.has_mem_trace(), depth, length, tel_key, prof_key,
                job.dvfs, hist_key)
        # round 18: the DEVICE LAYOUT axis.  A 2D batch x tile class
        # lowers a different program than a solo class (the shard_map
        # mesh, specs and exchange are part of the artifact), so the
        # layout tag joins the key and 1D/2D jobs never co-batch.  The
        # tag is derived from the probe measurement (memoized per base
        # key) + the controller's budget/device arithmetic.
        return base + (self._layout_tag(base, job, depth, length),)

    def _layout_tag(self, base: tuple, job: Job, mailbox_depth: int,
                    pad_length: int) -> tuple:
        measure = self._measures.get(base)
        if measure is None:
            measure = measure_job(job, mailbox_depth=mailbox_depth,
                                  pad_length=pad_length)
            self._measures[base] = measure
        return plan_layout(measure,
                           hbm_budget_bytes=self.hbm_budget_bytes,
                           batch_size=self.batch_size,
                           n_devices=self.n_devices)["tag"]

    def admit(self, job: Job) -> "tuple[JobClass, Pending]":
        """Queue `job` (validated by the caller) or refuse it.

        Raises `analysis.cost.ResidencyBudgetError` — with the itemized
        per-consumer breakdown attached as `.breakdown` — when the job
        can NEVER fit the per-device budget, and `QueueFullError` when
        the pending queue is at `max_pending` (backpressure)."""
        from graphite_tpu.analysis.cost import (
            ResidencyBudgetError, format_breakdown,
        )

        if self._depth >= self.max_pending:
            raise QueueFullError(
                f"pending queue is full ({self._depth} >= max_pending="
                f"{self.max_pending}) — drain results before submitting "
                "more")
        key = self.class_key(job)
        cls = self.classes.get(key)
        if cls is None:
            cls = JobClass(key, job,
                           mailbox_depth=key[3], pad_length=key[4],
                           hbm_budget_bytes=self.hbm_budget_bytes,
                           batch_size=self.batch_size,
                           n_devices=self.n_devices,
                           measure=self._measures.get(key[:-1]))
            self.classes[key] = cls
        if self.hbm_budget_bytes and cls.batch_cap < 1:
            bd = cls.breakdown(1)
            if self.n_devices > 1:
                best = cls.best_breakdown or bd
                extra = (
                    f" — at the best tile split the {self.n_devices} "
                    f"device(s) allow, one per-device block still costs "
                    + format_breakdown(best)
                    + "; shrink the trace/telemetry ring, raise the "
                    "budget, or add devices")
            else:
                extra = (
                    " — shrink the trace/telemetry ring, raise the "
                    "budget, or give the service devices to bin-pack "
                    "across (n_devices > 1 admits it under the 2D "
                    "batch x tile layout)")
            err = ResidencyBudgetError(
                f"job {job.job_id!r} can never fit hbm_budget_bytes="
                f"{self.hbm_budget_bytes}: one sim alone costs "
                + format_breakdown(bd) + extra)
            err.breakdown = bd
            raise err
        pending = Pending(job=job, seq=self._seq)
        self._seq += 1
        cls.fifo.append(pending)
        self._depth += 1
        return cls, pending

    def requeue_batch(self, cls: JobClass,
                      pendings: "list[Pending]") -> None:
        """Requeue a split half (or a lone retry) as a PRE-FORMED batch
        at the head of the ready line: it must re-run at its reduced
        size — returning the jobs to the class FIFO would let the next
        pop re-coalesce the exact batch that just failed.  The jobs
        were admitted once, so max_pending does not apply again
        (refusing here would drop accepted work)."""
        self._ready.appendleft((cls, list(pendings)))
        self._depth += len(pendings)

    def _oldest_waiting(self) -> "JobClass | None":
        """The class whose HEAD job is globally oldest (no class
        starves) — the ONE selector `peek_batch` reports and
        `next_batch` pops, so the two can never drift apart."""
        waiting = [c for c in self.classes.values() if c.fifo]
        if not waiting:
            return None
        return min(waiting, key=lambda c: c.fifo[0].seq)

    def peek_batch(self) -> "tuple[JobClass, int, Pending, bool] | None":
        """What `next_batch` WOULD pop, without popping: (class, batch
        size, head job, preformed) or None on an idle queue.  The
        service's latency-aware dwell policy reads this to decide
        whether an under-full batch should wait for more arrivals;
        `preformed` marks a requeued split/retry batch, which must
        never wait (its jobs are the globally oldest)."""
        if self._ready:
            cls, batch = self._ready[0]
            return cls, len(batch), batch[0], True
        cls = self._oldest_waiting()
        if cls is None:
            return None
        return (cls, min(len(cls.fifo), cls.batch_cap), cls.fifo[0],
                False)

    def full_class(self) -> "JobClass | None":
        """A class whose queue can ALREADY fill a batch (oldest head
        among them), or None.  The dwell policy runs a full class
        while the globally-oldest under-full head keeps aging — a full
        batch gains nothing by waiting."""
        full = [c for c in self.classes.values()
                if len(c.fifo) >= c.batch_cap]
        if not full:
            return None
        return min(full, key=lambda c: c.fifo[0].seq)

    def next_batch(self, from_cls: "JobClass | None" = None
                   ) -> "tuple[JobClass, list[Pending]] | None":
        """Pop the next batch: requeued (split/retry) batches first —
        they hold the globally oldest jobs — then the class whose HEAD
        job is globally oldest (no class starves), up to the class's
        budget-derived batch capacity, strict FIFO within the class.
        `from_cls` pops from a specific class instead (the dwell
        policy's run-the-full-class-now path); requeued batches still
        outrank it."""
        if self._ready:
            cls, batch = self._ready.popleft()
            self._depth -= len(batch)
            return cls, batch
        cls = from_cls if from_cls is not None else self._oldest_waiting()
        if cls is None:
            return None
        batch = []
        while cls.fifo and len(batch) < cls.batch_cap:
            batch.append(cls.fifo.popleft())
        if not batch:
            return None
        self._depth -= len(batch)
        return cls, batch
