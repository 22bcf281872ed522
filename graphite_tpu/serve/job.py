"""Job specs and result envelopes for the campaign service.

A `Job` is one simulation request: a trace, a configuration, optional
timing-knob overrides (the round-7 traced `Knobs` fields — they never
change the compiled program), an optional `TelemetrySpec`, an optional
per-job clock-skew scheme, and a seed carried as metadata.  `validate()`
runs every static check a host can prove before the job touches the
queue: trace well-formedness (`trace/validate.py`), geometry agreement,
knob-name/scheme compatibility — so a malformed job is rejected at
submit time with a named error instead of poisoning a batch minutes
into a compiled run.

A `JobResult` is the streaming envelope the service emits as each batch
completes: the job's own demuxed `SimResults` + telemetry timeline (or
a failure record after the retry budget is exhausted), plus the batch
bookkeeping (batch id, attempts, the knob point that ran).
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from graphite_tpu.config.config_file import ConfigFile
from graphite_tpu.config.simconfig import SimConfig

# The selectable clock-skew management schemes (engine/simulator.py):
# lax_barrier runs quantum barriers (the strict scheme; quantum_ps is a
# sweepable knob there), lax runs one unbounded quantum, lax_p2p runs
# unbounded quanta with pairwise slack clamping.  Exposed per-job so one
# service instance can serve a skew-tolerance scenario axis — jobs with
# different schemes compile different programs and never co-batch.
CLOCK_SCHEMES = ("lax_barrier", "lax", "lax_p2p")

STATUS_OK = "ok"
STATUS_FAILED = "failed"


def _coerce_config(config) -> SimConfig:
    if isinstance(config, str):
        config = ConfigFile.from_string(config)
    if isinstance(config, ConfigFile):
        config = SimConfig(config)
    if not isinstance(config, SimConfig):
        raise TypeError("config must be a SimConfig, ConfigFile, or "
                        "config INI text")
    return config


def override_clock_scheme(config: SimConfig, scheme: str) -> SimConfig:
    """A SimConfig identical to `config` except for the clock-skew
    management scheme — the per-job `clock_scheme` field's resolution.
    Rebuilt from the flat key dict so every other knob passes through
    untouched."""
    cfg = ConfigFile()
    for k, v in config.cfg.as_dict().items():
        cfg.set(k, v)
    cfg.set("clock_skew_management/scheme", scheme)
    return SimConfig(cfg)


def config_digest(config: SimConfig) -> str:
    """Stable digest of the full flat key dict — the static half of the
    service's program-class key (two jobs whose configs differ in ANY
    key never co-batch; timing values that are traced knobs still live
    in the config, so equal-digest is sufficient, not necessary, for
    program equality — the cache's fingerprint check is the proof)."""
    h = hashlib.sha256()
    for k, v in sorted(config.cfg.as_dict().items()):
        h.update(f"{k}={v}\n".encode())
    return h.hexdigest()


@dataclasses.dataclass
class Job:
    """One simulation request.

    `knobs`: round-7 traced timing-knob overrides (sweep/knobs.py
    KNOB_FIELDS) — same compiled program, different point.
    `telemetry`: an `obs.TelemetrySpec` to record a device timeline for
    this job (jobs with different specs never co-batch — the ring is
    baked into the program).  `profile`: an `obs.ProfileSpec` to record
    the per-tile spatial profile ring (same never-co-batch rule — the
    [S, T, m] ring is baked in too).  `dvfs`: a `dvfs.DvfsSpec`
    attaching the runtime DVFS manager (per-domain carried frequencies;
    same never-co-batch rule — the carried-frequency reads are baked
    into the program, so jobs with differing specs split classes); a
    `dvfs_domain_mhz` knob then seeds this job's operating point and
    co-batches with other points of the same spec.  `hist`: an
    `obs.HistSpec` recording device-resident latency histograms (the
    round-21 int64 bucket ring is baked in — same never-co-batch rule).
    `clock_scheme`:
    override the config's clock-skew management scheme (CLOCK_SCHEMES);
    None keeps the config's own.  `seed`: metadata echoed into the
    result envelope.
    """

    job_id: str
    config: object               # SimConfig | ConfigFile | INI text
    trace: object                # TraceBatch
    knobs: dict = dataclasses.field(default_factory=dict)
    telemetry: object = None     # obs.TelemetrySpec | None
    profile: object = None       # obs.ProfileSpec | None
    dvfs: object = None          # dvfs.DvfsSpec | None
    hist: object = None          # obs.HistSpec | None
    seed: "int | None" = None
    clock_scheme: "str | None" = None

    def __post_init__(self):
        self.config = _coerce_config(self.config)
        self._resolved = None

    @property
    def n_tiles(self) -> int:
        return int(self.trace.n_tiles)

    def resolved_config(self) -> SimConfig:
        """The config this job actually runs under (clock_scheme
        override applied)."""
        if self._resolved is None:
            if self.clock_scheme is None:
                self._resolved = self.config
            else:
                self._resolved = override_clock_scheme(
                    self.config, self.clock_scheme)
        return self._resolved

    def effective_scheme(self) -> str:
        return self.resolved_config().cfg.get_string(
            "clock_skew_management/scheme", "lax_barrier")

    def validate(self, *, validate_trace: bool = True) -> None:
        """Every statically provable admission check; raises ValueError
        (or `trace.validate.TraceValidationError`) naming the problem."""
        from graphite_tpu.sweep.knobs import (
            ALL_KNOB_FIELDS, DVFS_KNOB_FIELD,
        )

        if self.clock_scheme is not None \
                and self.clock_scheme not in CLOCK_SCHEMES:
            raise ValueError(
                f"job {self.job_id!r}: unknown clock_scheme "
                f"{self.clock_scheme!r} (valid: {', '.join(CLOCK_SCHEMES)})")
        sc = self.resolved_config()
        if self.n_tiles != sc.application_tiles:
            raise ValueError(
                f"job {self.job_id!r}: trace has {self.n_tiles} tiles "
                f"but the config expects {sc.application_tiles}")
        unknown = set(self.knobs) - set(ALL_KNOB_FIELDS)
        if unknown:
            raise ValueError(
                f"job {self.job_id!r}: unknown knob(s) {sorted(unknown)} "
                f"(valid: {', '.join(ALL_KNOB_FIELDS)})")
        if "quantum_ps" in self.knobs:
            if self.effective_scheme() != "lax_barrier":
                raise ValueError(
                    f"job {self.job_id!r}: quantum_ps knob needs the "
                    f"lax_barrier clock scheme (the "
                    f"{self.effective_scheme()} scheme has no quantum)")
            if int(self.knobs["quantum_ps"]) <= 0:
                raise ValueError(
                    f"job {self.job_id!r}: quantum_ps must be positive")
        for k, v in self.knobs.items():
            if k == DVFS_KNOB_FIELD:
                vals = [int(x) for x in v]   # a per-domain int vector
                if not vals or any(x <= 0 for x in vals):
                    raise ValueError(
                        f"job {self.job_id!r}: {DVFS_KNOB_FIELD} must "
                        "be a non-empty vector of positive MHz values")
                continue
            int(v)  # raises if not int-coercible
        if DVFS_KNOB_FIELD in self.knobs and self.dvfs is None:
            raise ValueError(
                f"job {self.job_id!r}: the {DVFS_KNOB_FIELD} knob needs "
                "dvfs=DvfsSpec(...) on the job (the carried-frequency "
                "program is opt-in)")
        if self.telemetry is not None:
            from graphite_tpu.obs.telemetry import TelemetrySpec

            if not isinstance(self.telemetry, TelemetrySpec):
                raise ValueError(
                    f"job {self.job_id!r}: telemetry must be an "
                    f"obs.TelemetrySpec")
        if self.profile is not None:
            from graphite_tpu.obs.profile import ProfileSpec

            if not isinstance(self.profile, ProfileSpec):
                raise ValueError(
                    f"job {self.job_id!r}: profile must be an "
                    f"obs.ProfileSpec")
        if self.dvfs is not None:
            from graphite_tpu.dvfs.runtime import DvfsSpec

            if not isinstance(self.dvfs, DvfsSpec):
                raise ValueError(
                    f"job {self.job_id!r}: dvfs must be a dvfs.DvfsSpec")
        if self.hist is not None:
            from graphite_tpu.obs.hist import HistSpec

            if not isinstance(self.hist, HistSpec):
                raise ValueError(
                    f"job {self.job_id!r}: hist must be an obs.HistSpec")
        if validate_trace:
            from graphite_tpu.trace.validate import validate_batch

            validate_batch(self.trace)

    def has_mem_trace(self) -> bool:
        """Does this TRACE carry memory operands?  This is deliberately
        the flags-only predicate — exactly the per-sim agreement check
        `SweepRunner` enforces on a batch — so the class key can never
        co-batch jobs the runner would refuse.  Config-level memory
        switches (enable_shared_mem, enable_icache_modeling) are
        already in the config digest half of the key."""
        from graphite_tpu.trace.schema import FLAG_MEM0_VALID, \
            FLAG_MEM1_VALID

        return bool(np.any(
            self.trace.flags & (FLAG_MEM0_VALID | FLAG_MEM1_VALID)))


@dataclasses.dataclass
class JobResult:
    """The streaming result envelope for one job."""

    job_id: str
    status: str                    # STATUS_OK | STATUS_FAILED
    results: object = None         # SimResults (ok only)
    telemetry: object = None       # obs.Timeline | None
    profile: object = None         # obs.TileProfile | None
    hist: object = None            # obs.Hist | None
    error: "str | None" = None     # failure message (failed only)
    batch_id: "int | None" = None
    attempts: int = 1
    seed: "int | None" = None
    knob_point: "dict | None" = None
    n_quanta: "int | None" = None
    n_iterations: "int | None" = None
    # of `n_iterations`, those in which this job advanced nothing
    # (`SweepOutcome.idle_iterations`; a device counter, in no digest)
    idle_iterations: "int | None" = None
    # what the memory engine's gates skipped in this job's lanes of the
    # batch's program (`SweepOutcome.phase_skips` / `.base_skips`: of
    # `n_iterations`; device counters, not simulated statistics, in no
    # digest), or None where the program counts none
    phase_skips: "dict | None" = None
    base_skips: "dict | None" = None
    # a power / DVFS target's scalars (`sweep/runner.power_row`): the
    # integrated energy summed over tiles (not the telemetry series'),
    # the DVFS_SET requests that took effect, and the CORE domain's
    # frequency where the whole job ended on one (a V/f sweep's point);
    # None where the target reports none
    energy_pj_total: "int | None" = None
    dvfs_transitions: "int | None" = None
    dvfs_level_mhz: "int | None" = None
    # host latency breakdown (round 14) — populated when the service
    # runs with tracing on: {"queue_dwell_s": ..., "batch_execute_s": ...}
    timings: "dict | None" = None

    @property
    def ok(self) -> bool:
        return self.status == STATUS_OK

    def to_json(self) -> dict:
        """One JSON-able dict (the CLI's per-job output line)."""
        row = {"job": self.job_id, "status": self.status,
               "batch": self.batch_id, "attempts": self.attempts}
        if self.seed is not None:
            row["seed"] = int(self.seed)
        if self.knob_point:
            row.update({
                k: (tuple(int(x) for x in v) if isinstance(
                    v, (tuple, list)) else int(v))
                for k, v in self.knob_point.items()})
        if self.ok and self.results is not None:
            r = self.results
            row.update({
                "completion_time_ns": r.completion_time_ps // 1000,
                "total_instructions": r.total_instructions,
                "n_quanta": self.n_quanta,
                "n_iterations": self.n_iterations,
                "func_errors": r.func_errors,
            })
            for k in ("energy_pj_total", "dvfs_transitions",
                      "dvfs_level_mhz"):
                if getattr(self, k) is not None:
                    row[k] = getattr(self, k)
            if self.telemetry is not None:
                row["telemetry_samples"] = len(self.telemetry)
                if "energy_pj" in getattr(self.telemetry, "series", ()):
                    col = self.telemetry.col("energy_pj")
                    if len(col) and not self.telemetry.wrapped:
                        # a delta series: the unwrapped sum is the job's
                        # total energy at its operating point(s) — the
                        # trade-curve's y-axis (wrapped rings undercount,
                        # so the field is omitted rather than wrong)
                        row["energy_pj"] = int(col.sum())
            if self.profile is not None:
                row["profile_samples"] = len(self.profile)
            if self.hist is not None:
                # total event count across sources — a cheap liveness
                # signal; the full counts go to --hist-out npz files
                row["hist_events"] = int(sum(
                    self.hist.total(s) for s in self.hist.sources))
        if self.timings:
            row.update({k: float(v) for k, v in self.timings.items()})
        if self.error is not None:
            row["error"] = self.error
        return row
