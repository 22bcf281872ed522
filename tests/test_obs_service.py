"""End-to-end campaign observability (round 14): the host metrics
registry, job-lifecycle span tracing, and their threading through the
campaign service.

The contract pins:
 - histograms are EXACT on a fake clock: deterministic fixed-bucket
   quantiles (first bucket reaching ceil(q*count)), hand-computed dwell
   values through the real service scheduling path;
 - every submitted job's span chain ends in exactly one terminal span
   (emit / reject / failed), across success, rejection, split/retry and
   exhausted-attempts paths;
 - `counters` is a pure compatibility view over the registry — one
   instrument per rate, identical keys to round 13;
 - exporters round-trip: Prometheus text parses back to the snapshot,
   span JSON-lines reload into the same per-job breakdown;
 - tracing/metrics are host-only: serve results are BIT-EQUAL with
   tracing on vs off (the device program never sees the tracer).
"""

import io
import json

import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.obs import (
    DEFAULT_LATENCY_BUCKETS, EnergyPrices, Histogram, MetricsError,
    MetricsRegistry, TERMINAL_SPANS, Tracer, job_breakdown,
    parse_exposition,
)
from graphite_tpu.obs.trace import load_jsonl
from graphite_tpu.serve import CampaignService, Job, JobResult, \
    QueueFullError, STATUS_OK
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic

TILES = 4


class FakeClock:
    """Monotonic seconds under test control."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _config(clock="lax", tiles=TILES):
    return SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme=clock)))


def _trace(seed, n=8, tiles=TILES):
    return synthetic.memory_stress_trace(
        tiles, n_accesses=n, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=seed)


def _bucket_of(v):
    """The deterministic quantile answer for an observation `v` under
    the default latency buckets (first bound >= v)."""
    return min(b for b in DEFAULT_LATENCY_BUCKETS if b >= v)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_hand_computed_quantiles(self):
        """Exactness on a hand-built observation set: quantile(q) is
        the upper bound of the first bucket whose cumulative count
        reaches ceil(q * count)."""
        h = Histogram("h", buckets=(1, 2, 4, 8))
        for v in (0.5, 1.0, 3.0, 3.0, 5.0, 7.0):
            h.observe(v)
        # counts per bucket: le=1 -> 2, le=2 -> 0, le=4 -> 2, le=8 -> 2
        assert h.counts == [2, 0, 2, 2, 0]
        assert h.count == 6 and h.sum == 19.5
        assert h.quantile(0.5) == 4    # rank 3 -> cum 2,2,4 -> le=4
        assert h.quantile(1 / 3) == 1  # rank 2 -> first bucket
        assert h.quantile(0.9) == 8    # rank 6
        assert h.quantile(1.0) == 8
        assert h.min == 0.5 and h.max == 7.0

    def test_overflow_bucket_resolves_to_true_max(self):
        h = Histogram("h", buckets=(1, 2))
        h.observe(0.5)
        h.observe(100.0)
        assert h.counts == [1, 0, 1]
        assert h.quantile(0.5) == 1
        assert h.quantile(1.0) == 100.0   # +Inf bucket -> exact max

    def test_empty_and_validation(self):
        h = Histogram("h", buckets=(1, 2))
        assert h.quantile(0.5) == 0.0 and h.mean == 0.0
        assert h.min == 0.0 and h.max == 0.0
        with pytest.raises(MetricsError, match="ascending"):
            Histogram("bad", buckets=(2, 1))
        with pytest.raises(MetricsError, match="implicit"):
            Histogram("bad", buckets=(1, float("inf")))
        with pytest.raises(MetricsError, match="outside"):
            h.quantile(0.0)


class TestRegistry:
    def test_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        c = reg.counter("a", "help")
        assert reg.counter("a") is c
        with pytest.raises(MetricsError, match="already registered"):
            reg.gauge("a")
        # two sites disagreeing on a histogram's bucket layout must
        # fail fast, not silently observe into the wrong buckets
        h = reg.histogram("h", buckets=(1, 2))
        assert reg.histogram("h", buckets=(1, 2)) is h
        with pytest.raises(MetricsError, match="buckets"):
            reg.histogram("h", buckets=(1, 2, 4))
        with pytest.raises(MetricsError, match="cannot decrease"):
            c.inc(-1)
        with pytest.raises(MetricsError, match="unknown metric"):
            reg["nope"]

    def test_snapshot_shapes(self):
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        reg.gauge("g").set(1.5)
        h = reg.histogram("h", buckets=(1, 10))
        h.observe(3)
        snap = reg.snapshot()
        assert snap["c"] == 2 and snap["g"] == 1.5
        assert snap["h"]["count"] == 1 and snap["h"]["p50"] == 10
        assert snap["h"]["sum"] == 3.0

    def test_exposition_round_trip(self):
        """Prometheus text -> parse_exposition recovers every counter,
        gauge, and histogram bucket/sum/count exactly."""
        reg = MetricsRegistry()
        reg.counter("jobs_total", "jobs").inc(7)
        reg.gauge("depth").set(3)
        h = reg.histogram("lat_seconds", buckets=(0.01, 0.1, 1))
        for v in (0.005, 0.5, 0.5, 2.0):
            h.observe(v)
        back = parse_exposition(reg.exposition())
        assert back["jobs_total"] == {"type": "counter", "value": 7}
        assert back["depth"] == {"type": "gauge", "value": 3}
        hist = back["lat_seconds"]
        assert hist["type"] == "histogram"
        assert hist["buckets"] == {"0.01": 1, "0.1": 1, "1": 3,
                                   "+Inf": 4}
        assert hist["count"] == 4 and hist["sum"] == pytest.approx(3.005)
        with pytest.raises(MetricsError, match="unknown metric"):
            parse_exposition("rogue_metric 1\n")

    def test_timeline_sampling_fake_clock(self):
        clk = FakeClock(10.0)
        reg = MetricsRegistry(clock=clk, max_timeline=2)
        c = reg.counter("n")
        for i in range(3):
            c.inc()
            clk.advance(1.0)
            reg.sample()
        # bounded: keeps the newest 2 rows, timestamps from the clock
        assert len(reg.timeline) == 2
        assert [row["t_s"] for row in reg.timeline] == [12.0, 13.0]
        assert [row["n"] for row in reg.timeline] == [2, 3]
        rows = [json.loads(ln) for ln
                in reg.timeline_jsonl().splitlines()]
        assert rows == list(reg.timeline)


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_lifecycle_fake_clock(self):
        clk = FakeClock(5.0)
        tr = Tracer(clock=clk)
        s = tr.begin("j0", "submit", seed=3)
        clk.advance(0.25)
        tr.end(s, ok=True)
        assert s.dur_s == 0.25 and s.attrs == {"seed": 3, "ok": True}
        tr.event("j0", "emit")
        rows = tr.to_rows()
        # timestamps are epoch-relative integer microseconds
        assert rows[0] == {"trace": "j0", "span": "submit",
                           "start_us": 0, "dur_us": 250000,
                           "seed": 3, "ok": True}
        assert rows[1]["start_us"] == 250000 and rows[1]["dur_us"] == 0

    def test_record_and_missing_terminal(self):
        tr = Tracer(clock=FakeClock())
        tr.record("j0", "queue", 1.0, 3.5, batch=0)
        tr.event("j0", "emit")
        tr.event("j1", "reject")
        tr.event("j2", "split")   # not terminal
        assert tr.trace("j0")[0].dur_s == 2.5
        assert tr.missing_terminal(["j0", "j1", "j2"]) == ["j2"]
        assert set(TERMINAL_SPANS) == {"emit", "reject", "failed"}

    def test_export_load_breakdown_round_trip(self):
        clk = FakeClock()
        tr = Tracer(clock=clk)
        with tr.span("j0", "submit"):
            clk.advance(0.5)
        tr.record("j0", "queue", clk.t, clk.t + 2.0)
        tr.record("batch-0", "batch", 0.0, 1.0, ok=True)
        tr.event("j0", "emit", batch=0, attempts=1)
        buf = io.StringIO()
        assert tr.export_jsonl(buf) == 4
        buf.seek(0)
        rows = load_jsonl(buf)
        assert len(rows) == 4
        (bd,) = job_breakdown(rows)   # batch-* excluded
        assert bd["job"] == "j0" and bd["status"] == "emit"
        assert bd["submit_us"] == 500000 and bd["queue_us"] == 2000000
        assert bd["total_us"] == 2500000
        assert bd["attempts"] == 1


# ---------------------------------------------------------------------------
# service threading (stubbed execution — no compiles, fake clock)
# ---------------------------------------------------------------------------


def _stub_ok(svc):
    def execute(cls, pendings, batch_id):
        return [JobResult(job_id=p.job.job_id, status=STATUS_OK,
                          batch_id=batch_id, attempts=p.attempts + 1)
                for p in pendings]
    return execute


class TestServiceObservability:
    def test_dwell_histogram_exact_on_fake_clock(self, monkeypatch):
        """Hand-computed queue dwell through the real scheduling path:
        enqueue timestamps, batch-form pop, histogram observation."""
        clk = FakeClock()
        svc = CampaignService(batch_size=4, tracing=True, clock=clk)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("j0", _config(), _trace(1)))
        clk.advance(1.75)
        svc.submit(Job("j1", _config(), _trace(2)))
        clk.advance(0.25)
        out = svc.run_all()
        assert [r.job_id for r in out] == ["j0", "j1"]
        h = svc.metrics["queue_dwell_seconds"]
        # exact: j0 waited 2.0 s, j1 0.25 s (binary-exact floats)
        assert h.count == 2 and h.sum == 2.25
        assert h.max == 2.0 and h.min == 0.25
        assert h.quantile(0.5) == _bucket_of(0.25)
        assert h.quantile(1.0) == _bucket_of(2.0)
        # the envelopes carry the same dwell
        assert out[0].timings["queue_dwell_s"] == 2.0
        assert out[1].timings["queue_dwell_s"] == 0.25
        # and the reconstructed queue spans match exactly
        qs = [s for s in svc.tracer.trace("j0") if s.name == "queue"]
        assert len(qs) == 1 and qs[0].dur_s == 2.0

    def test_span_chain_complete_and_ordered(self, monkeypatch):
        svc = CampaignService(batch_size=2, tracing=True,
                              clock=FakeClock())
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        for i in range(3):
            svc.submit(Job(f"j{i}", _config(), _trace(i + 1)))
        svc.run_all()
        assert svc.tracer.missing_terminal(
            ["j0", "j1", "j2"]) == []
        # the stub bypasses _execute, so no per-job execute span here
        # (the end-to-end test asserts the full chain)
        names = [s.name for s in svc.tracer.trace("j0")]
        # `job` (submit -> envelope) closes just before the terminal span
        assert names == ["validate", "admit", "submit", "queue", "job",
                         "emit"]
        # batch spans carry the execution bookkeeping
        batches = [s for s in svc.tracer.spans if s.name == "batch"]
        assert len(batches) == 2
        assert batches[0].attrs["capacity"] == 2
        assert batches[0].attrs["n_jobs"] == 2
        assert batches[0].attrs["ok"] is True
        assert "class" in batches[0].attrs

    def test_reject_and_backpressure_spans(self):
        svc = CampaignService(batch_size=2, max_pending=1, tracing=True,
                              clock=FakeClock())
        with pytest.raises(ValueError):
            svc.submit(Job("bad", _config(tiles=8), _trace(1)))
        assert svc.tracer.missing_terminal(["bad"]) == []
        assert svc.counters["rejected"] == 1
        svc.submit(Job("ok0", _config(), _trace(1)))
        with pytest.raises(QueueFullError):
            svc.submit(Job("ok1", _config(), _trace(2)))
        assert svc.counters["backpressure"] == 1
        bp = [s for s in svc.tracer.spans if s.name == "backpressure"]
        assert len(bp) == 1 and bp[0].trace_id == "ok1"
        # backpressure is NOT terminal — the job never entered the queue
        assert svc.tracer.missing_terminal(["ok1"]) == ["ok1"]

    def test_split_retry_and_failed_terminal_spans(self, monkeypatch):
        from graphite_tpu.engine.simulator import DeadlockError

        svc = CampaignService(batch_size=4, max_attempts=2,
                              tracing=True, clock=FakeClock())

        def always_fail(cls, pendings, batch_id):
            raise DeadlockError("stuck")

        monkeypatch.setattr(svc, "_execute", always_fail)
        for i in range(2):
            svc.submit(Job(f"j{i}", _config(), _trace(i + 1)))
        out = svc.run_all()
        assert all(not r.ok for r in out) and len(out) == 2
        assert svc.tracer.missing_terminal(["j0", "j1"]) == []
        assert svc.counters["splits"] == 1
        # split depth histogram: both jobs consumed max_attempts
        h = svc.metrics["split_depth"]
        assert h.count == 2 and h.sum == 4.0
        # failed batch spans are recorded with ok=False
        bad = [s for s in svc.tracer.spans
               if s.name == "batch" and not s.attrs["ok"]]
        assert len(bad) == 3   # 1 full batch + 2 singleton retries
        assert all("DeadlockError" in s.attrs["error"] for s in bad)

    def test_requeue_restarts_dwell_clock(self, monkeypatch):
        """A split member's second wait is a second observation from
        the requeue time, not a longer first one."""
        from graphite_tpu.engine.simulator import DeadlockError

        clk = FakeClock()
        svc = CampaignService(batch_size=2, max_attempts=3,
                              tracing=True, clock=clk)
        calls = {"n": 0}

        def fail_once(cls, pendings, batch_id):
            calls["n"] += 1
            if calls["n"] == 1:
                raise DeadlockError("first batch only")
            return _stub_ok(svc)(cls, pendings, batch_id)

        monkeypatch.setattr(svc, "_execute", fail_once)
        svc.submit(Job("j0", _config(), _trace(1)))
        svc.submit(Job("j1", _config(), _trace(2)))
        svc.run_all()
        h = svc.metrics["queue_dwell_seconds"]
        # 2 first waits + 2 post-split waits (fake clock: all zero)
        assert h.count == 4
        assert svc.counters["completed"] == 2

    def test_caller_owned_tracer_shares_the_service_timebase(
            self, monkeypatch):
        """A caller-owned Tracer must not mix timebases with the
        service clock: reconstructed spans (queue dwell) carry
        service-clock timestamps, so the two are reconciled at
        construction."""
        from graphite_tpu.engine.simulator import DeadlockError

        clk = FakeClock(100.0)
        tr = Tracer()   # caller default clock — service clock wins
        svc = CampaignService(batch_size=2, max_attempts=1,
                              tracing=tr, clock=clk)
        assert svc.tracer is tr and tr.clock is clk
        # no explicit clock: the service adopts the tracer's clock
        clk2 = FakeClock(7.0)
        svc2 = CampaignService(tracing=Tracer(clock=clk2))
        assert svc2._clock is clk2

        def fail(cls, pendings, batch_id):
            clk.advance(2.0)   # execution takes 2 s on the fake clock
            raise DeadlockError("x")

        monkeypatch.setattr(svc, "_execute", fail)
        svc.submit(Job("j0", _config(), _trace(1)))
        svc.run_all()
        # the failed-batch span covers the REAL execute window
        # (t0, t0 + wall), unshifted by later metric clock reads
        (bspan,) = [s for s in tr.spans if s.name == "batch"]
        assert bspan.dur_s == 2.0
        (qspan,) = [s for s in tr.trace("j0") if s.name == "queue"]
        assert qspan.t_end == bspan.t_start

    def test_counters_is_registry_view(self, monkeypatch):
        svc = CampaignService(batch_size=2, clock=FakeClock())
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        assert svc.tracer is None   # tracing defaults off
        for i in range(3):
            svc.submit(Job(f"j{i}", _config(), _trace(i + 1)))
        out = svc.run_all()
        assert len(out) == 3 and all(r.timings is None for r in out)
        c = svc.counters
        m = svc.metrics
        assert c["submitted"] == m["jobs_submitted_total"].value == 3
        assert c["completed"] == m["jobs_completed_total"].value == 3
        assert c["batches"] == m["batches_total"].value == 2
        assert c["mean_batch_occupancy"] == \
            m["batch_occupancy"].mean == pytest.approx(0.75)
        # identity: submitted == completed + failed
        assert c["submitted"] == c["completed"] + c["failed"]
        # metrics timeline sampled once per batch
        assert len(m.timeline) == 2


# ---------------------------------------------------------------------------
# energy spec plumbing (no compiles)
# ---------------------------------------------------------------------------


class TestEnergySpec:
    def test_prices_validation(self):
        with pytest.raises(ValueError, match="non-negative integer"):
            EnergyPrices(instruction_pj=-1)
        with pytest.raises(ValueError, match="non-negative integer"):
            EnergyPrices(l2_miss_pj=1.5)
        assert EnergyPrices(l2_miss_pj=3).needs_mem()
        assert not EnergyPrices(instruction_pj=3,
                                packet_pj=1).needs_mem()

    def test_energy_series_needs_prices(self):
        from graphite_tpu.engine.simulator import Simulator
        from graphite_tpu.obs import TelemetrySpec

        sim = Simulator(_config(), _trace(1))
        with pytest.raises(ValueError, match="energy_prices"):
            TelemetrySpec(sample_interval_ps=1,
                          series=("energy_pj",)).resolve(sim.params)
        spec = TelemetrySpec(
            sample_interval_ps=1, series=("energy_pj",),
            energy_prices=EnergyPrices(instruction_pj=1)).resolve(
                sim.params)
        assert spec.series == ("time_ps", "energy_pj")
        # dense selection includes energy exactly when prices are given
        dense_off = TelemetrySpec(sample_interval_ps=1).resolve(
            sim.params)
        dense_on = TelemetrySpec(
            sample_interval_ps=1,
            energy_prices=EnergyPrices()).resolve(sim.params)
        assert "energy_pj" not in dense_off.series
        assert dense_on.series == dense_off.series + ("energy_pj",)

    def test_memoryless_rejects_mem_prices(self):
        from graphite_tpu.engine.simulator import Simulator
        from graphite_tpu.obs import TelemetrySpec

        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, clock_scheme="lax")))
        batch = synthetic.message_ring_batch(TILES, n_rounds=2,
                                             compute_per_round=4)
        sim = Simulator(sc, batch)
        with pytest.raises(ValueError, match="no memory subsystem"):
            TelemetrySpec(
                sample_interval_ps=1,
                energy_prices=EnergyPrices(l2_miss_pj=5)).resolve(
                    sim.params)
        # instruction/packet-only prices are fine on memoryless traces
        spec = TelemetrySpec(
            sample_interval_ps=1,
            energy_prices=EnergyPrices(instruction_pj=2)).resolve(
                sim.params)
        assert "energy_pj" in spec.series

    def test_class_key_splits_on_energy_prices(self):
        from graphite_tpu.obs import TelemetrySpec
        from graphite_tpu.serve import AdmissionController

        adm = AdmissionController()
        t = _trace(1)
        base = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=16)
        priced = TelemetrySpec(
            sample_interval_ps=1_000_000, n_samples=16,
            energy_prices=EnergyPrices(instruction_pj=2))
        priced2 = TelemetrySpec(
            sample_interval_ps=1_000_000, n_samples=16,
            energy_prices=EnergyPrices(instruction_pj=9))
        keys = {adm.class_key(Job("a", _config(), t, telemetry=s))
                for s in (base, priced, priced2)}
        # different prices lower different literals -> never co-batch
        assert len(keys) == 3

    def test_from_power_model_integer_prices(self):
        prices = EnergyPrices.from_power_model(45)
        for f in ("instruction_pj", "l1d_access_pj", "l2_access_pj",
                  "l2_miss_pj", "dram_access_pj", "packet_pj"):
            v = getattr(prices, f)
            assert isinstance(v, int) and v > 0, f
        # bigger node -> no cheaper events (sanity on the native model)
        p90 = EnergyPrices.from_power_model(90)
        assert p90.dram_access_pj >= prices.dram_access_pj


# ---------------------------------------------------------------------------
# end-to-end: tracing on/off bit-equality + CLI renderers (one compile)
# ---------------------------------------------------------------------------


class TestEndToEnd:
    def test_tracing_on_off_bit_equal_and_exporters(self, tmp_path):
        from graphite_tpu.tools.report import main as report_main

        jobs = [("j0", 1), ("j1", 2), ("j2", 3)]

        def run(tracing):
            svc = CampaignService(batch_size=2, max_quanta=200_000,
                                  tracing=tracing)
            for jid, seed in jobs:
                svc.submit(Job(jid, _config(), _trace(seed), seed=seed))
            return svc, {r.job_id: r for r in svc.drain()}

        svc_off, off = run(False)
        svc_on, on = run(True)
        for jid, _ in jobs:
            a, b = off[jid].results, on[jid].results
            np.testing.assert_array_equal(a.clock_ps, b.clock_ps)
            np.testing.assert_array_equal(a.instruction_count,
                                          b.instruction_count)
            for k in a.mem_counters:
                np.testing.assert_array_equal(
                    a.mem_counters[k], b.mem_counters[k], err_msg=k)
            assert on[jid].timings is not None
            assert off[jid].timings is None
        assert svc_on.tracer.missing_terminal(
            [j for j, _ in jobs]) == []
        # the full lifecycle chain, in order, on the real execute path
        assert [s.name for s in svc_on.tracer.trace("j0")] == \
            ["validate", "admit", "submit", "queue", "execute", "job",
             "emit"]

        # span export -> report --spans (text + json)
        spath = str(tmp_path / "spans.jsonl")
        assert svc_on.export_spans(spath) > 0
        assert report_main(["--spans", spath, "--format", "text"]) == 0
        assert report_main(["--spans", spath]) == 0
        # metrics export -> report --metrics
        mpath = str(tmp_path / "metrics.prom")
        with open(mpath, "w") as fh:
            fh.write(svc_on.metrics.exposition())
        assert report_main(["--metrics", mpath,
                            "--format", "text"]) == 0
        back = parse_exposition(open(mpath).read())
        assert back["jobs_completed_total"]["value"] == 3
        assert back["queue_dwell_seconds"]["count"] == 3

    def test_report_modes_are_exclusive(self, capsys):
        from graphite_tpu.tools.report import main as report_main

        with pytest.raises(SystemExit):
            report_main([])
        with pytest.raises(SystemExit):
            report_main(["x.npz", "--spans", "y.jsonl"])
        capsys.readouterr()
