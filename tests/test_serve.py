"""Campaign service (graphite_tpu/serve/): admission control, the
fingerprint-keyed compiled-program cache, and the scheduler loop.

The contract pins:
 - jobs served through the batched campaign path are BIT-IDENTICAL
   (results + telemetry) to sequential Simulator runs — the service is
   scheduling, never semantics;
 - N same-fingerprint jobs trigger exactly ONE compile (round-7
   compile-count probe on the cached jitted runner), and a
   registry-mismatched fingerprint at cache-insert time errors loudly;
 - no admitted batch's residency_breakdown total ever exceeds
   `hbm_budget_bytes`; a job that can never fit is rejected at submit
   with the itemized per-consumer breakdown;
 - mixed geometries never co-batch; padded-batch tail masks never leak
   into the result stream; batch-failure split/retry converges; FIFO
   fairness holds under backpressure.
"""

import dataclasses

import numpy as np
import pytest

from graphite_tpu.analysis.cost import ResidencyBudgetError
from graphite_tpu.analysis.registry import ProgramRecord
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import DeadlockError, Simulator
from graphite_tpu.obs import TelemetrySpec
from graphite_tpu.obs.telemetry import SKIP_PREFIX, counts_the_program
from graphite_tpu.serve import (
    AdmissionController, CacheEntry, CampaignService, Job, JobResult,
    ProgramCache, ProgramCacheError, QueueFullError, STATUS_OK,
)
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.validate import TraceValidationError

TILES = 4


def _config(clock="lax"):
    return SimConfig(ConfigFile.from_string(config_text(
        TILES, shared_mem=True, clock_scheme=clock)))


def _trace(seed, n=10, tiles=TILES):
    return synthetic.memory_stress_trace(
        tiles, n_accesses=n, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=seed)


def _assert_results_equal(ra, rb, msg=""):
    np.testing.assert_array_equal(ra.clock_ps, rb.clock_ps, err_msg=msg)
    np.testing.assert_array_equal(
        ra.instruction_count, rb.instruction_count, err_msg=msg)
    assert ra.n_quanta == rb.n_quanta, msg
    if ra.mem_counters is not None:
        for k in ra.mem_counters:
            np.testing.assert_array_equal(
                ra.mem_counters[k], rb.mem_counters[k],
                err_msg=f"{msg}: {k}")


# ---------------------------------------------------------------------------
# job validation
# ---------------------------------------------------------------------------


class TestJobValidation:
    def test_geometry_mismatch(self):
        job = Job("j", _config(), _trace(1, tiles=8))
        with pytest.raises(ValueError, match="tiles"):
            job.validate()

    def test_unknown_knob(self):
        job = Job("j", _config(), _trace(1), knobs={"nope": 3})
        with pytest.raises(ValueError, match="unknown knob"):
            job.validate()

    def test_quantum_knob_needs_lax_barrier(self):
        job = Job("j", _config("lax"), _trace(1),
                  knobs={"quantum_ps": 1000})
        with pytest.raises(ValueError, match="lax_barrier"):
            job.validate()
        # the clock_scheme override can LEGALIZE the knob
        Job("j", _config("lax"), _trace(1), knobs={"quantum_ps": 1000},
            clock_scheme="lax_barrier").validate()

    def test_bad_clock_scheme(self):
        job = Job("j", _config(), _trace(1), clock_scheme="strict")
        with pytest.raises(ValueError, match="clock_scheme"):
            job.validate()

    def test_malformed_trace_rejected(self):
        bad = _trace(1)
        bad = dataclasses.replace(
            bad, op=np.where(bad.op == bad.op[0, 0], np.uint8(250),
                             bad.op))
        with pytest.raises(TraceValidationError):
            Job("j", _config(), bad).validate()

    def test_telemetry_type_checked(self):
        job = Job("j", _config(), _trace(1), telemetry={"interval": 1})
        with pytest.raises(ValueError, match="TelemetrySpec"):
            job.validate()


# ---------------------------------------------------------------------------
# program cache (pure host-side)
# ---------------------------------------------------------------------------


def _entry(name, fp="gfp1:aa", nbytes=100, shape=(2, 4, 16)):
    return CacheEntry(name=name,
                      record=ProgramRecord(name=name, fingerprint=fp,
                                           tiles=4),
                      jitted=lambda *a: None, max_quanta=1000,
                      nbytes=nbytes, shape_sig=shape)


class TestProgramCache:
    def test_byte_accounted_lru_eviction(self):
        cache = ProgramCache(max_bytes=250)
        for k in ("a", "b"):
            cache.put(k, _entry(k), expect_fingerprint="gfp1:aa")
        assert cache.get("a", (2, 4, 16)) is not None  # a now most-recent
        cache.put("c", _entry("c"), expect_fingerprint="gfp1:aa")
        # b was least-recently-used: evicted to fit 250 bytes
        assert cache.keys() == ["a", "c"]
        assert cache.evictions == 1
        assert cache.total_bytes <= 250

    def test_newest_entry_survives_even_over_budget(self):
        cache = ProgramCache(max_bytes=50)
        cache.put("a", _entry("a", nbytes=100),
                  expect_fingerprint="gfp1:aa")
        assert cache.keys() == ["a"]

    def test_insert_fingerprint_mismatch_errors_loudly(self):
        cache = ProgramCache()
        with pytest.raises(ProgramCacheError, match="registered identity"):
            cache.put("a", _entry("a", fp="gfp1:bb"),
                      expect_fingerprint="gfp1:aa")
        assert len(cache) == 0

    def test_shape_sig_mismatch_errors_instead_of_recompiling(self):
        cache = ProgramCache()
        cache.put("a", _entry("a"), expect_fingerprint="gfp1:aa")
        with pytest.raises(ProgramCacheError, match="shape"):
            cache.get("a", (4, 4, 16))


# ---------------------------------------------------------------------------
# admission control (host arithmetic; probes are built, never run)
# ---------------------------------------------------------------------------


class TestAdmission:
    def test_never_fits_rejected_with_itemized_breakdown(self):
        svc = CampaignService(hbm_budget_bytes=1000, batch_size=2)
        with pytest.raises(ResidencyBudgetError,
                           match="can never fit") as ei:
            svc.submit(Job("big", _config(), _trace(1)))
        bd = ei.value.breakdown
        assert set(bd) >= {"state", "trace", "total"}
        assert bd["total"] == bd["state"] + bd["trace"]
        assert "state" in str(ei.value) and "trace" in str(ei.value)
        assert svc.counters["rejected"] == 1

    def test_budget_caps_batch_capacity(self):
        probe = AdmissionController(batch_size=8)
        cls, _ = probe.admit(Job("p", _config(), _trace(1)))
        per_sim = cls.per_sim_total
        adm = AdmissionController(
            hbm_budget_bytes=int(2.5 * per_sim), batch_size=8)
        cls2, _ = adm.admit(Job("q", _config(), _trace(1)))
        assert cls2.batch_cap == 2
        assert cls2.breakdown(cls2.batch_cap)["total"] \
            <= int(2.5 * per_sim)
        # one more sim would not fit
        assert cls2.breakdown(cls2.batch_cap + 1)["total"] \
            > int(2.5 * per_sim)

    def test_backpressure_queue_full(self):
        svc = CampaignService(max_pending=2)
        svc.submit(Job("a", _config(), _trace(1)))
        svc.submit(Job("b", _config(), _trace(2)))
        with pytest.raises(QueueFullError, match="max_pending"):
            svc.submit(Job("c", _config(), _trace(3)))
        # backpressure is not a rejection: the job may resubmit later
        assert svc.counters["backpressure"] == 1
        assert svc.counters["rejected"] == 0
        assert svc.queue_depth == 2

    def test_class_keys_split_on_geometry_and_scheme(self):
        adm = AdmissionController()
        sc8 = SimConfig(ConfigFile.from_string(config_text(
            8, shared_mem=True, clock_scheme="lax")))
        k4 = adm.class_key(Job("a", _config(), _trace(1)))
        k8 = adm.class_key(Job("b", sc8, _trace(1, tiles=8)))
        k4lb = adm.class_key(Job("c", _config(), _trace(1),
                                 clock_scheme="lax_barrier"))
        k4tel = adm.class_key(Job("d", _config(), _trace(1),
                                  telemetry=TelemetrySpec(
                                      sample_interval_ps=1000)))
        assert len({k4, k8, k4lb, k4tel}) == 4
        # same shape + knob-only difference: SAME class (knobs are traced)
        k4b = adm.class_key(Job("e", _config(), _trace(2),
                                knobs={"dram_latency_ns": 99}))
        assert k4b == k4
        # a flags-memless trace keys separately — the exact per-sim
        # agreement SweepRunner enforces, so the runner's mixed-memness
        # refusal is unreachable from the service
        from graphite_tpu.trace.schema import Op
        t = _trace(1)
        memless = dataclasses.replace(
            t, flags=np.zeros_like(t.flags),
            op=np.where(t.op < 20, np.uint8(int(Op.IALU)), t.op))
        k4m = adm.class_key(Job("f", _config(), memless))
        assert k4m != k4

    def test_fifo_across_classes_serves_oldest_head(self):
        adm = AdmissionController(batch_size=2)
        sc8 = SimConfig(ConfigFile.from_string(config_text(
            8, shared_mem=True, clock_scheme="lax")))
        adm.admit(Job("a0", _config(), _trace(1)))
        adm.admit(Job("b0", sc8, _trace(1, tiles=8)))
        adm.admit(Job("a1", _config(), _trace(2)))
        adm.admit(Job("b1", sc8, _trace(2, tiles=8)))
        cls1, batch1 = adm.next_batch()
        assert [p.job.job_id for p in batch1] == ["a0", "a1"]
        cls2, batch2 = adm.next_batch()
        assert [p.job.job_id for p in batch2] == ["b0", "b1"]
        assert adm.next_batch() is None
        assert cls1 is not cls2


# ---------------------------------------------------------------------------
# scheduler policies (stubbed execution — no compiles)
# ---------------------------------------------------------------------------


def _stub_ok(svc):
    def execute(cls, pendings, batch_id):
        svc._last_residency = cls.breakdown(cls.batch_cap)["total"]
        return [JobResult(job_id=p.job.job_id, status=STATUS_OK,
                          batch_id=batch_id, attempts=p.attempts + 1)
                for p in pendings]
    return execute


class TestSchedulerPolicies:
    def test_mixed_geometries_never_cobatched(self, monkeypatch):
        svc = CampaignService(batch_size=4)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        sc8 = SimConfig(ConfigFile.from_string(config_text(
            8, shared_mem=True, clock_scheme="lax")))
        tiles_of = {}
        for i in range(3):
            svc.submit(Job(f"t4-{i}", _config(), _trace(i + 1)))
            tiles_of[f"t4-{i}"] = 4
            svc.submit(Job(f"t8-{i}", sc8, _trace(i + 1, tiles=8)))
            tiles_of[f"t8-{i}"] = 8
        done = svc.run_all()
        assert len(done) == 6
        for rep in svc.batch_log:
            sizes = {tiles_of[j] for j in rep.job_ids}
            assert len(sizes) == 1, f"batch {rep.batch_id} mixed {sizes}"
            assert rep.n_tiles == sizes.pop()
        assert len(svc.batch_log) == 2

    def test_split_retry_converges_to_singletons(self, monkeypatch):
        svc = CampaignService(batch_size=4, max_attempts=5)

        def flaky(cls, pendings, batch_id):
            if len(pendings) > 1:
                raise DeadlockError("multi-job batch poisoned")
            return _stub_ok(svc)(cls, pendings, batch_id)

        monkeypatch.setattr(svc, "_execute", flaky)
        ids = [f"j{i}" for i in range(4)]
        for i, jid in enumerate(ids):
            svc.submit(Job(jid, _config(), _trace(i + 1)))
        done = svc.run_all()
        assert sorted(r.job_id for r in done) == ids
        assert all(r.ok for r in done)
        # FIFO preserved through the splits
        assert [r.job_id for r in done] == ids
        c = svc.counters
        assert c["splits"] >= 2 and c["failed"] == 0
        assert c["completed"] == 4

    def test_always_failing_job_terminates_with_failed_envelope(
            self, monkeypatch):
        svc = CampaignService(batch_size=2, max_attempts=3)

        def always_fail(cls, pendings, batch_id):
            raise DeadlockError("always")

        monkeypatch.setattr(svc, "_execute", always_fail)
        svc.submit(Job("a", _config(), _trace(1)))
        svc.submit(Job("b", _config(), _trace(2)))
        for _ in range(64):   # hard bound: no infinite requeue
            if not svc.queue_depth:
                break
            svc.step()
        assert svc.queue_depth == 0
        done = svc.results
        assert sorted(r.job_id for r in done) == ["a", "b"]
        assert all(not r.ok and "DeadlockError" in r.error for r in done)
        assert all(r.attempts == 3 for r in done)
        assert svc.counters["failed"] == 2

    def test_fifo_order_under_backpressure(self, monkeypatch):
        svc = CampaignService(batch_size=2, max_pending=3)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        order = []
        for i in range(8):
            job = Job(f"j{i}", _config(), _trace(i % 3 + 1))
            while True:
                try:
                    svc.submit(job)
                    break
                except QueueFullError:
                    order.extend(r.job_id for r in svc.step())
        order.extend(r.job_id for r in svc.drain())
        assert order == [f"j{i}" for i in range(8)]


# ---------------------------------------------------------------------------
# end-to-end: real compiles, bit-equality, the compile-count probe
# ---------------------------------------------------------------------------


SERVE_SEEDS = (1, 2, 3)
SERVE_KNOBS = ({}, {"dram_latency_ns": 140}, {"hop_latency_cycles": 3})


@pytest.fixture(scope="module")
def served_campaign():
    """One budgeted service run shared by the end-to-end pins: three
    same-class jobs, batch_size 2 -> a full batch + a PADDED batch
    through one cached program, with hit verification on."""
    probe = AdmissionController(batch_size=2)
    cls, _ = probe.admit(Job("probe", _config(), _trace(1)))
    budget = int(2.4 * cls.per_sim_total)
    svc = CampaignService(hbm_budget_bytes=budget, batch_size=2,
                          max_quanta=200_000, verify_hits=True)
    jobs = [Job(f"j{i}", _config(), _trace(s), knobs=dict(k), seed=s)
            for i, (s, k) in enumerate(zip(SERVE_SEEDS, SERVE_KNOBS))]
    for j in jobs:
        svc.submit(j)
    results = {r.job_id: r for r in svc.drain()}
    return svc, jobs, results, budget


class TestServiceEndToEnd:
    def test_bit_identical_to_sequential(self, served_campaign):
        svc, jobs, results, _ = served_campaign
        assert sorted(results) == [j.job_id for j in jobs]
        for job in jobs:
            sim = Simulator(_config(), job.trace)
            if job.knobs:
                sim.params = dataclasses.replace(
                    sim.params,
                    mem=dataclasses.replace(sim.params.mem, **job.knobs))
            ref = sim.run()
            got = results[job.job_id]
            assert got.ok
            _assert_results_equal(got.results, ref, msg=job.job_id)

    def test_one_compile_for_n_same_fingerprint_jobs(
            self, served_campaign):
        svc, jobs, _, _ = served_campaign
        c = svc.counters
        assert c["compile_count"] == 1
        assert c["cache_hits"] == 1          # batch 2 hit batch 1's entry
        assert c["cache_hit_rate"] == 0.5
        assert len(svc.cache) == 1
        [entry] = svc.cache._entries.values()
        # the round-7 probe: ONE compiled executable served every batch
        assert entry.jitted._cache_size() == 1
        # and the entry resolves through the registry
        assert svc.registry[entry.name].fingerprint \
            == entry.record.fingerprint

    def test_padded_tail_never_leaks(self, served_campaign):
        svc, jobs, results, _ = served_campaign
        assert len(results) == 3             # 2 batches of capacity 2
        full, padded = svc.batch_log
        assert (full.n_jobs, full.batch_cap) == (2, 2)
        assert (padded.n_jobs, padded.batch_cap) == (1, 2)
        assert padded.occupancy == 0.5
        assert svc.counters["mean_batch_occupancy"] == pytest.approx(0.75)

    def test_no_admitted_batch_exceeds_budget(self, served_campaign):
        svc, _, _, budget = served_campaign
        assert svc.batch_log
        for rep in svc.batch_log:
            assert rep.residency_total <= budget, rep

    def test_registry_mismatch_at_insert_errors_loudly(
            self, served_campaign):
        svc, jobs, _, _ = served_campaign
        [name] = list(svc.registry)
        original = svc.registry[name]
        # force the next batch to MISS, with a poisoned registered
        # identity: the re-lowered fingerprint cannot match, and the
        # insert must refuse loudly instead of serving the program
        svc.cache._entries.clear()
        svc.registry[name] = dataclasses.replace(
            original, fingerprint="gfp1:" + "0" * 64)
        try:
            svc.submit(Job("poisoned", _config(), _trace(1)))
            with pytest.raises(ProgramCacheError, match="registered"):
                svc.step()
        finally:
            svc.registry[name] = original
            # the poisoned pending was consumed by the failed step


class TestServeTelemetryAndSchemes:
    def test_telemetry_jobs_equal_sequential_timelines(self):
        tel = TelemetrySpec(sample_interval_ps=1_000_000, n_samples=32)
        svc = CampaignService(batch_size=2, max_quanta=200_000)
        for i, s in enumerate((1, 2)):
            svc.submit(Job(f"t{i}", _config(), _trace(s), telemetry=tel))
        out = {r.job_id: r for r in svc.drain()}
        for i, s in enumerate((1, 2)):
            # the skip_* series and the iterations count what the
            # BATCH's program ran (the gates' predicates and the block's
            # exit are OR-ed over its sims): no solo run is their
            # oracle, every other series has one
            solo = Simulator(_config(), _trace(s), mem_gate_bytes=0,
                             telemetry=tel).run()
            tl = out[f"t{i}"].telemetry
            assert tl is not None
            assert tl.n_total == solo.telemetry.n_total
            skips = np.array([n.startswith(SKIP_PREFIX)
                              for n in tl.series])
            assert skips.any() and not skips.all()
            own = np.array([not counts_the_program(n) for n in tl.series])
            np.testing.assert_array_equal(tl.data[:, own],
                                          solo.telemetry.data[:, own])
            assert tl.data[:, skips].any()
            # the batch's program runs a phase in no fewer iterations
            # than the job's own gated run does
            it = tl.series.index("iterations")
            ran, ran_solo = (t.data[:, it].sum() - t.data[:, skips].sum(0)
                             for t in (tl, solo.telemetry))
            assert (ran >= ran_solo).all(), (ran, ran_solo)
            _assert_results_equal(out[f"t{i}"].results, solo, msg=f"t{i}")

    def test_clock_scheme_axis_batches_separately(self):
        svc = CampaignService(batch_size=2, max_quanta=200_000)
        svc.submit(Job("lb", _config(), _trace(5),
                       clock_scheme="lax_barrier"))
        svc.submit(Job("lx", _config(), _trace(5)))
        out = {r.job_id: r for r in svc.drain()}
        assert len({b.class_name for b in svc.batch_log}) == 2
        ref = Simulator(SimConfig(ConfigFile.from_string(config_text(
            TILES, shared_mem=True, clock_scheme="lax_barrier"))),
            _trace(5)).run()
        _assert_results_equal(out["lb"].results, ref, msg="lax_barrier")
        ref_lax = Simulator(_config(), _trace(5)).run()
        _assert_results_equal(out["lx"].results, ref_lax, msg="lax")
