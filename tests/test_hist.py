"""Device-resident latency histograms (graphite_tpu/obs/hist.py, round 21).

The contract pins:
 - `hist=None` (the default) lowers the HISTORICAL program — jaxpr
   structurally identical to the legacy entry point, with zero hist
   invars (the telemetry=None / profile=None contract, also enforced
   by the `hist-off` audit lint, which matches whole path segments so
   the pre-existing `line_util_hist` counter never trips it);
 - recording is pure observability: a hist-enabled run's SimResults
   are bit-equal to its hist=None twin;
 - CONSERVATION: every histogram total bit-equals the matching
   cumulative counter (`conservation_totals` documents each pairing) —
   the distribution analogue of round-16's cross-ring sum invariant;
 - boundary-source rows match a hand-stepped chunked oracle
   (run_chunk(1) + host-side searchsorted, one fleet skew observation
   per executed quantum);
 - quantiles use THE one shared definition (obs.metrics
   bucket_quantile), bit-equal to a host metrics Histogram over
   identical buckets;
 - vmapped campaigns demux [B, ...] bucket rings per sim equal to
   sequential runs (shard_map campaigns gather through the same
   demux);
 - serve jobs with differing hist specs never co-batch (distinct
   admission class keys) and the residency bill itemizes the ring;
 - the --perfetto export merges spans + timelines + histograms into
   one valid Chrome-trace JSON with per-pid monotone timestamps.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from graphite_tpu.analysis import rules
from graphite_tpu.analysis.audit import spec_from_simulator
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.obs import (
    HIST_BOUNDARY_SOURCES, HIST_CORE_SOURCES, HIST_MEM_SOURCES, Hist,
    HistSpec, available_hist_sources, conservation_totals,
)
from graphite_tpu.obs.metrics import Histogram, bucket_quantile
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic

import targets

TILES = 8
QUANTUM_PS = 1_000_000   # config_text default: 1000 ns lax_barrier


def _config(extra: str = ""):
    return targets.template_config(TILES, extra, shared_mem=True,
                                   clock_scheme="lax_barrier")


def _trace(seed=7, n=24):
    return targets.stress_trace(TILES, seed=seed, n_accesses=n)


def _ring_batch():
    return synthetic.message_ring_batch(TILES, n_rounds=4,
                                        compute_per_round=8)


class TestSpec:
    def test_edge_validation_matrix(self):
        with pytest.raises(ValueError, match="non-empty"):
            HistSpec(edges=())
        with pytest.raises(ValueError, match="non-negative"):
            HistSpec(edges=(-1, 4))
        with pytest.raises(ValueError, match="strictly ascending"):
            HistSpec(edges=(1, 4, 4))
        with pytest.raises(ValueError, match="strictly ascending"):
            HistSpec(edges=(8, 4))
        with pytest.raises(ValueError, match="log2_buckets"):
            HistSpec(log2_buckets=1)
        # valid: explicit ladder wins over log2_buckets
        spec = HistSpec(edges=(10, 100, 1000))
        np.testing.assert_array_equal(spec.bucket_edges(),
                                      [10, 100, 1000])
        assert spec.n_buckets == 4

    def test_log2_ladder(self):
        spec = HistSpec(log2_buckets=6)
        np.testing.assert_array_equal(spec.bucket_edges(),
                                      [1, 2, 4, 8, 16])
        assert spec.n_buckets == 6

    def test_resolve_selects_and_dedupes(self):
        sim = Simulator(_config(), _trace())
        spec = HistSpec(sources=("miss_lat_ps", "clock_skew_ps",
                                 "miss_lat_ps")).resolve(sim.params)
        assert spec.sources == ("miss_lat_ps", "clock_skew_ps")
        assert spec.n_sources == 2
        assert spec.n_tiles == TILES
        assert spec.resolved

    def test_dense_source_set(self):
        sim = Simulator(_config(), _trace())
        avail = available_hist_sources(sim.params)
        assert avail == (HIST_CORE_SOURCES + HIST_MEM_SOURCES
                         + HIST_BOUNDARY_SOURCES)
        assert HistSpec().resolve(sim.params).sources == avail

    def test_memoryless_program_offers_no_mem_sources(self):
        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, clock_scheme="lax_barrier")))
        sim = Simulator(sc, _ring_batch())
        assert available_hist_sources(sim.params) == \
            HIST_CORE_SOURCES + HIST_BOUNDARY_SOURCES
        with pytest.raises(ValueError, match="unavailable"):
            HistSpec(sources=("miss_lat_ps",)).resolve(sim.params)

    def test_energy_source_needs_prices(self):
        sim = Simulator(_config(), _trace())
        with pytest.raises(ValueError, match="energy_prices"):
            HistSpec(sources=("energy_pj",)).resolve(sim.params)

    def test_buffer_sig_and_ring_bytes(self):
        sim = Simulator(_config(), _trace())
        spec = HistSpec(sources=("miss_lat_ps", "clock_skew_ps"),
                        log2_buckets=16).resolve(sim.params)
        assert spec.buffer_sig() == ((2, 16), "int64")
        assert spec.ring_bytes() == (2 * 16 + 1) * 8
        pt = HistSpec(sources=("clock_skew_ps",), log2_buckets=8,
                      per_tile=True).resolve(sim.params)
        assert pt.buffer_sig() == ((TILES, 1, 8), "int64")
        assert pt.ring_bytes() == (TILES * 8 + 1) * 8
        # tile-sharded per-device bill: the tile axis divides, the
        # boundaries cursor stays replicated
        assert pt.ring_bytes(tile_shards=2) == (TILES // 2 * 8 + 1) * 8
        with pytest.raises(ValueError, match="not divisible"):
            pt.ring_bytes(tile_shards=3)

    def test_attach_rejects_stream_and_requires_spec(self):
        sim = Simulator(_config(), _trace(), stream=True)
        with pytest.raises(ValueError, match="single-device resident"):
            sim.attach_hist(HistSpec())
        sim2 = Simulator(_config(), _trace())
        with pytest.raises(TypeError, match="HistSpec"):
            sim2.attach_hist({"log2_buckets": 16})


class TestProgramIdentity:
    def test_hist_none_is_the_baseline_program(self):
        """hist=None must lower jaxpr-identically to the legacy entry
        point that never heard of histograms, with zero hist invars —
        and the pre-existing `line_util_hist` counter (a path whose
        SUBSTRING contains 'hist') must not trip the segment-matching
        lint."""
        from graphite_tpu.analysis.identity import same_program
        from graphite_tpu.engine.step import run_simulation

        sim = Simulator(_config(), _trace())
        closed_none, paths = sim.lower(max_quanta=512)
        params, qps = sim.params, sim.quantum_ps

        def legacy(st, tr):
            return run_simulation(params, tr, st, qps, 512)

        closed_legacy = jax.make_jaxpr(legacy)(sim.state,
                                               sim.device_trace)
        assert same_program(closed_none, closed_legacy)
        assert any("line_util_hist" in p for p in paths)
        assert not any(
            "hist" in p.split(".")[-1] and "line_util" not in p
            for p in paths)
        assert not rules.telemetry_off(closed_none, paths,
                                       state_key="hist",
                                       rule="hist-off")

    def test_hist_off_lint_fires_on_recording_program(self):
        simt = Simulator(_config(), _trace(), hist=HistSpec())
        closed, paths = simt.lower(max_quanta=512)
        fs = rules.telemetry_off(
            closed, paths, ring_sigs=(simt.hist_spec.buffer_sig(),),
            state_key="hist", rule="hist-off")
        assert fs
        assert all(f.rule == "hist-off" for f in fs)
        assert any("invar" in f.message for f in fs)

    def test_hist_off_lint_catches_internal_ring(self):
        H, B = 4, 16

        def bad(x):
            buf = jnp.zeros((H, B), jnp.int64)
            return buf.at[0, 0].add(x)

        closed = jax.make_jaxpr(bad)(jnp.asarray(1, jnp.int64))
        fs = rules.telemetry_off(closed, ["x"],
                                 ring_sigs=(((H, B), "int64"),),
                                 state_key="hist", rule="hist-off")
        assert fs and fs[0].data["shape"] == [H, B]

    def test_lint_segment_matching_known_bads(self):
        """The path matcher flags real hist state leaves in any
        spelling — attribute, index, quoted key — but never a segment
        that merely CONTAINS 'hist'."""
        closed = jax.make_jaxpr(lambda x: x + 1)(
            jnp.asarray(1, jnp.int64))
        for bad in ("[0].hist.buf", "state.hist.boundaries",
                    "carry['hist'].buf"):
            assert rules.telemetry_off(closed, [bad],
                                       state_key="hist",
                                       rule="hist-off"), bad
        for ok in ("[0].mem.counters.line_util_hist",
                   "state.history_log", "tiles.hist0gram"):
            assert not rules.telemetry_off(closed, [ok],
                                           state_key="hist",
                                           rule="hist-off"), ok

    def test_ring_buffer_forbidden_in_conds(self):
        simt = Simulator(_config(), _trace(), phase_gate=True,
                         mem_gate_bytes=0, hist=HistSpec())
        spec = spec_from_simulator("hist", simt, max_quanta=512)
        assert simt.hist_spec.buffer_sig() in spec.forbidden_cond_avals
        assert spec.expect_hist
        assert not rules.cond_payload(
            spec.closed, forbidden=spec.forbidden_cond_avals)

        sig = simt.hist_spec.buffer_sig()

        def bad(p, buf):
            return jax.lax.cond(p, lambda b: b + 1, lambda b: b, buf)

        closed = jax.make_jaxpr(bad)(True, jnp.zeros(sig[0], jnp.int64))
        assert rules.cond_payload(closed, forbidden=(sig,))

    def test_off_specs_carry_hist_sigs_and_audit_passes(self):
        from graphite_tpu.analysis.audit import audit

        sim = Simulator(_config(), _trace())
        off = spec_from_simulator("off", sim, max_quanta=512)
        assert not off.expect_hist
        assert off.hist_sig is not None

        simt = Simulator(_config(), _trace(), phase_gate=True,
                         mem_gate_bytes=0, hist=HistSpec())
        on = spec_from_simulator("hist-on", simt, max_quanta=512)
        report = audit([off, on])
        assert report.ok, [str(f) for f in report.errors]
        assert "hist-off" in {r.rule for r in report.results
                              if r.program == "off"}
        assert "hist-off" not in {r.rule for r in report.results
                                  if r.program == "hist-on"}


class TestRecording:
    def test_results_bit_equal_and_conserved(self):
        batch = _trace()
        r_off = Simulator(_config(), batch).run()
        sim = Simulator(_config(), batch, hist=HistSpec())
        r_on = sim.run()
        np.testing.assert_array_equal(r_on.clock_ps, r_off.clock_ps)
        np.testing.assert_array_equal(r_on.instruction_count,
                                      r_off.instruction_count)
        for k in r_off.mem_counters:
            np.testing.assert_array_equal(
                r_on.mem_counters[k], r_off.mem_counters[k], err_msg=k)
        assert r_off.hist is None
        h = r_on.hist
        assert isinstance(h, Hist)
        assert not h.per_tile
        assert h.sources == sim.hist_spec.sources
        # THE invariant: every histogram total bit-equals its counter
        cons = conservation_totals(h, r_on,
                                   protocol=sim.params.mem.protocol)
        assert set(cons) == set(h.sources)
        for s, (got, want) in cons.items():
            assert got == want, (s, got, want)
        assert cons["l1d_lat_ps"][0] > 0
        assert cons["miss_lat_ps"][0] > 0
        assert cons["clock_skew_ps"][0] == h.boundaries * TILES
        assert h.boundaries > 0

    def test_core_sources_conserved_on_memoryless_ring(self):
        sc = SimConfig(ConfigFile.from_string(config_text(
            TILES, clock_scheme="lax_barrier")))
        batch = _ring_batch()
        sim = Simulator(sc, batch, hist=HistSpec())
        res = sim.run()
        cons = conservation_totals(res.hist, res)
        for s, (got, want) in cons.items():
            assert got == want, (s, got, want)
        assert cons["net_lat_ps"][0] > 0
        assert cons["recv_stall_ps"][0] > 0

    def test_per_tile_ring_sums_to_aggregate(self):
        batch = _trace()
        agg = Simulator(_config(), batch,
                        hist=HistSpec(log2_buckets=24)).run().hist
        pt = Simulator(
            _config(), batch,
            hist=HistSpec(log2_buckets=24, per_tile=True)).run().hist
        assert pt.per_tile and pt.counts.shape[0] == TILES
        np.testing.assert_array_equal(pt.counts.sum(axis=0),
                                      agg.counts)
        assert pt.boundaries == agg.boundaries
        # counts_for: fleet sum by default, one plane with tile=
        for s in agg.sources:
            np.testing.assert_array_equal(pt.counts_for(s),
                                          agg.counts_for(s))
            assert pt.total(s) == agg.total(s)
        np.testing.assert_array_equal(
            pt.counts_for("clock_skew_ps", tile=3),
            pt.counts[3, pt.sources.index("clock_skew_ps")])
        with pytest.raises(ValueError, match="per_tile"):
            agg.counts_for("clock_skew_ps", tile=0)

    def test_boundary_rows_match_chunked_oracle(self):
        """Hand-stepped oracle: run_chunk(1) executes one quantum per
        call; each call is one whole-fleet skew observation.  The
        host-side searchsorted accumulation must bit-equal the device
        ring."""
        batch = _trace()
        edges = (1_000, 10_000, 100_000, 1_000_000)
        simt = Simulator(_config(), batch,
                         hist=HistSpec(sources=("clock_skew_ps",),
                                       edges=edges))
        h = simt.run().hist

        ref = Simulator(_config(), batch)
        counts = np.zeros(len(edges) + 1, np.int64)
        n = 0
        for _ in range(10_000):
            done, _ = ref.run_chunk(1)
            clocks = np.asarray(
                jax.device_get(ref.state.core.clock_ps), np.int64)
            skew = clocks - clocks.min()
            np.add.at(counts,
                      np.searchsorted(edges, skew, side="right"), 1)
            n += 1
            if done:
                break
        assert done
        assert h.boundaries == n
        np.testing.assert_array_equal(h.counts_for("clock_skew_ps"),
                                      counts)

    def test_barrier_host_dispatch_records_identically(self):
        batch = _trace()
        h_dev = Simulator(_config(), batch,
                          hist=HistSpec()).run().hist
        h_hb = Simulator(_config(), batch, barrier_host=True,
                         barrier_batch=2, hist=HistSpec()).run().hist
        assert h_hb.boundaries == h_dev.boundaries
        np.testing.assert_array_equal(h_hb.counts, h_dev.counts)

    def test_save_load_roundtrip(self, tmp_path):
        h = Simulator(_config(), _trace(),
                      hist=HistSpec(log2_buckets=20)).run().hist
        path = str(tmp_path / "hist.npz")
        h.save(path)
        back = Hist.load(path)
        assert back.sources == h.sources
        assert back.boundaries == h.boundaries
        np.testing.assert_array_equal(back.edges, h.edges)
        np.testing.assert_array_equal(back.counts, h.counts)
        assert back.summary() == h.summary()


class TestQuantiles:
    EDGES = (10, 100, 1_000, 10_000)

    def _hand_hist(self, counts):
        return Hist(sources=("lat",),
                    edges=np.asarray(self.EDGES, np.int64),
                    counts=np.asarray([counts], np.int64),
                    boundaries=0)

    def test_matches_shared_bucket_quantile(self):
        counts = [3, 7, 5, 0, 2]
        h = self._hand_hist(counts)
        for q in (0.01, 0.5, 0.9, 0.95, 0.99, 1.0):
            assert h.quantile("lat", q) == bucket_quantile(
                counts, list(self.EDGES), q, overflow=self.EDGES[-1])
        # cumulative: 3, 10, 15, 15, 17 -> ceil(.5*17)=9 in bucket 1
        assert h.quantile("lat", 0.5) == 100
        # overflow observations saturate at the last edge
        assert h.quantile("lat", 1.0) == 10_000

    def test_matches_host_metrics_histogram(self):
        """Identical buckets, identical counts: the device Hist and the
        host metrics Histogram answer every quantile identically (the
        ONE shared bucket_quantile definition)."""
        counts = [4, 0, 9, 2, 0]   # nothing in the +Inf/overflow tail
        h = self._hand_hist(counts)
        m = Histogram("lat", buckets=self.EDGES)
        m.counts = list(counts)
        m.count = sum(counts)
        for q in (0.25, 0.5, 0.75, 0.99, 1.0):
            assert h.quantile("lat", q) == m.quantile(q)

    def test_device_run_quantiles_consistent(self):
        sim = Simulator(_config(), _trace(), hist=HistSpec())
        h = sim.run().hist
        for s in h.sources:
            p50 = h.quantile(s, 0.5)
            p99 = h.quantile(s, 0.99)
            assert p50 <= p99
            assert p99 == bucket_quantile(
                [int(c) for c in h.counts_for(s)],
                [int(e) for e in h.edges], 0.99,
                overflow=int(h.edges[-1]))
        summ = h.summary()
        assert summ["miss_lat_ps_p99"] == h.quantile("miss_lat_ps",
                                                     0.99)
        assert summ["miss_lat_ps_count"] == h.total("miss_lat_ps")


class TestSweepDemux:
    def test_vmap_campaign_demuxes_per_sim_hists(self):
        from graphite_tpu.sweep import SweepRunner

        seeds = (1, 2, 3)
        traces = [_trace(seed=s) for s in seeds]
        sweep = SweepRunner(_config(), traces, shard_batch=False,
                            hist=HistSpec())
        out = sweep.run()
        assert out.hists is not None and len(out.hists) == 3
        proto = sweep.sim.params.mem.protocol
        for b in range(3):
            hb = out.hists[b]
            assert out.results[b].hist is hb
            solo = Simulator(_config(), traces[b],
                             mailbox_depth=sweep.mailbox_depth,
                             phase_gate=False, mem_gate_bytes=0,
                             hist=HistSpec()).run().hist
            assert hb.boundaries == solo.boundaries
            np.testing.assert_array_equal(hb.counts, solo.counts,
                                          err_msg=f"sim {b}")
            cons = conservation_totals(hb, out.results[b],
                                       protocol=proto)
            assert all(a == c for a, c in cons.values())

    def test_shard_map_campaign_gathers_device_buffers(self):
        from graphite_tpu.sweep import SweepRunner

        if len(jax.devices()) < 2:
            pytest.skip("needs the multi-device CPU platform")
        B = len(jax.devices())
        traces = [_trace(seed=s) for s in range(B)]
        sweep = SweepRunner(_config(), traces, shard_batch=True,
                            hist=HistSpec())
        out = sweep.run()
        assert len(out.hists) == B
        for b in (0, B - 1):
            solo = Simulator(_config(), traces[b],
                             mailbox_depth=sweep.mailbox_depth,
                             hist=HistSpec()).run().hist
            assert out.hists[b].boundaries == solo.boundaries
            np.testing.assert_array_equal(out.hists[b].counts,
                                          solo.counts,
                                          err_msg=f"sim {b}")

    def test_campaign_residency_itemizes_hist_rings(self):
        from graphite_tpu.sweep import SweepRunner

        traces = [_trace(seed=s) for s in (1, 2)]
        sweep = SweepRunner(_config(), traces, shard_batch=False,
                            hist=HistSpec())
        bd = sweep.residency_breakdown()
        assert bd["hist"] == 2 * sweep.sim.hist_spec.ring_bytes()


class TestServe:
    def test_class_key_splits_on_hist_spec(self):
        from graphite_tpu.serve import CampaignService, Job

        svc = CampaignService(batch_size=4)
        batch = _trace()
        j_off = Job("off", _config(), batch)
        j_a = Job("a", _config(), batch, hist=HistSpec())
        j_b = Job("b", _config(), batch,
                  hist=HistSpec(edges=(100, 1000)))
        j_a2 = Job("a2", _config(), batch, hist=HistSpec())
        keys = [svc.admission.class_key(j)
                for j in (j_off, j_a, j_b, j_a2)]
        assert keys[1] != keys[0]
        assert keys[1] != keys[2]
        assert keys[1] == keys[3]

    def test_job_validate_rejects_non_spec(self):
        from graphite_tpu.serve import Job

        with pytest.raises((TypeError, ValueError)):
            Job("bad", _config(), _trace(),
                hist={"log2_buckets": 16}).validate()

    def test_admission_bill_includes_hist_ring(self):
        from graphite_tpu.serve import CampaignService, Job

        svc = CampaignService(batch_size=2)
        job = Job("h", _config(), _trace(), hist=HistSpec())
        cls, _ = svc.admission.admit(job)
        assert cls.per_sim_bytes["hist"] == cls.hist.ring_bytes()
        assert "-hist" in svc._class_name(cls)

    def test_serve_cli_hist_out_writes_npz(self, tmp_path, capsys):
        from graphite_tpu.tools.serve import main as serve_main

        jobs = tmp_path / "jobs.jsonl"
        jobs.write_text(json.dumps({
            "id": "cli0", "tiles": 4, "seed": 1, "accesses": 8,
            "hist": {"log2_buckets": 24}}) + "\n")
        out_dir = tmp_path / "hists"
        assert serve_main(["--jobs", str(jobs), "--batch-size", "1",
                           "--hist-out", str(out_dir)]) == 0
        lines = [json.loads(ln) for ln in
                 capsys.readouterr().out.strip().splitlines()]
        row = next(r for r in lines if r.get("job") == "cli0")
        # one line a batch: what JAX traced, lowered, compiled or loaded
        # for it (the program ledger's delta) beside the compile count
        (batch,) = [r for r in lines if "programs" in r]
        assert (batch["batch"], batch["compile_count"]) == (0, 1)
        assert batch["programs"]["programs_lowered"] >= 1
        path = row["hist_file"]
        assert path == str(out_dir / "cli0.npz")
        saved = Hist.load(path)
        assert row["hist_events"] == sum(saved.totals().values())
        assert saved.total("l1d_lat_ps") > 0


class TestPerfetto:
    SPANS = [
        {"trace": "batch-0", "span": "batch", "start_us": 5,
         "dur_us": 900, "n_jobs": 1},
        {"trace": "j0", "span": "queue", "start_us": 0, "dur_us": 100},
    ]

    def test_unified_export_round_trip(self, tmp_path, capsys):
        from graphite_tpu.obs import TelemetrySpec
        from graphite_tpu.tools.report import main as report_main

        res = Simulator(
            _config(), _trace(),
            telemetry=TelemetrySpec(sample_interval_ps=QUANTUM_PS,
                                    n_samples=64),
            hist=HistSpec()).run()
        tl_path = str(tmp_path / "tl.npz")
        h_path = str(tmp_path / "hist.npz")
        res.telemetry.save(tl_path)
        res.hist.save(h_path)
        spans = tmp_path / "spans.jsonl"
        spans.write_text("".join(json.dumps(r) + "\n"
                                 for r in self.SPANS))
        out = str(tmp_path / "trace.json")
        assert report_main([tl_path, "--spans", str(spans),
                            "--hist", h_path,
                            "--perfetto", out]) == 0
        printed = json.loads(capsys.readouterr().out.strip())
        doc = json.load(open(out))
        assert doc["displayTimeUnit"] == "ns"
        evs = doc["traceEvents"]
        assert printed == {"perfetto": out, "events": len(evs)}

        # metadata first: both clock-track processes are named
        assert [e["ph"] for e in evs[:2]] == ["M", "M"]
        assert {e["pid"] for e in evs[:2]} == {1, 2}

        # host track: one X event per span row, us timestamps
        xs = [e for e in evs if e["ph"] == "X"]
        assert len(xs) == len(self.SPANS)
        assert all(e["pid"] == 1 for e in xs)
        assert {e["name"] for e in xs} == {"batch", "queue"}

        # sim track: telemetry counters + one instant per hist source
        cs = [e for e in evs if e["ph"] == "C"]
        assert cs and all(e["pid"] == 2 for e in cs)
        instants = {e["name"]: e for e in evs if e["ph"] == "i"}
        h = res.hist
        for s in h.sources:
            ev = instants[f"hist0.{s}"]
            assert ev["args"]["count"] == h.total(s)
            assert ev["args"]["p50"] == h.quantile(s, 0.5)
            assert ev["args"]["p99"] == h.quantile(s, 0.99)

        # write_perfetto's invariant: per-pid monotone timestamps
        for pid in (1, 2):
            ts = [e["ts"] for e in evs
                  if e["pid"] == pid and e["ph"] != "M"]
            assert ts == sorted(ts)

    def test_mode_validation(self, tmp_path):
        from graphite_tpu.tools.report import main as report_main

        h = tmp_path / "h.npz"
        Hist(sources=("lat",), edges=np.asarray([1], np.int64),
             counts=np.asarray([[0, 0]], np.int64),
             boundaries=0).save(str(h))
        # --hist outside perfetto mode is an argparse error
        with pytest.raises(SystemExit):
            report_main(["--hist", str(h)])
        # --perfetto with no inputs is an argparse error
        with pytest.raises(SystemExit):
            report_main(["--perfetto", str(tmp_path / "o.json")])
        # hist-only export works
        assert report_main(["--perfetto", str(tmp_path / "o.json"),
                            "--hist", str(h)]) == 0
