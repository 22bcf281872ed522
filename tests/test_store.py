"""Persistent AOT program store (graphite_tpu/store/): the on-disk
layout, the integrity/quarantine matrix, locking, GC, the CLI, and the
fleet-amortization contract through the campaign service.

The contract pins:
 - filesystem layer: atomic publication (manifest last), put/get round
   trip, checksum/truncation/version/fingerprint failures each raise a
   NAMED `StoreIntegrityError` AND quarantine the entry (rename to
   `.corrupt-*`) — corrupted artifacts are never served and never
   deleted silently; byte-budgeted LRU GC keeps the most-recently-used
   entry; concurrent writers serialize on the advisory lock and the
   losing writer's blob is discarded (the store stays sound);
 - fleet-once compilation: two fresh `CampaignService` instances over
   one shared store compile a class EXACTLY once total (probe counts
   real `Lowered.compile` calls, not bookkeeping), results bit-equal
   with the store on vs off, and every integrity failure falls back to
   a fresh compile — loudly, never a crash, never a wrong program;
 - the dwell knob: `max_dwell_s` holds an UNDER-FULL batch until its
   head job has waited the window; full batches and requeued splits
   never wait; 0 keeps the wait-for-nothing scheduler.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.serve import CampaignService, Job
from graphite_tpu.store import (
    ProgramStore, StoreIntegrityError, StoreKey,
)
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic

TILES = 4
ENV = ("jax-x", "jaxlib-y", "cpu", 1)


def _key(fp="gfp1:" + "a" * 64, batch=2, max_quanta=1000, env=ENV):
    return StoreKey(fingerprint=fp, batch=batch, max_quanta=max_quanta,
                    env=env)


def _store(tmp_path, **kw):
    return ProgramStore(str(tmp_path / "store"), **kw)


# ---------------------------------------------------------------------------
# filesystem layer (fake blobs, no jax)
# ---------------------------------------------------------------------------


class TestStoreLayout:
    def test_put_get_round_trip(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        man = st.put_blob(key, b"payload-bytes",
                          manifest={"name": "cls-a", "compile_s": 1.5})
        assert man["fingerprint"] == key.fingerprint
        assert man["payload_bytes"] == len(b"payload-bytes")
        blob, man2 = st.get_blob(key)
        assert blob == b"payload-bytes"
        assert man2["name"] == "cls-a"
        assert man2["compile_s"] == 1.5
        # manifest is the publication: both files exist, valid JSON
        edir = os.path.join(st.root, "entries", key.entry_id)
        assert sorted(os.listdir(edir)) == ["last_used", "manifest.json",
                                            "program.bin"]

    def test_miss_is_none_not_error(self, tmp_path):
        assert _store(tmp_path).get_blob(_key()) is None

    def test_key_axes_are_distinct_entries(self, tmp_path):
        st = _store(tmp_path)
        base = _key()
        variants = [
            _key(fp="gfp1:" + "b" * 64),
            _key(batch=4),
            _key(max_quanta=2000),
            _key(env=("jax-z",) + ENV[1:]),
        ]
        ids = {base.entry_id} | {k.entry_id for k in variants}
        assert len(ids) == 5
        st.put_blob(base, b"x")
        for k in variants:
            assert st.get_blob(k) is None

    def test_race_existing_valid_entry_wins(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        st.put_blob(key, b"first", manifest={"name": "first"})
        man = st.put_blob(key, b"second", manifest={"name": "second"})
        assert man["name"] == "first"
        assert st.counters["races"] == 1
        assert st.get_blob(key)[0] == b"first"


class TestIntegrityMatrix:
    """Every named corruption mode: quarantine + named raise + the
    next lookup is a clean miss (so the caller recompiles)."""

    def _filled(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        st.put_blob(key, b"good-payload", manifest={"name": "cls"})
        return st, key, os.path.join(st.root, "entries", key.entry_id)

    def _assert_quarantined(self, st, key, reason):
        with pytest.raises(StoreIntegrityError) as ei:
            st.get_blob(key)
        assert ei.value.reason == reason
        root = os.path.join(st.root, "entries")
        assert any(".corrupt-" in d for d in os.listdir(root))
        assert st.counters["integrity"] == 1
        # quarantined == gone from the serving path: clean miss now
        assert st.get_blob(key) is None

    def test_checksum_corruption(self, tmp_path):
        st, key, edir = self._filled(tmp_path)
        with open(os.path.join(edir, "program.bin"), "wb") as f:
            f.write(b"good-paylobd")    # same length, flipped byte
        self._assert_quarantined(st, key, "checksum")

    def test_truncated_payload(self, tmp_path):
        st, key, edir = self._filled(tmp_path)
        with open(os.path.join(edir, "program.bin"), "wb") as f:
            f.write(b"good")
        self._assert_quarantined(st, key, "truncated")

    def test_missing_payload(self, tmp_path):
        st, key, edir = self._filled(tmp_path)
        os.remove(os.path.join(edir, "program.bin"))
        self._assert_quarantined(st, key, "truncated")

    def test_version_drift(self, tmp_path):
        st, key, edir = self._filled(tmp_path)
        mpath = os.path.join(edir, "manifest.json")
        with open(mpath) as f:
            man = json.load(f)
        man["env"] = ["jax-older"] + man["env"][1:]
        with open(mpath, "w") as f:
            json.dump(man, f)
        self._assert_quarantined(st, key, "version")

    def test_stale_fingerprint_vs_expectation(self, tmp_path):
        """The caller's registry-resolved fingerprint outranks the
        manifest: a stale artifact recompiles, never serves."""
        st, key, edir = self._filled(tmp_path)
        with pytest.raises(StoreIntegrityError) as ei:
            st.get_blob(key, expect_fingerprint="gfp1:" + "f" * 64)
        assert ei.value.reason == "fingerprint"
        assert st.get_blob(key) is None    # quarantined

    def test_malformed_manifest(self, tmp_path):
        st, key, edir = self._filled(tmp_path)
        with open(os.path.join(edir, "manifest.json"), "w") as f:
            f.write("{not json")
        self._assert_quarantined(st, key, "manifest")

    def test_unloadable_payload_quarantines_on_deserialize(
            self, tmp_path):
        """A checksum-valid blob that is not an AOT payload fails at
        the deserialize layer with the same quarantine discipline."""
        st, key, _ = self._filled(tmp_path)
        with pytest.raises(StoreIntegrityError) as ei:
            st.load_executable(key)
        assert ei.value.reason == "deserialize"
        assert st.get_blob(key) is None

    def test_verify_is_nonquarantining(self, tmp_path):
        st, key, edir = self._filled(tmp_path)
        with open(os.path.join(edir, "program.bin"), "ab") as f:
            f.write(b"x")
        [row] = st.verify()
        assert not row["ok"] and row["reason"] == "truncated"
        # verify reported but did NOT move the entry
        assert os.path.isdir(edir)
        assert st.counters["integrity"] == 0

    def test_verify_catches_entry_not_living_at_its_key(self, tmp_path):
        # a dir restored under the wrong id (or a manifest whose key
        # fields were edited consistently with its checksum) would
        # quarantine at the first real request — verify must fail it
        # too, not bless a store that cannot serve
        st, key, edir = self._filled(tmp_path)
        wrong = os.path.join(os.path.dirname(edir), "f" * 40)
        os.rename(edir, wrong)
        [row] = st.verify()
        assert not row["ok"] and row["reason"] == "manifest"
        assert key.entry_id in row["message"]
        assert os.path.isdir(wrong)     # still non-quarantining


class TestGcAndEviction:
    def _fill(self, st, n, size=100):
        keys = []
        for i in range(n):
            k = _key(fp=f"gfp1:{i:064d}")
            st.put_blob(k, bytes(size), manifest={"name": f"c{i}"})
            keys.append(k)
        return keys

    def test_lru_gc_to_byte_budget(self, tmp_path):
        st = _store(tmp_path)
        keys = self._fill(st, 3)
        st.get_blob(keys[0])            # 0 is now most-recently-used
        sizes = {r["entry_id"]: r["bytes"] for r in st.entries()}
        budget = sizes[keys[0].entry_id] + sizes[keys[2].entry_id]
        evicted = st.gc(budget)
        assert evicted == [keys[1].entry_id]
        assert {r["entry_id"] for r in st.entries()} \
            == {keys[0].entry_id, keys[2].entry_id}
        assert st.total_bytes <= budget

    def test_mru_entry_survives_even_over_budget(self, tmp_path):
        st = _store(tmp_path)
        self._fill(st, 2)
        evicted = st.gc(1)              # budget smaller than any entry
        assert len(evicted) == 1
        assert len(st.entries()) == 1

    def test_auto_gc_on_fill(self, tmp_path):
        st = _store(tmp_path)
        st.max_bytes = 1               # every fill triggers eviction
        self._fill(st, 3)
        assert len(st.entries()) == 1
        assert st.counters["evictions"] == 2

    def test_evict_refuses_path_traversal_ids(self, tmp_path):
        # the id is a listing name, never a path: "entries/.." IS the
        # store root and rmtree would eat the whole store
        st = _store(tmp_path)
        keys = self._fill(st, 1)
        for bad in ("..", ".", "", os.path.join("..", "entries"),
                    f"subdir{os.sep}{keys[0].entry_id}"):
            assert not st.evict(bad)
        assert os.path.isdir(os.path.join(st.root, "entries"))
        assert os.path.isdir(os.path.join(st.root, "locks"))
        assert len(st.entries()) == 1
        assert st.counters["evictions"] == 0

    def test_evict_and_purge_corrupt(self, tmp_path):
        st = _store(tmp_path)
        keys = self._fill(st, 2)
        assert st.evict(keys[0].entry_id)
        assert not st.evict(keys[0].entry_id)
        # quarantine the survivor, then purge the wreckage
        st.quarantine(keys[1].entry_id, "checksum")
        assert st.stats()["corrupt"] == 1
        st.gc(include_corrupt=True)
        assert st.stats()["corrupt"] == 0


class TestStoreCli:
    """tools/store.py drives the same layer (`TestIntegrityMatrix`,
    `TestGcAndEviction`), so this pins the exit codes and the flag
    semantics that layer cannot express."""

    def _filled(self, tmp_path, n=2):
        st = _store(tmp_path)
        for i in range(n):
            st.put_blob(_key(fp=f"gfp1:{i:064d}"), bytes(100),
                        manifest={"name": f"c{i}"})
        return st

    def test_gc_zero_budget_is_a_refusal_not_a_noop(self, tmp_path,
                                                    capsys):
        from graphite_tpu.tools.store import main as store_main

        st = self._filled(tmp_path)
        # the store layer reads 0 as unbounded, so a CLI 0 would
        # silently evict nothing while exiting 0 — it must refuse
        assert store_main(["--store", st.root, "gc",
                           "--max-bytes", "0"]) == 2
        assert "--max-bytes must be positive" in capsys.readouterr().err
        assert len(st.entries()) == 2
        assert store_main(["--store", st.root, "gc",
                           "--max-bytes", "1"]) == 0
        assert len(st.entries()) == 1   # MRU survivor

    def test_verify_exits_by_the_stores_soundness(self, tmp_path, capsys):
        from graphite_tpu.tools.store import main as store_main

        st = self._filled(tmp_path)
        assert store_main(["--store", st.root, "verify"]) == 0
        pbin = os.path.join(st.root, "entries",
                            st.entries()[0]["entry_id"], "program.bin")
        with open(pbin, "r+b") as f:
            f.seek(64)
            f.write(b"\xff")           # same length, flipped byte
        assert store_main(["--store", st.root, "verify"]) == 1
        assert "checksum" in capsys.readouterr().out

    def test_nondirectory_store_is_a_clean_exit_2(self, tmp_path,
                                                  capsys):
        from graphite_tpu.tools.store import main as store_main

        f = tmp_path / "not-a-dir"
        f.write_text("x")
        assert store_main(["--store", str(f), "ls"]) == 2
        assert "not a directory" in capsys.readouterr().err


class TestConcurrentWriters:
    def test_flock_serializes_writers(self, tmp_path):
        """A writer holding the entry lock blocks a second writer; the
        store ends sound with exactly one published payload."""
        st = _store(tmp_path)
        key = _key()
        order = []
        release = threading.Event()
        entered = threading.Event()

        def holder():
            with st._lock(key.entry_id):
                entered.set()
                order.append("hold")
                release.wait(10)
                order.append("release")

        def writer():
            entered.wait(10)
            st.put_blob(key, b"from-writer", manifest={"name": "w"})
            order.append("write")

        th, tw = threading.Thread(target=holder), \
            threading.Thread(target=writer)
        th.start()
        tw.start()
        entered.wait(10)
        time.sleep(0.1)        # give the writer time to block
        assert "write" not in order
        release.set()
        th.join(10)
        tw.join(10)
        assert order == ["hold", "release", "write"]
        assert st.get_blob(key)[0] == b"from-writer"

    def test_parallel_put_same_key_single_entry(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        errs = []

        def put(i):
            try:
                st.put_blob(key, f"blob-{i}".encode(),
                            manifest={"name": f"t{i}"})
            except Exception as e:     # noqa: BLE001 - test collects
                errs.append(e)

        threads = [threading.Thread(target=put, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert not errs
        assert len(st.entries()) == 1
        # whichever writer won, the entry is internally consistent
        blob, man = st.get_blob(key)
        assert blob.decode() == f"blob-{man['name'][1:]}"
        assert st.counters["fills"] + st.counters["races"] == 4
        assert st.counters["fills"] >= 1


# ---------------------------------------------------------------------------
# record serialization hardening (analysis/registry.py)
# ---------------------------------------------------------------------------


class TestRecordSerialization:
    def test_round_trip_through_manifest_json(self):
        from graphite_tpu.analysis.registry import ProgramRecord

        rec = ProgramRecord(name="serve-x", fingerprint="gfp1:ab",
                            tiles=8, knobs=("dram_latency_ns",))
        man = json.loads(json.dumps({"name": rec.name, **rec.to_json()}))
        back = ProgramRecord.from_json(man["name"], man)
        assert back == rec

    def test_malformed_record_is_a_clean_valueerror(self):
        from graphite_tpu.analysis.registry import ProgramRecord

        with pytest.raises(ValueError, match="malformed ProgramRecord"):
            ProgramRecord.from_json("x", {"tiles": 4})     # no fingerprint
        with pytest.raises(ValueError, match="malformed ProgramRecord"):
            ProgramRecord.from_json("x", {"fingerprint": "gfp1:ab",
                                          "tiles": "not-an-int"})


# ---------------------------------------------------------------------------
# fleet amortization through the service (real compiles)
# ---------------------------------------------------------------------------


def _config(tiles=TILES):
    return SimConfig(ConfigFile.from_string(config_text(
        tiles, shared_mem=True, clock_scheme="lax")))


def _trace(seed, n=10, tiles=TILES):
    return synthetic.memory_stress_trace(
        tiles, n_accesses=n, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=seed)


def _jobs():
    return [Job(f"j{s}", _config(), _trace(s), seed=s) for s in (1, 2, 3)]


class _CompileCounter:
    """Counts REAL XLA compiles (jax.stages.Lowered.compile calls) —
    the probe that pins 'fleet-once', immune to counter bookkeeping."""

    def __init__(self, monkeypatch):
        import jax

        self.count = 0
        orig = jax.stages.Lowered.compile

        def counting(lowered, *a, **kw):
            self.count += 1
            return orig(lowered, *a, **kw)

        monkeypatch.setattr(jax.stages.Lowered, "compile", counting)


@pytest.fixture(scope="module")
def shared_store_fleet(tmp_path_factory):
    """Two fresh services over ONE store dir, plus a store-off oracle:
    the expensive compile work shared by the fleet pins below."""
    sdir = str(tmp_path_factory.mktemp("fleet") / "store")
    oracle = CampaignService(batch_size=2, max_quanta=200_000)
    for j in _jobs():
        oracle.submit(j)
    oracle_res = {r.job_id: r for r in oracle.drain()}

    svc_a = CampaignService(batch_size=2, max_quanta=200_000, store=sdir)
    for j in _jobs():
        svc_a.submit(j)
    a_res = {r.job_id: r for r in svc_a.drain()}

    svc_b = CampaignService(batch_size=2, max_quanta=200_000, store=sdir)
    warm = svc_b.warm_start()
    for j in _jobs():
        svc_b.submit(j)
    b_res = {r.job_id: r for r in svc_b.drain()}
    return sdir, oracle_res, svc_a, a_res, svc_b, b_res, warm


class TestFleetAmortization:
    def test_store_on_bit_identical_to_store_off(
            self, shared_store_fleet):
        _, oracle_res, _, a_res, _, b_res, _ = shared_store_fleet
        for jid, ref in oracle_res.items():
            for got in (a_res[jid], b_res[jid]):
                assert got.ok
                np.testing.assert_array_equal(
                    got.results.clock_ps, ref.results.clock_ps,
                    err_msg=jid)
                for k in ref.results.mem_counters:
                    np.testing.assert_array_equal(
                        got.results.mem_counters[k],
                        ref.results.mem_counters[k], err_msg=f"{jid}:{k}")

    def test_fleet_compiles_class_exactly_once_total(
            self, shared_store_fleet):
        _, _, svc_a, _, svc_b, _, warm = shared_store_fleet
        ca, cb = svc_a.counters, svc_b.counters
        # process A: the one compile + the fill
        assert ca["compile_count"] == 1
        assert ca["store_misses"] == 1 and ca["store_fills"] == 1
        assert ca["store_hits"] == 0
        # process B: warm-started, ZERO compiles, all store hits
        assert warm == 1
        assert cb["compile_count"] == 0 and cb["store_misses"] == 0
        assert cb["store_hits"] == 1
        assert cb["store_integrity"] == 0
        # B's cache entry knows it came from disk AND what the
        # original miss paid
        [entry] = svc_b.cache._entries.values()
        assert entry.source == "store"
        assert entry.compile_s > 0 and entry.deserialize_s > 0

    def test_second_fleet_member_pays_zero_real_compiles(
            self, shared_store_fleet, monkeypatch):
        """The probe: a THIRD service over the same store serves the
        class with zero `Lowered.compile` calls (counted at the jax
        layer, not our counters)."""
        sdir, oracle_res, *_ = shared_store_fleet
        probe = _CompileCounter(monkeypatch)
        svc = CampaignService(batch_size=2, max_quanta=200_000,
                              store=sdir)
        for j in _jobs():
            svc.submit(j)
        res = {r.job_id: r for r in svc.drain()}
        assert probe.count == 0
        assert svc.counters["store_hits"] == 1
        np.testing.assert_array_equal(
            res["j1"].results.clock_ps,
            oracle_res["j1"].results.clock_ps)

    def test_corrupted_entry_recompiles_loudly_never_serves(
            self, shared_store_fleet, monkeypatch):
        sdir, oracle_res, *_ = shared_store_fleet
        st = ProgramStore(sdir)
        [row] = st.entries()
        p = os.path.join(sdir, "entries", row["entry_id"], "program.bin")
        with open(p, "rb") as f:
            blob = f.read()
        with open(p, "wb") as f:
            f.write(blob[:50] + bytes([blob[50] ^ 0xFF]) + blob[51:])
        try:
            probe = _CompileCounter(monkeypatch)
            svc = CampaignService(batch_size=2, max_quanta=200_000,
                                  store=sdir)
            for j in _jobs():
                svc.submit(j)
            res = {r.job_id: r for r in svc.drain()}
            c = svc.counters
            assert c["store_integrity"] == 1       # quarantined loudly
            assert c["store_hits"] == 0
            assert probe.count == 1                # fell back to compile
            assert c["compile_count"] == 1
            # and the recompiled program is still the right one
            np.testing.assert_array_equal(
                res["j2"].results.clock_ps,
                oracle_res["j2"].results.clock_ps)
            # the wreckage is preserved for forensics
            assert ProgramStore(sdir).stats()["corrupt"] == 1
        finally:
            # the fallback compile re-filled the store; leave it sound
            # for any later test using the fixture
            ProgramStore(sdir).gc(include_corrupt=True)

    def test_store_survives_service_restart_after_quarantine(
            self, shared_store_fleet):
        """After the corruption test's recompile-and-refill, a fresh
        service still warm-starts — the fleet self-heals."""
        sdir, *_ = shared_store_fleet
        svc = CampaignService(batch_size=2, max_quanta=200_000,
                              store=sdir)
        assert svc.warm_start() == 1


# ---------------------------------------------------------------------------
# the dwell knob (stubbed execution, fake clock — no compiles)
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _stub_ok(svc):
    from graphite_tpu.serve import JobResult, STATUS_OK

    def execute(cls, pendings, batch_id):
        svc._last_residency = 0
        return [JobResult(job_id=p.job.job_id, status=STATUS_OK,
                          batch_id=batch_id, attempts=p.attempts + 1)
                for p in pendings]
    return execute


class TestDwellKnob:
    def test_default_zero_runs_immediately(self, monkeypatch):
        clk = _Clock()
        svc = CampaignService(batch_size=4, clock=clk)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("a", _config(), _trace(1)))
        assert len(svc.step()) == 1    # under-full batch, no waiting

    def test_underfull_batch_waits_out_the_window(self, monkeypatch):
        clk = _Clock()
        svc = CampaignService(batch_size=4, clock=clk, max_dwell_s=2.0)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("a", _config(), _trace(1)))
        assert svc.step() == []                 # held: dwell 0 < 2
        assert svc._dwell_wait_s == pytest.approx(2.0)
        clk.advance(1.5)
        assert svc.step() == []                 # still inside the window
        assert svc._dwell_wait_s == pytest.approx(0.5)
        clk.advance(0.5)
        out = svc.step()                        # window over: run it
        assert [r.job_id for r in out] == ["a"]
        # the dwell histogram recorded the wait the knob bought
        assert svc.metrics["queue_dwell_seconds"].max \
            == pytest.approx(2.0)

    def test_full_batch_never_waits(self, monkeypatch):
        clk = _Clock()
        svc = CampaignService(batch_size=2, clock=clk, max_dwell_s=60.0)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("a", _config(), _trace(1)))
        svc.submit(Job("b", _config(), _trace(2)))
        assert len(svc.step()) == 2     # capacity reached: no hold

    def test_filling_during_the_window_releases_early(self, monkeypatch):
        clk = _Clock()
        svc = CampaignService(batch_size=2, clock=clk, max_dwell_s=10.0)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("a", _config(), _trace(1)))
        assert svc.step() == []
        clk.advance(1.0)
        svc.submit(Job("b", _config(), _trace(2)))
        assert len(svc.step()) == 2     # filled: runs 9 s early

    def test_force_and_frozen_clock_drain_terminate(self, monkeypatch):
        clk = _Clock()
        svc = CampaignService(batch_size=4, clock=clk, max_dwell_s=5.0)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("a", _config(), _trace(1)))
        assert len(svc.step(force=True)) == 1
        # a frozen injected clock cannot age the head job: drain must
        # force rather than spin
        svc.submit(Job("b", _config(), _trace(2)))
        out = list(svc.drain())
        assert [r.job_id for r in out] == ["b"]

    def test_full_class_runs_while_held_head_ages(self, monkeypatch):
        """The hold applies to the globally-oldest UNDER-FULL head
        only: a different class whose queue can already fill a batch
        runs immediately (a full batch gains nothing by waiting), and
        the held head keeps aging meanwhile."""
        clk = _Clock()
        svc = CampaignService(batch_size=2, clock=clk, max_dwell_s=60.0)
        monkeypatch.setattr(svc, "_execute", _stub_ok(svc))
        svc.submit(Job("a", _config(), _trace(1)))            # oldest
        svc.submit(Job("b0", _config(8), _trace(1, tiles=8)))
        svc.submit(Job("b1", _config(8), _trace(2, tiles=8)))
        out = svc.step()          # B is FULL: runs despite A's hold
        assert [r.job_id for r in out] == ["b0", "b1"]
        assert svc.step() == []   # A alone again: still held
        clk.advance(60.0)
        assert [r.job_id for r in svc.step()] == ["a"]

    def test_requeued_split_never_waits(self, monkeypatch):
        from graphite_tpu.engine.simulator import DeadlockError

        clk = _Clock()
        svc = CampaignService(batch_size=2, max_attempts=4, clock=clk,
                              max_dwell_s=60.0)
        calls = {"n": 0}

        def flaky(cls, pendings, batch_id):
            calls["n"] += 1
            if len(pendings) > 1:
                raise DeadlockError("poisoned pair")
            return _stub_ok(svc)(cls, pendings, batch_id)

        monkeypatch.setattr(svc, "_execute", flaky)
        svc.submit(Job("a", _config(), _trace(1)))
        svc.submit(Job("b", _config(), _trace(2)))
        assert svc.step() == []          # pair fails, splits
        # the split halves are PRE-FORMED: they run with no dwell hold
        done = [r.job_id for r in svc.step() + svc.step()]
        assert done == ["a", "b"]
        assert calls["n"] == 3


# ---------------------------------------------------------------------------
# reader/writer/GC arbitration under the entry lock
# ---------------------------------------------------------------------------


class TestReaderArbitration:
    """A reader that saw a torn view arbitrates under the entry lock
    before it may quarantine: a concurrently REPAIRED entry serves, a
    concurrently EVICTED entry reads as a clean miss — never a
    quarantined healthy entry, never a phantom integrity alarm for
    routine GC."""

    def _torn(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        st.put_blob(key, b"good-payload", manifest={"name": "cls"})
        edir = os.path.join(st.root, "entries", key.entry_id)
        with open(os.path.join(edir, "program.bin"), "wb") as f:
            f.write(b"good-paylobd")    # checksum fails lock-free
        return st, key, edir

    def test_repaired_entry_serves_instead_of_quarantining(
            self, tmp_path, monkeypatch):
        import contextlib

        st, key, edir = self._torn(tmp_path)
        orig = ProgramStore._lock

        @contextlib.contextmanager
        def lock_after_writer_repaired(store, name):
            with orig(store, name):
                # the racing writer held the lock FIRST and repaired
                with open(os.path.join(edir, "program.bin"), "wb") as f:
                    f.write(b"good-payload")
                yield

        monkeypatch.setattr(ProgramStore, "_lock",
                            lock_after_writer_repaired)
        blob, man = st.get_blob(key)
        assert blob == b"good-payload"
        assert man["name"] == "cls"
        assert st.counters["integrity"] == 0
        assert not any(".corrupt-" in d for d in
                       os.listdir(os.path.join(st.root, "entries")))

    def test_entry_evicted_under_reader_is_a_miss(
            self, tmp_path, monkeypatch):
        import contextlib
        import shutil

        st, key, edir = self._torn(tmp_path)
        orig = ProgramStore._lock

        @contextlib.contextmanager
        def lock_after_gc_evicted(store, name):
            with orig(store, name):
                shutil.rmtree(edir, ignore_errors=True)
                yield

        monkeypatch.setattr(ProgramStore, "_lock", lock_after_gc_evicted)
        assert st.get_blob(key) is None     # a miss, not corruption
        assert st.counters["integrity"] == 0


class TestWarmStartLimit:
    def test_limit_stages_mru_first_and_dedups(self, tmp_path,
                                               monkeypatch):
        from graphite_tpu.store import aot

        env = aot.runtime_env()
        st = _store(tmp_path)
        clk = [100.0]
        st._clock = lambda: clk[0]
        fp1, fp2 = "gfp1:" + "1" * 17, "gfp1:" + "2" * 17
        st.put_blob(_key(fp=fp1, batch=2, max_quanta=777, env=env),
                    b"one", manifest={"name": "one"})
        clk[0] = 200.0
        st.put_blob(_key(fp=fp2, batch=2, max_quanta=777, env=env),
                    b"two", manifest={"name": "two"})
        monkeypatch.setattr(aot, "deserialize_compiled",
                            lambda blob: ("exe", bytes(blob)))
        svc = CampaignService(batch_size=2, max_quanta=777, store=st)
        assert svc.warm_start(limit=1) == 1
        assert list(svc._warm) == [(fp2, 2)]    # MRU staged first
        assert svc.warm_start() == 1            # stages only the rest
        assert set(svc._warm) == {(fp1, 2), (fp2, 2)}

    def test_unreachable_store_is_a_cold_start_not_a_crash(
            self, tmp_path):
        import shutil

        st = _store(tmp_path)
        svc = CampaignService(batch_size=2, max_quanta=777, store=st)
        shutil.rmtree(st.root)
        assert svc.warm_start() == 0


class TestManifestTypeCorruption:
    def test_wrong_typed_field_is_integrity_not_crash(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        st.put_blob(key, b"good-payload", manifest={"name": "cls"})
        mpath = os.path.join(st.root, "entries", key.entry_id,
                             "manifest.json")
        with open(mpath) as f:
            man = json.load(f)
        man["payload_bytes"] = "12a"    # JSON-valid, wrong type
        with open(mpath, "w") as f:
            json.dump(man, f)
        with pytest.raises(StoreIntegrityError) as ei:
            st.get_blob(key)
        assert ei.value.reason == "manifest"
        assert st.get_blob(key) is None    # quarantined

    def test_verify_reports_wrong_type_without_raising(self, tmp_path):
        st = _store(tmp_path)
        key = _key()
        st.put_blob(key, b"good-payload")
        mpath = os.path.join(st.root, "entries", key.entry_id,
                             "manifest.json")
        with open(mpath) as f:
            man = json.load(f)
        man["payload_bytes"] = [12]     # int([12]) raises TypeError
        with open(mpath, "w") as f:
            json.dump(man, f)
        [row] = st.verify()
        assert not row["ok"] and row["reason"] == "manifest"


class TestLockHousekeeping:
    def test_gc_unlinks_orphan_locks_keeps_live_and_corrupt(
            self, tmp_path):
        st = _store(tmp_path)
        keys = [_key(fp=f"gfp1:{i:017d}") for i in range(3)]
        for k in keys:
            st.put_blob(k, b"x" * 8)
        st.evict(keys[0].entry_id)
        st.quarantine(keys[2].entry_id, "checksum")
        st.gc()
        locks = os.listdir(os.path.join(st.root, "locks"))
        assert f"{keys[0].entry_id}.lock" not in locks   # orphan: gone
        assert f"{keys[1].entry_id}.lock" in locks       # live entry
        assert f"{keys[2].entry_id}.lock" in locks       # quarantine
        # the surviving entry still locks and serves
        assert st.get_blob(keys[1])[0] == b"x" * 8
        st.put_blob(keys[0], b"refill")                  # lock recreated
        assert st.get_blob(keys[0])[0] == b"refill"
