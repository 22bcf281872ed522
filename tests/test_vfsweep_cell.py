"""`vfsweep256-canneal` (benchmark/configs/canneal-dvfs-256-vfsweep.json):
a V/f sweep as a served campaign, held at sizes tier-1 can afford (16 and
64 tiles; the 256-tile target itself is the chip's).

The cell serves `canneal-dvfs-1024`'s target - two DVFS domains, `[general]
enable_power_modeling` - at 256 tiles through `CampaignService`, one job a
row of the 22 nm V/f table, batches of four that mix rows.  So:

- a served batch of four levels == the golden interpreter BIT FOR BIT per
  job on line-disjoint stepped canneal at 16 tiles: clocks, memory
  counters, the V/f table, every energy component (the served path's
  independent witness), and over the six levels of a stream core dynamic
  energy falls and completion time rises row by row;
- the same batch == four solo `Simulator.run()`s bit for bit on the racy
  traffic at 64 tiles, every statistic; against the golden that traffic
  is held inside the configuration's `golden_envelope` by `correct`
  itself, at 256 tiles and for all 12 jobs (the reference's stored golden
  numbers: the arithmetic is checked here, the 64-tile re-run is `slow`);
- a STAGED geometry under the sim axis (the cell's: `dir_stage` forced at
  16 tiles here) == solo, energy included, and a served staged batch
  through both landing kernels (interpreted) lands B * T lanes;
- a power target is its own program class, a device mesh is refused with
  the missing shard spec named, and the rows / envelopes / trade curve
  carry the integrated energy;
- the configuration is what the manifest lists, the driver judges a
  served grid, the three per-layer readers the cell adds read a recorded
  `ctx`.
"""

import dataclasses
import json
import os
import sys
import types

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.obs.trace import Tracer
from graphite_tpu.serve.job import Job, JobResult
from graphite_tpu.serve.service import CampaignService
from graphite_tpu.sweep.runner import SweepRunner
from graphite_tpu.tools import report
from graphite_tpu.trace.benchmarks import DVFS_SCHEDULES
from graphite_tpu.trace.schema import Op

from test_canneal_dvfs import (
    LEVELS_MHZ, assert_equal_statistics, sim_config, stepped,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import checks, digest, paths, target
    from lib.ctx import Ctx
    from probe_golden_hbh import envelope, numbers
    DRIVER = paths.load_module("drivers", "campaign_vf_closed")
finally:
    sys.path.remove(BENCH)

NAME, CELL_NAME = "canneal-dvfs-256-vfsweep", "vfsweep256-canneal"
CELL = target.load_config(NAME)
BIG = target.load_config("canneal-dvfs-1024")
TRAFFIC = paths.load_json("traffic", "campaign-vf-closed.json")
ENV = CELL["golden_envelope"]["statistics"]
REFERENCE = target.load_reference(NAME)
ONE = CELL["control"]["config_text"]["dvfs_domains"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
SERVICE = dict(TRAFFIC["service"])
# a batch that mixes levels, as the cell's do: the fastest, two of the
# middle, the slowest
MIXED = (0, 2, 3, 5)


def level(tiles: int, footprint: int, k: int, **kw):
    """The cell's generator at `tiles` tiles, the whole run at row `k`."""
    return stepped(tiles, footprint, dvfs_schedule=f"level-{k}", **kw)


def serve(sc, traces: dict, **service):
    """{key: envelope} of `traces` ({key: TraceBatch}) through a traced
    service with the cell's arguments, submitted in the dict's order."""
    svc = CampaignService(**{**SERVICE, **service},
                          tracing=Tracer())
    for key, trace in traces.items():
        svc.submit(Job(job_id=str(key), config=sc, trace=trace, seed=7))
    return svc, {e.job_id: e for e in svc.drain(force=True)}


# --- the schedule family -------------------------------------------------

@pytest.mark.parametrize("k", range(6))
def test_level_schedule_is_one_row_everywhere(k):
    """`level-<k>`: every tile asks for row k's frequency at every step;
    nothing else of the trace moves with k."""
    assert DVFS_SCHEDULES[f"level-{k}"](3, 4) == LEVELS_MHZ[k]
    batch, base = level(16, 200, k), level(16, 200, 0)
    sets = batch.op == int(Op.DVFS_SET)
    assert int(sets.sum()) == 16 * 5
    assert set(batch.aux1[sets]) == {LEVELS_MHZ[k]}
    assert set(batch.aux0[sets]) == {0}
    for f in dataclasses.fields(batch):
        a, b = getattr(batch, f.name), getattr(base, f.name)
        same = np.array_equal(a, b) if f.name != "aux1" \
            else np.array_equal(a[~sets], b[~sets])
        assert same, f.name


# --- (i), (iv): served == golden, and the curve's shape -----------------

@pytest.fixture(scope="module")
def served16():
    """The six levels of one line-disjoint stream at 16 tiles through
    the service: a full batch of four and a batch of two, padded."""
    sc = sim_config(16)
    traces = {k: level(16, 12, k, disjoint=True) for k in range(6)}
    svc, envs = serve(sc, traces)
    return sc, traces, svc, envs


@pytest.fixture(scope="module")
def gold16(served16):
    """The golden interpreter on each of `served16`'s six traces."""
    sc, traces = served16[:2]
    return {k: run_golden(sc, trace) for k, trace in traces.items()}


@pytest.mark.parametrize("k", range(6))
def test_served_equals_golden_bit_for_bit(served16, gold16, k):
    """BASELINE.md's contract for race-free traffic, through `serve/`,
    `sweep/` and `vmap`, in a batch whose jobs run at other levels."""
    env = served16[3][str(k)]
    assert env.status == "ok"
    res, gold = env.results, gold16[k]
    assert res.func_errors == 0
    assert_equal_statistics(
        res, gold,
        ["clock_ps", "sync_stall_ps"]
        + ["mem_counters." + n for n in gold.mem_counters]
        + ["dvfs_counters." + n for n in gold.dvfs_counters]
        + ["energy_pj." + n for n in gold.energy_pj])
    assert sorted(gold.energy_pj) == sorted(res.energy_pj)
    assert np.array_equal(
        res.instruction_count, gold.instruction_count
        + gold.recv_instructions + gold.sync_instructions)
    # the whole job ran at its level, and the envelope says so
    assert set(res.dvfs_counters["freq_mhz"][:, 0]) == {LEVELS_MHZ[k]}
    assert set(res.dvfs_counters["freq_mhz"][:, 1]) == {1000}
    assert (env.dvfs_level_mhz, env.dvfs_transitions) \
        == (LEVELS_MHZ[k], 16 * 5)
    assert env.energy_pj_total == int(gold.energy_pj["total"].sum())
    row = env.to_json()
    assert (row["energy_pj_total"], row["dvfs_level_mhz"],
            row["dvfs_transitions"]) == (
        env.energy_pj_total, LEVELS_MHZ[k], 80)


def test_energy_falls_and_time_rises_level_by_level(served16):
    """What a dropped V squared (or a frequency that did not reach the
    core block) would break: over the six jobs of a stream the core's
    dynamic energy falls strictly and completion time rises strictly."""
    envs = served16[3]
    res = [envs[str(k)].results for k in range(6)]
    dyn = [int(r.energy_pj["core_dynamic"].sum()) for r in res]
    done = [r.completion_time_ps for r in res]
    assert dyn == sorted(dyn, reverse=True) and len(set(dyn)) == 6
    assert done == sorted(done) and len(set(done)) == 6
    # dynamic energy goes with V squared alone (the events are the same):
    # 0.80 V against 1.00 V is 0.64, to the femtojoule prices' rounding
    assert dyn[5] / dyn[0] == pytest.approx(0.64, abs=0.002)
    # the jobs of a batch do not end together: the batch runs to the
    # longest of them and the others wait in it
    svc = served16[2]
    assert [(b.n_jobs, b.batch_cap) for b in svc.batch_log] \
        == [(4, 4), (2, 4)]
    assert len({envs[str(k)].n_iterations for k in range(4)}) == 4


def test_batch_records_one_power_demux_span(served16):
    tracer = served16[2].tracer
    for b in (0, 1):
        spans = [s for s in tracer.trace(f"batch-{b}")
                 if s.name == "power_demux"]
        assert len(spans) == 1
        by = {s.name: s for s in tracer.trace(f"batch-{b}")}
        assert spans[0].attrs == {"parent": "results", "sims": 4}
        assert by["results"].t_start <= spans[0].t_start \
            and spans[0].t_end <= by["results"].t_end


# --- (ii): served == solo on the racy traffic, and the envelope ---------

@pytest.fixture(scope="module")
def racy64():
    """The cell's own racy traffic at 64 tiles and 3,906 lines (a
    quarter of the cell: its 61 lines a tile), one batch of four mixed
    levels through the service."""
    sc = sim_config(64)
    traces = {k: level(64, 3906, k) for k in MIXED}
    return sc, traces, serve(sc, traces)[1]


@pytest.mark.parametrize("k", MIXED)
def test_served_equals_solo_on_racy_traffic(racy64, k):
    """Every statistic of `SimResults`, `energy_pj` and `dvfs_counters`
    among them: what the cell's stored digests are made of."""
    sc, traces, envs = racy64
    solo = digest.statistics(Simulator(sc, traces[k]).run())
    got = digest.statistics(envs[str(k)].results)
    assert len(solo) == 54 and sorted(got) == sorted(solo)
    for name in solo:
        np.testing.assert_array_equal(got[name], solo[name], err_msg=name)
    assert int(solo["dvfs_counters.errors"].sum()) == 0


@pytest.mark.slow
@pytest.mark.parametrize("k", (0, 5))
def test_served_inside_the_golden_envelope(racy64, k):
    """On racing lines golden and engine take different valid orders, and
    under a uniform level tiles run in lockstep and tie often: at 64
    tiles too the served job stays inside the configuration's
    `golden_envelope` against the golden at both ends of the table, and
    at 370 MHz the single-domain control does not.  `slow`: the cell's
    own 256 tiles are held by `correct` (the stored golden numbers of
    all 12 jobs), this is the same arithmetic on a re-run quarter."""
    sc, traces, envs = racy64
    gold = numbers(run_golden(sc, traces[k]), ENV)
    rows = envelope(gold, numbers(envs[str(k)].results, ENV), ENV)
    assert [r for r in rows if r[3]] == []
    if k == 5:
        ctl = Simulator(sim_config(64, dvfs_domains=ONE), traces[k]).run()
        assert any(r[3] for r in envelope(gold, numbers(ctl, ENV), ENV))


@pytest.mark.parametrize("k", (0, 5))
def test_stored_envelope_numbers(k):
    """The configuration's stored 256-tile readings (CPU counts, PR 51)
    by the judge's own arithmetic: the golden's numbers are the
    reference's, the engine's lie inside every limit, and every limit
    lies between the engine's largest reading over the 12 jobs and the
    control's - or, where no control moves a statistic, above the
    engine's with room."""
    mhz = LEVELS_MHZ[k]
    stored = lambda w: {s: v[f"{w}_f{mhz}"]             # noqa: E731
                        for s, v in ENV.items()}
    assert stored("golden") \
        == REFERENCE["golden"]["jobs"][DRIVER.job_key(1234, mhz)]
    rows = envelope(stored("golden"), stored("engine"), ENV)
    assert not [r for r in rows if r[3]]
    assert [round(r[1], 4) for r in rows] \
        == [v[f"pct_f{mhz}"] for v in ENV.values()]
    for name, v in ENV.items():
        assert v[f"pct_f{mhz}"] <= v["engine_pct_max"] < v["limit_pct"] / 2
    # the control is outside by the clocks, by the total energy and by
    # every leakage component (which follow the clock; the network's,
    # which it moves into the core's domain, by 38%)
    out = {n for n, v in ENV.items() if v["control_pct_max"] > v["limit_pct"]}
    assert out == {"clock_ps.sum", "clock_ps.max", "energy_pj.total.sum",
                   "energy_pj.total.max"} | {
        n for n in ENV if n.endswith("_static.sum")}
    # canneal-dvfs-1024's twelve statistics first, then every component
    big = BIG["golden_envelope"]["statistics"]
    assert [(v["statistic"], v["reduce"]) for v in ENV.values()][:12] \
        == [(v["statistic"], v["reduce"]) for v in big.values()]
    comps = sorted(REFERENCE["statistics"])
    assert sorted(v["statistic"] for v in list(ENV.values())[12:]) \
        == [c for c in comps
            if c.startswith("energy_pj.") and c != "energy_pj.total"]
    # a dropped V squared, or a job one row off, is outside: the golden's
    # own core dynamic energy moves 7.8-9.3% a row
    dyn = [REFERENCE["golden"]["jobs"][DRIVER.job_key(1234, f)]
           ["energy_pj.core_dynamic.sum"] for f in LEVELS_MHZ]
    step = [100.0 * (a - b) / a for a, b in zip(dyn, dyn[1:])]
    assert min(step) > 70 * ENV["energy_pj.core_dynamic.sum"]["limit_pct"]


# --- (iii): the staged geometry under the sim axis ----------------------

def test_staged_geometry_under_the_sim_axis_equals_solo():
    """The cell's directory is STAGED (sharers store 128 MB at 256
    tiles): under `vmap` the batching rule of `flush_staged` and
    `apply_entry` folds the sims into the lane axis and makes the solo
    choice of the folded store (`row_landing._fold_sims`: the kernels the
    1,024-tile solo cells run, at the cell's 4 x 256 lanes on a TPU); the
    staging overlay's fetch is a batched gather.  Forced at 16 tiles
    here, where the folded 32 lanes' sharers rows are not lane-aligned
    and the CPU lowers the scatter forms anyway: the arithmetic is the
    solo run's, bit for bit."""
    sc = sim_config(16)
    traces = [level(16, 244, k) for k in (0, 5)]
    runner = SweepRunner(sc, traces, dir_stage=True)
    assert runner.sim.params.mem.dir_stage_cap == 96
    out = runner.run()
    # the lane that retunes (370 MHz) against its solo run; the 1000 MHz
    # lane's solo program would be one more compile for the same path
    solo = Simulator(sc, traces[1], dir_stage=True)
    assert solo.params.mem.dir_stage_cap == 96
    want = digest.statistics(solo.run())
    got = digest.statistics(out.results[1])
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    rows = out.json_rows()
    assert [r["dvfs_level_mhz"] for r in rows] == [1000, 370]
    assert [r["energy_pj_total"] for r in rows] == [
        int(r.energy_pj["total"].sum()) for r in out.results]
    assert [r["dvfs_transitions"] for r in rows] == [80, 80]


# 128 sets x 128 ways a slice: at 16 tiles (one sharer word a way) a
# sharers row of 128 words and entry words in whole (8, 128) tiles - the
# smallest directory BOTH landing kernels take
BOTH_KERNELS = "[dram_directory]\ntotal_entries = 16384\nassociativity = 128\n"


def test_served_staged_batch_through_the_kernels_lands_b_times_t_lanes(
        monkeypatch):
    """A served staged batch with both landings through the interpreted
    kernels (on the CPU each choice lowers its default arm, the scatter
    form: swapped for the kernel, as `tests/test_row_landing.py` does for
    the solo engine) ends where the scatter forms end, every statistic,
    and both kernels were handed B * T lanes."""
    import jax.numpy as jnp

    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.memory import row_landing
    from graphite_tpu.tools._template import config_text

    text = dict(CELL["config_text"])
    text.pop("tiles")
    sc = SimConfig(ConfigFile.from_string(
        config_text(16, **text) + BOTH_KERNELS))
    traces = [level(16, 244, k) for k in (0, 5)]
    plain = SweepRunner(sc, traces, dir_stage=True, inner_block=4)
    d = plain.sim.state.mem.directory
    assert d.entry.shape == (16, 256, 128) and d.entry.dtype == np.uint32
    assert d.sharers.shape == (16, 128, 128)
    want = plain.run()
    calls = set()

    def staged(sharers, skey, sval):
        calls.add(("staged", sharers.shape))
        return row_landing.land_staged(
            sharers, skey, sval, jnp.sum(skey >= 0, axis=1, dtype=jnp.int32),
            interpret=True)

    def entry(store, sets, way, delta, live):
        calls.add(("entry", store.shape, sets.shape))
        return row_landing.land_entry(store, sets, way, delta, live,
                                      interpret=True)

    monkeypatch.setattr(row_landing, "scatter_staged", staged)
    monkeypatch.setattr(row_landing, "scatter_entry", entry)
    got = SweepRunner(sc, traces, dir_stage=True, inner_block=4).run()
    # (the 16-lane shapes are `custom_vmap` tracing the solo body before
    # its rule runs; what is lowered is the folded call)
    assert calls == {("staged", (16, 128, 128)), ("staged", (32, 128, 128)),
                     ("entry", (16, 256, 128), (3, 16)),
                     ("entry", (32, 256, 128), (3, 32))}
    for a, b in zip(want.results, got.results):
        assert int(a.mem_counters["dir_accesses"].sum()) > 0
        a, b = digest.statistics(a), digest.statistics(b)
        assert sorted(a) == sorted(b)
        for name in a:
            np.testing.assert_array_equal(a[name], b[name], err_msg=name)


# --- the program class, the mesh, the rows ------------------------------

def test_power_target_is_its_own_program_class():
    """The class key digests the config text: the same trace under the
    power target, under DVFS alone and under neither are three classes,
    and the six levels of a stream are one."""
    from graphite_tpu.serve.admission import AdmissionController

    adm = AdmissionController(batch_size=4)
    trace = level(16, 200, 0)
    keys = {name: adm.class_key(Job(job_id=name, config=sc, trace=trace))
            for name, sc in (("power", sim_config(16)),
                             ("dvfs", sim_config(16, power=False)),
                             ("plain", sim_config(16, power=False,
                                                  dvfs=False,
                                                  dvfs_domains=None)))}
    assert len(set(keys.values())) == 3
    levels = {adm.class_key(Job(job_id=str(k), config=sim_config(16),
                                trace=level(16, 200, k)))
              for k in range(6)}
    assert levels == {keys["power"]}


def test_device_mesh_refuses_power_naming_the_shard_spec():
    """One device serves a power target; a mesh layout still cannot: the
    energy accumulators have no shard spec (`Simulator(mesh=)` refuses
    the same way)."""
    with pytest.raises(NotImplementedError,
                       match="EnergyState.*no shard spec"):
        SweepRunner(sim_config(16), [level(16, 200, 0)] * 2,
                    layout=(1, 2))
    # without power the same layout builds: the refusal is the energy's
    SweepRunner(sim_config(16, power=False), [level(16, 200, 0)] * 2,
                layout=(1, 2))
    # left to itself on several devices (tier-1 forces eight) the runner
    # takes the batch-axis mesh where the batch divides over them - and
    # for a power target the one layout that serves it
    import jax

    n = len(jax.devices())
    assert n > 1
    auto = SweepRunner(sim_config(16, power=False),
                       [level(16, 200, 0)] * n)
    assert auto.layout_name.startswith("1d-batch")
    assert SweepRunner(sim_config(16),
                       [level(16, 200, 0)] * n).layout_name == "solo"
    with pytest.raises(NotImplementedError, match="no shard spec"):
        SweepRunner(sim_config(16), [level(16, 200, 0)] * n,
                    layout="batch")


def test_trade_curve_reads_the_integrated_total(tmp_path):
    """`tools/report.py --trade-curve` on a two-job file: a job with the
    integrated total is plotted by it, not by the telemetry series' sum
    (ROADMAP D20: the two definitions differ); one without keeps the
    series'."""
    rows = [
        {"job": "a", "completion_time_ns": 100, "energy_pj": 900,
         "energy_pj_total": 500, "dvfs_level_mhz": 1000},
        {"job": "b", "completion_time_ns": 200, "energy_pj": 300},
    ]
    path = tmp_path / "rows.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    scatter, frontier = report.energy_trade_rows(rows)
    assert [(s["job"], s["energy_pj"], s["edp_pj_ns"]) for s in scatter] \
        == [("a", 500, 50_000), ("b", 300, 60_000)]
    assert scatter[0]["dvfs_level_mhz"] == 1000
    assert [f["job"] for f in frontier] == ["a", "b"]
    lines = report.render_trade_curve(str(path), "text")
    assert "dvfs_level_mhz" in lines[0] and " 500 " in lines[1]
    # an envelope without power leaves the three fields out of its row
    plain = JobResult(job_id="p", status="ok").to_json()
    assert not {"energy_pj_total", "dvfs_level_mhz",
                "dvfs_transitions"} & set(plain)


# --- (v): the configuration ---------------------------------------------

def test_configuration_is_what_the_manifest_lists():
    entry, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CELL["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CELL["reduced"] == [
        "temperature_steps", "footprint_lines", "tiles"]
    assert sorted(CELL["reduced_detail"]) == sorted(CELL["reduced"])
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL_NAME]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "campaign-vf-closed", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # canneal-dvfs-1024's target, letter for letter but the tile count
    assert {**BIG["config_text"], "tiles": 256} == CELL["config_text"]
    same = set(BIG["expect"]) - {"params.n_tiles", "barrier_host"}
    assert {k: CELL["expect"][k] for k in same} \
        == {k: BIG["expect"][k] for k in same}
    assert CELL["expect"]["params.mem.dir_stage_cap"] == 96
    assert CELL["expect"]["params.dvfs.n_domains"] == 2
    assert CELL["simulator"] == {}
    gen = CELL["trace"]["kwargs"]
    assert (gen["n_tiles"], gen["footprint_lines"], gen["swaps_per_tile"],
            gen["seed"], gen["dvfs_schedule"]) \
        == (256, 15625, 9, 1234, "level-0")
    DRIVER.check_generator(CELL, TRAFFIC)
    assert (TRAFFIC["levels"], TRAFFIC["streams"], TRAFFIC["pool"]) \
        == (list(range(6)), [1234, 1235], [1234, 1235])
    assert (SERVICE["batch_size"], SERVICE["max_dwell_s"],
            SERVICE["n_devices"], SERVICE["store"]) == (4, 0, 1, None)
    added = {"served_lane_idle_share": "%", "power_demux_ms": "ms",
             "served_dvfs_sets_per_job": "count"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in added:
            assert m["workloads"] == [CELL_NAME], m["name"]
            assert (m["moves"], m["unit"]) \
                == ("sim_records_per_s", added[m["name"]])
            paths.load_module("layer_metrics", m["name"])
    assert set(added) <= {m["name"] for m in MANIFEST["per_layer"]}
    for name in ("run_fetch_ms", "home_side_busy_share"):
        m, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
        assert m["workloads"][-1] == CELL_NAME
    reference = REFERENCE
    assert sorted(reference["jobs"]) == sorted(
        DRIVER.job_key(s, f) for s in TRAFFIC["pool"] for f in LEVELS_MHZ)
    assert reference["origins"] == ["cpu-backend", "golden"]
    assert reference["golden"]["origin"] == "golden"
    assert sorted(reference["golden"]["jobs"]) == sorted(reference["jobs"])
    assert all(sorted(g) == sorted(ENV)
               for g in reference["golden"]["jobs"].values())
    assert reference["jobs"][DRIVER.job_key(1234, 1000)] \
        == reference["digest"]
    assert reference["origin"] == "cpu-backend"
    assert {"energy_pj.total", "energy_pj.core_dynamic",
            "dvfs_counters.freq_mhz", "dvfs_counters.errors"} \
        <= set(reference["statistics"])
    assert reference["jobs_statistics"] == 54


def test_expectations_hold_where_the_directory_stages():
    """`expect` at 16 tiles with the staging forced (256 tiles stage by
    themselves: 128 MB of sharers)."""
    sim = Simulator(sim_config(16), level(16, 200, 0), dir_stage=True)
    expect = {k: v for k, v in CELL["expect"].items()
              if k != "params.n_tiles"}
    target.check_expectations({"expect": expect}, sim)
    mem = sim.params.mem
    assert 4 * 256 * mem.dir_sets * mem.dir_ways * 8 >= 64 << 20


# --- the driver's judge and the readers, on a served grid ---------------

@pytest.fixture(scope="module")
def judged(served16, gold16):
    """A `ctx` as the driver leaves it after one grid: `served16`'s six
    envelopes as the window's jobs, their own digests as the stored
    ones and the golden's numbers on their traces as the stored golden
    (what is under test is the judge, not the engine)."""
    sc, traces, svc, envs = served16
    jobs = [{"key": DRIVER.job_key(1234, LEVELS_MHZ[k]), "stream": 1234,
             "level": k, "mhz": LEVELS_MHZ[k], "t_submit": 0.0,
             "t_envelope": 1.0, "envelopes": [envs[str(k)]]}
            for k in range(6)]
    ref = {j["key"]: digest.combined(digest.hashes(digest.statistics(
        j["envelopes"][0].results))) for j in jobs}
    ctx = Ctx(cell={}, config=CELL, traffic=TRAFFIC,
              reference={"origin": "cpu-backend", "jobs": ref, "golden": {
                  "origin": "golden", "jobs": {
                      j["key"]: numbers(gold16[j["level"]], ENV)
                      for j in jobs}}},
              seed=0, seconds=1.0)
    ctx.readings = [{"t0": 0.0, "wall_s": 1.0, "jobs": jobs,
                     "error": None}]
    ctx.attempted, ctx.window_s = 6, 1.0
    ctx.own.update(svc=svc, trace_instructions={
        1234: checks.trace_instructions(traces[0])})
    return ctx


def _judge(ctx):
    lines = []
    return DRIVER.judge(ctx, lines.append), "\n".join(lines)


def test_judge_accepts_a_sound_grid(judged):
    (correct, failed), out = _judge(judged)
    assert correct and failed == 0
    assert "rejected DVFS_SET (or no dvfs_counters): 0" in out
    assert "without energy_pj: 0" in out and "6 of 6" not in out
    assert "0 of 6 compared" in out
    # race-free traffic: the served job IS the golden's, to the picojoule
    assert out.count("worst envelope: 0.0000%") == len(ENV) == 23
    assert "(golden, 6 jobs x 23 statistics): 0 []" in out


@pytest.mark.parametrize("what", ["digest", "rejected", "no_energy",
                                  "lost", "clock", "v_squared"])
def test_judge_refuses(judged, what):
    ctx = dataclasses.replace(judged, readings=[{
        **judged.readings[0],
        "jobs": [dict(j) for j in judged.readings[0]["jobs"]]}])
    job = ctx.readings[0]["jobs"][2]
    env = job["envelopes"][0]
    res = env.results
    if what == "digest":
        ctx.reference = {**ctx.reference, "jobs": {
            **ctx.reference["jobs"], job["key"]: "0" * 64}}
    elif what == "rejected":
        errors = res.dvfs_counters["errors"].copy()
        errors[3] = 1
        job["envelopes"] = [dataclasses.replace(
            env, results=dataclasses.replace(res, dvfs_counters={
                **res.dvfs_counters, "errors": errors}))]
    elif what == "no_energy":
        job["envelopes"] = [dataclasses.replace(
            env, results=dataclasses.replace(res, energy_pj=None))]
    elif what in ("clock", "v_squared"):
        # an ENGINE that is wrong the same way on every backend (the
        # digest would follow it): clocks 2% long, or the core's dynamic
        # energy priced at the neighbouring row's voltage (0.96 V squared)
        stat, scale = {"clock": ("clock_ps.sum", 1 / 1.02),
                       "v_squared": ("energy_pj.core_dynamic.sum",
                                     1 / 0.9216)}[what]
        gold = ctx.reference["golden"]["jobs"]
        ctx.reference = {**ctx.reference, "golden": {
            **ctx.reference["golden"], "jobs": {**gold, job["key"]: {
                **gold[job["key"]],
                stat: int(gold[job["key"]][stat] * scale)}}}}
    else:
        job["envelopes"] = []
    (correct, failed), out = _judge(ctx)
    assert not correct and failed >= 1, out
    if what in ("clock", "v_squared"):
        assert f"1 ['{job['key']}'] (limit 0)" in out.splitlines()[-1]


def _reader(name):
    return paths.load_module("layer_metrics", name)


def test_served_lane_idle_share_reader(judged, capsys):
    read = _reader("served_lane_idle_share").read
    envs = [j["envelopes"][0] for j in judged.readings[0]["jobs"]]
    trips = [max(e.n_iterations for e in envs[:4]),
             max(e.n_iterations for e in envs[4:])]
    lost = sum(e.idle_iterations for e in envs) \
        + sum(trips[0] - e.n_iterations for e in envs[:4]) \
        + sum(trips[1] - e.n_iterations for e in envs[4:]) \
        + 2 * trips[1]                      # the second batch's two pads
    want = 100.0 * lost / (4 * sum(trips))
    assert read(judged) == pytest.approx(want)
    assert 25.0 < want < 100.0
    assert "after it finished" in capsys.readouterr().out
    # envelopes of a program without the counter: nothing, no raise
    bare = types.SimpleNamespace(
        status="ok", batch_id=0, n_iterations=5, idle_iterations=None)
    ctx = types.SimpleNamespace(own={}, readings=[{"jobs": [
        {"envelopes": [bare]}]}])
    assert read(ctx) is None
    assert read(types.SimpleNamespace(own={}, readings=[])) is None


def test_power_demux_ms_reader(judged):
    read = _reader("power_demux_ms").read
    tracer = judged.own["svc"].tracer
    durs = [s.dur_s for s in tracer.spans if s.name == "power_demux"]
    assert len(durs) == 2
    assert read(judged) == pytest.approx(1e3 * sum(durs) / 2)
    # a service without the span (no power; the parent): nothing
    ctx = types.SimpleNamespace(
        own={"svc": types.SimpleNamespace(tracer=Tracer(), batch_log=[])},
        readings=judged.readings)
    assert read(ctx) is None


def test_served_dvfs_sets_per_job_reader(judged, capsys):
    read = _reader("served_dvfs_sets_per_job").read
    assert read(judged) == 16 * 5
    out = capsys.readouterr().out
    assert "at 1000 MHz" in out and "at 370 MHz" in out
    bare = types.SimpleNamespace(status="ok")
    ctx = types.SimpleNamespace(own={}, readings=[{"jobs": [
        {"stream": 1, "envelopes": [bare]}]}])
    assert read(ctx) is None
