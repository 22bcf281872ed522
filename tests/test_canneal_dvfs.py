"""`canneal1024-dvfs` (benchmark/configs/canneal-dvfs-1024.json): what the
cell assumes of the program, held at sizes tier-1 can afford (16 and 64
tiles; the 1024-tile target itself is `benchmark/probe_golden_dvfs.py`'s
and the chip's).

The cell is BASELINE.json's fifth graduated configuration: the 1024-tile
coherent target with the core and its caches in one DVFS domain,
directory and networks in another, every tile retuned at every
temperature step of a stepped canneal, and energy integrated interval by
interval.  So:

- `tools/_template.config_text`: unchanged text with the defaults, a
  `domains` line and a technology node that their readers find;
- `canneal_trace`: unchanged records with the defaults, the schedule's
  records where it is asked for;
- energy and the V/f table as statistics (`SimResults.energy_pj`,
  `.dvfs_counters`): equal to the golden interpreter's BIT FOR BIT on
  line-disjoint stepped canneal across retunes, equal between the
  host-driven and the single-region program, in agreement with the float
  host pass (`TileEnergyMonitor`) where that is right (no transition)
  and below it where it is not, untouched by a rejected request, and
  absent (no leaf, no operation) with power modelling off;
- the configuration's `golden_envelope` on the cell's own racy traffic at
  64 tiles;
- the three per-layer readers the cell adds, on a recorded `ctx`.
"""

import dataclasses
import hashlib
import json
import os
import sys
import types

import jax
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.memory.params import MemParams
from graphite_tpu.models.dvfs import DvfsParams
from graphite_tpu.power.accounting import FJ_PER_PJ, EnergyParams
from graphite_tpu.power.interface import TileEnergyMonitor
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.benchmarks import canneal_trace
from graphite_tpu.trace.schema import (
    FLAG_MEM0_VALID, Op, TraceBatch, TraceBuilder,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import digest, paths, target
    from probe_golden_hbh import envelope, numbers
finally:
    sys.path.remove(BENCH)

NAME, CELL_NAME = "canneal-dvfs-1024", "canneal1024-dvfs"
CELL = target.load_config(NAME)
GEN = CELL["trace"]["kwargs"]
ENV = CELL["golden_envelope"]["statistics"]
TWO = CELL["config_text"]["dvfs_domains"]
ONE = CELL["control"]["config_text"]["dvfs_domains"]
LEVELS_MHZ = (1000, 870, 750, 630, 500, 370)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def sim_config(tiles: int, **text) -> SimConfig:
    """The cell's target at `tiles` tiles."""
    return target.build_sim_config(
        {"config_text": {**CELL["config_text"], **text, "tiles": tiles}})


def stepped(tiles: int, footprint: int, disjoint: bool = False,
            **kw) -> TraceBatch:
    """The cell's generator at `tiles` tiles; `disjoint` moves every
    tile's lines into a region of its own (no race: the golden's
    ordering contract holds)."""
    batch = canneal_trace(**{**GEN, "n_tiles": tiles,
                             "footprint_lines": footprint, **kw})
    if disjoint:
        mem = (batch.flags & FLAG_MEM0_VALID) != 0
        base = np.arange(tiles, dtype=np.uint32)[:, None] * np.uint32(
            footprint * 64)
        batch.addr0[:] = np.where(mem, batch.addr0 + base, batch.addr0)
    return batch


def sha(batch: TraceBatch) -> str:
    h = hashlib.sha256()
    for f in dataclasses.fields(batch):
        a = np.ascontiguousarray(getattr(batch, f.name))
        h.update(f"{f.name}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# --- tools/_template.config_text ---------------------------------------

# sha256 of config_text(n, ...) as the parent of PR 44 wrote it
TEXT_PINS = {
    (64, ()): "68545580c89239b34b90bcc4c73d432a7baf6a5ead6694505b84de1e663f8a12",
    (1024, (("shared_mem", True), ("core", "iocoom"))): "816a3339081a0ea06f5fafa20f37ba0f08d4b8c8c2b9a85b5a3ef60ef3d8e609",
}


@pytest.mark.parametrize("key", sorted(TEXT_PINS, key=str))
def test_config_text_defaults_are_unchanged(key):
    tiles, kw = key
    text = config_text(tiles, **dict(kw))
    assert hashlib.sha256(text.encode()).hexdigest() == TEXT_PINS[key]
    assert "dvfs" not in text and "technology_node" not in text
    assert "enable_power_modeling" not in text


def test_config_text_dvfs_parses_to_the_one_default_domain():
    cfg = ConfigFile.from_string(config_text(16, dvfs=True))
    assert cfg.has("dvfs/domains") and not cfg.has("dvfs/domains/domains")
    dvp = DvfsParams.from_config(cfg)
    assert (dvp.n_domains, dvp.core_domain) == (1, 0)
    assert dvp.module_domains == (0,) * 7
    assert SimConfig(cfg).enable_power_modeling is False
    with pytest.raises(ValueError, match="dvfs_domains needs dvfs"):
        config_text(16, dvfs_domains=TWO)


def test_config_text_two_domains_reach_the_engine():
    sc = SimConfig(ConfigFile.from_string(config_text(
        16, shared_mem=True, dvfs=True, dvfs_domains=TWO, power=True)))
    dvp = DvfsParams.from_config(sc.cfg)
    assert (dvp.n_domains, dvp.core_domain, dvp.sync_delay_cycles) \
        == (2, 0, 2)
    assert dvp.module_domains == (0, 0, 0, 0, 1, 1, 1)
    assert dvp.levels_text == CELL["expect"]["params.dvfs.levels_text"]
    assert sc.enable_power_modeling is True
    # the asynchronous boundary: L2 (3) <-> DIRECTORY (4) crosses, core
    # and its caches do not; one domain has no crossing at all
    mp = MemParams.from_config(sc)
    assert (mp.sync_cycles(3, 4), mp.sync_cycles(0, 3)) == (2, 0)
    one = MemParams.from_config(SimConfig(ConfigFile.from_string(
        config_text(16, shared_mem=True, dvfs=True))))
    assert one.sync_cycles(3, 4) == 0


@pytest.mark.parametrize("node,fastest_at_800mv", [(22, 370), (32, 420),
                                                    (45, 460)])
def test_technology_node_reaches_both_readers(node, fastest_at_800mv):
    text = config_text(16, dvfs=True, power=True).replace(
        "technology_node = 22", f"technology_node = {node}")
    sc = SimConfig(ConfigFile.from_string(text))
    assert sc.technology_node == node
    assert DvfsParams.from_config(sc.cfg).max_freq_mhz[-1] \
        == fastest_at_800mv


# --- trace/benchmarks.canneal_trace ------------------------------------

def test_canneal_defaults_are_unchanged():
    """sha256 over every field of the default records, as the parent of
    PR 44 generated them."""
    assert sha(canneal_trace(16, footprint_lines=512, swaps_per_tile=8)) \
        == "b2d0c34c9888e9ac81cc455cc22ea9d3b34b84c3d12f72b1a81560749fc8248b"


def test_canneal_steps_and_schedule():
    tiles, steps, swaps = 16, 5, 9
    plain = stepped(tiles, 200, dvfs_schedule=None)
    batch = stepped(tiles, 200)
    count = lambda b, op: int((b.op == int(op)).sum())    # noqa: E731
    assert count(plain, Op.DVFS_SET) == 0
    assert count(batch, Op.DVFS_SET) == tiles * steps
    assert count(batch, Op.BARRIER_WAIT) == tiles * steps
    assert count(batch, Op.BBLOCK) == count(batch, Op.BRANCH) \
        == tiles * steps * swaps
    # the schedule adds its records and moves no other
    assert count(batch, Op.NOP) - count(plain, Op.NOP) in (0, -steps)
    for t in range(tiles):
        sets = batch.op[t] == int(Op.DVFS_SET)
        assert list(batch.aux0[t][sets]) == [0] * steps
        assert list(batch.aux1[t][sets]) == [
            LEVELS_MHZ[(t + s) % 6] for s in range(steps)]
        # a step: the request, then the swaps, then the barrier
        ops = [int(o) for o in batch.op[t] if o != int(Op.NOP)]
        first = ops.index(int(Op.DVFS_SET))
        nxt = ops.index(int(Op.DVFS_SET), first + 1)
        assert ops[nxt - 1] == int(Op.BARRIER_WAIT)
    with pytest.raises(ValueError, match="unknown dvfs_schedule"):
        canneal_trace(4, dvfs_schedule="uniform")


# --- energy and the V/f table as statistics ----------------------------

def assert_equal_statistics(a, b, names):
    for name in names:
        x, y = a, b
        for part in name.split("."):
            x = x[part] if isinstance(x, dict) else getattr(x, part)
            y = y[part] if isinstance(y, dict) else getattr(y, part)
        assert np.array_equal(np.asarray(x), np.asarray(y)), name


@pytest.fixture(scope="module")
def disjoint16():
    """(config, trace, host-driven simulator, its results): the cell's
    target at 16 tiles on line-disjoint stepped canneal."""
    sc, batch = sim_config(16), stepped(16, 12, disjoint=True)
    sim = Simulator(sc, batch, barrier_host=True)
    return sc, batch, sim, sim.run()


@pytest.fixture(scope="module")
def flat16():
    """(simulator, results) of the same target on the same line-disjoint
    swaps WITHOUT the schedule: every tile at 1.0 V throughout."""
    sim = Simulator(sim_config(16),
                    stepped(16, 12, disjoint=True, dvfs_schedule=None),
                    barrier_host=True)
    return sim, sim.run()


@pytest.fixture(scope="module")
def racy64():
    """(trace, host-driven results): the cell's own racy traffic at 64
    tiles and 976 lines, a sixteenth of the cell."""
    batch = stepped(64, 976)
    return batch, Simulator(sim_config(64), batch, barrier_host=True).run()


def test_engine_equals_golden_bit_for_bit_across_retunes(disjoint16):
    """BASELINE.md's contract for race-free traffic, now across 80
    transitions: clocks, every memory counter, the V/f table and every
    energy component."""
    sc, batch, _, res = disjoint16
    gold = run_golden(sc, batch)
    assert res.func_errors == 0
    assert int(res.dvfs_counters["errors"].sum()) == 0
    assert_equal_statistics(
        res, gold,
        ["clock_ps", "sync_stall_ps"]
        + ["mem_counters." + k for k in gold.mem_counters]
        + ["dvfs_counters." + k for k in gold.dvfs_counters]
        + ["energy_pj." + k for k in gold.energy_pj])
    assert np.array_equal(
        res.instruction_count, gold.instruction_count
        + gold.recv_instructions + gold.sync_instructions)
    assert sorted(gold.energy_pj) == sorted(res.energy_pj)
    # the run did what the cell is for: every tile ends a step on a
    # level of its own, five levels crossed, energy on every component
    # that has events
    assert sorted(set(res.dvfs_counters["freq_mhz"][:, 0])) \
        == sorted(LEVELS_MHZ)
    assert set(res.dvfs_counters["freq_mhz"][:, 1]) == {1000}
    assert np.array_equal(gold.core_freq_mhz,
                          res.dvfs_counters["freq_mhz"][:, 0])
    for k in ("core_dynamic", "core_static", "l1d_dynamic", "l2_dynamic",
              "l2_static", "dram_dynamic", "network_static", "total"):
        assert (res.energy_pj[k] > 0).all(), k
    parts = [v for k, v in res.energy_pj.items() if k != "total"]
    assert np.array_equal(sum(parts), res.energy_pj["total"])
    assert all(v.dtype == np.int64 for v in res.energy_pj.values())
    assert "Tile Energy Monitor Summary" in res.summary()
    assert res.summary().count("  DVFS Summary:") == 16


def test_integer_energy_against_the_float_host_pass(disjoint16, flat16):
    """`TileEnergyMonitor` prices a whole run at ONE voltage: right with
    no transition (the integers agree with it to 0.2 % a component that
    is at least 1 nJ, 0.1 % in total: the rounding of prices to whole fJ
    and of leakage to whole uW), an over-estimate of 5-25 % on the
    schedule's run, whose tiles spend four steps of five below 1.0 V."""
    _, _, sim, res = disjoint16
    flat_sim, flat = flat16
    # a slower level costs less for the same events: the price list falls
    # with the voltage squared, and the schedule's run - the same record
    # counts - pays less core dynamic energy on every tile
    ep = sim.params.energy
    per_instr = dict(ep.prices[ep.columns.index("core_dynamic")])[
        "instructions"]
    assert list(per_instr) == sorted(per_instr, reverse=True)
    assert per_instr[0] * 0.8 ** 2 == pytest.approx(per_instr[-1], rel=1e-3)
    assert (res.energy_pj["core_dynamic"]
            <= flat.energy_pj["core_dynamic"]).all()
    assert res.energy_pj["core_dynamic"].sum() \
        < 0.9 * flat.energy_pj["core_dynamic"].sum()
    mon = TileEnergyMonitor(flat_sim, flat)
    assert mon.node_nm == 22
    for t in (0, 5, 15):
        want = mon.tile_energy_j(t, 1.0)
        assert sorted(want) == sorted(flat.energy_pj)
        for k, joules in want.items():
            got = int(flat.energy_pj[k][t])
            assert got == pytest.approx(
                joules * 1e12, rel=2e-3 if joules > 1e-9 else 0.05,
                abs=1), (t, k)
        assert int(flat.energy_pj["total"][t]) == pytest.approx(
            want["total"] * 1e12, rel=1e-3)
    mon = TileEnergyMonitor(sim, res)
    at_one_volt = sum(mon.tile_energy_j(t, 1.0)["total"]
                      for t in range(16)) * 1e12
    integrated = int(res.energy_pj["total"].sum())
    assert 1.05 < at_one_volt / integrated < 1.25


@pytest.mark.parametrize("tiles", [16, 64])
def test_host_driven_equals_single_region(tiles, request):
    """The two drive loops are one simulation: every statistic, energy
    and the V/f table among them - at 64 tiles on the cell's own (racy)
    traffic, at 16 on the line-disjoint one (the fixtures' host-driven
    runs: one more program each)."""
    if tiles == 64:
        batch, host = request.getfixturevalue("racy64")
    else:
        _, batch, _, host = request.getfixturevalue("disjoint16")
    region = Simulator(sim_config(tiles), batch, barrier_host=False).run()
    a, b = digest.statistics(host), digest.statistics(region)
    assert sorted(a) == sorted(b)
    assert [k for k in a if not np.array_equal(a[k], b[k])] == []
    assert {"energy_pj.total", "dvfs_counters.freq_mhz"} <= set(a)
    assert int(host.dvfs_counters["errors"].sum()) == 0
    assert host.func_errors == 0


def test_rejected_request_closes_no_interval():
    """HOLD above the level's maximum and a bad domain: counted in
    `errors`, the table untouched, and the energy that of the same run
    without the two records."""
    def builders(with_bad: bool):
        out = []
        for t in range(4):
            b = TraceBuilder()
            b.bblock(100, 100)
            b.dvfs_set(0, 500)                 # ok: 840 mV
            b.bblock(100, 100)
            if with_bad and t == 1:
                b.dvfs_set(0, 870, hold=True)  # 840 mV holds 500 at most
            if with_bad and t == 2:
                b.dvfs_set(7, 500)             # no such domain
            b.bblock(100, 100)
            out.append(b)
        return TraceBatch.from_builders(out)

    sc = SimConfig(ConfigFile.from_string(config_text(
        4, dvfs=True, dvfs_domains=TWO, power=True)))
    good = Simulator(sc, builders(False)).run()
    bad_batch = builders(True)
    bad = Simulator(sc, bad_batch).run()
    assert list(bad.dvfs_counters["errors"]) == [0, 1, 1, 0]
    assert list(good.dvfs_counters["errors"]) == [0] * 4
    assert_equal_statistics(
        bad, good, ["clock_ps", "dvfs_counters.freq_mhz",
                    "dvfs_counters.voltage_mv"]
        + ["energy_pj." + k for k in good.energy_pj])
    assert set(bad.dvfs_counters["voltage_mv"][:, 0]) == {840}
    # no memory model: the cache and DRAM components are absent
    assert sorted(good.energy_pj) == sorted(
        ["core_dynamic", "core_static", "network_dynamic",
         "network_static", "total"])
    gold = run_golden(sc, bad_batch)
    assert_equal_statistics(
        bad, gold, ["clock_ps"]
        + ["dvfs_counters." + k for k in gold.dvfs_counters]
        + ["energy_pj." + k for k in gold.energy_pj])


def lowered_text(sim) -> str:
    """The host-batch program, lowered and not compiled, with the
    location names scopes live in."""
    import jax.numpy as jnp

    return sim._hb_get_runner().lower(
        sim.state, jnp.asarray(0, jnp.int64),
        jnp.asarray(1, jnp.int32)).as_text(debug_info=True)


def test_power_off_carries_nothing():
    """Off, the state has no leaf of it, the program no operation, the
    results no field: the same lowered text as with the `[dvfs]`
    section alone, which is the program every earlier PR locked."""
    batch = stepped(16, 200)
    off = Simulator(sim_config(16, power=False), batch, barrier_host=True)
    on = Simulator(sim_config(16), batch, barrier_host=True)
    assert off.params.energy is None and off.state.energy is None
    n_off = len(jax.tree_util.tree_leaves(off.state))
    assert len(jax.tree_util.tree_leaves(on.state)) == n_off + 3
    assert "gt.energy" not in lowered_text(off)
    assert "gt.energy" in lowered_text(on)
    # results read from the initial state: no program is compiled
    res = off._results_from_state(0)
    assert res.energy_pj is None
    assert res.dvfs_counters is not None       # it has a [dvfs] section
    assert on._results_from_state(0).energy_pj["total"].sum() == 0
    plain = Simulator(sim_config(16, dvfs=False, dvfs_domains=None,
                                 power=False), batch,
                      barrier_host=True)._results_from_state(0)
    assert plain.energy_pj is None and plain.dvfs_counters is None
    assert "energy_pj.total" not in digest.statistics(plain)


def test_energy_params_are_integers_of_the_native_library():
    sc = sim_config(16)
    ep = EnergyParams.from_config(sc, DvfsParams.from_config(sc.cfg),
                                  MemParams.from_config(sc))
    assert hash(ep) == hash(EnergyParams.from_config(
        sc, DvfsParams.from_config(sc.cfg), MemParams.from_config(sc)))
    assert ep.voltages_mv == (1000, 960, 920, 880, 840, 800)
    flat = [p for col in ep.prices
            for p in (col if isinstance(col[0], int)
                      else [x for _, tab in col for x in tab])]
    assert all(isinstance(p, int) and p > 0 for p in flat)
    dram = dict(ep.prices[ep.columns.index("dram_dynamic")])
    assert set(dram["dram_accesses"]) == {10_240 * FJ_PER_PJ}
    # CORE and the caches in domain 0, the router in domain 1, DRAM none
    assert ep.domains == (0,) * 8 + (-1, 1, 1)


# --- the configuration and its envelope --------------------------------

def test_configuration_is_what_the_manifest_lists():
    entry, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CELL["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == CELL["reduced"] == [
        "temperature_steps", "footprint_lines"]
    assert sorted(CELL["reduced_detail"]) == sorted(CELL["reduced"])
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL_NAME]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "solo-repeat-step2", 1)
    coh = target.load_config("coh-1024-memstress")
    for k, v in coh["config_text"].items():
        assert CELL["config_text"][k] == v, k
    for k, v in coh["expect"].items():
        assert CELL["expect"][k] == v, k
    assert CELL["simulator"] == {"barrier_host": True}
    added = {"dvfs_busy_share", "energy_busy_share", "dvfs_sets_per_run"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in added:
            # (the two scope shares are read in PR 51's served V/f cell
            # too: a list only grows at its end)
            assert m["workloads"][0] == CELL_NAME, m["name"]
            assert m["moves"] == "sim_records_per_s"
    assert added <= {m["name"] for m in MANIFEST["per_layer"]}
    reference = target.load_reference(NAME)
    assert {"energy_pj.total", "dvfs_counters.freq_mhz",
            "dvfs_counters.errors"} <= set(reference["statistics"])
    assert {v["origin"] for v in reference["statistics"].values()} \
        == {"cpu-backend"}


def test_expectations_hold_at_64_tiles():
    sim = Simulator(sim_config(64), stepped(64, 976), barrier_host=True)
    expect = {k: v for k, v in CELL["expect"].items()
              if k != "params.n_tiles"}
    target.check_expectations({"expect": expect}, sim)


def test_golden_envelope_at_64_tiles(racy64):
    """The reference is the engine's own, and the cell's lines race: what
    holds the engine to the independent golden on such traffic is the
    configuration's `golden_envelope`.  Here at 64 tiles (a sixteenth of
    the cell in tiles and footprint, the same steps, swaps and schedule):
    the engine's every percentage inside the limit the configuration
    states, the single-domain control's outside at least one.  The
    1024-tile numbers themselves are `probe_golden_dvfs.py`'s."""
    batch, res = racy64
    gold = numbers(run_golden(sim_config(64), batch), ENV)
    assert res.func_errors == 0
    rows = envelope(gold, numbers(res, ENV), ENV)
    assert [r for r in rows if r[3]] == []
    ctl = Simulator(sim_config(64, dvfs_domains=ONE), batch,
                    barrier_host=True).run()
    assert any(r[3] for r in envelope(gold, numbers(ctl, ENV), ENV))
    # the stored 1024-tile numbers: the engine's inside, the control's
    # outside at least one limit
    stored = lambda k: {s: v[k] for s, v in ENV.items()}    # noqa: E731
    assert not [r for r in envelope(stored("golden"), stored("engine"),
                                    ENV) if r[3]]
    assert any(r[3] for r in envelope(stored("golden"), stored("control"),
                                      ENV))


# --- the per-layer readers the cell adds --------------------------------

def _ctx(busy=None, results=None, batch=None):
    scoped = None if busy is None else {
        "scoped": True, "spans": [], "busy_s": busy}
    return types.SimpleNamespace(
        readings=[] if results is None else [
            {"records": 100, "results": results}],
        own={"scope_trace": scoped, "batch": batch}, config={})


def _reader(name):
    return paths.load_module("layer_metrics", name)


@pytest.mark.parametrize("name,scope", [("dvfs_busy_share", "gt.dvfs"),
                                        ("energy_busy_share", "gt.energy")])
def test_scope_share_readers(name, scope):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert (entry["source"], entry["unit"]) == ("device_trace", "%")
    read = _reader(name).read
    busy = {"gt.core": 0.6, "gt.dvfs": 0.3, "gt.energy": 0.1}
    want = {"gt.dvfs": 40.0, "gt.energy": 10.0}[scope]
    assert read(_ctx(busy=busy)) == pytest.approx(want)
    # a program without the scope (the parent; power off): nothing
    assert read(_ctx(busy={"gt.core": 1.0})) is None
    assert read(_ctx()) is None


def test_dvfs_sets_per_run_reader(capsys):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == "dvfs_sets_per_run"]
    assert (entry["source"], entry["unit"]) == ("program_counter", "count")
    read = _reader("dvfs_sets_per_run").read
    batch = stepped(16, 200)
    freq = np.stack([np.array(LEVELS_MHZ * 3)[:16], np.full(16, 1000)], 1)
    res = types.SimpleNamespace(dvfs_counters={
        "freq_mhz": freq, "voltage_mv": freq,
        "errors": np.array([0, 2] + [0] * 14)})
    assert read(_ctx(results=res, batch=batch)) == 16 * 5 - 2
    assert "6 distinct final core frequencies" in capsys.readouterr().out
    # the parent's results carry no such field: nothing, and no raise
    assert read(_ctx(results=types.SimpleNamespace(), batch=batch)) is None
    assert read(_ctx(batch=batch)) is None
