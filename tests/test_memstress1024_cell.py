"""`memstress1024-coh` (benchmark/configs/coh-1024-memstress.json): what
the cell assumes of the program, held at sizes tier-1 can afford.

The cell runs the 1024-tile full-directory target HOST-DRIVEN
(`barrier_host=True`) although the auto rule picks that only for a
SEND-carrying trace, because the benchmark can take a bounded traced
slice only of the host-driven program (`drivers/solo_repeat.py`:
`run_chunk(3)` after one quantum).  So, on the cell's own generator at 16
and 64 tiles (`core: simple`, as the cell):

- the configuration loads through `benchmark/lib/target.py`, is
  `coh-1024`'s target letter for letter, and its `expect` holds on the
  built 1024-tile `Simulator`;
- host-driven == single-region, bit for bit on every statistic of
  `SimResults` (ROADMAP D1's check, for memory traffic);
- the traced slice is LIVE: after `run_chunk(1)`, `run_chunk(3)` runs
  iterations in which the memory engine's phases and its home gate do
  work;
- chunked == whole: `run_chunk(1)`, `run_chunk(3)`, then chunks to the
  end leave the statistics of one whole `run()`.  `prev_qend` is NOT
  carried across `run_chunk` calls (ROADMAP M9): a trace of loads and
  stores leaves no tile behind a boundary, so the floor it provides never
  binds here - this test is what says so;
- the three per-layer readers the cell adds, on a recorded `ctx`.
"""

import json
import os
import sys
import types

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.trace.synthetic import memory_stress_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import digest, paths, target
finally:
    sys.path.remove(BENCH)

NAME, CELL_NAME = "coh-1024-memstress", "memstress1024-coh"
CELL = target.load_config(NAME)
GEN = CELL["trace"]["kwargs"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "traffic", "solo-repeat.json")) as _f:
    TRAFFIC = json.load(_f)


def small(tiles: int):
    """The cell's target and traffic at `tiles` tiles."""
    sc = target.build_sim_config(
        {"config_text": {**CELL["config_text"], "tiles": tiles}})
    return sc, memory_stress_trace(**{**GEN, "n_tiles": tiles})


@pytest.fixture(scope="module", params=[16, 64])
def pair(request):
    """(host-driven simulator, its initial state, its whole run's
    statistics, the single-region run's statistics)."""
    sc, batch = small(request.param)
    host = Simulator(sc, batch, **CELL["simulator"])
    initial = host.state
    whole = digest.statistics(host.run())
    one = Simulator(sc, batch)
    return host, initial, whole, digest.statistics(one.run()), one


def test_configuration_is_coh1024_and_expect_holds():
    base = target.load_config("coh-1024")
    assert CELL["config_text"] == base["config_text"]
    assert CELL["expect"] == base["expect"]
    assert CELL["simulator"] == {"barrier_host": True}
    assert CELL["reduced"] == ["n_accesses"]
    entry, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert (entry["source"], entry["reduced"]) == (CELL["source"],
                                                  CELL["reduced"])
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL_NAME]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "solo-repeat", 1)
    ref = target.load_reference(NAME)
    assert ref["origins"] == ["cpu-backend"]
    assert ref["trace"] == CELL["trace"]
    assert len(ref["statistics"]) == 39
    sim = Simulator(target.build_sim_config(CELL), target.build_trace(CELL),
                    **CELL["simulator"])
    target.check_expectations(CELL, sim)
    # what the cell exercises is on: six phase conds, the home gate and
    # the staged directory writes, not the whole-engine gate
    assert sim.params.mem.phase_gate and not sim.params.mem_gate
    assert sim.params.mem.dir_stage_cap > 0
    batch = target.build_trace(CELL)
    assert batch.n_tiles * batch.length == 66_560


def test_host_driven_equals_single_region(pair):
    host, _, whole, single, one = pair
    assert host.barrier_host and not one.barrier_host
    assert len(whole) == 39
    for k in whole:
        np.testing.assert_array_equal(whole[k], single[k], err_msg=k)
    assert host.last_n_iterations == one.last_n_iterations
    assert int(np.asarray(whole["func_errors"]).sum()) == 0


def test_traced_slice_is_live_and_chunked_equals_whole(pair):
    host, initial, whole, _, _ = pair
    skip, n = TRAFFIC["trace_skip_quanta"], TRAFFIC["trace_quanta"]
    host.state = initial
    done, quanta = host.run_chunk(skip)
    assert not done and quanta == skip
    before = {**host.last_phase_skips, **host.last_base_skips}
    done, more = host.run_chunk(n)
    assert not done and more == n
    iters = int(host.last_n_iterations)
    delta = {k: v - before[k] for k, v in
             {**host.last_phase_skips, **host.last_base_skips}.items()}
    # every quantum of the slice works, and ends on its one idle iteration
    assert iters >= 2 * n and host.last_idle_iterations == n
    # a phase that ran in an iteration did not count a skip there
    for phase in ("requester", "home_start", "sharer", "requester_fill",
                  "base"):
        assert 0 <= delta[phase] < iters, (phase, delta, iters)
    quanta += more
    while not done:
        done, more = host.run_chunk(5)
        quanta += more
    assert quanta == int(whole["n_quanta"])
    chunked = digest.statistics(
        host._results_from_state(quanta, host._spans(None)))
    for k in whole:
        np.testing.assert_array_equal(chunked[k], whole[k], err_msg=k)


class _Sim:
    """What a reader sees of `Simulator` after one whole run."""

    def __init__(self, skips, iterations, done=True):
        self.last_base_skips = skips
        self.last_n_iterations = iterations
        self.params = types.SimpleNamespace(inner_block=32)
        self.state = types.SimpleNamespace(done=np.full(4, done))
        self.runs = 0

    def run(self):
        self.runs += 1
        self.state = types.SimpleNamespace(done=np.ones(4, bool))


def _ctx(**own):
    results = types.SimpleNamespace(mem_counters={
        "dir_accesses": np.array([81_000, 431]),
        "invalidations": np.array([35_963, 0])})
    scoped = {"scoped": True, "spans": [], "busy_s": {
        "gt.mem.home_evict": 1.0, "gt.mem.home_start": 2.0,
        "gt.mem.home_finish": 1.0, "gt.mem.base": 3.0,
        "gt.mem.stage_flush": 1.0, "gt.mem.requester": 4.0,
        "gt.fetch": 6.0, "unscoped": 2.0}}
    return types.SimpleNamespace(
        readings=[{"records": 66_560, "results": results}],
        own={"scope_trace": scoped, "initial_state": "initial", **own})


# the CPU's counts of the cell at 1024 tiles: 37 of 2,400 / 32 = 75
# blocks skipped the flush; 81,431 directory accesses over 66,560 records
READERS = [
    ("home_side_busy_share", {}, 40.0, 0),
    ("dir_flush_skip_share",
     {"sim": _Sim({"base": 1704, "flush": 37}, 2400)}, 100 * 37 / 75, 0),
    # a part-run state (the traced slice's) is replaced by a whole run's
    ("dir_flush_skip_share",
     {"sim": _Sim({"base": 1704, "flush": 37}, 2400, done=False)},
     100 * 37 / 75, 1),
    # a program without the counter (the parent of the PR that added it)
    ("dir_flush_skip_share", {"sim": _Sim({"base": 1704}, 2400)}, None, 0),
    ("dir_flush_skip_share", {"sim": object()}, None, 0),
    ("dir_accesses_per_record", {}, 81_431 / 66_560, 0),
]


@pytest.mark.parametrize("name,own,want,runs", READERS)
def test_layer_metric_readers(name, own, want, runs):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    # (PR 38 appended `memstress1024-shl2` to the directory counter's,
    # PR 44 `canneal1024-dvfs` to those its program reports, PR 48
    # `memstress1024-atac` to every list that holds this cell)
    assert entry["workloads"][0] == CELL_NAME
    assert set(entry["workloads"]) <= {CELL_NAME, "memstress1024-shl2",
                                       "canneal1024-dvfs",
                                       "memstress1024-atac",
                                       "vfsweep256-canneal"}
    assert entry["moves"] == "sim_records_per_s"
    sys.path.insert(0, BENCH)
    try:
        reader = paths.load_module("layer_metrics", name)
        got = reader.read(_ctx(**own))
    finally:
        sys.path.remove(BENCH)
    assert got == (None if want is None else pytest.approx(want))
    assert getattr(own.get("sim"), "runs", 0) == runs


@pytest.mark.parametrize("name,scope,from_end", [
    ("stage_flush_busy_share", "gt.mem.stage_flush", 14),
    ("entry_land_busy_share", "gt.mem.entry_land", 10),
    ("stage_overlay_busy_share", "gt.mem.stage_overlay", 9),
])
@pytest.mark.parametrize("scoped,want", [
    (True, 5.0),        # 1.0 s under the scope of 20.0 s busy
    (False, None),      # a program without it: no such scope
    (None, None),       # a run without a scope trace
], ids=["scoped", "unscoped", "untraced"])
def test_staged_scope_readers(name, scope, from_end, scoped, want):
    """PR 43's `stage_flush_busy_share`, PR 45's `entry_land_busy_share`
    and PR 46's `stage_overlay_busy_share`: the flush's / the entry
    words' landing's / the staging table's index and value fetches'
    scope by itself, in the staged cells (`mem_ungated_busy_share` holds
    the first with `gt.mem.base`, and not the other two: a scope trace
    counts an operation for its deepest scope); nothing where the
    program has no such scope."""
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    # (PR 51's served V/f cell, staged under the sim axis, joins where
    # its traced slice reads the scope)
    assert entry["workloads"][:4] == [CELL_NAME, "a2a1024-fftskel",
                                      "canneal1024-dvfs",
                                      "memstress1024-atac"]
    assert entry["workloads"][4:] in ([], ["vfsweep256-canneal"])
    assert (entry["moves"], entry["better"]) == ("sim_records_per_s",
                                                 "lower")
    # appended (PR 44's three metrics follow the flush's, PR 45's them,
    # PR 46's that, PR 48's five the overlay's, PR 51's three those)
    assert [m["name"] for m in MANIFEST["per_layer"]].index(
        name) == len(MANIFEST["per_layer"]) - from_end
    ctx = _ctx()
    busy = ctx.own["scope_trace"]["busy_s"]
    if scope not in busy:       # the landing's second, out of the base's
        busy[scope], busy["gt.mem.base"] = 1.0, busy["gt.mem.base"] - 1.0
    if scoped is None:
        ctx.own["scope_trace"] = None
    elif not scoped:
        del ctx.own["scope_trace"]["busy_s"][scope]
    sys.path.insert(0, BENCH)
    try:
        got = paths.load_module("layer_metrics", name).read(ctx)
    finally:
        sys.path.remove(BENCH)
    assert got == (None if want is None else pytest.approx(want))


def test_readers_find_nothing_in_an_older_program():
    """The driver runs the benchmark's files over the parent too: where
    the program has no such counter or results, a reader returns None."""
    sys.path.insert(0, BENCH)
    try:
        ctx = types.SimpleNamespace(
            readings=[{"records": 10, "results": types.SimpleNamespace()}],
            own={"scope_trace": None})
        for name in ("home_side_busy_share", "dir_flush_skip_share",
                     "dir_accesses_per_record"):
            assert paths.load_module("layer_metrics", name).read(ctx) is None
    finally:
        sys.path.remove(BENCH)
