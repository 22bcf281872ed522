"""`memstress1024-shl2` (benchmark/configs/shl2-mesi-1024-memstress.json):
what the cell assumes of the program, held at sizes tier-1 can afford.

The cell is the 1024-tile target under `pr_l1_sh_l2_mesi` - the second
memory engine, `memory/engine_shl2.py` - driven as `memstress1024-coh` is:
the same traffic, host-driven (`barrier_host=True`) so that the benchmark
can take a bounded traced slice.  So, on the cell's own generator at 16
and 64 tiles (`core: simple`, as the cell):

- the configuration loads through `benchmark/lib/target.py`, is
  `coh-1024-memstress`'s target but for `protocol`, under its traffic
  letter for letter, and its `expect` holds on the built 1024-tile
  `Simulator` (build only), whose embedded directory is the
  `u32[1024,1024,256]` sharers store the cell is there for;
- host-driven == single-region, bit for bit on every statistic of
  `SimResults`;
- the traced slice is LIVE and chunked == whole on this engine too;
- the control (`pr_l1_sh_l2_msi`: no E state) moves `invalidations` and
  `clock_ps`;
- `gt.mem.dir_apply` names the landing of a home phase's row plan in the
  gated and the ungated shared-L2 program, and nothing in a private-L2
  one; in the gated program the landing runs under its phase's gate;
- the two per-layer readers the cell adds, on a recorded `ctx`.
"""

import json
import os
import re
import sys
import types

import jax
import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.memory.engine_shl2 import SHL2_PHASE_NAMES
from graphite_tpu.trace.synthetic import memory_stress_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import digest, paths, target
finally:
    sys.path.remove(BENCH)

NAME, CELL_NAME = "shl2-mesi-1024-memstress", "memstress1024-shl2"
SCOPE = "gt.mem.dir_apply"
CELL = target.load_config(NAME)
GEN = CELL["trace"]["kwargs"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "traffic", "solo-repeat.json")) as _f:
    TRAFFIC = json.load(_f)


def small(tiles: int, **text):
    """The cell's target and traffic at `tiles` tiles."""
    sc = target.build_sim_config(
        {"config_text": {**CELL["config_text"], **text, "tiles": tiles}})
    return sc, memory_stress_trace(**{**GEN, "n_tiles": tiles})


@pytest.fixture(scope="module", params=[16, 64])
def pair(request):
    """(host-driven simulator, its initial state, its whole run's
    statistics, the single-region run's statistics, that simulator)."""
    sc, batch = small(request.param)
    host = Simulator(sc, batch, **CELL["simulator"])
    initial = host.state
    whole = digest.statistics(host.run())
    one = Simulator(sc, batch)
    return host, initial, whole, digest.statistics(one.run()), one


def test_configuration_is_coh1024_memstress_but_for_the_protocol():
    base = target.load_config("coh-1024-memstress")
    assert CELL["config_text"] == {**base["config_text"],
                                   "protocol": "pr_l1_sh_l2_mesi"}
    assert CELL["trace"] == base["trace"]
    assert CELL["simulator"] == base["simulator"] == {"barrier_host": True}
    assert CELL["reduced"] == ["n_accesses"]
    assert CELL["control"]["config_text"] == {"protocol": "pr_l1_sh_l2_msi"}
    # what both targets share of `expect` is equal; the rest names the
    # engine (the private-L2 directory's ways / the slice's sharer words)
    shared = set(CELL["expect"]) & set(base["expect"]) - {
        "params.mem.protocol"}
    assert {k: CELL["expect"][k] for k in shared} == {
        k: base["expect"][k] for k in shared}
    assert len(shared) == len(base["expect"]) - 2
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


def test_expect_holds_on_the_built_1024_tile_target():
    batch = target.build_trace(CELL)
    assert batch.n_tiles * batch.length == 66_560
    sim = Simulator(target.build_sim_config(CELL), batch,
                    **CELL["simulator"])
    target.check_expectations(CELL, sim)
    # what the cell exercises is on: six phase conds, not the whole-engine
    # gate (the state is over its ceiling); and the store it is there for
    assert sim.params.mem.phase_gate and not sim.params.mem_gate
    d = sim.state.mem.dir
    assert (d.sharers.shape, str(d.sharers.dtype)) == (
        (1024, 1024, 256), "uint32")
    assert (d.word.shape, str(d.word.dtype)) == ((1024, 1024, 8), "int64")
    assert d.sharers.nbytes == 1_073_741_824


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
    before = dict(host.last_phase_skips)
    assert sorted(before) == sorted(SHL2_PHASE_NAMES)
    done, more = host.run_chunk(n)
    assert not done and more == n
    iters = int(host.last_n_iterations)
    delta = {k: v - before[k] for k, v in host.last_phase_skips.items()}
    # every quantum of the slice works, and ends on its one idle iteration
    assert iters >= 2 * n and host.last_idle_iterations == n
    # a phase that ran in an iteration did not count a skip there; no L1
    # line is evicted at 64 accesses a tile, so home_evict never runs
    for phase in ("requester", "sharer", "home_start", "home_finish",
                  "requester_fill"):
        assert 0 <= delta[phase] < iters, (phase, delta, iters)
    assert delta["home_evict"] == iters
    quanta += more
    while not done:
        done, more = host.run_chunk(5)
        quanta += more
    assert quanta == int(whole["n_quanta"])
    chunked = digest.statistics(
        host._results_from_state(quanta, host._spans(None)))
    for k in whole:
        np.testing.assert_array_equal(chunked[k], whole[k], err_msg=k)


def test_control_moves_the_e_state(pair):
    """`pr_l1_sh_l2_msi` for `_mesi`: a lone reader is granted SHARED,
    not EXCLUSIVE, so its later store is a miss the home must serve."""
    host, _, whole, _, _ = pair
    sc, batch = small(host.params.n_tiles,
                      **CELL["control"]["config_text"])
    msi = digest.statistics(Simulator(sc, batch, **CELL["simulator"]).run())

    def total(stats, k):
        return int(np.asarray(stats[k]).astype(np.int64).sum())

    for k in ("mem_counters.invalidations", "clock_ps",
              "mem_counters.l1d_write_hits"):
        assert total(msi, k) != total(whole, k), k
    assert total(msi, "mem_counters.l1d_write_hits") < total(
        whole, "mem_counters.l1d_write_hits")
    assert int(np.asarray(msi["func_errors"]).sum()) == 0


def _lowered_text(sim) -> str:
    fn, args = sim._auditable_fn(4096)
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _lowered_scopes(sim) -> set:
    return set(re.findall(r"gt\.[a-z0-9_.]*[a-z0-9_]", _lowered_text(sim)))


@pytest.mark.parametrize("protocol,kw,named", [
    ("pr_l1_sh_l2_mesi", {}, True),
    ("pr_l1_sh_l2_mesi", {"phase_gate": False}, True),
    ("pr_l1_pr_l2_dram_directory_msi", {}, False),
    ("pr_l1_pr_l2_dram_directory_msi", {"phase_gate": False}, False),
])
def test_dir_apply_names_the_embedded_directorys_landing(protocol, kw, named):
    sc, batch = small(16, protocol=protocol)
    sim = Simulator(sc, batch, **kw)
    assert sim.params.mem.phase_gate == kw.get("phase_gate", True)
    found = _lowered_scopes(sim)
    assert "gt.mem.home_start" in found
    assert (SCOPE in found) == named


@pytest.mark.parametrize("gate", [True, False])
def test_landing_runs_under_its_phases_gate(gate):
    """On the chip a landing on the 1 GB sharers store costs 3 ms whatever
    it adds (PERF.md section 6, PR 38), so a gated home phase lands its
    plan inside `engine._run_if`'s zero-or-one-trip loop, keyed on the
    phase's own predicate; the ungated program lands it inline."""
    sc, batch = small(16)
    text = _lowered_text(Simulator(sc, batch, phase_gate=gate))
    for phase in ("home_evict", "home_finish", "home_start"):
        paths = set(re.findall(
            rf"gt\.mem\.{phase}/([a-z_/]*){re.escape(SCOPE)}/", text))
        assert paths == {"while/body/" if gate else ""}, (phase, paths)


def _ctx(busy=None, counters=None, config=None):
    results = types.SimpleNamespace(**(
        {} if counters is None else {"mem_counters": counters,
                                     "clock_ps": np.array([7, 8])}))
    scoped = None if busy is None else {
        "scoped": True, "spans": [], "busy_s": busy}
    return types.SimpleNamespace(
        readings=[{"records": 66_560, "results": results}],
        own={"scope_trace": scoped}, config=config or {})


# the CPU's counts of the cell at 1024 tiles (ISSUE 38): 32,009 slice
# hits of 64,984 requests a home started
COUNTERS = {"l2_hits": np.array([32_000, 9]),
            "l2_misses": np.array([32_975, 0]),
            "dir_accesses": np.array([89_767, 0]),
            "invalidations": np.array([26_757, 0])}
BUSY = {"gt.mem.dir_apply": 1.0, "gt.mem.home_start": 2.0,
        "gt.mem.requester": 4.0, "gt.fetch": 1.0, "unscoped": 2.0}
READERS = [
    ("dir_apply_busy_share", _ctx(busy=BUSY), 10.0),
    # a program without the scope: the parent of the PR that added it
    ("dir_apply_busy_share", _ctx(busy={"gt.mem.home_start": 1.0}), None),
    ("dir_apply_busy_share", _ctx(), None),
    ("l2_slice_hit_share", _ctx(counters=COUNTERS),
     100 * 32_009 / 64_984),
    # the configuration's golden envelope is printed, never judged, here
    ("l2_slice_hit_share", _ctx(counters=COUNTERS, config=CELL),
     100 * 32_009 / 64_984),
    ("l2_slice_hit_share", _ctx(counters={"dir_accesses": np.ones(2)}),
     None),
    ("l2_slice_hit_share", _ctx(), None),
]


@pytest.mark.parametrize("name,ctx,want", READERS)
def test_layer_metric_readers(name, ctx, want):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL_NAME]
    assert entry["moves"] == "sim_records_per_s"
    assert entry["layer"] == "memory engines - memory/engine.py"
    sys.path.insert(0, BENCH)
    try:
        got = paths.load_module("layer_metrics", name).read(ctx)
    finally:
        sys.path.remove(BENCH)
    assert got == (None if want is None else pytest.approx(want))


def test_cell_reports_what_the_manifest_lists():
    """Every metric the cell is listed under has a reader file.  The two
    skip shares of `engine.py`'s base and flush are not asked of it (this
    engine counts neither); the two BUSY shares that read `gt.mem.base`
    are (REVIEW of PR 38: the shl2 program carries that scope, 0.46% of
    the traced slice, and the three home phases)."""
    listed = {m["name"] for m in MANIFEST["per_layer"] + MANIFEST[
        "end_to_end"] if CELL_NAME in m.get("workloads", [CELL_NAME])}
    assert {"sim_records_per_s", "peak_hbm_gb", "setup_s",
            "dir_apply_busy_share", "l2_slice_hit_share",
            "mem_phase_busy_share", "dir_accesses_per_record",
            "mem_ungated_busy_share", "home_side_busy_share"} <= listed
    assert not listed & {"mem_base_skip_share", "dir_flush_skip_share",
                         "run_fetch_ms", "dispatches_per_run",
                         "run_wall_p95_s"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in listed:
            assert os.path.exists(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
