"""`hbh256-radix` (benchmark/configs/hbh-256-radix.json): what the cell
assumes of the program, held at sizes tier-1 can afford.

The cell is BASELINE.json's third graduated configuration - 256 tiles
under `emesh_hop_by_hop`, the per-hop contention NoC
(`models/network_hop_by_hop.py`), on the RADIX skeleton at SPLASH-2's
size - host-driven (`barrier_host=True`) so that the benchmark can take a
bounded traced slice, with no memory engine.  So:

- the configuration loads through `benchmark/lib/target.py`, is what
  `BENCHMARK.json` lists, and its `expect` holds on the built 256-tile
  `Simulator`;
- on the cell's generator at 16 and 64 tiles, host-driven == single
  region in every statistic, `noc_counters` among them; stepping the
  host-driven run quantum by quantum (`run_chunk(1)`, ROADMAP M9) equals
  the whole run;
- `SimResults.noc_counters` counts what the model did: every packet reads
  XY distance + 2 ports, utilization is its flits on each, the summed
  delay is the summed `contention_ps` that `route_hop_by_hop` returns;
  and it is None under `emesh_hop_counter`, the cell's control;
- the 256-tile target on the CPU backend reproduces every stored hash,
  lies inside every limit of the configuration's `golden_envelope`
  against the STORED golden numbers (tests/test_hbh256_golden.py
  re-derives those), the control's stored numbers lie outside, and the
  traced slice (`solo-repeat-q19`) is the first permutation all-to-all;
- the four per-layer readers the cell adds, on a recorded `ctx`.
"""

import json
import os
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.models.network_hop_by_hop import (
    NOC_COUNTERS, NUM_PORTS, PORT_INJECT, PORT_SELF, HopByHopParams,
    init_noc_state, noc_counters, route_hop_by_hop,
)
from graphite_tpu.models.network_user import user_packet_bits
from graphite_tpu.trace.benchmarks import radix_trace
from graphite_tpu.trace.schema import Op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import checks, digest, paths, target
    from probe_golden_hbh import envelope, numbers
finally:
    sys.path.remove(BENCH)

NAME, CELL_NAME = "hbh-256-radix", "hbh256-radix"
LAYER = "NoC + mailboxes - engine/step.py net block, models/network_*"
CELL = target.load_config(NAME)
GEN = CELL["trace"]["kwargs"]
ENV = CELL["golden_envelope"]["statistics"]
COUNTER_NAMES = [name for name, _ in NOC_COUNTERS]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def small(tiles: int, **text):
    """The cell's target and traffic at `tiles` tiles."""
    sc = target.build_sim_config(
        {"config_text": {**CELL["config_text"], **text, "tiles": tiles}})
    return sc, radix_trace(**{**GEN, "n_tiles": tiles})


@pytest.fixture(scope="module", params=[16, 64])
def pair(request):
    """(host-driven simulator, its initial state, its whole run's
    results, the single-region simulator, its results, the trace)."""
    sc, batch = small(request.param)
    host = Simulator(sc, batch, **CELL["simulator"])
    initial = host.state
    whole = host.run()
    one = Simulator(sc, batch)
    return host, initial, whole, one, one.run(), batch


def _sends(batch):
    """(src, dst, payload bytes) of every SEND record of the trace."""
    src, idx = np.nonzero(batch.op == int(Op.SEND))
    return (src, batch.aux0[src, idx].astype(np.int64),
            batch.aux1[src, idx].astype(np.int64))


def test_configuration_is_graduated_config_3():
    assert CELL["config_text"] == {
        "tiles": 256, "core": "simple", "shared_mem": False,
        "clock_scheme": "lax_barrier", "network": "emesh_hop_by_hop"}
    assert CELL["trace"] == {
        "module": "benchmarks", "function": "radix_trace",
        "kwargs": {"n_tiles": 256, "keys_per_tile": 4096, "radix": 1024}}
    assert CELL["simulator"] == {"barrier_host": True}
    assert CELL["reduced"] == []
    assert CELL["control"]["config_text"] == {"network": "emesh_hop_counter"}
    entry, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert (entry["source"], entry["reduced"], entry["file"]) == (
        CELL["source"], [], f"benchmark/configs/{NAME}.json")
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL_NAME]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "solo-repeat-q19", 1)
    # the traffic is solo-repeat's letter for letter but for the slice
    assert _traffic("solo-repeat-q19") == {
        **_traffic("solo-repeat"), "trace_skip_quanta": 18, "trace_quanta": 1}
    ref = target.load_reference(NAME)
    assert ref["origins"] == ["cpu-backend"]
    assert ref["trace"] == CELL["trace"]
    assert len(ref["statistics"]) == 22
    assert sorted(k for k in ref["statistics"] if "." in k) == sorted(
        "noc_counters." + n for n in COUNTER_NAMES)
    # every limit has its reason, and lies between its two readings
    for k, v in ENV.items():
        assert v["why"], k
        (_, got, limit, _), = envelope(
            {k: v["golden"]}, {k: v["engine"]}, {k: v})
        (_, ctl, _, out), = envelope(
            {k: v["golden"]}, {k: v["control"]}, {k: v})
        assert got <= limit < ctl and out, (k, got, limit, ctl)


def test_expect_holds_on_the_built_256_tile_target():
    batch = target.build_trace(CELL)
    assert checks.trace_records(batch) == 404_981
    ops, counts = np.unique(batch.op, return_counts=True)
    by_op = {Op(int(o)).name: int(c) for o, c in zip(ops, counts)}
    del by_op["NOP"]
    assert by_op == {"SEND": 197_370, "NET_RECV": 197_370, "BBLOCK": 7_680,
                     "BARRIER_WAIT": 2_304, "THREAD_EXIT": 256,
                     "BARRIER_INIT": 1}
    _, _, payload = _sends(batch)
    assert sorted(np.unique(payload, return_counts=True)[1]) == [
        1_530, 195_840]                    # the prefix trees / 3 all-to-alls
    sim = Simulator(target.build_sim_config(CELL), batch,
                    **CELL["simulator"])
    target.check_expectations(CELL, sim)
    # no memory engine; the state the cell is there for is the port store
    assert sim.state.mem is None and sim.params.mem is None
    q = sim.state.noc_user.queues.data
    assert (q.shape, str(q.dtype)) == ((256 * NUM_PORTS + 1, 10), "int64")


def test_host_driven_equals_single_region(pair):
    host, _, whole, one, single, _ = pair
    assert host.barrier_host and not one.barrier_host
    a, b = digest.statistics(whole), digest.statistics(single)
    assert len(a) == 22 and sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert host.last_n_iterations == one.last_n_iterations
    assert whole.func_errors == 0


def test_stepping_quantum_by_quantum_equals_the_whole_run(pair):
    """ROADMAP M9: the host-driven loop keeps its last boundary beside
    the state, so `run_chunk(1)` again and again walks the run's own
    quanta (it restarted the window arithmetic at 0 on every call, and on
    a SEND + barrier trace spun through idle quanta after a few)."""
    host, initial, whole, _, _, _ = pair
    whole_iterations = None
    for step in (1, 3):
        host.state = initial            # the setter clears the boundary
        assert host._hb_prev_qend is None
        quanta = iterations = 0
        done = False
        while not done and quanta <= whole.n_quanta:
            done, n = host.run_chunk(step)
            quanta += n
            iterations += int(host.last_n_iterations)
        assert done and quanta == whole.n_quanta
        chunked = digest.statistics(host._results_from_state(quanta))
        for k, v in digest.statistics(whole).items():
            np.testing.assert_array_equal(chunked[k], v, err_msg=k)
        whole_iterations = whole_iterations or iterations
        assert iterations == whole_iterations
    # and a run() starts its windows at 0 whatever a chunk left behind
    host.state = initial
    host.run_chunk(2)
    assert host._hb_prev_qend is not None
    host._state = initial
    again = digest.statistics(host.run())
    for k, v in digest.statistics(whole).items():
        np.testing.assert_array_equal(again[k], v, err_msg=k)


def test_counters_count_every_port_on_the_xy_path(pair):
    host, _, whole, _, _, batch = pair
    nc, p = whole.noc_counters, host.params.user_hbh
    assert sorted(nc) == sorted(COUNTER_NAMES)
    assert all(v.shape == (p.n_tiles, NUM_PORTS) and v.dtype == np.int64
               for v in nc.values())
    src, dst, payload = _sends(batch)
    w = p.mesh_width
    dist = abs(src % w - dst % w) + abs(src // w - dst // w)
    flits = -(-np.asarray(user_packet_bits(payload)) // p.flit_width_bits)
    assert int(nc["requests"].sum()) == int((dist + 2).sum())
    assert int(nc["utilization_cycles"].sum()) == int(
        (flits * (dist + 2)).sum())
    # a packet is injected at its sender and delivered at its receiver
    np.testing.assert_array_equal(
        nc["requests"][:, PORT_INJECT], np.bincount(src, minlength=p.n_tiles))
    np.testing.assert_array_equal(
        nc["requests"][:, PORT_SELF], np.bincount(dst, minlength=p.n_tiles))
    np.testing.assert_array_equal(nc["requests"][:, PORT_INJECT],
                                  whole.packets_sent)
    assert np.all(nc["analytical_reads"] <= nc["requests"])
    # contention is a part of the packets' latency, and a large one here
    cycle_ps = 10**6 // p.freq_mhz
    assert 0 < int(nc["delay_cycles"].sum()) * cycle_ps < int(
        whole.total_packet_latency_ps.sum())


def test_delay_cycles_sum_the_contention_the_route_returns():
    """`route_hop_by_hop` returns each packet's `contention_ps` and
    `engine/step.py` drops it; the port store's delay column is the same
    sum, kept: round after round of random packets on a 4 x 4 mesh."""
    sc, _ = small(16)
    p = HopByHopParams.from_config(sc, "user")
    rng = np.random.default_rng(42)
    nst, total_ps, clock = init_noc_state(p), 0, np.zeros(16, np.int64)
    for _ in range(40):
        live = rng.random(16) < 0.7
        dst = rng.integers(0, 16, 16)
        clock = clock + 1000 * rng.integers(0, 30, 16)
        nst, arrival, zero_load, contention = route_hop_by_hop(
            p, nst, np.arange(16), dst, user_packet_bits(
                jnp.asarray(rng.choice([8, 64, 4096], 16))),
            jnp.asarray(clock), jnp.asarray(live), True)
        assert np.all(np.asarray(contention)[~live] == 0)
        np.testing.assert_array_equal(
            np.asarray(arrival)[live],
            (clock + np.asarray(zero_load) + np.asarray(contention))[live])
        total_ps += int(np.asarray(contention).sum())
    nc = noc_counters(np.asarray(nst.queues.data), 16)
    assert total_ps > 0
    assert int(nc["delay_cycles"].sum()) * (10**6 // p.freq_mhz) == total_ps


def test_no_counters_under_the_hop_counter():
    """The control: `emesh_hop_counter` has no port queue, so the run
    reports no `noc_counters` (`lib/digest.statistics` then skips the
    field, as it does for the five other configurations) and packets are
    faster."""
    sc, batch = small(16, **CELL["control"]["config_text"])
    res = Simulator(sc, batch, **CELL["simulator"]).run()
    assert res.noc_counters is None
    assert not [k for k in digest.statistics(res) if "noc" in k]
    assert "Port Requests" not in res.summary()
    hbh = Simulator(small(16)[0], batch, **CELL["simulator"]).run()
    assert int(res.total_packet_latency_ps.sum()) < int(
        hbh.total_packet_latency_ps.sum())
    assert "    Port Requests: " in hbh.summary()
    assert hbh.summary().count("Analytical Model Used") == 16


def test_cell_256_digest_within_golden_envelope():
    """The reference is the engine's own (`cpu-backend`), so something
    independent has to hold IT: the golden does, through the
    configuration's `golden_envelope`.  The run here is the one
    `make_reference.py` made the hashes from, so every number compared
    with the golden's is a statistic `correct` pins on the chip."""
    sc, batch = target.build_sim_config(CELL), target.build_trace(CELL)
    sim = Simulator(sc, batch, **CELL["simulator"])
    initial = sim.state
    res = sim.run()
    assert (sim.last_n_iterations, res.n_quanta, sim.last_run_dispatches) \
        == (1_733, 55, 7)
    ref = target.load_reference(NAME)
    hs = digest.hashes(digest.statistics(res))
    assert digest.compare(hs, ref["statistics"]) == []
    assert digest.combined(
        {k: hs[k] for k in ref["statistics"]}) == ref["digest"]
    engine = numbers(res, ENV)
    assert engine == {k: v["engine"] for k, v in ENV.items()}
    golden = {k: v["golden"] for k, v in ENV.items()}
    assert not [r for r in envelope(golden, engine, ENV) if r[3]]
    # the control's stored numbers are outside (all eight: no contention
    # moves the clocks, and it keeps no port counter)
    control = {k: v["control"] for k, v in ENV.items()}
    assert all(r[3] for r in envelope(golden, control, ENV))
    # what the cell reports of the run: the contention model's share of
    # the packets' latency, and the reads on the M/G/1 arm
    nc = res.noc_counters
    assert 100 * int(nc["analytical_reads"].sum()) / int(
        nc["requests"].sum()) == pytest.approx(88.362, abs=1e-3)
    assert int(res.total_packet_latency_ps.sum()) == 29_212_137_000
    # the traced slice (traffic/solo-repeat-q19.json): quantum 19 is the
    # whole first permutation all-to-all
    traffic = _traffic("solo-repeat-q19")
    sim.state = initial
    done, n = sim.run_chunk(traffic["trace_skip_quanta"])
    assert (done, n, sim.last_n_iterations) == (False, 18, 67)
    sent = int(np.asarray(sim.state.net.packets_sent).sum())
    done, n = sim.run_chunk(traffic["trace_quanta"])
    assert (done, n, sim.last_n_iterations) == (False, 1, 513)
    assert int(np.asarray(sim.state.net.packets_sent).sum()) - sent == 65_280


def _ctx(busy=None, counters=None, config=None, hbh=True):
    results = types.SimpleNamespace(
        noc_counters=counters, clock_ps=np.array([7, 8]),
        recv_instructions=np.array([1, 2]),
        sync_instructions=np.array([3, 0]),
        total_packet_latency_ps=np.array([4_000_000, 1_000_000]))
    scoped = None if busy is None else {
        "scoped": True, "spans": [], "busy_s": busy}
    sim = types.SimpleNamespace(params=types.SimpleNamespace(
        user_hbh=types.SimpleNamespace(freq_mhz=1000) if hbh else None))
    return types.SimpleNamespace(
        readings=[{"records": 404_981, "results": results}],
        own={"scope_trace": scoped, "sim": sim}, config=config or {})


COUNTERS = {"requests": np.array([[900, 90], [10, 0]]),
            "utilization_cycles": np.array([[5_000, 0], [0, 0]]),
            "delay_cycles": np.array([[3_000, 400], [100, 0]]),
            "analytical_reads": np.array([[800, 80], [4, 0]])}
BUSY = {"gt.net.hbh.scan": 3.0, "gt.net.hbh.commit": 1.0,
        "gt.net.mailbox": 2.0, "gt.net.route": 0.5, "gt.core": 1.5,
        "unscoped": 2.0}
READERS = [
    ("hbh_scan_busy_share", _ctx(busy=BUSY), 30.0),
    ("hbh_commit_busy_share", _ctx(busy=BUSY), 10.0),
    # a program without the scopes: the parent of the PR that added them
    ("hbh_scan_busy_share", _ctx(busy={"gt.net.route": 1.0}), None),
    ("hbh_commit_busy_share", _ctx(busy={"gt.net.route": 1.0}), None),
    ("hbh_scan_busy_share", _ctx(), None),
    ("hbh_commit_busy_share", _ctx(), None),
    ("noc_contention_share", _ctx(counters=COUNTERS), 70.0),
    # the configuration's golden envelope is printed, never judged, here
    ("noc_contention_share", _ctx(counters=COUNTERS, config=CELL), 70.0),
    # a program without the counters (the parent), or another network
    ("noc_contention_share", _ctx(), None),
    ("noc_contention_share", _ctx(counters=COUNTERS, hbh=False), None),
    ("noc_fallback_share", _ctx(counters=COUNTERS), 88.4),
    ("noc_fallback_share", _ctx(), None),
]


@pytest.mark.parametrize("name,ctx,want", READERS)
def test_layer_metric_readers(name, ctx, want):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL_NAME]
    assert entry["moves"] == "sim_records_per_s"
    assert entry["layer"] == LAYER
    assert entry["source"] == ("device_trace" if name.startswith("hbh_")
                               else "program_counter")
    sys.path.insert(0, BENCH)
    try:
        got = paths.load_module("layer_metrics", name).read(ctx)
    finally:
        sys.path.remove(BENCH)
    assert got == (None if want is None else pytest.approx(want))


def test_net_busy_share_holds_both_halves():
    """`net_busy_share` (its reader is not this PR's to edit) takes every
    `gt.net.*` scope: the two halves are inside it."""
    sys.path.insert(0, BENCH)
    try:
        got = paths.load_module("layer_metrics", "net_busy_share").read(
            _ctx(busy=BUSY))
    finally:
        sys.path.remove(BENCH)
    assert got == pytest.approx(65.0)


def test_cell_reports_what_the_manifest_lists():
    """ISSUE 42's list: the drive loop's, the quantum loop's and the
    device's metrics and the set-up spans; none of the memory engines'
    (no engine is built) and no p95 (some tens of readings a window)."""
    listed = {m["name"] for m in MANIFEST["per_layer"] + MANIFEST[
        "end_to_end"] if CELL_NAME in m.get("workloads", [CELL_NAME])}
    assert listed == {
        "sim_records_per_s", "peak_hbm_gb", "setup_s", "compile_s",
        "outside_run_share", "records_per_iter", "wall_per_iter_ms",
        "idle_iter_share", "device_idle_share", "core_busy_share",
        "net_busy_share", "sync_busy_share", "unscoped_busy_share",
        "dispatches_counted", "run_dispatch_ms", "import_s",
        "trace_build_s", "state_place_s", "lower_s", "program_load_s",
        "program_compile_s", "programs_compiled", "setup_traced_s",
        "hbh_scan_busy_share", "hbh_commit_busy_share",
        "noc_contention_share", "noc_fallback_share"}
    for m in MANIFEST["per_layer"]:
        if m["name"] in listed:
            assert os.path.exists(os.path.join(
                BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    # appended: the new cell was the last of every list it joined (PR 44's
    # `canneal1024-dvfs` and PR 48's `memstress1024-atac` follow it), and
    # the four new metrics came last, in this order (PR 43's, PR 44's
    # three, PR 45's, PR 46's, PR 48's five and PR 51's three follow)
    assert [w["name"] for w in MANIFEST["workloads"]][5] == CELL_NAME
    assert [c["name"] for c in MANIFEST["configs"]][5] == NAME
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index("hbh_scan_busy_share")
    assert names[first:first + 4] == [
        "hbh_scan_busy_share", "hbh_commit_busy_share",
        "noc_contention_share", "noc_fallback_share"]
    assert names[first + 4:] == [
        "stage_flush_busy_share", "dvfs_busy_share", "energy_busy_share",
        "dvfs_sets_per_run", "entry_land_busy_share",
        "stage_overlay_busy_share", "atac_hub_busy_share",
        "atac_fanout_busy_share", "hub_wait_cycles_per_packet",
        "hub_fallback_share", "dir_broadcasts_per_record",
        "served_lane_idle_share", "power_demux_ms",
        "served_dvfs_sets_per_job"]
    for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]:
        if CELL_NAME in m.get("workloads", []):
            later = m["workloads"][m["workloads"].index(CELL_NAME) + 1:]
            assert later in ([], ["canneal1024-dvfs"],
                             ["canneal1024-dvfs", "memstress1024-atac"],
                             ["canneal1024-dvfs", "memstress1024-atac",
                              "vfsweep256-canneal"]), m["name"]
