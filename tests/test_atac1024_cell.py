"""`memstress1024-atac` (benchmark/configs/atac-ackwise-1024-memstress.json):
what the cell assumes of the program, held at sizes tier-1 can afford.

The cell runs the 1,024-core ATAC target - 64 optical clusters of 16,
coherence messages over `memory = atac`, the ACKwise_4 limited directory -
on `memstress1024-coh`'s generator at half its length, host-driven.  Its
reference is the engine's own on XLA's CPU backend (the lines race, and
the engine routes an iteration's packets against the hub state of before
the iteration), so what holds the engine to the independent golden
(`golden/memory_model.py` with the serial hub oracle `_AtacNet`) is here:

- engine == golden BIT FOR BIT at 64 tiles (4 clusters of 16 and 16
  clusters of 4) where the golden's ordering contract holds - one packet
  a hub an iteration, nothing races: a read-modify-write chain, an INV
  broadcast sweep of an overflowed entry that 63 holders acknowledge, and
  the generator's private half, every access under one mutex - in the
  clocks, the 21 memory counters (`dir_broadcasts` non-zero) and the four
  hub counters of `SimResults.atac_counters`;
- on the cell's own generator at 64 tiles, the configuration's
  `golden_envelope`: the engine's every percentage inside the limit the
  configuration states, the `emesh_hop_counter` control's outside;
- host-driven == single-region, bit for bit on every statistic, at 16 and
  64 tiles, and the traced slice (`run_chunk(3)` after one quantum) is
  live and chunked == whole;
- `atac_counters` is `None`, out of the digest and out of `summary()`
  under `emesh_hop_counter`;
- `tools/_template.config_text(atac_cluster_size=)` leaves the text of
  every other configuration byte-identical;
- the five per-layer readers the cell adds, on a recorded `ctx`.

The 1,024-tile engine run on the CPU backend is 100 s from an empty
compile cache: reproducing the STORED hashes is `make_reference.py`'s and
`benchmark/probe_golden_atac.py`'s (both call `check_expectations` on the
built 1,024-tile target), not a tier-1 test's; the program is asked of
the TPU compiler in `tests/test_chip_compile.py -m slow -k atac`.
"""

import functools
import hashlib
import json
import os
import sys
import types

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.memory.params import MemParams
from graphite_tpu.models.network_atac import ATAC_COUNTERS, _cluster_of
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.schema import FLAG_MEM0_WRITE, TraceBatch, TraceBuilder
from graphite_tpu.trace.synthetic import memory_stress_trace

from targets import fresh_mem_noc, mem_net_at

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import digest, paths, target
    from probe_golden_hbh import envelope, numbers
finally:
    sys.path.remove(BENCH)

NAME, CELL_NAME = "atac-ackwise-1024-memstress", "memstress1024-atac"
LAYER_NOC = "NoC + mailboxes - engine/step.py net block, models/network_*"
LAYER_MEM = "memory engines - memory/engine.py"
CELL = target.load_config(NAME)
GEN = CELL["trace"]["kwargs"]
ENV = CELL["golden_envelope"]["statistics"]
COUNTER_NAMES = [name for name, _ in ATAC_COUNTERS]
NEW_METRICS = ["atac_hub_busy_share", "atac_fanout_busy_share",
               "hub_wait_cycles_per_packet", "hub_fallback_share",
               "dir_broadcasts_per_record"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "traffic", "solo-repeat.json")) as _f:
    TRAFFIC = json.load(_f)


SWEPT = 0xA00000     # the line of `serialized`'s broadcast sweep


def sim_config(tiles: int, **text):
    """The cell's target at `tiles` tiles."""
    return target.build_sim_config(
        {"config_text": {**CELL["config_text"], **text, "tiles": tiles}})


def racy(tiles: int) -> TraceBatch:
    """The cell's own generator at `tiles` tiles."""
    return memory_stress_trace(**{**GEN, "n_tiles": tiles})


def serialized(tiles: int, writer: int = 36) -> TraceBatch:
    """Three phases between barriers, every access under ONE mutex, so
    that one tile at a time touches memory and one packet at a time a
    hub: engine iteration order and the golden's clock order coincide.
    (1) a read-modify-write chain of every fourth tile over two shared
    lines; (2) every tile
    loads one line (its ACKwise_4 entry overflows), then `writer` - a
    holder in another cluster than the line's home - stores to it: a
    broadcast INV sweep of every tile that the 63 other holders
    acknowledge, and four loads from four clusters that read the send
    hub the sweep occupied; (3) the generator's private half: its first
    two draws a tile over the tile's own working set."""
    bs = [TraceBuilder() for _ in range(tiles)]
    bs[0].mutex_init(0)
    bs[0].barrier_init(9, tiles)

    def barrier():
        for b in bs:
            b.barrier_wait(9)

    def locked(t, addr, store=False, load=True):
        bs[t].mutex_lock(0)
        if load:
            bs[t].load(addr, 8)
        if store:
            bs[t].store(addr, 8)
        bs[t].mutex_unlock(0)

    barrier()
    for t in range(0, tiles, 4):
        locked(t, 0x900000 + (t // 4 % 2) * 64, store=True)
    barrier()
    for t in range(tiles):
        locked(t, SWEPT)
    barrier()
    locked(writer, SWEPT, store=True, load=False)
    barrier()
    for t in (1, 5, writer, tiles - 1):
        locked(t, SWEPT + 64)
    barrier()
    private = memory_stress_trace(**{**GEN, "n_tiles": tiles,
                                     "n_accesses": 2,
                                     "shared_fraction": 0.0})
    for t in range(tiles):
        for i in range(2):
            write = bool(private.flags[t, i] & FLAG_MEM0_WRITE)
            locked(t, int(private.addr0[t, i]), store=write, load=not write)
    return TraceBatch.from_builders(bs)


@functools.lru_cache(maxsize=None)
def pair_at(tiles: int):
    """(host-driven simulator, its initial state, its whole run's
    results, the single-region simulator, its results, the trace) on the
    cell's generator: clusters of 16 as the cell's at 64 tiles, of 4 at
    16 (where 16 would be ONE cluster and no packet would see a hub)."""
    sc = sim_config(tiles, atac_cluster_size=16 if tiles >= 64 else 4)
    batch = racy(tiles)
    host = Simulator(sc, batch, **CELL["simulator"])
    initial = host.state
    whole = host.run()
    one = Simulator(sc, batch)
    return host, initial, whole, one, one.run(), batch


@pytest.fixture(scope="module", params=[16, 64])
def pair(request):
    return pair_at(request.param)


# --- the configuration ----------------------------------------------------

def test_configuration_is_what_the_manifest_lists():
    assert CELL["config_text"] == {
        "tiles": 1024, "core": "simple", "shared_mem": True,
        "clock_scheme": "lax_barrier", "network": "atac",
        "protocol": "pr_l1_pr_l2_dram_directory_msi", "scheme": "ackwise",
        "max_hw_sharers": 4, "atac_cluster_size": 16}
    # memstress1024-coh's generator and every parameter of it but the
    # length, so the two cells differ by network, scheme and n_accesses
    coh = target.load_config("coh-1024-memstress")
    assert CELL["trace"] == {**coh["trace"], "kwargs": {
        **coh["trace"]["kwargs"], "n_accesses": 32}}
    for k in ("tiles", "core", "shared_mem", "clock_scheme", "protocol"):
        assert CELL["config_text"][k] == coh["config_text"][k], k
    for k, v in coh["expect"].items():
        if k not in ("params.mem.dir_type", "params.mem.net_kind"):
            assert CELL["expect"][k] == v, k
    assert CELL["simulator"] == {"barrier_host": True}
    assert CELL["reduced"] == ["n_accesses"] == list(CELL["reduced_detail"])
    assert CELL["control"]["config_text"] == {"network": "emesh_hop_counter"}
    assert any("k = 4" in a for a in CELL["assumed"])
    assert any("simple core" in a for a in CELL["assumed"])
    entry, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert (entry["source"], entry["reduced"], entry["file"]) == (
        CELL["source"], ["n_accesses"], f"benchmark/configs/{NAME}.json")
    assert len(entry["source"]) <= 200
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL_NAME]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        NAME, "solo-repeat", 1)
    # appended: the eighth configuration, the eighth cell, five metrics
    # behind `stage_overlay_busy_share`, and the last name of every list
    # it joined (PR 51's served V/f cell and its three metrics follow)
    assert MANIFEST["configs"][7] is entry
    assert MANIFEST["workloads"][7] is cell
    names = [m["name"] for m in MANIFEST["per_layer"]]
    first = names.index("stage_overlay_busy_share") + 1
    assert names[first:first + 5] == NEW_METRICS
    assert names[first + 5:] == [
        "served_lane_idle_share", "power_demux_ms",
        "served_dvfs_sets_per_job"]
    joined = [m for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]
              if CELL_NAME in m.get("workloads", [])]
    assert all(m["workloads"][m["workloads"].index(CELL_NAME) + 1:]
               in ([], ["vfsweep256-canneal"]) for m in joined)
    # ... which are the lists that hold memstress1024-coh, and its own
    assert {m["name"] for m in joined} == set(NEW_METRICS) | {
        m["name"] for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]
        if "memstress1024-coh" in m.get("workloads", [])}
    ref = target.load_reference(NAME)
    assert ref["origins"] == ["cpu-backend"]
    assert ref["trace"] == CELL["trace"]
    assert ref["config_text"] == CELL["config_text"]
    # the 39 of a private-L2 simple-core target and the four hub counters
    assert len(ref["statistics"]) == 39 + 4
    assert sorted(k for k in ref["statistics"]
                  if k.startswith("atac_counters.")) == sorted(
        "atac_counters." + n for n in COUNTER_NAMES)
    batch = target.build_trace(CELL)
    assert batch.n_tiles * batch.length == 33_792
    # every limit has its reason, and lies between its two readings
    for k, v in ENV.items():
        assert v["why"], k
        (_, got, limit, _), = envelope(
            {k: v["golden"]}, {k: v["engine"]}, {k: v})
        (_, ctl, _, out), = envelope(
            {k: v["golden"]}, {k: v["control"]}, {k: v})
        assert got < limit < ctl and out, (k, got, limit, ctl)
    assert ENV["mem_counters.dir_broadcasts.sum"]["engine"] == 801
    assert ENV["atac_counters.requests.sum"]["engine"] == 181_866


# sha256 of config_text(64, ...) as the parent of PR 48 wrote it
TEXT_PINS = {
    (): "68545580c89239b34b90bcc4c73d432a7baf6a5ead6694505b84de1e663f8a12",
    (("shared_mem", True), ("network", "atac"), ("scheme", "ackwise"),
     ("max_hw_sharers", 4)):
        "d1583c0f6d5b4a83c0fb80dad590f8589622fd4615d1838d6f3afa7e9b74e7aa",
}


@pytest.mark.parametrize("kw", list(TEXT_PINS), ids=["defaults", "atac"])
def test_config_text_defaults_are_unchanged(kw):
    text = config_text(64, **dict(kw))
    assert hashlib.sha256(text.encode()).hexdigest() == TEXT_PINS[kw]
    assert "network/atac" not in text
    with_it = config_text(64, **dict(kw), atac_cluster_size=16)
    assert with_it.replace("[network/atac]\ncluster_size = 16\n", "") == text


def test_expect_holds_at_64_tiles():
    """The configuration's `expect`, with what follows the tile count
    scaled: 4 clusters of 16 on the 8 x 8 mesh, the T1 caches and the
    ACKwise_4 directory as stated."""
    host = pair_at(64)[0]
    expect = {**CELL["expect"], "params.n_tiles": 64,
              "params.mem.net_atac.n_clusters": 4}
    target.check_expectations({"expect": expect}, host)
    p = host.params.mem.net_atac
    assert (p.mesh_width, p.mesh_height) == (8, 8)
    # waveguide 10 ps/mm x (8 + 8) mm, E-O + O-E a cycle each at 1 GHz
    assert p.optical_link_ps == 160 + 2000
    assert host.params.user_atac is not None      # `network` names both


# --- engine == golden, bit for bit -----------------------------------------

@pytest.mark.parametrize("cluster", [16, 4], ids=["4x16", "16x4"])
def test_engine_equals_golden_bit_for_bit_where_nothing_races(cluster):
    sc = sim_config(64, atac_cluster_size=cluster)
    batch = serialized(64)
    res = Simulator(sc, batch, **CELL["simulator"]).run()
    gold = run_golden(sc, batch)
    assert res.func_errors == 0
    np.testing.assert_array_equal(res.clock_ps, gold.clock_ps, err_msg="clock")
    assert len(gold.mem_counters) == 21
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)
    assert sorted(res.atac_counters) == sorted(gold.atac_counters) \
        == sorted(COUNTER_NAMES)
    # ONE place is outside the contract by the sweep's nature: its 63
    # acknowledgements reach the home in one iteration, so those that
    # leave their cluster read the home's RECEIVE hub in one call, each
    # against the hub's state of before it (`scatter_queue_delay`'s
    # same-call contract), where the golden queues one behind another
    # that left later but had the shorter way.  The last to arrive waits
    # in neither, so the clocks above are exact; the summed delay at that
    # one hub is the engine's 0 against the golden's 192 (4 x 16) or 36
    # (16 x 4) cycles.  Everywhere else all four counters are exact
    mp = MemParams.from_config(sc)
    home = mp.mc_tiles[(SWEPT // mp.line_size) % len(mp.mc_tiles)]
    acks = mp.net_atac.n_clusters + int(_cluster_of(mp.net_atac,
                                                    np.int32(home)))
    for k in COUNTER_NAMES:
        e, g = res.atac_counters[k], gold.atac_counters[k]
        assert e.shape == (2 * 64 // cluster,)
        if k == "delay_cycles":
            assert 0 <= e[acks] < g[acks], (e[acks], g[acks])
            e, g = np.delete(e, acks), np.delete(g, acks)
        np.testing.assert_array_equal(e, g, err_msg=k)
    # the sweep: ONE broadcast, acknowledged by the 63 other holders
    mc = res.mem_counters
    assert int(np.asarray(mc["dir_broadcasts"]).sum()) == 1
    assert int(np.asarray(mc["invalidations"]).sum()) >= 63
    ac = res.atac_counters
    half = 64 // cluster
    assert int(ac["requests"][:half].sum()) > 0
    assert int(ac["requests"][half:].sum()) > 0
    # the sweep's one charge: every tile outside the home's cluster
    assert int(ac["utilization_cycles"][:half].max()) >= 64 - cluster
    assert int(ac["delay_cycles"].sum()) > 0


def test_queue_charges_hold_no_scatter_and_no_gather():
    """The engagement counter of `scatter_queue_delay`'s dense lowering is
    static, so it is a test: at the cell's shape - 1,024 lanes onto 2 x 64
    + 1 hub queues - a whole `route_atac` (send hub, receive hub) holds no
    scatter and no gather, and neither does the hop-by-hop fan-out's
    charge of 1,024 lanes onto 1,024 x 6 + 1 ports."""
    import jax
    import jax.numpy as jnp

    from graphite_tpu.analysis.walk import iter_eqns
    from graphite_tpu.memory.engine import mem_net_fanout
    from graphite_tpu.models.network_atac import init_atac_state, route_atac
    from graphite_tpu.models.network_hop_by_hop import init_noc_state

    def indexed(fn, *args):
        names = {e.primitive.name
                 for e in iter_eqns(jax.make_jaxpr(fn)(*args))}
        return sorted(n for n in names
                      if n.startswith("scatter") or n == "gather")

    T = 1024
    tiles = jnp.arange(T, dtype=jnp.int32)
    t0 = jnp.zeros(T, jnp.int64)
    p = MemParams.from_config(sim_config(T)).net_atac
    hubs = init_atac_state(p)
    assert hubs.hub_queues.data.shape == (129, 10)
    assert indexed(
        lambda st: route_atac(p, st, tiles, tiles[::-1], 64, t0,
                              jnp.ones(T, bool), True), hubs) == []

    mp = MemParams.from_config(sim_config(T, network="emesh_hop_by_hop"))
    ports = init_noc_state(mp.net_hbh)
    assert ports.queues.data.shape == (6145, 10)
    assert indexed(
        lambda st: mem_net_fanout(mp, st, jnp.ones((T, T), bool), 64, t0,
                                  True), ports) == []


@pytest.mark.parametrize("kind", ["emesh_hop_counter", "atac",
                                  "emesh_hop_by_hop"])
def test_fanout_divides_no_matrix_at_a_static_frequency(kind):
    """The engagement counter of PR 50 is static, so it is a test: at T =
    1,024 the jaxpr of `mem_net_fanout` holds no `div` / `rem` with a
    [T, T] result at the cells' static 1,000 MHz (10^6 / f reduced to a
    multiplication at trace time).  A traced frequency keeps the
    division."""
    import jax
    import jax.numpy as jnp

    from graphite_tpu.analysis.walk import iter_eqns
    from graphite_tpu.memory.engine import mem_net_fanout

    T = 1024
    mp = MemParams.from_config(sim_config(T, network=kind))
    net = mp.net_atac or mp.net_hbh
    assert (net.freq_mhz if net else mp.net_freq_mhz) == 1000
    args = (fresh_mem_noc(mp), jnp.ones((T, T), bool),
            jnp.zeros(T, jnp.int64), jnp.asarray(True))

    def fanout(freq, noc, send, t0, enabled):
        return mem_net_fanout(mem_net_at(mp, freq), noc, send, mp.req_bits,
                              t0, enabled)

    def divisions(fn, *operands):
        return sum(1 for e in iter_eqns(jax.make_jaxpr(fn)(*operands))
                   if e.primitive.name in ("div", "rem")
                   and e.outvars[0].aval.shape == (T, T))

    assert divisions(functools.partial(fanout, 1000), *args) == 0
    assert divisions(fanout, jnp.asarray(1000, jnp.int64), *args) >= 1


# --- the cell's own traffic -------------------------------------------------

def test_host_driven_equals_single_region(pair):
    host, _, whole, one, single, _ = pair
    assert host.barrier_host and not one.barrier_host
    a, b = digest.statistics(whole), digest.statistics(single)
    assert len(a) == 39 + 4 and sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert host.last_n_iterations == one.last_n_iterations
    assert whole.func_errors == 0
    mc, ac = whole.mem_counters, whole.atac_counters
    if len(whole.clock_ps) >= 64:
        # (16 tiles overflow no entry of k = 4 pointers before a store)
        assert int(np.asarray(mc["dir_broadcasts"]).sum()) > 0
    assert int(ac["requests"].sum()) > 0
    assert int(ac["analytical_reads"].sum()) > 0
    summary = whole.summary()
    assert "ATAC Hub Summary (MEMORY)" in summary
    assert summary.count("  Cluster ") == len(ac["requests"]) // 2


def test_traced_slice_is_live_and_chunked_equals_whole():
    host, initial, whole, *_ = pair_at(64)
    skip, n = TRAFFIC["trace_skip_quanta"], TRAFFIC["trace_quanta"]
    host.state = initial
    done, quanta = host.run_chunk(skip)
    assert not done and quanta == skip
    hubs = np.asarray(host.state.mem.noc.hub_queues.total_requests).sum()
    done, more = host.run_chunk(n)
    assert not done and more == n
    assert host.last_n_iterations >= 2 * n
    # the slice routes packets over the hubs
    assert np.asarray(
        host.state.mem.noc.hub_queues.total_requests).sum() > hubs
    quanta += more
    while not done:
        done, more = host.run_chunk(5)
        quanta += more
    assert quanta == whole.n_quanta
    chunked = digest.statistics(
        host._results_from_state(quanta, host._spans(None)))
    for k, v in digest.statistics(whole).items():
        np.testing.assert_array_equal(chunked[k], v, err_msg=k)


def test_golden_envelope_at_64_tiles_and_the_control():
    """The reference is the engine's own, the cell's lines race and its
    hubs are read by many packets an iteration: what holds the engine to
    the independent golden on such traffic is the configuration's
    `golden_envelope`.  Here at 64 tiles (4 clusters of 16): the engine's
    every percentage inside the limit the configuration states, the
    `emesh_hop_counter` control's outside.  The 1,024-tile numbers
    themselves are `probe_golden_atac.py`'s."""
    host, _, res, _, _, batch = pair_at(64)
    gold = numbers(run_golden(host.config, batch), ENV)
    rows = envelope(gold, numbers(res, ENV), ENV)
    assert [r for r in rows if r[3]] == []
    ctl = Simulator(sim_config(64, **CELL["control"]["config_text"]), batch,
                    **CELL["simulator"]).run()
    out = {r[0] for r in envelope(gold, numbers(ctl, ENV), ENV) if r[3]}
    assert {"clock_ps.sum", "clock_ps.max"} | {
        f"atac_counters.{n}.sum" for n in COUNTER_NAMES} <= out
    # no hub, no counters: out of the results, the digest and the summary
    assert ctl.atac_counters is None
    assert not [k for k in digest.statistics(ctl) if "atac" in k]
    assert len(digest.statistics(ctl)) == 39
    assert "ATAC Hub Summary" not in ctl.summary()
    # the stored 1,024-tile numbers: the engine's inside every limit, the
    # control's outside every one
    stored = lambda k: {s: v[k] for s, v in ENV.items()}    # noqa: E731
    assert not [r for r in envelope(stored("golden"), stored("engine"),
                                    ENV) if r[3]]
    assert all(r[3] for r in envelope(stored("golden"), stored("control"),
                                      ENV))


# --- the per-layer readers the cell adds ----------------------------------

COUNTERS = {"requests": np.array([900, 90, 10, 0]),
            "utilization_cycles": np.array([5_000, 0, 0, 0]),
            "delay_cycles": np.array([30_000, 4_000, 1_000, 0]),
            "analytical_reads": np.array([800, 80, 4, 0])}
BUSY = {"gt.net.atac.hub": 3.0, "gt.net.atac.fanout": 1.0,
        "gt.net.route": 0.5, "gt.mem.home_start": 2.0, "gt.core": 1.5,
        "unscoped": 2.0}


def _ctx(busy=None, counters=None, config=None, mem=True):
    results = types.SimpleNamespace(
        atac_counters=counters, clock_ps=np.array([7, 8]),
        mem_counters={"dir_broadcasts": np.array([800, 1]),
                      "invalidations": np.array([14_000, 56])}
        if mem else None)
    scoped = None if busy is None else {
        "scoped": True, "spans": [], "busy_s": busy}
    return types.SimpleNamespace(
        readings=[{"records": 33_792, "results": results}],
        own={"scope_trace": scoped}, config=config or {})


READERS = [
    ("atac_hub_busy_share", _ctx(busy=BUSY), 30.0),
    ("atac_fanout_busy_share", _ctx(busy=BUSY), 10.0),
    # a program without the scopes: the parent of the PR that added them
    ("atac_hub_busy_share", _ctx(busy={"gt.net.route": 1.0}), None),
    ("atac_fanout_busy_share", _ctx(busy={"gt.net.route": 1.0}), None),
    ("atac_hub_busy_share", _ctx(), None),
    ("atac_fanout_busy_share", _ctx(), None),
    ("hub_wait_cycles_per_packet", _ctx(counters=COUNTERS), 35.0),
    # the configuration's golden envelope is printed, never judged, here
    ("hub_wait_cycles_per_packet", _ctx(counters=COUNTERS, config=CELL),
     35.0),
    ("hub_fallback_share", _ctx(counters=COUNTERS), 88.4),
    # a program without the counters (the parent), or another network
    ("hub_wait_cycles_per_packet", _ctx(), None),
    ("hub_fallback_share", _ctx(), None),
    ("dir_broadcasts_per_record", _ctx(), 801 / 33_792),
    ("dir_broadcasts_per_record", _ctx(mem=False), None),
]


@pytest.mark.parametrize("name,ctx,want", READERS)
def test_layer_metric_readers(name, ctx, want):
    entry, = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [CELL_NAME]
    assert (entry["moves"], entry["better"]) == ("sim_records_per_s", "lower")
    assert entry["layer"] == (LAYER_MEM if name.startswith("dir_")
                              else LAYER_NOC)
    assert entry["source"] == ("device_trace" if name.startswith("atac_")
                               else "program_counter")
    sys.path.insert(0, BENCH)
    try:
        got = paths.load_module("layer_metrics", name).read(ctx)
    finally:
        sys.path.remove(BENCH)
    assert got == (None if want is None else pytest.approx(want))


def test_readers_find_nothing_in_an_older_program():
    """The driver runs the benchmark's files over the parent too: where
    the program has no such counter, scope or results, a reader returns
    None and does not raise."""
    ctx = types.SimpleNamespace(
        readings=[{"records": 10, "results": types.SimpleNamespace()}],
        own={"scope_trace": None}, config={})
    empty = types.SimpleNamespace(readings=[], own={"scope_trace": None},
                                  config={})
    sys.path.insert(0, BENCH)
    try:
        for name in NEW_METRICS:
            reader = paths.load_module("layer_metrics", name)
            assert reader.read(ctx) is None, name
            assert reader.read(empty) is None, name
    finally:
        sys.path.remove(BENCH)


def test_net_busy_share_holds_both_scopes():
    """`net_busy_share` (its reader is not this PR's to edit) takes every
    `gt.net.*` scope: the hubs' and the fan-out's are inside it."""
    sys.path.insert(0, BENCH)
    try:
        got = paths.load_module("layer_metrics", "net_busy_share").read(
            _ctx(busy=BUSY))
    finally:
        sys.path.remove(BENCH)
    assert got == pytest.approx(45.0)
