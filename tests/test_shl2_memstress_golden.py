"""The host-driven program of `memstress1024-shl2` held to the shared-L2
golden model: the independent witness its stored digest cannot be.

The cell's `correct` compares every reading with
`benchmark/references/shl2-mesi-1024-memstress.json`, which the ENGINE
made on XLA's CPU backend: that catches the chip's emulated int64, a
miscompile and any later PR that moves a statistic, and is not
independent of `engine/step.py` or `memory/engine_shl2.py`.  The golden
cannot provide the digest: 128 lines shared by free-running tiles race,
and the golden orders a race in another valid way (BASELINE.md's racy
carve-out).  Here the cell's target at 64 tiles (same config text, `core:
simple`, `pr_l1_sh_l2_mesi`, the host-driven drive loop the cell forces
with `barrier_host=True`) is compared with `graphite_tpu.golden.
run_golden`, whose `golden/memory_model_shl2.py` shares no code with the
engine:

- BIT-EXACT on `clock_ps` and the 18 memory counters the shared-L2 golden
  keeps (the engine's other three, the line-utilisation ones, stay zero),
  where the golden's ordering contract holds: the cell's own generator
  with its private half only, its working set cut so that it is
  line-disjoint AND no slice set is over its 8 ways - the condition step 0
  of ISSUE 38 found (asserted from the trace:
  `benchmark/probe_golden_shl2.py: slice_pressure`); an INV multicast to
  63 sharers; a read-modify-write chain that walks modified lines from
  tile to tile; and a lone reader's EXCLUSIVE grant with its silent E->M
  upgrade (MESI's own path: the store after the load is an L1 hit and
  reaches no home);
- within an ENVELOPE on the cell's own traffic (the measured percentages
  stand beside their limits); what no interleaving can move stays exact;
- at 256 tiles what step 0 settled: the generator's private half at the
  cell's working set overflows slice sets and the two sides may part (a
  slice set's LRU way depends on the order in which unrelated tiles'
  requests reached one home: the golden serves by issue clock, the engine
  by arrival); cut to fit the sets (`fits-256x64`) it is bit-exact.  The
  same at 1024 tiles (`fits-1024x64`), on the target the cell runs;
- and THE STORED DIGEST ITSELF held to the golden
  (`test_cell_1024_digest_within_golden_envelope`): the cell's target
  and traffic at 1024 tiles on the CPU backend reproduce every hash of
  `benchmark/references/shl2-mesi-1024-memstress.json`, and those
  statistics lie inside the configuration's `golden_envelope` (the
  golden's sum, the engine's, a limit between the reading and the
  control's) on every statistic the golden keeps.  `correct` holds the
  chip to that digest exactly, so the chip is held to the envelope
  through it; `lib/checks.py` itself compares hashes only.
"""

import os
import sys

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.memory.params import MemParams
from graphite_tpu.trace.schema import TraceBatch, TraceBuilder
from graphite_tpu.trace.synthetic import memory_stress_trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
sys.path[:0] = [HERE, BENCH]
try:
    from probe_golden_shl2 import CASES, envelope, slice_pressure, summed
    from test_memory_golden import mutex_rmw, share_then_write
    from test_memstress1024_shl2_cell import (
        CELL, GEN, NAME, digest, small, target)
finally:
    sys.path.remove(HERE)
    sys.path.remove(BENCH)

TILES = 64
LINE_UTIL = ("line_util_hist", "line_util_reads", "line_util_writes")


def lone_reader_upgrades(n, lines=6, base=0xC00000):
    """Every tile loads `lines` lines of its own, then stores to each:
    under MESI the load is granted EXCLUSIVE and the store upgrades E->M
    in the L1 with no message; under MSI each store is a second miss."""
    bs = [TraceBuilder() for _ in range(n)]
    for t, b in enumerate(bs):
        for li in range(lines):
            b.load(base + (t * lines + li) * 64, 8)
        for li in range(lines):
            b.store(base + (t * lines + li) * 64, 8)
    return TraceBatch.from_builders(bs)


def stream(tiles, **over):
    return memory_stress_trace(**{**GEN, "n_tiles": tiles, **over})


# name -> (tiles, trace, {counter: least sum the golden must show}): each
# trace makes the phases do the work it is here for
EXACT = {
    "private_half": (TILES, lambda: stream(
        TILES, **{k: CASES["fits-64x64"][0][k]
                  for k in ("shared_fraction", "working_set_bytes")}),
        {"l1d_write_misses": 1000, "l2_misses": 3000}),
    "inv_fanout": (TILES, lambda: share_then_write(TILES, lines=2, rounds=2),
                   {"invalidations": 4 * (TILES - 1)}),
    "rmw_chain": (TILES, lambda: mutex_rmw(TILES, 2, lines=5),
                  {"invalidations": 100, "l2_hits": 200}),
    "excl_upgrade": (TILES, lambda: lone_reader_upgrades(TILES),
                     {"l1d_write_hits": 6 * TILES}),
    # what step 0 settled at 256 tiles: exact once no slice set overflows
    "fits_256x64": (256, lambda: memory_stress_trace(
        **CASES["fits-256x64"][0]), {"l2_misses": 7000}),
    # and at the cell's own size: the 1024-tile target, 1.29 GB of state
    "fits_1024x64": (1024, lambda: memory_stress_trace(
        **CASES["fits-1024x64"][0]), {"l2_misses": 8000}),
}
# the cell's own traffic at 64 tiles: |engine - golden| / golden of the
# summed statistic, limit (measured at the cell's seed 7; the largest of
# seeds 0, 1, 2, 7: CPU counts, PR 38)
ENVELOPE = {
    "l1d_read_misses": 0.01,     # 0.0009 (0.0009)
    "l1d_write_misses": 0.01,    # 0      (0.0019)
    "l2_misses": 0.01,           # 0      (0): no slice set overflows here
    "l2_hits": 0.01,             # 0.0011 (0.0027)
    "dir_accesses": 0.01,        # 0.0005 (0.0013)
    "dram_reads": 0.01,          # 0      (0)
    "invalidations": 0.02,       # 0.0046 (0.0046): 1,305 against 1,311
    "clock_ps": 0.05,            # 0.0154 (0.0180); one tile's: 0.073 (0.103)
}


def both(tiles, batch):
    sc, _ = small(tiles)
    sim = Simulator(sc, batch, **CELL["simulator"])
    assert sim.barrier_host
    assert sim.params.mem.protocol == "pr_l1_sh_l2_mesi"
    return sim, sim.run(), run_golden(sc, batch)


def total(x) -> int:
    return int(np.asarray(x).astype(np.int64).sum())


@pytest.mark.parametrize("name", sorted(EXACT))
def test_host_driven_equals_golden(name):
    tiles, make, least = EXACT[name]
    batch = make()
    sim, res, gold = both(tiles, batch)
    fullest, over = slice_pressure(batch, sim.params.mem)
    assert over == 0 and fullest <= sim.params.mem.l2.num_ways
    np.testing.assert_array_equal(np.asarray(res.clock_ps), gold.clock_ps,
                                  err_msg="clock_ps")
    assert len(gold.mem_counters) == 18
    assert sorted(set(res.mem_counters) - set(gold.mem_counters)) == sorted(
        LINE_UTIL)
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)
    for k in LINE_UTIL:
        assert total(res.mem_counters[k]) == 0, k
    for k, n in least.items():
        assert total(gold.mem_counters[k]) >= n, (k, n)
    assert int(np.asarray(res.func_errors)) == 0


def test_excl_upgrade_is_the_e_state():
    """The control's guarantee, against the golden: the same trace under
    plain MSI turns every silent upgrade into a second request."""
    batch = lone_reader_upgrades(TILES)
    _, mesi, _ = both(TILES, batch)
    sc, _ = small(TILES, **CELL["control"]["config_text"])
    msi = Simulator(sc, batch, **CELL["simulator"]).run()
    gold = run_golden(sc, batch)
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(msi.mem_counters[k]), g,
                                      err_msg=k)
    assert total(mesi.mem_counters["l1d_write_hits"]) == 6 * TILES
    assert total(msi.mem_counters["l1d_write_hits"]) == 0
    assert total(msi.mem_counters["dir_accesses"]) == 2 * total(
        mesi.mem_counters["dir_accesses"])


def test_overflowing_slice_sets_are_outside_the_contract():
    """256 x 64 of the private half at the cell's working set: 996 slice
    sets hold more lines than ways (25 in the fullest).  The two sides
    are then two valid orders of a race and NOT held equal; the trace
    alone says so."""
    batch = memory_stress_trace(**CASES["overflow-256x64"][0])
    mp = MemParams.from_config(small(256)[0])
    fullest, over = slice_pressure(batch, mp)
    assert fullest > mp.l2.num_ways and over > 900
    assert not CASES["overflow-256x64"][1] and CASES["fits-256x64"][1]


def test_cell_traffic_within_golden_envelope():
    batch = stream(TILES)
    sim, res, gold = both(TILES, batch)
    eng = {k: total(v) for k, v in res.mem_counters.items()}
    eng["clock_ps"] = total(res.clock_ps)
    gld = {k: total(v) for k, v in gold.mem_counters.items()}
    gld["clock_ps"] = total(gold.clock_ps)
    # what no interleaving can move is exact: every load and store is
    # one L1D access, every request a home starts a slice hit or miss,
    # every slice miss a DRAM read
    n_mem = TILES * GEN["n_accesses"]
    for side in (eng, gld):
        assert side["l1d_read_hits"] + side["l1d_read_misses"] \
            + side["l1d_write_hits"] + side["l1d_write_misses"] == n_mem
        assert side["l2_hits"] + side["l2_misses"] \
            == side["l1d_read_misses"] + side["l1d_write_misses"]
        assert side["l2_misses"] == side["dram_reads"]
    for a, b in (("l1d_read_hits", "l1d_read_misses"),
                 ("l1d_write_hits", "l1d_write_misses")):
        assert eng[a] + eng[b] == gld[a] + gld[b]
    # the rest to an envelope; the traffic does what the cell is for
    assert gld["invalidations"] > 1000 and gld["l2_hits"] > 1500
    assert int(np.asarray(res.func_errors)) == 0
    for k, limit in ENVELOPE.items():
        rel = abs(eng[k] - gld[k]) / gld[k]
        assert rel <= limit, (k, eng[k], gld[k], rel, limit)


def test_cell_1024_digest_within_golden_envelope():
    """The reference is the engine's own (`cpu-backend`), so something
    independent has to hold IT: here the golden does, at the cell's size
    on the cell's traffic.  The run is the one `make_reference.py` made
    the hashes from, so every statistic compared with the golden is a
    statistic `correct` pins on the chip."""
    env = CELL["golden_envelope"]["statistics"]
    sc, batch = target.build_sim_config(CELL), target.build_trace(CELL)
    res = Simulator(sc, batch, **CELL["simulator"]).run()
    ref = target.load_reference(NAME)
    hs = digest.hashes(digest.statistics(res))
    assert digest.compare(hs, ref["statistics"]) == []
    assert digest.combined(
        {k: hs[k] for k in ref["statistics"]}) == ref["digest"]
    # what the golden provides to a reference (make_reference.GOLDEN_KEYS)
    gold = {k: v for k, v in digest.statistics(run_golden(sc, batch)).items()
            if k.startswith(("clock_ps", "mem_counters."))}
    assert len(gold) == 19
    g, e = summed(gold), summed(digest.statistics(res))
    # what the envelope leaves out is zero on both sides, so exact
    assert {k for k in g if g[k] or e[k]} == set(env)
    for k, v in env.items():
        assert (g[k], e[k]) == (v["golden"], v["engine"]), k
    rows = envelope(g, e, env)
    assert not [r for r in rows if r[3]], rows
    # each limit has room on both sides of the reading, and the control's
    # stored sums (`probe_golden_shl2.py control-1024` makes them: the
    # same traffic under pr_l1_sh_l2_msi) are outside what the E state
    # moves and only that
    assert all(3 * pct <= limit or k in ("clock_ps", "l1d_read_hits",
                                         "l1d_write_hits", "l1d_write_misses")
               for k, pct, limit, _ in rows), rows
    control = {k: v["control"] for k, v in env.items()}
    assert sorted(r[0] for r in envelope(g, control, env) if r[3]) == [
        "invalidations", "l1d_write_hits", "l1d_write_misses"]
