"""The host-driven program of `memstress1024-coh` held to the golden
interpreter: the independent witness its stored digest cannot be.

The cell's `correct` compares every reading with
`benchmark/references/coh-1024-memstress.json`, which the ENGINE made on
XLA's CPU backend: that catches the chip's emulated int64, a miscompile
and any later PR that moves a statistic, and is not independent of
`engine/step.py` or `memory/engine.py`.  The golden cannot provide the
digest: 128 lines shared by free-running tiles race, and the golden
orders a race in another valid way (BASELINE.md's racy carve-out).  Here
the cell's target at 64 tiles (same config text, `core: simple`, the
host-driven drive loop the cell forces with `barrier_host=True`) is
compared with `graphite_tpu.golden.run_golden`, which shares no code with
the engine:

- BIT-EXACT, clocks and all 21 memory counters, where the golden's
  ordering contract holds (tests/test_memory_golden.py): the cell's own
  generator with its private half only - line-disjoint AND no directory
  set over its 16 ways, the condition ROADMAP M6 found (asserted from the
  trace: `benchmark/probe_golden.py: set_pressure`) - an INV multicast
  to 63 sharers, and a read-modify-write chain that walks modified lines
  from tile to tile;
- within an ENVELOPE on the cell's own traffic (the measured percentages
  stand beside their limits); what no interleaving can move stays exact.

At 256 and 1024 tiles the private half alone DOES overflow directory
sets, the NULLIFY victim then depends on the order in which unrelated
tiles' requests reached one home, and golden and engine part by a miss or
two: `benchmark/probe_golden.py` (CPU, minutes) shows that, the
bit-exact variants that fit the sets at those sizes, and the envelope at
1024 tiles (PERF.md section 2).
"""

import os
import sys

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "benchmark")
sys.path[:0] = [HERE, BENCH]
try:
    from probe_golden import set_pressure
    from test_memory_golden import mutex_rmw, share_then_write
    from test_memstress1024_cell import CELL, GEN, small
finally:
    sys.path.remove(HERE)
    sys.path.remove(BENCH)

TILES = 64

# name -> (trace, {counter: least sum the golden must show}): each trace
# makes the phases do the work it is here for
EXACT = {
    "private_half": (lambda: small_stream(0.0),
                     {"l1d_write_misses": 1500, "l2_misses": 3800}),
    "inv_fanout": (lambda: share_then_write(TILES, lines=2, rounds=2),
                   {"invalidations": 4 * (TILES - 1)}),
    "rmw_chain": (lambda: mutex_rmw(TILES, 2, lines=5),
                  {"invalidations": 100, "dram_writes": 100,
                   "evictions": 100}),
}
# the cell's own traffic at 64 tiles: |engine - golden| / golden of the
# summed statistic, limit (measured at the cell's seed 7; the largest of
# seeds 0, 1, 2, 7: CPU counts, PR 35)
ENVELOPE = {
    "l1d_read_misses": 0.01,     # 0.0017 (0.0029)
    "l1d_write_misses": 0.01,    # 0      (0.0018)
    "l2_misses": 0.01,           # 0.0010 (0.0025)
    "dram_reads": 0.01,          # 0.0025 (0.0025)
    "invalidations": 0.04,       # 0.0140 (0.0140)
    "dram_writes": 0.04,         # 0.0044 (0.0152)
    "evictions": 0.25,           # 0.0833 (0.1053): 24 against 26
    "clock_ps": 0.03,            # 0.0054 (0.0093); one tile's: 0.081
}


def small_stream(shared_fraction: float):
    from graphite_tpu.trace.synthetic import memory_stress_trace

    return memory_stress_trace(**{**GEN, "n_tiles": TILES,
                                  "shared_fraction": shared_fraction})


def both(batch):
    sc, _ = small(TILES)
    sim = Simulator(sc, batch, **CELL["simulator"])
    assert sim.barrier_host
    return sim, sim.run(), run_golden(sc, batch)


def total(x) -> int:
    return int(np.asarray(x).astype(np.int64).sum())


@pytest.mark.parametrize("name", sorted(EXACT))
def test_host_driven_equals_golden(name):
    make, least = EXACT[name]
    batch = make()
    sim, res, gold = both(batch)
    fullest, over = set_pressure(batch, sim.params.mem)
    assert over == 0 and fullest <= sim.params.mem.dir_ways
    np.testing.assert_array_equal(np.asarray(res.clock_ps), gold.clock_ps,
                                  err_msg="clock_ps")
    assert len(gold.mem_counters) == 21
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)
    for k, n in least.items():
        assert total(gold.mem_counters[k]) >= n, (k, n)
    assert int(np.asarray(res.func_errors)) == 0


def test_cell_traffic_within_golden_envelope():
    batch = small_stream(GEN["shared_fraction"])
    sim, res, gold = both(batch)
    eng = {k: total(v) for k, v in res.mem_counters.items()}
    eng["clock_ps"] = total(res.clock_ps)
    gld = {k: total(v) for k, v in gold.mem_counters.items()}
    gld["clock_ps"] = total(gold.clock_ps)
    # what no interleaving can move is exact: every load and store is
    # one L1D access, every L2 miss one directory access
    n_mem = TILES * GEN["n_accesses"]
    for side in (eng, gld):
        assert side["l1d_read_hits"] + side["l1d_read_misses"] \
            + side["l1d_write_hits"] + side["l1d_write_misses"] == n_mem
        assert side["l2_misses"] == side["dir_accesses"]
    for a, b in (("l1d_read_hits", "l1d_read_misses"),
                 ("l1d_write_hits", "l1d_write_misses")):
        assert eng[a] + eng[b] == gld[a] + gld[b]
    # the rest to an envelope; the traffic does what the cell is for
    assert gld["invalidations"] > 1000 and gld["dram_writes"] > 300
    assert int(np.asarray(res.func_errors)) == 0
    for k, limit in ENVELOPE.items():
        rel = abs(eng[k] - gld[k]) / gld[k]
        assert rel <= limit, (k, eng[k], gld[k], rel, limit)
