"""`hbh256-radix`'s independent witness: the dense hop-by-hop engine
against `golden.run_golden` (the serial per-hop `_HbhNet`) on the cell's
generator.

The engine routes the packets of one iteration against the port state of
before the iteration (the same-call batching contract,
`models/network_hop_by_hop.py`); the golden one packet at a time.  So:

- **bit-exact where the golden's ordering contract holds** - at most one
  packet a port an iteration: RADIX's histogram, prefix tree and barriers
  (`trace/benchmarks._prefix_tree`: in a round every sender's XY path is
  its own), at 64 tiles through the host-driven path, on `clock_ps`, the
  instruction counts, the ports' requests and utilization and the summed
  delay (a read on the M/G/1 arm books its wait on the next port: four
  reads of 936);
- **an envelope on the all-to-all** - the cell's whole trace at 64 tiles:
  the counters that do not depend on the order are exact, the clocks and
  the delays lie inside the configuration's limits;
- the 38 s golden run at 256 tiles that re-derives the numbers stored in
  `golden_envelope` is `slow` (`benchmark/probe_golden_hbh.py` is the same
  by hand; tier-1 holds the ENGINE's side and the limits on the stored
  numbers: tests/test_hbh256_cell.py).
"""

import os
import sys

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.trace.benchmarks import (
    _BAR, _barrier, _prefix_tree, radix_trace,
)
from graphite_tpu.trace.schema import TraceBatch, TraceBuilder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
try:
    from lib import target
    from probe_golden_hbh import envelope, numbers
finally:
    sys.path.remove(BENCH)

CELL = target.load_config("hbh-256-radix")
GEN = CELL["trace"]["kwargs"]
ENV = CELL["golden_envelope"]["statistics"]
EXACT = ("clock_ps", "recv_instructions", "sync_instructions")


def retired(res):
    """The trace's own instructions: the engine counts the charged recv
    and sync stalls among a tile's instructions, the golden apart."""
    return res.instruction_count - res.recv_instructions \
        - res.sync_instructions


def _config(tiles):
    return target.build_sim_config(
        {"config_text": {**CELL["config_text"], "tiles": tiles}})


def tree_trace(tiles: int, passes: int = 2) -> TraceBatch:
    """`radix_trace` without its permutation: per digit pass the
    histogram `BBLOCK`, a barrier, the prefix tree, a barrier."""
    keys, radix = GEN["keys_per_tile"], GEN["radix"]
    builders = [TraceBuilder() for _ in range(tiles)]
    builders[0].barrier_init(_BAR, tiles)
    for _ in range(passes):
        for b in builders:
            b.bblock(keys * 2 + radix, keys * 2 + radix)
        _barrier(builders)
        _prefix_tree(builders, tiles, radix)
        _barrier(builders)
    return TraceBatch.from_builders(builders)


def both(tiles, batch):
    sc = _config(tiles)
    return (Simulator(sc, batch, **CELL["simulator"]).run(),
            run_golden(sc, batch))


def test_prefix_tree_is_bit_exact_at_64_tiles():
    res, gold = both(64, tree_trace(64))
    for k in EXACT:
        np.testing.assert_array_equal(
            getattr(res, k), getattr(gold, k), err_msg=k)
    np.testing.assert_array_equal(retired(res), gold.instruction_count)
    e, g = res.noc_counters, gold.noc_counters
    assert sorted(e) == sorted(g)
    for k in ("requests", "utilization_cycles"):
        np.testing.assert_array_equal(e[k], g[k], err_msg=k)
    # the tree is long-haul traffic on a quiet mesh: ports are read, and
    # successive rounds do find a port's tail in their way
    assert int(g["requests"].sum()) == 936
    assert int(e["delay_cycles"].sum()) == int(g["delay_cycles"].sum()) > 0
    # the one documented difference (`_dense_contention`): a read on the
    # M/G/1 arm is taken at the SCANNED read time, so where the golden
    # waits 17 cycles analytically at one port and 945 at the next, the
    # engine waits 0 and 962 - the same sum, the same arrival, another
    # port's column; two packets a pass here, on two ports each
    moved = np.argwhere(e["delay_cycles"] != g["delay_cycles"])
    fell = np.argwhere(e["analytical_reads"] != g["analytical_reads"])
    assert len(moved) == 2 * len(fell) == 4, (moved, fell)
    assert int(g["analytical_reads"].sum() - e["analytical_reads"].sum()) == 4
    assert res.func_errors == 0


def test_cell_traffic_at_64_tiles_is_inside_the_envelope():
    """The cell's own generator, all-to-alls included: up to 64 packets
    share an iteration, so the two sides part - by less than the limits
    the configuration sets for 256 tiles, and never on what the order of
    the packets cannot move."""
    res, gold = both(64, radix_trace(**{**GEN, "n_tiles": 64}))
    e, g = numbers(res, ENV), numbers(gold, ENV)
    rows = envelope(g, e, ENV)
    assert not [r for r in rows if r[3]], rows
    for k in ("noc_counters.requests.sum",
              "noc_counters.utilization_cycles.sum"):
        assert e[k] == g[k] > 0, k
    np.testing.assert_array_equal(res.noc_counters["requests"],
                                  gold.noc_counters["requests"])
    # the all-to-all is where they part: the clocks are not equal
    assert e["clock_ps.max"] != g["clock_ps.max"]
    assert e["noc_counters.delay_cycles.sum"] < g[
        "noc_counters.delay_cycles.sum"]
    np.testing.assert_array_equal(retired(res), gold.instruction_count)


def test_control_is_outside_the_envelope_at_64_tiles():
    sc = target.build_sim_config({"config_text": {
        **CELL["config_text"], **CELL["control"]["config_text"],
        "tiles": 64}})
    batch = radix_trace(**{**GEN, "n_tiles": 64})
    ctl = numbers(Simulator(sc, batch, **CELL["simulator"]).run(), ENV)
    gold = numbers(run_golden(_config(64), batch), ENV)
    outside = {r[0] for r in envelope(gold, ctl, ENV) if r[3]}
    assert {"noc_counters.requests.sum",
            "noc_counters.delay_cycles.sum"} <= outside


@pytest.mark.slow
def test_stored_golden_numbers_are_the_goldens_at_256_tiles():
    """38 s: the golden on the cell's trace gives the numbers the
    configuration stores under `golden_envelope`."""
    gold = run_golden(target.build_sim_config(CELL), target.build_trace(CELL))
    assert numbers(gold, ENV) == {k: v["golden"] for k, v in ENV.items()}
