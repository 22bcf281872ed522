"""The L1 victim's look-up in the L2, folded into the phase's one gather
of the L2 store (PR 32), held to the golden interpreter.

When an L1 fill evicts a line, the engine clears that line's cached-loc
in the L2 (`l2_cloc`), for which it must find the victim's way in the
victim's L2 set.  `memory/engine.py` now fetches that set in the SAME
gather as the request's (`cache_array.gather_row_pair`), because a second
gather of the carried store made XLA copy it whole
(tests/test_inplace_stores.py).  These are the directed cases of that
fold, beside `tests/test_campaign_golden.py`'s serialised-sharing ones:
engine against `golden.run_golden` (`core: simple`, which shares no code
with the engine), clocks and all 21 memory counters EXACT, solo through
`Simulator.run()` and served through `CampaignService(batch_size=4)` -
the un-gated `vmap` program - at four DRAM latencies.

Every tile works on lines of its own (golden's ordering contract:
tests/test_memory_golden.py), and the tiles run the episodes shifted
against each other, so one engine iteration holds lanes that fill from
the L2, lanes that miss, and lanes that upgrade.  Where two tiles touch
one line they take turns under a mutex and barriers.

Geometry (the reference's T1 caches): L1D 128 sets x 4 ways, L2 1024 sets
x 8 ways, 64-byte lines - 8 KB apart is the same L1D set in another L2
set, 64 KB apart the same set of both.
"""

import os
import sys

import numpy as np
import pytest

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.serve.job import Job
from graphite_tpu.serve.service import CampaignService
from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_memory_golden import MSI, make_config  # noqa: E402

TILES = 4
LATENCIES = (60, 100, 140, 180)
L1_SET = 8 << 10        # bytes between lines of one L1D set
L2_SET = 64 << 10       # ... of one L2 set (and one L1D set)
REGION = (4 << 20) + (16 << 10)   # between two tiles' private regions:
#                                   apart in the directory's sets too (16
#                                   ways a set: 64 KB strides of four
#                                   tiles in one set would evict entries)


def region(t):
    return 0x1000000 + t * REGION


def refill(b, base, stride, rounds=3):
    """Five lines of one L1D set (four ways): every later load misses the
    L1, hits the L2, and fills the L1 over a VALID victim, whose L2 set
    is the request's own (`stride` = L2_SET) or another (L1_SET)."""
    for _ in range(rounds):
        for k in range(5):
            b.load(base + k * stride, 8)


def upgrades(b, base, n=4):
    """Load then store: the store finds the line SHARED (MSI has no
    exclusive state), invalidates it in the L1 and the L2, sends the
    eviction and refetches; the reply fills a way that is invalid with a
    stale tag."""
    for k in range(n):
        b.load(base + k * 64, 8)
        b.store(base + k * 64, 8)


def shifted(episodes):
    """Tile t runs the episodes rotated by t: one iteration, many kinds."""
    bs = [TraceBuilder() for _ in range(TILES)]
    for t, b in enumerate(bs):
        for i in range(len(episodes)):
            episodes[(i + t) % len(episodes)](b, region(t) + i * (1 << 20))
    return TraceBatch.from_builders(bs)


def same_set():
    return shifted([lambda b, a: refill(b, a, L2_SET),
                    lambda b, a: upgrades(b, a)])


def other_set():
    return shifted([lambda b, a: refill(b, a, L1_SET),
                    lambda b, a: upgrades(b, a)])


def free_way():
    """With the I-cache modelled, an instruction fetch brings its line
    into the L1I and the L2; a load of the same line then misses the L1D,
    hits the L2 and fills a way that was NEVER used: the victim's line
    reads -1 (`gather_row_pair`'s floor-mod row)."""
    def fetch_then_load(b, base):
        for k in range(6):
            b.instr(Op.IALU, pc=base + k * 64)
            b.load(base + k * 64, 8, pc=base + k * 64)

    return shifted([fetch_then_load, lambda b, a: refill(b, a, L2_SET, 2)])


def upgrade_beside_fill():
    """`requester_unroll` 3: a record's slots start in ONE iteration, each
    pass reading the stores its predecessor wrote.  `load_store` reads a
    line that refills the L1 from the L2 and writes a SHARED line (the
    upgrade) in the same record."""
    def both(b, base):
        for k in range(5):
            b.load(base + k * L2_SET, 8)            # the L1D set, once over
        for k in range(4):
            b.load(base + (1 << 19) + k * 64, 8)    # SHARED lines to upgrade
        for k in range(4):
            b.load_store(base + k * L2_SET, base + (1 << 19) + k * 64, 8)

    return shifted([both, lambda b, a: refill(b, a, L1_SET, 2)])


def one_victim_for_both():
    """The requester-fill's case: the line the L1 evicts is the very line
    the L2 evicts in the same fill.  X is kept in the L1D by hits (which
    do not touch the L2's recency) while seven more lines of its set fill
    the L2's eight ways; then X is least recent in both, and a ninth line
    N evicts it from both at once.  The victim must then be looked up in
    the L2 AS THE FILL LEAVES IT (X is gone), or the clear lands on N's
    own cached-loc - and tile 0 would miss the invalidation of N's L1D
    copy when tile 1 writes N, and hit a stale line afterwards."""
    bs = [TraceBuilder() for _ in range(TILES)]
    bs[0].barrier_init(9, TILES)
    for b in bs:
        b.barrier_wait(9)
    a = region(0)
    x, lines = a, [a + k * L2_SET for k in range(1, 8)]
    n = a + 8 * L2_SET
    b = bs[0]
    b.load(x, 8)
    for addr in lines[:3]:
        b.load(addr, 8)
    b.load(x, 8)                    # L1 hit: X most recent in the L1D only
    b.load(lines[3], 8)
    b.load(lines[4], 8)
    b.load(x, 8)
    b.load(lines[5], 8)
    b.load(lines[6], 8)             # the L2 set is full: X and seven more
    b.load(lines[4], 8)             # L1 hit: now X is the L1D's oldest too
    b.load(n, 8)                    # evicts X from the L2 and from the L1D
    for t in range(TILES):
        bs[t].barrier_wait(9)
    bs[1].store(n, 8)               # invalidates tile 0's copies of N
    for t in range(TILES):
        bs[t].barrier_wait(9)
    bs[0].load(n, 8)                # must MISS the L1D
    for t in range(2, TILES):       # the others keep the lanes mixed
        refill(bs[t], region(t), L2_SET, 2)
    return TraceBatch.from_builders(bs)


# name -> (trace, config text beyond the default, {counter: least total
# the golden must show}): each trace does what it is here for
CASES = {
    "same_set": (same_set, "", {"l2_hits": 4 * 10, "evictions": 4 * 4}),
    "other_set": (other_set, "", {"l2_hits": 4 * 10, "evictions": 4 * 4}),
    "free_way": (free_way, "[general]\nenable_icache_modeling = true\n",
                 {"l1i_misses": 4 * 6, "l2_hits": 4 * 6}),
    "upgrade_beside_fill": (upgrade_beside_fill,
                            "[general]\nrequester_unroll = 3\n",
                            {"l2_hits": 4 * 4, "evictions": 4 * 4}),
    "one_victim_for_both": (one_victim_for_both, "",
                            {"invalidations": 1, "evictions": 1}),
}


def config(name, latency_ns=None):
    extra = CASES[name][1]
    if latency_ns is not None:
        extra += f"[dram]\nlatency = {latency_ns}\n"
    return make_config(TILES, MSI, net="emesh_hop_counter", extra=extra)


@pytest.fixture(scope="module")
def traces():
    return {name: make() for name, (make, _, _) in CASES.items()}


@pytest.fixture(scope="module")
def served(traces):
    """Every trace at the four latencies through ONE service: a batch is
    one trace at every latency, as in `campaign64-dram`."""
    svc = CampaignService(batch_size=4, store=None, shard_batch=False,
                          n_devices=1, max_dwell_s=0)
    for name, trace in traces.items():
        for lat in LATENCIES:
            svc.submit(Job(job_id=f"{name}-L{lat}", config=config(name),
                           trace=trace, knobs={"dram_latency_ns": lat}))
    return {e.job_id: e for e in svc.drain(force=True)}


def assert_equals_golden(results, gold, least):
    np.testing.assert_array_equal(np.asarray(results.clock_ps),
                                  gold.clock_ps, err_msg="clock_ps")
    assert len(gold.mem_counters) == 21
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(
            np.asarray(results.mem_counters[k]), g, err_msg=k)
    for k, n in least.items():
        assert int(np.asarray(gold.mem_counters[k]).sum()) >= n, (k, n)
    assert int(np.asarray(results.func_errors)) == 0


@pytest.mark.parametrize("name", sorted(CASES))
def test_solo_equals_golden(traces, name):
    sc = config(name)
    assert_equals_golden(Simulator(sc, traces[name]).run(),
                         run_golden(sc, traces[name]), CASES[name][2])


@pytest.mark.parametrize("lat", LATENCIES)
@pytest.mark.parametrize("name", sorted(CASES))
def test_served_equals_golden(traces, served, name, lat):
    env = served[f"{name}-L{lat}"]
    assert env.status == "ok" and env.knob_point == {"dram_latency_ns": lat}
    assert_equals_golden(env.results,
                         run_golden(config(name, lat), traces[name]),
                         CASES[name][2])


def test_the_stale_hit_would_show(traces):
    """`one_victim_for_both` ends on the load that tells the two look-ups
    apart: tile 0 misses its L1D there (golden), five loads of X and
    lines[4] having hit before."""
    gold = run_golden(config("one_victim_for_both"),
                      traces["one_victim_for_both"])
    assert int(gold.mem_counters["l1d_read_hits"][0]) == 3
    assert int(gold.mem_counters["l1d_read_misses"][0]) == 10
