"""The served campaign program held to the golden interpreter: the
independent witness of what `campaign64-dram` runs and its stored
digests cannot vouch for.

The cell's `correct` compares served envelopes with digests the engine
itself made on the CPU backend (`benchmark/make_reference_campaign.py`):
independent of `serve/`, `sweep/`, `vmap` and the knob operands, not of
`engine/step.py` or `memory/engine.py`.  What is new in that cell is the
program: the engine under `vmap` (B = 4), the whole-engine `mem_gate`
OFF and, since ISSUE 36, the six phase gates and the home gate ON with
their predicates OR-ed over the batch, its MSI phases 2-5 doing work
(INV fan-out, write-backs of modified lines, evictions), the DRAM
latency a traced knob.  Here that program - the cell's own 64-tile target
(`benchmark/configs/ref-default-64-campaign.json`) with the in-order core
the golden interpreter models (the configuration's `control`), through
`CampaignService(batch_size=4)` at the cell's four latencies - is
compared with `graphite_tpu.golden.run_golden`, which shares no code with
the engine and gets its latency through the config text:

- BIT-EXACT, clocks and all 21 memory counters, on the sharing the
  golden's ordering contract covers (tests/test_memory_golden.py):
  the cell's own generator with its private half only (line-disjoint,
  40% stores, evictions; at 64 tiles no directory set holds more lines
  than its 16 ways - past that, line-disjoint traffic races for the
  set's victim and is not exact either: BASELINE.md, ROADMAP M6), an INV
  multicast to 63 sharers, and a
  read-modify-write chain that walks modified lines from tile to tile
  (write-back, downgrade, invalidation of the old owner);
- within an ENVELOPE on the cell's own traffic.  Free-running tiles that
  share 32 lines race for them, the engine's iteration order and the
  oracle's clock order resolve a race differently (both are valid
  serialisations under lax synchronisation: BASELINE.md's racy-workload
  carve-out), so no exact digest of this traffic can come from the
  golden.  The order-independent identities stay exact.

Not witnessed by anything independent of the engine: every statistic
under iocoom (ROADMAP M7), which is what the cell's digests hold.
"""

import json
import os
import sys

import numpy as np
import pytest

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.golden import run_golden
from graphite_tpu.memory.engine import PHASE_NAMES
from graphite_tpu.serve.job import Job
from graphite_tpu.serve.service import CampaignService
from graphite_tpu.sweep.runner import SweepRunner
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.synthetic import memory_stress_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_memory_golden import mutex_rmw, share_then_write  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "ref-default-64-campaign.json")) as _f:
    CELL = json.load(_f)
with open(os.path.join(ROOT, "benchmark", "traffic",
                       "campaign-closed.json")) as _f:
    TRAFFIC = json.load(_f)
LATENCIES = tuple(TRAFFIC["dram_latency_ns"])
TILES = CELL["config_text"]["tiles"]
GEN = CELL["trace"]["kwargs"]


def text(latency_ns=None) -> str:
    """The cell's target with its control's core (`simple`)."""
    args = {**CELL["config_text"], **CELL["control"]["config_text"]}
    t = config_text(args.pop("tiles"), **args)
    if latency_ns is not None:
        t += f"\n[dram]\nlatency = {latency_ns}\n"
    return t


def cell_stream(shared_fraction: float):
    return memory_stress_trace(**{**GEN, "shared_fraction": shared_fraction})


# name -> (trace, {counter: least sum the golden must show}): each trace
# makes the phases do the work it is here for
EXACT = {
    "private_half": (lambda: cell_stream(0.0),
                     {"l1d_write_misses": 2000, "evictions": 100}),
    "inv_fanout": (lambda: share_then_write(TILES, lines=2, rounds=2),
                   {"invalidations": 4 * (TILES - 1)}),
    "rmw_chain": (lambda: mutex_rmw(TILES, 2, lines=5),
                  {"invalidations": 100, "dram_writes": 100,
                   "evictions": 100}),
}
# the cell's own traffic: |engine - golden| / golden of the summed
# statistic, limit (largest measured over the pool's 16 jobs at 64 tiles,
# PR 31: PERF.md section 2)
ENVELOPE = {
    "l1d_read_misses": 0.02,     # 0.0116
    "l1d_write_misses": 0.02,    # 0.0036
    "l2_misses": 0.015,          # 0.0078
    "dram_reads": 0.015,         # 0.0089
    "invalidations": 0.05,       # 0.0324
    "dram_writes": 0.07,         # 0.0409
    "evictions": 0.10,           # 0.0521
    "clock_ps": 0.08,            # 0.0439 (one tile's clock: 0.163)
}


@pytest.fixture(scope="module")
def served():
    """Every trace at the four latencies through ONE service: a batch of
    four is one trace at every latency, as in the cell."""
    sc = SimConfig(ConfigFile.from_string(text()))
    traces = {name: make() for name, (make, _) in EXACT.items()}
    traces["cell"] = cell_stream(GEN["shared_fraction"])
    svc = CampaignService(**TRAFFIC["service"])
    for name, trace in traces.items():
        for lat in LATENCIES:
            svc.submit(Job(job_id=f"{name}-L{lat}", config=sc, trace=trace,
                           knobs={"dram_latency_ns": lat}))
    envelopes = {e.job_id: e for e in svc.drain(force=True)}
    return {"svc": svc, "traces": traces, "envelopes": envelopes}


def golden(served, name, lat):
    sc = SimConfig(ConfigFile.from_string(text(lat)))
    return run_golden(sc, served["traces"][name])


def total(x) -> int:
    return int(np.asarray(x).astype(np.int64).sum())


def test_the_program_is_the_cells(served):
    """4-wide batches of one trace at the four latencies, nothing padded,
    on the cell's geometry; and a 4-wide runner, as the service builds
    one per batch, has the phase gates on (ISSUE 36) and the whole-engine
    gate off; the envelopes carry what the gates skipped."""
    svc = served["svc"]
    log = list(svc.batch_log)
    assert [(b.n_jobs, b.batch_cap) for b in log] == [(4, 4)] * 4
    assert svc.counters["padded_slots"] == 0
    for cls in svc.admission.classes.values():
        assert cls.params.n_tiles == TILES
        assert cls.params.iocoom is None
        assert cls.params.mem.protocol == CELL["expect"][
            "params.mem.protocol"]
    runner = SweepRunner(
        SimConfig(ConfigFile.from_string(text())),
        [served["traces"]["cell"]] * 4,
        [{"dram_latency_ns": lat} for lat in LATENCIES], shard_batch=False)
    assert runner.sim.params.mem.phase_gate
    assert not runner.sim.params.mem_gate
    for env in served["envelopes"].values():
        assert set(env.phase_skips) == set(PHASE_NAMES)
        assert 0 < sum(env.phase_skips.values()) < 6 * env.n_iterations
        assert 0 < env.base_skips["base"] < env.n_iterations


@pytest.mark.parametrize("lat", LATENCIES)
@pytest.mark.parametrize("name", sorted(EXACT))
def test_served_program_equals_golden(served, name, lat):
    env = served["envelopes"][f"{name}-L{lat}"]
    assert env.status == "ok" and env.knob_point == {"dram_latency_ns": lat}
    gold = golden(served, name, lat)
    np.testing.assert_array_equal(np.asarray(env.results.clock_ps),
                                  gold.clock_ps, err_msg="clock_ps")
    assert len(gold.mem_counters) == 21
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(
            np.asarray(env.results.mem_counters[k]), g, err_msg=k)
    for k, least in EXACT[name][1].items():
        assert total(gold.mem_counters[k]) >= least, (k, least)
    assert int(np.asarray(env.results.func_errors)) == 0


@pytest.mark.parametrize("lat", LATENCIES)
def test_cell_traffic_within_golden_envelope(served, lat):
    env = served["envelopes"][f"cell-L{lat}"]
    assert env.status == "ok"
    gold = golden(served, "cell", lat)
    eng = {k: total(v) for k, v in env.results.mem_counters.items()}
    eng["clock_ps"] = total(env.results.clock_ps)
    gld = {k: total(v) for k, v in gold.mem_counters.items()}
    gld["clock_ps"] = total(gold.clock_ps)
    # what no interleaving can move is exact: every load and store is
    # one L1D access, every L2 miss one directory access
    n_mem = int(TILES * GEN["n_accesses"])
    for side in (eng, gld):
        assert side["l1d_read_hits"] + side["l1d_read_misses"] \
            + side["l1d_write_hits"] + side["l1d_write_misses"] == n_mem
        assert side["l2_misses"] == side["dir_accesses"]
    for a, b in (("l1d_read_hits", "l1d_read_misses"),
                 ("l1d_write_hits", "l1d_write_misses")):
        assert eng[a] + eng[b] == gld[a] + gld[b]
    # the rest to an envelope; the traffic does what the cell is for
    assert gld["invalidations"] > 1000 and gld["dram_writes"] > 500
    for k, limit in ENVELOPE.items():
        rel = abs(eng[k] - gld[k]) / gld[k]
        assert rel <= limit, (k, eng[k], gld[k], rel, limit)
