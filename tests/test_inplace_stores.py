"""The engine's carried stores are updated in place: no phase of the
memory engine makes XLA copy a whole store in the iteration body.

What this guards against is a READER of a carried store that is not a
data-dependence predecessor of the store's scatter.  XLA's copy insertion
cannot order such a read before the in-place write, so it copies the
whole store instead - every iteration, in every program that runs the
phase (PR 32: the requester's second look-up into `ms.l2` was a third of
the served campaign program's device time).  The contract is
`cache_array.scatter_row`'s: "the scatter is then the meta array's only
remaining use and XLA updates the loop-carried buffer in place instead of
copying it."  The jaxpr cannot show a breach (there is no copy in it);
the optimized program can, so these cases compile the programs the drive
loops dispatch - on the CPU backend, at 4 tiles so a case is seconds -
and read the compiled text with `analysis/loop_copies.py`.

The CPU backend's answer is a proxy: `tests/test_chip_compile.py` asks the
TPU compiler the same of the un-gated program.
"""

import numpy as np
import pytest

from graphite_tpu.analysis.loop_copies import (
    computations, copies_of, loop_copies, loops,
)
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.sweep.runner import SweepRunner
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.synthetic import memory_stress_trace

TILES = 4
# the engine's iteration body is the deepest loop that runs the requester
ITERATION = "gt.mem.requester/"
HLO_TYPE = {"int64": "s64", "uint32": "u32", "uint8": "u8"}

# program -> store -> the most whole-store copies the iteration body may
# hold: what PR 32 left.  In brackets what its parent (01e8532) had.
#
# The three that `solo-gated` keeps per cache store are not a phase's:
# they are the whole-engine `mem_gate` cond of engine/step.py as the CPU
# backend compiles it (its operand, its skip arm, the head of its run
# arm); the TPU compiler makes none of them.  `func_mem` is copied around
# `_apply_functional`'s load check, which reads the word AFTER the
# iteration's stores landed (a `set`, not an add: no delta to depend on).
PINNED = {
    "solo-gated": {"l2.meta": 3,            # [5]
                   "l2_cloc": 3,            # [4]
                   "directory.entry": 0, "directory.sharers": 0,
                   "func_mem": 3},
    "solo-phase-gated": {"l2.meta": 0,      # [5]
                         "l2_cloc": 0,      # [3]
                         "directory.entry": 0, "directory.sharers": 0,
                         "func_mem": 0},
    "solo-ungated": {"l2.meta": 0,          # [2]
                     "l2_cloc": 0,          # [2]
                     "directory.entry": 0, "directory.sharers": 0,
                     "func_mem": 2},
    # the staged program carries the entry store as u32 words (PR 45):
    # gathered by column, landed by `row_landing.scatter_entry` here
    "solo-staged": {"l2.meta": 0, "l2_cloc": 0,
                    "directory.entry": 0, "directory.sharers": 0,
                    "func_mem": 0},
    "campaign-b2": {"l2.meta": 0,           # [2]
                    "l2_cloc": 0,
                    "directory.entry": 0, "directory.sharers": 0,
                    "func_mem": 2},
}
SOLO = {"solo-gated": {},
        "solo-phase-gated": {"mem_gate_bytes": 0},
        "solo-staged": {"mem_gate_bytes": 0, "dir_stage": True},
        "solo-ungated": {"phase_gate": False, "mem_gate_bytes": 0}}


def _trace(seed=0):
    return memory_stress_trace(TILES, n_accesses=8, working_set_bytes=8192,
                               write_fraction=0.4, shared_fraction=0.5,
                               seed=seed)


@pytest.fixture(scope="module")
def programs():
    """name -> (compiled text, {store: (shape, HLO type)}), compiled on
    first use."""
    sc = SimConfig(ConfigFile.from_string(
        config_text(TILES, core="simple", shared_mem=True)))
    made = {}

    def stores(sim):
        ms = sim.state.mem
        arrays = {"l2.meta": ms.l2.meta, "l2_cloc": ms.l2_cloc,
                  "directory.entry": ms.directory.entry,
                  "directory.sharers": ms.directory.sharers,
                  "func_mem": ms.func_mem}
        return {k: (tuple(np.shape(v))[-3:], HLO_TYPE[str(v.dtype)])
                for k, v in arrays.items()}

    def get(name):
        if name not in made:
            if name in SOLO:
                sim = Simulator(sc, _trace(), **SOLO[name])
                made[name] = (sim.compiled_text(), stores(sim))
            else:
                runner = SweepRunner(
                    sc, [_trace(0), _trace(1)],
                    [{"dram_latency_ns": 60}, {"dram_latency_ns": 140}],
                    shard_batch=False)
                # a sim's stores carry the batch axis in front
                made[name] = (runner.compiled_text(), stores(runner.sim))
        return made[name]

    return get


@pytest.mark.parametrize("name", sorted(PINNED))
def test_no_store_is_copied_in_the_iteration_body(programs, name):
    text, stores = programs(name)
    copies = loop_copies(text, under=ITERATION)
    found = {store: copies_of(copies, shape, (hlo_type,))
             for store, (shape, hlo_type) in stores.items()}
    # `l2.meta` first, the store this file exists for: it has ONE reader
    # per phase, and that reader feeds the phase's scatter
    for store, most in PINNED[name].items():
        assert len(found[store]) <= most, (
            store, [c.line[:160] for c in found[store]])


@pytest.mark.parametrize("name", sorted(PINNED))
def test_the_iteration_body_is_the_one_searched(programs, name):
    """The reader finds the loop nest it claims to: quantum loop > inner
    block > iteration, the requester named in the third and in no outer
    one (so an outer loop's copies - the per-block and per-quantum
    freeze of a campaign's finished sims - are not counted as the
    iteration's, and the iteration's are not missed)."""
    text, stores = programs(name)
    comps = computations(text)
    nest = loops(comps)
    named = [lp for lp in nest.values()
             if any(ITERATION in line for c in lp.comps for line in comps[c])]
    assert [lp.depth for lp in named] == [3]
    every = loop_copies(text)
    inner = loop_copies(text, under=ITERATION)
    assert {c.loop.depth for c in inner} <= {3, 4}
    assert all(c in every for c in inner)
    if name == "campaign-b2":
        # the freeze is real and lives outside the iteration: each outer
        # loop copies the L2 meta store of the whole batch once a trip
        shape, hlo_type = stores["l2.meta"]
        outer = [c for c in copies_of(every, shape, (hlo_type,))
                 if c.loop.depth < 3]
        assert sorted(c.loop.depth for c in outer) == [1, 2]
        assert all(c.shape[0] == 2 for c in outer)


def test_reader_on_a_made_up_program():
    """`loop_copies` on text small enough to check by eye."""
    text = """HloModule m

%inner_body (p: (s64[4,8])) -> (s64[4,8]) {
  %p = (s64[4,8]{1,0}) parameter(0)
  %g = s64[4,8]{1,0} get-tuple-element(%p), index=0
  %copy.1 = s64[4,8]{1,0} copy(%g), metadata={op_name="jit(f)/gt.mem.requester/gather"}
  ROOT %t = (s64[4,8]{1,0}) tuple(%copy.1)
}

%branch (q: s64[2,4,8]) -> s64[2,4,8] {
  %q = s64[2,4,8]{2,1,0} parameter(0)
  ROOT %copy.2 = s64[2,4,8]{2,1,0} copy(%q)
}

%cond (p: (s64[4,8])) -> pred[] {
  ROOT %c = pred[] constant(true)
}

%outer_body (p: (s64[4,8])) -> (s64[4,8]) {
  %p = (s64[4,8]{1,0}) parameter(0)
  %w = (s64[4,8]{1,0}) while(%p), condition=%cond, body=%inner_body
  %x = s64[2,4,8]{2,1,0} conditional(%k, %a, %a), branch_computations={%branch, %branch}
  %copy.3 = u8[4,8]{1,0:T(8,128)(4,1)} copy(%y)
  ROOT %t = (s64[4,8]{1,0}) tuple(%z)
}

ENTRY %main (a: s64[4,8]) -> s64[4,8] {
  %copy.4 = s64[4,8]{1,0} copy(%a)
  %w = (s64[4,8]{1,0}) while(%t), condition=%cond, body=%outer_body
}
"""
    every = loop_copies(text)
    assert [(c.name, c.loop.depth, c.dtype, c.shape) for c in every] == [
        ("copy.2", 1, "s64", (2, 4, 8)), ("copy.3", 1, "u8", (4, 8)),
        ("copy.1", 2, "s64", (4, 8))]           # copy.4 is in no loop
    assert [c.name for c in loop_copies(text, under=ITERATION)] == ["copy.1"]
    assert [c.name for c in loop_copies(text, min_size=33)] == ["copy.2"]
    assert [c.name for c in copies_of(every, (4, 8), ("s64",))] == [
        "copy.2", "copy.1"]                     # a batch axis in front
    assert copies_of(every, (4, 8), ("u32",)) == []
    with pytest.raises(ValueError):
        loop_copies(text, under="gt.no.such.scope/")
