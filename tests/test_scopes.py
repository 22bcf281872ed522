"""Device scope names (graphite_tpu/obs/scopes.py): every registered name
lands in the `op_name` paths of the programs that must contain it, an
unregistered name is refused, and the scopes change no equation (the
lowered jaxpr is `identity.same_program` with and without them, so
PROGRAMS.lock and BUDGETS.json cannot move).
"""

import contextlib
import re

import jax
import pytest

from graphite_tpu.analysis import identity
from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine import step
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.memory import engine, engine_shl2
from graphite_tpu.memory.engine import PHASE_NAMES
from graphite_tpu.memory.engine_shl2 import SHL2_PHASE_NAMES
from graphite_tpu.models import iocoom, network_atac, network_hop_by_hop
from graphite_tpu.obs import TelemetrySpec, scopes
from graphite_tpu.parallel import px
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace.benchmarks import (
    canneal_trace, fft_trace, radix_trace,
)
from graphite_tpu.trace.synthetic import memory_stress_trace

TILES = 16
MSI = "pr_l1_pr_l2_dram_directory_msi"
SHL2 = "pr_l1_sh_l2_msi"
SCOPED_MODULES = (step, engine, engine_shl2, iocoom, network_hop_by_hop,
                  network_atac, px)

# what each program must contain: everything but the scopes whose code it
# does not run
ONLY_SHARDED = {"gt.px"}
ONLY_SHL2 = {"gt.mem.dir_apply"}     # the embedded directory's landing
# the two halves of emesh_hop_by_hop's dense contention (PR 42)
ONLY_HBH = {"gt.net.hbh.scan", "gt.net.hbh.commit"}
# an energy interval's close inside the DVFS arm: only with [general]
# enable_power_modeling (PR 44)
ONLY_POWER = {"gt.energy"}
# the landing on the u32 entry words (PR 45) and the staging table's index
# and value fetches (PR 46): only in a STAGED private-L2 program (`msi`
# below is one)
ONLY_STAGED = {"gt.mem.stage_flush", "gt.mem.entry_land",
               "gt.mem.stage_overlay"}
# the hubs' queue charges and the fan-out's ATAC leg (PR 48): only under
# `memory = atac`
ONLY_ATAC = {"gt.net.atac.hub", "gt.net.atac.fanout"}
MSI_SCOPES = [s for s in scopes.SCOPES
              if s not in ONLY_SHARDED | ONLY_SHL2 | ONLY_HBH | ONLY_POWER
              | ONLY_ATAC]
SHL2_SCOPES = [s for s in scopes.SCOPES
               if s not in ONLY_SHARDED | ONLY_HBH | ONLY_POWER
               | ONLY_STAGED | ONLY_ATAC | {"gt.core.iocoom", "gt.obs"}]
# the memoryless hop-by-hop target (`hbh256-radix`'s, at 16 tiles): the
# core, the mailboxes, the route with its two halves, the barrier
HBH_SCOPES = ["gt.quantum", "gt.fetch", "gt.core", "gt.net.mailbox",
              "gt.net.route", "gt.sync.barrier"] + sorted(ONLY_HBH)
# `canneal1024-dvfs`'s target at 16 tiles: the private-L2 program with
# the simple core, two DVFS domains and power modelling on
DVFS_SCOPES = [s for s in MSI_SCOPES if s not in ONLY_STAGED | {
    "gt.core.iocoom", "gt.obs"}] + sorted(ONLY_POWER)
# `memstress1024-atac`'s target at 16 tiles (four clusters of 4): the
# private-L2 program with the simple core, ACKwise_4 over `memory = atac`
ATAC_SCOPES = [s for s in MSI_SCOPES if s not in ONLY_STAGED | {
    "gt.core.iocoom", "gt.obs"}] + sorted(ONLY_ATAC)
TWO_DOMAINS = ("<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE> "
               "<1.0, DIRECTORY, NETWORK_USER, NETWORK_MEMORY>")


def build(program: str):
    if program == "dvfs-served":
        # `vfsweep256-canneal`'s program at 16 tiles: the `dvfs` target
        # under a sim axis (two jobs of a V/f sweep), its directory staged
        # as the 256-tile cell's is
        from graphite_tpu.sweep.runner import SweepRunner

        text = config_text(TILES, shared_mem=True, protocol=MSI, dvfs=True,
                           dvfs_domains=TWO_DOMAINS, power=True)
        return SweepRunner(
            SimConfig(ConfigFile.from_string(text)),
            [canneal_trace(TILES, footprint_lines=200, swaps_per_tile=2,
                           temperature_steps=2,
                           dvfs_schedule=f"level-{k}") for k in (0, 5)],
            dir_stage=True)
    if program == "hbh":
        text = config_text(TILES, network="emesh_hop_by_hop")
        return Simulator(SimConfig(ConfigFile.from_string(text)),
                         radix_trace(TILES, keys_per_tile=64),
                         barrier_host=True)
    if program == "dvfs":
        text = config_text(TILES, shared_mem=True, protocol=MSI, dvfs=True,
                           dvfs_domains=TWO_DOMAINS, power=True)
        return Simulator(
            SimConfig(ConfigFile.from_string(text)),
            canneal_trace(TILES, footprint_lines=200, swaps_per_tile=2,
                          temperature_steps=2,
                          dvfs_schedule="rotate-levels"),
            barrier_host=True)
    if program == "atac":
        text = config_text(TILES, shared_mem=True, protocol=MSI,
                           network="atac", scheme="ackwise",
                           max_hw_sharers=4, atac_cluster_size=4)
        return Simulator(
            SimConfig(ConfigFile.from_string(text)),
            memory_stress_trace(TILES, n_accesses=8,
                                working_set_bytes=1 << 12,
                                write_fraction=0.4, shared_fraction=0.5,
                                seed=7),
            barrier_host=True)
    batch = fft_trace(n_tiles=TILES, points_per_tile=64, use_memory=True)
    if program == "shl2":
        text = config_text(TILES, shared_mem=True, protocol=SHL2)
        return Simulator(SimConfig(ConfigFile.from_string(text)), batch)
    text = config_text(TILES, core="iocoom", shared_mem=True, protocol=MSI)
    kw = {}
    if program == "msi":
        # staging and a telemetry ring, so that their scopes have code
        kw = dict(dir_stage=True,
                  telemetry=TelemetrySpec(sample_interval_ps=1_000_000))
    elif program == "msi-sharded":
        from graphite_tpu.parallel.mesh import make_tile_mesh

        kw = dict(mesh=make_tile_mesh(2), spmd="shard_map")
    return Simulator(SimConfig(ConfigFile.from_string(text)), batch, **kw)


def op_names(sim: Simulator) -> set:
    """The location names of the program `run()` dispatches, lowered and
    not compiled: its `op_name` paths (`jit(fn)/...`; relative to the
    body under shard_map) among file, function and argument names."""
    if not isinstance(sim, Simulator):          # a SweepRunner
        lowered = sim._get_runner(4096).lower(*sim.abstract_inputs())
    elif sim.mesh is not None:
        from graphite_tpu.parallel.mesh import make_shard_map_runner

        lowered = make_shard_map_runner(
            sim.params, sim.quantum_ps, 4096, sim.mesh, sim.state,
            sim.device_trace).lower(sim.state, sim.device_trace)
    else:
        fn, args = sim._auditable_fn(4096)
        lowered = jax.jit(fn).lower(*args)
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def found():
    """{program: the set of deepest scopes over its op_names}, each
    program lowered once."""
    cache = {}

    def get(program):
        if program not in cache:
            cache[program] = {scopes.deepest(n)
                              for n in op_names(build(program))
                              if n.startswith(("jit(", "gt."))
                              and "/" in n}
        return cache[program]
    return get


@pytest.mark.parametrize("name", MSI_SCOPES)
def test_msi_iocoom_program_names_scope(found, name):
    assert name in found("msi")


@pytest.mark.parametrize("name", SHL2_SCOPES)
def test_shared_l2_program_names_scope(found, name):
    assert name in found("shl2")


@pytest.mark.parametrize("name", HBH_SCOPES)
def test_hop_by_hop_program_names_scope(found, name):
    assert name in found("hbh")


def test_dvfs_power_program_names_its_scopes(found):
    """One test, not one a name: the program is lowered once (tier-1's
    clock, ISSUE 44)."""
    assert set(DVFS_SCOPES) <= found("dvfs")


def test_served_dvfs_power_program_keeps_its_scopes_under_vmap(found):
    """The V/f campaign's program (PR 51): under the sim axis a scope's
    name is wrapped (`vmap(gt.dvfs)`) and still resolves, the DVFS arm
    and the energy close among them, with the staged directory's three."""
    assert set(DVFS_SCOPES) | ONLY_STAGED <= found("dvfs-served")
    assert not ONLY_ATAC & found("dvfs-served")


def test_atac_program_names_its_scopes(found):
    """One test, as `dvfs`'s: both hub charges of a unicast and the
    fan-out's ATAC leg, inside `gt.net.route`."""
    assert set(ATAC_SCOPES) <= found("atac")


@pytest.mark.parametrize("program", ["msi", "shl2", "hbh", "dvfs"])
def test_programs_under_another_network_have_no_atac_scope(found, program):
    assert not ONLY_ATAC & found(program)


@pytest.mark.parametrize("program", ["msi", "shl2", "hbh"])
def test_programs_without_power_modelling_close_no_interval(found, program):
    assert not ONLY_POWER & found(program)


@pytest.mark.parametrize("program", ["msi", "shl2"])
def test_hop_counter_programs_have_no_hop_by_hop_half(found, program):
    assert not ONLY_HBH & found(program)


def test_sharded_program_names_the_exchange(found):
    assert "gt.px" in found("msi-sharded")


def test_private_l2_program_has_no_embedded_directory_landing(found):
    assert not ONLY_SHL2 & found("msi")


def test_every_operation_with_a_path_is_scoped(found):
    """`gt.quantum` encloses the loop nest: what is left unscoped is what
    XLA adds on its own, never an equation of the program."""
    assert None not in found("msi") and None not in found("shl2")


def test_registry():
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    assert all(s.startswith("gt.") and "/" not in s for s in scopes.SCOPES)
    assert set(PHASE_NAMES) == set(SHL2_PHASE_NAMES)
    for phase in PHASE_NAMES:
        assert "gt.mem." + phase in scopes.SCOPES
    with pytest.raises(ValueError, match="not a registered scope"):
        scopes.scope("gt.typo")


def test_cache_tag_follows_the_registry():
    """Scopes live in the executable and JAX's cache key ignores them, so
    the drive loop's jitted functions carry a tag derived from the
    registry: a stale executable cannot be served for a newer one."""
    import hashlib

    assert scopes.CACHE_TAG == "s" + hashlib.sha1(
        ",".join(scopes.SCOPES).encode()).hexdigest()[:6]
    sim = build("shl2")
    assert sim._get_runner(4096).__name__ == "run_" + scopes.CACHE_TAG
    assert sim._hb_get_runner().__name__ == "qrun_" + scopes.CACHE_TAG
    assert f"jit_run_{scopes.CACHE_TAG}" in sim._get_runner(4096).lower(
        sim.state).as_text()[:400]


@pytest.mark.parametrize("path,want", [
    ("jit(run)/gt.quantum/while/body/gt.core/gt.net.mailbox/cond/"
     "branch_1_fun/scatter-add", "gt.net.mailbox"),
    ("jit(run)/vmap(gt.quantum)/while/body/vmap(gt.core)/add", "gt.core"),
    ("jit(run)/gt.quantum/while/body/gt.core/gt.mem.base/"
     "gt.mem.requester_fill/cond/branch_1_fun/gt.net.route/mul",
     "gt.net.route"),
    ("jit(run)/gt.quantum/while/body/gt.core/gt.mem.requester_fill/add",
     "gt.mem.requester_fill"),
    ("jit(run)/gt.quantum/while/body/gt.core/gt.typo/add", "gt.core"),
    ("jit(qrun)/gt.quantum/while/body/gt.core/gt.net.mailbox/cond/"
     "branch_1_fun/gt.net.route/gt.net.hbh.scan/cummax", "gt.net.hbh.scan"),
    ("jit(qrun)/gt.quantum/while/body/gt.core/gt.net.mailbox/cond/"
     "branch_1_fun/gt.net.route/gt.net.hbh.commit/reduce_max",
     "gt.net.hbh.commit"),
    ("jit(qrun)/gt.quantum/while/body/gt.core/gt.dvfs/cond/branch_1_fun/"
     "gt.energy/mul", "gt.energy"),
    ("jit(qrun)/gt.quantum/while/body/gt.core/gt.dvfs/cond/branch_1_fun/"
     "select_n", "gt.dvfs"),
    ("jit(qrun)/gt.quantum/while/body/gt.core/gt.mem.base/"
     "gt.mem.home_start/cond/branch_1_fun/gt.net.route/gt.net.atac.fanout/"
     "cumsum", "gt.net.atac.fanout"),
    ("jit(qrun)/gt.quantum/while/body/gt.core/gt.mem.base/"
     "gt.mem.requester/cond/branch_1_fun/gt.net.route/gt.net.atac.hub/"
     "scatter-add", "gt.net.atac.hub"),
    ("jit(run)/while/body/add", None),
    ("", None),
])
def test_deepest(path, want):
    assert scopes.deepest(path) == want


@contextlib.contextmanager
def scopes_off(monkeypatch):
    """`scope` patched to a null context, decorated functions unwrapped."""
    with monkeypatch.context() as m:
        for mod in SCOPED_MODULES:
            m.setattr(mod, "scope", lambda name: contextlib.nullcontext())
            for attr, fn in list(vars(mod).items()):
                if callable(fn) and hasattr(fn, "__wrapped__") \
                        and getattr(fn, "__module__", "").startswith(
                            "graphite_tpu."):
                    m.setattr(mod, attr, fn.__wrapped__)
        yield


@pytest.mark.parametrize("program", ["msi", "shl2", "hbh", "dvfs", "atac"])
def test_scopes_change_no_equation(monkeypatch, program):
    scoped = build(program).lower()[0]
    with scopes_off(monkeypatch):
        sim = build(program)
        assert not any("gt." in n for n in op_names(sim)), \
            "the null patch left a scope in place"
        plain = sim.lower()[0]
    assert identity.same_program(scoped, plain)
