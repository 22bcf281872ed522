"""The targets tier-1's tests share: configs and traces by name.

A target is a config text and a trace.  Several files wrote the same
memory-engine config and the same two workloads out each for itself;
they are here once, so that two tests that mean the same target say so.
Every call builds a new object: a test may `cfg.set(...)` on what it gets.

There is no cache of built `Simulator`s or of their results here, and
Step 0 of PR 47 says why (PERF.md "Tier-1's cost").  A fresh `Simulator`
is a fresh `jax.jit` closure over its trace, and the trace's CONTENT is
baked into the executable, so the same geometry under another seed is
another program: tier-1 asks for 536 programs of a second or more and
only 93 constructions repeat one, which the persistent cache already
serves.  A process-wide cache of runs spares five files 28 s of tracing
and lowering in their 776 CPU-seconds cold, less than two runs of one
tree differ by: not worth its keys and its eviction.

A new program costs its compile on every cold run (ROADMAP D14): name a
target from here before writing another config or trace.
"""

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.tools._template import config_text
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.schema import TraceBatch, TraceBuilder

MSI = "pr_l1_pr_l2_dram_directory_msi"
MOSI = "pr_l1_pr_l2_dram_directory_mosi"
SHL2_MSI = "pr_l1_sh_l2_msi"
SHL2_MESI = "pr_l1_sh_l2_mesi"


# ---- configs --------------------------------------------------------------


def template_config(tiles, extra="", **kw) -> SimConfig:
    """`tools/_template.config_text(tiles, **kw)` plus `extra` sections."""
    return SimConfig(ConfigFile.from_string(config_text(tiles, **kw) + extra))


def memory_config(tiles, proto=MSI, net="magic", extra="") -> SimConfig:
    """The memory-engine tests' target: shared memory on a magic USER
    network with the MEMORY network chosen apart from it and no clock
    scheme named — three things the template cannot say."""
    return SimConfig(ConfigFile.from_string(f"""
[general]
total_cores = {tiles}
mode = lite
max_frequency = 1.0
enable_shared_mem = true
[network]
user = magic
memory = {net}
[network/emesh_hop_counter]
flit_width = 64
[network/emesh_hop_counter/router]
delay = 1
[network/emesh_hop_counter/link]
delay = 1
[network/emesh_hop_by_hop]
flit_width = 64
[network/emesh_hop_by_hop/router]
delay = 1
[network/emesh_hop_by_hop/link]
delay = 1
[caching_protocol]
type = {proto}
[core/static_instruction_costs]
mov = 1
ialu = 1
{extra}
"""))


# ---- the memory network alone ----------------------------------------------


def mem_net_at(mp, freq_mhz):
    """`mp` (a `MemParams`) with its MEMORY network clocked at `freq_mhz`,
    in whichever model carries the clock (static: a Python int)."""
    import dataclasses

    return dataclasses.replace(
        mp, net_freq_mhz=freq_mhz,
        net_hbh=mp.net_hbh and dataclasses.replace(mp.net_hbh,
                                                   freq_mhz=freq_mhz),
        net_atac=mp.net_atac and dataclasses.replace(mp.net_atac,
                                                     freq_mhz=freq_mhz))


def fresh_mem_noc(mp):
    """The MEMORY network's contention state, idle: hub queues under
    `memory = atac`, port queues under hop_by_hop, None otherwise."""
    from graphite_tpu.models.network_atac import init_atac_state
    from graphite_tpu.models.network_hop_by_hop import init_noc_state

    if mp.net_atac is not None:
        return init_atac_state(mp.net_atac)
    return None if mp.net_hbh is None else init_noc_state(mp.net_hbh)


# ---- traces ---------------------------------------------------------------


def stress_trace(tiles, seed=7, n_accesses=24, working_set_bytes=1 << 12,
                 write_fraction=0.4, shared_fraction=0.5) -> TraceBatch:
    """`synthetic.memory_stress_trace`: free-running racy traffic."""
    return synthetic.memory_stress_trace(
        tiles, n_accesses=n_accesses, working_set_bytes=working_set_bytes,
        write_fraction=write_fraction, shared_fraction=shared_fraction,
        seed=seed)


def mutex_rmw(n, rounds, base=0x900000, lines=2) -> TraceBatch:
    """Mutex-serialized read-modify-write of shared lines: at any moment
    exactly one tile touches the shared data, so engine iteration order
    and oracle clock order coincide (the bit-exact contract)."""
    bs = [TraceBuilder() for _ in range(n)]
    bs[0].mutex_init(0)
    bs[0].barrier_init(9, n)
    for b in bs:
        b.barrier_wait(9)
    for r in range(n * rounds):
        t = r % n
        addr = base + (r % lines) * 64
        bs[t].mutex_lock(0)
        bs[t].load(addr, 8)
        bs[t].store(addr, 8)
        bs[t].mutex_unlock(0)
    return TraceBatch.from_builders(bs)
