"""Shared config-INI template for the tools' drivers (graduated, the tests' targets).

One source of truth for the sweep/benchmark configuration surface so knob
changes land in every driver at once.
"""

from __future__ import annotations


def config_text(tiles: int, *, core: str = "simple",
                network: str = "emesh_hop_counter",
                shared_mem: bool = False,
                protocol: str = "pr_l1_pr_l2_dram_directory_msi",
                scheme: str = "full_map", max_hw_sharers: int = 2,
                clock_scheme: str = "lax_barrier",
                dvfs: bool = False, dvfs_domains: str | None = None,
                power: bool = False,
                atac_cluster_size: int | None = None) -> str:
    """`atac_cluster_size` writes a `[network/atac]` section with that
    `cluster_size` (`carbon_sim.cfg:315-352` ships 4; the ATAC paper's
    chip is 64 clusters of 16); every other key of the section stays at
    the engine's default, which mirrors `carbon_sim.cfg`.  `dvfs` writes
    a `[dvfs]` section: `dvfs_domains` (the reference's
    `<f_ghz, MODULE, ...>` list form, `carbon_sim.cfg:147-155`) or, left
    out, the one domain `carbon_sim.cfg` ships.  `power` writes the
    reference's own `[general] enable_power_modeling = true`: the run
    then integrates per-tile energy (`SimResults.energy_pj`).  Either
    writes `technology_node` under `[general]`, where both of its readers
    look (`models/dvfs.load_levels`, `SimConfig.technology_node`).  With
    all four at their defaults the text is what it always was."""
    if dvfs_domains is not None and not dvfs:
        raise ValueError("dvfs_domains needs dvfs=True")
    extra = ""
    if atac_cluster_size is not None:
        extra += f"[network/atac]\ncluster_size = {atac_cluster_size}\n"
    if dvfs or power:
        extra += "\n[general]\ntechnology_node = 22\n"
        if power:
            extra += "enable_power_modeling = true\n"
    if dvfs:
        domains = dvfs_domains or (
            "<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE, DIRECTORY, "
            "NETWORK_USER, NETWORK_MEMORY>")
        extra += f"""[dvfs]
synchronization_delay = 2
domains = "{domains}"
"""
    return f"""
[general]
total_cores = {tiles}
mode = lite
max_frequency = 1.0
enable_shared_mem = {"true" if shared_mem else "false"}
[tile]
model_list = <{tiles}, {core}>
[caching_protocol]
type = {protocol}
[dram_directory]
directory_type = {scheme}
max_hw_sharers = {max_hw_sharers}
[network]
user = {network}
memory = {network}
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
num_flits_per_port_buffer = 4
[network/emesh_hop_by_hop/link]
delay = 1
[core/static_instruction_costs]
generic = 1
mov = 1
ialu = 1
falu = 3
fmul = 5
[branch_predictor]
type = one_bit
mispredict_penalty = 14
size = 1024
[clock_skew_management]
scheme = {clock_scheme}
[clock_skew_management/lax_barrier]
quantum = 1000
{extra}
"""


def coherence_stress_workload(n_tiles: int, *, n_accesses: int = 40,
                              protocol: str =
                              "pr_l1_pr_l2_dram_directory_msi"):
    """The shared cross-shard coherence attestation workload: one config +
    trace used by BOTH the sharding test matrix (tests/test_sharding.py)
    and the driver's multichip dryrun (__graft_entry__.py), so the two
    cannot drift apart.  shared_fraction drives cross-tile (and, sharded,
    cross-device) protocol traffic: line homes stripe over ALL tiles
    (`dram/num_controllers` ALL), so requests/replies/invalidations cross
    every shard cut.  Returns (SimConfig, TraceBatch)."""
    from graphite_tpu.config import ConfigFile, SimConfig
    from graphite_tpu.trace import synthetic

    sc = SimConfig(ConfigFile.from_string(config_text(
        n_tiles, shared_mem=True, protocol=protocol, clock_scheme="lax")))
    batch = synthetic.memory_stress_trace(
        n_tiles, n_accesses=n_accesses, working_set_bytes=1 << 13,
        write_fraction=0.4, shared_fraction=0.5, seed=7)
    return sc, batch
