"""DVFS domains: frequency/voltage per module class.

Reference: `common/system/dvfs_manager.{h,cc}` (`dvfs_manager.h:19-88`),
config `[dvfs] domains` (`carbon_sim.cfg:147-155`), per-technology V/f
tables `technology/dvfs_levels_*.cfg`.

Round-1 scope: domain parsing + initial frequencies (consumed by the core
and network models) and the synchronization delay at asynchronous boundary
crossings.  Runtime set_frequency (the DVFS network + voltage scaling +
level tables) is layered on in the DVFSManager engine module.
"""

from __future__ import annotations

import re

from graphite_tpu.config.config_file import ConfigFile
from graphite_tpu.time_types import ghz_to_mhz

# Module classes (`dvfs.h` / `dvfs_manager.cc` domain map)
DVFS_MODULES = (
    "CORE",
    "L1_ICACHE",
    "L1_DCACHE",
    "L2_CACHE",
    "DIRECTORY",
    "NETWORK_USER",
    "NETWORK_MEMORY",
)


def parse_dvfs_domains(cfg: ConfigFile) -> list[tuple[int, list[str]]]:
    """Parse `[dvfs] domains` tuples `<freq_ghz, MODULE, ...>`.

    Returns [(freq_mhz, [modules]), ...] (`carbon_sim.cfg:148-151`).
    """
    text = cfg.get_string(
        "dvfs/domains",
        "<1.0, CORE, L1_ICACHE, L1_DCACHE, L2_CACHE, DIRECTORY, "
        "NETWORK_USER, NETWORK_MEMORY>",
    )
    domains: list[tuple[int, list[str]]] = []
    for tup in re.finditer(r"<([^<>]*)>", text):
        fields = [f.strip() for f in tup.group(1).split(",") if f.strip()]
        if not fields:
            continue
        freq_mhz = ghz_to_mhz(float(fields[0]))
        modules = [m.upper() for m in fields[1:]]
        for m in modules:
            if m not in DVFS_MODULES:
                raise ValueError(f"unknown DVFS module {m!r} in domains")
        domains.append((freq_mhz, modules))
    if not domains:
        raise ValueError("no DVFS domains parsed")
    # every module must belong to exactly one domain
    seen: set[str] = set()
    for _, modules in domains:
        for m in modules:
            if m in seen:
                raise ValueError(f"DVFS module {m} in two domains")
            seen.add(m)
    return domains


def module_freq_mhz(cfg: ConfigFile, module: str) -> int:
    """Initial frequency of the domain containing `module`, default 1 GHz."""
    for freq_mhz, modules in parse_dvfs_domains(cfg):
        if module.upper() in modules:
            return freq_mhz
    return 1000


def module_domain_index(cfg: ConfigFile, module: str) -> int:
    """Index of the domain containing `module` (-1 if unlisted).

    Used for `hasSameDVFSDomain` checks (`dvfs_manager.cc` domain map):
    synchronization delay applies only across different domains.
    """
    for i, (_, modules) in enumerate(parse_dvfs_domains(cfg)):
        if module.upper() in modules:
            return i
    return -1


def synchronization_delay_cycles(cfg: ConfigFile) -> int:
    """Delay crossing asynchronous domain boundaries (`carbon_sim.cfg:153-155`)."""
    return cfg.get_int("dvfs/synchronization_delay", 2)


# --------------------------------------------------------------------------
# voltage/frequency levels (`technology/dvfs_levels_*.cfg`,
# `DVFSManager::initializeDVFSLevels`)

# Built-in per-node tables: rows of (voltage V, max-frequency-factor); the
# max frequency at a voltage = factor * [general] max_frequency.  Matches
# the `technology/` table format; a `dvfs_levels_path` config key loads a
# file in that format instead.
_BUILTIN_LEVELS = {
    22: ((1.0, 1.0), (0.96, 0.87), (0.92, 0.75), (0.88, 0.63),
         (0.84, 0.5), (0.8, 0.37)),
    32: ((1.0, 1.0), (0.96, 0.88), (0.92, 0.77), (0.88, 0.65),
         (0.84, 0.54), (0.8, 0.42)),
    45: ((1.0, 1.0), (0.96, 0.89), (0.92, 0.78), (0.88, 0.68),
         (0.84, 0.57), (0.8, 0.46)),
}

# DVFS API return codes (`common/user/dvfs.h:10-17`)
RC_OK = 0
RC_INVALID_TILE = -1
RC_INVALID_DOMAIN = -2
RC_INVALID_VOLTAGE_OPTION = -3
RC_INVALID_FREQUENCY = -4
RC_ABOVE_MAX_FOR_VOLTAGE = -5

AUTO = 0
HOLD = 1


def load_levels(cfg: ConfigFile) -> tuple[tuple[float, float], ...]:
    """(voltage, max-frequency-factor) rows, descending voltage."""
    path = cfg.get_string("general/dvfs_levels_path", "")
    if path:
        rows = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                v, factor = line.split()[:2]
                rows.append((float(v), float(factor)))
        if not rows:
            raise ValueError(f"no DVFS levels in {path!r}")
        return tuple(sorted(rows, key=lambda r: -r[0]))
    node = cfg.get_int("general/technology_node", 22)
    if node not in _BUILTIN_LEVELS:
        raise ValueError(f"no DVFS levels for technology node {node}nm")
    rows = _BUILTIN_LEVELS[node]
    # every consumer assumes descending (voltage, frequency) order
    return tuple(sorted(rows, key=lambda r: -r[0]))


import dataclasses as _dc


@_dc.dataclass(frozen=True)
class DvfsParams:
    """Static DVFS tables for the engine + host API."""

    voltages_mv: tuple          # descending
    max_freq_mhz: tuple         # max frequency at each voltage, descending
    n_domains: int
    core_domain: int            # index of the domain containing CORE
    sync_delay_cycles: int
    domain_freq_mhz: tuple      # initial frequency per domain
    # domain index per DVFS_MODULES entry (unlisted modules fold into
    # domain 0) — lets the runtime DVFS manager map a counter/price term
    # to its operating point without re-parsing the config
    module_domains: tuple = ()

    @classmethod
    def from_config(cls, cfg: ConfigFile) -> "DvfsParams":
        levels = load_levels(cfg)
        max_f = ghz_to_mhz(cfg.get_float("general/max_frequency", 1.0))
        domains = parse_dvfs_domains(cfg)
        core_dom = 0
        for i, (f, modules) in enumerate(domains):
            if "CORE" in modules:
                core_dom = i
            if f > max_f:
                raise ValueError(
                    f"DVFS domain {i} initial frequency {f} MHz exceeds "
                    f"[general] max_frequency ({max_f} MHz)")
        return cls(
            voltages_mv=tuple(int(round(v * 1000)) for v, _ in levels),
            max_freq_mhz=tuple(int(round(f * max_f)) for _, f in levels),
            n_domains=len(domains),
            core_domain=core_dom,
            sync_delay_cycles=synchronization_delay_cycles(cfg),
            domain_freq_mhz=tuple(f for f, _ in domains),
            module_domains=tuple(
                max(module_domain_index(cfg, m), 0) for m in DVFS_MODULES),
        )

    @property
    def levels_text(self) -> str:
        """The V/f table on one line, `<mV>@<MHz>` per level, descending:
        what a configuration file can state and compare as one string."""
        return " ".join(f"{v}@{f}" for v, f in zip(self.voltages_mv,
                                                    self.max_freq_mhz))

    def min_voltage_mv(self, freq_mhz: int) -> int:
        """Lowest voltage supporting `freq_mhz` (`getMinVoltage`), or -1."""
        best = -1
        for v, f in zip(self.voltages_mv, self.max_freq_mhz):
            if freq_mhz <= f:
                best = v
        return best

    def max_freq_at_mv(self, voltage_mv: int) -> int:
        for v, f in zip(self.voltages_mv, self.max_freq_mhz):
            if v == voltage_mv:
                return f
        return 0


class DVFSManager:
    """Host-side DVFS API facade (`dvfs.h` semantics with rc codes).

    Operates on a Simulator's state between/after runs; the in-trace
    DVFS_SET events apply the same table logic on device.
    """

    def __init__(self, sim):
        self._sim = sim
        # the same tables the in-trace DVFS_SET path validates against
        self.params = (sim.params.dvfs if sim.params.dvfs is not None
                       else DvfsParams.from_config(sim.config.cfg))

    def get_domain(self, module: str) -> int:
        idx = module_domain_index(self._sim.config.cfg, module)
        return idx

    def get_dvfs(self, tile_id: int, domain: int):
        """(rc, frequency_ghz, voltage_v)."""
        import numpy as np

        n = self._sim.params.n_tiles
        if tile_id < 0 or tile_id >= n:
            return RC_INVALID_TILE, 0.0, 0.0
        if domain < 0 or domain >= self.params.n_domains:
            return RC_INVALID_DOMAIN, 0.0, 0.0
        dv = self._sim.state.dvfs
        f = int(np.asarray(dv.freq_mhz)[tile_id, domain])
        v = int(np.asarray(dv.voltage_mv)[tile_id, domain])
        return RC_OK, f / 1000.0, v / 1000.0

    def set_dvfs(self, tile_id: int, domain: int, frequency_ghz: float,
                 voltage_flag: int = AUTO) -> int:
        """Immediate (inter-quantum) DVFS set with reference rc codes."""
        import jax.numpy as jnp
        import numpy as np

        n = self._sim.params.n_tiles
        if tile_id < 0 or tile_id >= n:
            return RC_INVALID_TILE
        if domain < 0 or domain >= self.params.n_domains:
            return RC_INVALID_DOMAIN
        if voltage_flag not in (AUTO, HOLD):
            return RC_INVALID_VOLTAGE_OPTION
        freq_mhz = int(round(frequency_ghz * 1000))
        if freq_mhz <= 0 or freq_mhz > self.params.max_freq_mhz[0]:
            return RC_INVALID_FREQUENCY
        dv = self._sim.state.dvfs
        if voltage_flag == HOLD:
            cur_v = int(np.asarray(dv.voltage_mv)[tile_id, domain])
            if freq_mhz > self.params.max_freq_at_mv(cur_v):
                return RC_ABOVE_MAX_FOR_VOLTAGE
            new_v = cur_v
        else:
            new_v = self.params.min_voltage_mv(freq_mhz)
        new_dv = dv.replace(
            freq_mhz=dv.freq_mhz.at[tile_id, domain].set(freq_mhz),
            voltage_mv=dv.voltage_mv.at[tile_id, domain].set(new_v),
        )
        state = self._sim.state.replace(dvfs=new_dv)
        if domain == self.params.core_domain:
            state = state.replace(core=state.core.replace(
                freq_mhz=state.core.freq_mhz.at[tile_id].set(
                    jnp.asarray(freq_mhz, state.core.freq_mhz.dtype))))
        self._sim.state = state
        return RC_OK
