"""Energy as a statistic of the run: per-tile accumulators that close an
interval at the operating point that was in force.

The reference closes a tile's energy interval at the OLD operating point
on every `setDVFS` and once more at the end of the run
(`tile_energy_monitor.h:17-128`, the per-voltage wrappers of
`mcpat_core_interface.h`): events since the last close, priced at the
voltage they happened under, plus leakage over the elapsed time at that
voltage.  `TileEnergyMonitor` (power/interface.py) prices a whole run at
ONE voltage after the fact, which is right only for a run without a
transition; this module is the interval rule, in integers, for the
engine (`engine/step.py: _dvfs_block`, on the device), for the read of
the results (`Simulator._results_host`, numpy on the host) and - through
its tables only - for the golden interpreter, which keeps its own loop.

On with `[general] enable_power_modeling = true` (the reference's key);
off, `EngineParams.energy` and `SimState.energy` are None and no program
carries a leaf or an operation of it.

**Units.**  No float reaches a statistic (the TPU's float64 is not the
host's).  Prices come from the native library (`native/energy`, through
`power/interface.py`'s per-voltage interfaces) ONCE, at construction, for
every level of the configuration's V/f table, and are rounded there: a
dynamic event's price to whole femtojoules (a branch lookup is 0.7 pJ at
22 nm: whole picojoules would misprice it by a third), a leakage power to
whole microwatts.  Dynamic columns accumulate fJ, static columns
microwatt-picoseconds (1e-18 J): sums of integer products, no division
anywhere on the device.  `to_pj` rounds each column to whole picojoules
once, when results are read.  int64 holds 9e18: a 50 mW leak for 180
simulated seconds, or 1e9 DRAM accesses a tile.

**What is priced** is what `TileEnergyMonitor.tile_energy_j` prices, term
by term (core front end, ALU, load/store unit and branch predictor; the
three caches by hit / write / tag lookup; DRAM by line; one router and
one link traversal a USER packet; leakage of core, caches, router and
link), so the two agree on a run with no transition.  Left out, as there:
the directory's own array, the memory network's flits (the hop-counter
model counts none), clock distribution.  DRAM is off-die: its price has
no voltage.  A module's voltage is its DVFS domain's on that tile.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from graphite_tpu.models.dvfs import DVFS_MODULES

# raw event counts a tile's interval is measured in, in column order
RAW = ("instructions", "mem_ops", "branches", "packets_sent",
       "l1i_hits", "l1i_misses", "l1d_read_hits", "l1d_write_hits",
       "l1d_misses", "l2_hits", "l2_misses", "dram_accesses")
N_RAW_CORE = 4            # the columns a memoryless target has
# a derived count: max(instructions - mem_ops - branches, 0) of the
# interval, the integer-ALU work (`TileEnergyMonitor.tile_energy_j`)
INT_OPS = "int_ops"

# accumulator columns: (name, DVFS module whose voltage prices it or None,
# static?)  Dynamic columns hold fJ, static ones uW*ps.
_COLUMNS = (
    ("core_dynamic", "CORE", False),
    ("core_static", "CORE", True),
    ("l1i_dynamic", "L1_ICACHE", False),
    ("l1i_static", "L1_ICACHE", True),
    ("l1d_dynamic", "L1_DCACHE", False),
    ("l1d_static", "L1_DCACHE", True),
    ("l2_dynamic", "L2_CACHE", False),
    ("l2_static", "L2_CACHE", True),
    ("dram_dynamic", None, False),
    ("network_dynamic", "NETWORK_USER", False),
    ("network_static", "NETWORK_USER", True),
)
FJ_PER_PJ = 1000
UWPS_PER_PJ = 10**6


@dataclasses.dataclass(frozen=True)
class EnergyParams:
    """The static price list of one target: hashable, folded into the
    compiled step as literals."""

    node_nm: int
    voltages_mv: tuple       # the V/f table's levels, descending
    columns: tuple           # accumulator column names, in order
    static: tuple            # per column: a leakage column?
    domains: tuple           # per column: DVFS domain index, -1 for none
    # dynamic columns: ((raw name or INT_OPS, (fJ at level 0, ...)), ...);
    # static columns: the leakage power (uW at level 0, ...)
    prices: tuple
    has_mem: bool

    @property
    def raw(self) -> tuple:
        return RAW if self.has_mem else RAW[:N_RAW_CORE]

    @classmethod
    def from_config(cls, sim_config, dvfs_params, mem_params
                    ) -> "EnergyParams":
        from graphite_tpu.power.interface import (
            DSENTInterface, McPATCacheInterface, McPATCoreInterface,
            load_native,
        )

        node = sim_config.technology_node
        volts = tuple(int(v) for v in dvfs_params.voltages_mv)

        def fj(joules: float) -> int:
            return int(round(joules * 1e15))

        def uw(watts: float) -> int:
            return int(round(watts * 1e6))

        def levels(fn) -> tuple:
            return tuple(fn(mv / 1000.0) for mv in volts)

        core = McPATCoreInterface(node)
        noc = DSENTInterface(node)
        priced = {
            "core_dynamic": (
                ("instructions", levels(lambda v: fj(
                    core.dynamic_energy_j(v, instructions=1)))),
                (INT_OPS, levels(lambda v: fj(
                    core.dynamic_energy_j(v, instructions=0, int_ops=1)))),
                ("mem_ops", levels(lambda v: fj(
                    core.dynamic_energy_j(v, instructions=0, mem_ops=1)))),
                ("branches", levels(lambda v: fj(
                    core.dynamic_energy_j(v, instructions=0, branches=1)))),
            ),
            "core_static": levels(
                lambda v: uw(core.leakage_energy_j(v, 1.0))),
            "network_dynamic": (
                ("packets_sent", levels(lambda v: fj(
                    noc.router_dynamic_energy_j(v, 1)
                    + noc.link_dynamic_energy_j(v, 1)))),
            ),
            "network_static": levels(lambda v: uw(noc.static_power_w(v))),
        }
        if mem_params is not None:
            line = mem_params.line_size

            def cache(lvl):
                return McPATCacheInterface(
                    node, lvl.num_sets * lvl.num_ways * line,
                    lvl.num_ways, line, num_banks=lvl.num_banks)

            for name, lvl, terms in (
                    ("l1i", mem_params.l1i,
                     (("l1i_hits", (1, 0, 0)), ("l1i_misses", (0, 0, 1)))),
                    ("l1d", mem_params.l1d,
                     (("l1d_read_hits", (1, 0, 0)),
                      ("l1d_write_hits", (0, 1, 0)),
                      ("l1d_misses", (0, 0, 1)))),
                    ("l2", mem_params.l2,
                     (("l2_hits", (1, 0, 0)), ("l2_misses", (0, 0, 1))))):
                cif = cache(lvl)
                priced[name + "_dynamic"] = tuple(
                    (raw, levels(lambda v, rwt=rwt: fj(
                        cif.dynamic_energy_j(v, *rwt))))
                    for raw, rwt in terms)
                priced[name + "_static"] = levels(
                    lambda v: uw(cif.leakage_energy_j(v, 1.0)))
            dram = fj(load_native().dram_access_energy_j(node, line))
            priced["dram_dynamic"] = (
                ("dram_accesses", (dram,) * len(volts)),)
        cols = [c for c in _COLUMNS if c[0] in priced]
        mod_dom = dvfs_params.module_domains
        return cls(
            node_nm=node,
            voltages_mv=volts,
            columns=tuple(c[0] for c in cols),
            static=tuple(c[2] for c in cols),
            domains=tuple(
                -1 if c[1] is None else mod_dom[DVFS_MODULES.index(c[1])]
                for c in cols),
            prices=tuple(priced[c[0]] for c in cols),
            has_mem=mem_params is not None,
        )


def raw_counts(xp, ep: EnergyParams, core, packets_sent, mem_counters):
    """int64[T, len(ep.raw)]: the event counts to date, from the counters
    the carry (or a fetched copy of it) already holds."""
    cols = [core.instruction_count, None,
            core.bp_correct + core.bp_incorrect, packets_sent]
    if ep.has_mem:
        mc = mem_counters
        misses = mc.l1d_read_misses + mc.l1d_write_misses
        cols[1] = mc.l1d_read_hits + mc.l1d_write_hits + misses
        cols += [mc.l1i_hits, mc.l1i_misses, mc.l1d_read_hits,
                 mc.l1d_write_hits, misses, mc.l2_hits, mc.l2_misses,
                 mc.dram_reads + mc.dram_writes]
    else:
        cols[1] = xp.zeros_like(cols[0])
    return xp.stack([xp.asarray(c).astype(xp.int64) for c in cols], axis=1)


def close_interval(xp, ep: EnergyParams, raw_now, clock_ps, voltage_mv,
                   last_raw, last_clock_ps):
    """int64[T, len(ep.columns)]: what every tile's open interval adds to
    its accumulators if it is closed now - the events since `last_raw` at
    the price of the voltage in force (`voltage_mv` int32[T, ND], the
    per-tile table), leakage over `clock_ps - last_clock_ps` at that
    voltage.  `xp` is numpy or jax.numpy: the same integer arithmetic on
    the host and inside the compiled step."""
    i64 = xp.int64
    volts = xp.asarray(np.asarray(ep.voltages_mv, np.int32))
    d = {name: raw_now[:, k] - last_raw[:, k]
         for k, name in enumerate(ep.raw)}
    d[INT_OPS] = xp.maximum(
        d["instructions"] - d["mem_ops"] - d["branches"], 0)
    dt = (clock_ps - last_clock_ps).astype(i64)
    level_of = {}

    def at_level(table, dom):
        if dom < 0:                      # off-die: one price
            return xp.asarray(table[0], i64)
        if dom not in level_of:
            level_of[dom] = xp.argmax(
                volts[None, :] == voltage_mv[:, dom][:, None], axis=1)
        return xp.asarray(np.asarray(table, np.int64))[level_of[dom]]

    out = []
    for static, dom, price in zip(ep.static, ep.domains, ep.prices):
        if static:
            out.append(dt * at_level(price, dom))
        else:
            e = xp.zeros_like(dt)
            for name, table in price:
                e = e + d[name] * at_level(table, dom)
            out.append(e)
    return xp.stack(out, axis=1)


def to_pj(ep: EnergyParams, acc) -> dict:
    """{column: int64[T] pJ, ..., "total"}: each accumulator column
    rounded half up to whole picojoules; `total` sums the rounded
    columns, so the parts add up to it exactly."""
    acc = np.asarray(acc).astype(np.int64)
    out = {}
    for k, (name, static) in enumerate(zip(ep.columns, ep.static)):
        unit = UWPS_PER_PJ if static else FJ_PER_PJ
        out[name] = (acc[:, k] + unit // 2) // unit
    out["total"] = sum(out.values())
    return out


def output_summary(energy_pj: dict) -> str:
    """The per-tile block `TileEnergyMonitor.output_summary` shapes, from
    the integrated picojoules."""
    def joules(pj) -> str:
        return f"{int(pj) * 1e-12:.6e}"

    def group(t, prefixes):
        return sum(int(v[t]) for k, v in energy_pj.items()
                   if k.startswith(prefixes))

    lines = ["Tile Energy Monitor Summary"]
    total = energy_pj["total"]
    for t in range(len(total)):
        lines.append(f"  Tile {t}:")
        lines.append(f"    Total Energy (in J): {joules(total[t])}")
        lines.append(
            f"    Core Energy (in J): {joules(group(t, ('core_',)))}")
        if "l1d_dynamic" in energy_pj:
            lines.append("    Cache Energy (in J): "
                         f"{joules(group(t, ('l1', 'l2')))}")
            lines.append(f"    DRAM Energy (in J): "
                         f"{joules(group(t, ('dram_',)))}")
        lines.append("    Network Energy (in J): "
                     f"{joules(group(t, ('network_',)))}")
    lines.append(f"  Total Energy (in J): {joules(np.sum(total))}")
    return "\n".join(lines)
