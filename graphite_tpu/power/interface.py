"""ctypes bindings + interface classes over the native energy library.

`McPATCoreInterface`/`McPATCacheInterface`/`DSENTInterface` mirror the
reference's wrappers (`common/mcpat/`, `simulator.cc:93-104`): constructed
per structure, queried per voltage (DVFS changes create new operating
points, like the reference's per-voltage wrapper cache), and fed event
counters to produce (area, leakage energy, dynamic energy) breakdowns.
`TileEnergyMonitor` aggregates them per tile over a run
(`common/tile/tile_energy_monitor.h:17-128`).
"""

from __future__ import annotations

import ctypes
import os
import subprocess

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "lib", "libgraphite_energy.so")


class _SramOut(ctypes.Structure):
    _fields_ = [("area_mm2", ctypes.c_double),
                ("leakage_power_w", ctypes.c_double),
                ("read_energy_j", ctypes.c_double),
                ("write_energy_j", ctypes.c_double),
                ("tag_energy_j", ctypes.c_double)]


class _CoreOut(ctypes.Structure):
    _fields_ = [("area_mm2", ctypes.c_double),
                ("leakage_power_w", ctypes.c_double),
                ("ifu_energy_j", ctypes.c_double),
                ("decode_energy_j", ctypes.c_double),
                ("rf_energy_j", ctypes.c_double),
                ("ialu_energy_j", ctypes.c_double),
                ("fpu_energy_j", ctypes.c_double),
                ("mul_energy_j", ctypes.c_double),
                ("lsu_energy_j", ctypes.c_double),
                ("bypass_energy_j", ctypes.c_double),
                ("bpred_energy_j", ctypes.c_double)]


class _NocOut(ctypes.Structure):
    _fields_ = [("router_area_mm2", ctypes.c_double),
                ("router_leakage_w", ctypes.c_double),
                ("buffer_energy_j", ctypes.c_double),
                ("crossbar_energy_j", ctypes.c_double),
                ("arbiter_energy_j", ctypes.c_double),
                ("link_energy_j_per_mm", ctypes.c_double),
                ("link_leakage_w_per_mm", ctypes.c_double)]


_lib = None


def load_native() -> ctypes.CDLL:
    """Load (building if needed) the native energy library."""
    global _lib
    if _lib is not None:
        return _lib
    # always invoke make: the rule depends on the .cc, so an up-to-date
    # build is a no-op and source edits are never silently ignored
    proc = subprocess.run(["make", "-C", _NATIVE_DIR],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native energy library build failed:\n{proc.stderr}")
    lib = ctypes.CDLL(_LIB_PATH)
    lib.sram_energy.argtypes = [
        ctypes.c_int, ctypes.c_double, ctypes.c_long, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(_SramOut)]
    lib.core_energy.argtypes = [
        ctypes.c_int, ctypes.c_double, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(_CoreOut)]
    lib.noc_energy.argtypes = [
        ctypes.c_int, ctypes.c_double, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(_NocOut)]
    lib.dram_access_energy_j.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.dram_access_energy_j.restype = ctypes.c_double
    lib.energy_model_abi_version.restype = ctypes.c_int
    assert lib.energy_model_abi_version() == 1
    _lib = lib
    return lib


class McPATCacheInterface:
    """Per-cache-structure energy (`mcpat_cache_interface.h:22-72`)."""

    def __init__(self, node_nm: int, size_bytes: int, associativity: int,
                 line_bytes: int = 64, ports: int = 1, num_banks: int = 1):
        # num_banks mirrors the reference's only use of the knob — the
        # McPAT cache config (`mcpat_cache_interface.cc:226`): banked
        # arrays split the bitline/wordline energy per access.  Clamp the
        # bank count so each bank holds >= 1 KB (a physical SRAM macro
        # floor) instead of flooring the per-bank size: a small cache
        # configured with many banks would otherwise charge the 1 KB-array
        # energy num_banks times over and overestimate several-fold.
        num_banks = max(1, min(num_banks, size_bytes // 1024))
        self._args = (node_nm, max(size_bytes // num_banks, 1024),
                      associativity, line_bytes, ports)
        self._num_banks = num_banks
        self._cache: dict = {}   # per-voltage operating points

    def at_voltage(self, voltage: float) -> _SramOut:
        if voltage not in self._cache:
            node, size, assoc, line, ports = self._args
            out = _SramOut()
            load_native().sram_energy(node, voltage, size, assoc, line,
                                      ports, ctypes.byref(out))
            self._cache[voltage] = out
        return self._cache[voltage]

    def area_mm2(self, voltage: float = 1.0) -> float:
        return self.at_voltage(voltage).area_mm2 * self._num_banks

    def dynamic_energy_j(self, voltage: float, reads: int, writes: int,
                         tag_lookups: int = 0) -> float:
        o = self.at_voltage(voltage)
        return (reads * o.read_energy_j + writes * o.write_energy_j
                + tag_lookups * o.tag_energy_j)

    def leakage_energy_j(self, voltage: float, seconds: float) -> float:
        # all banks leak; dynamic energy is per-access in ONE bank
        return (self.at_voltage(voltage).leakage_power_w
                * self._num_banks * seconds)


class McPATCoreInterface:
    """Per-core energy with the IFU/LSU/EXU breakdown
    (`mcpat_core_interface.h:19-99`)."""

    def __init__(self, node_nm: int, issue_width: int = 1,
                 load_queue_entries: int = 8, store_queue_entries: int = 8):
        self._args = (node_nm, issue_width, load_queue_entries,
                      store_queue_entries)
        self._cache: dict = {}

    def at_voltage(self, voltage: float) -> _CoreOut:
        if voltage not in self._cache:
            node, w, lq, sq = self._args
            out = _CoreOut()
            load_native().core_energy(node, voltage, w, lq, sq,
                                      ctypes.byref(out))
            self._cache[voltage] = out
        return self._cache[voltage]

    def area_mm2(self, voltage: float = 1.0) -> float:
        return self.at_voltage(voltage).area_mm2

    def dynamic_energy_j(self, voltage: float, *, instructions: int,
                         int_ops: int = 0, fp_ops: int = 0,
                         mul_ops: int = 0, mem_ops: int = 0,
                         branches: int = 0, reg_reads: int = 0) -> float:
        """Event counters → energy (`updateEventCounters` + compute)."""
        o = self.at_voltage(voltage)
        return (
            instructions * (o.ifu_energy_j + o.decode_energy_j
                            + o.bypass_energy_j)
            + reg_reads * o.rf_energy_j
            + int_ops * o.ialu_energy_j
            + fp_ops * o.fpu_energy_j
            + mul_ops * o.mul_energy_j
            + mem_ops * o.lsu_energy_j
            + branches * o.bpred_energy_j
        )

    def leakage_energy_j(self, voltage: float, seconds: float) -> float:
        return self.at_voltage(voltage).leakage_power_w * seconds


class DSENTInterface:
    """NoC router+link energy (the contrib/dsent analog,
    `simulator.cc:93-99`)."""

    def __init__(self, node_nm: int, num_ports: int = 5,
                 flit_bits: int = 64, buffers_per_port: int = 4,
                 link_length_mm: float = 1.0):
        self._args = (node_nm, num_ports, flit_bits, buffers_per_port)
        self.link_length_mm = link_length_mm
        self._cache: dict = {}

    def at_voltage(self, voltage: float) -> _NocOut:
        if voltage not in self._cache:
            node, p, f, b = self._args
            out = _NocOut()
            load_native().noc_energy(node, voltage, p, f, b,
                                     ctypes.byref(out))
            self._cache[voltage] = out
        return self._cache[voltage]

    def router_dynamic_energy_j(self, voltage: float, flits: int) -> float:
        o = self.at_voltage(voltage)
        return flits * (o.buffer_energy_j + o.crossbar_energy_j
                        + o.arbiter_energy_j)

    def link_dynamic_energy_j(self, voltage: float, flit_hops: int) -> float:
        o = self.at_voltage(voltage)
        return flit_hops * o.link_energy_j_per_mm * self.link_length_mm

    def static_power_w(self, voltage: float) -> float:
        o = self.at_voltage(voltage)
        return (o.router_leakage_w
                + o.link_leakage_w_per_mm * self.link_length_mm)


class TileEnergyMonitor:
    """Aggregate per-tile energy over a run
    (`tile_energy_monitor.h:17-128`): core + caches + network dynamic
    energy from the run's counters, plus leakage over completion time.

    A host pass over the FINAL counters, in floats, that prices the whole
    run at the ONE voltage it is given: right for a run without a DVFS
    transition, an over-estimate where tiles spent time at lower levels
    (the reference closes an interval at the old operating point on every
    `setDVFS`).  For runs with transitions read `SimResults.energy_pj`
    (`[general] enable_power_modeling = true`; power/accounting.py): the
    same terms, integrated interval by interval in integers; the two
    agree to 0.1 % where no transition happens
    (tests/test_canneal_dvfs.py)."""

    def __init__(self, sim, results, node_nm: int | None = None):
        self.node_nm = node_nm or sim.config.technology_node
        self.sim = sim
        self.results = results
        mp = sim.params.mem
        line = mp.line_size if mp is not None else 64
        self.core_if = McPATCoreInterface(self.node_nm)
        self.l1i_if = self._cache_if(mp.l1i, line) if mp else None
        self.l1d_if = self._cache_if(mp.l1d, line) if mp else None
        self.l2_if = self._cache_if(mp.l2, line) if mp else None
        self.noc_if = DSENTInterface(self.node_nm)

    def _cache_if(self, lvl, line):
        return McPATCacheInterface(
            self.node_nm, lvl.num_sets * lvl.num_ways * line,
            lvl.num_ways, line, num_banks=lvl.num_banks)

    def tile_energy_j(self, tile: int, voltage: float = 1.0) -> dict:
        r = self.results
        seconds = r.clock_ps[tile] * 1e-12
        instr = int(r.instruction_count[tile])
        branches = int(r.bp_correct[tile] + r.bp_incorrect[tile])
        # split the instruction mix from the available counters: memory
        # ops from L1-D accesses, the remainder as integer ALU work
        mem_ops = 0
        if r.mem_counters is not None:
            mc = r.mem_counters
            mem_ops = int(mc["l1d_read_hits"][tile]
                          + mc["l1d_read_misses"][tile]
                          + mc["l1d_write_hits"][tile]
                          + mc["l1d_write_misses"][tile])
        int_ops = max(instr - mem_ops - branches, 0)
        core_dyn = self.core_if.dynamic_energy_j(
            voltage, instructions=instr, int_ops=int_ops,
            mem_ops=mem_ops, branches=branches)
        out = {
            "core_dynamic": core_dyn,
            "core_static": self.core_if.leakage_energy_j(voltage, seconds),
        }
        if r.mem_counters is not None and self.l1d_if is not None:
            mc = r.mem_counters
            out["l1i_dynamic"] = self.l1i_if.dynamic_energy_j(
                voltage, int(mc["l1i_hits"][tile]),
                0, int(mc["l1i_misses"][tile]))
            out["l1d_dynamic"] = self.l1d_if.dynamic_energy_j(
                voltage,
                int(mc["l1d_read_hits"][tile]),
                int(mc["l1d_write_hits"][tile]),
                int(mc["l1d_read_misses"][tile]
                    + mc["l1d_write_misses"][tile]))
            out["l2_dynamic"] = self.l2_if.dynamic_energy_j(
                voltage, int(mc["l2_hits"][tile]), 0,
                int(mc["l2_misses"][tile]))
            for lif, key in ((self.l1i_if, "l1i_static"),
                             (self.l1d_if, "l1d_static"),
                             (self.l2_if, "l2_static")):
                out[key] = lif.leakage_energy_j(voltage, seconds)
            dram_e = load_native().dram_access_energy_j(
                self.node_nm, self.sim.params.mem.line_size)
            out["dram_dynamic"] = dram_e * int(
                mc["dram_reads"][tile] + mc["dram_writes"][tile])
        # charged at the sender only (no double count across tiles);
        # single-flit per packet approximation — multi-hop/multi-flit
        # accounting needs the NoC model's per-hop counters
        flits = int(r.packets_sent[tile])
        out["network_dynamic"] = (
            self.noc_if.router_dynamic_energy_j(voltage, flits)
            + self.noc_if.link_dynamic_energy_j(voltage, flits))
        out["network_static"] = self.noc_if.static_power_w(voltage) * seconds
        out["total"] = sum(out.values())
        return out

    def output_summary(self) -> str:
        """Per-tile energy summary (`tile_energy_monitor` outputSummary)."""
        lines = ["Tile Energy Monitor Summary"]
        total = 0.0
        for t in range(self.results.n_tiles):
            e = self.tile_energy_j(t)
            total += e["total"]
            lines.append(f"  Tile {t}:")
            lines.append(f"    Total Energy (in J): {e['total']:.6e}")
            lines.append(
                "    Core Energy (in J): "
                f"{e['core_dynamic'] + e['core_static']:.6e}")
            if "l1d_dynamic" in e:
                cache_e = sum(v for k, v in e.items()
                              if k.startswith(("l1", "l2")))
                lines.append(f"    Cache Energy (in J): {cache_e:.6e}")
            lines.append(
                "    Network Energy (in J): "
                f"{e['network_dynamic'] + e['network_static']:.6e}")
        lines.append(f"  Total Energy (in J): {total:.6e}")
        return "\n".join(lines)
