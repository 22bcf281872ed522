"""Event-driven sequential interpreter of the trace semantics (the oracle).

Independent second implementation for differential testing: a classic
discrete-event loop (always advance the runnable tile with the smallest
clock; blocked tiles park until their wake event exists).  Every
synchronization decision is ordered by (simulated time, tile id) — the
semantics the vectorized engine (`engine/step.py`) claims to implement
with masked iterations:

 - costs: static table cycles at the tile frequency (ceil ps conversion),
   one-bit branch predictor (predict last outcome, pc % size), BBLOCK runs
   aux1 cycles / aux0 instructions, dynamic records carry their cost;
 - SEND: zero-load arrival = clock + route latency (magic 1 cycle;
   hop-counter XY hops * (router+link) + receive serialization flits,
   self-sends skip serialization); RECV: clock = max(clock, arrival),
   charged as an instruction only when it waited;
 - BARRIER: release at the maximum arrival time (`SimBarrier`);
 - MUTEX: handoff at unlock time to the waiter with the earliest
   (clock, tile) key (`SimMutex`);
 - COND: wait releases the mutex; a signal at time S wakes the earliest
   eligible waiter (wait began at or before S) at time S, which then
   re-acquires the mutex; signals with no eligible waiter are lost;
   broadcast wakes every eligible waiter (`SimCond`);
 - THREAD_JOIN: clock pinned at max(clock, target stream's exit clock);
   Op.SPAWN (dynamic) sets clock = max(clock, value);
 - SYSCALL / DVFS_GET: the MCP / DVFS-manager round trip (2 cycles at
   1 GHz — both networks are magic);
 - ENABLE/DISABLE_MODELS: zero cost and no counters while disabled.

Scope: core timing + sync/messaging as above, plus — when shared memory
is enabled and the trace touches memory — the full private-L1/L2
dram-directory hierarchy via `golden.memory_model.GoldenMemory` (an
independent sequential implementation; see its docstring for the
ordering discipline and the exact-vs-envelope test contract).

 - DVFS_SET: the per-tile V/f table (AUTO / HOLD, the rc codes as an
   error count); a CORE-domain retune moves the tile's core AND its
   caches' clock (`GoldenMemory.freq` follows), directory and networks
   keep their domain's frequency, as the engine's per-tile path has it.
   Out of scope: the chip-global runtime spec (`dvfs/runtime.py`).
 - energy, under `[general] enable_power_modeling`: a tile's interval is
   closed at the operating point that was in force when its DVFS_SET
   succeeds and once more at the end, by the rule of
   `power/accounting.py` in plain Python integers (the price list is
   the configuration's, `EnergyParams`; the loop is this file's).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from graphite_tpu.trace.schema import FLAG_BRANCH_TAKEN, Op, TraceBatch

ANY_SENDER = -1  # CAPI wildcard sender (`engine/step.py:57`)

HEADER_BYTES = 64  # NetPacket header (`network.h:27-53`)
FAR = 2**62


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def cycles_to_ps(cycles: int, freq_mhz: int) -> int:
    return _ceil_div(cycles * 10**6, freq_mhz)


@dataclasses.dataclass
class GoldenResult:
    clock_ps: np.ndarray
    instruction_count: np.ndarray
    recv_instructions: np.ndarray
    sync_instructions: np.ndarray
    bp_correct: np.ndarray
    bp_incorrect: np.ndarray
    # per-tile memory-hierarchy counters ({name: np.ndarray[T]}), None
    # when the run had no memory model
    mem_counters: dict | None = None
    # barrier / mutex / cond waits charged (engine: `core.sync_stall_ps`)
    sync_stall_ps: np.ndarray | None = None
    # per-tile rejected DVFS_SET requests (engine: `dvfs.errors`)
    dvfs_errors: np.ndarray | None = None
    # per-tile final CORE-domain frequency after in-trace retunes
    core_freq_mhz: np.ndarray | None = None
    # per-port event counters of the user NoC ({name: int64[T, 6]}, the
    # engine's `SimResults.noc_counters`), None unless the user network
    # is emesh_hop_by_hop
    noc_counters: dict | None = None
    # per-hub event counters of the MEMORY network's ATAC hubs ({name:
    # int64[2 * n_clusters]}, send hubs then receive hubs: the engine's
    # `SimResults.atac_counters`), None unless the memory network is atac
    atac_counters: dict | None = None
    # the engine's `SimResults.energy_pj` ({component: int64[T] pJ}),
    # None unless [general] enable_power_modeling
    energy_pj: dict | None = None
    # the engine's `SimResults.dvfs_counters`, None unless the
    # configuration has a [dvfs] section
    dvfs_counters: dict | None = None


class _Net:
    def __init__(self, kind, freq_mhz, mesh_width, hop_cycles, flit_bits):
        self.kind = kind
        self.freq_mhz = freq_mhz
        self.w = mesh_width
        self.hop_cycles = hop_cycles
        self.flit_bits = flit_bits

    def latency_ps(self, src, dst, payload_bytes, enabled):
        if self.kind == "magic":
            return cycles_to_ps(1, self.freq_mhz)
        hops = abs(src % self.w - dst % self.w) + abs(
            src // self.w - dst // self.w)
        cycles = hops * self.hop_cycles
        if src != dst and self.flit_bits > 0:
            cycles += _ceil_div((HEADER_BYTES + payload_bytes) * 8,
                                self.flit_bits)
        return cycles_to_ps(cycles, self.freq_mhz) if enabled else 0


class _HbhNet:
    """Serial per-hop emesh_hop_by_hop oracle: the reference's hop loop
    (`network_model_emesh_hop_by_hop.cc:146-265` + router contention)
    implemented one packet at a time over per-port queue dicts — the
    independent counterpart of the engine's dense-grid formulation (which
    must match it exactly for cross-call queueing; same-call packet
    batching follows the engine's documented approximation contract, so
    differential tests use serialized traffic)."""

    def __init__(self, p):
        self.p = p  # HopByHopParams (config-derived constants)
        self.q: dict[int, dict] = {}  # qid -> queue scalars

    def _queue(self, qid):
        return self.q.setdefault(qid, dict(
            qt=0, ws=0, sum_st=0, sum_st2=0, n=0, newest=0,
            requests=0, utilization_cycles=0, delay_cycles=0,
            analytical_reads=0))

    def _delay(self, qid, t, proc):
        s = self._queue(qid)
        qp = self.p.queue
        if qp.kind in ("history_list", "history_tree"):
            if qp.analytical_enabled and (t + proc) < s["ws"]:
                # M/G/1 fallback from the running moments: the formula of
                # `queue_model_m_g_1.cc:18-47` with mu = n / sum_st,
                # lambda = min(n / newest, 0.999 mu) and 1 / mu^2 + var =
                # sum_st2 / n, as an exact fraction (no backend's floats)
                if s["n"] == 0:
                    return 0, True
                st = max(s["sum_st"], 1)
                if 1000 * st <= 999 * s["newest"]:
                    w = _ceil_div(s["sum_st2"], 2 * (s["newest"] - st))
                else:       # arrivals capped at 0.999 of the service rate
                    w = _ceil_div(999 * s["sum_st2"], 2 * st)
                return w, True
            return max(s["qt"] - t, 0), False
        return max(s["qt"] - t, 0), False

    def _commit(self, qid, t, delay, proc):
        s = self._queue(qid)
        qp = self.p.queue
        in_window = True
        if qp.kind in ("history_list", "history_tree"):
            in_window = not (qp.analytical_enabled
                             and (t + proc) < s["ws"])
        if in_window:
            s["qt"] = max(s["qt"], t) + proc
            s["ws"] = max(s["ws"], s["qt"] - qp.history_span)
        s["sum_st"] += proc
        s["sum_st2"] += proc * proc
        s["n"] += 1
        s["newest"] = max(s["newest"], t + delay + proc)
        # event counters (`updateQueueUtilizationCounters`)
        s["requests"] += 1
        s["utilization_cycles"] += proc
        s["delay_cycles"] += delay
        s["analytical_reads"] += not in_window

    def port_counters(self) -> dict:
        """{name: int64[n_tiles, 6]} over the mesh's port queues."""
        from graphite_tpu.models.network_hop_by_hop import (
            NOC_COUNTERS, NUM_PORTS,
        )

        out = {name: np.zeros((self.p.n_tiles, NUM_PORTS), np.int64)
               for name, _ in NOC_COUNTERS}
        for qid, s in self.q.items():
            for name in out:
                out[name][divmod(qid, NUM_PORTS)] = s[name]
        return out

    def route(self, src, dst, payload_bytes, t_send_ps, enabled):
        """Returns the arrival time in ps (absolute)."""
        return self.route_bits(
            src, dst, (HEADER_BYTES + payload_bytes) * 8, t_send_ps,
            enabled)

    def route_bits(self, src, dst, bits, t_send_ps, enabled):
        """Route a packet of `bits` modeled length (no NetPacket header —
        the MEMORY net's ShmemMsg lengths are carried raw)."""
        from graphite_tpu.models.network_hop_by_hop import (
            NUM_PORTS, PORT_DOWN, PORT_INJECT, PORT_LEFT, PORT_RIGHT,
            PORT_SELF, PORT_UP,
        )

        p = self.p
        if not enabled:
            return t_send_ps
        flits = max(_ceil_div(bits, p.flit_width_bits), 1)
        # Time::toCycles is ceil (`time_types.h:104-109`)
        t = _ceil_div(t_send_ps * p.freq_mhz, 10**6)

        def hop_delay(qid, t):
            if not p.contention_enabled:
                return 0
            d, _ = self._delay(qid, t, flits)
            self._commit(qid, t, d, flits)
            return d

        # injection
        t = t + p.router_delay + hop_delay(
            src * NUM_PORTS + PORT_INJECT, t)
        # XY route, scalar arithmetic (independent of the engine's helper)
        w = p.mesh_width
        cx, cy = src % w, src // w
        tx, ty = dst % w, dst // w
        while True:
            if cx < tx:
                port, cx = PORT_RIGHT, cx + 1
            elif cx > tx:
                port, cx = PORT_LEFT, cx - 1
            elif cy < ty:
                port, cy = PORT_UP, cy + 1
            elif cy > ty:
                port, cy = PORT_DOWN, cy - 1
            else:
                port = PORT_SELF
            # the queue consulted is the port at the tile BEFORE moving
            at = ((cy if port in (PORT_SELF, PORT_RIGHT, PORT_LEFT)
                   else cy - (1 if port == PORT_UP else -1)) * w
                  + (cx if port in (PORT_SELF, PORT_UP, PORT_DOWN)
                     else cx - (1 if port == PORT_RIGHT else -1)))
            t = t + p.router_delay + p.link_delay + hop_delay(
                at * NUM_PORTS + port, t)
            if port == PORT_SELF:
                break
        if src != dst:
            t += flits
        return cycles_to_ps(int(t), p.freq_mhz)

    def fanout(self, src, targets, bits, t0_ps, enabled, n_copies=None,
               ranks=None, copy_set=None):
        """A home's multicast, mirroring the ENGINE's shared fan-out
        approximation (`memory/engine.py mem_net_fanout`): ONE inject-port
        charge of n_copies*flits, rank-of-target serialization (by tile
        id), then each copy's zero-load path — intermediate-hop queues are
        neither read nor committed for fan-out copies.  This is the one
        piece of the memory NoC the oracle shares with the engine by
        construction instead of independently (documented there); all
        unicast flows remain independently per-hop modeled.  Returns
        {target: arrival_ps}."""
        from graphite_tpu.models.network_hop_by_hop import (
            NUM_PORTS, PORT_INJECT,
        )

        p = self.p
        targets = sorted(targets)
        if not enabled or not targets:
            return {s: t0_ps for s in targets}
        flits = max(_ceil_div(bits, p.flit_width_bits), 1)
        k = n_copies if n_copies is not None else len(targets)
        t0 = _ceil_div(t0_ps * p.freq_mhz, 10**6)
        inj = 0
        if p.contention_enabled:
            qid = src * NUM_PORTS + PORT_INJECT
            inj, _ = self._delay(qid, t0, k * flits)
            self._commit(qid, t0, inj, k * flits)
        w = p.mesh_width
        step = p.router_delay + p.link_delay
        out = {}
        for i, s in enumerate(targets):
            rank = ranks[s] if ranks is not None else i
            hops = abs(src % w - s % w) + abs(src // w - s // w)
            zl = p.router_delay + (hops + 1) * step + (
                0 if s == src else flits)
            out[s] = t0_ps + cycles_to_ps(
                int(zl + inj + rank * flits), p.freq_mhz)
        return out


class _AtacNet(_HbhNet):
    """Serial ATAC optical-NoC oracle (`network_model_atac.cc:337-368`):
    one packet at a time over per-hub queue dicts — the independent
    counterpart of `models/network_atac.route_atac`.  Intra-cluster (or
    short-distance under distance_based routing) unicasts ride the ENet
    at hop cost; everything else pays ENet-to-hub, send-hub queue +
    router, the optical link (waveguide + E-O/O-E), receive-hub queue +
    router, and the receive net, plus receiver serialization."""

    # (route() is inherited: _HbhNet already wraps route_bits with the
    # NetPacket header)

    def hub_counters(self) -> dict:
        """{name: int64[2 * n_clusters]}, send hubs then receive hubs."""
        from graphite_tpu.models.network_atac import ATAC_COUNTERS

        out = {name: np.zeros(2 * self.p.n_clusters, np.int64)
               for name, _ in ATAC_COUNTERS}
        for qid, s in self.q.items():
            for name in out:
                out[name][qid] = s[name]
        return out

    def _cluster(self, t):
        p = self.p
        x, y = t % p.mesh_width, t // p.mesh_width
        cpr = p.mesh_width // p.cluster_width
        return (y // p.cluster_height) * cpr + (x // p.cluster_width)

    def _hub(self, c):
        p = self.p
        cpr = p.mesh_width // p.cluster_width
        return ((c // cpr) * p.cluster_height * p.mesh_width
                + (c % cpr) * p.cluster_width)

    def _hops(self, a, b):
        w = self.p.mesh_width
        return abs(a % w - b % w) + abs(a // w - b // w)

    def _use_onet(self, src, dst):
        p = self.p
        same = self._cluster(src) == self._cluster(dst)
        if p.global_routing_strategy == "distance_based":
            return not (same
                        or self._hops(src, dst)
                        <= p.unicast_distance_threshold)
        return not same

    def route_bits(self, src, dst, bits, t_send_ps, enabled):
        """Route a packet of `bits` modeled length (raw ShmemMsg lengths
        on the MEMORY net, NetPacket-headered on the USER net)."""
        p = self.p  # AtacParams
        if not enabled:
            return t_send_ps
        flits = max(_ceil_div(bits, p.flit_width_bits), 1)

        def cyc_ps(n):
            return _ceil_div(int(n) * 10**6, p.freq_mhz)

        ser_ps = 0 if src == dst else cyc_ps(flits)
        csrc, cdst = self._cluster(src), self._cluster(dst)
        if not self._use_onet(src, dst):
            return (t_send_ps
                    + cyc_ps(self._hops(src, dst) * p.enet_hop_cycles)
                    + ser_ps)

        sendhub_arrive = t_send_ps + cyc_ps(
            self._hops(src, self._hub(csrc)) * p.enet_hop_cycles)
        if p.contention_enabled:
            t_cyc = _ceil_div(sendhub_arrive * p.freq_mhz, 10**6)
            d, _ = self._delay(csrc, t_cyc, flits)
            self._commit(csrc, t_cyc, d, flits)
        else:
            d = 0
        sendhub_done = sendhub_arrive + cyc_ps(d + p.send_hub_cycles)
        recvhub_arrive = sendhub_done + p.optical_link_ps
        if p.contention_enabled:
            t_cyc = _ceil_div(recvhub_arrive * p.freq_mhz, 10**6)
            d2, _ = self._delay(p.n_clusters + cdst, t_cyc, flits)
            self._commit(p.n_clusters + cdst, t_cyc, d2, flits)
        else:
            d2 = 0
        recvhub_done = recvhub_arrive + cyc_ps(d2 + p.receive_hub_cycles)
        return (recvhub_done
                + cyc_ps(p.receive_net_levels * p.receive_net_cycles)
                + ser_ps)

    def _zeroload_ps(self, src, dst, bits):
        """Contention-free path cost (engine's atac_zeroload_ps mirror)."""
        p = self.p
        flits = max(_ceil_div(bits, p.flit_width_bits), 1)

        def cyc_ps(n):
            return _ceil_div(int(n) * 10**6, p.freq_mhz)

        ser = 0 if src == dst else cyc_ps(flits)
        if not self._use_onet(src, dst):
            return (cyc_ps(self._hops(src, dst) * p.enet_hop_cycles)
                    + ser, False)
        onet = (cyc_ps(self._hops(src, self._hub(self._cluster(src)))
                       * p.enet_hop_cycles)
                + cyc_ps(p.send_hub_cycles) + p.optical_link_ps
                + cyc_ps(p.receive_hub_cycles)
                + cyc_ps(p.receive_net_levels * p.receive_net_cycles))
        return onet + ser, True

    def fanout(self, src, targets, bits, t0_ps, enabled, n_copies=None,
               ranks=None, copy_set=None):
        """A home's multicast, mirroring the ENGINE's ATAC fan-out
        (`memory/engine.py mem_net_fanout` atac leg): ONE send-hub charge
        of k_onet*flits (delay applied to ONet copies), rank-of-target
        serialization (by tile id) for every copy, then each copy's
        zero-load path.  Returns {target: arrival_ps}."""
        p = self.p
        targets = sorted(targets)
        if not enabled or not targets:
            return {s: t0_ps for s in targets}
        flits = max(_ceil_div(bits, p.flit_width_bits), 1)
        zl = {s: self._zeroload_ps(src, s, bits) for s in targets}
        # the hub charge counts every ONet COPY — broadcast sweeps pass
        # the full copy set (engine: (send_hs & onet_pair).sum())
        copies = copy_set if copy_set is not None else targets
        k_onet = sum(1 for s in copies if self._use_onet(src, s))
        inj = 0
        if p.contention_enabled and k_onet > 0:
            t_cyc = _ceil_div(t0_ps * p.freq_mhz, 10**6)
            inj, _ = self._delay(self._cluster(src), t_cyc, k_onet * flits)
            self._commit(self._cluster(src), t_cyc, inj, k_onet * flits)

        def cyc_ps(n):
            return _ceil_div(int(n) * 10**6, p.freq_mhz)

        out = {}
        for i, s in enumerate(targets):
            rank = ranks[s] if ranks is not None else i
            lat, onet = zl[s]
            # ONE cycles->ps conversion for the combined extra cycles —
            # the engine converts the sum (rank*flits + hub delay) once,
            # and split ceil conversions diverge at frequencies that do
            # not divide 10^6
            out[s] = t0_ps + lat + cyc_ps(
                rank * flits + (inj if onet else 0))
        return out


class _Tile:
    __slots__ = ("tid", "clock", "idx", "done", "blocked", "counts")

    def __init__(self, tid):
        self.tid = tid
        self.clock = 0
        self.idx = 0
        self.done = False
        self.blocked = None  # None | ("recv", src) | ("barrier", b)
        #                       | ("mutex", m) | ("join", t) | ("cond", c, m)
        self.counts = dict(instr=0, recv=0, sync=0, bp_ok=0, bp_bad=0,
                           sync_ps=0, sent=0)


def run_golden(sim_config, batch: TraceBatch,
               syscall_rt_ps: int = 2000) -> GoldenResult:
    cfg = sim_config.cfg
    T = batch.n_tiles
    # per-tile core frequency comes from the CORE DVFS domain, exactly as
    # the simulator initializes it (`simulator.py` core_freq)
    from graphite_tpu.models.dvfs import DvfsParams, module_freq_mhz

    freq_mhz = int(module_freq_mhz(cfg, "CORE"))
    # per-tile V/f state for in-trace DVFS_SET (mirrors the engine's
    # legacy per-tile table: AUTO picks the minimum voltage for the
    # frequency, HOLD keeps the current voltage and fails above its
    # maximum, invalid requests count and leave state unchanged; the
    # retune itself is zero-cost).  Core instruction costs read the
    # issuing tile's CORE-domain frequency.
    dvp = DvfsParams.from_config(cfg)
    dvfs_freq = [[int(f) for f in dvp.domain_freq_mhz] for _ in range(T)]
    dvfs_volt = [[int(dvp.min_voltage_mv(int(f)))
                  for f in dvp.domain_freq_mhz] for _ in range(T)]
    dvfs_errors = [0] * T
    core_freq = [freq_mhz] * T

    # static cost table
    from graphite_tpu.trace.schema import STATIC_COST_KEYS

    costs = [cfg.get_int(f"core/static_instruction_costs/{k}", 0)
             for k in STATIC_COST_KEYS]

    net_kind = cfg.get_string("network/user", "magic")
    if net_kind == "magic":
        net = _Net("magic", 1000, 0, 0, -1)
    elif net_kind == "emesh_hop_by_hop":
        from graphite_tpu.models.network_hop_by_hop import HopByHopParams

        net = _HbhNet(HopByHopParams.from_config(sim_config, "user"))
    elif net_kind == "atac":
        from graphite_tpu.models.network_atac import AtacParams

        net = _AtacNet(AtacParams.from_config(sim_config, "user"))
    else:
        from graphite_tpu.models.network_user import mesh_dims

        w, _ = mesh_dims(T)
        router = cfg.get_int(f"network/{net_kind}/router/delay", 1)
        link = cfg.get_int(f"network/{net_kind}/link/delay", 1)
        flit = cfg.get_int(f"network/{net_kind}/flit_width", 64)
        net = _Net("emesh", 1000, w, router + link, flit)

    bp_size = cfg.get_int("branch_predictor/size", 1024)
    bp_penalty = cfg.get_int("branch_predictor/mispredict_penalty", 14)
    bp_bits = np.zeros((T, bp_size), np.uint8)

    # memory hierarchy (same gating as the engine, `simulator.py`):
    # enable_shared_mem AND the trace actually touches memory
    from graphite_tpu.trace.schema import FLAG_MEM0_VALID, FLAG_MEM1_VALID

    has_mem = bool(
        np.any(batch.flags & (FLAG_MEM0_VALID | FLAG_MEM1_VALID))
    ) or cfg.get_bool("general/enable_icache_modeling", False)
    # scope guard: the golden core model is the simple 1-IPC in-order
    # pipeline; iocoom tiles overlap memory latencies in the scoreboard
    # (`iocoom_core_model.cc:120-280`) which this oracle does not model
    for tt in range(T):
        ct = sim_config.tile_spec(tt).core_type
        if ct not in ("simple", "magic"):
            raise NotImplementedError(
                f"golden oracle models the simple core only; tile {tt} "
                f"is {ct!r}")
    mem = None
    if sim_config.enable_shared_mem and has_mem:
        from graphite_tpu.memory.params import MemParams

        mp = MemParams.from_config(sim_config)
        if mp.protocol.startswith("pr_l1_sh_l2"):
            from graphite_tpu.golden.memory_model_shl2 import GoldenShL2

            mem = GoldenShL2(mp, module_freq_mhz(cfg, "CORE"))
        else:
            from graphite_tpu.golden.memory_model import GoldenMemory

            mem = GoldenMemory(mp, module_freq_mhz(cfg, "CORE"))

    tiles = [_Tile(t) for t in range(T)]
    enabled = [True]  # models toggle is GLOBAL (PerformanceCounterManager)
    # messages: (src,dst) -> FIFO of (arrival_ps,)
    channels: dict[tuple, list] = {}
    barriers: dict[int, dict] = {}   # id -> {count, arrived:[(clock,tile)]}
    mutexes: dict[int, dict] = {}    # id -> {locked, handoff, waiters}
    conds: dict[int, list] = {}      # id -> [(arrival, tile, mutex_id)]
    exit_clock: dict[int, int] = {}
    # split-form rendezvous state (BARRIER_ARRIVE/SYNC, COND_JOIN),
    # generation-exact (the engine keeps a GEN_RING-deep ring; identical
    # while rendezvous lag <= GEN_RING, the documented bound)
    bar_gen: dict[int, int] = {}      # id -> releases so far
    bar_release: dict[tuple, int] = {}  # (id, gen) -> release time
    sig_seq: dict[int, int] = {}      # cond id -> published signals so far
    sig_time: dict[tuple, int] = {}   # (cond id, seq) -> publish time

    # energy (power/accounting.py's rule; its price list, this loop)
    ep = None
    if sim_config.enable_power_modeling:
        from graphite_tpu.power.accounting import EnergyParams

        ep = EnergyParams.from_config(
            sim_config, dvp, mem.mp if mem is not None else None)
        energy_acc = [[0] * len(ep.columns) for _ in range(T)]
        energy_last = [dict.fromkeys(ep.raw, 0) for _ in range(T)]
        energy_last_clock = [0] * T

    def close_energy(t: _Tile):
        if ep is None:
            return
        c, tid = t.counts, t.tid
        now = dict(instructions=c["instr"] + c["recv"] + c["sync"],
                   mem_ops=0, branches=c["bp_ok"] + c["bp_bad"],
                   packets_sent=c["sent"])
        if ep.has_mem:
            mc = {k: int(mem.counters[k][tid]) for k in (
                "l1i_hits", "l1i_misses", "l1d_read_hits", "l1d_write_hits",
                "l1d_read_misses", "l1d_write_misses", "l2_hits",
                "l2_misses", "dram_reads", "dram_writes")}
            misses = mc["l1d_read_misses"] + mc["l1d_write_misses"]
            now.update(
                mem_ops=mc["l1d_read_hits"] + mc["l1d_write_hits"] + misses,
                l1i_hits=mc["l1i_hits"], l1i_misses=mc["l1i_misses"],
                l1d_read_hits=mc["l1d_read_hits"],
                l1d_write_hits=mc["l1d_write_hits"], l1d_misses=misses,
                l2_hits=mc["l2_hits"], l2_misses=mc["l2_misses"],
                dram_accesses=mc["dram_reads"] + mc["dram_writes"])
        d = {k: now[k] - energy_last[tid][k] for k in ep.raw}
        d["int_ops"] = max(
            d["instructions"] - d["mem_ops"] - d["branches"], 0)
        dt = t.clock - energy_last_clock[tid]
        for k, (static, dom, price) in enumerate(
                zip(ep.static, ep.domains, ep.prices)):
            lvl = 0 if dom < 0 else ep.voltages_mv.index(
                dvfs_volt[tid][dom])
            if static:
                energy_acc[tid][k] += dt * price[lvl]
            else:
                energy_acc[tid][k] += sum(
                    d[name] * table[lvl] for name, table in price)
        energy_last[tid] = {k: now[k] for k in ep.raw}
        energy_last_clock[tid] = t.clock

    def runnable(t: _Tile) -> bool:
        if t.done or t.blocked is not None:
            return False
        return t.idx < batch.length

    def rec(t, field):
        return int(getattr(batch, field)[t.tid, t.idx])

    def grant_mutex(m: int):
        """Hand the mutex to the waiter with the smallest (eff_clock, tile)
        key, at the unlock handoff time (`SimMutex`)."""
        mx = mutexes.setdefault(m, dict(locked=False, handoff=0, waiters=[]))
        if mx["locked"] or not mx["waiters"]:
            return
        mx["waiters"].sort()
        eff_clock, wtid, wake = mx["waiters"].pop(0)
        mx["locked"] = True
        t = tiles[wtid]
        new_clock = max(eff_clock, mx["handoff"], wake)
        if new_clock > t.clock and enabled[0]:
            t.counts["sync"] += 1
            t.counts["sync_ps"] += new_clock - t.clock
        t.clock = new_clock
        t.blocked = None

    def try_unblock(t: _Tile):
        """Re-check a parked tile's wake condition."""
        kind = t.blocked[0]
        if kind == "recv":
            src = t.blocked[1]
            if src == ANY_SENDER:
                cand = [(q[0], s) for (s, d), q in channels.items()
                        if d == t.tid and q]
                if not cand:
                    return
                arrival, src = min(cand)
            else:
                q = channels.get((src, t.tid))
                if not q:
                    return
                arrival = q[0]
            channels[(src, t.tid)].pop(0)
            if arrival > t.clock:
                if enabled[0]:
                    t.counts["recv"] += 1
                t.clock = arrival
            t.blocked = None
            t.idx += 1
        elif kind == "join":
            target = t.blocked[1]
            if target in exit_clock:
                t.clock = max(t.clock, exit_clock[target])
                t.blocked = None
                t.idx += 1
        elif kind == "bsync":
            b, gen = t.blocked[1], t.blocked[2]
            if bar_gen.get(b, 0) >= gen:
                rel = bar_release.get((b, gen), 0)
                if rel > t.clock and enabled[0]:
                    t.counts["sync"] += 1
                    t.counts["sync_ps"] += rel - t.clock
                t.clock = max(t.clock, rel)
                t.blocked = None
                t.idx += 1
        elif kind == "cjoin":
            c, k = t.blocked[1], t.blocked[2]
            if sig_seq.get(c, 0) >= k:
                st = sig_time.get((c, k), 0)
                if st > t.clock and enabled[0]:
                    t.counts["sync"] += 1
                    t.counts["sync_ps"] += st - t.clock
                t.clock = max(t.clock, st)
                t.blocked = None
                t.idx += 1

    def step(t: _Tile):
        op = rec(t, "op")
        aux0, aux1 = rec(t, "aux0"), rec(t, "aux1")
        advance = True
        if op == Op.THREAD_EXIT or op == Op.NOP:
            t.done = True
            exit_clock[t.tid] = t.clock
            for other in tiles:
                if other.blocked and other.blocked[0] == "join" \
                        and other.blocked[1] == t.tid:
                    try_unblock(other)
            return
        def mem_acc():
            """Memory latency of this record's slots (0 without a model);
            data slots mutate cache/directory state even when models are
            disabled (the icache slot exists only while enabled)."""
            if mem is None:
                return 0
            return mem.access_record(
                t.tid, op, rec(t, "flags"), rec(t, "pc"),
                rec(t, "addr0"), rec(t, "addr1"), t.clock, enabled[0])

        if op < Op.DYNAMIC_MISC and op != Op.BRANCH:   # static instr
            acc = mem_acc()
            if enabled[0]:
                t.clock += cycles_to_ps(costs[op], core_freq[t.tid]) + acc
                t.counts["instr"] += 1
        elif op == Op.BRANCH:
            pc = rec(t, "pc") % bp_size
            taken = 1 if (rec(t, "flags") & FLAG_BRANCH_TAKEN) else 0
            ok = bp_bits[t.tid, pc] == taken
            bp_bits[t.tid, pc] = taken
            cycles = 1 if ok else bp_penalty
            acc = mem_acc()
            if enabled[0]:
                t.clock += cycles_to_ps(cycles, core_freq[t.tid]) + acc
                t.counts["instr"] += 1
                t.counts["bp_ok" if ok else "bp_bad"] += 1
        elif op < 20:                                   # dynamic
            dyn = int(batch.dyn_ps[t.tid, t.idx])
            if op == Op.SPAWN:
                t.clock = max(t.clock, dyn)
            else:
                if enabled[0]:
                    t.clock += dyn
                    t.counts["instr"] += 1
        elif op == Op.BBLOCK:
            acc = mem_acc()
            if enabled[0]:
                t.clock += cycles_to_ps(aux1, core_freq[t.tid]) + acc
                t.counts["instr"] += aux0
        elif op == Op.SEND:
            t.counts["sent"] += 1
            if isinstance(net, _HbhNet):
                arrival = net.route(t.tid, aux0, aux1, t.clock, enabled[0])
            else:
                arrival = t.clock + net.latency_ps(
                    t.tid, aux0, aux1, enabled[0])
            channels.setdefault((t.tid, aux0), []).append(arrival)
            for other in tiles:
                if other.blocked and other.blocked[0] == "recv":
                    try_unblock(other)
        elif op == Op.NET_RECV:
            t.blocked = ("recv", aux0)
            try_unblock(t)
            return  # try_unblock advances idx on success
        elif op == Op.BARRIER_INIT:
            b = barriers.setdefault(aux0, dict(count=0, arrived=[]))
            b["count"] = aux1  # re-arm the count; arrivals stay
        elif op in (Op.BARRIER_WAIT, Op.BARRIER_ARRIVE):
            blocking = op == Op.BARRIER_WAIT
            b = barriers[aux0]
            # arrival time captured NOW (ARRIVE lanes keep running)
            b["arrived"].append((t.clock, t.tid, blocking))
            if blocking:
                t.blocked = ("barrier", aux0)
            t.idx += 1  # the record commits at release time
            if len(b["arrived"]) >= b["count"]:
                release = max(c for c, _, _ in b["arrived"])
                for (c, x, was_blocking) in b["arrived"]:
                    if not was_blocking:
                        continue
                    tx = tiles[x]
                    if release > tx.clock and enabled[0]:
                        tx.counts["sync"] += 1
                        tx.counts["sync_ps"] += release - tx.clock
                    tx.clock = max(tx.clock, release)
                    tx.blocked = None
                b["arrived"] = []
                g = bar_gen.get(aux0, 0) + 1
                bar_gen[aux0] = g
                bar_release[(aux0, g)] = release
            return
        elif op == Op.BARRIER_SYNC:
            t.blocked = ("bsync", aux0, aux1)
            try_unblock(t)
            return
        elif op == Op.COND_JOIN:
            t.blocked = ("cjoin", aux0, aux1)
            try_unblock(t)
            return
        elif op == Op.MUTEX_INIT:
            mutexes[aux0] = dict(locked=False, handoff=0, waiters=[])
        elif op == Op.MUTEX_LOCK:
            mutexes.setdefault(
                aux0, dict(locked=False, handoff=0, waiters=[]))
            mutexes[aux0]["waiters"].append((t.clock, t.tid, 0))
            t.blocked = ("mutex", aux0)
            t.idx += 1
            grant_mutex(aux0)
            return
        elif op == Op.MUTEX_UNLOCK:
            mx = mutexes[aux0]
            mx["locked"] = False
            mx["handoff"] = t.clock
            grant_mutex(aux0)
        elif op == Op.COND_INIT:
            conds[aux0] = []
        elif op == Op.COND_WAIT:
            # release the mutex, park on the cond
            mx = mutexes[aux1]
            mx["locked"] = False
            mx["handoff"] = t.clock
            conds.setdefault(aux0, []).append((t.clock, t.tid, aux1))
            t.blocked = ("cond", aux0, aux1)
            t.idx += 1
            grant_mutex(aux1)
            return
        elif op in (Op.COND_SIGNAL, Op.COND_BROADCAST) and aux1 > 0:
            # published form (live frontend): bump the sequence + stamp
            k = sig_seq.get(aux0, 0) + 1
            sig_seq[aux0] = k
            sig_time[(aux0, k)] = t.clock
        elif op in (Op.COND_SIGNAL, Op.COND_BROADCAST):
            S = t.clock
            waiters = conds.setdefault(aux0, [])
            elig = sorted(w for w in waiters if w[0] <= S)
            wake = elig if op == Op.COND_BROADCAST else elig[:1]
            for (arr, wtid, m) in wake:
                waiters.remove((arr, wtid, m))
                # woken waiter re-acquires its mutex; its grant key is its
                # effective clock max(clock, wake time S)
                mutexes[m]["waiters"].append(
                    (max(tiles[wtid].clock, S), wtid, S))
                tiles[wtid].blocked = ("mutex", m)
                grant_mutex(m)
            # no eligible waiter: the signal is lost
        elif op == Op.THREAD_SPAWN:
            pass  # functionally nothing: streams are pre-laid-out
        elif op == Op.THREAD_JOIN:
            t.blocked = ("join", aux0)
            try_unblock(t)
            return
        elif op == Op.ENABLE_MODELS:
            enabled[0] = True
        elif op == Op.DISABLE_MODELS:
            enabled[0] = False
        elif op in (Op.SYSCALL, Op.DVFS_GET):
            if enabled[0]:
                t.clock += syscall_rt_ps
        elif op == Op.DVFS_SET:
            # zero-cost retune; mirrors the engine's `_dvfs_block`
            # validation exactly (legacy per-tile table).  aux1 < 0 is
            # the HOLD encoding: keep the current voltage, the request
            # must fit under its max frequency.  AUTO picks the minimum
            # voltage for the frequency.  An invalid domain or an
            # unachievable frequency counts one error, state untouched.
            req = abs(aux1)
            dom = min(max(aux0, 0), dvp.n_domains - 1)
            valid_dom = 0 <= aux0 < dvp.n_domains
            auto_mv = dvp.min_voltage_mv(req) if req > 0 else -1
            if aux1 < 0:  # HOLD: current voltage caps the frequency
                cap = dvp.max_freq_at_mv(dvfs_volt[t.tid][dom])
                ok = valid_dom and auto_mv >= 0 and req <= cap
                new_mv = dvfs_volt[t.tid][dom]
            else:
                ok = valid_dom and auto_mv >= 0
                new_mv = auto_mv
            if ok:
                # the interval closes at the OLD operating point
                close_energy(t)
                dvfs_freq[t.tid][dom] = req
                dvfs_volt[t.tid][dom] = new_mv
                if dom == dvp.core_domain:
                    core_freq[t.tid] = req
                    if mem is not None:
                        # the caches run on their tile's core clock
                        mem.freq[t.tid] = req
            else:
                dvfs_errors[t.tid] += 1
        else:
            raise NotImplementedError(f"golden: op {op}")
        if advance:
            t.idx += 1

    # main loop: smallest-clock runnable tile first
    while True:
        # state-conditioned rendezvous kinds wake lazily here
        for t in tiles:
            if t.blocked and t.blocked[0] in ("bsync", "cjoin"):
                try_unblock(t)
        run = [t for t in tiles if runnable(t)]
        if not run:
            # every tile done, or deadlock (mirrors the engine's detector)
            if all(t.done or t.idx >= batch.length for t in tiles):
                break
            stuck = [t.tid for t in tiles if not t.done]
            raise RuntimeError(f"golden: deadlock, blocked tiles {stuck}")
        t = min(run, key=lambda x: (x.clock, x.tid))
        step(t)

    energy_pj = None
    if ep is not None:
        from graphite_tpu.power.accounting import to_pj

        for t in tiles:
            close_energy(t)
        energy_pj = to_pj(ep, np.asarray(energy_acc, np.int64))
    dvfs_counters = None
    if cfg.has_section("dvfs"):
        dvfs_counters = {
            "freq_mhz": np.asarray(dvfs_freq, np.int32),
            "voltage_mv": np.asarray(dvfs_volt, np.int32),
            "errors": np.asarray(dvfs_errors, np.int64),
        }
    return GoldenResult(
        energy_pj=energy_pj,
        dvfs_counters=dvfs_counters,
        sync_stall_ps=np.asarray(
            [t.counts["sync_ps"] for t in tiles], np.int64),
        clock_ps=np.asarray([t.clock for t in tiles], np.int64),
        instruction_count=np.asarray(
            [t.counts["instr"] for t in tiles], np.int64),
        recv_instructions=np.asarray(
            [t.counts["recv"] for t in tiles], np.int64),
        sync_instructions=np.asarray(
            [t.counts["sync"] for t in tiles], np.int64),
        bp_correct=np.asarray([t.counts["bp_ok"] for t in tiles], np.int64),
        bp_incorrect=np.asarray(
            [t.counts["bp_bad"] for t in tiles], np.int64),
        mem_counters=(
            {k: np.asarray(v, np.int64) for k, v in mem.counters.items()}
            if mem is not None else None),
        dvfs_errors=np.asarray(dvfs_errors, np.int64),
        core_freq_mhz=np.asarray(core_freq, np.int64),
        noc_counters=(net.port_counters()
                      if net_kind == "emesh_hop_by_hop" else None),
        atac_counters=(mem.net.hub_counters()
                       if isinstance(getattr(mem, "net", None), _AtacNet)
                       else None),
    )
