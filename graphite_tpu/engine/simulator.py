"""Host-side simulation orchestration (the Simulator/MCP analog).

Reference: `common/system/simulator.{h,cc}` boots transport, managers, and
per-tile threads (`simulator.cc:83-133`); the MCP thread serves centralized
requests (`mcp.cc:59-146`); the lax-barrier loop synchronizes every quantum
(`lax_barrier_sync_client.cc:31-68`).  Here the Simulator builds the engine
parameters from the parsed config, owns the device state, and drives the
compiled quantum step in a host loop; everything the MCP did between quanta
(deadlock detection, stats sampling, shutdown) happens here.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from graphite_tpu.config.config_file import ConfigFile
from graphite_tpu.config.simconfig import SimConfig
from graphite_tpu.engine.state import DeviceTrace, SimState, init_state
from graphite_tpu.engine.step import EngineParams
from graphite_tpu.models.dvfs import module_freq_mhz
from graphite_tpu.models.network_atac import AtacState, atac_counters
from graphite_tpu.models.network_hop_by_hop import NocState, noc_counters
from graphite_tpu.models.network_user import UserNetworkParams
from graphite_tpu.obs.scopes import tagged
from graphite_tpu.obs.trace import NO_SPANS, RunSpans, SetupSpans, constructs
from graphite_tpu.time_types import cycles_to_ps, ns_to_ps, ps_to_ns
from graphite_tpu.trace.schema import STATIC_COST_KEYS, Op, TraceBatch

class DeadlockError(RuntimeError):
    pass


class MailboxOverflowError(RuntimeError):
    pass


@dataclasses.dataclass
class SimResults:
    """Final counters, mirroring the `sim.out` summary structure
    (`core_model.cc:90-115`, `tile.cc:105-123`)."""

    n_tiles: int
    completion_time_ps: int
    instruction_count: np.ndarray
    clock_ps: np.ndarray
    memory_stall_ps: np.ndarray
    execution_stall_ps: np.ndarray
    recv_instructions: np.ndarray
    recv_stall_ps: np.ndarray
    sync_instructions: np.ndarray
    sync_stall_ps: np.ndarray
    bp_correct: np.ndarray
    bp_incorrect: np.ndarray
    packets_sent: np.ndarray
    packets_received: np.ndarray
    total_packet_latency_ps: np.ndarray
    n_quanta: int
    # memory-subsystem counters (per-tile arrays), None when no memory model
    mem_counters: "dict | None" = None
    func_errors: int = 0
    # per-port event counters of the USER NoC's contention model
    # ({name: int64[n_tiles, 6]}, ports RIGHT LEFT UP DOWN SELF INJECT:
    # `models/network_hop_by_hop.NOC_COUNTERS`), None unless the user
    # network is emesh_hop_by_hop — the reference's router models keep
    # the same four (`router_model.h:15-79`)
    noc_counters: "dict | None" = None
    # per-hub event counters of the MEMORY network's ATAC hubs ({name:
    # int64[2 * n_clusters]}, send hubs then receive hubs:
    # `models/network_atac.ATAC_COUNTERS`, the same four), None unless
    # the memory network is atac
    atac_counters: "dict | None" = None
    # iocoom detailed stall breakdown (`iocoom_core_model.cc:64-77`),
    # None for the simple core model
    detailed_stalls: "dict | None" = None
    # device-recorded telemetry timeline (obs.Timeline) when the run was
    # built with a TelemetrySpec, else None.  Pure observability: a
    # telemetry-enabled run's other fields are bit-equal to its
    # telemetry=None twin (pinned in tests/test_telemetry.py)
    telemetry: "object | None" = None
    # device-recorded per-tile profile (obs.TileProfile) when the run
    # was built with a ProfileSpec, else None.  Same pure-observability
    # contract as telemetry (pinned in tests/test_profile.py)
    profile: "object | None" = None
    # device-recorded latency histograms (obs.Hist) when the run was
    # built with a HistSpec, else None.  Same pure-observability
    # contract (pinned in tests/test_hist.py)
    hist: "object | None" = None
    # per-tile energy in integer picojoules ({component: int64[n_tiles]},
    # `TileEnergyMonitor.tile_energy_j`'s components and "total"),
    # integrated interval by interval at the operating point in force
    # (power/accounting.py); None unless [general] enable_power_modeling
    energy_pj: "dict | None" = None
    # the per-tile V/f table a run ends with ({"freq_mhz", "voltage_mv":
    # int[n_tiles, n_domains], "errors": int64[n_tiles] rejected DVFS_SET
    # requests}); None unless the configuration has a [dvfs] section
    dvfs_counters: "dict | None" = None

    @property
    def total_instructions(self) -> int:
        return int(self.instruction_count.sum())

    def summary(self) -> str:
        """sim.out-style per-tile summary (`simulator.cc:152-170`)."""
        out = []
        out.append("Simulation Summary")
        out.append(f"Target Completion Time (in nanoseconds): "
                   f"{ps_to_ns(self.completion_time_ps)}")
        out.append(f"Total Instructions: {self.total_instructions}")
        for t in range(self.n_tiles):
            out.append(f"Tile {t} Summary:")
            out.append("  Core Summary:")
            out.append(f"    Total Instructions: {int(self.instruction_count[t])}")
            out.append("    Completion Time (in nanoseconds): "
                       f"{ps_to_ns(int(self.clock_ps[t]))}")
            out.append(f"    Synchronization Stalls: {int(self.sync_instructions[t])}")
            out.append(f"    Network Recv Stalls: {int(self.recv_instructions[t])}")
            out.append("    Stall Time Breakdown (in nanoseconds): ")
            out.append(f"      Memory: {ps_to_ns(int(self.memory_stall_ps[t]))}")
            out.append("      Execution Unit: "
                       f"{ps_to_ns(int(self.execution_stall_ps[t]))}")
            out.append("      Synchronization: "
                       f"{ps_to_ns(int(self.sync_stall_ps[t]))}")
            out.append("      Network Recv: "
                       f"{ps_to_ns(int(self.recv_stall_ps[t]))}")
            if self.detailed_stalls is not None:
                # `iocoom_core_model.cc:64-77` outputSummary
                ds = self.detailed_stalls
                out.append("    Detailed Stall Time Breakdown "
                           "(in nanoseconds): ")
                out.append(f"      Load Queue: "
                           f"{ps_to_ns(int(ds['load_queue'][t]))}")
                out.append(f"      Store Queue: "
                           f"{ps_to_ns(int(ds['store_queue'][t]))}")
                out.append(f"      L1-I Cache: "
                           f"{ps_to_ns(int(ds['l1icache'][t]))}")
                out.append(
                    "      L1-D Cache (Intra-Instruction): "
                    f"{ps_to_ns(int(ds['intra_ins_l1dcache'][t]))}")
                out.append(
                    "      L1-D Cache (Inter-Instruction): "
                    f"{ps_to_ns(int(ds['inter_ins_l1dcache'][t]))}")
                out.append(
                    "      Execution Unit (Intra-Instruction): "
                    f"{ps_to_ns(int(ds['intra_ins_execution_unit'][t]))}")
                out.append(
                    "      Execution Unit (Inter-Instruction): "
                    f"{ps_to_ns(int(ds['inter_ins_execution_unit'][t]))}")
            bp_total = int(self.bp_correct[t] + self.bp_incorrect[t])
            if bp_total:
                out.append("    Branch Predictor:")
                out.append(f"      Num Correct: {int(self.bp_correct[t])}")
                out.append(f"      Num Incorrect: {int(self.bp_incorrect[t])}")
            if self.mem_counters is not None:
                mc = self.mem_counters
                out.append("  Cache Summary:")
                out.append(f"    L1-I Misses: {int(mc['l1i_misses'][t])}")
                out.append(
                    "    L1-D Misses: "
                    f"{int(mc['l1d_read_misses'][t] + mc['l1d_write_misses'][t])}")
                out.append(f"    L2 Misses: {int(mc['l2_misses'][t])}")
                # miss-type breakdown (`cache.cc outputSummary`, populated
                # under `[l2_cache/<type>] track_miss_types`)
                if int(mc["l2_cold_misses"][t] + mc["l2_capacity_misses"][t]
                       + mc["l2_sharing_misses"][t]):
                    out.append(
                        f"      Cold Misses: {int(mc['l2_cold_misses'][t])}")
                    out.append("      Capacity Misses: "
                               f"{int(mc['l2_capacity_misses'][t])}")
                    out.append("      Sharing Misses: "
                               f"{int(mc['l2_sharing_misses'][t])}")
                # cache-line utilization (cache_line_utilization.h; under
                # `[l2_cache/<type>] track_cache_line_utilization`)
                if ("line_util_hist" in mc
                        and int(np.asarray(mc["line_util_hist"][t]).sum())):
                    hist = np.asarray(mc["line_util_hist"][t])
                    out.append("    Cache Line Utilization (L2):")
                    out.append("      Total Reads: "
                               f"{int(mc['line_util_reads'][t])}")
                    out.append("      Total Writes: "
                               f"{int(mc['line_util_writes'][t])}")
                    labels = ("0", "1", "2-3", "4-7", "8-15", "16-31",
                              "32-63", ">=64")
                    for lb, n in zip(labels, hist):
                        out.append(f"      Accesses {lb}: {int(n)}")
            out.append("  Network Summary (USER):")
            out.append(f"    Packets Sent: {int(self.packets_sent[t])}")
            out.append(f"    Packets Received: {int(self.packets_received[t])}")
            if self.packets_received[t]:
                avg = self.total_packet_latency_ps[t] / self.packets_received[t] / 1000
                out.append(f"    Average Packet Latency (in nanoseconds): {avg:.3f}")
            if self.noc_counters is not None:
                # per-port contention counters (`router_model.cc`
                # outputSummary analog), summed over the tile's six ports
                nc = self.noc_counters
                out.append(
                    f"    Port Requests: {int(nc['requests'][t].sum())}")
                out.append("    Port Utilization (in cycles): "
                           f"{int(nc['utilization_cycles'][t].sum())}")
                out.append("    Total Contention Delay (in cycles): "
                           f"{int(nc['delay_cycles'][t].sum())}")
                out.append("    Analytical Model Used: "
                           f"{int(nc['analytical_reads'][t].sum())}")
            if self.dvfs_counters is not None:
                # `dvfs_manager.cc` per-domain operating points
                dc = self.dvfs_counters
                out.append("  DVFS Summary:")
                for d in range(dc["freq_mhz"].shape[1]):
                    out.append(
                        f"    Domain {d}: "
                        f"{int(dc['freq_mhz'][t, d]) / 1000:g} GHz at "
                        f"{int(dc['voltage_mv'][t, d]) / 1000:g} V")
                out.append(
                    f"    Rejected Requests: {int(dc['errors'][t])}")
        if self.atac_counters is not None:
            # the memory network's optical hubs (`network_model_atac.cc`
            # outputSummary prints each on its hub's tile), a line a
            # cluster: send hub, receive hub
            ac = self.atac_counters
            nc = len(ac["requests"]) // 2
            out.append("ATAC Hub Summary (MEMORY), send hub / receive hub:")
            labels = (("Requests", "requests"),
                      ("Utilization (in cycles)", "utilization_cycles"),
                      ("Total Contention Delay (in cycles)", "delay_cycles"),
                      ("Analytical Model Used", "analytical_reads"))
            for c in range(nc):
                out.append(f"  Cluster {c}: " + ", ".join(
                    f"{label} {int(ac[k][c])} / {int(ac[k][nc + c])}"
                    for label, k in labels))
        if self.energy_pj is not None:
            from graphite_tpu.power.accounting import output_summary

            out.append(output_summary(self.energy_pj))
        return "\n".join(out)


def tree_bytes(tree) -> int:
    """Bytes of a pytree's arrays, from their shapes (no device sync)."""
    return sum(int(getattr(x, "nbytes", 0))
               for x in jax.tree_util.tree_leaves(tree))


def _mem_state_bytes(mp) -> int:
    """Rough HBM footprint of the protocol state: directory (dominant),
    cache meta words, and the [T, T] mailbox matrices."""
    T = mp.n_tiles
    dir_entry = mp.sharer_words * 4 + 8  # sharers words + packed word
    dir_bytes = T * mp.dir_sets * mp.dir_ways * dir_entry
    cache_bytes = 8 * T * (
        mp.l1i.num_sets * mp.l1i.num_ways
        + mp.l1d.num_sets * mp.l1d.num_ways
        + 2 * mp.l2.num_sets * mp.l2.num_ways)
    mail_bytes = 4 * T * T * 13
    return dir_bytes + cache_bytes + mail_bytes


def auto_mailbox_depth(batch: "TraceBatch") -> int:
    """Upper-bound the per-(dst, src) mailbox ring occupancy from the
    recorded trace, so no caller has to guess `mailbox_depth`: overflow
    is unreachable for recorded traces.

    The bound is barrier-phase aware: records are bucketed by the count
    of completed blocking barrier waits before them on their lane (the
    only cross-lane ordering a trace guarantees).  In any execution,
    messages in flight for a pair during epoch e cannot exceed the
    pair's sends through epoch e minus its receives completed in epochs
    strictly before e (later sends have not happened; earlier receives
    have).  ANY_SENDER receives cannot be credited to a pair, but they
    do bound the total into their destination, so each pair also takes
    the destination-wide bound.  Epochs only order lanes when every
    lane passes the same sequence of GLOBAL barriers, so barrier credit
    applies only when one barrier id is waited on, its declared
    participant count covers all tiles, and every lane waits equally
    often; anything else (including no barriers) collapses to one epoch
    — the exact worst case, every send of the pair outstanding at once.
    The engine's fail-stop `MailboxOverflowError` remains the backstop.
    """
    from graphite_tpu.trace.schema import Op

    op = np.asarray(batch.op)
    aux0 = np.asarray(batch.aux0)
    aux1 = np.asarray(batch.aux1)
    T, L = op.shape
    send_mask = op == int(Op.SEND)
    if L == 0 or not send_mask.any():
        return 2
    recv_mask = op == int(Op.NET_RECV)

    is_bar = (op == int(Op.BARRIER_WAIT)) | (op == int(Op.BARRIER_SYNC))
    bar_global = False
    if is_bar.any():
        bar_ids = np.unique(aux0[is_bar])
        per_lane = is_bar.sum(axis=1)
        init_mask = op == int(Op.BARRIER_INIT)
        counts = np.unique(aux1[init_mask & np.isin(aux0, bar_ids)])
        bar_global = (
            len(bar_ids) == 1
            and (per_lane == per_lane[0]).all() and per_lane[0] > 0
            and len(counts) > 0 and (counts >= T).all())
    if bar_global:
        epoch = np.cumsum(is_bar, axis=1) - is_bar   # exclusive prefix
        E = int(epoch.max()) + 1
    else:
        epoch = np.zeros((T, L), np.int64)
        E = 1
    lanes = np.broadcast_to(np.arange(T)[:, None], (T, L))

    s_src = lanes[send_mask]
    s_dst = np.clip(aux0[send_mask], 0, T - 1)
    s_e = epoch[send_mask]
    r_dst = lanes[recv_mask]
    r_src = aux0[recv_mask]                          # -1 = ANY_SENDER
    r_e = epoch[recv_mask]

    # per-destination bound (all sources vs all receives at d)
    dst_sends = np.zeros((T, E), np.int64)
    np.add.at(dst_sends, (s_dst, s_e), 1)
    dst_recvs = np.zeros((T, E), np.int64)
    np.add.at(dst_recvs, (r_dst, r_e), 1)
    dst_s_cum = np.cumsum(dst_sends, axis=1)
    dst_r_cum_prev = np.concatenate(
        [np.zeros((T, 1), np.int64), np.cumsum(dst_recvs, axis=1)[:, :-1]],
        axis=1)
    dst_bound = (dst_s_cum - dst_r_cum_prev).max(axis=1)   # [T]

    # per-pair bound over the pairs that actually send
    pair_ids = s_src.astype(np.int64) * T + s_dst
    pairs, pair_idx = np.unique(pair_ids, return_inverse=True)
    P = len(pairs)
    pair_sends = np.zeros((P, E), np.int64)
    np.add.at(pair_sends, (pair_idx, s_e), 1)
    pair_recvs = np.zeros((P, E), np.int64)
    specific = r_src >= 0
    rp_ids = r_src[specific].astype(np.int64) * T + r_dst[specific]
    rp_pos = np.searchsorted(pairs, rp_ids)
    in_range = rp_pos < P
    rp_match = np.zeros_like(rp_ids, bool)
    rp_match[in_range] = pairs[rp_pos[in_range]] == rp_ids[in_range]
    np.add.at(pair_recvs, (rp_pos[rp_match], r_e[specific][rp_match]), 1)
    pair_s_cum = np.cumsum(pair_sends, axis=1)
    pair_r_cum_prev = np.concatenate(
        [np.zeros((P, 1), np.int64), np.cumsum(pair_recvs, axis=1)[:, :-1]],
        axis=1)
    pair_bound = (pair_s_cum - pair_r_cum_prev).max(axis=1)
    bound = np.minimum(pair_bound, dst_bound[pairs % T]).max()
    # Unphased send streams (no barriers between rounds) degenerate to
    # the total-sends-per-pair worst case; a [T, T, total] ring would
    # dwarf the real occupancy (recv interlock keeps it small), so cap
    # the automatic size — the engine's overflow fail-stop still guards
    # the cap, and the explicit knob remains for genuinely deep traffic.
    return int(np.clip(bound, 2, 64))


def mem_phase_names(params: EngineParams) -> tuple:
    """The memory engine's protocol-phase names, in the skip-vector's
    order (one source of truth for skip-counter labeling — Simulator's
    last_phase_skips and the sweep runner's per-sim demux)."""
    if params.mem.protocol.startswith("pr_l1_sh_l2"):
        from graphite_tpu.memory.engine_shl2 import SHL2_PHASE_NAMES
        return SHL2_PHASE_NAMES
    from graphite_tpu.memory.engine import PHASE_NAMES
    return PHASE_NAMES


# run_streamed's default [T, W] window length — also the window bound
# residency_breakdown prices for a streaming sim, so the two stay one
# number.
STREAM_WINDOW_RECORDS = 4096

_STREAM_RUNNERS: dict = {}
# Each cached wrapper pins a compiled executable (tens of MB of device
# program + host tracing caches); long-lived processes sweeping many
# configs would otherwise grow without bound.
_STREAM_RUNNERS_MAX = 8


def _streamed_runner(params: EngineParams, quantum_ps, max_quanta: int,
                     mesh=None, spmd=None, state_ex=None, window_ex=None):
    """One jitted streamed-run wrapper per (params, quantum, max_quanta,
    mesh program): identical configs share a wrapper, so a warmup run on
    one Simulator instance warms the executable every other instance
    uses.  LRU-bounded at _STREAM_RUNNERS_MAX entries."""
    key = (params, quantum_ps, int(max_quanta), mesh, spmd)
    fn = _STREAM_RUNNERS.get(key)
    if fn is not None:
        # LRU refresh (dicts preserve insertion order)
        del _STREAM_RUNNERS[key]
        _STREAM_RUNNERS[key] = fn
    if fn is None:
        if spmd == "shard_map":
            from graphite_tpu.parallel.mesh import make_shard_map_runner

            fn = make_shard_map_runner(
                params, quantum_ps, max_quanta, mesh, state_ex, window_ex,
                streamed=True)
        else:
            from graphite_tpu.engine.step import run_simulation

            fn = jax.jit(
                lambda st, tr, base: run_simulation(
                    params, tr, st, quantum_ps, max_quanta, trace_base=base))
        while len(_STREAM_RUNNERS) >= _STREAM_RUNNERS_MAX:
            _STREAM_RUNNERS.pop(next(iter(_STREAM_RUNNERS)))
        _STREAM_RUNNERS[key] = fn
    return fn


class Simulator:
    """Builds engine parameters from a SimConfig and runs a trace batch."""

    @constructs
    def __init__(
        self,
        config: SimConfig | ConfigFile | str,
        trace: TraceBatch,
        *,
        mailbox_depth: int | None = None,
        inner_block: int = 32,
        bp_size: int | None = None,
        n_barriers: int = 64,
        n_mutexes: int = 64,
        n_conds: int = 64,
        mesh=None,
        stream: bool = False,
        spmd: str | None = None,
        donate: bool = False,
        dir_stage: bool | None = None,
        barrier_host: bool | None = None,
        phase_gate: bool | None = None,
        mem_gate_bytes: int | None = None,
        barrier_batch: int | None = None,
        telemetry=None,
        profile=None,
        dvfs=None,
        hist=None,
        tracer=None,
    ):
        """`tracer`: an `obs.Tracer`, attached from the start (as
        `attach_tracer` would): construction and `warmup()` record their
        set-up spans (obs/trace.py: SETUP_SPANS) in it instead of the
        process-wide `obs.trace.SETUP`, each also a `gt:<name>`
        annotation, and a mesh placement then ends in one
        `block_until_ready`.  Host side only, like every tracer.

        `dir_stage`: force the directory write-staging path on/off
        (None = auto: on for single-device private-L2 runs whose sharers
        store is >= 64 MB — the regime where XLA's dense scatter lowering
        dominates; see MemParams.dir_stage_cap).

        `spmd` (mesh runs only): "shard_map" — the packed-exchange
        multi-chip program (parallel/px.py; the default for every
        protocol) — or "gspmd" — whole-program partitioning via
        sharding specs (the legacy path).

        `phase_gate`: per-phase activity gating of the memory engines —
        each protocol phase under its own scalar-predicate lax.cond
        carrying only small per-phase state, so quiet phases cost ~zero
        at EVERY scale including the >= 1 GB directories where the
        whole-engine mem_gate must stay off (MemParams.phase_gate).
        None = on whenever the memory subsystem is built; False is the
        escape hatch back to the straight-line engine.  Config key:
        `[general] phase_gate`.

        `mem_gate_bytes`: the whole-engine mem_gate's state-size ceiling
        (the gate's lax.cond double-buffers the carried memory state, so
        it auto-disables above this; formerly a hard-coded 1 << 30).
        Config key: `[general] mem_gate_bytes`.

        `barrier_batch`: quanta per host dispatch under `barrier_host`
        (a bounded device-side while_loop that early-exits on
        host-visible work — done/overflow/deadlock — amortizing the
        host round trip per dispatch ~K x;
        `engine/step.barrier_host_batch`).
        1 restores the per-quantum dispatch.  Config key:
        `[general] barrier_batch` (default 8).

        `telemetry`: an `obs.TelemetrySpec` to record a device-resident
        metric timeline inside the compiled loop (sampled on
        `sample_interval_ps` simulated-time boundaries, zero host sync;
        read back post-run via `Simulator.telemetry` /
        `SimResults.telemetry`).  None — the default — lowers a
        bit-identical program (the knobs=None contract).

        `profile`: an `obs.ProfileSpec` to record the device-resident
        PER-TILE profile ring ([S, T, m], sampled on the same
        simulated-time boundaries as telemetry; read back via
        `Simulator.profile` / `SimResults.profile`).  Same None
        bit-identity contract, enforced by the `profile-off` lint.

        `dvfs`: a `dvfs.DvfsSpec` attaching the runtime DVFS manager —
        the chip-global per-domain operating point rides the carry
        (`SimState.dvfs_rt`), in-trace DVFS_SET events and the optional
        governor retune it, and the memory/network timing conversions
        read the carried frequencies.  Same None bit-identity contract,
        enforced by the `dvfs-off` lint.

        `donate=True` gives the input state's device buffers to XLA each
        run (halves big-state HBM residency — required for the 1024-tile
        full-directory coherence runs, PERF.md); the pre-run state object
        becomes unusable, so warmup()/state-restoring repeat patterns
        must keep the default."""
        if isinstance(config, str):
            config = ConfigFile.from_file(config)
        if isinstance(config, ConfigFile):
            config = SimConfig(config)
        self.config = config
        cfg = config.cfg
        self.trace_batch = trace
        n_tiles = trace.n_tiles
        if n_tiles != config.application_tiles:
            raise ValueError(
                f"trace has {n_tiles} tiles but config expects "
                f"{config.application_tiles} application tiles"
            )
        if mailbox_depth is None:
            # size the [T, T, D] rings from the trace itself (barrier-
            # phase-aware in-flight bound); overflow stays a fail-stop
            mailbox_depth = auto_mailbox_depth(trace)
        costs = tuple(
            cfg.get_int(f"core/static_instruction_costs/{k}", 0)
            for k in STATIC_COST_KEYS
        )
        bp_type = cfg.get_string("branch_predictor/type", "one_bit")

        # Memory subsystem: built when shared memory is enabled AND the
        # trace actually touches memory (`general/enable_shared_mem`,
        # `carbon_sim.cfg:40-44`; protocol factory `memory_manager.cc:31-48`).
        from graphite_tpu.trace.schema import FLAG_MEM0_VALID, FLAG_MEM1_VALID

        has_mem = bool(
            np.any(trace.flags & (FLAG_MEM0_VALID | FLAG_MEM1_VALID))
        ) or cfg.get_bool("general/enable_icache_modeling", False)
        # dynamic records (op 15-19) commit without waiting on memory
        # completion, so memory flags on them would leave slot machinery
        # dangling into the next record (and diverge from the golden
        # oracle, which gives dynamic ops no memory slots) — reject the
        # combination outright; no builder emits it
        dyn_mem = np.any(
            (trace.op >= 15) & (trace.op < 20)
            & ((trace.flags & (FLAG_MEM0_VALID | FLAG_MEM1_VALID)) != 0))
        if bool(dyn_mem):
            raise ValueError(
                "dynamic trace records (ops 15-19) must not carry "
                "FLAG_MEM*_VALID memory operands")
        if dir_stage and not (config.enable_shared_mem and has_mem):
            raise ValueError(
                "dir_stage=True needs the memory subsystem (shared mem "
                "enabled and a memory-carrying trace)")
        mem_params = None
        if config.enable_shared_mem and has_mem:
            from graphite_tpu.memory import MemParams

            mem_params = MemParams.from_config(config)
            supported = ("pr_l1_pr_l2_dram_directory_msi",
                         "pr_l1_pr_l2_dram_directory_mosi",
                         "pr_l1_sh_l2_msi", "pr_l1_sh_l2_mesi")
            if mem_params.protocol not in supported:
                raise NotImplementedError(
                    f"caching protocol {mem_params.protocol!r} pending "
                    f"(available: {', '.join(supported)})"
                )
            # Directory write-staging (MemParams.dir_stage_cap): lifts
            # the coherence-storm floor — XLA lowers per-lane scatters on
            # the big sharers store as full-array dense passes, so big
            # directories stage writes and flush once per inner block
            # (PERF.md round-5).  Private-L2 protocols only.  Auto-on
            # stays conservative: single-device programs whose sharers
            # store alone is >= 64 MB.  Meshed runs stage on EXPLICIT
            # dir_stage=True (the per-lane rows shard with the
            # directory).
            private_l2 = mem_params.protocol.startswith("pr_l1_pr_l2")
            sharers_bytes = (4 * n_tiles * mem_params.dir_sets
                             * mem_params.dir_ways
                             * mem_params.sharer_words)
            if dir_stage is None:
                dir_stage = (private_l2 and mesh is None
                             and sharers_bytes >= 64 << 20)
            if dir_stage:
                if not private_l2:
                    # Not "pending work": the shared-L2 engines don't
                    # NEED staging.  Their embedded directory (round-5
                    # packed words + set-row-major sharer rows) is
                    # written as ONE add-a-delta row scatter per phase,
                    # not the private engine's three per-lane
                    # entry-granular passes that staging amortizes — so
                    # there is no dense-scatter storm to lift.
                    raise ValueError(
                        "dir_stage applies to the private-L2 directory "
                        "protocols only: the shared-L2 engines' embedded "
                        "directory already writes one row-form scatter "
                        "per phase (no per-entry dense-pass storm to "
                        "stage away), so staging would add table scans "
                        "for nothing")
                wpi = (5 if mem_params.dir_type == "limited_no_broadcast"
                       else 3)
                # per-LANE capacity: each home stages at most
                # writes_per_iter entries per iteration
                mem_params = dataclasses.replace(
                    mem_params,
                    dir_stage_cap=wpi * inner_block)
            # Per-phase activity gating (round 6): on by default for
            # every memory-engine program — the per-phase conds carry
            # only small state (see MemParams.phase_gate), so unlike the
            # whole-engine mem_gate there is no size ceiling; predicates
            # are replicated-deterministic, so sharded programs gate
            # identically on every device.
            if phase_gate is None:
                phase_gate = cfg.get_bool("general/phase_gate", True)
            if phase_gate:
                mem_params = dataclasses.replace(mem_params,
                                                 phase_gate=True)
        # Full hop-by-hop USER NoC with per-port contention
        user_hbh = None
        user_atac = None
        if config.network_types[0] == "emesh_hop_by_hop":
            from graphite_tpu.models.network_hop_by_hop import HopByHopParams

            user_hbh = HopByHopParams.from_config(config, "user")
        elif config.network_types[0] == "atac":
            from graphite_tpu.models.network_atac import AtacParams

            user_atac = AtacParams.from_config(config, "user")
        iocoom_params = None
        # Per-tile core models (`[tile] model_list` heterogeneity,
        # `config.cc:365-472`): iocoom tiles run the pipeline algebra, the
        # rest the simple 1-IPC path, mixed freely within one mesh
        core_types = [config.tile_spec(t).core_type for t in range(n_tiles)]
        unknown = {t for t in core_types
                   if t not in ("iocoom", "simple", "default", "magic")}
        if unknown:
            raise NotImplementedError(f"core model(s) {sorted(unknown)!r}")
        iocoom_tiles = None
        if "iocoom" in core_types:
            from graphite_tpu.models.iocoom import IocoomParams

            iocoom_params = IocoomParams.from_config(cfg)
            if any(t != "iocoom" for t in core_types):
                iocoom_tiles = tuple(t == "iocoom" for t in core_types)
        from graphite_tpu.models.dvfs import DvfsParams

        dvfs_params = DvfsParams.from_config(cfg)
        # energy as a statistic of the run, under the reference's key
        energy_params = None
        if config.enable_power_modeling:
            from graphite_tpu.power.accounting import EnergyParams

            if mesh is not None:
                raise NotImplementedError(
                    "[general] enable_power_modeling on a device mesh: "
                    "the energy accumulators have no shard spec yet")
            energy_params = EnergyParams.from_config(
                config, dvfs_params, mem_params)
        # the V/f table is reported only where the configuration speaks
        # of DVFS itself
        self._report_dvfs = cfg.has_section("dvfs")
        self.params = EngineParams(
            n_tiles=n_tiles,
            static_cost_cycles=costs,
            net=UserNetworkParams.from_config(config, "user"),
            bp_enabled=(bp_type != "none"),
            bp_size=bp_size or cfg.get_int("branch_predictor/size", 1024),
            bp_mispredict_penalty=cfg.get_int(
                "branch_predictor/mispredict_penalty", 14
            ),
            mailbox_depth=mailbox_depth,
            inner_block=inner_block,
            n_conds=n_conds,
            # SYSTEM network is always magic (`config.cc:484`) and outside
            # the DVFS domain map (only NETWORK_USER/NETWORK_MEMORY are
            # tunable modules): 1 cycle each way to the MCP at 1 GHz
            syscall_rt_ps=int(cycles_to_ps(2, 1000)),
            iocoom=iocoom_params,
            iocoom_tiles=iocoom_tiles,
            dvfs=dvfs_params,
            energy=energy_params,
            mem=mem_params,
            user_hbh=user_hbh,
            user_atac=user_atac,
            # the engine gate's lax.cond double-buffers the memory state in
            # HBM; keep it only while the duplicate comfortably fits (the
            # directory sharer maps grow as tiles^2 x dir entries).  Above
            # the (config-driven) ceiling the per-phase gating inside the
            # engine takes over — its conds carry only small state, so it
            # has no such ceiling (MemParams.phase_gate).
            mem_gate=(mem_params is None
                      or _mem_state_bytes(mem_params)
                      < self._resolve_mem_gate_bytes(cfg, mem_gate_bytes)),
            # runtime BBLOCK compression for per-instruction streams
            # (simple-core memoryless runs; bit-exact by construction —
            # engine/step.py plain-run batching)
            # 16 measured best on the 1024-tile per-instruction streamed
            # ring (8: 1.06M, 16: 1.76M, 32: 0.79M instr/s — PERF.md);
            # configs above the measured-safe ceiling are clamped + warned
            plain_unroll=self._resolve_plain_unroll(
                cfg, mem_params, iocoom_params),
        )
        # Clock-skew scheme (`carbon_sim.cfg:85-108`): lax_barrier uses the
        # config quantum; lax runs one unbounded quantum; lax_p2p runs
        # unbounded quanta with per-iteration random pairwise clamping
        # (`lax_p2p_sync_client.h:13-83`) applied inside the step.
        scheme = cfg.get_string("clock_skew_management/scheme", "lax_barrier")
        self.p2p_slack_ps = None
        if scheme == "lax_barrier":
            self.quantum_ps = ns_to_ps(
                cfg.get_int("clock_skew_management/lax_barrier/quantum", 1000)
            )
        elif scheme == "lax_p2p":
            self.quantum_ps = None
            self.p2p_slack_ps = ns_to_ps(
                cfg.get_int("clock_skew_management/lax_p2p/slack", 1000)
            )
        else:
            self.quantum_ps = None  # lax: unbounded
        # Host-driven lax_barrier quanta: at 1024 tiles with the memory
        # engine and a SEND-carrying trace the Simulator drives the
        # barrier loop host-side — a bounded region per dispatch (no
        # outer while_loop, qend as an argument,
        # `engine/step.barrier_host_batch`) with identical quantum
        # semantics (`lax_barrier_sync_server.h:12-36`).  The rule dates
        # from a machine on which the single-region program could not be
        # built at this size.  On the TPU v5e with the installed
        # compiler BOTH variants compile, at the same footprint (2.45 GB
        # arguments, 1.34 GB temp), both run the 1024-tile
        # full-directory FFT in 5.4 GB of HBM, and their statistics
        # agree bit for bit (PERF.md, PR 25).  The rule is kept as it
        # was; ROADMAP Queue 3 (D1b) carries the follow-up to delete
        # this auto-selection.  Override via barrier_host.
        if barrier_host is None:
            from graphite_tpu.trace.schema import Op as _Op

            barrier_host = (self.quantum_ps is not None
                            and mem_params is not None
                            and n_tiles >= 1024
                            and bool(np.any(trace.op == int(_Op.SEND)))
                            and mesh is None and not stream)
        if barrier_host and self.quantum_ps is None:
            raise ValueError(
                "barrier_host=True needs the lax_barrier clock scheme "
                "(there are no quanta to drive host-side otherwise)")
        self.barrier_host = bool(barrier_host)
        if self.barrier_host and (mesh is not None or stream):
            raise ValueError(
                "host-driven lax_barrier quanta support single-device "
                "resident runs only")
        # quanta per host dispatch under barrier_host (the batched
        # device-side loop; 1 = the legacy per-quantum dispatch)
        if barrier_batch is None:
            barrier_batch = cfg.get_int("general/barrier_batch", 8)
        if barrier_batch < 1:
            raise ValueError("barrier_batch must be >= 1")
        self.barrier_batch = int(barrier_batch)
        if self.p2p_slack_ps is not None:
            self.params = dataclasses.replace(
                self.params, p2p_slack_ps=self.p2p_slack_ps)

        models_on = not cfg.get_bool(
            "general/trigger_models_within_application", False
        )
        core_freq = module_freq_mhz(cfg, "CORE")
        span = SetupSpans(tracer)
        with span("init_state") as made:
            self.state: SimState = init_state(
                n_tiles,
                core_freq_mhz=core_freq,
                bp_size=self.params.bp_size,
                mailbox_depth=mailbox_depth,
                n_barriers=n_barriers,
                n_mutexes=n_mutexes,
                n_conds=n_conds,
                models_enabled=models_on,
            )
            if mem_params is not None:
                from graphite_tpu.memory import init_mem_state

                if mem_params.protocol.startswith("pr_l1_sh_l2"):
                    from graphite_tpu.memory.engine_shl2 import (
                        init_shl2_state,
                    )

                    self.state = self.state.replace(
                        mem=init_shl2_state(mem_params))
                else:
                    self.state = self.state.replace(
                        mem=init_mem_state(mem_params))
                if mem_params.net_hbh is not None:
                    # per-port queue state of the MEMORY NoC (`[network]
                    # memory = emesh_hop_by_hop`) — coherence messages
                    # route through it with per-hop contention
                    # (mem_net_send)
                    from graphite_tpu.models.network_hop_by_hop import (
                        init_noc_state,
                    )

                    self.state = self.state.replace(
                        mem=self.state.mem.replace(
                            noc=init_noc_state(mem_params.net_hbh)))
                elif mem_params.net_atac is not None:
                    # ATAC hub-queue state of the MEMORY NoC (`[network]
                    # memory = atac`) — coherence messages route over
                    # the clusters/hubs/waveguide with hub contention
                    from graphite_tpu.models.network_atac import (
                        init_atac_state,
                    )

                    self.state = self.state.replace(
                        mem=self.state.mem.replace(
                            noc=init_atac_state(mem_params.net_atac)))
            if user_hbh is not None:
                from graphite_tpu.models.network_hop_by_hop import (
                    init_noc_state,
                )

                self.state = self.state.replace(
                    noc_user=init_noc_state(user_hbh))
            if user_atac is not None:
                from graphite_tpu.models.network_atac import init_atac_state

                self.state = self.state.replace(
                    noc_user=init_atac_state(user_atac))
            if iocoom_params is not None:
                from graphite_tpu.models.iocoom import init_iocoom_state

                self.state = self.state.replace(
                    ioc=init_iocoom_state(n_tiles, iocoom_params))
            from graphite_tpu.engine.state import DvfsState

            nd = dvfs_params.n_domains
            init_freqs = jnp.broadcast_to(
                jnp.asarray(dvfs_params.domain_freq_mhz,
                            jnp.int32)[None, :],
                (n_tiles, nd)).copy()
            init_volts = jnp.asarray(
                [dvfs_params.min_voltage_mv(f)
                 for f in dvfs_params.domain_freq_mhz], jnp.int32)
            self.state = self.state.replace(dvfs=DvfsState(
                freq_mhz=init_freqs,
                voltage_mv=jnp.broadcast_to(
                    init_volts[None, :], (n_tiles, nd)).copy(),
                errors=jnp.zeros(n_tiles, jnp.int64),
            ))
            if energy_params is not None:
                from graphite_tpu.engine.state import EnergyState

                self.state = self.state.replace(energy=EnergyState(
                    acc=jnp.zeros(
                        (n_tiles, len(energy_params.columns)), jnp.int64),
                    last_raw=jnp.zeros(
                        (n_tiles, len(energy_params.raw)), jnp.int64),
                    last_clock_ps=jnp.zeros(n_tiles, jnp.int64),
                ))
            made.attrs["bytes"] = tree_bytes(self.state)
        # streaming mode keeps the trace host-side; run_streamed() uploads
        # [T, W] windows on demand (bounded HBM regardless of trace size)
        self.stream = bool(stream)
        self.mesh = mesh
        # Multi-chip program selection: the packed shard_map exchange is
        # the default for EVERY protocol (one collective per engine
        # phase; PERF.md) — the reference's process striping serves
        # every protocol equally.  spmd='gspmd' keeps the legacy
        # whole-program-partitioning path.
        if spmd not in (None, "shard_map", "gspmd"):
            raise ValueError(f"unknown spmd program {spmd!r} "
                             "(expected 'shard_map' or 'gspmd')")
        if mesh is not None and spmd is None:
            spmd = "shard_map"
        self.spmd = spmd if mesh is not None else None
        self.device_trace = None
        if not stream:
            with span("encode_trace") as made:
                self.device_trace = DeviceTrace.from_batch(trace)
                made.attrs["bytes"] = tree_bytes(self.device_trace)
        if mesh is not None:
            with span("place", devices=mesh.size) as made:
                # Shard the tile axis over the device mesh (SURVEY §2.10):
                # the TPU-native form of Graphite's process striping.
                # Streamed runs shard the state here and each [T, W]
                # window at upload (run_streamed) — the two scale
                # mechanisms compose: bounded-HBM traces on a multi-chip
                # mesh.
                if self.spmd == "shard_map":
                    from graphite_tpu.parallel.mesh import place_shard_map

                    if stream:
                        self.state = place_shard_map(self.state, mesh)
                    else:
                        self.state, self.device_trace = place_shard_map(
                            self.state, mesh, self.device_trace)
                else:
                    from graphite_tpu.parallel.mesh import (
                        shard_sim, shard_state,
                    )

                    if stream:
                        self.state = shard_state(self.state, mesh)
                    else:
                        self.state, self.device_trace = shard_sim(
                            self.state, self.device_trace, mesh
                        )
                if span.on:
                    # the tracer's one sync: what the devices still owed
                    jax.block_until_ready((self.state, self.device_trace))
                made.attrs["bytes"] = tree_bytes(
                    (self.state, self.device_trace))
        self.donate = bool(donate)
        # subquantum iterations executed by the last run (device loop
        # observability: wall / iterations = the engine's per-iteration
        # cost, the number PERF.md's floor analysis tracks), and those of
        # them in which nothing advanced: one a quantum, which is how
        # the quantum loop learns that the quantum is over
        # (engine/step._quantum_loop)
        self.last_n_iterations = 0
        self.last_idle_iterations = 0
        # launch counters (plain ints, always on): programs the drive loop
        # launched — by the last COMPLETED run() (either path; run_chunk
        # and warmup leave it alone), and by every run / run_chunk /
        # run_streamed of this instance so far
        self.last_run_dispatches = 0
        self.n_dispatches = 0
        # host span tracing of the drive loop (`tracer=`, attach_tracer);
        # None runs the loop with no span, no annotation and no extra
        # device sync
        self.tracer = tracer
        self._runner = None
        self._runner_max_quanta = None
        self._hb_runner = None
        # lower-once plumbing (round 11): audit, cost and fingerprint
        # all consume one lowering per (program, max_quanta) instead of
        # re-tracing per consumer; `lower_count` is the trace-count
        # probe the identity tests pin.  `lower_gen` counts program-
        # identity mutations (attach_telemetry) so wrappers holding
        # their own lowering caches (SweepRunner) can invalidate too.
        self._lowered = {}
        self.lower_count = 0
        self.lower_gen = 0
        # device-resident telemetry timeline (graphite_tpu/obs): resolve
        # the spec against this program's series set and seed the ring
        # into the state carry; None records nothing and lowers the
        # historical program bit-identically
        self.telemetry_spec = None
        # device-resident per-tile profile ring (graphite_tpu/obs/
        # profile.py): same attach/resolve/None-contract as telemetry
        self.profile_spec = None
        # runtime DVFS manager (graphite_tpu/dvfs): same attach/resolve/
        # None-contract — None carries no DvfsRtState leaves
        self.dvfs_spec = None
        # device-resident latency histograms (graphite_tpu/obs/hist.py):
        # same attach/resolve/None-contract as telemetry/profile
        self.hist_spec = None
        if telemetry is not None:
            self.attach_telemetry(telemetry)
        if profile is not None:
            self.attach_profile(profile)
        if dvfs is not None:
            self.attach_dvfs(dvfs)
        if hist is not None:
            self.attach_hist(hist)

    def attach_tracer(self, tracer) -> None:
        """Attach (or, with None, detach) an `obs.Tracer`: every later
        `run()` / `run_chunk()` / `run_streamed()` records one `run-<n>`
        trace (or the caller's `trace_id`) of `obs.trace.RUN_SPANS`,
        each also a `jax.profiler.TraceAnnotation("gt:<name>")`, and a
        later `warmup()` its set-up spans.  Host
        side only: no program changes and results are bit-equal.  With
        a tracer the loop adds ONE `block_until_ready` per dispatch (the
        `wait` span), so that waiting for the device is told apart from
        copying its results."""
        self.tracer = tracer

    def _spans(self, trace_id=None):
        if self.tracer is None:
            return NO_SPANS
        return RunSpans(self.tracer, trace_id)

    def compiled_text(self, max_quanta: int = 1_000_000) -> str:
        """Optimized HLO text of the program `run()` dispatches: each
        instruction with the `op_name` path (`obs/scopes.py` names) it
        was compiled with.  After `warmup()` or a run this re-reads the
        jit's own executable (no compile).  Single-device and GSPMD
        programs only."""
        if self.spmd == "shard_map":
            raise ValueError("compiled_text() does not cover shard_map "
                             "runners")
        if self.barrier_host:
            lowered = self._hb_get_runner().lower(
                self.state, jnp.asarray(0, jnp.int64),
                jnp.asarray(1, jnp.int32))
        else:
            lowered = self._get_runner(max_quanta).lower(self.state)
        return lowered.compile().as_text()

    def attach_telemetry(self, spec) -> None:
        """Attach (or replace) a telemetry spec on a not-yet-run
        instance: resolves the series selection against this program,
        seeds the ring buffer into the state carry, and invalidates any
        compiled runner (the spec is baked into the lowering).  Used by
        `StatisticsManager`'s device backend to upgrade a plain sim."""
        from graphite_tpu.obs.telemetry import TelemetrySpec, init_telemetry

        if not isinstance(spec, TelemetrySpec):
            raise TypeError("telemetry must be an obs.TelemetrySpec")
        spec = spec.resolve(self.params)
        if self.mesh is not None or self.stream:
            # the ONE residency-refusal exception type (analysis/cost.py):
            # the message carries the analyzer's per-consumer breakdown so
            # the caller sees exactly what the refused layout would cost
            from graphite_tpu.analysis.cost import (
                ResidencyBudgetError, format_breakdown,
            )

            raise ResidencyBudgetError(
                "telemetry timelines support single-device resident runs "
                "and batched sweeps only (the ring is not threaded "
                "through the Simulator's own multi-chip exchange or the "
                "streaming window loop).  For a multi-device run, serve "
                "the sim as a campaign under SweepRunner's 2D "
                "batch x tile layout (layout='tile'/'2d'), which records "
                "the ring replicated per batch cell and splits the "
                "residency bill into per-device tile blocks — or use "
                "the chunked StatisticsManager backend.  Refused "
                "residency: "
                + format_breakdown(self.residency_breakdown(spec)))
        self.telemetry_spec = spec
        self.state = self.state.replace(telemetry=init_telemetry(spec))
        self._runner = None
        self._runner_max_quanta = None
        self._hb_runner = None
        self._lowered = {}   # the spec is baked into the lowering too
        self.lower_gen += 1

    def attach_profile(self, spec) -> None:
        """Attach (or replace) a per-tile profile spec on a not-yet-run
        instance: resolves the series selection against this program,
        seeds the [S, T, m] ring into the state carry, and invalidates
        any compiled runner (the spec is baked into the lowering) —
        the spatial-profiler twin of `attach_telemetry`."""
        from graphite_tpu.obs.profile import ProfileSpec, init_profile

        if not isinstance(spec, ProfileSpec):
            raise TypeError("profile must be an obs.ProfileSpec")
        spec = spec.resolve(self.params)
        if self.mesh is not None or self.stream:
            from graphite_tpu.analysis.cost import (
                ResidencyBudgetError, format_breakdown,
            )

            raise ResidencyBudgetError(
                "per-tile profile rings support single-device resident "
                "runs and batched sweeps only (the ring is not threaded "
                "through the Simulator's own multi-chip exchange or the "
                "streaming window loop).  For a multi-device run, serve "
                "the sim as a campaign under SweepRunner's 2D "
                "batch x tile layout (layout='tile'/'2d'): the "
                "[S, T, m] ring's tile axis shards with the directory "
                "and reassembles on fetch, so each device holds only "
                "its tile block of the ring.  Refused residency: "
                + format_breakdown(
                    self.residency_breakdown(profile_spec=spec)))
        self.profile_spec = spec
        self.state = self.state.replace(profile=init_profile(spec))
        self._runner = None
        self._runner_max_quanta = None
        self._hb_runner = None
        self._lowered = {}   # the spec is baked into the lowering too
        self.lower_gen += 1

    def attach_hist(self, spec) -> None:
        """Attach (or replace) a latency-histogram spec on a
        not-yet-run instance: resolves the source selection against
        this program, seeds the bucket-count ring into the state carry,
        and invalidates any compiled runner (the spec is baked into the
        lowering) — the distribution twin of `attach_profile`."""
        from graphite_tpu.obs.hist import HistSpec, init_hist

        if not isinstance(spec, HistSpec):
            raise TypeError("hist must be an obs.HistSpec")
        spec = spec.resolve(self.params)
        if self.mesh is not None or self.stream:
            from graphite_tpu.analysis.cost import (
                ResidencyBudgetError, format_breakdown,
            )

            raise ResidencyBudgetError(
                "latency histograms support single-device resident "
                "runs and batched sweeps only (the ring is not threaded "
                "through the Simulator's own multi-chip exchange or the "
                "streaming window loop).  For a multi-device run, serve "
                "the sim as a campaign under SweepRunner's 2D "
                "batch x tile layout (layout='tile'/'2d'): a per-tile "
                "ring's tile axis shards with the directory and "
                "reassembles on fetch.  Refused residency: "
                + format_breakdown(
                    self.residency_breakdown(hist_spec=spec)))
        self.hist_spec = spec
        self.state = self.state.replace(hist=init_hist(spec))
        self._runner = None
        self._runner_max_quanta = None
        self._hb_runner = None
        self._lowered = {}   # the spec is baked into the lowering too
        self.lower_gen += 1

    def attach_dvfs(self, spec, domain_mhz=None) -> None:
        """Attach (or replace) a runtime-DVFS spec on a not-yet-run
        instance: validates it against this program's [dvfs] tables,
        seeds the per-domain carry (`SimState.dvfs_rt`) from the
        config's initial domain frequencies — or `domain_mhz`, an
        int32[n_domains] override — and invalidates any compiled runner
        (the spec is baked into the lowering).  The CORE domain's seed
        broadcasts into `CoreState.freq_mhz` (chip-global semantics)."""
        from graphite_tpu.dvfs.runtime import (
            DvfsSpec, core_freq_tiles, init_dvfs_rt,
        )

        if not isinstance(spec, DvfsSpec):
            raise TypeError("dvfs must be a dvfs.DvfsSpec")
        spec = spec.resolve(self.params)
        if self.mesh is not None or self.stream:
            raise ValueError(
                "the runtime DVFS manager supports single-device "
                "resident runs and batched sweeps only (the carry is "
                "not threaded through the Simulator's own multi-chip "
                "exchange or the streaming window loop); serve the sim "
                "as a batched campaign under SweepRunner instead")
        rt = init_dvfs_rt(self.params.dvfs, spec, domain_mhz)
        self.dvfs_spec = spec
        self.state = self.state.replace(
            dvfs_rt=rt,
            core=self.state.core.replace(freq_mhz=core_freq_tiles(
                self.params.dvfs, rt, self.state.core.freq_mhz)))
        self._runner = None
        self._runner_max_quanta = None
        self._hb_runner = None
        self._lowered = {}   # the spec is baked into the lowering too
        self.lower_gen += 1

    def residency_breakdown(self, telemetry_spec=None,
                            profile_spec=None, hist_spec=None) -> dict:
        """Per-consumer HBM residency estimate of THIS sim's layout
        (analysis/cost.residency_breakdown): state pytree, resident
        device trace (or one streaming window bound), telemetry ring,
        per-tile profile ring, histogram ring.  `telemetry_spec`/
        `profile_spec`/`hist_spec` override the attached specs — the
        attach_* refusal paths price the spec they are refusing before
        it is attached."""
        from graphite_tpu.analysis.cost import residency_breakdown

        spec = telemetry_spec if telemetry_spec is not None \
            else self.telemetry_spec
        if spec is not None and not spec.resolved:
            spec = spec.resolve(self.params)
        pspec = profile_spec if profile_spec is not None \
            else self.profile_spec
        if pspec is not None and not pspec.resolved:
            pspec = pspec.resolve(self.params)
        hspec = hist_spec if hist_spec is not None else self.hist_spec
        if hspec is not None and not hspec.resolved:
            hspec = hspec.resolve(self.params)
        # the rings are itemized as their own consumers — strip them
        # from the state pytree so an attached spec is not counted twice
        state = self.state
        if state.telemetry is not None:
            state = state.replace(telemetry=None)
        if state.profile is not None:
            state = state.replace(profile=None)
        if state.hist is not None:
            state = state.replace(hist=None)
        stream_bytes = None
        if self.stream:
            # run_streamed's default [T, W] window, double-buffered by
            # the prefetch staging — pure arithmetic, never materialized
            # (this runs inside refusal paths on memory-constrained
            # devices, so it must not allocate what it is pricing)
            from graphite_tpu.analysis.cost import trace_record_bytes

            stream_bytes = (2 * self.params.n_tiles
                            * STREAM_WINDOW_RECORDS
                            * trace_record_bytes(self.trace_batch))
        return residency_breakdown(
            state=state, trace=self.device_trace,
            telemetry_spec=spec, profile_spec=pspec, hist_spec=hspec,
            stream_window_bytes=stream_bytes)

    @property
    def profile(self):
        """The recorded per-tile profile (obs.TileProfile) of
        everything run so far, or None when the sim records none."""
        if self.profile_spec is None:
            return None
        from graphite_tpu.obs.profile import profile_from_state

        return profile_from_state(self.profile_spec, self.state.profile)

    @property
    def hist(self):
        """The recorded latency histograms (obs.Hist) of everything
        run so far, or None when the sim records none."""
        if self.hist_spec is None:
            return None
        from graphite_tpu.obs.hist import hist_from_state

        return hist_from_state(self.hist_spec, self.state.hist)

    @property
    def telemetry(self):
        """The recorded timeline (obs.Timeline) of everything run so
        far, or None when the sim records no telemetry."""
        if self.telemetry_spec is None:
            return None
        from graphite_tpu.obs.telemetry import timeline_from_state

        return timeline_from_state(self.telemetry_spec,
                                   self.state.telemetry)

    @staticmethod
    def _resolve_mem_gate_bytes(cfg, mem_gate_bytes) -> int:
        """The whole-engine mem_gate's state-size ceiling: kwarg, else
        `[general] mem_gate_bytes`, else the historical 1 GB default —
        an escape hatch now, not a hard-code (per-phase gating covers
        the regime above it)."""
        if mem_gate_bytes is not None:
            return int(mem_gate_bytes)
        return cfg.get_int("general/mem_gate_bytes", 1 << 30)

    @staticmethod
    def _resolve_plain_unroll(cfg, mem_params, iocoom_params) -> int:
        from graphite_tpu.engine.step import PLAIN_UNROLL_MAX

        pu = cfg.get_int(
            "general/plain_unroll",
            16 if (mem_params is None and iocoom_params is None) else 1)
        if pu > PLAIN_UNROLL_MAX:
            import warnings

            warnings.warn(
                f"[general] plain_unroll = {pu} exceeds the measured-safe "
                f"ceiling {PLAIN_UNROLL_MAX} (the [T, K] follow-on gather "
                f"regresses superlinearly past it — PERF.md unroll sweep); "
                f"clamping to {PLAIN_UNROLL_MAX}",
                stacklevel=3)
            pu = PLAIN_UNROLL_MAX
        return pu

    @property
    def last_phase_skips(self):
        """Per-phase lax.cond skip counts of the memory engine across
        everything run so far (gate observability: skip rate = skips /
        `last_n_iterations`).  Dict phase-name -> count in the engine's
        own phase order, or None when the run has no memory subsystem.
        Counts every skip source: the per-phase conds AND whole-engine
        mem_gate skips (which count as a skip of every phase)."""
        if self.state.mem is None:
            return None
        skips = np.asarray(jax.device_get(self.state.mem.phase_skips))
        names = mem_phase_names(self.params)
        return {n: int(v) for n, v in zip(names, skips.tolist())}

    @property
    def last_base_skips(self):
        """What the memory engine's home-activity gate skipped across
        everything run so far: {"base": iterations whose directory
        working-set gather and merged scatter did not run (a
        whole-engine mem_gate skip counts), "flush": inner blocks whose
        staging flush did not run}.  Denominators: `last_n_iterations`
        and the run's blocks (a quantum's last block stops at its idle
        iteration, so there are more of them than `last_n_iterations`
        over `inner_block`).  None when the run has no memory
        subsystem or its engine has no such gate (shared-L2)."""
        skips = getattr(self.state.mem, "base_skips", None)
        if skips is None:
            return None
        from graphite_tpu.memory.engine import BASE_SKIP_NAMES

        return dict(zip(BASE_SKIP_NAMES,
                        np.asarray(jax.device_get(skips)).tolist()))

    def _get_runner(self, max_quanta: int):
        if self._runner is None or self._runner_max_quanta != max_quanta:
            if self.spmd == "shard_map":
                from graphite_tpu.parallel.mesh import make_shard_map_runner

                sm = make_shard_map_runner(
                    self.params, self.quantum_ps, max_quanta, self.mesh,
                    self.state, self.device_trace)
                trace = self.device_trace
                self._runner = lambda st: sm(st, trace)
            else:
                from graphite_tpu.engine.step import make_simulation_runner

                self._runner = make_simulation_runner(
                    self.params, self.device_trace, self.quantum_ps,
                    max_quanta, donate=self.donate,
                    telemetry=self.telemetry_spec,
                    profile=self.profile_spec,
                    dvfs=self.dvfs_spec,
                    hist=self.hist_spec)
            self._runner_max_quanta = max_quanta
        return self._runner

    def lower(self, max_quanta: int = 4096):
        """The compiled program as a ClosedJaxpr, plus its flat invar
        paths — the program auditor's input (analysis/audit.py).

        Lowers the program run() actually compiles: the single-region
        device-driven loop, or — for barrier_host sims — the bounded
        batched host-dispatch region (`engine/step.barrier_host_batch`,
        with its dynamic prev_qend/budget operands), so audit verdicts
        certify the executed artifact.  `jax.make_jaxpr` only: pure
        tracing, no compile, so auditing works on CPU-only CI.  Path i
        of the returned list names closed.jaxpr.invars[i] (state leaves
        first, then trace leaves).

        Lower-once: the (closed, paths) pair is cached per max_quanta —
        the auditor, the cost model and the identity fingerprint all
        describe ONE tracing instead of re-lowering per consumer
        (`lower_count` counts actual traces; the identity tests pin it
        at 1 across the whole audit+cost+fingerprint pipeline)."""
        from graphite_tpu.analysis.walk import invar_path_strings

        hit = self._lowered.get(max_quanta)
        if hit is None:
            fn, args = self._auditable_fn(max_quanta)
            closed = jax.make_jaxpr(fn)(*args)
            self.lower_count += 1
            hit = (closed, invar_path_strings(args))
            self._lowered[max_quanta] = hit
        return hit

    def _auditable_fn(self, max_quanta: int = 4096):
        """(fn, args) of the program run() actually executes — lower()
        traces it with make_jaxpr; the cost model's backend cross-check
        (analysis/cost.backend_memory_comparison) jits and compiles the
        SAME pair, so the static estimate and memory_analysis() always
        describe one artifact."""
        if self.mesh is not None or self.stream:
            raise ValueError(
                "lower() supports single-device resident programs only "
                "(the auditable artifact is the one-region jaxpr)")
        params = self.params
        tel = self.telemetry_spec
        prof = self.profile_spec
        dv = self.dvfs_spec
        hs = self.hist_spec
        if self.barrier_host:
            from graphite_tpu.engine.step import barrier_host_batch

            qps = int(self.quantum_ps)

            def fn(st, tr, prev_qend, budget):
                return barrier_host_batch(params, tr, st, prev_qend,
                                          qps, budget, telemetry=tel,
                                          profile=prof, dvfs=dv, hist=hs)

            args = (self.state, self.device_trace,
                    jnp.asarray(0, jnp.int64),
                    jnp.asarray(self.barrier_batch, jnp.int32))
        else:
            from graphite_tpu.engine.step import run_simulation

            qps = self.quantum_ps

            def fn(st, tr):
                return run_simulation(params, tr, st, qps, max_quanta,
                                      telemetry=tel, profile=prof,
                                      dvfs=dv, hist=hs)

            args = (self.state, self.device_trace)
        return fn, args

    @property
    def state(self) -> SimState:
        return self._state

    @state.setter
    def state(self, state: SimState) -> None:
        """A state handed in from outside (construction, a restored
        initial state, a checkpoint) is at no known quantum boundary: the
        host-driven loop's boundary floor goes back to 0 with it."""
        self._state = state
        self._hb_prev_qend = None

    def run_chunk(self, n_quanta: int, *, trace_id=None):
        """Run at most `n_quanta` quanta (for sampled/checkpointed runs).

        Returns (done, quanta_executed).  Unlike run(), hitting the bound
        is not an error — the caller samples/checkpoints and continues.
        """
        span = self._spans(trace_id)
        with span("run", call="run_chunk"):
            return self._run_chunk(n_quanta, span)

    def _run_chunk(self, n_quanta: int, span):
        if self.barrier_host:
            nq, all_done = self._host_barrier_loop(n_quanta, span)
            return all_done, nq
        with span("dispatch", parent="run"):
            state, n_quanta_dev, deadlock_dev, n_iters, n_idle = (
                self._get_runner(n_quanta)(self.state))
            self.n_dispatches += 1
        if span.on:
            with span("wait", parent="dispatch"):
                jax.block_until_ready((n_quanta_dev, deadlock_dev, n_iters))
        with span("fetch", parent="wait"):
            (nq, deadlock, overflow, done, self.last_n_iterations,
             self.last_idle_iterations) = jax.device_get((
                 n_quanta_dev, deadlock_dev, state.net.overflow,
                 state.done, n_iters, n_idle))
        if bool(overflow):
            raise MailboxOverflowError(
                "a (dst,src) mailbox ring overflowed; re-run with a "
                "larger mailbox_depth")
        if bool(deadlock):
            blocked = np.flatnonzero(~done).tolist()
            raise DeadlockError(
                f"no progress across a quantum; blocked tiles: "
                f"{blocked[:16]}{'...' if len(blocked) > 16 else ''}")
        self.state = state
        return bool(done.all()), int(nq)

    def _run_host_barrier(self, max_quanta: int, span) -> SimResults:
        """lax_barrier quanta driven host-side (see run()): one compiled
        BOUNDED multi-quantum region per dispatch (`barrier_host_batch` —
        a device-side while_loop over up to `barrier_batch` quanta, no
        unbounded outer loop) — the variant the 1024-tile +
        memory-engine lax_barrier combination selects (see the
        selection rule in __init__).  Semantics mirror
        `run_simulation`'s device loop exactly: next boundary above the
        laggard tile, empty quanta skipped, zero-progress with a tile
        beyond the boundary jumps the window, else deadlock.  The batch
        loop early-exits to the host on host-visible work (all done,
        mailbox overflow, deadlock), so each host round trip is
        amortized over up to K quanta instead of one."""
        before = self.n_dispatches
        self._hb_prev_qend = None       # a run() starts its windows at 0
        n, all_done = self._host_barrier_loop(max_quanta, span)
        if not all_done:
            raise RuntimeError(f"exceeded max_quanta={max_quanta}")
        self.last_run_dispatches = self.n_dispatches - before
        return self._results_from_state(n, span)

    def _hb_get_runner(self):
        if self._hb_runner is None:
            from graphite_tpu.engine.step import barrier_host_batch

            params, trace = self.params, self.device_trace
            qps = int(self.quantum_ps)
            tel = self.telemetry_spec
            prof = self.profile_spec
            dv = self.dvfs_spec
            hs = self.hist_spec

            def qrun(st, prev_qend, budget):
                return barrier_host_batch(params, trace, st, prev_qend,
                                          qps, budget, telemetry=tel,
                                          profile=prof, dvfs=dv, hist=hs)

            self._hb_runner = jax.jit(
                tagged(qrun), donate_argnums=(0,) if self.donate else ())
        return self._hb_runner

    def _host_barrier_loop(self, max_quanta: int, span=NO_SPANS):
        """Run up to max_quanta host-driven barrier quanta in batches of
        `barrier_batch` per dispatch; returns (quanta_executed,
        all_done).  Mutates self.state.  The budget rides as a DYNAMIC
        operand, so run_chunk-style partial budgets never recompile and
        never overshoot.  Each batch is one `dispatch` / `wait` /
        `fetch` triple of `span`.  The last quantum's boundary stays on
        the instance beside the state it belongs to, so a later call
        (`run_chunk` again and again) goes on from it; assigning `state`
        clears it."""

        runner = self._hb_get_runner()
        state = self.state
        prev_qend = (jnp.asarray(0, jnp.int64)
                     if self._hb_prev_qend is None else self._hb_prev_qend)
        n = 0
        total_iters = total_idle = 0
        batch = 0
        done = jax.device_get(state.done)
        while n < max_quanta and not done.all():
            budget = min(self.barrier_batch, max_quanta - n)
            with span("dispatch", parent="run", batch=batch):
                state, prev_qend, nq_d, deadlock_d, iters_d, idle_d = runner(
                    state, prev_qend, jnp.asarray(budget, jnp.int32))
                self.n_dispatches += 1
            if span.on:
                with span("wait", parent="dispatch", batch=batch):
                    jax.block_until_ready((nq_d, deadlock_d, iters_d))
            with span("fetch", parent="wait", batch=batch) as fetched:
                nq, deadlock, iters, idle, done, overflow = jax.device_get(
                    (nq_d, deadlock_d, iters_d, idle_d, state.done,
                     state.net.overflow))
                if fetched is not None:
                    fetched.attrs.update(quanta=int(nq),
                                         iterations=int(iters))
            batch += 1
            n += int(nq)
            total_iters += int(iters)
            total_idle += int(idle)
            if bool(overflow):
                raise MailboxOverflowError(
                    "a (dst,src) mailbox ring overflowed; re-run with a "
                    "larger mailbox_depth")
            if bool(deadlock):
                blocked = np.flatnonzero(~done).tolist()
                raise DeadlockError(
                    f"no progress across a quantum; blocked tiles: "
                    f"{blocked[:16]}{'...' if len(blocked) > 16 else ''}")
            if int(nq) == 0 and not done.all():
                # the device loop ran zero quanta without raising a flag:
                # its entry condition should make this unreachable
                raise DeadlockError(
                    "host-barrier batch made no progress and raised no "
                    "flag")
        self.state = state
        self._hb_prev_qend = prev_qend
        self.last_n_iterations = total_iters
        self.last_idle_iterations = total_idle
        return n, bool(done.all())

    @staticmethod
    def _result_parts(state: SimState):
        """Device-side pytrees for the summary counters (shared by run()
        and _results_from_state — keep in one place)."""
        # (the memory NoC's ATAC hub queues ride whole, as the user
        # NoC's port queues do below)
        mem_part = (
            (state.mem.counters, state.mem.func_errors,
             state.mem.noc.hub_queues.data
             if isinstance(state.mem.noc, AtacState) else None)
            if state.mem is not None else None
        )
        ioc_part = (
            {
                "load_queue": state.ioc.load_queue_stall_ps,
                "store_queue": state.ioc.store_queue_stall_ps,
                "l1icache": state.ioc.l1icache_stall_ps,
                "intra_ins_l1dcache": state.ioc.intra_ins_l1dcache_stall_ps,
                "inter_ins_l1dcache": state.ioc.inter_ins_l1dcache_stall_ps,
                "intra_ins_execution_unit":
                    state.ioc.intra_ins_execution_unit_stall_ps,
                "inter_ins_execution_unit":
                    state.ioc.inter_ins_execution_unit_stall_ps,
            }
            if state.ioc is not None else None
        )
        # the user NoC's port queues ride whole (a slice here would be a
        # device program of its own, compiled inside the first run())
        net_part = (state.net.packets_sent, state.net.packets_received,
                    state.net.total_latency_ps,
                    state.noc_user.queues.data
                    if isinstance(state.noc_user, NocState) else None)
        tel_part = (
            (state.telemetry.buf, state.telemetry.count)
            if state.telemetry is not None else None
        )
        prof_part = (
            (state.profile.buf, state.profile.times, state.profile.count)
            if state.profile is not None else None
        )
        hist_part = (
            (state.hist.buf, state.hist.boundaries)
            if state.hist is not None else None
        )
        return (net_part, mem_part, ioc_part, tel_part, prof_part,
                hist_part)

    def _power_part(self, state: SimState):
        """Device-side leaves for `dvfs_counters` and `energy_pj` (None
        where the run reports neither: nothing more is fetched)."""
        if not (self._report_dvfs or self.params.energy is not None):
            return None
        return (state.dvfs, state.energy)

    def _power_host(self, power_h, core, net_h, mem_h):
        """(energy_pj, dvfs_counters) from already-fetched leaves.  The
        interval every tile still has open is closed here, on the host,
        in the integers the device closes with: the state is not
        touched, so results can be read again (or mid-run)."""
        if power_h is None:
            return None, None
        dvfs_h, energy_h = power_h
        dvfs_counters = None
        if self._report_dvfs:
            dvfs_counters = {
                "freq_mhz": np.asarray(dvfs_h.freq_mhz),
                "voltage_mv": np.asarray(dvfs_h.voltage_mv),
                "errors": np.asarray(dvfs_h.errors),
            }
        energy_pj = None
        ep = self.params.energy
        if ep is not None and energy_h is not None:
            from graphite_tpu.power.accounting import (
                close_interval, raw_counts, to_pj,
            )

            raw_now = raw_counts(
                np, ep, core, net_h[0],
                None if mem_h is None else mem_h[0])
            acc = np.asarray(energy_h.acc) + close_interval(
                np, ep, raw_now, np.asarray(core.clock_ps),
                np.asarray(dvfs_h.voltage_mv),
                np.asarray(energy_h.last_raw),
                np.asarray(energy_h.last_clock_ps))
            energy_pj = to_pj(ep, acc)
        return energy_pj, dvfs_counters

    def _timeline_host(self, tel_h):
        """Demux an already-fetched (buf, count) pair into a Timeline —
        keeps the ring inside run()'s ONE batched device→host fetch
        (a separate read is one more host round trip)."""
        if tel_h is None or self.telemetry_spec is None:
            return None
        from graphite_tpu.obs.telemetry import Timeline

        buf, count = tel_h
        return Timeline.from_host_state(self.telemetry_spec,
                                        np.asarray(buf), int(count))

    def _profile_host(self, prof_h):
        """Demux an already-fetched (buf, times, count) triple into a
        TileProfile — rides run()'s ONE batched device→host fetch like
        the telemetry ring."""
        if prof_h is None or self.profile_spec is None:
            return None
        from graphite_tpu.obs.profile import TileProfile

        buf, times, count = prof_h
        return TileProfile.from_host_state(
            self.profile_spec, np.asarray(buf), np.asarray(times),
            int(count))

    def _hist_host(self, hist_h):
        """Demux an already-fetched (buf, boundaries) pair into a Hist —
        rides run()'s ONE batched device→host fetch like the other
        rings."""
        if hist_h is None or self.hist_spec is None:
            return None
        from graphite_tpu.obs.hist import Hist

        buf, boundaries = hist_h
        return Hist(sources=tuple(self.hist_spec.sources),
                    edges=self.hist_spec.bucket_edges(),
                    counts=np.asarray(buf), boundaries=int(boundaries))

    def _results_from_state(self, n_quanta: int,
                            span=NO_SPANS) -> SimResults:
        """SimResults from the CURRENT state (after run_chunk loops)."""
        state = self.state
        (net_part, mem_part, ioc_part, tel_part, prof_part,
         hist_part) = self._result_parts(state)
        with span("fetch", parent="run"):
            (core_h, net_h, mem_h, ioc_h, tel_h, prof_h, hist_h,
             power_h) = jax.device_get((
                 state.core, net_part, mem_part, ioc_part, tel_part,
                 prof_part, hist_part, self._power_part(state),
             ))
        with span("results", parent="fetch"):
            return self._results_host(
                core_h, net_h, mem_h, n_quanta, ioc_h,
                telemetry=self._timeline_host(tel_h),
                profile=self._profile_host(prof_h),
                hist=self._hist_host(hist_h),
                power=self._power_host(power_h, core_h, net_h, mem_h))

    def write_output(self, results: SimResults,
                     output_dir: str = "results") -> str:
        """Write the `sim.out` summary + a config snapshot, mirroring the
        reference's per-run results directory (`carbon_sim.cfg:11-30`,
        `simulator.cc:152-170`)."""
        import os

        os.makedirs(output_dir, exist_ok=True)
        out_path = os.path.join(output_dir, "sim.out")
        with open(out_path, "w") as f:
            f.write(results.summary() + "\n")
        with open(os.path.join(output_dir, "carbon_sim.cfg"), "w") as f:
            for key, value in sorted(self.config.cfg.as_dict().items()):
                f.write(f"{key} = {value}\n")
        return out_path

    def run_streamed(self, window_records: int = STREAM_WINDOW_RECORDS,
                     max_quanta: int = 1_000_000,
                     max_windows: int = 1_000_000, *,
                     trace_id=None) -> SimResults:
        """Like run(), but the trace streams host->HBM in [T, W] windows
        (the schema's promised streaming mode — `trace/schema.py`; the
        reference analog is Pin's continuous instruction pipe,
        `pin/instruction_modeling.cc:13-21`).  Device memory for trace
        data is bounded by one window regardless of trace length.

        Windows have PER-TILE base records (each lane's window follows
        its own stream position), so lanes may skew arbitrarily — a
        leader pausing at its window edge never starves a laggard.  The
        device loop runs until every lane is done, deadlocked, or paused
        at its window's end; the host then re-bases every lane's window
        at its current record and re-enters.  A guessed next window
        (every lane one full window ahead — the lockstep case) is staged
        with an async upload while the device crunches, overlapping
        transfer with compute.

        Traced like run() (`attach_tracer`): per window one `dispatch` /
        `wait` / `fetch`, and `refill` for each window placement.
        """
        span = self._spans(trace_id)
        with span("run", call="run_streamed"):
            return self._run_streamed(int(window_records), max_quanta,
                                      max_windows, span)

    def _run_streamed(self, W: int, max_quanta: int, max_windows: int,
                      span) -> SimResults:
        batch = self.trace_batch

        # mesh runs shard each [T, W] window on upload (row t of every
        # window lives with tile t's shard) — streaming and multi-chip
        # striping compose.  Under shard_map the per-tile base vector is
        # replicated control state (the engine lo()s it for local reads).
        if self.mesh is not None and self.spmd == "shard_map":
            from graphite_tpu.parallel.mesh import place_shard_map_window

            def place(win, b):
                return place_shard_map_window(win, self.mesh, b)
        elif self.mesh is not None:
            from graphite_tpu.parallel.mesh import shard_window

            def place(win, b):
                return shard_window(win, self.mesh, b)
        else:
            def place(win, b):
                return win, jnp.asarray(b)

        # module-level runner cache: a fresh jit(lambda) per call (or per
        # Simulator — benchmark warmups use a throwaway instance) would
        # register a new wrapper whose traces don't share the previous
        # executables, silently putting re-compilation inside timed runs
        first_window = None
        if self.spmd == "shard_map":
            bases0 = np.zeros(batch.n_tiles, np.int32)
            with span("refill", parent="run", window=0):
                first_window = place(DeviceTrace.window(batch, bases0, W),
                                     bases0)
            runner = _streamed_runner(
                self.params, self.quantum_ps, max_quanta, self.mesh,
                self.spmd, self.state, first_window[0])
        else:
            runner = _streamed_runner(self.params, self.quantum_ps,
                                      max_quanta)

        bases = np.zeros(batch.n_tiles, np.int32)
        state = self.state
        if first_window is not None:
            window, dev_bases = first_window
        else:
            with span("refill", parent="run", window=0):
                window, dev_bases = place(
                    DeviceTrace.window(batch, bases, W), bases)
        prefetch_bases = None
        prefetch = None
        prefetch_on = True  # lockstep so far; first miss turns it off
        n_quanta = total_iters = total_idle = 0
        for w in range(max_windows):
            with span("dispatch", parent="run", window=w):
                out = runner(state, window, dev_bases)
                self.n_dispatches += 1
            # overlap: stage the lockstep-guess window during the run —
            # only while every slide so far matched the guess (a skewed
            # run would rebuild + re-upload a discarded window each slide)
            guess = bases + W
            if prefetch_on and (guess < batch.length).any():
                prefetch_bases = guess
                with span("refill", parent="dispatch", window=w + 1,
                          prefetch=True):
                    prefetch = place(DeviceTrace.window(batch, guess, W),
                                     guess)
            else:
                prefetch_bases = None
            state, nq_dev, deadlock_dev, iters_dev, idle_dev = out
            if span.on:
                with span("wait", parent="dispatch", window=w):
                    jax.block_until_ready((nq_dev, deadlock_dev))
            with span("fetch", parent="wait", window=w):
                done, idx, deadlock, overflow, nq, iters, idle = (
                    jax.device_get((state.done, state.core.idx,
                                    deadlock_dev, state.net.overflow,
                                    nq_dev, iters_dev, idle_dev)))
                n_quanta += int(nq)
                total_iters += int(iters)
                total_idle += int(idle)
            if bool(overflow):
                raise MailboxOverflowError(
                    "a (dst,src) mailbox ring overflowed; re-run with a "
                    "larger mailbox_depth")
            if done.all():
                break
            if bool(deadlock):
                blocked = np.flatnonzero(~done).tolist()
                raise DeadlockError(
                    f"no progress across a quantum; blocked tiles: "
                    f"{blocked[:16]}{'...' if len(blocked) > 16 else ''}")
            new_bases = np.where(done, bases, idx.astype(np.int32))
            if (new_bases == bases).all():
                # every lane held position across a full window run —
                # cannot happen unless the device loop bailed for a
                # reason the flags above should have caught
                raise DeadlockError(
                    "streaming made no progress across a window slide")
            bases = new_bases
            hit = (prefetch_bases is not None
                   and np.array_equal(prefetch_bases, bases))
            if not hit:
                prefetch_on = False
            if hit:
                window, dev_bases = prefetch
            else:
                with span("refill", parent="fetch", window=w + 1):
                    window, dev_bases = place(
                        DeviceTrace.window(batch, bases, W), bases)
        else:
            raise RuntimeError(f"exceeded max_windows={max_windows}")
        self.state = state
        self.last_n_iterations = total_iters
        self.last_idle_iterations = total_idle
        return self._results_from_state(n_quanta, span)

    def warmup(self, max_quanta: int = 1_000_000) -> None:
        """Compile (and execute once, discarding results) the full runner —
        for benchmarking so timed runs exclude compilation.  Recorded as
        the set-up span `warmup` over `first_dispatch` (the call into the
        runner through `block_until_ready`), under which the program
        ledger hangs what JAX traced, lowered, compiled or loaded."""
        span = SetupSpans(self.tracer)
        with span("warmup"):
            if self.donate:
                # the donated run would delete self.state's buffers and
                # the discarded output is the only live copy — a later
                # run() would fail with an opaque "array has been deleted"
                raise RuntimeError(
                    "warmup() is incompatible with donate=True (the "
                    "warmup run would consume self.state); warm a "
                    "separate non-donating instance and adopt_runner() "
                    "from it")
            if self.barrier_host:
                # compile + execute one single-quantum batch (the program
                # run() dispatches under barrier_host); the output is
                # discarded, self.state stays untouched
                runner = self._hb_get_runner()
                args = (self.state, jnp.asarray(0, jnp.int64),
                        jnp.asarray(1, jnp.int32))
            else:
                runner, args = self._get_runner(max_quanta), (self.state,)
            with span("first_dispatch"):
                jax.block_until_ready(runner(*args))

    def adopt_runner(self, other: "Simulator") -> None:
        """Reuse another instance's compiled runner.

        For timed repeat runs with donate=True (which consumes the ran
        instance's state): build a fresh instance over the SAME config and
        trace batch, adopt the first instance's runner, and the timed run
        excludes retrace/recompile.  The runner closes over the other
        instance's device trace, so both instances must be built from the
        SAME trace batch object and identical config/donation."""
        if other._runner is None and other._hb_runner is None:
            raise ValueError(
                "adopt_runner: the donor has no compiled runner (run it "
                "first) — adopting nothing would silently time a "
                "retrace+recompile")
        if (other.params != self.params or other.spmd != self.spmd
                or other.quantum_ps != self.quantum_ps
                or other.mesh != self.mesh
                or other.donate != self.donate
                or other.barrier_host != self.barrier_host
                or other.barrier_batch != self.barrier_batch
                # the recording specs are baked into the lowering: an
                # adopted runner with different specs would silently
                # record nothing (or retrace) instead of refusing
                or other.telemetry_spec != self.telemetry_spec
                or other.profile_spec != self.profile_spec
                or other.dvfs_spec != self.dvfs_spec
                or other.hist_spec != self.hist_spec
                or other.trace_batch is not self.trace_batch):
            raise ValueError(
                "adopt_runner needs the same trace batch and identical "
                "config/program/quantum/mesh/donation/recording specs")
        # the adopted runner closes over the donor's device trace — drop
        # this instance's duplicate upload (matters at 1024-tile scale)
        self.device_trace = other.device_trace
        self._runner = other._runner
        self._runner_max_quanta = other._runner_max_quanta
        self._hb_runner = other._hb_runner

    def run(self, max_quanta: int = 1_000_000, *,
            trace_id=None) -> SimResults:
        """Drive quanta until every tile's trace is exhausted.

        The whole quantum loop runs on device as one compiled region
        (`run_simulation`): loop control (next boundary above the laggard
        tile, zero-progress/deadlock detection, overflow) is device-side,
        so the run costs a single host↔device round trip where the
        previous per-quantum host loop paid one per control read (the
        cost of a round trip is not measured on the current machine).
        Empty quanta are skipped by jumping qend to the next boundary above
        the laggard tile's clock (the reference's barrier only collects
        *running* threads, so idle quanta never happen there either —
        `lax_barrier_sync_server.h:12-36`).  A quantum with zero progress
        while some tile was eligible to run is a genuine deadlock.

        Under `barrier_host` (the 1024-tile + memory-engine lax_barrier
        combination) the barrier loop runs host-side instead — identical
        quantum semantics, one bounded compiled region per `barrier_batch`
        quanta (early-exiting on host-visible work).

        With a tracer attached (`attach_tracer`) the call records one
        trace, `run-<n>` or the caller's `trace_id`: `run` > `dispatch`
        > `wait` > `fetch` > `results` (obs/trace.py: RUN_SPANS).
        """
        span = self._spans(trace_id)
        with span("run", call="run"):
            if self.barrier_host:
                return self._run_host_barrier(max_quanta, span)
            return self._run_one_region(max_quanta, span)

    def _run_one_region(self, max_quanta: int, span) -> SimResults:
        with span("dispatch", parent="run"):
            state, n_quanta_dev, deadlock_dev, n_iters, n_idle = (
                self._get_runner(max_quanta)(self.state))
            self.n_dispatches += 1
        if span.on:
            with span("wait", parent="dispatch"):
                jax.block_until_ready((n_quanta_dev, deadlock_dev, n_iters))
        # ONE batched device→host fetch for control flags + all summary
        # counters + the telemetry ring (each separate read is one more
        # host round trip).
        (net_part, mem_part, ioc_part, tel_part, prof_part,
         hist_part) = self._result_parts(state)
        with span("fetch", parent="wait"):
            host = jax.device_get((
                n_quanta_dev, deadlock_dev, state.net.overflow, state.done,
                state.core, net_part, mem_part, ioc_part, tel_part,
                prof_part, hist_part, n_iters, n_idle,
                self._power_part(state),
            ))
        (n_quanta, deadlock, overflow, done, core_h, net_h, mem_h,
         ioc_h, tel_h, prof_h, hist_h, self.last_n_iterations,
         self.last_idle_iterations, power_h) = host
        if bool(overflow):
            raise MailboxOverflowError(
                "a (dst,src) mailbox ring overflowed; re-run with a "
                "larger mailbox_depth"
            )
        if bool(deadlock):
            blocked = np.flatnonzero(~done).tolist()
            raise DeadlockError(
                f"no progress across a quantum; blocked tiles: "
                f"{blocked[:16]}{'...' if len(blocked) > 16 else ''}"
            )
        if not bool(done.all()):
            raise RuntimeError(f"exceeded max_quanta={max_quanta}")
        self.state = state
        self.last_run_dispatches = 1
        with span("results", parent="fetch"):
            return self._results_host(
                core_h, net_h, mem_h, int(n_quanta), ioc_h,
                telemetry=self._timeline_host(tel_h),
                profile=self._profile_host(prof_h),
                hist=self._hist_host(hist_h),
                power=self._power_host(power_h, core_h, net_h, mem_h))

    def _results_host(self, core, net_h, mem_h, n_quanta: int,
                      ioc_h=None, telemetry=None,
                      profile=None, hist=None,
                      power=None) -> SimResults:
        """Assemble SimResults from already-fetched host arrays;
        `power` is `_power_host`'s pair."""
        clock = np.asarray(core.clock_ps)
        energy_pj, dvfs_counters = power or (None, None)
        mem_counters = hubs_h = None
        func_errors = 0
        if mem_h is not None:
            import dataclasses as _dc

            counters_h, func_errors_h, hubs_h = mem_h
            mem_counters = {
                f.name: np.asarray(getattr(counters_h, f.name))
                for f in _dc.fields(counters_h)
            }
            func_errors = int(func_errors_h)
        packets_sent, packets_received, total_latency_ps, noc_h = net_h
        return SimResults(
            n_tiles=self.params.n_tiles,
            completion_time_ps=int(clock.max()),
            instruction_count=np.asarray(core.instruction_count),
            clock_ps=clock,
            memory_stall_ps=np.asarray(core.memory_stall_ps),
            execution_stall_ps=np.asarray(core.execution_stall_ps),
            recv_instructions=np.asarray(core.recv_instructions),
            recv_stall_ps=np.asarray(core.recv_stall_ps),
            sync_instructions=np.asarray(core.sync_instructions),
            sync_stall_ps=np.asarray(core.sync_stall_ps),
            bp_correct=np.asarray(core.bp_correct),
            bp_incorrect=np.asarray(core.bp_incorrect),
            packets_sent=np.asarray(packets_sent),
            packets_received=np.asarray(packets_received),
            total_packet_latency_ps=np.asarray(total_latency_ps),
            n_quanta=n_quanta,
            mem_counters=mem_counters,
            func_errors=func_errors,
            noc_counters=(None if noc_h is None else noc_counters(
                np.asarray(noc_h), self.params.n_tiles)),
            atac_counters=(None if hubs_h is None else atac_counters(
                np.asarray(hubs_h), self.params.mem.net_atac.n_clusters)),
            detailed_stalls=(
                {k: np.asarray(v) for k, v in ioc_h.items()}
                if ioc_h is not None else None),
            telemetry=telemetry,
            profile=profile,
            hist=hist,
            energy_pj=energy_pj,
            dvfs_counters=dvfs_counters,
        )

