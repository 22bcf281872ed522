"""The vectorized subquantum step: every tile advances one trace record.

This replaces Graphite's per-instruction host control flow — Pin callback →
`CoreModel::queueInstruction/iterate` (`pin/instruction_modeling.cc:13-21`,
`common/tile/core/models/simple_core_model.cc:37-97`) and the blocking
netRecv / MCP sync-server round trips (`network.cc:358-460`,
`common/system/sync_server.cc:27-160`) — with a masked SoA state machine:

 - one `lax.scan` iteration processes (at most) one trace record per tile,
   all tiles in parallel;
 - blocked operations (recv with no matching packet, barrier not full,
   mutex held) simply do not advance `idx`; they retry next iteration, when
   messages pushed by other tiles in earlier iterations have landed;
 - sends scatter into per-(dst,src) mailbox rings — each sender lane owns
   its own src column, so writes never collide;
 - barrier arrivals/releases use scatter-add/scatter-max plus a global
   release mask, reproducing SimBarrier's max-arrival-time release
   (`sync_server.cc:133-160`);
 - mutex grants pick the earliest-simulated-time waiter via a segmented
   min over (clock, tile) keys, reproducing SimMutex handoff-at-unlock-time
   (`sync_server.cc:27-57,185-240`) deterministically (the reference's FIFO
   is host-arrival-order and racy).

Timing semantics per record mirror the reference exactly:
 - static instruction cost from the `[core/static_instruction_costs]` table
   (`core_model.cc:65-76`), converted at the tile's DVFS frequency;
 - branch cost 1 cycle on correct prediction else the mispredict penalty,
   one-bit predictor indexed by pc (`instruction.cc:47-70`,
   `one_bit_branch_predictor.cc:13-24`, `carbon_sim.cfg:202-205`);
 - dynamic instruction cost carried in the record (`instruction.h:149-198`);
 - netRecv: clock = max(clock, arrival); a RecvInstruction is accounted only
   when arrival > clock (`network.cc:443-453`);
 - barrier release at max arrival time with a SyncInstruction only when the
   wait was positive (`sync_server.cc:141-144`, `sync_client.cc:83-87`);
 - models-disabled ⇒ zero cost and no counters, but full functional effect
   (`simulator.cc:399-413`, `core_model.h` _enabled gate).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from graphite_tpu.intmath import nn_mod

from graphite_tpu.engine.state import SimState, DeviceTrace
from graphite_tpu.models.network_user import UserNetworkParams, route_latency_ps
from graphite_tpu.obs.scopes import scope, tagged
from graphite_tpu.parallel.px import IDENT, ParallelCtx
from graphite_tpu.trace.schema import (
    FLAG_BRANCH_TAKEN,
    Op,
)
from graphite_tpu.time_types import cycles_to_ps

I64 = jnp.int64
FAR_FUTURE_PS = 2**62  # python int: folds to an inline literal, never a device-constant buffer
ANY_SENDER = -1

# Measured-safe ceiling for plain-run batching: the [T, KX] follow-on
# gather goes superlinear past this (PERF.md unroll sweep on the
# 1024-tile per-instruction ring: 8 -> 1.06M, 16 -> 1.76M, 32 -> 0.79M
# instr/s).  The engine clamps the effective unroll here; the Simulator
# warns when a config asks for more.
PLAIN_UNROLL_MAX = 16


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Static compile-time parameters of the step function."""

    n_tiles: int
    static_cost_cycles: tuple  # 20 ints (`carbon_sim.cfg:189-200`)
    net: UserNetworkParams
    bp_enabled: bool = True
    bp_size: int = 1024
    bp_mispredict_penalty: int = 14
    mailbox_depth: int = 8
    inner_block: int = 32      # most iterations between staging flushes
    n_conds: int = 64          # cond-variable id space (sync tables)
    syscall_rt_ps: int = 2000  # SYSTEM-net round trip to the MCP (2 cyc @1GHz)
    # iocoom core model (None = simple 1-IPC in-order model)
    iocoom: "object" = None    # IocoomParams | None
    # heterogeneous cores (`[tile] model_list`, `config.cc:365-472`): which
    # tiles run the iocoom model (None = all, when iocoom is set); the rest
    # use the simple 1-IPC path
    iocoom_tiles: "tuple | None" = None
    # DVFS tables (always set by Simulator; the None fallback — a raw
    # frequency poke without validation — serves direct engine-level use)
    dvfs: "object" = None      # DvfsParams | None
    # memory subsystem (None = enable_shared_mem false: memory operands
    # cost nothing, like the reference's disabled shared-mem knob)
    mem: "object" = None       # MemParams | None
    # USER network full hop-by-hop model with per-port contention
    user_hbh: "object" = None  # HopByHopParams | None
    # USER network ATAC optical model (clusters + hubs + waveguide)
    user_atac: "object" = None  # AtacParams | None
    # Gate the memory engine behind a "any memory work this iteration"
    # lax.cond (big win on mixed compute/memory traces).  XLA double-
    # buffers the cond's carried outputs, so the Simulator disables the
    # gate when the memory state (directory sharer maps dominate at large
    # tile counts) exceeds its (config-driven) mem_gate_bytes ceiling —
    # above it the engines' PER-PHASE gating (MemParams.phase_gate,
    # conds carrying only small state) takes over.
    mem_gate: bool = True
    # Commit up to this many consecutive PLAIN records (static
    # non-branch instruction costs — no machinery, memory, or predictor
    # state) per lane per iteration: runtime BBLOCK compression for
    # per-instruction streams, bit-exact by construction (each follow-on
    # stays quantum-bounded like the per-iteration active check).
    # Simple-core memoryless runs only; 1 = off.
    plain_unroll: int = 1
    # energy price list (power/accounting.EnergyParams) under [general]
    # enable_power_modeling, else None: no leaf, no operation
    energy: "object" = None
    # lax_p2p clock-skew scheme (`lax_p2p_sync_client.h:13-83`): when set,
    # each iteration every tile draws a pseudorandom partner and advances
    # only if its clock is within `slack` of the partner's — the
    # random-pairwise clamping of the reference, minus the raciness (our
    # sync decisions are simulated-time-ordered, so unlike the reference
    # the scheme changes scheduling, not results)
    p2p_slack_ps: "int | None" = None


def _gather_field(field: jax.Array, idx: jax.Array) -> jax.Array:
    return jnp.take_along_axis(field, idx[:, None], axis=1)[:, 0]


def _elect_min(mask, gid, key, n_groups):
    """Per-group minimum of `key` over lanes with `mask`, via a scatter-min
    into group buckets (bucket n_groups collects masked-off lanes).
    Returns int64[n_groups]; empty groups hold 2**62.  A lane wins its
    group's election iff mask & (key == result[gid])."""
    best = (
        jnp.full((n_groups + 1,), 2**62, I64)
        .at[jnp.where(mask, gid, n_groups)]
        .min(jnp.where(mask, key, jnp.asarray(2**62, I64)))
    )
    return best[:n_groups]




@scope("gt.core")
def subquantum_iteration(
    params: EngineParams,
    trace: DeviceTrace,
    state: SimState,
    quantum_end_ps: jax.Array,
    trace_base: jax.Array | None = None,
    px: ParallelCtx = IDENT,
    knobs=None,
    dvfs=None,
    hist=None,
) -> tuple[SimState, jax.Array]:
    """Process one trace record per tile; returns (state, tiles_advanced).

    With `dvfs` (a resolved `dvfs.DvfsSpec`) and the `SimState.dvfs_rt`
    carry attached, the memory/network/DRAM timing conversions read the
    CARRIED per-domain frequencies instead of the constant-folded
    MemParams values, and in-trace DVFS_SET events elect new chip-global
    operating points (dvfs/runtime.py).  None — the default — keeps the
    historical program bit-identical (the `dvfs-off` audit rule).

    With `knobs` (a sweep.Knobs pytree) set, the memory engines read
    their timing scalars — DRAM latency, directory access cycles, NoC
    hop latency, DVFS sync delay — from its TRACED leaves instead of
    the static params, so one compiled program serves every timing
    point of a sweep (sweep/knobs.py).  None keeps the historical
    constant-folded program bit-identically.

    With `trace_base` (int32[T]) set, `trace` is a [T, W] WINDOW of the
    full record stream, row t starting at global record index
    `trace_base[t]` (host->HBM streaming, the Pin-pipe analog —
    `pin/instruction_modeling.cc` streams continuously).  Lanes whose
    global idx has run past their window's end simply pause (wall-time
    only; clocks and all protocol state carry over) until the host
    slides their window.

    With a sharded `px` (shard_map multi-chip), `trace` and
    `core.bp_bits` hold only this device's block of tile rows; every
    other input is replicated.  Block-local reads are packed into one
    all-gather here (and one per memory-engine phase); all decision
    logic then runs replicated, and block-local arrays take their lanes'
    writes locally (see parallel/px.py).
    """
    T = params.n_tiles
    D = params.mailbox_depth
    core, net, sync = state.core, state.net, state.sync
    tiles = np.arange(T, dtype=np.int32)
    if trace_base is None:
        idx = jnp.minimum(core.idx, trace.length - 1)
        in_window = None
    else:
        idx = jnp.clip(core.idx - trace_base, 0, trace.length - 1)
        in_window = core.idx < trace_base + trace.length

    # Record fetch: per-row gathers on the [T, L] trace cost ~0.25 ms each
    # on TPU (gather lowers poorly), so when every tile is at the SAME
    # column — the common case for lockstep stretches — read the column
    # with one dynamic_slice instead.  The gather path runs only when tiles
    # have diverged (blocked on sync/messages).  Under a sharded px the
    # trace and bp_bits rows are block-local: the reads below see only
    # this device's lanes and ONE packed all-gather replicates them.
    with scope("gt.fetch"):
        gather_fields = (trace.op, trace.flags, trace.pc, trace.aux0, trace.aux1,
                         trace.dyn_ps) + (
            (trace.addr0, trace.addr1) if params.mem is not None else ()) + (
            (trace.rreg0, trace.rreg1, trace.wreg)
            if params.iocoom is not None else ())
        uniform = jnp.all(idx == idx[0])
        idx_l = px.lo(idx)

        def _read_uniform(_):
            return tuple(
                lax.dynamic_slice_in_dim(f, idx[0], 1, axis=1)[:, 0]
                for f in gather_fields
            )

        def _read_gather(_):
            return tuple(_gather_field(f, idx_l) for f in gather_fields)

        fetched_l = lax.cond(uniform, _read_uniform, _read_gather, None)
        # branch prediction reads ride the same exchange (bp_bits block-local)
        bp_index_l = nn_mod(fetched_l[2], params.bp_size).astype(jnp.int32)
        bp_pred_l = jnp.take_along_axis(
            core.bp_bits, bp_index_l[:, None], axis=1)[:, 0]
        agd = px.ag(fetched_l + (bp_pred_l,))
        fetched, bp_pred = agd[:-1], agd[-1]
        op = fetched[0].astype(jnp.int32)
        flags = fetched[1].astype(jnp.int32)
        pc = fetched[2]
        aux0 = fetched[3]
        aux1 = fetched[4]
        dyn_ps = fetched[5]

    enabled = state.models_enabled
    stream_end = (op == Op.NOP) | (op == Op.THREAD_EXIT)
    if in_window is not None:
        # a paused lane's fetched record is the clipped window edge —
        # it must neither latch done nor execute
        stream_end = stream_end & in_window
    done = state.done | stream_end
    active = (~done) & (core.clock_ps < quantum_end_ps)
    if in_window is not None:
        active = active & in_window

    # lax_p2p random pairwise clamping (`lax_p2p_sync_client.h:13-83`):
    # each tile draws a pseudorandom partner this round and holds if it is
    # more than `slack` ahead of a still-running partner.  The globally
    # minimum-clock lane can never hold (its partner's clock is >= its
    # own), so some lane always advances — no scheme-induced deadlock.
    if params.p2p_slack_ps is not None:
        rnd = (state.p2p_round.astype(jnp.uint32) * jnp.uint32(747796405)
               + tiles.astype(jnp.uint32) * jnp.uint32(2891336453))
        rnd = (rnd ^ (rnd >> 13)) * jnp.uint32(1103515245)
        # a random partner OTHER than self (self-pairing would be a no-op
        # check and weakens the bound badly at small tile counts)
        partner = ((tiles.astype(jnp.uint32) + 1
                    + rnd % jnp.uint32(max(T - 1, 1)))
                   % jnp.uint32(T)).astype(jnp.int32)
        ahead = core.clock_ps > (
            core.clock_ps[partner] + jnp.asarray(params.p2p_slack_ps, I64))
        active = active & ~(ahead & ~done[partner])
        p2p_round = state.p2p_round + 1
    else:
        p2p_round = state.p2p_round

    # --- memory subsystem (caches + coherence protocol) ------------------
    # Runs every iteration: requester lanes start/advance their record's
    # memory slots; home/sharer machinery serves protocol messages even for
    # tiles past the quantum boundary (like the reference's sim threads).
    if params.mem is not None:
        from graphite_tpu.memory.engine import (
            RecView, mem_idle_out, memory_engine_step, slots_present,
        )

        # below its size ceiling the whole engine sits under ONE cond
        # (mem_gate, further down), which skips the base with it
        whole_gate = params.mem_gate and not px.sharded
        if params.mem.protocol.startswith("pr_l1_sh_l2"):
            from graphite_tpu.memory.engine_shl2 import shl2_engine_step
            engine_step = shl2_engine_step
        else:
            # The engine's own home-activity gate is for the regime
            # where the per-phase conds are the only gating.  Inside the
            # whole-engine cond it was measured to cost what it saves:
            # at 64 tiles the base is live in most iterations that run
            # the engine at all, and its passes over 8 MB stores are
            # cheaper than two control-flow headers (PERF.md §6, PR 29).
            engine_step = functools.partial(memory_engine_step,
                                            home_gate=not whole_gate)
        # knob lifting: swap the timing-scalar fields for the (traced)
        # sweep knobs; geometry and every other static field untouched
        mem_p = params.mem if knobs is None else knobs.apply_mem(params.mem)
        if dvfs is not None and state.dvfs_rt is not None:
            # runtime DVFS: the memory-network and directory frequencies
            # come from the carried operating point (same replace lift)
            from graphite_tpu.dvfs.runtime import apply_rt_mem

            mem_p = apply_rt_mem(params.dvfs, mem_p, state.dvfs_rt)
        addr0, addr1 = fetched[6], fetched[7]
        rec = RecView(op=op, flags=flags, pc=pc, addr0=addr0, addr1=addr1,
                      aux0=aux0, aux1=aux1)
        # Skip the whole engine (hundreds of small kernels) on iterations
        # with provably no memory work: no live protocol state and no
        # active lane whose record carries memory slots.  Compute-heavy
        # stretches (bblock runs) then pay ~nothing for the memory model.
        # Sharded px runs ungated: the engine's per-phase all-gathers must
        # not sit inside a lax.cond (and the sharded workloads are
        # coherence-dense, so the gate would rarely skip anyway).
        # per-call miss-fill events only materialize when the histograms
        # ask for them — fill_events=False keeps MemStepOut leaf-free and
        # the hist-off trace byte-identical (PROGRAMS.lock fingerprints)
        with scope("gt.mem.base"):
            fill_ev = hist is not None
            if whole_gate:
                need_mem = state.mem.live | jnp.any(
                    active & slots_present(mem_p, rec, enabled).any(axis=1))
                mem_out = lax.cond(
                    need_mem,
                    lambda _: engine_step(mem_p, state.mem, rec,
                                          core.clock_ps, core.freq_mhz,
                                          active, enabled,
                                          fill_events=fill_ev),
                    lambda _: mem_idle_out(mem_p, state.mem, rec, enabled,
                                           fill_events=fill_ev),
                    None)
            else:
                mem_out = engine_step(
                    mem_p, state.mem, rec, core.clock_ps, core.freq_mhz,
                    active, enabled, px=px, fill_events=fill_ev)
        mem_state = mem_out.ms
        mem_ok = mem_out.mem_complete
        mem_acc_ps = mem_out.acc_ps
        mem_progress = mem_out.progress
    else:
        mem_state = state.mem
        mem_ok = jnp.ones((T,), jnp.bool_)
        mem_acc_ps = jnp.zeros((T,), I64)
        mem_progress = jnp.zeros((), jnp.int32)

    # --- classify -------------------------------------------------------
    is_branch = op == Op.BRANCH
    is_static = (op < Op.DYNAMIC_MISC) & ~is_branch      # 0-14 minus branch
    is_dynamic = (op >= Op.DYNAMIC_MISC) & (op < 20)     # 15-19
    is_spawn_instr = op == Op.SPAWN
    is_send = op == Op.SEND
    is_recv = op == Op.NET_RECV
    is_binit = op == Op.BARRIER_INIT
    is_bwait = op == Op.BARRIER_WAIT
    # co-located split forms (see schema): non-blocking arrival + blocking
    # rendezvous on the release generation / published signal sequence
    is_barrive = op == Op.BARRIER_ARRIVE
    is_bsync = op == Op.BARRIER_SYNC
    is_cjoin = op == Op.COND_JOIN
    is_minit = op == Op.MUTEX_INIT
    is_mlock = op == Op.MUTEX_LOCK
    is_munlock = op == Op.MUTEX_UNLOCK
    is_join = op == Op.THREAD_JOIN
    is_bblock = op == Op.BBLOCK
    # Events that always complete in one iteration:
    is_syscall = op == Op.SYSCALL
    is_simple_event = (
        (op == Op.THREAD_SPAWN)
        | is_binit | is_minit | is_munlock
        | (op == Op.ENABLE_MODELS) | (op == Op.DISABLE_MODELS)
        | (op == Op.DVFS_SET) | (op == Op.DVFS_GET)
        | is_syscall  # blocking round trip to the MCP, charged as cost_ps
        | (op == Op.COND_INIT)  # effects applied in the mutex+cond block
        # COND_SIGNAL/COND_BROADCAST commit conditionally (cond_post_commit):
        # surplus same-iteration posters retry, so they are NOT simple
    )

    # --- static + dynamic instruction costs ------------------------------
    cost_table = jnp.asarray(params.static_cost_cycles, dtype=I64)
    static_cycles = cost_table[jnp.clip(op, 0, 19)]

    bp_index = nn_mod(pc, params.bp_size).astype(jnp.int32)  # bp_pred: fetch ag
    taken = ((flags & FLAG_BRANCH_TAKEN) != 0).astype(jnp.uint8)
    bp_correct_now = bp_pred == taken
    if params.bp_enabled:
        branch_cycles = jnp.where(bp_correct_now, 1, params.bp_mispredict_penalty)
    else:
        branch_cycles = jnp.ones((T,), I64)

    cycles = jnp.where(is_branch, branch_cycles, static_cycles)
    cost_ps = cycles_to_ps(cycles, core.freq_mhz.astype(I64))
    cost_ps = jnp.where(is_dynamic, dyn_ps, cost_ps)
    cost_ps = jnp.where(op < 20, cost_ps, 0)  # events carry no direct cost
    # ... except syscalls and DVFS queries: the app thread blocks for a
    # round trip — to the MCP's SyscallServer over the SYSTEM network
    # (`syscall_model.cc` marshalling) or to the target DVFS manager over
    # the DVFS network (`dvfs_manager.cc` remote get).  Both networks are
    # always magic (`config.cc:484-485` → 1 cycle each way).
    cost_ps = jnp.where(is_syscall | (op == Op.DVFS_GET),
                        jnp.asarray(params.syscall_rt_ps, I64), cost_ps)
    # compressed run: aux1 = total cycles for aux0 instructions
    cost_ps = jnp.where(
        is_bblock,
        cycles_to_ps(aux1.astype(I64), core.freq_mhz.astype(I64)),
        cost_ps,
    )
    cost_ps = jnp.where(enabled, cost_ps, 0)

    # The network / barrier / mutex / join machinery each runs under a
    # lax.cond keyed on "any lane has such an op right now" — compute-heavy
    # stretches then skip the scatter-heavy machinery entirely (a TPU
    # scatter costs ~0.2-0.9 ms regardless of how many lanes are masked on).
    # Each predicate goes through px.any_sim: in a campaign's batched
    # program it is OR-ed over the sims, so it stays a scalar and the cond
    # stays a cond under vmap; a sim whose own predicate is false runs the
    # block with every lane masked off, which returns its inputs (the
    # mutex/cond block has to be told to: see there).  (The two
    # `uniform` fetch conds are an ALL over a per-sim index, not an ANY
    # over lanes, and stay per-sim: a select under vmap.)
    dst = jnp.clip(aux0, 0, T - 1)
    send_now = active & is_send

    # --- SEND + RECV: (dst, src) mailbox rings ---------------------------
    def _net_block(_):
        with scope("gt.net.route"):
            if params.user_hbh is not None:
                from graphite_tpu.models.network_hop_by_hop import route_hop_by_hop
                from graphite_tpu.models.network_user import user_packet_bits

                noc_user, arrival_ps, _, _ = route_hop_by_hop(
                    params.user_hbh, state.noc_user, tiles, dst,
                    user_packet_bits(aux1), core.clock_ps, send_now, enabled)
                lat_ps = arrival_ps - core.clock_ps
            elif params.user_atac is not None:
                from graphite_tpu.models.network_atac import route_atac
                from graphite_tpu.models.network_user import user_packet_bits

                noc_user, arrival_ps, _ = route_atac(
                    params.user_atac, state.noc_user, tiles, dst,
                    user_packet_bits(aux1), core.clock_ps, send_now, enabled)
                lat_ps = arrival_ps - core.clock_ps
            else:
                noc_user = state.noc_user
                lat_ps = route_latency_ps(params.net, tiles, dst, aux1, enabled)
                arrival_ps = core.clock_ps + lat_ps
        slot = nn_mod(net.head[dst, tiles], D).astype(jnp.int32)
        # Write under mask: redirect masked-off lanes to their own (t, t)
        # cell at a dummy slot; since each lane writes a distinct src
        # column, no collisions occur either way.  Updates are add-a-delta
        # so the scatter is the array's ONLY remaining use — XLA then
        # updates the loop-carried mailbox buffers in place instead of
        # copying ~100MB per iteration.
        w_dst = jnp.where(send_now, dst, tiles)
        old_time = net.time_ps[w_dst, slot, tiles]
        old_lat = net.lat_ps[w_dst, slot, tiles]
        time_ps_new = net.time_ps.at[w_dst, slot, tiles].add(
            jnp.where(send_now, arrival_ps - old_time, 0)
        )
        lat_arr_new = net.lat_ps.at[w_dst, slot, tiles].add(
            jnp.where(send_now, lat_ps.astype(jnp.int32) - old_lat, 0)
        )
        head_new = net.head.at[w_dst, tiles].add(jnp.where(send_now, 1, 0))
        count_sent = net.count.at[w_dst, tiles].add(
            jnp.where(send_now, 1, 0))

        # RECV matches against the POST-send arrays: a packet sent this
        # iteration is immediately visible (its timestamp carries the
        # arrival time, so simulated timing is unchanged — this only
        # removes retry iterations and lets the send scatters alias).
        # Specific-sender receives only touch their own (dst, src) ring:
        # O(T) gathers.  The earliest-across-all-senders scan for
        # ANY_SENDER receives is O(T^2) and runs under its own cond.
        is_any_recv = is_recv & (aux0 == ANY_SENDER)

        def _any_src(_):
            tail = nn_mod(head_new - count_sent, D).astype(jnp.int32)  # [T, T]
            tail_times = jnp.take_along_axis(
                time_ps_new, tail[:, None, :], axis=1)[:, 0, :]
            masked_times = jnp.where(
                count_sent > 0, tail_times, FAR_FUTURE_PS)
            return jnp.argmin(masked_times, axis=1).astype(jnp.int32)

        any_src = lax.cond(
            px.any_sim(jnp.any(active & is_any_recv)),
            _any_src, lambda _: jnp.zeros((T,), jnp.int32), None)
        want_src = jnp.where(is_any_recv, any_src, jnp.clip(aux0, 0, T - 1))
        sel_count = count_sent[tiles, want_src]
        sel_tail = nn_mod(head_new[tiles, want_src] - sel_count,
                          D).astype(jnp.int32)
        matched = sel_count > 0
        recv_time = jnp.where(
            matched, time_ps_new[tiles, sel_tail, want_src], FAR_FUTURE_PS)
        recv_lat = lat_arr_new[tiles, sel_tail, want_src]
        recv_now = active & is_recv & matched
        # pop (count -1)
        count_new = count_sent.at[tiles, want_src].add(
            jnp.where(recv_now, -1, 0))
        # only a send can overflow its ring; check just the written cells
        overflow = net.overflow | jnp.any(
            send_now & (count_sent[w_dst, tiles] > D))
        return (time_ps_new, lat_arr_new, head_new, count_new, overflow,
                noc_user, recv_now, recv_time, recv_lat)

    def _net_skip(_):
        return (net.time_ps, net.lat_ps, net.head, net.count, net.overflow,
                state.noc_user, jnp.zeros((T,), jnp.bool_),
                jnp.full((T,), FAR_FUTURE_PS, I64), jnp.zeros((T,), jnp.int32))

    with scope("gt.net.mailbox"):
        (time_ps_new, lat_arr_new, head_new, count_new, overflow, noc_user,
         recv_now, recv_time, recv_lat) = lax.cond(
            px.any_sim(jnp.any(send_now | (active & is_recv))),
            _net_block, _net_skip, None)
    recv_wait_ps = jnp.maximum(recv_time - core.clock_ps, 0)
    recv_wait_ps = jnp.where(recv_now, recv_wait_ps, 0)

    # --- BARRIER ---------------------------------------------------------
    def _barrier_block(_):
        # Masked scatter-updates use the add-a-delta idiom: masked-off
        # lanes contribute +0, so duplicate dummy indices cannot clobber a
        # live update (a plain masked .set would).
        bar = jnp.clip(aux0, 0, sync.barrier_count.shape[0] - 1)
        binit_now = active & is_binit
        # several tiles may init the same barrier in one iteration (the
        # vectorized trace generators do); elect one writer per id so the
        # add-a-delta stays idempotent instead of summing every lane's delta
        n_bars = sync.barrier_count.shape[0]
        init_best = _elect_min(binit_now, bar, tiles.astype(I64), n_bars)
        init_win = binit_now & (tiles.astype(I64) == init_best[bar])
        barrier_count = sync.barrier_count.at[bar].add(
            jnp.where(init_win, aux1 - sync.barrier_count[bar], 0)
        )
        # arrivals: blocking waits joining the rendezvous, plus the
        # co-located split form's non-blocking BARRIER_ARRIVE records
        arrive_only = active & is_barrive
        new_arrival = (active & is_bwait & ~sync.barrier_waiting
                       ) | arrive_only
        arr_tgt = jnp.where(new_arrival, bar, 0)
        barrier_arrived = sync.barrier_arrived.at[arr_tgt].add(
            jnp.where(new_arrival, 1, 0)
        )
        barrier_time = sync.barrier_time_ps.at[arr_tgt].max(
            jnp.where(new_arrival, core.clock_ps, 0)
        )
        release_bar = (barrier_count > 0) & (barrier_arrived >= barrier_count)
        participant = is_bwait & (sync.barrier_waiting | new_arrival) & ~done
        released = participant & release_bar[bar]
        release_time = barrier_time[bar]
        barrier_waiting = ((sync.barrier_waiting
                            | (new_arrival & ~arrive_only)) & ~released)
        # the split form's rendezvous: wait for the given release
        # generation, then take THAT generation's release time (per-gen
        # ring; see state.GEN_RING)
        from graphite_tpu.engine.state import GEN_RING

        barrier_gen = sync.barrier_gen + release_bar.astype(jnp.int32)
        slot = nn_mod(barrier_gen, GEN_RING).astype(jnp.int32)
        n_bars_r = jnp.arange(n_bars, dtype=jnp.int32)
        cur_slot = sync.barrier_release_ps[n_bars_r, slot]
        barrier_release = sync.barrier_release_ps.at[n_bars_r, slot].set(
            jnp.where(release_bar, barrier_time, cur_slot))
        bsync_now = active & is_bsync & (barrier_gen[bar] >= aux1)
        bsync_time = barrier_release[
            bar, (aux1 % GEN_RING).astype(jnp.int32)]
        # reset released barriers
        barrier_arrived = jnp.where(release_bar, 0, barrier_arrived)
        barrier_time = jnp.where(release_bar, 0, barrier_time)
        return (barrier_count, barrier_arrived, barrier_time,
                barrier_waiting, released, release_time,
                barrier_gen, barrier_release, arrive_only, bsync_now,
                bsync_time)

    def _barrier_skip(_):
        return (sync.barrier_count, sync.barrier_arrived,
                sync.barrier_time_ps, sync.barrier_waiting,
                jnp.zeros((T,), jnp.bool_), jnp.zeros((T,), I64),
                sync.barrier_gen, sync.barrier_release_ps,
                jnp.zeros((T,), jnp.bool_), jnp.zeros((T,), jnp.bool_),
                jnp.zeros((T,), I64))

    with scope("gt.sync.barrier"):
        (barrier_count, barrier_arrived, barrier_time, barrier_waiting,
         released, release_time, barrier_gen, barrier_release_ps,
         barrive_now, bsync_now, bsync_time) = lax.cond(
            px.any_sim(jnp.any(
                active & (is_binit | is_bwait | is_barrive | is_bsync))),
            _barrier_block, _barrier_skip, None)
    barrier_wait_ps = jnp.maximum(release_time - core.clock_ps, 0)
    barrier_wait_ps = jnp.where(released, barrier_wait_ps, 0)
    bsync_wait_ps = jnp.where(
        bsync_now, jnp.maximum(bsync_time - core.clock_ps, 0), 0)

    # --- MUTEX + COND ----------------------------------------------------
    # One gated block: condition variables interlock with mutexes
    # (COND_WAIT releases its mutex; a signaled waiter re-acquires it —
    # `sync_server.cc` SimCond::wait/signal/broadcast + SimMutex).
    NM = sync.mutex_locked.shape[0]
    NC = params.n_conds
    is_cwait = op == Op.COND_WAIT
    is_csig = op == Op.COND_SIGNAL
    is_cbcast = op == Op.COND_BROADCAST
    is_cinit = op == Op.COND_INIT
    BIG = jnp.asarray(2**62, I64)

    def _mutex_cond_block(_):
        mux = jnp.clip(aux0, 0, NM - 1)       # mutex ops' mutex id
        cw_mux = jnp.clip(aux1, 0, NM - 1)    # COND_WAIT's mutex id (aux1)
        cid = jnp.clip(aux0, 0, NC - 1)       # cond ops'/waiters' cond id
        minit_now = active & is_minit
        mutex_locked = sync.mutex_locked.at[mux].add(
            jnp.where(minit_now, -sync.mutex_locked[mux], 0)
        )
        # COND_WAIT arrival: join the FIFO (key = arrival time) and release
        # the mutex below (`SimCond::wait` pushes the waiter then unlocks)
        cwait_arrive = (active & is_cwait
                        & ~sync.cond_waiting & ~sync.cond_signaled)
        cond_waiting = sync.cond_waiting | cwait_arrive
        cond_arrival = jnp.where(
            cwait_arrive, core.clock_ps, sync.cond_arrival_ps)

        # --- signal/broadcast posting --------------------------------------
        # Engine-iteration order is NOT simulated-time order (a tile can be
        # behind in records yet ahead in time), so signals park in per-cond
        # pending slots stamped with their simulated time; delivery below
        # resolves them in simulated-time order.  One signal per cond per
        # iteration is accepted (the earliest by (time, tile)); surplus
        # same-iteration signalers simply do not commit their record and
        # retry next iteration (clock unchanged — timing unaffected).
        psig = sync.cond_sig_time_ps            # [NC, K], FAR = empty
        pbc = sync.cond_bcast_time_ps           # [NC],    FAR = none
        # COND_INIT resets the cond's pending state
        cinit_now = active & is_cinit
        init_cond = jnp.zeros((NC,), jnp.bool_).at[cid].max(cinit_now)
        psig = jnp.where(init_cond[:, None], BIG, psig)
        pbc = jnp.where(init_cond, BIG, pbc)
        # published (aux1>0) signals use the co-located split machinery
        # below, not the pending-slot delivery
        sig_now = active & is_csig & (aux1 <= 0)
        bcast_now = active & is_cbcast & (aux1 <= 0)
        post_key = core.clock_ps * jnp.asarray(T, I64) + tiles.astype(I64)
        sbest = _elect_min(sig_now, cid, post_key, NC)
        sig_elect = sig_now & (post_key == sbest[cid])
        free = psig >= FAR_FUTURE_PS            # [NC, K]
        have_free = free.any(axis=1)
        free_k = jnp.argmax(free, axis=1).astype(jnp.int32)
        sig_post = sig_elect & have_free[cid]
        psig = psig.at[cid, free_k[cid]].min(
            jnp.where(sig_post, core.clock_ps, BIG))
        bbest = _elect_min(bcast_now, cid, post_key, NC)
        bc_elect = bcast_now & (post_key == bbest[cid])
        bc_post = bc_elect & (pbc[cid] >= FAR_FUTURE_PS)
        pbc = pbc.at[cid].min(jnp.where(bc_post, core.clock_ps, BIG))

        # --- delivery / drop, in simulated-time order ----------------------
        # A pending signal S wakes the earliest eligible waiter (wait began
        # at W <= S).  Resolution waits until engine order can no longer
        # contradict simulated-time order: deliver when the chosen waiter's
        # W is at or before every still-running tile's clock (a later
        # registrant could at best tie, and simultaneous wait/signal is a
        # race even in the reference), and drop as LOST when every
        # still-running tile has reached S with no eligible waiter.
        # Comparisons are NON-strict: a tile pinned exactly at the post time
        # (e.g. the poster blocked on a join) must not hold delivery forever.
        # A pending broadcast and pending signals on one cond resolve in
        # simulated-time order, one per iteration — the earlier wakes first
        # and the later re-evaluates against the remaining waiters.
        runner = ~done & ~cond_waiting & ~sync.cond_signaled
        min_active = jnp.min(jnp.where(runner, core.clock_ps, BIG))
        S = jnp.min(psig, axis=1)               # [NC] earliest pending
        s_k = jnp.argmin(psig, axis=1).astype(jnp.int32)
        bc_time = pbc                           # [NC]
        have_sig = (S < FAR_FUTURE_PS) & (S < bc_time)  # signal resolves 1st
        bc_first = (bc_time < FAR_FUTURE_PS) & (bc_time <= S)
        elig = cond_waiting & (cond_arrival <= S[cid])
        wake_key = cond_arrival * jnp.asarray(T, I64) + tiles.astype(I64)
        ckey = jnp.where(elig, wake_key, BIG)
        cbest = _elect_min(elig, cid, ckey, NC)
        any_elig = cbest < BIG
        best_arrival = cbest // jnp.asarray(T, I64)
        safe_deliver = have_sig & any_elig & (best_arrival <= min_active)
        lost = have_sig & ~any_elig & (min_active >= S)
        woken_s = elig & safe_deliver[cid] & (ckey == cbest[cid])
        clear_slot = safe_deliver | lost
        psig = psig.at[jnp.arange(NC), s_k].max(
            jnp.where(clear_slot, BIG, 0))
        # pending broadcast: wakes every waiter with W <= S_bcast
        bc_ready = bc_first & (min_active >= bc_time)
        woken_b = (cond_waiting & bc_ready[cid]
                   & (cond_arrival <= bc_time[cid]) & ~woken_s)
        pbc = jnp.where(bc_ready, BIG, pbc)

        woken = woken_b | woken_s
        cond_wake = jnp.where(
            woken_b, bc_time[cid],
            jnp.where(woken_s, S[cid], sync.cond_wake_ps))
        cond_signaled = sync.cond_signaled | woken
        cond_waiting = cond_waiting & ~woken

        # lock candidates: MUTEX_LOCK lanes + signaled COND_WAIT lanes
        # re-acquiring their mutex (`SimCond::signal` → `SimMutex::lock`)
        relock = is_cwait & ~done & cond_signaled
        plain_lock = is_mlock & ~done & (sync.mutex_waiting | active)
        lock_candidate = plain_lock | relock
        lmux = jnp.where(relock, cw_mux, mux)
        eff_clock = jnp.where(
            relock, jnp.maximum(core.clock_ps, cond_wake), core.clock_ps)
        grant_key = eff_clock * jnp.asarray(T, I64) + tiles.astype(I64)
        best_key = _elect_min(lock_candidate, lmux, grant_key, NM)
        grantable = mutex_locked == 0
        # Time-order completeness guard (mirrors cond delivery): a grant
        # may only commit when nothing can still produce an earlier
        # (time, tile) request for ANY mutex:
        #  - lanes at non-blocking records will request at >= their current
        #    clock (conservatively keyed with tile 0);
        #  - candidates on other FREE mutexes could commit and re-emerge at
        #    their own (earlier) clock — so only the earliest candidate
        #    among grantable ones commits per iteration;
        #  - candidates on LOCKED mutexes re-emerge no earlier than their
        #    holder's future unlock (>= the holder's current clock), so
        #    they are bounded transitively through the holder and may be
        #    excluded — excluding them is also what keeps lock-ordered
        #    nesting deadlock-free (a waiter on a held mutex must not veto
        #    the holder's own acquisition of its next lock);
        #  - recv/join/barrier-parked lanes re-emerge at wake times bounded
        #    below by some running lane's clock, so they are covered by
        #    the advancing-lane bound transitively.
        # split-form rendezvous ops block too: their lanes re-emerge at
        # wake times bounded below by the publisher's clock, so they are
        # covered by the advancing-lane bound transitively (like recv)
        cur_blocking = (is_recv | is_join | is_bwait | is_mlock | is_cwait
                        | is_bsync | is_cjoin)
        advancing = ~done & ~cur_blocking
        min_adv_key = jnp.min(jnp.where(
            advancing, core.clock_ps * jnp.asarray(T, I64), BIG))
        free_cand_min = jnp.min(jnp.where(
            lock_candidate & grantable[lmux], grant_key, BIG))
        granted = (lock_candidate & grantable[lmux]
                   & (grant_key == best_key[lmux])
                   & (grant_key == free_cand_min)
                   & (grant_key <= min_adv_key))
        mutex_grab_time = sync.mutex_time_ps[lmux]
        # wait until: the mutex handoff, and for woken waiters the signal
        # time — clock_new = clock + wait = max(clock, wake, grab)
        wait_until = jnp.where(
            relock, jnp.maximum(mutex_grab_time, cond_wake),
            mutex_grab_time)
        mutex_wait_ps = jnp.maximum(wait_until - core.clock_ps, 0)
        mutex_wait_ps = jnp.where(granted, mutex_wait_ps, 0)
        # grant is unique per mutex (key includes tile id), unlock unique
        # per mutex (single owner), so add-deltas cannot double-apply
        mutex_locked = mutex_locked.at[lmux].add(jnp.where(granted, 1, 0))
        mutex_owner = sync.mutex_owner.at[lmux].add(
            jnp.where(granted, tiles - sync.mutex_owner[lmux], 0)
        )
        mutex_waiting = (plain_lock & ~granted) | (
            sync.mutex_waiting & ~is_mlock
        )
        cond_signaled = cond_signaled & ~granted  # commit clears the flag
        # unlock: explicit MUTEX_UNLOCK, or COND_WAIT arrival releasing its
        # mutex; stamp the handoff time (`sync_server.cc:211-240`)
        unlock_now = active & is_munlock
        un_do = unlock_now | cwait_arrive
        un_mux = jnp.where(cwait_arrive, cw_mux, mux)
        mutex_locked = mutex_locked.at[un_mux].add(jnp.where(un_do, -1, 0))
        mutex_owner = mutex_owner.at[un_mux].add(
            jnp.where(un_do, -1 - mutex_owner[un_mux], 0)
        )
        mutex_time = sync.mutex_time_ps.at[un_mux].add(
            jnp.where(un_do, core.clock_ps - sync.mutex_time_ps[un_mux], 0)
        )
        if px.sim_axis is not None:
            # The one thing this block does with every lane masked off:
            # it drops a pending signal or broadcast as LOST once every
            # running tile has reached its time.  A sim whose own
            # predicate is false keeps it pending, as its solo program
            # does (a waiter arriving AT the signal's time still takes
            # it), so that a neighbour's sync record cannot move it.
            psig = jnp.where(mc_own, psig, sync.cond_sig_time_ps)
            pbc = jnp.where(mc_own, pbc, sync.cond_bcast_time_ps)
        # The block moves state that no lane's commit shows: a COND_WAIT
        # arrival joins the FIFO and releases its mutex, a pending signal
        # wakes a waiter or is dropped, a lock request registers — each
        # can enable a commit in a LATER iteration, so an iteration that
        # only did that is not idle (`_quantum_loop` ends a quantum at
        # the first idle one).  Owner, handoff time, arrival and wake
        # times change only together with one of these.
        moved = (jnp.any(mutex_locked != sync.mutex_locked)
                 | jnp.any(mutex_waiting != sync.mutex_waiting)
                 | jnp.any(cond_waiting != sync.cond_waiting)
                 | jnp.any(cond_signaled != sync.cond_signaled)
                 | jnp.any(psig != sync.cond_sig_time_ps)
                 | jnp.any(pbc != sync.cond_bcast_time_ps))
        return (mutex_locked, mutex_owner, mutex_time, mutex_waiting,
                granted, mutex_wait_ps, cond_waiting, cond_signaled,
                cond_arrival, cond_wake, psig, pbc,
                sig_post | bc_post, moved)

    def _mutex_cond_skip(_):
        return (sync.mutex_locked, sync.mutex_owner, sync.mutex_time_ps,
                sync.mutex_waiting, jnp.zeros((T,), jnp.bool_),
                jnp.zeros((T,), I64), sync.cond_waiting, sync.cond_signaled,
                sync.cond_arrival_ps, sync.cond_wake_ps,
                sync.cond_sig_time_ps, sync.cond_bcast_time_ps,
                jnp.zeros((T,), jnp.bool_), jnp.asarray(False))

    with scope("gt.sync.mutex_cond"):
        mc_own = jnp.any(
            (active & (is_minit | is_munlock | is_csig
                       | is_cbcast | is_cinit))
            | (is_mlock & ~done & (sync.mutex_waiting | active))
            | (is_cwait & ~done))
        (mutex_locked, mutex_owner, mutex_time, mutex_waiting, granted,
         mutex_wait_ps, cond_waiting, cond_signaled, cond_arrival_ps,
         cond_wake_ps, cond_sig_time_ps, cond_bcast_time_ps,
         cond_post_commit, sync_moved) = lax.cond(
            px.any_sim(mc_own), _mutex_cond_block, _mutex_cond_skip, None)

    # --- published cond signals + COND_JOIN (co-located split form) ------
    # A publishing signal/broadcast bumps the cond's signal sequence and
    # stamps its time; COND_JOIN(k) waits for sequence >= k and takes the
    # stamped time (the waiter's wake).  The mutex dance around it uses
    # plain MUTEX_UNLOCK / MUTEX_LOCK records (see schema).
    # Same-iteration race contract: when two lanes publish to one cond in
    # the SAME subquantum iteration, both lanes read the post-scatter-add
    # sequence, so only the final sequence's ring slot is stamped (with
    # the max of both clocks) and the intermediate slot keeps its stale
    # time — a COND_JOIN on the intermediate sequence then takes a
    # bounded-stale timestamp.  Same class as the reference's racy
    # same-instant signal ordering (its MCP serves them in host-arrival
    # order); recorded traces order same-cond publishes through the
    # recording app's own locking, so the window is one engine iteration.
    pub_now = active & (is_csig | is_cbcast) & (aux1 > 0)

    def _pub_block(_):
        from graphite_tpu.engine.state import GEN_RING

        cid = jnp.clip(aux0, 0, NC - 1)
        # cond ids are allocated once per app run, so COND_INIT does not
        # reset the sequence (a publish record on another lane may replay
        # before a later-positioned init on the creator's lane)
        seq = sync.cond_sig_seq.at[jnp.where(pub_now, cid, 0)].add(
            jnp.where(pub_now, 1, 0))
        slot = nn_mod(seq[cid], GEN_RING).astype(jnp.int32)
        seq_ps = sync.cond_sig_seq_ps.at[
            jnp.where(pub_now, cid, 0),
            jnp.where(pub_now, slot, 0)].max(
            jnp.where(pub_now, core.clock_ps, 0))
        cjoin_now = active & is_cjoin & (seq[cid] >= aux1)
        cjoin_t = seq_ps[cid, (aux1 % GEN_RING).astype(jnp.int32)]
        return seq, seq_ps, cjoin_now, cjoin_t

    with scope("gt.sync.mutex_cond"):
        (cond_sig_seq, cond_sig_seq_ps, cjoin_now, cjoin_time) = lax.cond(
            px.any_sim(jnp.any(pub_now | (active & is_cjoin))),
            _pub_block,
            lambda _: (sync.cond_sig_seq, sync.cond_sig_seq_ps,
                       jnp.zeros((T,), jnp.bool_), jnp.zeros((T,), I64)),
            None)
    cjoin_wait_ps = jnp.where(
        cjoin_now, jnp.maximum(cjoin_time - core.clock_ps, 0), 0)

    # --- JOIN ------------------------------------------------------------
    # The target's liveness is read off its own fetched record (every
    # lane's current op is already in hand — same clipped index the fetch
    # used), so the old per-target trace re-gather is gone; a paused
    # streaming target's window-edge record must not read as THREAD_EXIT.
    at_exit = op == Op.THREAD_EXIT
    if in_window is not None:
        at_exit = at_exit & in_window

    def _join_block(_):
        join_target = jnp.clip(aux0, 0, T - 1)
        target_done = state.done[join_target] | at_exit[join_target]
        join_now = active & is_join & target_done
        join_time = jnp.maximum(core.clock_ps, core.clock_ps[join_target])
        return join_now, join_time

    with scope("gt.sync.join"):
        join_now, join_time = lax.cond(
            px.any_sim(jnp.any(active & is_join)), _join_block,
            lambda _: (jnp.zeros((T,), jnp.bool_), core.clock_ps), None)

    # --- commit: advance mask, clocks, counters --------------------------
    # Instruction records with memory operands commit only once all their
    # memory slots completed (`simple_core_model.cc:53-90`: the per-operand
    # latencies and the execution cost land on the clock together).
    instr_like = is_static | is_branch
    advance = active & (
        ((instr_like | is_bblock) & mem_ok) | (is_dynamic & ~is_spawn_instr)
        | is_simple_event | is_send
    )
    advance = advance | recv_now | released | (active & is_spawn_instr)
    advance = advance | granted | join_now | cond_post_commit
    advance = advance | barrive_now | bsync_now | cjoin_now | pub_now

    clock = core.clock_ps
    if params.iocoom is not None:
        # IOCOOM: instruction-like records go through the scoreboard /
        # load-store queue pipeline algebra; everything else (events,
        # dynamic, bblock) keeps the simple cost accumulation (the
        # reference adds dynamic costs directly, `iocoom_core_model.cc:88`)
        from graphite_tpu.models.iocoom import iocoom_commit

        slot_lat = (mem_out.slot_lat_ps if params.mem is not None
                    else jnp.zeros((T, 3), I64))
        # heterogeneous tiles: non-iocoom lanes take the simple path below
        ioc_tiles = (jnp.asarray(params.iocoom_tiles, jnp.bool_)
                     if params.iocoom_tiles is not None
                     else jnp.ones((T,), jnp.bool_))
        ioc_commit_mask = advance & instr_like & ioc_tiles
        new_ioc, ioc_clock, ioc_mem_stall, ioc_exec_stall = iocoom_commit(
            params.iocoom, state.ioc,
            commit=ioc_commit_mask,
            clock_ps=core.clock_ps,
            freq_mhz=core.freq_mhz.astype(I64),
            cost_ps=cost_ps,
            flags=flags,
            rreg0=fetched[-3].astype(jnp.int32),
            rreg1=fetched[-2].astype(jnp.int32),
            wreg=fetched[-1].astype(jnp.int32),
            addr0=(fetched[6] if params.mem is not None
                   else jnp.zeros((T,), jnp.uint32)),
            addr1=(fetched[7] if params.mem is not None
                   else jnp.zeros((T,), jnp.uint32)),
            slot_lat_ps=slot_lat,
            enabled=enabled,
        )
        simple_instr = instr_like & ~ioc_tiles
        clock = jnp.where(advance & (is_bblock
                                     | (is_dynamic & ~is_spawn_instr)
                                     | is_simple_event | is_send
                                     | simple_instr),
                          clock + cost_ps
                          + jnp.where(is_bblock | simple_instr,
                                      mem_acc_ps, 0),
                          clock)
        clock = jnp.where(ioc_commit_mask, ioc_clock, clock)
    else:
        new_ioc = state.ioc
        ioc_mem_stall = None
        ioc_exec_stall = None
        clock = jnp.where(advance & (instr_like | is_bblock
                                     | (is_dynamic & ~is_spawn_instr)
                                     | is_simple_event | is_send),
                          clock + cost_ps
                          + jnp.where(instr_like | is_bblock, mem_acc_ps, 0),
                          clock)
    clock = jnp.where(active & is_spawn_instr,
                      jnp.maximum(clock, dyn_ps), clock)
    clock = jnp.where(recv_now, jnp.maximum(clock, recv_time), clock)
    clock = jnp.where(released, jnp.maximum(clock, release_time), clock)
    clock = jnp.where(granted, clock + mutex_wait_ps, clock)
    clock = jnp.where(join_now, join_time, clock)
    clock = jnp.where(bsync_now, jnp.maximum(clock, bsync_time), clock)
    clock = jnp.where(cjoin_now, jnp.maximum(clock, cjoin_time), clock)

    # DVFS_SET retunes the target domain's frequency, validated against the
    # voltage/frequency tables (`DVFSManager::getVoltage`, technology
    # levels): AUTO picks the minimum voltage for the frequency; HOLD
    # (encoded aux1 < 0) fails if the frequency exceeds the current
    # voltage's maximum; invalid requests count into dvfs errors and leave
    # state unchanged (`dvfs.h` rc codes -2/-4/-5).
    is_dvfs_set = op == Op.DVFS_SET
    # runtime DVFS (round 19): with a spec + carry attached, successful
    # DVFS_SET requests additionally elect the chip-global per-domain
    # operating point — the dmask cond output exists ONLY then (python-
    # level gate), so dvfs=None lowers the historical cond byte-identically
    want_rt = dvfs is not None and state.dvfs_rt is not None
    new_rt = state.dvfs_rt
    # energy accounting: a tile whose request succeeds closes its open
    # interval at the operating point that WAS in force — inside the
    # taken arm, so an iteration without a request pays nothing (same
    # python-level gate: power off lowers the historical cond)
    want_energy = params.energy is not None and state.energy is not None
    new_energy = state.energy
    if params.dvfs is not None and state.dvfs is not None:
        dvp = params.dvfs
        ND = dvp.n_domains

        def _dvfs_block(_):
            req = jnp.abs(aux1)
            hold = aux1 < 0
            dom = jnp.clip(aux0, 0, ND - 1)
            valid_dom = (aux0 >= 0) & (aux0 < ND)
            volts = jnp.asarray(dvp.voltages_mv, jnp.int32)   # [L] desc
            maxf = jnp.asarray(dvp.max_freq_mhz, jnp.int32)   # [L] desc
            L = len(dvp.voltages_mv)
            ok_levels = req[:, None] <= maxf[None, :]         # [T, L]
            freq_ok = ok_levels.any(axis=1) & (req > 0)
            # minimum voltage = last satisfying level (descending tables)
            lvl = (L - 1) - jnp.argmax(
                ok_levels[:, ::-1], axis=1).astype(jnp.int32)
            auto_v = volts[jnp.clip(lvl, 0, L - 1)]
            cur_v = state.dvfs.voltage_mv[tiles, dom]
            cur_lvl = jnp.argmax(
                volts[None, :] == cur_v[:, None], axis=1).astype(jnp.int32)
            hold_ok = req <= maxf[cur_lvl]
            attempt = active & is_dvfs_set
            ok = attempt & valid_dom & freq_ok & (~hold | hold_ok)
            err = attempt & ~(valid_dom & freq_ok & (~hold | hold_ok))
            new_v = jnp.where(hold, cur_v, auto_v)
            dmask = (dom[:, None] == jnp.arange(ND, dtype=jnp.int32)[None, :]
                     ) & ok[:, None]
            freq2 = jnp.where(dmask, req[:, None], state.dvfs.freq_mhz)
            volt2 = jnp.where(dmask, new_v[:, None], state.dvfs.voltage_mv)
            errs2 = state.dvfs.errors + err.astype(I64)
            core_set = ok & (dom == dvp.core_domain)
            out = (freq2, volt2, errs2, core_set, req)
            if want_rt:
                out = out + (dmask,)
            if want_energy:
                from graphite_tpu.power.accounting import (
                    close_interval, raw_counts,
                )

                with scope("gt.energy"):
                    en = state.energy
                    raw_now = raw_counts(
                        jnp, params.energy, core, net.packets_sent,
                        None if state.mem is None else state.mem.counters)
                    closed = close_interval(
                        jnp, params.energy, raw_now, core.clock_ps,
                        state.dvfs.voltage_mv, en.last_raw,
                        en.last_clock_ps)
                    out = out + (
                        en.acc + jnp.where(ok[:, None], closed, 0),
                        jnp.where(ok[:, None], raw_now, en.last_raw),
                        jnp.where(ok, core.clock_ps, en.last_clock_ps))
            return out

        def _dvfs_skip(_):
            out = (state.dvfs.freq_mhz, state.dvfs.voltage_mv,
                   state.dvfs.errors, jnp.zeros((T,), jnp.bool_),
                   jnp.zeros((T,), aux1.dtype))
            if want_rt:
                out = out + (jnp.zeros((T, ND), jnp.bool_),)
            if want_energy:
                en = state.energy
                out = out + (en.acc, en.last_raw, en.last_clock_ps)
            return out

        with scope("gt.dvfs"):
            dvfs_out = lax.cond(
                px.any_sim(jnp.any(active & is_dvfs_set)),
                _dvfs_block, _dvfs_skip, None)
        (dv_freq, dv_volt, dv_errs, dvfs_core_set, dvfs_req) = dvfs_out[:5]
        new_dvfs = state.dvfs.replace(
            freq_mhz=dv_freq, voltage_mv=dv_volt, errors=dv_errs)
        if want_energy:
            new_energy = state.energy.replace(
                acc=dvfs_out[-3], last_raw=dvfs_out[-2],
                last_clock_ps=dvfs_out[-1])
        if want_rt:
            from graphite_tpu.dvfs.runtime import (
                core_freq_tiles, elect_domains,
            )

            with scope("gt.dvfs"):
                new_rt = elect_domains(dvp, state.dvfs_rt, dvfs_req,
                                       dvfs_out[5])
                # chip-global CORE domain: the elected frequency broadcasts
                # to every tile (the per-tile table above stays the legacy
                # get/set view)
                freq_mhz = core_freq_tiles(dvp, new_rt, core.freq_mhz)
        else:
            freq_mhz = jnp.where(
                dvfs_core_set, dvfs_req.astype(core.freq_mhz.dtype),
                core.freq_mhz)
    else:
        new_dvfs = state.dvfs
        dvfs_set_now = active & is_dvfs_set & (aux0 == 0) & (aux1 > 0)
        freq_mhz = jnp.where(dvfs_set_now, aux1, core.freq_mhz)

    # --- plain-run batching (per-instruction streams) --------------------
    # A lane whose record committed may commit up to plain_unroll-1
    # FOLLOW-ON records in the same iteration when they are PLAIN static
    # costs (op <= MFENCE, not BRANCH): no machinery, no memory slots, no
    # predictor state — pure additive cost, so batching is bit-exact (per
    # record ceil cycles->ps conversion, accumulated clock must stay
    # before qend exactly like the per-iteration `active` check; a DVFS
    # retune is an event, so the batch always runs at one frequency).
    # This is runtime BBLOCK compression for externally captured
    # per-instruction traces — the streamed replay's floor (PERF.md).
    # (lax_p2p excluded: its pairwise clamp is a PER-ITERATION hold, so
    # batching extra records would overrun the slack bound)
    if (params.plain_unroll > 1 and params.mem is None
            and params.iocoom is None and params.p2p_slack_ps is None
            and trace.length > 1):
        # short traces (compressed benchmark skeletons) bound the window;
        # PLAIN_UNROLL_MAX clamps configs past the measured-safe ceiling
        # (the follow-on gather regresses superlinearly above it)
        KX = min(params.plain_unroll - 1, PLAIN_UNROLL_MAX - 1,
                 trace.length - 1)
        offs = np.arange(1, KX + 1, dtype=np.int32)
        pos_l = jnp.minimum(idx_l[:, None] + offs[None, :],
                            trace.length - 1)
        # lockstep fast path (same trick as the record fetch): one
        # dynamic column slice instead of a per-row gather; the gather
        # runs when lanes diverged or the slice would clamp at the edge
        ok_uniform = uniform & (idx[0] + 1 + KX <= trace.length)
        with scope("gt.fetch"):
            ops_x_l = lax.cond(
                ok_uniform,
                lambda _: lax.dynamic_slice_in_dim(
                    trace.op, idx[0] + 1, KX, axis=1),
                lambda _: jnp.take_along_axis(trace.op, pos_l, axis=1),
                None)
            ops_x = px.ag(ops_x_l).astype(jnp.int32)
        valid = (idx[:, None] + offs[None, :]) < trace.length
        plain = valid & (ops_x <= int(Op.MFENCE)) & (
            ops_x != int(Op.BRANCH))
        cycles_x = cost_table[jnp.clip(ops_x, 0, 19)]
        cost_x = cycles_to_ps(cycles_x, freq_mhz.astype(I64)[:, None])
        # the CURRENT record may be an ENABLE/DISABLE_MODELS event — its
        # follow-ons run under the POST-event model state (same formula
        # the commit applies to state.models_enabled below)
        en_post = jnp.where(
            jnp.any(active & (op == Op.DISABLE_MODELS)), False,
            jnp.where(jnp.any(active & (op == Op.ENABLE_MODELS)), True,
                      enabled))
        cost_x = jnp.where(en_post, cost_x, 0)
        cum_before = clock[:, None] + jnp.cumsum(cost_x, axis=1) - cost_x
        commit_x = (plain & (cum_before < quantum_end_ps)
                    & advance[:, None])
        commit_x = jnp.cumprod(commit_x.astype(jnp.int32), axis=1) > 0
        extra_n = commit_x.sum(axis=1).astype(jnp.int32)
        extra_charged = jnp.where(en_post, extra_n, 0)
        extra_cost = jnp.where(commit_x, cost_x, 0).sum(axis=1)
        clock = clock + extra_cost
    else:
        extra_n = jnp.zeros((T,), jnp.int32)
        extra_charged = extra_n
        extra_cost = jnp.zeros((T,), I64)

    instr_now = advance & (is_static | is_branch
                           | (is_dynamic & ~is_spawn_instr))
    recv_charged = recv_now & (recv_wait_ps > 0) & enabled
    sync_charged = (released & (barrier_wait_ps > 0) | granted
                    & (mutex_wait_ps > 0)
                    | (bsync_now & (bsync_wait_ps > 0))
                    | (cjoin_now & (cjoin_wait_ps > 0))) & enabled

    # --- latency histograms (round 21): commit-site scatter-add ----------
    # Python-level gate: hist=None adds zero ops and zero carry leaves,
    # so the off program lowers byte-identically (the hist-off lint).
    # The recording masks are the counter-increment masks above — the
    # conservation invariant obs/hist.conservation_totals documents.
    new_hist = state.hist
    if hist is not None:
        from graphite_tpu.obs.hist import hist_commit_update

        mem_kw = {}
        if params.mem is not None:
            mem_kw = dict(
                present=slots_present(mem_p, rec, enabled),
                slot_lat_ps=mem_out.slot_lat_ps,
                # per-call miss completions from the engine's phase-6
                # fill delta (MemStepOut.fill_now) — an entry/exit phase
                # comparison would miss transactions that start AND fill
                # within one engine call
                miss_now=mem_out.fill_now & enabled,
                miss_lat_ps=mem_out.fill_lat_ps,
            )
        with scope("gt.obs"):
            new_hist = hist_commit_update(
                hist, state.hist,
                advance=advance, enabled=enabled,
                recv_now=recv_now, recv_lat_ps=recv_lat,
                recv_charged=recv_charged, recv_wait_ps=recv_wait_ps,
                sync_charged=sync_charged,
                sync_wait_ps=(barrier_wait_ps + mutex_wait_ps
                              + bsync_wait_ps + cjoin_wait_ps),
                px=px, **mem_kw)

    new_core = core.replace(
        clock_ps=clock,
        freq_mhz=freq_mhz,
        idx=core.idx + advance.astype(jnp.int32) + extra_n,
        instruction_count=core.instruction_count
        + (instr_now & enabled).astype(I64)
        + extra_charged.astype(I64)
        + jnp.where(advance & is_bblock & enabled, aux0.astype(I64), 0)
        + recv_charged.astype(I64)
        + sync_charged.astype(I64),
        memory_stall_ps=core.memory_stall_ps
        + (jnp.where(advance & (is_bblock | simple_instr), mem_acc_ps, 0)
           + ioc_mem_stall
           if params.iocoom is not None else
           jnp.where(advance & (instr_like | is_bblock), mem_acc_ps, 0)),
        execution_stall_ps=core.execution_stall_ps + extra_cost
        + (jnp.where(advance & (is_bblock | simple_instr), cost_ps, 0)
           + ioc_exec_stall
           if params.iocoom is not None else
           jnp.where(advance & (is_static | is_branch | is_bblock),
                     cost_ps, 0)),
        recv_instructions=core.recv_instructions + recv_charged.astype(I64),
        recv_stall_ps=core.recv_stall_ps
        + jnp.where(recv_charged, recv_wait_ps, 0),
        sync_instructions=core.sync_instructions + sync_charged.astype(I64),
        sync_stall_ps=core.sync_stall_ps
        + jnp.where(released & enabled, barrier_wait_ps, 0)
        + jnp.where(granted & enabled, mutex_wait_ps, 0)
        + jnp.where(enabled, bsync_wait_ps + cjoin_wait_ps, 0),
        # delta-add (uint8 modular): old + (taken - old) == taken; avoids a
        # second gather of bp_bits inside the scatter so the buffer updates
        # in place ((tiles, bp_index) pairs are unique per lane); applied
        # block-local under a sharded px
        bp_bits=px.lane_col_add(
            core.bp_bits, *px.lo((
                bp_index,
                jnp.where(active & is_branch & enabled, taken - bp_pred, 0)
                .astype(jnp.uint8)))),
        bp_correct=core.bp_correct
        + (active & is_branch & bp_correct_now & enabled).astype(I64),
        bp_incorrect=core.bp_incorrect
        + (active & is_branch & ~bp_correct_now & enabled).astype(I64),
    )
    new_net = net.replace(
        time_ps=time_ps_new,
        lat_ps=lat_arr_new,
        head=head_new,
        count=count_new,
        overflow=overflow,
        packets_sent=net.packets_sent + send_now.astype(I64),
        packets_received=net.packets_received + recv_now.astype(I64),
        total_latency_ps=net.total_latency_ps
        + jnp.where(recv_now, recv_lat.astype(I64), 0),
    )
    new_sync = sync.replace(
        barrier_count=barrier_count,
        barrier_arrived=barrier_arrived,
        barrier_time_ps=barrier_time,
        barrier_waiting=barrier_waiting,
        barrier_gen=barrier_gen,
        barrier_release_ps=barrier_release_ps,
        cond_sig_seq=cond_sig_seq,
        cond_sig_seq_ps=cond_sig_seq_ps,
        mutex_locked=mutex_locked,
        mutex_owner=mutex_owner,
        mutex_time_ps=mutex_time,
        mutex_waiting=mutex_waiting,
        cond_waiting=cond_waiting,
        cond_signaled=cond_signaled,
        cond_arrival_ps=cond_arrival_ps,
        cond_wake_ps=cond_wake_ps,
        cond_sig_time_ps=cond_sig_time_ps,
        cond_bcast_time_ps=cond_bcast_time_ps,
    )
    enable_now = jnp.any(active & (op == Op.ENABLE_MODELS))
    disable_now = jnp.any(active & (op == Op.DISABLE_MODELS))
    models_enabled = jnp.where(
        disable_now, False, jnp.where(enable_now, True, state.models_enabled)
    )
    if params.mem is not None:
        # reset the per-record slot machinery on commit
        mem_state = mem_state.replace(req=mem_state.req.replace(
            slot=jnp.where(advance, 0, mem_state.req.slot),
            acc_ps=jnp.where(advance, 0, mem_state.req.acc_ps),
            slot_lat_ps=jnp.where(
                advance[:, None], 0, mem_state.req.slot_lat_ps),
        ))
    new_state = SimState(
        core=new_core,
        net=new_net,
        sync=new_sync,
        models_enabled=models_enabled,
        done=done,
        mem=mem_state,
        noc_user=noc_user,
        ioc=new_ioc,
        dvfs=new_dvfs,
        p2p_round=p2p_round,
        # telemetry + profile rings ride the carry untouched here; the
        # OUTER quantum loop appends rows (obs.telemetry_tick /
        # obs.profile_tick) — None adds no leaves
        telemetry=state.telemetry,
        profile=state.profile,
        dvfs_rt=new_rt,
        hist=new_hist,
        energy=new_energy,
    )
    return new_state, (jnp.sum(advance, dtype=jnp.int32) + mem_progress
                       + sync_moved.astype(jnp.int32))


def _quantum_loop(params, trace, state, qend, trace_base=None, px=IDENT,
                  knobs=None, dvfs=None, hist=None):
    """One quantum: iterations until one in which no tile makes progress,
    in blocks of at most `inner_block` (the staging flush's cadence).
    Returns (state, total_progress, n_iterations, idle_iterations); the
    last counts the iterations in which nothing advanced.

    An iteration that advances nothing leaves the state at a fixed point
    (every later one would advance nothing either), so a quantum of N
    working iterations runs N + 1: the block stops after the first idle
    iteration and the quantum stops with it.  Under a sim axis the block
    runs while ANY sim of the program advanced (`px.any_sim`, one scalar
    trip count a program); each sim's quantum still ends on its own idle
    iteration.

    `lax_p2p` is the exception: `p2p_round` advances every iteration and
    redraws every tile's partner, so an idle iteration is no fixed point
    there.  It keeps whole blocks of `inner_block` iterations and ends a
    quantum on a block whose summed progress is 0."""
    whole_blocks = params.p2p_slack_ps is not None

    def block(state):
        # A while_loop, NOT a lax.scan: a scan's body is multiplied by
        # `length` in the static cost model's dense-iteration view
        # (analysis/cost.py), which would price a 32-iteration BLOCK
        # where the budgets name the protocol iteration; a while body
        # counts once.
        def body(carry):
            st, prog, idle, _, _, i = carry
            st, adv = subquantum_iteration(params, trace, st, qend,
                                           trace_base, px=px, knobs=knobs,
                                           dvfs=dvfs, hist=hist)
            return (st, prog + adv, idle + (adv == 0), adv,
                    px.any_sim(adv > 0), i + 1)

        def more(carry):
            *_, live, i = carry
            in_block = i < params.inner_block
            return in_block if whole_blocks else in_block & live

        staged = (params.mem is not None
                  and getattr(params.mem, "dir_stage_cap", 0))
        flush_gate = staged and params.mem.phase_gate
        if flush_gate:
            base_skips0 = state.mem.base_skips[0]
        zero = jnp.asarray(0, jnp.int32)
        state, progress, idle, last_adv, _, trips = lax.while_loop(
            more, body,
            (state, zero, zero, zero, jnp.asarray(True), zero))
        if staged:
            # One amortized dense pass applies the block's staged
            # directory writes (memory/engine.dir_stage_flush); capacity
            # covers a full block, so flushing here is always in time.
            # Never under a lax.cond: a cond that returns the multi-GB
            # sharers store double-buffers it in HBM (the pathology that
            # disables mem_gate at this scale).  Gated IN PLACE instead
            # (engine._run_if), by the home-activity gate: a slot is
            # staged only by a home phase, a home phase runs only in an
            # iteration whose base ran, and the table is empty at block
            # entry (every block ends here) — so a block whose `trips`
            # iterations (fewer than `inner_block` where it stopped at
            # an idle one) ALL skipped the base staged nothing, and its
            # flush would drop every slot.  The counter is replicated
            # control state (unlike the block-local `sn`), so every
            # device of a mesh takes the same arm.  Under a sim
            # axis it counts the program's skips (the OR-ed `home_live`),
            # but it rides the batched state: reduced over the sims so
            # the in-place loop's predicate stays a scalar.
            from graphite_tpu.memory.engine import (
                FLUSH_SKIPPED, dir_stage_flush,
            )

            mem = state.mem
            flush_live = None
            if flush_gate:
                flush_live = px.any_sim(mem.base_skips[0] - base_skips0
                                        < trips)
                mem = mem.replace(base_skips=mem.base_skips + jnp.where(
                    flush_live, 0, FLUSH_SKIPPED))
            with scope("gt.mem.stage_flush"):
                state = state.replace(mem=mem.replace(
                    directory=dir_stage_flush(mem.directory, flush_live)))
        # what the quantum goes on for: the block's whole progress under
        # lax_p2p, else its last iteration's
        return (state, progress, idle, progress if whole_blocks else last_adv,
                trips)

    def cond(carry):
        _, _, again, _, _ = carry
        return again > 0

    def body(carry):
        st, total, _, iters, idle = carry
        st, blk, blk_idle, again, trips = block(st)
        return st, total + blk, again, iters + trips, idle + blk_idle

    state, total, _, iters, idle = lax.while_loop(
        cond, body,
        (state, jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32),
         jnp.asarray(0, jnp.int64), jnp.asarray(0, jnp.int64)))
    return state, total, iters, idle


def run_quantum(
    params: EngineParams, trace: DeviceTrace, state: SimState, qend: jax.Array
) -> SimState:
    """Run one lax-barrier quantum as a single compiled XLA region.

    Runs subquantum iterations under a while_loop until one in which no
    tile makes progress (all done, all past the quantum boundary, or —
    transiently — all blocked on messages that can only arrive next
    quantum); see `_quantum_loop`.  The quantum of `clock_skew_management/
    lax_barrier` (`carbon_sim.cfg:92-97`).  Deliberately NOT a module-level
    `jit(static_argnums=0)`: jitting here with dataclass static args hits a
    jax-0.9 dispatch bug (constant-buffer miscount after topology changes);
    callers jit a closure instead (see `make_simulation_runner`).
    """
    return _quantum_loop(params, trace, state, qend)[0]


def run_simulation(
    params: EngineParams,
    trace: DeviceTrace,
    state: SimState,
    quantum_ps: "int | jax.Array | None",
    max_quanta: int = 1_000_000,
    trace_base: jax.Array | None = None,
    px: ParallelCtx = IDENT,
    knobs=None,
    telemetry=None,
    profile=None,
    dvfs=None,
    hist=None,
):
    """The whole simulation as ONE compiled region: an outer while_loop over
    lax-barrier quanta (the MCP barrier loop, `lax_barrier_sync_server.h`)
    wrapping the per-quantum progress loop.

    `quantum_ps` may be a TRACED int64 scalar (the sweep's quantum knob):
    boundary math is pure arithmetic, so a per-point quantum rides the
    same compiled program.  `knobs` (sweep.Knobs) likewise threads traced
    timing scalars into the memory engines; see subquantum_iteration.

    Device-driven on purpose: every per-quantum control read is a
    host↔device round trip (its cost is not measured on the current
    machine).  Loop control (next quantum
    boundary, zero-progress/deadlock detection, overflow) is computed on
    device; the host reads back one final state.

    Returns (state, n_quanta, deadlock flag, n_iterations,
    idle_iterations) — deadlock means a quantum made zero progress while
    some tile was eligible to run (same condition the reference debugs
    with its progress trace, `pin/progress_trace.cc`); the two counts are
    the subquantum iterations run and those of them in which nothing
    advanced (`_quantum_loop`).

    `telemetry` (a RESOLVED obs.TelemetrySpec; state.telemetry must hold
    the matching TelemetryState) appends one row to the device-resident
    timeline ring whenever a quantum crosses a `sample_interval_ps`
    simulated-time boundary — the reference's statistics-thread sampling
    points, recorded with zero host sync.  None (the default) lowers a
    bit-identical program (the round-7 knobs=None contract; enforced by
    the telemetry-off audit lint).

    `profile` (a RESOLVED obs.ProfileSpec; state.profile must hold the
    matching ProfileState) appends one [T, m] per-tile row to the
    spatial profile ring on the SAME simulated-time boundaries — the
    second ring of the round-16 spatial profiler.  None (the default)
    lowers a bit-identical program (the `profile-off` audit lint).

    `dvfs` (a RESOLVED dvfs.DvfsSpec; state.dvfs_rt must hold the
    matching DvfsRtState) turns on the runtime DVFS manager: carried
    per-domain frequencies feed the timing conversions, in-trace
    DVFS_SET events retune, the optional governor steps the V/f ladder
    at quantum boundaries, and (with scale_energy) the energy series
    prices each domain at its current V²·f operating point.  None (the
    default) lowers a bit-identical program (the `dvfs-off` audit lint).

    `hist` (a RESOLVED obs.HistSpec; state.hist must hold the matching
    HistState) records the latency histograms: the commit-site sources
    scatter inside `subquantum_iteration` and the boundary sources
    (clock skew, energy deltas) sample here every executed quantum.
    None (the default) lowers a bit-identical program (the `hist-off`
    audit lint).
    """
    if telemetry is not None:
        from graphite_tpu.obs.telemetry import telemetry_tick
    if profile is not None:
        from graphite_tpu.obs.profile import profile_tick
    if hist is not None:
        from graphite_tpu.obs.hist import hist_boundary_tick
    if dvfs is not None:
        from graphite_tpu.dvfs.runtime import core_freq_tiles, governor_tick
    # energy terms price at the carried operating point only when asked
    dvfs_energy = (params.dvfs
                   if dvfs is not None and dvfs.scale_energy else None)
    INF_QEND = jnp.asarray(2**61, I64)
    if quantum_ps is None:
        qps = None
    elif isinstance(quantum_ps, jax.Array):
        qps = quantum_ps          # traced sweep knob (int64 scalar)
    else:
        qps = int(quantum_ps)

    def next_boundary(clock):
        return (clock // qps + 1) * qps

    def cond(carry):
        st, qend, n, deadlock, stalled, _, _ = carry
        return (
            ~jnp.all(st.done)
            & ~st.net.overflow
            & ~deadlock
            & ~stalled
            & (n < max_quanta)
        )

    def body(carry):
        st, prev_qend, n, deadlock, stalled, iters, idle = carry
        clocks = st.core.clock_ps
        not_done = ~st.done
        min_pending = jnp.min(jnp.where(not_done, clocks, jnp.asarray(2**62, I64)))
        if qps is None:
            qend = INF_QEND
        else:
            qend = jnp.maximum(prev_qend + qps, next_boundary(min_pending))
        st2, progress, blk_iters, blk_idle = _quantum_loop(
            params, trace, st, qend, trace_base, px=px, knobs=knobs,
            dvfs=dvfs, hist=hist)
        if dvfs is not None and dvfs.governor is not None:
            # reactive governor: step the governed domains' V/f level on
            # the utilization window — masked arithmetic only (the
            # telemetry_tick pattern), evaluated at the quantum boundary
            with scope("gt.dvfs"):
                rt2 = governor_tick(dvfs.governor, params.dvfs,
                                    st2.dvfs_rt, st2)
                st2 = st2.replace(
                    dvfs_rt=rt2,
                    core=st2.core.replace(freq_mhz=core_freq_tiles(
                        params.dvfs, rt2, st2.core.freq_mhz)))
        if telemetry is not None:
            with scope("gt.obs"):
                st2 = st2.replace(telemetry=telemetry_tick(
                    telemetry, st2, progress=progress, blk_iters=blk_iters,
                    dvfs=dvfs_energy))
        if profile is not None:
            # same boundary arithmetic as the telemetry tick — with
            # equal intervals XLA CSEs the shared scalar reductions, so
            # the two rings cost one boundary test per quantum; under a
            # tile-sharded px the [S, T, m] ring is block-local and the
            # tick appends only this device's lanes (obs/profile.py)
            with scope("gt.obs"):
                st2 = st2.replace(profile=profile_tick(profile, st2, px=px,
                                                       dvfs=dvfs_energy))
        if hist is not None:
            # boundary sources sample EVERY executed quantum (each one
            # is a whole-fleet skew observation — the four-scheme
            # study's instrument); under a tile-sharded px the per-tile
            # ring appends only this device's lanes (obs/hist.py)
            with scope("gt.obs"):
                st2 = st2.replace(hist=hist_boundary_tick(hist, st2, px=px,
                                                          dvfs=dvfs_energy))
        # Zero progress: if some non-done tile sits beyond qend (it crossed
        # the boundary executing one long record), jump the window up to it
        # — blocked peers may wait on its future sends.  Only when every
        # non-done tile was already eligible is this a genuine deadlock.
        zero = (progress == 0) & jnp.any(~st2.done)
        if trace_base is not None:
            # streaming: lanes past the window end are merely paused;
            # zero progress with a paused lane returns to the host for a
            # window slide instead of flagging deadlock
            paused = jnp.any(
                ~st2.done
                & (st2.core.idx >= trace_base + trace.length))
        else:
            paused = jnp.asarray(False)
        if qps is not None:
            ahead_clock = jnp.min(jnp.where(
                ~st2.done & (st2.core.clock_ps >= qend),
                st2.core.clock_ps, jnp.asarray(2**62, I64)))
            have_ahead = ahead_clock < 2**62
            qend_next = jnp.where(
                zero & have_ahead, next_boundary(ahead_clock) - qps, qend)
            deadlock = zero & ~have_ahead & ~paused
            stalled = zero & ~have_ahead & paused
        else:
            qend_next = qend
            deadlock = zero & ~paused
            stalled = zero & paused
        return (st2, qend_next, n + 1, deadlock, stalled, iters + blk_iters,
                idle + blk_idle)

    with scope("gt.quantum"):
        state, _, n_quanta, deadlock, _, n_iters, n_idle = lax.while_loop(
            cond, body,
            (state, jnp.asarray(0, I64), jnp.asarray(0, jnp.int32),
             jnp.asarray(False), jnp.asarray(False), jnp.asarray(0, I64),
             jnp.asarray(0, I64)))
    return state, n_quanta, deadlock, n_iters, n_idle


def barrier_host_batch(
    params: EngineParams,
    trace: DeviceTrace,
    state: SimState,
    prev_qend: jax.Array,     # int64[] qend of the previous quantum
    quantum_ps: int,
    max_quanta: jax.Array,    # int32[] quanta budget for THIS dispatch
    telemetry=None,
    profile=None,
    dvfs=None,
    hist=None,
):
    """Up to `max_quanta` lax_barrier quanta as ONE compiled region — the
    batched form of the host-driven barrier loop (Simulator.barrier_host).

    The per-quantum host dispatch costs a host round trip each (not
    measured on the current machine); this bounded device-side
    while_loop amortizes it ~K per dispatch and EARLY-EXITS
    back to the host exactly when a quantum raises host-visible work:
    every tile done, a mailbox overflow, or a genuine deadlock (zero
    progress with no tile beyond the boundary).  Quantum semantics are
    identical to the per-quantum host loop: next boundary above the
    laggard tile, empty quanta skipped via the prev_qend floor, and a
    zero-progress quantum with a tile beyond the boundary jumps the
    window up to it (`lax_barrier_sync_server.h:12-36`).

    Returns (state, prev_qend, n_quanta, deadlock, n_iterations,
    idle_iterations); the host threads prev_qend into the next dispatch
    so boundary progression is seamless across batches.

    `telemetry` / `profile` sample the device-resident rings exactly as
    in `run_simulation`; the sampling cursors ride the state carry, so
    recording is seamless across dispatches too.
    """
    if telemetry is not None:
        from graphite_tpu.obs.telemetry import telemetry_tick
    if profile is not None:
        from graphite_tpu.obs.profile import profile_tick
    if hist is not None:
        from graphite_tpu.obs.hist import hist_boundary_tick
    if dvfs is not None:
        from graphite_tpu.dvfs.runtime import core_freq_tiles, governor_tick
    dvfs_energy = (params.dvfs
                   if dvfs is not None and dvfs.scale_energy else None)
    qps = int(quantum_ps)

    def next_boundary(clock):
        return (clock // qps + 1) * qps

    def cond(carry):
        st, _, n, deadlock, _, _ = carry
        return (
            ~jnp.all(st.done)
            & ~st.net.overflow
            & ~deadlock
            & (n < max_quanta)
        )

    def body(carry):
        st, prev, n, deadlock, iters, idle = carry
        clocks = st.core.clock_ps
        min_pending = jnp.min(jnp.where(~st.done, clocks,
                                        jnp.asarray(2**62, I64)))
        qend = jnp.maximum(prev + qps, next_boundary(min_pending))
        st2, progress, blk_iters, blk_idle = _quantum_loop(
            params, trace, st, qend, dvfs=dvfs, hist=hist)
        if dvfs is not None and dvfs.governor is not None:
            with scope("gt.dvfs"):
                rt2 = governor_tick(dvfs.governor, params.dvfs,
                                    st2.dvfs_rt, st2)
                st2 = st2.replace(
                    dvfs_rt=rt2,
                    core=st2.core.replace(freq_mhz=core_freq_tiles(
                        params.dvfs, rt2, st2.core.freq_mhz)))
        if telemetry is not None:
            with scope("gt.obs"):
                st2 = st2.replace(telemetry=telemetry_tick(
                    telemetry, st2, progress=progress, blk_iters=blk_iters,
                    dvfs=dvfs_energy))
        if profile is not None:
            with scope("gt.obs"):
                st2 = st2.replace(profile=profile_tick(profile, st2,
                                                       dvfs=dvfs_energy))
        if hist is not None:
            with scope("gt.obs"):
                st2 = st2.replace(hist=hist_boundary_tick(hist, st2,
                                                          dvfs=dvfs_energy))
        zero = (progress == 0) & jnp.any(~st2.done)
        ahead_clock = jnp.min(jnp.where(
            ~st2.done & (st2.core.clock_ps >= qend),
            st2.core.clock_ps, jnp.asarray(2**62, I64)))
        have_ahead = ahead_clock < 2**62
        # a tile crossed the boundary executing one long record: jump the
        # window so the NEXT quantum's floor lands just below it
        qend_next = jnp.where(zero & have_ahead,
                              next_boundary(ahead_clock) - qps, qend)
        deadlock = zero & ~have_ahead
        return (st2, qend_next, n + 1, deadlock, iters + blk_iters,
                idle + blk_idle)

    with scope("gt.quantum"):
        state, prev_qend, n, deadlock, iters, idle = lax.while_loop(
            cond, body,
            (state, jnp.asarray(prev_qend, I64), jnp.asarray(0, jnp.int32),
             jnp.asarray(False), jnp.asarray(0, I64), jnp.asarray(0, I64)))
    return state, prev_qend, n, deadlock, iters, idle


def make_simulation_runner(params: EngineParams, trace: DeviceTrace,
                           quantum_ps: int | None, max_quanta: int,
                           donate: bool = False, telemetry=None,
                           profile=None, dvfs=None, hist=None):
    """`donate=True` hands the input state's buffers to XLA (halves the
    protocol state's HBM residency — the 1024-tile directory is 2.4 GB).
    On the v5e's 16 GB it is headroom, not a requirement: the 1024-tile
    full-directory run peaks at 5.4 GB without it (PERF.md, PR 25).  The
    caller's old state object is consumed."""
    def run(state: SimState):
        return run_simulation(params, trace, state, quantum_ps, max_quanta,
                              telemetry=telemetry, profile=profile,
                              dvfs=dvfs, hist=hist)

    return jax.jit(tagged(run), donate_argnums=(0,) if donate else ())
