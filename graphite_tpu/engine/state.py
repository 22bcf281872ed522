"""Simulation state: struct-of-arrays pytrees over the tile axis.

The reference scatters this state across per-tile C++ objects
(`Tile`/`Core`/`CoreModel`/`Network` — `common/tile/tile.cc:15-37`); here it
is a pytree of dense arrays with leading dimension n_tiles so one XLA step
advances every tile.  Checkpoint/resume (absent in the reference, SURVEY §5)
falls out for free: the state pytree *is* the checkpoint.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from graphite_tpu.trace.schema import TraceBatch

# ring depth for per-generation barrier-release / cond-signal times: the
# split rendezvous ops are generation-exact while a joiner lags at most
# GEN_RING releases/signals behind (far beyond the one-generation bound
# the frontend's usage patterns give)
GEN_RING = 8


@struct.dataclass
class CoreState:
    """Per-tile core-model state (`common/tile/core/core_model.h:19-146`)."""

    clock_ps: jax.Array          # int64[T] — CoreModel::_curr_time
    idx: jax.Array               # int32[T] — next trace record
    freq_mhz: jax.Array          # int32[T] — per-tile core frequency
    # counters (`core_model.cc:90-115` outputSummary)
    instruction_count: jax.Array     # int64[T]
    memory_stall_ps: jax.Array       # int64[T]
    execution_stall_ps: jax.Array    # int64[T]
    recv_instructions: jax.Array     # int64[T]
    recv_stall_ps: jax.Array         # int64[T]
    sync_instructions: jax.Array     # int64[T]
    sync_stall_ps: jax.Array         # int64[T]
    # branch predictor (`branch_predictors/one_bit_branch_predictor.cc`)
    bp_bits: jax.Array           # uint8[T, bp_size]
    bp_correct: jax.Array        # int64[T]
    bp_incorrect: jax.Array      # int64[T]


@struct.dataclass
class UserNetState:
    """The USER network (`packet_type.h:40-56`) as per-pair mailbox rings.

    Replaces the reference's per-tile `_netQueue` + condition variable
    (`network.cc:358-460`) and the TCP transport underneath: slot
    [dst, k, src] holds the k-th in-flight packet from src to dst.  Each
    sender lane writes only its own src column, so scatters never collide.
    The slot axis sits OUTSIDE the src axis so the minor dimension is the
    tile count: a [T, T, D] layout pads D up to the 128-lane tile on TPU
    (64x physical blowup at depth 2 — PERF.md "array padding").
    """

    time_ps: jax.Array     # int64[T, D, T] — arrival time at receiver
    lat_ps: jax.Array      # int32[T, D, T] — zero-load delay (for stats)
    head: jax.Array        # int32[T, T] — total pushes (mod D write slot)
    count: jax.Array       # int32[T, T] — in-flight entries
    overflow: jax.Array    # bool[]     — any ring exceeded D (sim invalid)
    # receive-side counters (`network_model.cc` updateReceiveCounters)
    packets_sent: jax.Array      # int64[T]
    packets_received: jax.Array  # int64[T]
    total_latency_ps: jax.Array  # int64[T]


@struct.dataclass
class SyncState:
    """Simulated sync objects (`common/system/sync_server.h:86-114`).

    The MCP SyncServer's SimBarrier/SimMutex tables become dense arrays
    indexed by object id; arrivals use scatter-adds, releases are computed
    globally per subquantum iteration.
    """

    barrier_count: jax.Array     # int32[NB] — participant count (init)
    barrier_arrived: jax.Array   # int32[NB]
    barrier_time_ps: jax.Array   # int64[NB] — max arrival time
    barrier_waiting: jax.Array   # bool[T] — this tile has joined its barrier
    # co-located split form (BARRIER_ARRIVE/BARRIER_SYNC): release
    # generation counter + a GEN_RING-deep ring of per-generation release
    # times (generation-exact for rendezvous lag <= GEN_RING releases)
    barrier_gen: jax.Array       # int32[NB]
    barrier_release_ps: jax.Array  # int64[NB, GEN_RING]
    # published cond signals (COND_SIGNAL aux1>0 / COND_JOIN): sequence
    # counter + per-sequence time ring
    cond_sig_seq: jax.Array      # int32[NC]
    cond_sig_seq_ps: jax.Array   # int64[NC, GEN_RING]
    mutex_locked: jax.Array      # int32[NM] — 0 free / 1 held
    mutex_owner: jax.Array       # int32[NM]
    mutex_time_ps: jax.Array     # int64[NM] — time of last lock/unlock
    mutex_waiting: jax.Array     # bool[T] — tile has a pending lock request
    # condition variables (`sync_server.cc` SimCond): a tile at a COND_WAIT
    # record is either waiting (in the FIFO, mutex released), or signaled
    # (woken, re-acquiring the mutex).  Signals/broadcasts park in per-cond
    # pending slots stamped with their simulated time and are delivered in
    # simulated-time order — to a waiter whose wait began at or before the
    # signal — or dropped once provably lost (pthread lost-signal
    # semantics), regardless of engine-iteration arrival order.
    cond_waiting: jax.Array      # bool[T]
    cond_signaled: jax.Array     # bool[T]
    cond_arrival_ps: jax.Array   # int64[T] — wait arrival (FIFO order key)
    cond_wake_ps: jax.Array      # int64[T] — signal/broadcast time
    cond_sig_time_ps: jax.Array  # int64[NC, K] — pending signals (FAR=empty)
    cond_bcast_time_ps: jax.Array  # int64[NC] — pending broadcast (FAR=none)


@struct.dataclass
class DvfsState:
    """Per-tile per-domain frequency/voltage (`dvfs_manager.h:19-88`).

    The CORE domain's frequency is mirrored authoritatively in
    CoreState.freq_mhz (every cost conversion uses it); non-CORE domains
    are tracked for the get/set API, with their model frequencies static
    per run (documented divergence: the reference retunes cache/network
    timing mid-run on those domains too)."""

    freq_mhz: jax.Array     # int32[T, ND]
    voltage_mv: jax.Array   # int32[T, ND]
    errors: jax.Array       # int64[T] — failed in-trace DVFS_SET events


@struct.dataclass
class EnergyState:
    """Per-tile energy accumulators and the open interval's start
    (`power/accounting.py`: columns, units, the interval rule).  Carried
    only with `[general] enable_power_modeling = true`."""

    acc: jax.Array            # int64[T, C] — fJ (dynamic) / uW*ps (static)
    last_raw: jax.Array       # int64[T, R] — event counts at the last close
    last_clock_ps: jax.Array  # int64[T] — the tile's clock at the last close


@struct.dataclass
class SimState:
    core: CoreState
    net: UserNetState
    sync: SyncState
    models_enabled: jax.Array    # bool[] — CarbonEnableModels/DisableModels
    done: jax.Array              # bool[T] — thread exited (THREAD_EXIT)
    # memory subsystem (None when enable_shared_mem=false, the reference's
    # `general/enable_shared_mem` knob — `carbon_sim.cfg:40-44`)
    mem: "object" = None
    # USER-network hop-by-hop port-contention state (None unless
    # network/user = emesh_hop_by_hop)
    noc_user: "object" = None
    # iocoom core-model state (None unless core type = iocoom)
    ioc: "object" = None
    # per-domain DVFS state (always populated by Simulator; the None path
    # exists only for direct engine-level construction in tests)
    dvfs: "object" = None
    # lax_p2p pairing round counter (drives the pseudorandom partner draw;
    # carried unconditionally — one int32 scalar)
    p2p_round: "jax.Array" = None
    # device-resident telemetry ring (obs/telemetry.TelemetryState) when
    # the run records a timeline; None (no pytree leaves — the program
    # lowers bit-identically to one with no telemetry at all) otherwise
    telemetry: "object" = None
    # device-resident per-tile profile ring (obs/profile.ProfileState)
    # when the run records the spatial profiler; None (no pytree leaves
    # — same bit-identity contract as telemetry) otherwise
    profile: "object" = None
    # runtime DVFS manager carry (dvfs/runtime.DvfsRtState): chip-global
    # per-domain operating point + governor cursors when a DvfsSpec is
    # attached; None (no pytree leaves — same bit-identity contract as
    # telemetry/profile) otherwise
    dvfs_rt: "object" = None
    # device-resident latency-histogram ring (obs/hist.HistState) when
    # the run records distributions; None (no pytree leaves — same
    # bit-identity contract as telemetry/profile) otherwise
    hist: "object" = None
    # per-tile energy accumulators (EnergyState) under [general]
    # enable_power_modeling; None (no pytree leaves — same bit-identity
    # contract) otherwise
    energy: "object" = None


@struct.dataclass
class DeviceTrace:
    """TraceBatch resident on device, one array per field, [T, L]."""

    op: jax.Array
    flags: jax.Array
    pc: jax.Array
    addr0: jax.Array
    addr1: jax.Array
    size0: jax.Array
    size1: jax.Array
    aux0: jax.Array
    aux1: jax.Array
    dyn_ps: jax.Array
    rreg0: jax.Array
    rreg1: jax.Array
    wreg: jax.Array

    @classmethod
    def from_batch(cls, batch: TraceBatch) -> "DeviceTrace":
        return cls(
            **{
                f.name: jnp.asarray(getattr(batch, f.name))
                for f in dataclasses.fields(batch)
            }
        )

    @classmethod
    def window(cls, batch: TraceBatch, bases: "np.ndarray",
               length: int) -> "DeviceTrace":
        """A [T, length] window with PER-TILE start records `bases[t]`,
        NOP-padded past each stream's end — the unit of host->HBM
        streaming.  Per-tile bases let lanes skew arbitrarily (a leader
        pausing at its window edge never forces the window away from a
        laggard).  Rows are cut host-side so only `length` records per
        tile ever travel to the device."""
        import numpy as np

        from graphite_tpu.trace.schema import Op

        L = batch.length
        cols = bases[:, None] + np.arange(length)[None, :]   # [T, W]
        valid = cols < L
        cols = np.minimum(cols, L - 1)
        fields = {}
        for f in dataclasses.fields(batch):
            arr = np.take_along_axis(getattr(batch, f.name), cols, axis=1)
            if f.name == "op":
                arr = np.where(valid, arr, np.uint8(Op.NOP))
            fields[f.name] = jnp.asarray(arr)
        return cls(**fields)

    @property
    def length(self) -> int:
        return self.op.shape[1]


def init_state(
    n_tiles: int,
    *,
    core_freq_mhz: int | np.ndarray,
    bp_size: int = 1024,
    mailbox_depth: int = 8,
    n_barriers: int = 64,
    n_mutexes: int = 64,
    n_conds: int = 64,
    n_pending_signals: int = 4,
    models_enabled: bool = True,
) -> SimState:
    T, D = n_tiles, mailbox_depth
    i64 = jnp.int64
    core = CoreState(
        clock_ps=jnp.zeros(T, i64),
        idx=jnp.zeros(T, jnp.int32),
        freq_mhz=jnp.broadcast_to(
            jnp.asarray(core_freq_mhz, jnp.int32), (T,)
        ).copy(),
        instruction_count=jnp.zeros(T, i64),
        memory_stall_ps=jnp.zeros(T, i64),
        execution_stall_ps=jnp.zeros(T, i64),
        recv_instructions=jnp.zeros(T, i64),
        recv_stall_ps=jnp.zeros(T, i64),
        sync_instructions=jnp.zeros(T, i64),
        sync_stall_ps=jnp.zeros(T, i64),
        bp_bits=jnp.zeros((T, bp_size), jnp.uint8),
        bp_correct=jnp.zeros(T, i64),
        bp_incorrect=jnp.zeros(T, i64),
    )
    net = UserNetState(
        time_ps=jnp.zeros((T, D, T), i64),
        lat_ps=jnp.zeros((T, D, T), jnp.int32),
        head=jnp.zeros((T, T), jnp.int32),
        count=jnp.zeros((T, T), jnp.int32),
        overflow=jnp.zeros((), jnp.bool_),
        packets_sent=jnp.zeros(T, i64),
        packets_received=jnp.zeros(T, i64),
        total_latency_ps=jnp.zeros(T, i64),
    )
    sync = SyncState(
        barrier_count=jnp.zeros(n_barriers, jnp.int32),
        barrier_arrived=jnp.zeros(n_barriers, jnp.int32),
        barrier_time_ps=jnp.zeros(n_barriers, i64),
        barrier_waiting=jnp.zeros(T, jnp.bool_),
        barrier_gen=jnp.zeros(n_barriers, jnp.int32),
        barrier_release_ps=jnp.zeros((n_barriers, GEN_RING), i64),
        cond_sig_seq=jnp.zeros(n_conds, jnp.int32),
        cond_sig_seq_ps=jnp.zeros((n_conds, GEN_RING), i64),
        mutex_locked=jnp.zeros(n_mutexes, jnp.int32),
        mutex_owner=jnp.full(n_mutexes, -1, jnp.int32),
        mutex_time_ps=jnp.zeros(n_mutexes, i64),
        mutex_waiting=jnp.zeros(T, jnp.bool_),
        cond_waiting=jnp.zeros(T, jnp.bool_),
        cond_signaled=jnp.zeros(T, jnp.bool_),
        cond_arrival_ps=jnp.zeros(T, i64),
        cond_wake_ps=jnp.zeros(T, i64),
        cond_sig_time_ps=jnp.full((n_conds, n_pending_signals), 2**62, i64),
        cond_bcast_time_ps=jnp.full(n_conds, 2**62, i64),
    )
    return SimState(
        core=core,
        net=net,
        sync=sync,
        models_enabled=jnp.asarray(models_enabled, jnp.bool_),
        done=jnp.zeros(T, jnp.bool_),
        p2p_round=jnp.zeros((), jnp.int32),
    )
