"""Memory-subsystem state: caches, directory, protocol mailboxes, DRAM.

Layout notes (all leading axis = tile):
 - REQUEST cells live per REQUESTER lane ([T] + a target-home vector)
   because each tile has exactly one outstanding L2 miss
   (`l2_cache_cntlr.h` _outstanding_shmem_msg) — the compact analog of
   the per-address request queue in `dram_directory_cntlr.cc:59-96`;
   homes pop the earliest (time, requester) via a segment-min over the
   lanes targeting them.
 - FWD cells [sharer, home] carry INV/FLUSH/WB requests from a home's
   active transaction; a home owns its column (one transaction at a time)
   and clears it when the transaction ends, so stale messages cannot leak
   into a later transaction.
 - ACK cells [home, sharer] carry INV/FLUSH/WB replies; a sharer owns its
   cell.
 - EVICT cells [home, src] carry unsolicited evictions (INV_REP/FLUSH_REP
   from `l2_cache_cntlr.cc:75-116 insertCacheLine`); the L2 fill that would
   emit a second eviction to the same home blocks until the cell frees
   (back-pressure; homes drain one eviction per subquantum iteration).
 - The functional store is a single word-addressed array: the coherence
   protocol serializes conflicting accesses, so applying values at access
   completion preserves the observable semantics of the reference's
   in-cache data + DRAM map (`dram_cntlr.h:37`) without moving bytes
   through the mailboxes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from graphite_tpu.memory.cache_array import CacheArrays, make_cache
from graphite_tpu.memory.params import MemParams
from graphite_tpu.memory.row_landing import can_land_entry

I64 = jnp.int64

# message types (subset of `shmem_msg.h:12-30`)
MSG_NONE = 0
MSG_SH_REQ = 1
MSG_EX_REQ = 2
MSG_INV_REQ = 3
MSG_FLUSH_REQ = 4
MSG_WB_REQ = 5
MSG_INV_REP = 6
MSG_FLUSH_REP = 7
MSG_WB_REP = 8
MSG_SH_REP = 9
MSG_EX_REP = 10
MSG_NULLIFY = 11
MSG_EXCL_REP = 12   # MESI exclusive grant (`pr_l1_sh_l2_mesi`)

# directory states (`directory_state.h`)
DIR_UNCACHED = 0
DIR_SHARED = 1
DIR_MODIFIED = 2
DIR_OWNED = 3    # MOSI

# requester phases
PHASE_IDLE = 0
PHASE_WAIT_REPLY = 1

# memory components (indices into MemParams.module_domains)
MOD_CORE = 0
MOD_L1I = 1
MOD_L1D = 2
MOD_L2 = 3
MOD_DIR = 4
MOD_NET_MEM = 5


# Packed directory-entry word layout (int64[T, DS, DW]): one scatter
# per engine phase updates (tag, dstate, owner, nsharers) together —
# four separate arrays cost four dense-lowered scatters plus their
# layout-conversion copies each phase (PERF.md round-5).  The all-zero
# word IS the free entry (tag+1 = 0, owner+1 = 0 -> -1, UNCACHED, 0
# sharers), so init is plain zeros.
DIR_TAG_BITS = 34        # bits 0..33: line + 1 (0 = free)
DIR_STATE_SHIFT = 34     # bits 34..36: directory state
DIR_OWNER_SHIFT = 37     # bits 37..49: owner tile + 1
DIR_NSH_SHIFT = 50       # bits 50..62: sharer count
DIR_ID_BITS = 13         # owner/nsharers field width (tiles <= 8190)


@struct.dataclass
class DirectoryArrays:
    """Per-home-slice directory cache (`cache/directory_cache.h:20-68`).

    Kept as structured [T, DS, DW(, SW)] arrays: a flat 2-D repack
    (entry-major, large minor dim) was built and measured 1.6x SLOWER —
    the computed-column gathers lower worse than structured indexing,
    and the whole-array copies it targeted barely moved (PERF.md
    round-3 findings).

    The entry store has TWO forms, chosen once, where the state is built
    (`entry_as_words`), and told apart by dtype ever after
    (`engine._entry_rows`, `engine._entry_land`, `row_landing.entry_int64`
    are all that look):
     - int64[T, DS, DW]: one scatter-add an iteration lands the home
       phases' plan.  On a TPU the compiler keeps an int64 array as two
       u32 halves tiled (8, 128) and an element scatter linearises them:
       five passes over a half an open iteration, cheap on a small store
       (8 MB at 64 tiles) and 0.97 ms on the 128 MiB one (PR 45);
     - u32[T, 2 * DW, DS], the same words as the halves already lay (ways
       on sublanes, sets on lanes; a lane's low words in rows 0..DW-1,
       its high words in rows DW..2*DW-1): where a program is lowered for
       a TPU, a kernel lands the plan's live words' tiles alone
       (`row_landing.land_entry`; a campaign's sims as more lanes);
       elsewhere an XLA gather-and-set on the same words.  The int64 word
       exists only in the gathered rows."""

    # packed (tag, dstate, owner, nsharers) word per entry — layout above
    entry: jax.Array     # int64[T, DS, DW] | uint32[T, 2*DW, DS]
    # full-map bitvector, stored set-row-major [T, DS, DW*SW] (way w's
    # words at [.., w*SW:(w+1)*SW]): a [T, DS, DW, SW] layout pads SW up
    # to the 128-lane tile on TPU (4x physical at 1024 tiles — PERF.md
    # "array padding"), and the set-row form matches how every phase
    # reads it anyway
    sharers: jax.Array   # uint32[T, DS, DW*SW]
    # sharers write-staging rows, PER HOME LANE (MemParams.dir_stage_cap
    # > 0; see engine._stage_put / dir_stage_flush).  Append-only: a put
    # lands at the lane's cursor `sn`; keys may repeat within a row —
    # reads take the latest match and the flush applies only each key's
    # last slot (round 12; every directory write is home-lane-local, so
    # the rows are block-local under shard_map).  None when staging is
    # disabled.
    skey: "object" = None  # int32[T, c] set*DW + way, -1 = empty
    sval: "object" = None  # uint32[T, c, SW] staged sharer words
    sn: "object" = None    # int32[T] slots appended since last flush


@struct.dataclass
class TxnState:
    """One active directory transaction per home tile.

    The dense form of the front-of-queue request being serviced
    (`dram_directory_cntlr.cc:44-130`); `saved_*` holds the original
    request while a NULLIFY (directory-entry replacement,
    `processDirectoryEntryAllocationReq`) runs first.
    """

    active: jax.Array        # bool[T]
    mtype: jax.Array         # uint8[T] MSG_SH_REQ/MSG_EX_REQ/MSG_NULLIFY
    line: jax.Array          # int32[T]
    requester: jax.Array     # int32[T]
    time_ps: jax.Array       # int64[T] running ShmemPerfModel clock
    pending: jax.Array       # uint32[T, SW] outstanding INV/FLUSH/WB acks
    data_cached: jax.Array   # bool[T] reply data arrived via FLUSH/WB_REP
    saved_valid: jax.Array   # bool[T]
    saved_type: jax.Array    # uint8[T]
    saved_line: jax.Array    # int32[T]
    saved_requester: jax.Array  # int32[T]
    saved_time_ps: jax.Array    # int64[T]
    last_line: jax.Array     # int32[T]  same-address serialization floor
    last_done_ps: jax.Array  # int64[T]
    # one-entry flushed-data buffer per home (`_cached_data_list` analog):
    # a FLUSH_REP eviction parks its line here; a later request for the
    # same line is served without a DRAM read
    cdata_line: jax.Array    # int32[T]
    cdata_valid: jax.Array   # bool[T]


@struct.dataclass
class MemMailboxes:
    # The request "matrix" is stored per REQUESTER lane: each tile has
    # exactly one outstanding L2 (shared-L2: L1) miss (`l2_cache_cntlr.h`
    # _outstanding_shmem_msg — the requester sits in PHASE_WAIT_REPLY
    # until its reply fills), so the writer set of the old [T, T] form's
    # column was provably one tile and the [T, T] matrix carried T-1
    # dead cells per lane.  Round 12 compacts it to [T] lanes +
    # `req_home`; the home-side pop is a segment-min over requesters
    # with the SAME (time, requester) key order as the old row scan
    # (engine._req_earliest), so the compaction is bit-exact.
    req_type: jax.Array    # uint8[T(requester)]
    req_home: jax.Array    # int32[T] target home of the live request
    req_line: jax.Array    # int32[T]
    req_time: jax.Array    # int64[T]
    evict_type: jax.Array  # uint8[T(home), T(src)]
    evict_line: jax.Array  # int32[T, T]
    evict_time: jax.Array  # int64[T, T]
    fwd_type: jax.Array    # uint8[T(sharer), T(home)]
    fwd_line: jax.Array    # int32[T, T]
    fwd_time: jax.Array    # int64[T, T]
    ack_type: jax.Array    # uint8[T(home), T(sharer)]
    ack_line: jax.Array    # int32[T, T]
    ack_time: jax.Array    # int64[T, T]
    rep_type: jax.Array    # uint8[T(requester)]
    rep_time: jax.Array    # int64[T]


@struct.dataclass
class RequesterState:
    phase: jax.Array       # int32[T] PHASE_*
    slot: jax.Array        # int32[T] current memory slot of the record
    acc_ps: jax.Array      # int64[T] accumulated memory latency this record
    clock_ps: jax.Array    # int64[T] running shmem clock of current slot
    line: jax.Array        # int32[T] line being fetched
    is_write: jax.Array    # bool[T]
    component: jax.Array   # uint8[T] MOD_L1I or MOD_L1D
    instr_buf: jax.Array   # int32[T] instruction-buffer line (`core.cc:207-219`)
    # per-slot latency of the current record [icache, mem0, mem1] — the
    # iocoom model needs per-operand latencies (`DynamicMemoryInfo::_latency`)
    slot_lat_ps: jax.Array  # int64[T, 3]


@struct.dataclass
class MemCounters:
    l1i_hits: jax.Array        # int64[T]
    l1i_misses: jax.Array
    l1d_read_hits: jax.Array
    l1d_read_misses: jax.Array
    l1d_write_hits: jax.Array
    l1d_write_misses: jax.Array
    l2_hits: jax.Array
    l2_misses: jax.Array
    evictions: jax.Array
    invalidations: jax.Array   # INV_REQs served with a valid line
    dir_accesses: jax.Array
    dir_broadcasts: jax.Array  # ackwise/limited_broadcast INV sweeps sent to all tiles
    dram_reads: jax.Array
    dram_writes: jax.Array
    dram_total_lat_ps: jax.Array
    # L2 miss-type classification (`cache.h:45-49` COLD/CAPACITY/SHARING;
    # populated when `[l2_cache/<type>] track_miss_types` — private-L2
    # engines only)
    l2_cold_misses: jax.Array
    l2_capacity_misses: jax.Array
    l2_sharing_misses: jax.Array
    # L2 cache-line utilization (`cache/cache_line_utilization.h`; MOSI
    # l2_cache_cntlr eviction/invalidation hooks) — populated when
    # `[l2_cache/<type>] track_cache_line_utilization`:
    # histogram of per-line TOTAL accesses classified when the line
    # leaves the L2 (buckets: 0, 1, 2-3, 4-7, ..., >=64), plus the
    # classified lines' accumulated read/write access counts
    line_util_hist: jax.Array    # int64[T, 8]
    line_util_reads: jax.Array   # int64[T]
    line_util_writes: jax.Array  # int64[T]


@struct.dataclass
class MemState:
    l1i: CacheArrays
    l1d: CacheArrays
    l2: CacheArrays
    l2_cloc: jax.Array       # uint8[T, S2, W2] which L1 holds it (0/MOD_L1I/MOD_L1D)
    # per-L2-line utilization counters when track_cache_line_utilization:
    # uint32[T, S2, W2], low 16 bits = read accesses, high 16 = writes
    # (saturating); None when tracking is off
    l2_util: "object"
    directory: DirectoryArrays
    txn: TxnState
    mail: MemMailboxes
    req: RequesterState
    counters: MemCounters
    func_mem: jax.Array      # uint32[mem_words] functional word store
    func_errors: jax.Array   # int64[] failed FLAG_CHECK loads
    # bool[] — any protocol state outstanding (messages, transactions,
    # waiting requesters); False lets the step skip the engine entirely
    live: jax.Array
    # int64[6] — per-phase lax.cond skip counts under phase gating
    # (MemParams.phase_gate; engine.PHASE_NAMES order).  A whole-engine
    # mem_gate skip counts every phase.  Replicated control state under
    # shard_map (deterministic from replicated predicates).
    phase_skips: jax.Array = None
    # int64[2] — what the home-activity gate skipped (engine.
    # BASE_SKIP_NAMES order): iterations whose base (the
    # directory working-set gather and the merged scatter) did not run,
    # inner blocks whose staging flush did not run.  A whole-engine
    # mem_gate skip counts as a skipped base.  Stays 0 with the gates
    # off.  Replicated control state, like phase_skips — and not only
    # observed: the flush's gate reads how far `base` moved over a block
    # (engine/step.py).
    base_skips: jax.Array = None
    # per-port queue state of the MEMORY NoC when `[network] memory =
    # emesh_hop_by_hop` (models/network_hop_by_hop.NocState), else None
    noc: "object" = None
    # L2 miss-type tracking bitmaps, uint32[T, 3, MT_WORDS] (rows:
    # fetched / evicted / invalidated — the reference's three address
    # sets, `cache.cc getMissType`, hashed to MT_BITS buckets per tile;
    # bucket collisions are a documented approximation shared with the
    # oracle).  None when track_miss_types is off.
    mt: "object" = None


# the engines' protocol phase count (engine.PHASE_NAMES /
# engine_shl2.SHL2_PHASE_NAMES index the skip vector)
N_PHASES = 6


def init_mem_common(mp: MemParams) -> dict:
    """The protocol-independent state pieces (L1/L2 arrays, mailboxes,
    requester machinery, counters, functional memory) — shared between the
    private-L2 and shared-L2 engines."""
    T = mp.n_tiles

    def zi64():
        return jnp.zeros(T, I64)

    mail = MemMailboxes(
        req_type=jnp.zeros(T, jnp.uint8),
        req_home=jnp.zeros(T, jnp.int32),
        req_line=jnp.zeros(T, jnp.int32),
        req_time=jnp.zeros(T, I64),
        evict_type=jnp.zeros((T, T), jnp.uint8),
        evict_line=jnp.zeros((T, T), jnp.int32),
        evict_time=jnp.zeros((T, T), I64),
        fwd_type=jnp.zeros((T, T), jnp.uint8),
        fwd_line=jnp.zeros((T, T), jnp.int32),
        fwd_time=jnp.zeros((T, T), I64),
        ack_type=jnp.zeros((T, T), jnp.uint8),
        ack_line=jnp.zeros((T, T), jnp.int32),
        ack_time=jnp.zeros((T, T), I64),
        rep_type=jnp.zeros(T, jnp.uint8),
        rep_time=zi64(),
    )
    req = RequesterState(
        phase=jnp.zeros(T, jnp.int32),
        slot=jnp.zeros(T, jnp.int32),
        acc_ps=zi64(),
        clock_ps=zi64(),
        line=jnp.zeros(T, jnp.int32),
        is_write=jnp.zeros(T, jnp.bool_),
        component=jnp.zeros(T, jnp.uint8),
        instr_buf=jnp.full(T, -1, jnp.int32),
        slot_lat_ps=jnp.zeros((T, 3), jnp.int64),
    )
    counters = MemCounters(
        l1i_hits=zi64(), l1i_misses=zi64(),
        l1d_read_hits=zi64(), l1d_read_misses=zi64(),
        l1d_write_hits=zi64(), l1d_write_misses=zi64(),
        l2_hits=zi64(), l2_misses=zi64(),
        evictions=zi64(), invalidations=zi64(),
        dir_accesses=zi64(), dir_broadcasts=zi64(),
        dram_reads=zi64(), dram_writes=zi64(),
        dram_total_lat_ps=zi64(),
        l2_cold_misses=zi64(), l2_capacity_misses=zi64(),
        l2_sharing_misses=zi64(),
        line_util_hist=jnp.zeros((T, 8), I64),
        line_util_reads=zi64(), line_util_writes=zi64(),
    )
    return dict(
        l1i=make_cache(T, mp.l1i.num_sets, mp.l1i.num_ways),
        l1d=make_cache(T, mp.l1d.num_sets, mp.l1d.num_ways),
        l2=make_cache(T, mp.l2.num_sets, mp.l2.num_ways),
        mail=mail,
        req=req,
        counters=counters,
        # +1 scratch word absorbing masked-off dummy writes
        func_mem=jnp.zeros(max(mp.func_mem_words, 1) + 1, jnp.uint32),
        func_errors=jnp.zeros((), I64),
        phase_skips=jnp.zeros(N_PHASES, I64),
    )


# miss-type tracking hash space: 2^16 buckets = 2048 uint32 words/set
MT_BITS = 1 << 16
MT_WORDS = MT_BITS // 32
MT_FETCHED, MT_EVICTED, MT_INVALIDATED = 0, 1, 2


def entry_as_words(mp: MemParams) -> bool:
    """Whether the directory's entry store is carried as u32 words
    (DirectoryArrays): where a pass over a big store an iteration is
    what the program cannot afford — the programs that stage their
    sharers writes for the same reason (a sharers store of 64 MB and up,
    or staging asked for) — and the words' shape lets the kernel land on
    them.  The others keep the int64 store and the programs they had."""
    return bool(mp.dir_stage_cap) and can_land_entry(
        mp.n_tiles, mp.dir_sets, mp.dir_ways)


def init_mem_state(mp: MemParams) -> MemState:
    T = mp.n_tiles
    SW = mp.sharer_words
    DS, DW = mp.dir_sets, mp.dir_ways

    def zi64():
        return jnp.zeros(T, I64)

    directory = DirectoryArrays(
        # (the all-zero word is the free entry in either form)
        entry=(jnp.zeros((T, 2 * DW, DS), jnp.uint32) if entry_as_words(mp)
               else jnp.zeros((T, DS, DW), I64)),
        sharers=jnp.zeros((T, DS, DW * SW), jnp.uint32),
        skey=(jnp.full((T, mp.dir_stage_cap), -1, jnp.int32)
              if mp.dir_stage_cap else None),
        sval=(jnp.zeros((T, mp.dir_stage_cap, SW), jnp.uint32)
              if mp.dir_stage_cap else None),
        sn=(jnp.zeros(T, jnp.int32) if mp.dir_stage_cap else None),
    )
    txn = TxnState(
        active=jnp.zeros(T, jnp.bool_),
        mtype=jnp.zeros(T, jnp.uint8),
        line=jnp.zeros(T, jnp.int32),
        requester=jnp.zeros(T, jnp.int32),
        time_ps=zi64(),
        pending=jnp.zeros((T, SW), jnp.uint32),
        data_cached=jnp.zeros(T, jnp.bool_),
        saved_valid=jnp.zeros(T, jnp.bool_),
        saved_type=jnp.zeros(T, jnp.uint8),
        saved_line=jnp.zeros(T, jnp.int32),
        saved_requester=jnp.zeros(T, jnp.int32),
        saved_time_ps=zi64(),
        last_line=jnp.full(T, -1, jnp.int32),
        last_done_ps=zi64(),
        cdata_line=jnp.full(T, -1, jnp.int32),
        cdata_valid=jnp.zeros(T, jnp.bool_),
    )
    mt = (jnp.zeros((T, 3, MT_WORDS), jnp.uint32)
          if mp.l2.track_miss_types else None)
    return MemState(
        l2_cloc=jnp.zeros((T, mp.l2.num_sets, mp.l2.num_ways), jnp.uint8),
        l2_util=(jnp.zeros((T, mp.l2.num_sets, mp.l2.num_ways), jnp.uint32)
                 if mp.l2.track_line_utilization else None),
        directory=directory,
        txn=txn,
        live=jnp.zeros((), jnp.bool_),
        mt=mt,
        base_skips=jnp.zeros(2, I64),
        **init_mem_common(mp),
    )
