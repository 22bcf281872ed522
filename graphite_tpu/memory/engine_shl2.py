"""Vectorized shared-L2 protocol engine (pr_l1_sh_l2_msi / _mesi).

Reference: `common/tile/memory_subsystem/pr_l1_sh_l2_{msi,mesi}/` — private
L1s with a DISTRIBUTED shared L2: the L2 slice at a line's home tile holds
both the data and an embedded directory entry over the L1 copies
(`l2_cache_cntlr.h:27-67`, `l2_directory_cfg.cc`).  An L1 miss sends
EX/SH_REQ to the home (`l1_cache_cntlr.cc:81-160`); the home's L2 either
serves it (running the directory FSM over the L1 sharers,
`l2_cache_cntlr.cc:443-700`) or allocates the line in state DATA_INVALID
and fetches it from DRAM (`:541-560,900-915`).  MESI grants EXCLUSIVE on a
read of an uncached line (`pr_l1_sh_l2_mesi/l2_cache_cntlr.cc:660-680`).

Vectorized form mirrors engine.py's discipline: one lane per tile, dense
mailboxes, one active transaction per home, simulated time carried in
messages.  Like engine.py, the engine takes the packed shard_map
exchange context (`parallel/px.py`): every phase gathers its lanes' L1 /
L2-slice / embedded-directory rows block-locally, exchanges them in ONE
packed all-gather, computes full-width on replicated control state, and
scatters row deltas back to this device's block — so shared-L2 meshes
ride the same one-collective-per-phase program as the private-L2 engines
(the reference's process striping serves every protocol equally,
`config.cc` computeProcessToTileMapping + `socktransport.cc`).

The embedded directory is stored packed like the private engine's
(state/owner/nsharers/cloc in ONE int64 word per L2 line, all-zero =
UNCACHED; sharer bitvectors set-row-major [T, S2, W2*SW] so the minor
dim stays un-padded on TPU — PERF.md "array padding").

Documented simplifications (same class as engine.py's):
 - upgrade replies are modeled as EX_REP (same message count, the data
   serialization is slightly larger than the reference's UPGRADE_REP);
 - one transaction per home serializes same-home requests (the reference
   queues per address);
 - the DRAM fetch is a timing-only round trip to the line's DRAM home
   (`dram_home_lookup`), not a separate controller state machine.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from graphite_tpu.memory import cache_array as ca
from graphite_tpu.memory import row_landing
from graphite_tpu.memory.cache_array import (
    EXCLUSIVE, INVALID, MODIFIED, SHARED,
    state_readable, state_writable,
)
from graphite_tpu.memory.engine import (
    MemStepOut, RecView, _dir_set_field, _ID_MASK, _req_consume,
    _req_earliest, _row_earliest,
    _rows_exchange, _run_if, clear_bit, lowest_sharer, mem_net_fanout,
    mem_net_latency_ps, mem_net_send, set_bit, test_bit, unpack_sharers,
)
from graphite_tpu.memory.params import MemParams
from graphite_tpu.memory.state import (
    DIR_MODIFIED, DIR_SHARED, DIR_UNCACHED,
    MOD_CORE, MOD_L1D, MOD_L1I, MOD_L2, MOD_NET_MEM,
    MSG_EX_REP, MSG_EX_REQ, MSG_EXCL_REP, MSG_FLUSH_REP, MSG_FLUSH_REQ,
    MSG_INV_REP, MSG_INV_REQ, MSG_NONE, MSG_NULLIFY, MSG_SH_REP, MSG_SH_REQ,
    MSG_WB_REP, MSG_WB_REQ,
    PHASE_IDLE, PHASE_WAIT_REPLY,
    MemCounters, MemMailboxes, RequesterState, init_mem_common,
)
from graphite_tpu.obs.scopes import scope
from graphite_tpu.parallel.px import IDENT, ParallelCtx
from graphite_tpu.time_types import cycles_to_ps
from graphite_tpu.trace.schema import (
    FLAG_CHECK, FLAG_MEM0_VALID, FLAG_MEM0_WRITE, FLAG_MEM1_VALID,
    FLAG_MEM1_WRITE,
)

I64 = jnp.int64
U32 = jnp.uint32
FAR = 2**62

# phase order of the shared-L2 engine's skip vector (ShL2State.phase_skips)
SHL2_PHASE_NAMES = ("requester", "sharer", "home_evict", "home_finish",
                    "home_start", "requester_fill")


def dir_store_avals(ms) -> tuple:
    """(shape, dtype) signatures of the embedded directory's big stores
    — the [T, S2, W2] packed words and [T, S2, W2*SW] sharer rows —
    that a gated shl2 home phase must NEVER return as lax.cond outputs
    (the `_RowAcc` row-delta plan carries them instead; see `_cond_dir`).
    Enforced program-wide by the auditor's cond-payload rule
    (analysis/rules.py)."""
    d = ms.dir
    return (
        (tuple(d.word.shape), str(d.word.dtype)),
        (tuple(d.sharers.shape), str(d.sharers.dtype)),
    )

# L2 slice data state (`cache_line_info.h` ShL2CacheLineInfo): the line is
# allocated (directory live) but its data is still in flight from DRAM
DATA_INVALID = 5

# MESI directory state for an exclusive clean L1 copy
DIR_EXCLUSIVE = 4

# packed embedded-directory word layout (int64[T, S2, W2]; all-zero word =
# UNCACHED, owner -1, 0 sharers, cloc 0):
SHL2_STATE_SHIFT = 0    # bits 0..2: directory state
SHL2_OWNER_SHIFT = 3    # bits 3..15: owner tile + 1
SHL2_NSH_SHIFT = 16     # bits 16..28: sharer count
SHL2_CLOC_SHIFT = 29    # bits 29..30: caching component (MOD_L1I/L1D)


@struct.dataclass
class ShL2Dir:
    """Per-L2-line embedded directory, packed (layout above)."""

    word: jax.Array      # int64[T(home), S2, W2]
    sharers: jax.Array   # uint32[T(home), S2, W2*SW] set-row-major


def _d_state(w):
    return (w & 7).astype(jnp.uint8)


def _d_owner(w):
    return ((w >> SHL2_OWNER_SHIFT) & _ID_MASK).astype(jnp.int32) - 1


def _d_nsh(w):
    return ((w >> SHL2_NSH_SHIFT) & _ID_MASK).astype(jnp.int32)


def _d_cloc(w):
    return ((w >> SHL2_CLOC_SHIFT) & 3).astype(jnp.uint8)


def _dir_rows_local(d: ShL2Dir, sets_l):
    """This device's [Tl, W2] word row + [Tl, W2*SW] sharers row at each
    local lane's set (exchanged via _rows_exchange at the call sites)."""
    Tl = d.word.shape[0]
    lt = jnp.arange(Tl, dtype=jnp.int32)
    return d.word[lt, sets_l], d.sharers[lt, sets_l]


def _entry_at(dw, dsh, way):
    """(dstate, owner, sharers, nsh, cloc) at `way` from full-width rows."""
    word = jnp.take_along_axis(dw, way[:, None], axis=1)[:, 0]
    W2 = dw.shape[1]
    sh3 = dsh.reshape(dsh.shape[0], W2, -1)
    sharers = jnp.take_along_axis(sh3, way[:, None, None], axis=1)[:, 0]
    return (_d_state(word), _d_owner(word), sharers, _d_nsh(word),
            _d_cloc(word))


def _row_update(dw, way, mask, *, dstate=None, owner=None, nsharers=None,
                cloc=None):
    """Masked per-lane field update of the entry at `way` in the [T, W2]
    word row (pure bit math; the phase's single scatter applies it)."""
    word = jnp.take_along_axis(dw, way[:, None], axis=1)[:, 0]
    new = word
    if dstate is not None:
        new = _dir_set_field(new, jnp.asarray(dstate, jnp.uint8),
                             SHL2_STATE_SHIFT, 7)
    if owner is not None:
        new = _dir_set_field(new, owner.astype(I64) + 1,
                             SHL2_OWNER_SHIFT, _ID_MASK)
    if nsharers is not None:
        new = _dir_set_field(new, nsharers, SHL2_NSH_SHIFT, _ID_MASK)
    if cloc is not None:
        new = _dir_set_field(new, cloc, SHL2_CLOC_SHIFT, 3)
    onehot = (jnp.arange(dw.shape[1], dtype=jnp.int32)[None, :]
              == way[:, None]) & mask[:, None]
    return jnp.where(onehot, new[:, None], dw)


def _rowsh_update(dsh, way, mask, new_sh):
    """Masked per-lane sharers write at `way` in the [T, W2*SW] row."""
    W2SW = dsh.shape[1]
    SW = new_sh.shape[1]
    W2 = W2SW // SW
    sh3 = dsh.reshape(dsh.shape[0], W2, SW)
    onehot = (jnp.arange(W2, dtype=jnp.int32)[None, :, None]
              == way[:, None, None]) & mask[:, None, None]
    return jnp.where(onehot, new_sh[:, None, :], sh3).reshape(
        dsh.shape[0], W2SW)


def _scatter_add_rows(store, rows, delta):
    """`store[rows] += delta` as ONE XLA scatter-add (rows unique and
    sorted: aliases in place).  On the chip it costs a pass over `store`
    whatever it adds, and hardly less for fewer rows (PERF.md §6, PR 39)."""
    return store.at[rows].add(
        delta, unique_indices=True, indices_are_sorted=True)


@scope("gt.mem.dir_apply")
def _dir_apply_rows(d: ShL2Dir, px: ParallelCtx, sets, dwd, dshd):
    """Land full-width embedded-directory ROW deltas block-locally, in
    place: one add-a-delta per array (per-lane rows unique).  Zero
    deltas — masked-off lanes — add nothing.  Under its own scope: a
    gated phase's plan lands OUTSIDE the phase's cond, so the landing's
    device time is not the phase's.

    What a landing costs on the chip (v5e; `_hand/landing39.py`,
    PERF.md §6 PR 39): as an XLA scatter-add, a pass over the store it
    lands on — 3.5 ms on the 1.07 GB sharers store of 1,024 tiles at
    1,024 rows, 3.2 ms at 128, 0.46 ms on a store an eighth the size.
    So where the program is lowered for a TPU, has no sim axis and a
    sharers row is lane-aligned (512 tiles and up under the default
    8-way slice), the sharers plan lands through
    `row_landing.land_rows`, which moves only the plan's rows: 0.085 ms
    for the same 1,024.  Everywhere else, and for the int64 word store
    (1/32 the bytes; Mosaic has no int64), the scatter-add."""
    sets_l, dwd_l, dshd_l = px.lo((sets, dwd, dshd))
    Tl, S, W = d.sharers.shape
    lt = jnp.arange(Tl, dtype=jnp.int32)
    land = _scatter_add_rows
    if px.sim_axis is None and row_landing.can_land(Tl, S, W):
        land = functools.partial(
            jax.lax.platform_dependent,
            tpu=row_landing.land_rows, default=_scatter_add_rows)
    # the sharers store row-flat, as XLA lays it anyway: the scatter XLA
    # makes of a two-index one carries no name, so its device time would
    # read as the phase's (or nobody's) and not as this scope's
    return d.replace(
        word=d.word.at[lt, sets_l].add(
            dwd_l, unique_indices=True, indices_are_sorted=True),
        sharers=land(d.sharers.reshape(Tl * S, W), lt * S + sets_l,
                     dshd_l).reshape(Tl, S, W))


def _dir_scatter(d: ShL2Dir, px: ParallelCtx, sets, dw0, dw, dsh0, dsh,
                 acc: "_RowAcc | None" = None):
    """Apply the phase's accumulated full-width row updates — directly
    (ungated path) or deferred into `acc` so a gated phase's lax.cond
    returns the compact [T, W2(*SW)] row deltas instead of carrying the
    big stores (see shl2_engine_step's per-phase gating)."""
    if acc is not None:
        acc.add(sets, dw - dw0, dsh - dsh0)
        return d
    return _dir_apply_rows(d, px, sets, dw - dw0, dsh - dsh0)


class _RowAcc:
    """Deferred embedded-directory row deltas of one gated home phase
    (the shared-L2 analog of engine._DirAcc — the shl2 phases already
    compute row-form deltas, so the plan is just (sets, Δword rows,
    Δsharers rows), full-width replicated like the rows themselves)."""

    def __init__(self):
        self.plan = None

    def add(self, sets, dwd, dshd):
        if self.plan is not None:
            raise AssertionError(
                "_RowAcc: one _dir_scatter per gated shl2 phase")
        self.plan = (sets, dwd, dshd)

    def pack(self, d, n_tiles):
        if self.plan is not None:
            return self.plan
        return _RowAcc.zero_pack(d, n_tiles)

    @staticmethod
    def zero_pack(d, n_tiles):
        return (jnp.zeros(n_tiles, jnp.int32),
                jnp.zeros((n_tiles, d.word.shape[2]), I64),
                jnp.zeros((n_tiles, d.sharers.shape[2]), U32))


def _cond_nodir(pred, fn, ms):
    """Run a directory-free shl2 phase under a scalar-predicate lax.cond
    with the embedded directory detached from the carried operands."""
    d0 = ms.dir

    def run(m):
        return fn(m)

    def skip(m):
        return m, jnp.zeros((), jnp.int32)

    ms2, prog = jax.lax.cond(pred, run, skip, ms.replace(dir=None))
    return ms2.replace(dir=d0), prog


def _cond_dir(pred, fn, ms, n_tiles, px):
    """Run a home-side shl2 phase under a scalar-predicate lax.cond: the
    embedded directory is read inside (cond input, no double-buffering)
    but written only through the `_RowAcc` delta plan the cond returns;
    `_dir_apply_rows` lands the plan outside, in place, and only where
    the phase ran (`engine._run_if`: a skipped phase's plan is zero, and
    a landing is not free — on the chip it is priced by the plan's rows
    where `_dir_apply_rows` takes the row kernel, 0.085 ms at 1,024
    tiles, and by the whole sharers store where it takes the
    scatter-add, 3.5 ms there).  `fn(ms, acc) -> (ms, progress)` must leave ms.dir
    untouched."""
    d0 = ms.dir

    def run(m):
        acc = _RowAcc()
        m2, prog = fn(m.replace(dir=d0), acc)
        return m2.replace(dir=None), prog, acc.pack(d0, n_tiles)

    def skip(m):
        return (m, jnp.zeros((), jnp.int32), _RowAcc.zero_pack(d0, n_tiles))

    ms2, prog, plan = jax.lax.cond(pred, run, skip, ms.replace(dir=None))
    landed = _run_if(pred, lambda d: _dir_apply_rows(d, px, *plan), d0)
    return ms2.replace(dir=landed), prog


@struct.dataclass
class ShL2Txn:
    active: jax.Array      # bool[T]
    mtype: jax.Array       # uint8[T]
    line: jax.Array        # int32[T]
    requester: jax.Array   # int32[T]
    req_comp: jax.Array    # uint8[T] MOD_L1I / MOD_L1D
    time_ps: jax.Array     # int64[T]
    pending: jax.Array     # uint32[T, SW]
    dram_ready_ps: jax.Array  # int64[T] (FAR = no fetch in flight)
    got_flush: jax.Array   # bool[T] — dirty data arrived (L2 turns M)
    saved_valid: jax.Array
    saved_type: jax.Array
    saved_line: jax.Array
    saved_requester: jax.Array
    saved_comp: jax.Array
    saved_time_ps: jax.Array
    last_line: jax.Array
    last_done_ps: jax.Array


@struct.dataclass
class ShL2State:
    l1i: ca.CacheArrays
    l1d: ca.CacheArrays
    l2: ca.CacheArrays          # the local SLICE (home-indexed lines)
    dir: ShL2Dir
    mail: MemMailboxes
    txn: ShL2Txn
    req: RequesterState
    counters: MemCounters
    func_mem: jax.Array
    func_errors: jax.Array
    # bool[] — any protocol state outstanding; False lets the step skip
    # the engine entirely (see engine.mem_idle_out)
    live: jax.Array
    # int64[6] — per-phase lax.cond skip counts under phase gating
    # (SHL2_PHASE_NAMES order; see MemState.phase_skips)
    phase_skips: jax.Array = None
    # MEMORY-NoC port-queue state when memory = emesh_hop_by_hop (see
    # engine.mem_net_send); None otherwise
    noc: "object" = None


def init_shl2_state(mp: MemParams) -> ShL2State:
    """Build from the shared pieces (L1/L2 arrays, mailboxes, requester)."""
    base = init_mem_common(mp)
    T = mp.n_tiles
    S2, W2 = mp.l2.num_sets, mp.l2.num_ways
    SW = mp.sharer_words
    zdir = ShL2Dir(
        word=jnp.zeros((T, S2, W2), I64),
        sharers=jnp.zeros((T, S2, W2 * SW), U32),
    )
    txn = ShL2Txn(
        active=jnp.zeros(T, jnp.bool_),
        mtype=jnp.zeros(T, jnp.uint8),
        line=jnp.zeros(T, jnp.int32),
        requester=jnp.zeros(T, jnp.int32),
        req_comp=jnp.zeros(T, jnp.uint8),
        time_ps=jnp.zeros(T, I64),
        pending=jnp.zeros((T, SW), U32),
        dram_ready_ps=jnp.full(T, FAR, I64),
        got_flush=jnp.zeros(T, jnp.bool_),
        saved_valid=jnp.zeros(T, jnp.bool_),
        saved_type=jnp.zeros(T, jnp.uint8),
        saved_line=jnp.zeros(T, jnp.int32),
        saved_requester=jnp.zeros(T, jnp.int32),
        saved_comp=jnp.zeros(T, jnp.uint8),
        saved_time_ps=jnp.zeros(T, I64),
        last_line=jnp.full(T, -1, jnp.int32),
        last_done_ps=jnp.zeros(T, I64),
    )
    return ShL2State(dir=zdir, txn=txn, live=jnp.zeros((), jnp.bool_),
                     **base)


def _l2_home(mp: MemParams, line):
    """The L2 slice holding `line`: interleaved over ALL tiles
    (`l2_cache_hash_fn.cc` home lookup)."""
    return (line % mp.n_tiles).astype(jnp.int32)


def _dram_lat_ps(mp: MemParams, home, enabled):
    """DRAM fetch round trip from the home's L2 slice: network to the DRAM
    home + access + return (`DRAM_FETCH_REQ`/`REP`)."""
    mc = jnp.asarray(mp.mc_tiles, jnp.int32)
    dram_home = mc[(home % len(mp.mc_tiles)).astype(jnp.int32)]
    net = mem_net_latency_ps(mp, home, dram_home, mp.rep_bits, enabled)
    acc = jnp.where(enabled,
                    (mp.dram_latency_ns + mp.dram_processing_ns) * 1000, 0)
    return 2 * net + acc


def shl2_engine_step(
    mp: MemParams,
    ms: ShL2State,
    rec: RecView,
    clock_ps: jax.Array,
    freq_mhz: jax.Array,
    active: jax.Array,
    enabled,
    px: ParallelCtx = IDENT,
    fill_events: bool = False,
) -> MemStepOut:
    T = mp.n_tiles
    tiles = jnp.arange(T, dtype=jnp.int32)
    fmhz = freq_mhz.astype(I64)
    progress = jnp.zeros((), jnp.int32)
    mesi = mp.protocol.endswith("mesi")

    def ccyc(n, f=None):
        ps = cycles_to_ps(jnp.asarray(n, I64), fmhz if f is None else f)
        return jnp.where(enabled, ps, 0)

    sync_core_l1 = ccyc(mp.sync_cycles(MOD_CORE, MOD_L1D))
    sync_l1_net = ccyc(mp.sync_cycles(MOD_L1D, MOD_NET_MEM))
    sync_l2_net = ccyc(mp.sync_cycles(MOD_L2, MOD_NET_MEM))
    l2_access = ccyc(mp.l2.data_and_tags_cycles)

    # ======================================================================
    # (1) requester slot starts: L1-only lookup; misses go to the L2 home
    # ======================================================================
    flags = rec.flags
    # shared with engine.py + the mem_gate's skip decision — MUST stay the
    # same definition or the gate could idle-skip live slots
    from graphite_tpu.memory.engine import next_present_slot, slots_present

    present = slots_present(mp, rec, enabled)

    def next_present(slot):
        return next_present_slot(present, slot)

    def _phase_requester(ms):
        slot = next_present(ms.req.slot)
        has_slot = slot < 3
        idle = ms.req.phase == PHASE_IDLE
        starting = active & idle & has_slot

        s_is_icache = slot == 0
        s_addr = jnp.where(
            s_is_icache, rec.pc.astype(jnp.int32),
            jnp.where(slot == 1, rec.addr0.astype(jnp.int32),
                      rec.addr1.astype(jnp.int32)))
        s_line = (s_addr.astype(jnp.uint32) >> mp.line_bits).astype(jnp.int32)
        s_write = jnp.where(
            s_is_icache, False,
            jnp.where(slot == 1, (flags & FLAG_MEM0_WRITE) != 0,
                      (flags & FLAG_MEM1_WRITE) != 0))

        ibuf_hit = starting & s_is_icache & (s_line == ms.req.instr_buf)
        new_instr_buf = jnp.where(starting & s_is_icache, s_line,
                                  ms.req.instr_buf)

        # L1 rows: block-local gathers, ONE exchange, full-width row ops
        s_line_l = px.lo(s_line)
        rows_l = (
            ca.gather_row(ms.l1i, s_line_l, px.lo_const(mp.l1i.sets_mod)),
            ca.gather_row(ms.l1d, s_line_l, px.lo_const(mp.l1d.sets_mod)),
        )
        (l1i_row, l1d_row), _ = _rows_exchange(px, rows_l)
        l1i_hit, l1i_way, l1i_state = ca.row_lookup(l1i_row, s_line)
        l1d_hit, l1d_way, l1d_state = ca.row_lookup(l1d_row, s_line)
        l1_state = jnp.where(s_is_icache, l1i_state, l1d_state)
        l1_permit = jnp.where(s_write, state_writable(l1_state),
                              state_readable(l1_state))
        do_l1 = starting & ~ibuf_hit
        l1_hit_now = do_l1 & l1_permit
        l1_miss = do_l1 & ~l1_permit

        l1_dat = jnp.where(s_is_icache, ccyc(mp.l1i.data_and_tags_cycles),
                           ccyc(mp.l1d.data_and_tags_cycles))
        l1_tag = jnp.where(s_is_icache, ccyc(mp.l1i.tags_cycles),
                           ccyc(mp.l1d.tags_cycles))
        sclock = clock_ps + sync_core_l1
        l1_hit_done_ps = sclock + l1_dat

        # MESI silent upgrade: a write to an EXCLUSIVE L1 line promotes to M
        # with no messages (the write-hit path: E is writable)
        promote = l1_hit_now & s_write & (l1_state == EXCLUSIVE)
        l1d_row = ca.row_set_state(l1d_row, l1d_way, MODIFIED,
                                   promote & ~s_is_icache)
        # hits refresh recency under LRU; round_robin's update is a no-op
        if mp.l1i.replacement != "round_robin":
            l1i_row = ca.row_touch(l1i_row, l1i_way, l1_hit_now & s_is_icache)
        if mp.l1d.replacement != "round_robin":
            l1d_row = ca.row_touch(l1d_row, l1d_way, l1_hit_now & ~s_is_icache)
        l1i_upd = ca.scatter_row(ms.l1i, px.lo(l1i_row))
        l1d_upd = ca.scatter_row(ms.l1d, px.lo(l1d_row))

        # L1 miss: an upgrade (write to readable-but-unwritable line) keeps the
        # line until the reply; a plain miss sends the request right away.  In
        # both cases the L1 stays untouched here — the FILL path replaces it.
        s_home = _l2_home(mp, s_line)
        rq_type = jnp.where(s_write, MSG_EX_REQ, MSG_SH_REQ).astype(jnp.uint8)
        req_send_ps = sclock + l1_tag + sync_l1_net
        noc, rq_arrival = mem_net_send(
            mp, ms.noc, tiles, s_home, mp.req_bits, req_send_ps, l1_miss,
            enabled)
        mail = ms.mail
        # per-requester lane (one outstanding miss per tile): plain
        # masked selects, no matrix scatter
        mail = mail.replace(
            req_type=jnp.where(l1_miss, rq_type, mail.req_type),
            req_home=jnp.where(l1_miss, s_home, mail.req_home),
            req_line=jnp.where(l1_miss, s_line, mail.req_line),
            req_time=jnp.where(l1_miss, rq_arrival, mail.req_time),
        )

        slot_done_now = ibuf_hit | l1_hit_now
        slot_done_ps = jnp.where(ibuf_hit, clock_ps + ccyc(1), l1_hit_done_ps)
        req_state = ms.req.replace(
            phase=jnp.where(l1_miss, PHASE_WAIT_REPLY, ms.req.phase),
            line=jnp.where(l1_miss, s_line, ms.req.line),
            is_write=jnp.where(l1_miss, s_write, ms.req.is_write),
            component=jnp.where(
                l1_miss, jnp.where(s_is_icache, MOD_L1I, MOD_L1D),
                ms.req.component).astype(jnp.uint8),
            clock_ps=jnp.where(l1_miss, req_send_ps, ms.req.clock_ps),
            acc_ps=ms.req.acc_ps
            + jnp.where(slot_done_now, slot_done_ps - clock_ps, 0),
            slot_lat_ps=jnp.where(
                (slot_done_now[:, None]
                 & (jnp.arange(3)[None, :] == slot[:, None])),
                (slot_done_ps - clock_ps)[:, None], ms.req.slot_lat_ps),
            instr_buf=new_instr_buf,
            slot=jnp.where(slot_done_now, slot + 1,
                           jnp.where(starting, slot, ms.req.slot)),
        )
        counters = ms.counters.replace(
            l1i_hits=ms.counters.l1i_hits
            + ((l1_hit_now | ibuf_hit) & s_is_icache & enabled).astype(I64),
            l1i_misses=ms.counters.l1i_misses
            + (l1_miss & s_is_icache & enabled).astype(I64),
            l1d_read_hits=ms.counters.l1d_read_hits
            + (l1_hit_now & ~s_is_icache & ~s_write & enabled).astype(I64),
            l1d_read_misses=ms.counters.l1d_read_misses
            + (l1_miss & ~s_is_icache & ~s_write & enabled).astype(I64),
            l1d_write_hits=ms.counters.l1d_write_hits
            + (l1_hit_now & ~s_is_icache & s_write & enabled).astype(I64),
            l1d_write_misses=ms.counters.l1d_write_misses
            + (l1_miss & ~s_is_icache & s_write & enabled).astype(I64),
        )
        prog = jnp.sum(slot_done_now | l1_miss, dtype=jnp.int32)
        ms = ms.replace(l1i=l1i_upd, l1d=l1d_upd, mail=mail, req=req_state,
                        counters=counters, noc=noc)
        ms = _apply_functional(mp, ms, rec, slot, s_addr, s_write, slot_done_now)
        return ms, prog

    # per-phase gating as in the private-L2 engine: each predicate is
    # OR-ed over a campaign's sim axis (px.any_sim; the identity without
    # one), so a phase cond stays a cond under `vmap`
    gate = bool(getattr(mp, "phase_gate", False))
    # a lane that cannot start now cannot start later this iteration
    # (only the fill phase returns a lane to PHASE_IDLE)
    pred1 = px.any_sim(jnp.any(active & (ms.req.phase == PHASE_IDLE)
                               & (next_present(ms.req.slot) < 3)))
    with scope("gt.mem." + SHL2_PHASE_NAMES[0]):
        if gate:
            ms, p = _cond_nodir(pred1, _phase_requester, ms)
        else:
            ms, p = _phase_requester(ms)
    progress = progress + p

    # ======================================================================
    # (2) L1 sharers serve INV/FLUSH/WB from homes
    # ======================================================================
    pred2 = px.any_sim((ms.mail.fwd_type != MSG_NONE).any())
    with scope("gt.mem." + SHL2_PHASE_NAMES[1]):
        if gate:
            ms, p = _cond_nodir(
                pred2,
                lambda m: _sharer_step(mp, m, fmhz, enabled,
                                       jnp.zeros((), jnp.int32),
                                       sync_l1_net, px),
                ms)
        else:
            ms, p = _sharer_step(mp, ms, fmhz, enabled,
                                 jnp.zeros((), jnp.int32), sync_l1_net, px)
    progress = progress + p

    # ======================================================================
    # (3) homes consume L1 evictions (directory + L2 dirty fill)
    # ======================================================================
    pred3 = px.any_sim((ms.mail.evict_type != MSG_NONE).any())
    with scope("gt.mem." + SHL2_PHASE_NAMES[2]):
        if gate:
            ms, p = _cond_dir(
                pred3,
                lambda m, a: _home_evictions(mp, m, l2_access, enabled,
                                             jnp.zeros((), jnp.int32), px,
                                             acc=a),
                ms, T, px)
        else:
            ms, p = _home_evictions(mp, ms, l2_access, enabled,
                                    jnp.zeros((), jnp.int32), px)
    progress = progress + p

    # ======================================================================
    # (4) homes consume acks / dram arrivals, finish transactions
    # ======================================================================
    pred4 = px.any_sim((ms.mail.ack_type != MSG_NONE).any()
                       | ms.txn.active.any())
    with scope("gt.mem." + SHL2_PHASE_NAMES[3]):
        if gate:
            ms, p = _cond_dir(
                pred4,
                lambda m, a: _home_finish(mp, m, l2_access, sync_l2_net,
                                          enabled, jnp.zeros((), jnp.int32),
                                          mesi, px, acc=a),
                ms, T, px)
        else:
            ms, p = _home_finish(mp, ms, l2_access, sync_l2_net, enabled,
                                 jnp.zeros((), jnp.int32), mesi, px)
    progress = progress + p

    # ======================================================================
    # (5) homes start transactions
    # ======================================================================
    pred5 = px.any_sim((ms.mail.req_type != MSG_NONE).any()
                       | (ms.txn.saved_valid & ~ms.txn.active).any())
    with scope("gt.mem." + SHL2_PHASE_NAMES[4]):
        if gate:
            ms, p = _cond_dir(
                pred5,
                lambda m, a: _home_starts(mp, m, l2_access, sync_l2_net,
                                          enabled, jnp.zeros((), jnp.int32),
                                          mesi, px, acc=a),
                ms, T, px)
        else:
            ms, p = _home_starts(mp, ms, l2_access, sync_l2_net, enabled,
                                 jnp.zeros((), jnp.int32), mesi, px)
    progress = progress + p

    # ======================================================================
    # (6) requesters consume replies (fill L1)
    # ======================================================================
    pred6 = px.any_sim(((ms.req.phase == PHASE_WAIT_REPLY)
                        & (ms.mail.rep_type != MSG_NONE)).any())
    # fill observability for the round-21 latency histograms: phase 6's
    # fill is the only writer of req.slot / req.acc_ps in this block, so
    # the pre/post delta is the exact per-call miss completion (see
    # engine.MemStepOut.fill_now)
    slot_pre6 = ms.req.slot
    acc_pre6 = ms.req.acc_ps
    with scope("gt.mem." + SHL2_PHASE_NAMES[5]):
        if gate:
            ms, p = _cond_nodir(
                pred6,
                lambda m: _requester_fill(mp, m, rec, clock_ps, fmhz, enabled,
                                          jnp.zeros((), jnp.int32),
                                          sync_l1_net, px),
                ms)
        else:
            ms, p = _requester_fill(mp, ms, rec, clock_ps, fmhz, enabled,
                                    jnp.zeros((), jnp.int32), sync_l1_net, px)
    progress = progress + p

    final_slot = next_present(ms.req.slot)
    mem_complete = (ms.req.phase == PHASE_IDLE) & (final_slot >= 3)
    # protocol-liveness flag (see engine.mem_idle_out): includes in-flight
    # home-side DRAM fetches, which this engine tracks outside txn.active
    from graphite_tpu.memory.engine import protocol_live

    ms = ms.replace(live=protocol_live(
        ms, (ms.txn.dram_ready_ps < FAR).any()))
    if gate:
        skipped = 1 - jnp.stack(
            [pred1, pred2, pred3, pred4, pred5, pred6]).astype(I64)
        ms = ms.replace(phase_skips=ms.phase_skips + skipped)
    return MemStepOut(
        ms=ms, mem_complete=mem_complete, acc_ps=ms.req.acc_ps,
        slot_lat_ps=ms.req.slot_lat_ps, progress=progress,
        fill_now=(ms.req.slot != slot_pre6) if fill_events else None,
        fill_lat_ps=(ms.req.acc_ps - acc_pre6) if fill_events else None,
    )


def _apply_functional(mp, ms: ShL2State, rec: RecView, slot, s_addr,
                      s_write, mask):
    if mp.func_mem_words <= 0:
        return ms
    word = ((s_addr.astype(jnp.uint32) >> 2) % mp.func_mem_words).astype(
        jnp.int32)
    value = jnp.where(slot == 1, rec.aux0, rec.aux1).astype(jnp.uint32)
    wr = mask & s_write
    tgt = jnp.where(wr, word, mp.func_mem_words)
    fm = ms.func_mem.at[tgt].set(jnp.where(wr, value, 0))
    check = mask & ~s_write & (slot == 1) & ((rec.flags & FLAG_CHECK) != 0)
    loaded = fm[word]
    errs = jnp.sum(check & (loaded != rec.aux0.astype(jnp.uint32)),
                   dtype=I64)
    return ms.replace(func_mem=fm, func_errors=ms.func_errors + errs)


def _sharer_step(mp, ms: ShL2State, fmhz, enabled, progress, sync_l1_net,
                 px: ParallelCtx = IDENT):
    """L1-side service of INV/FLUSH/WB (`l1_cache_cntlr.cc` handlers)."""
    T = mp.n_tiles
    tiles = jnp.arange(T, dtype=jnp.int32)
    mail = ms.mail

    def ccyc(n):
        ps = cycles_to_ps(jnp.asarray(n, I64), fmhz)
        return jnp.where(enabled, ps, 0)

    h, found = _row_earliest(mail.fwd_type, mail.fwd_time)
    ftype = mail.fwd_type[tiles, h]
    fline = mail.fwd_line[tiles, h]
    ftime = mail.fwd_time[tiles, h]

    fline_l = px.lo(fline)
    rows_l = (
        ca.gather_row(ms.l1i, fline_l, px.lo_const(mp.l1i.sets_mod)),
        ca.gather_row(ms.l1d, fline_l, px.lo_const(mp.l1d.sets_mod)),
    )
    (l1i_row, l1d_row), _ = _rows_exchange(px, rows_l)
    l1i_hit, l1i_way, l1i_state = ca.row_lookup(l1i_row, fline)
    l1d_hit, l1d_way, l1d_state = ca.row_lookup(l1d_row, fline)
    have = l1i_hit | l1d_hit
    serve = found & have
    was_dirty = ((l1d_hit & ((l1d_state == MODIFIED)))
                 | (l1i_hit & (l1i_state == MODIFIED)))

    is_inv = ftype == MSG_INV_REQ
    is_wb = ftype == MSG_WB_REQ
    done_ps = ftime + sync_l1_net + ccyc(mp.l1d.data_and_tags_cycles)

    inv_do = serve & ~is_wb
    l1i_row = ca.row_invalidate(l1i_row, fline, inv_do & l1i_hit)
    l1d_row = ca.row_invalidate(l1d_row, fline, inv_do & l1d_hit)
    # WB downgrades M/E -> SHARED, data written back
    l1i_row = ca.row_set_state(l1i_row, l1i_way, SHARED,
                               serve & is_wb & l1i_hit)
    l1d_row = ca.row_set_state(l1d_row, l1d_way, SHARED,
                               serve & is_wb & l1d_hit)
    l1i = ca.scatter_row(ms.l1i, px.lo(l1i_row))
    l1d = ca.scatter_row(ms.l1d, px.lo(l1d_row))

    # ack: FLUSH_REP when dirty data travels (flush of M, or WB of M),
    # else INV_REP / WB_REP
    ack = jnp.where(
        is_inv, MSG_INV_REP,
        jnp.where(is_wb,
                  jnp.where(was_dirty, MSG_FLUSH_REP, MSG_WB_REP),
                  MSG_FLUSH_REP)).astype(jnp.uint8)
    # a FLUSH of a clean (S/E) line carries no data: INV_REP
    ack = jnp.where((ftype == MSG_FLUSH_REQ) & ~was_dirty, MSG_INV_REP, ack)
    ack_bits = jnp.where(ack == MSG_INV_REP, mp.req_bits, mp.rep_bits)
    noc, ack_arrival = mem_net_send(
        mp, ms.noc, tiles, h, ack_bits, done_ps, serve, enabled)
    wh = jnp.where(serve, h, 0)
    mail = mail.replace(
        ack_type=mail.ack_type.at[wh, tiles].set(
            jnp.where(serve, ack, mail.ack_type[wh, tiles])),
        ack_line=mail.ack_line.at[wh, tiles].set(
            jnp.where(serve, fline, mail.ack_line[wh, tiles])),
        ack_time=mail.ack_time.at[wh, tiles].set(
            jnp.where(serve, ack_arrival, mail.ack_time[wh, tiles])),
    )
    ch = jnp.where(found, h, 0)
    mail = mail.replace(
        fwd_type=mail.fwd_type.at[tiles, ch].set(
            jnp.where(found, MSG_NONE, mail.fwd_type[tiles, ch])),
    )
    counters = ms.counters.replace(
        invalidations=ms.counters.invalidations
        + (serve & is_inv & enabled).astype(I64))
    progress = progress + jnp.sum(found, dtype=jnp.int32)
    return ms.replace(l1i=l1i, l1d=l1d, mail=mail, counters=counters,
                      noc=noc), progress


def _home_evictions(mp, ms: ShL2State, l2_access, enabled, progress,
                    px: ParallelCtx = IDENT, acc: "_RowAcc | None" = None):
    """L1 eviction notices update the embedded directory; dirty flushes
    land in the L2 slice (its line turns MODIFIED wrt DRAM)."""
    T = mp.n_tiles
    tiles = jnp.arange(T, dtype=jnp.int32)
    mail = ms.mail

    src, found = _row_earliest(mail.evict_type, mail.evict_time)
    etype = mail.evict_type[tiles, src]
    eline = mail.evict_line[tiles, src]
    etime = mail.evict_time[tiles, src]

    eline_l = px.lo(eline)
    mod_l = px.lo_const(mp.l2.sets_mod)
    l2row_l = ca.gather_row(ms.l2, eline_l, mod_l)
    sets_l = (eline_l % jnp.asarray(mod_l)).astype(jnp.int32)
    dw_l, dsh_l = _dir_rows_local(ms.dir, sets_l)
    (l2row,), (dw, dsh) = _rows_exchange(px, (l2row_l,), (dw_l, dsh_l))
    dw0, dsh0 = dw, dsh
    l2_hit, l2_way, l2_state = ca.row_lookup(l2row, eline)
    sets = (eline % jnp.asarray(mp.l2.sets_mod)).astype(jnp.int32)
    apply = found & l2_hit
    dstate, owner, sharers, nsh, cloc = _entry_at(dw, dsh, l2_way)

    was_sharer = test_bit(sharers, src)
    new_sharers = clear_bit(sharers, src, apply)
    new_nsh = nsh - (apply & was_sharer).astype(jnp.int32)
    is_flush = etype == MSG_FLUSH_REP
    from_owner = src == owner
    new_owner = jnp.where(apply & from_owner, -1, owner)
    new_dstate = jnp.where(
        apply,
        jnp.where(new_nsh == 0, DIR_UNCACHED, DIR_SHARED),
        dstate).astype(jnp.uint8)
    dw = _row_update(dw, l2_way, apply, dstate=new_dstate, owner=new_owner,
                     nsharers=new_nsh)
    dsh = _rowsh_update(dsh, l2_way, apply, new_sharers)
    d = _dir_scatter(ms.dir, px, sets, dw0, dw, dsh0, dsh, acc=acc)
    # dirty flush data lands in the slice
    l2row = ca.row_set_state(l2row, l2_way, MODIFIED, apply & is_flush)
    l2 = ca.scatter_row(ms.l2, px.lo(l2row))

    txn = ms.txn
    txn_match = txn.active & found & (txn.line == eline)
    txn = txn.replace(
        pending=clear_bit(txn.pending, src, txn_match),
        time_ps=jnp.where(txn_match,
                          jnp.maximum(txn.time_ps, etime + l2_access),
                          txn.time_ps),
        got_flush=txn.got_flush | (txn_match & is_flush),
    )
    csrc = jnp.where(found, src, 0)
    mail = mail.replace(
        evict_type=mail.evict_type.at[tiles, csrc].set(
            jnp.where(found, MSG_NONE, mail.evict_type[tiles, csrc])),
    )
    counters = ms.counters.replace(
        evictions=ms.counters.evictions + (found & enabled).astype(I64))
    progress = progress + jnp.sum(found, dtype=jnp.int32)
    return ms.replace(dir=d, l2=l2, mail=mail, txn=txn,
                      counters=counters), progress


def _home_finish(mp, ms: ShL2State, l2_access, sync_l2_net, enabled,
                 progress, mesi, px: ParallelCtx = IDENT,
                 acc: "_RowAcc | None" = None):
    """Consume acks + DRAM arrivals; finish when nothing is pending."""
    T = mp.n_tiles
    tiles = jnp.arange(T, dtype=jnp.int32)
    mail = ms.mail
    txn = ms.txn

    match = (mail.ack_type != MSG_NONE) & txn.active[:, None] & (
        mail.ack_line == txn.line[:, None])
    any_match = match.any(axis=1)
    max_ack = jnp.where(match, mail.ack_time, 0).max(axis=1)
    got_flush = (match & (mail.ack_type == MSG_FLUSH_REP)).any(axis=1)

    SW = mp.sharer_words
    pad = SW * 32 - T
    mpad = jnp.pad(match, ((0, 0), (0, pad)))
    acked_words = (
        mpad.reshape(T, SW, 32).astype(U32)
        << jnp.arange(32, dtype=U32)[None, None, :]
    ).sum(axis=2, dtype=U32)
    txn = txn.replace(
        pending=txn.pending & ~acked_words,
        time_ps=jnp.where(any_match,
                          jnp.maximum(txn.time_ps, max_ack + l2_access),
                          txn.time_ps),
        got_flush=txn.got_flush | got_flush,
    )
    mail = mail.replace(ack_type=jnp.where(
        mail.ack_type != MSG_NONE, MSG_NONE, mail.ack_type))

    # the phase's L2 + directory rows for each home's transaction line
    tl_l = px.lo(txn.line)
    mod_l = px.lo_const(mp.l2.sets_mod)
    l2row_l = ca.gather_row(ms.l2, tl_l, mod_l)
    sets_l = (tl_l % jnp.asarray(mod_l)).astype(jnp.int32)
    dw_l, dsh_l = _dir_rows_local(ms.dir, sets_l)
    (l2row,), (dw, dsh) = _rows_exchange(px, (l2row_l,), (dw_l, dsh_l))
    dw0, dsh0 = dw, dsh
    sets = (txn.line % jnp.asarray(mp.l2.sets_mod)).astype(jnp.int32)

    # DRAM arrival: the fetched line fills the slice in SHARED
    dram_in = txn.active & (txn.dram_ready_ps < FAR) & (
        txn.pending == 0).all(axis=1)
    l2_hit, l2_way, _ = ca.row_lookup(l2row, txn.line)
    l2row = ca.row_set_state(l2row, l2_way, SHARED, dram_in & l2_hit)
    txn = txn.replace(
        time_ps=jnp.where(dram_in,
                          jnp.maximum(txn.time_ps, txn.dram_ready_ps),
                          txn.time_ps),
        dram_ready_ps=jnp.where(dram_in, FAR, txn.dram_ready_ps),
    )

    # finish: no pending acks, no pending dram
    no_pending = (txn.pending == 0).all(axis=1) & (txn.dram_ready_ps >= FAR)
    finish = txn.active & no_pending
    is_ex = txn.mtype == MSG_EX_REQ
    is_sh = txn.mtype == MSG_SH_REQ
    is_nullify = txn.mtype == MSG_NULLIFY

    _, l2_way, l2_state = ca.row_lookup(l2row, txn.line)
    r = txn.requester
    rbit = set_bit(jnp.zeros((T, mp.sharer_words), U32), r, finish)
    dstate, owner, sharers, nsh, cloc = _entry_at(dw, dsh, l2_way)

    # dirty acks flushed data into the slice
    l2row = ca.row_set_state(l2row, l2_way, MODIFIED,
                             finish & txn.got_flush & ~is_nullify)

    # EX finish: directory MODIFIED owner=r
    exf = finish & is_ex
    dw = _row_update(dw, l2_way, exf,
                     dstate=jnp.full(T, DIR_MODIFIED, jnp.uint8), owner=r,
                     nsharers=jnp.ones(T, jnp.int32), cloc=txn.req_comp)
    dsh = _rowsh_update(dsh, l2_way, exf, rbit)
    # SH finish: add r as a sharer; MESI grants EXCLUSIVE when alone
    shf = finish & is_sh
    had = test_bit(sharers, r)
    alone = (nsh - had.astype(jnp.int32)) == 0
    excl = shf & alone & mesi
    sh_dstate = jnp.where(excl, DIR_EXCLUSIVE, DIR_SHARED).astype(jnp.uint8)
    dw = _row_update(dw, l2_way, shf, dstate=sh_dstate,
                     owner=jnp.where(excl, r, -1),
                     nsharers=nsh + (~had).astype(jnp.int32),
                     cloc=txn.req_comp)
    dsh = _rowsh_update(dsh, l2_way, shf, sharers | rbit)
    # NULLIFY finish: entry dies; dirty data (slice M or flushed) → DRAM
    nlf = finish & is_nullify
    wb_dram = nlf & ((l2_state == MODIFIED) | txn.got_flush)
    l2row = ca.row_invalidate(l2row, txn.line, nlf)
    dw = _row_update(dw, l2_way, nlf,
                     dstate=jnp.full(T, DIR_UNCACHED, jnp.uint8),
                     owner=jnp.full(T, -1, jnp.int32),
                     nsharers=jnp.zeros(T, jnp.int32))
    dsh = _rowsh_update(dsh, l2_way, nlf,
                        jnp.zeros((T, mp.sharer_words), U32))
    l2 = ca.scatter_row(ms.l2, px.lo(l2row))
    d = _dir_scatter(ms.dir, px, sets, dw0, dw, dsh0, dsh, acc=acc)

    # reply to the requester (the slice access was charged at txn start)
    rep_ready = txn.time_ps + sync_l2_net
    rep_msg = jnp.where(
        finish & is_ex, MSG_EX_REP,
        jnp.where(excl, MSG_EXCL_REP, MSG_SH_REP)).astype(jnp.uint8)
    rep_go = finish & ~is_nullify
    noc, rep_arrival = mem_net_send(
        mp, ms.noc, tiles, r, mp.rep_bits, rep_ready, rep_go, enabled)
    wr = jnp.where(rep_go, r, 0)
    mail = mail.replace(
        rep_type=mail.rep_type.at[wr].add(
            jnp.where(rep_go, rep_msg, 0).astype(jnp.uint8)),
        rep_time=mail.rep_time.at[wr].add(
            jnp.where(rep_go, rep_arrival, 0)),
    )
    mail = mail.replace(
        fwd_type=jnp.where(finish[None, :], MSG_NONE, mail.fwd_type))
    txn = txn.replace(
        active=txn.active & ~finish,
        got_flush=txn.got_flush & ~finish,
        last_line=jnp.where(finish, txn.line, txn.last_line),
        last_done_ps=jnp.where(finish, rep_ready, txn.last_done_ps),
    )
    counters = ms.counters.replace(
        dram_writes=ms.counters.dram_writes + (wb_dram & enabled).astype(I64),
    )
    progress = progress + jnp.sum(finish, dtype=jnp.int32) + jnp.sum(
        any_match | dram_in, dtype=jnp.int32)
    return ms.replace(l2=l2, dir=d, mail=mail, txn=txn,
                      counters=counters, noc=noc), progress


def _home_starts(mp, ms: ShL2State, l2_access, sync_l2_net, enabled,
                 progress, mesi, px: ParallelCtx = IDENT,
                 acc: "_RowAcc | None" = None):
    T = mp.n_tiles
    tiles = jnp.arange(T, dtype=jnp.int32)
    mail = ms.mail
    txn = ms.txn

    can_start = ~txn.active
    use_saved = can_start & txn.saved_valid
    r_col, r_found = _req_earliest(mail)
    use_pop = can_start & ~use_saved & r_found
    starting = use_saved | use_pop
    rtype = jnp.where(use_saved, txn.saved_type,
                      mail.req_type[r_col]).astype(jnp.uint8)
    rline = jnp.where(use_saved, txn.saved_line, mail.req_line[r_col])
    rreq = jnp.where(use_saved, txn.saved_requester, r_col)
    rcomp = jnp.where(use_saved, txn.saved_comp, MOD_L1D).astype(jnp.uint8)
    rtime = jnp.where(use_saved, txn.saved_time_ps,
                      mail.req_time[r_col])
    rtime = rtime + jnp.where(use_saved, 0, sync_l2_net)
    rtime = jnp.where(starting & (rline == txn.last_line),
                      jnp.maximum(rtime, txn.last_done_ps), rtime)
    mail = _req_consume(mail, use_pop, r_col)
    txn = txn.replace(saved_valid=txn.saved_valid & ~use_saved)

    # ---- L2 slice lookup / allocation (all on rline's SET: the victim
    # and the effective line share it, so ONE row exchange serves the
    # whole phase) ---------------------------------------------------------
    rline_l = px.lo(rline)
    mod_l = px.lo_const(mp.l2.sets_mod)
    l2row_l = ca.gather_row(ms.l2, rline_l, mod_l)
    sets_l = (rline_l % jnp.asarray(mod_l)).astype(jnp.int32)
    dw_l, dsh_l = _dir_rows_local(ms.dir, sets_l)
    (l2row,), (dw, dsh) = _rows_exchange(px, (l2row_l,), (dw_l, dsh_l))
    dw0, dsh0 = dw, dsh
    sets = (rline % jnp.asarray(mp.l2.sets_mod)).astype(jnp.int32)

    l2_hit, way, l2_state = ca.row_lookup(l2row, rline)
    # allocate on miss; a valid victim with L1 copies runs NULLIFY first
    v_way, v_valid, v_line, v_state = ca.row_pick_victim(
        l2row, mp.l2.replacement, mp.l2.ways_limit)
    v_dstate, v_owner, v_sharers, v_nsh, v_cloc = _entry_at(dw, dsh, v_way)
    need_alloc = starting & ~l2_hit
    nullify_live = need_alloc & v_valid & (v_dstate != DIR_UNCACHED)
    # clean victim with no L1 copies: drop now (dirty → DRAM write)
    silent_kill = need_alloc & v_valid & (v_dstate == DIR_UNCACHED)
    l2row = ca.row_invalidate(l2row, v_line, silent_kill)
    dram_wb = silent_kill & (v_state == MODIFIED)

    txn = txn.replace(
        saved_valid=jnp.where(nullify_live, True, txn.saved_valid),
        saved_type=jnp.where(nullify_live, rtype, txn.saved_type),
        saved_line=jnp.where(nullify_live, rline, txn.saved_line),
        saved_requester=jnp.where(nullify_live, rreq, txn.saved_requester),
        saved_comp=jnp.where(nullify_live, rcomp, txn.saved_comp),
        saved_time_ps=jnp.where(nullify_live, rtime, txn.saved_time_ps),
    )
    # install the new line (DATA_INVALID until DRAM returns)
    do_install = need_alloc & ~nullify_live
    alloc_way = v_way  # pick_victim returns invalid-way-first
    l2row = ca.row_insert(l2row, rline, alloc_way, DATA_INVALID, do_install)
    dw = _row_update(dw, alloc_way, do_install,
                     dstate=jnp.full(T, DIR_UNCACHED, jnp.uint8),
                     owner=jnp.full(T, -1, jnp.int32),
                     nsharers=jnp.zeros(T, jnp.int32))
    dsh = _rowsh_update(dsh, alloc_way, do_install,
                        jnp.zeros((T, mp.sharer_words), U32))

    eff_line = jnp.where(nullify_live, v_line, rline)
    eff_type = jnp.where(nullify_live, MSG_NULLIFY, rtype).astype(jnp.uint8)
    eff_time = rtime + l2_access
    run_req = starting & ~nullify_live

    # re-read the directory for the effective line (post-install rows)
    _, eff_way, eff_l2_state = ca.row_lookup(l2row, eff_line)
    dstate, owner, sharers, nsh, cloc = _entry_at(dw, dsh, eff_way)

    is_ex = eff_type == MSG_EX_REQ
    is_sh = eff_type == MSG_SH_REQ
    data_missing = run_req & (eff_l2_state == DATA_INVALID)

    # (a) data present, dstate FSM
    served = run_req & ~data_missing
    uncached = dstate == DIR_UNCACHED
    shared = dstate == DIR_SHARED
    owned_like = (dstate == DIR_MODIFIED) | (dstate == DIR_EXCLUSIVE)

    # immediate finishes: SH on UNCACHED/SHARED, EX on UNCACHED → resolved
    # by the finish pass next iteration (pending stays empty).  Fan-outs:
    # EX on SHARED → INV sharers; anything on M/E → FLUSH/WB the owner;
    # NULLIFY → INV/FLUSH everyone.
    is_nullify = eff_type == MSG_NULLIFY
    fan_inv = (served & is_ex & shared) | (nullify_live & shared)
    fan_owner = ((served | nullify_live) & owned_like)
    owner_bits = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                         jnp.clip(owner, 0, T - 1), fan_owner)
    pending = jnp.where(fan_inv[:, None], sharers, owner_bits)
    fan = fan_inv | fan_owner
    fwd_msg = jnp.where(
        fan_inv, MSG_INV_REQ,
        jnp.where(is_sh, MSG_WB_REQ, MSG_FLUSH_REQ)).astype(jnp.uint8)
    # EX on SHARED where the requester itself is a sharer: don't ask the
    # requester to invalidate its own line (upgrade) — clear its bit.
    # ONLY for the upgrade case: a NULLIFY sweep must invalidate the saved
    # requester's copy of the VICTIM line too, or it would keep a stale L1
    # copy after the directory entry dies.
    upgrade_clear = served & is_ex & shared
    pending = clear_bit(pending, jnp.clip(rreq, 0, T - 1),
                        upgrade_clear & test_bit(pending, rreq))

    # ---- directory-scheme variants on the embedded L1-sharer directory
    # (`l2_directory_cfg.cc` analog; same semantics as the private-L2
    # engine's schemes — see memory/engine.py)
    k = mp.max_hw_sharers
    already = test_bit(sharers, rreq)
    sh_over = jnp.zeros((T,), jnp.bool_)
    over_bc = jnp.zeros((T,), jnp.bool_)
    if mp.dir_type == "limited_no_broadcast":
        # SH on SHARED at capacity: displace the lowest tracked sharer
        sh_over = served & is_sh & shared & (nsh >= k) & ~already
        victim = lowest_sharer(sharers)
        victim_bits = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                              jnp.clip(victim, 0, T - 1),
                              sh_over & (victim >= 0))
        dw = _row_update(dw, eff_way, sh_over, nsharers=nsh - 1)
        dsh = _rowsh_update(dsh, eff_way, sh_over, sharers & ~victim_bits)
        pending = jnp.where(sh_over[:, None], victim_bits, pending)
        fwd_msg = jnp.where(sh_over, MSG_INV_REQ, fwd_msg).astype(jnp.uint8)
        fan = fan | sh_over
        # M/E at capacity (k=1): the owner's WB becomes a FLUSH and the
        # entry empties (addSharer failure on the downgrade); the finish
        # then installs {requester} alone (MESI re-grants EXCLUSIVE)
        sh_over_m = served & is_sh & owned_like & (nsh >= k) & ~already
        fwd_msg = jnp.where(sh_over_m, MSG_FLUSH_REQ,
                            fwd_msg).astype(jnp.uint8)
        dw = _row_update(dw, eff_way, sh_over_m,
                         dstate=jnp.full(T, DIR_UNCACHED, jnp.uint8),
                         owner=jnp.full(T, -1, jnp.int32),
                         nsharers=jnp.zeros(T, jnp.int32))
        dsh = _rowsh_update(dsh, eff_way, sh_over_m,
                            jnp.zeros((T, mp.sharer_words), U32))
    if mp.dir_type == "limitless":
        sw_mode = (nsh > k) | (is_sh & ~already & (nsh >= k)
                               & (shared | owned_like))
        eff_time = eff_time + jnp.where(
            enabled & starting & sw_mode,
            cycles_to_ps(jnp.asarray(mp.limitless_trap_cycles, I64),
                         mp.dir_freq_mhz),
            0)
    l2 = ca.scatter_row(ms.l2, px.lo(l2row))
    d = _dir_scatter(ms.dir, px, sets, dw0, dw, dsh0, dsh, acc=acc)

    activate = fan | data_missing | served | nullify_live
    txn = txn.replace(
        active=txn.active | (starting & activate),
        mtype=jnp.where(starting, eff_type, txn.mtype).astype(jnp.uint8),
        line=jnp.where(starting, eff_line, txn.line),
        requester=jnp.where(starting, rreq, txn.requester),
        req_comp=jnp.where(starting, rcomp, txn.req_comp).astype(jnp.uint8),
        time_ps=jnp.where(starting, eff_time, txn.time_ps),
        pending=jnp.where(starting[:, None], pending, txn.pending),
        got_flush=jnp.where(starting, False, txn.got_flush),
        dram_ready_ps=jnp.where(
            data_missing,
            eff_time + _dram_lat_ps(mp, tiles, enabled),
            jnp.where(starting, FAR, txn.dram_ready_ps)),
    )

    # multicast forwards
    targets = unpack_sharers(pending, T)
    send = fan[:, None] & targets
    if mp.dir_type in ("ackwise", "limited_broadcast"):
        # overflowed entries lose sharer precision: INV sweeps broadcast to
        # every tile except the requester (its upgrade copy must survive);
        # acks still awaited only from true holders (non-holders silent)
        over_bc = fan_inv & (nsh > k)
        send = send | (over_bc[:, None]
                       & (tiles[None, :] != jnp.clip(rreq, 0, T - 1)[:, None]))
    send_t = send.T
    noc, arrive = mem_net_fanout(
        mp, ms.noc, send, mp.req_bits, eff_time, enabled)
    mail = mail.replace(
        fwd_type=jnp.where(send_t, fwd_msg[None, :], mail.fwd_type),
        fwd_line=jnp.where(send_t, eff_line[None, :], mail.fwd_line),
        fwd_time=jnp.where(send_t, arrive.T, mail.fwd_time),
    )
    counters = ms.counters.replace(
        dir_accesses=ms.counters.dir_accesses
        + (starting & enabled).astype(I64),
        dir_broadcasts=ms.counters.dir_broadcasts
        + (over_bc & enabled).astype(I64),
        l2_hits=ms.counters.l2_hits
        + (run_req & ~data_missing & enabled).astype(I64),
        l2_misses=ms.counters.l2_misses
        + (data_missing & enabled).astype(I64),
        dram_reads=ms.counters.dram_reads
        + (data_missing & enabled).astype(I64),
        dram_writes=ms.counters.dram_writes + (dram_wb & enabled).astype(I64),
        dram_total_lat_ps=ms.counters.dram_total_lat_ps
        + jnp.where(data_missing & enabled,
                    (mp.dram_latency_ns + mp.dram_processing_ns) * 1000, 0),
    )
    progress = progress + jnp.sum(starting, dtype=jnp.int32)
    return ms.replace(l2=l2, dir=d, mail=mail, txn=txn,
                      counters=counters, noc=noc), progress


def _requester_fill(mp, ms: ShL2State, rec: RecView, clock_ps, fmhz,
                    enabled, progress, sync_l1_net,
                    px: ParallelCtx = IDENT):
    """Reply fills the L1 (`handleMsgFromL2Cache` → insertCacheLine)."""
    T = mp.n_tiles
    tiles = jnp.arange(T, dtype=jnp.int32)
    mail = ms.mail

    def ccyc(n):
        ps = cycles_to_ps(jnp.asarray(n, I64), fmhz)
        return jnp.where(enabled, ps, 0)

    have_rep = (ms.req.phase == PHASE_WAIT_REPLY) & (mail.rep_type != MSG_NONE)
    line = ms.req.line
    comp_l1i = ms.req.component == MOD_L1I
    new_state = jnp.where(
        mail.rep_type == MSG_EX_REP, MODIFIED,
        jnp.where(mail.rep_type == MSG_EXCL_REP, EXCLUSIVE,
                  SHARED)).astype(jnp.uint8)

    # Upgrade replies land in the line's EXISTING way (the S copy stays
    # put during an EX upgrade); only true misses pick a victim.
    line_l = px.lo(line)
    rows_l = (
        ca.gather_row(ms.l1i, line_l, px.lo_const(mp.l1i.sets_mod)),
        ca.gather_row(ms.l1d, line_l, px.lo_const(mp.l1d.sets_mod)),
    )
    (l1i_row, l1d_row), _ = _rows_exchange(px, rows_l)
    l1i_hit, l1i_hway, _ = ca.row_lookup(l1i_row, line)
    l1d_hit, l1d_hway, _ = ca.row_lookup(l1d_row, line)
    l1i_vway, l1i_vv, l1i_vline, l1i_vstate = ca.row_pick_victim(
        l1i_row, mp.l1i.replacement, mp.l1i.ways_limit)
    l1d_vway, l1d_vv, l1d_vline, l1d_vstate = ca.row_pick_victim(
        l1d_row, mp.l1d.replacement, mp.l1d.ways_limit)
    l1i_way = jnp.where(l1i_hit, l1i_hway, l1i_vway)
    l1d_way = jnp.where(l1d_hit, l1d_hway, l1d_vway)
    already = jnp.where(comp_l1i, l1i_hit, l1d_hit)
    v_valid = jnp.where(comp_l1i, l1i_vv, l1d_vv) & ~already
    v_line = jnp.where(comp_l1i, l1i_vline, l1d_vline)
    v_state = jnp.where(comp_l1i, l1i_vstate, l1d_vstate)
    v_home = _l2_home(mp, v_line)
    need_evict = have_rep & v_valid
    evict_busy = mail.evict_type[v_home, tiles] != MSG_NONE
    fill = have_rep & ~(need_evict & evict_busy)
    evict_go = need_evict & fill

    l1i_row = ca.row_insert(l1i_row, line, l1i_way, new_state,
                            fill & comp_l1i)
    l1d_row = ca.row_insert(l1d_row, line, l1d_way, new_state,
                            fill & ~comp_l1i)
    l1i = ca.scatter_row(ms.l1i, px.lo(l1i_row))
    l1d = ca.scatter_row(ms.l1d, px.lo(l1d_row))

    e_msg = jnp.where(v_state == MODIFIED, MSG_FLUSH_REP,
                      MSG_INV_REP).astype(jnp.uint8)
    fill_ps = mail.rep_time + sync_l1_net + ccyc(
        mp.l1d.data_and_tags_cycles)
    e_bits = jnp.where(v_state == MODIFIED, mp.rep_bits, mp.req_bits)
    noc, e_arrival = mem_net_send(
        mp, ms.noc, tiles, v_home, e_bits, fill_ps, evict_go, enabled)
    wh = jnp.where(evict_go, v_home, 0)
    mail = mail.replace(
        evict_type=mail.evict_type.at[wh, tiles].set(
            jnp.where(evict_go, e_msg, mail.evict_type[wh, tiles])),
        evict_line=mail.evict_line.at[wh, tiles].set(
            jnp.where(evict_go, v_line, mail.evict_line[wh, tiles])),
        evict_time=mail.evict_time.at[wh, tiles].set(
            jnp.where(evict_go, e_arrival,
                      mail.evict_time[wh, tiles])),
        rep_type=jnp.where(fill, MSG_NONE, mail.rep_type),
        rep_time=jnp.where(fill, 0, mail.rep_time),
    )
    req = ms.req.replace(
        phase=jnp.where(fill, PHASE_IDLE, ms.req.phase),
        slot=jnp.where(fill, ms.req.slot + 1, ms.req.slot),
        acc_ps=ms.req.acc_ps + jnp.where(fill, fill_ps - clock_ps, 0),
        slot_lat_ps=jnp.where(
            (fill[:, None]
             & (jnp.arange(3)[None, :] == ms.req.slot[:, None])),
            (fill_ps - clock_ps)[:, None], ms.req.slot_lat_ps),
    )
    ms = ms.replace(l1i=l1i, l1d=l1d, mail=mail, req=req, noc=noc)
    s_addr = jnp.where(ms.req.slot - 1 == 1, rec.addr0.astype(jnp.int32),
                       rec.addr1.astype(jnp.int32))
    ms = _apply_functional(mp, ms, rec, ms.req.slot - 1, s_addr,
                           ms.req.is_write, fill)
    counters = ms.counters.replace(
        evictions=ms.counters.evictions + (evict_go & enabled).astype(I64))
    progress = progress + jnp.sum(fill, dtype=jnp.int32)
    return ms.replace(counters=counters), progress


# ---------------------------------------------------------------------------
# Host-side census (analysis/protocol.py differential mode)
# ---------------------------------------------------------------------------


def shl2_line_census(ms: ShL2State, mp: MemParams, lines) -> dict:
    """Abstract per-line coherence view of a (fetched) ShL2State.

    Shared-L2 counterpart of `engine.line_census`: per line, the per-tile
    L1I/L1D states, the home slice's L2 data state, and the embedded
    directory entry at the slice way holding the line.  Pure host-side
    numpy; see `analysis/protocol.py`.
    """
    l1i_tag = np.asarray(ms.l1i.tags)
    l1i_st = np.asarray(ms.l1i.state)
    l1d_tag = np.asarray(ms.l1d.tags)
    l1d_st = np.asarray(ms.l1d.state)
    l2_tag = np.asarray(ms.l2.tags)
    l2_st = np.asarray(ms.l2.state)
    word = np.asarray(ms.dir.word)
    sharers = np.asarray(ms.dir.sharers)
    T = mp.n_tiles
    sw = mp.sharer_words

    def cache_state(tag, st, t, line):
        s = line % tag.shape[1]
        hit = tag[t, s, :] == line
        return int(st[t, s, hit.argmax()]) if hit.any() else 0

    out = {}
    for line in lines:
        home = line % T
        sset = line % l2_tag.shape[1]
        slice_st = 0
        dent = None
        hit = l2_tag[home, sset, :] == line
        if hit.any():
            way = int(hit.argmax())
            slice_st = int(l2_st[home, sset, way])
            w = int(word[home, sset, way])
            dstate = (w >> SHL2_STATE_SHIFT) & 7
            owner = ((w >> SHL2_OWNER_SHIFT) & _ID_MASK) - 1
            bits = sharers[home, sset, way * sw:(way + 1) * sw]
            shset = frozenset(
                i * 32 + b for i in range(sw) for b in range(32)
                if (int(bits[i]) >> b) & 1)
            dent = (int(dstate), int(owner), shset)
        out[line] = {
            "l1i": tuple(cache_state(l1i_tag, l1i_st, t, line)
                         for t in range(T)),
            "l1d": tuple(cache_state(l1d_tag, l1d_st, t, line)
                         for t in range(T)),
            "slice": slice_st,
            "dir": dent,
        }
    return out
