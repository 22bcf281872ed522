"""The vectorized MSI dram-directory protocol engine.

One `memory_engine_step` advances every tile's memory machinery by one
subquantum iteration.  It is the TPU-native fusion of three reference code
paths that each ran on their own host thread:

 - the app thread's `L1CacheCntlr::processMemOpFromCore` →
   `L2CacheCntlr::processShmemRequestFromL1Cache` miss path
   (`l1_cache_cntlr.cc:90-180`, `l2_cache_cntlr.cc:181-292`);
 - the home tile's sim thread running the directory FSM
   (`dram_directory_cntlr.cc:44-559`);
 - every other tile's sim thread serving INV/FLUSH/WB requests
   (`l2_cache_cntlr.cc:295-503`).

Concurrency discipline (replaces locks + semaphores + TCP):
 - each tile lane owns its own row of every cache tensor and at most one
   mailbox cell per matrix per iteration, so scatters never collide;
 - a home tile's fan-out (invalidation multicast) is a dense outer-product
   write into the FWD matrix, of which the home owns a full column (it has
   one active transaction at a time — the vectorized form of the
   per-address request queue serialization in `dram_directory_cntlr.cc`);
 - sharers and homes consume one incoming message per iteration (earliest
   timestamp first), which makes the engine deterministic — the reference's
   arrival-order FIFO is host-timing dependent.

Timing follows the reference exactly where stated (cache access cycles,
synchronization delays at DVFS-domain crossings, directory access cycles,
DRAM latency + bandwidth serialization, network zero-load + serialization);
simulated time rides in the messages, never in a global clock.

Known divergences (documented for the parity harness):
 - a home services one transaction at a time even across different
   addresses; sim-time is message-carried so this only serializes *wall*
   progress, plus a same-address completion floor mirrors the reference's
   per-address queue (`processNextReqFromL2Cache`);
 - directory NULLIFY picks the min-sharer victim of the set without the
   "not in request queue" exclusion (our serialization makes it moot);
 - DRAM queue-model contention is layered on separately (queue_models).

Directory schemes (`directory_schemes/directory_entry_*.cc`): all five are
supported — full_map, limited_no_broadcast (capacity-displacement INV of one
tracked sharer), ackwise / limited_broadcast (broadcast INV sweeps on
overflowed entries; acks awaited only from true holders), limitless
(software-trap penalty on overflowed entries).  The sharers bitvector stays
exact ground truth in all schemes — the schemes differ in *which messages
travel* and *what they cost*, which is what the timing model observes.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from graphite_tpu.intmath import nn_div, nn_mod

from graphite_tpu.memory import cache_array as ca
from graphite_tpu.memory import row_landing
from graphite_tpu.memory.cache_array import (
    INVALID, MODIFIED, OWNED, SHARED, state_readable, state_writable,
)
from graphite_tpu.memory.params import MemParams
from graphite_tpu.memory.state import (
    DIR_ID_BITS, DIR_MODIFIED, DIR_NSH_SHIFT, DIR_OWNED, DIR_OWNER_SHIFT,
    DIR_SHARED, DIR_STATE_SHIFT, DIR_TAG_BITS, DIR_UNCACHED,
    MOD_CORE, MOD_DIR, MOD_L1D, MOD_L1I, MOD_L2, MOD_NET_MEM,
    MSG_EX_REP, MSG_EX_REQ, MSG_FLUSH_REP, MSG_FLUSH_REQ, MSG_INV_REP,
    MSG_INV_REQ, MSG_NONE, MSG_NULLIFY, MSG_SH_REP, MSG_SH_REQ, MSG_WB_REP,
    MSG_WB_REQ,
    MT_EVICTED, MT_FETCHED, MT_INVALIDATED,
    PHASE_IDLE, PHASE_WAIT_REPLY,
    MemState,
)
from graphite_tpu.obs.scopes import scope
from graphite_tpu.parallel.px import IDENT, ParallelCtx
from graphite_tpu.time_types import cycles_to_ps
from graphite_tpu.trace.schema import (
    FLAG_CHECK, FLAG_MEM0_VALID, FLAG_MEM0_WRITE, FLAG_MEM1_VALID,
    FLAG_MEM1_WRITE, Op,
)

I64 = jnp.int64
U32 = jnp.uint32
FAR = 2**62  # python int: folds to an inline literal, never a device-constant buffer


# --------------------------------------------------------------------------
# small helpers


def _bit_word(idx):
    # idx is a tile id (>= 0 at every call site): truncating div/rem
    return (nn_div(idx, 32).astype(jnp.int32),
            nn_mod(idx, 32).astype(jnp.uint32))


def set_bit(words: jax.Array, idx: jax.Array, mask: jax.Array) -> jax.Array:
    """words[t, idx[t]//64] |= 1 << idx%64 where mask; words is [T, SW]."""
    T = words.shape[0]
    tiles = np.arange(T, dtype=np.int32)
    w, b = _bit_word(idx)
    cur = words[tiles, w]
    new = cur | (jnp.uint32(1) << b)
    return words.at[tiles, w].set(jnp.where(mask, new, cur))


def clear_bit(words: jax.Array, idx: jax.Array, mask: jax.Array) -> jax.Array:
    T = words.shape[0]
    tiles = np.arange(T, dtype=np.int32)
    w, b = _bit_word(idx)
    cur = words[tiles, w]
    new = cur & ~(jnp.uint32(1) << b)
    return words.at[tiles, w].set(jnp.where(mask, new, cur))


def test_bit(words: jax.Array, idx: jax.Array) -> jax.Array:
    T = words.shape[0]
    tiles = np.arange(T, dtype=np.int32)
    w, b = _bit_word(idx)
    return ((words[tiles, w] >> b) & jnp.uint32(1)) != 0


def popcount(words: jax.Array) -> jax.Array:
    """[T, SW] → int32[T]."""
    return jax.lax.population_count(words).sum(axis=1).astype(jnp.int32)


def lowest_sharer(words: jax.Array) -> jax.Array:
    """Lowest set bit index per row ([T, SW] → int32[T], -1 when empty).

    The deterministic form of `DirectoryEntry::getOneSharer` (the reference
    returns an arbitrary list member)."""
    nonzero = words != 0
    w_idx = jnp.argmax(nonzero, axis=1).astype(jnp.int32)
    any_bit = nonzero.any(axis=1)
    tiles = np.arange(words.shape[0], dtype=np.int32)
    w = words[tiles, w_idx]
    low = w & (~w + jnp.uint32(1))
    bit = jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
    return jnp.where(any_bit, w_idx * 32 + bit, -1)


# packed directory-entry word accessors (layout: memory/state.py).  All
# pure bit math on int64 — unpacking is free ALU inside fusions.
_TAG_MASK = (1 << DIR_TAG_BITS) - 1
_ID_MASK = (1 << DIR_ID_BITS) - 1


def dir_tag(word):
    return (word & _TAG_MASK).astype(jnp.int32) - 1


def dir_state(word):
    return ((word >> DIR_STATE_SHIFT) & 7).astype(jnp.uint8)


def dir_owner(word):
    return ((word >> DIR_OWNER_SHIFT) & _ID_MASK).astype(jnp.int32) - 1


def dir_nsh(word):
    return ((word >> DIR_NSH_SHIFT) & _ID_MASK).astype(jnp.int32)


def _dir_set_field(word, val, shift, mask):
    return (word & ~(mask << shift)) | ((val.astype(I64) & mask) << shift)


def unpack_sharers(words: jax.Array, n: int) -> jax.Array:
    """[T, SW] uint32 → bool[T, n] (bit s of row t)."""
    s = np.arange(n)
    w = (s // 32).astype(np.int32)
    b = (s % 32).astype(np.uint32)
    return ((words[:, w] >> b[None, :]) & jnp.uint32(1)) != 0


def _row_earliest(cell_type: jax.Array, cell_time: jax.Array):
    """Earliest nonzero cell per row: (col int32[T], found bool[T]).

    Deterministic total order on (time, column) — the reference's
    arrival-order processing is host-timing dependent; this is not.
    """
    C = cell_type.shape[1]
    key = jnp.where(
        cell_type != MSG_NONE,
        cell_time * C + np.arange(C, dtype=np.int64)[None, :],
        FAR,
    )
    col = jnp.argmin(key, axis=1).astype(jnp.int32)
    found = jnp.take_along_axis(key, col[:, None].astype(jnp.int64), axis=1)[:, 0] < FAR
    return col, found


def _req_earliest(mail):
    """Earliest pending request per HOME over the per-requester lanes:
    (requester int32[T], found bool[T]).

    key = time * T + requester, segment-min'd into home buckets — the
    same deterministic total order `_row_earliest` applies to a [T, T]
    matrix, without the matrix."""
    T = mail.req_type.shape[0]
    r = np.arange(T, dtype=np.int64)
    live = mail.req_type != MSG_NONE
    key = jnp.where(live, mail.req_time * T + r, FAR)
    best = (
        jnp.full((T + 1,), FAR, I64)
        .at[jnp.where(live, mail.req_home, T)]
        .min(key)
    )[:T]
    found = best < FAR
    col = jnp.where(found, nn_mod(best, T), 0).astype(jnp.int32)
    return col, found


def _req_consume(mail, use_pop, r_col):
    """Clear the popped requester lanes (each home pops at most one)."""
    T = mail.req_type.shape[0]
    r = np.arange(T, dtype=np.int32)
    live = mail.req_type != MSG_NONE
    popped = live & use_pop[mail.req_home] & (r_col[mail.req_home] == r)
    return mail.replace(req_type=jnp.where(popped, MSG_NONE,
                                           mail.req_type))


def _mesh_hops(w: int, src, dst):
    """XY hops between tiles of a mesh `w` wide (broadcasts `src` against
    `dst`; the per-axis `nn_mod` / `nn_div` stay on the host for numpy
    operands, `intmath`'s contract)."""
    return (jnp.abs(nn_mod(src, w) - nn_mod(dst, w))
            + jnp.abs(nn_div(src, w) - nn_div(dst, w)))


@scope("gt.net.route")
def mem_net_latency_ps(mp: MemParams, src, dst, bits: int, enabled):
    """MEMORY-network zero-load latency (`network_model_emesh_hop_counter.cc`
    + receive serialization `network_model.cc:119-149`; ATAC zero-load
    path costs under `memory = atac`).

    `src` / `dst` are taken to the device whatever they are (traced [T]
    from the unicast callers, numpy `arange`s from the fan-out): hops and
    flits are cheap int32 / int64 vector arithmetic there.  What must not
    reach the device is the conversion's int64 DIVISION, and at a static
    `net_freq_mhz` that divides 1e6 (every shipped target's 1,000 MHz)
    `cycles_to_ps` has none (`time_types._ps_per_cycle`)."""
    src = jnp.asarray(src)
    dst = jnp.asarray(dst)
    if mp.net_kind == "magic":
        cycles = jnp.where(enabled, jnp.ones_like(src, I64), 0)
        return cycles_to_ps(cycles, mp.net_freq_mhz)
    if mp.net_atac is not None:
        from graphite_tpu.models.network_atac import atac_zeroload_ps

        return atac_zeroload_ps(mp.net_atac, src, dst, bits, enabled)
    flits = (bits + mp.flit_width_bits - 1) // mp.flit_width_bits
    cycles = (_mesh_hops(mp.mesh_width, src, dst).astype(I64)
              * mp.hop_latency_cycles + jnp.where(src == dst, 0, flits))
    cycles = jnp.where(enabled, cycles, 0)
    return cycles_to_ps(cycles, mp.net_freq_mhz)


@scope("gt.net.route")
def mem_net_send(mp: MemParams, noc, src, dst, bits, t0_ps, mask, enabled):
    """Unicast a coherence message through the MEMORY network.

    Returns (noc, arrival_ps[T]).  With `[network] memory =
    emesh_hop_by_hop` (mp.net_hbh) the packet routes through the dense
    per-hop contention engine on the memory NoC's own port-queue state
    (`MemState.noc`); with `memory = atac` (mp.net_atac) it routes over
    the ATAC clusters/hubs/waveguide with hub contention on the memory
    NoC's own AtacState — the analog of the reference routing every
    ShmemMsg through the configured memory network model (any-model-per-
    net factory `network.cc:21-40`, `carbon_sim.cfg:281-282`).
    Otherwise zero-load hop-counter/magic math (state untouched)."""
    if mp.net_atac is not None:
        from graphite_tpu.models.network_atac import route_atac

        bits = jnp.broadcast_to(jnp.asarray(bits, I64), jnp.shape(src))
        noc, arrival_ps, _ = route_atac(
            mp.net_atac, noc, src, dst, bits, t0_ps, mask, enabled)
        return noc, arrival_ps
    if mp.net_hbh is None:
        return noc, t0_ps + mem_net_latency_ps(mp, src, dst, bits, enabled)
    from graphite_tpu.models.network_hop_by_hop import route_hop_by_hop

    bits = jnp.broadcast_to(jnp.asarray(bits, I64), jnp.shape(src))
    noc, arrival_ps, _, _ = route_hop_by_hop(
        mp.net_hbh, noc, src, dst, bits, t0_ps, mask, enabled)
    return noc, arrival_ps


@scope("gt.net.route")
def mem_net_fanout(mp: MemParams, noc, send_hs, bits: int, t0_ps, enabled):
    """A home's INV/FLUSH/WB multicast through the MEMORY network.

    send_hs: bool[T(home), T(target)]; t0_ps: int64[T(home)].  Returns
    (noc, arrival_ps[T, T]).

    The reference (broadcast tree disabled, the default
    `carbon_sim.cfg:304`) sends one unicast per target through the
    memory model.  Dense per-pair routing would cost [T^2, h, w] grids,
    so under hop_by_hop the fan-out charges the dominant contention
    exactly and approximates the rest:
     - the home's INJECT port serializes the k copies: each copy pays
       the inject queue delay plus its rank among the targets (by tile
       id, deterministic) times its flit count, and the port commits
       k * flits of occupancy;
     - each copy then pays the hop-by-hop ZERO-LOAD path cost (router +
       per-hop router+link + receive serialization); intermediate-hop
       queue contention for fan-out copies is NOT charged (documented
       approximation — under the serialized oracle contract those queues
       are empty, so serialized workloads remain exact).

    The [T, T] arrival matrix is built on the device in every open home
    phase from two `arange`s; each network kind ends in `cycles_to_ps` at
    the network's STATIC frequency, which multiplies where that frequency
    divides 1e6 (`time_types._ps_per_cycle`: every shipped target's 1,000
    MHz), so the phase holds no int64 division over [T, T] - what a chip
    that emulates int64 paid 0.5 ms a matrix for.  At a static frequency
    that does not divide 1e6 the division is there, by the reduced
    constant; at a traced one, by the full ratio.
    """
    T = mp.n_tiles
    src = np.arange(T, dtype=np.int32)[:, None]
    dst = np.arange(T, dtype=np.int32)[None, :]
    if mp.net_atac is not None:
        # ATAC multicast (`network_model_atac.cc:372-500` broadcast over
        # the waveguide): the home's SEND HUB serializes its ONet copies
        # (one queue charge of k_onet * flits, delay applied to ONet
        # copies), every copy pays its rank (by tile id) times flits at
        # the source, then its zero-load path — the same
        # dominant-contention-exact / intermediate-hops-approximate
        # contract as the hop-by-hop fan-out below, mirrored by the
        # oracle (`_AtacNet.fanout`)
        from graphite_tpu.models import queue_models as qm
        from graphite_tpu.models.network_atac import (
            _cluster_of, atac_use_onet, atac_zeroload_ps,
        )
        from graphite_tpu.time_types import ps_to_cycles

        p = mp.net_atac
        with scope("gt.net.atac.fanout"):
            zl = atac_zeroload_ps(p, src, dst, bits, enabled)   # [T, T]
            flits = max(
                1, (bits + p.flit_width_bits - 1) // p.flit_width_bits)
            onet_pair = atac_use_onet(p, src, dst)              # [T, T]
            k_onet = (send_hs & onet_pair).sum(axis=1, dtype=I64)
            fan = send_hs.any(axis=1)
            t0_cyc = ps_to_cycles(t0_ps, p.freq_mhz)
            if p.contention_enabled:
                go = fan & (k_onet > 0) & jnp.asarray(enabled, bool)
                home = np.arange(T, dtype=np.int32)
                qid = jnp.where(go, _cluster_of(p, home),
                                2 * p.n_clusters).astype(jnp.int32)
                queues, hub_delay = qm.scatter_queue_delay(
                    p.queue, noc.hub_queues, qid, t0_cyc, k_onet * flits,
                    go)
                noc = noc.replace(hub_queues=queues)
            else:
                hub_delay = jnp.zeros(T, I64)
            rank = jnp.cumsum(send_hs.astype(I64), axis=1) - 1
            extra_cyc = rank * flits + jnp.where(
                onet_pair, hub_delay[:, None], 0)
            extra_cyc = jnp.where(jnp.asarray(enabled, bool), extra_cyc, 0)
            arrival = t0_ps[:, None] + zl + cycles_to_ps(
                extra_cyc, p.freq_mhz)
        return noc, arrival
    if mp.net_hbh is None:
        lat = mem_net_latency_ps(mp, src, dst, bits, enabled)
        return noc, t0_ps[:, None] + lat
    from graphite_tpu.models import queue_models as qm
    from graphite_tpu.models.network_hop_by_hop import (
        NUM_PORTS, PORT_INJECT,
    )
    from graphite_tpu.time_types import ps_to_cycles

    p = mp.net_hbh
    flits = max(1, (bits + p.flit_width_bits - 1) // p.flit_width_bits)
    hops = _mesh_hops(p.mesh_width, src, dst).astype(I64)
    step = p.router_delay + p.link_delay
    zl = p.router_delay + (hops + 1) * step + jnp.where(
        src == dst, 0, flits)
    fan = send_hs.any(axis=1)
    k = send_hs.sum(axis=1, dtype=I64)
    t0_cyc = ps_to_cycles(t0_ps, p.freq_mhz)
    if p.contention_enabled:
        qid = (np.arange(T, dtype=np.int32) * NUM_PORTS + PORT_INJECT)
        queues, inj_delay = qm.scatter_queue_delay(
            p.queue, noc.queues, qid, t0_cyc, k * flits,
            fan & jnp.asarray(enabled, bool))
        noc = noc.replace(queues=queues)
    else:
        inj_delay = jnp.zeros(T, I64)
    rank = (jnp.cumsum(send_hs.astype(I64), axis=1) - 1)
    cyc = zl + inj_delay[:, None] + rank * flits
    cyc = jnp.where(jnp.asarray(enabled, bool), cyc, 0)
    arrival = t0_ps[:, None] + cycles_to_ps(cyc, p.freq_mhz)
    return noc, arrival


# --------------------------------------------------------------------------
# L2 cache-line utilization (`cache/cache_line_utilization.h`: per-line
# read/write access counters; harvested at the MOSI L2 controller's
# eviction/invalidation hook points, `mosi/l2_cache_cntlr.cc:120`).
# Packed uint32 per line: low 16 bits = reads, high 16 = writes
# (saturating).  Classified into a log2 histogram (0, 1, 2-3, ..., >=64)
# when the line leaves the L2.


def _util_inc(cur, is_write, mask):
    """Saturating read/write increment of packed util counters [T]."""
    inc = jnp.where(is_write, jnp.uint32(1) << 16, jnp.uint32(1))
    fld = jnp.where(is_write, cur >> 16, cur & jnp.uint32(0xFFFF))
    return jnp.where(mask & (fld < 0xFFFF), cur + inc, cur)


def _util_classify(counters, util_val, mask, enabled):
    """Histogram a departing line's packed util counter."""
    rd = (util_val & jnp.uint32(0xFFFF)).astype(I64)
    wr = (util_val >> 16).astype(I64)
    total = (rd + wr).astype(jnp.int32)
    bucket = jnp.minimum(7, 32 - jax.lax.clz(total)).astype(jnp.int32)
    m = mask & jnp.asarray(enabled, bool)
    tiles = np.arange(util_val.shape[0], dtype=np.int32)
    return counters.replace(
        line_util_hist=counters.line_util_hist.at[tiles, bucket].add(
            m.astype(I64), unique_indices=True),
        line_util_reads=counters.line_util_reads + jnp.where(m, rd, 0),
        line_util_writes=counters.line_util_writes + jnp.where(m, wr, 0))


def _util_row_local(l2_util, line_l, sets_mod_l):
    """This device's [Tl, W2] util row at each local lane's L2 set (the
    cross-device exchange happens via _rows_exchange at the call sites)."""
    Tl = l2_util.shape[0]
    lt = np.arange(Tl, dtype=np.int32)
    sets_l = nn_mod(line_l, jnp.asarray(sets_mod_l)).astype(jnp.int32)
    return l2_util[lt, sets_l]


def _util_scatter(px: ParallelCtx, l2_util, line, sets_mod, way, cur, new):
    """Apply per-lane packed-counter updates block-locally (add-a-delta,
    unique rows)."""
    sets = nn_mod(line, jnp.asarray(sets_mod)).astype(jnp.int32)
    sets_l, way_l, cur_l, new_l = px.lo((sets, way, cur, new))
    Tl = l2_util.shape[0]
    lt = np.arange(Tl, dtype=np.int32)
    return l2_util.at[lt, sets_l, way_l].add(
        new_l - cur_l, unique_indices=True, indices_are_sorted=True)


def _mt_bit(line):
    """Hash bucket of a line in the miss-type bitmaps (MT_BITS buckets)."""
    from graphite_tpu.memory.state import MT_BITS

    h = (line.astype(jnp.uint32) & jnp.uint32(MT_BITS - 1))
    return (h // 32).astype(jnp.int32), (h % 32).astype(jnp.uint32)


def _mt_test(mt, row: int, line):
    T = mt.shape[0]
    tiles = jnp.arange(T, dtype=jnp.int32)
    w, b = _mt_bit(line)
    return ((mt[tiles, row, w] >> b) & jnp.uint32(1)) != 0


def _mt_update(mt, row: int, line, mask, set_bit_val: bool):
    """Set or clear the line's bucket bit in bitmap `row` where mask
    (delta-add scatter: per-lane rows are unique).  Operates on whatever
    block of tile rows `mt` holds — sharded callers pass block-local
    line/mask."""
    T = mt.shape[0]
    tiles = jnp.arange(T, dtype=jnp.int32)
    w, b = _mt_bit(line)
    cur = mt[tiles, row, w]
    new = (cur | (jnp.uint32(1) << b)) if set_bit_val else (
        cur & ~(jnp.uint32(1) << b))
    return mt.at[tiles, row, w].add(
        jnp.where(mask, new - cur, jnp.uint32(0)),
        unique_indices=True, indices_are_sorted=True)


def _mt_same_bucket(a, b):
    """Do two lines hash to the same miss-type bucket?  (Pure math — lets
    the sharded path fold a just-applied local bitmap write into an
    already-exchanged pre-write test bit.)"""
    from graphite_tpu.memory.state import MT_BITS

    m = jnp.uint32(MT_BITS - 1)
    return (a.astype(jnp.uint32) & m) == (b.astype(jnp.uint32) & m)


# --------------------------------------------------------------------------
# shard_map phase-exchange helpers: block-local row gathers packed into one
# all-gather per engine phase (identity under the single-device px) — see
# parallel/px.py for the exchange design.


def _row_pack(row: "ca.CacheRow"):
    """The compact exchanged form of a gathered cache row."""
    return row.meta0, row.sets


def _rows_exchange(px: ParallelCtx, local_rows, extra=()):
    """Exchange locally gathered CacheRows (+ any extra per-lane fields)
    to full tile width in ONE packed collective (identity single-device)."""
    if not px.sharded:
        return tuple(local_rows), tuple(extra)
    packed = tuple(_row_pack(r) for r in local_rows)
    out = px.ag((packed, tuple(extra)))
    rows = tuple(ca.row_from_meta(m, s) for (m, s) in out[0])
    return rows, out[1]


def _l1_fill_ways(mp: MemParams, px: ParallelCtx, l1i_row, l1d_row, comp_l):
    """(l1i_way, l1d_way, victim_valid, victim_line): the way of each
    block-local L1 row that a fill would take and, for the L1 the access
    goes to (`comp_l`: the L1I), whether that way holds a live line and
    which.  The L1 rows alone decide it, so a requester phase knows the
    victim's line BEFORE it reads the L2 store and can fetch the victim's
    L2 set in the same gather as the request's
    (`cache_array.gather_row_pair`)."""
    l1i_way, l1i_vv, l1i_vline, _ = ca.row_pick_victim(
        l1i_row, mp.l1i.replacement, px.lo_const(mp.l1i.ways_limit))
    l1d_way, l1d_vv, l1d_vline, _ = ca.row_pick_victim(
        l1d_row, mp.l1d.replacement, px.lo_const(mp.l1d.ways_limit))
    return (l1i_way, l1d_way, jnp.where(comp_l, l1i_vv, l1d_vv),
            jnp.where(comp_l, l1i_vline, l1d_vline))


@dataclasses.dataclass(frozen=True)
class RecView:
    """Current trace record fields needed by the memory engine (all [T])."""

    op: jax.Array
    flags: jax.Array
    pc: jax.Array
    addr0: jax.Array
    addr1: jax.Array
    aux0: jax.Array
    aux1: jax.Array


@struct.dataclass
class MemStepOut:
    ms: MemState
    mem_complete: jax.Array  # bool[T] all slots of current record done
    acc_ps: jax.Array        # int64[T] memory latency of the record so far
    slot_lat_ps: jax.Array   # int64[T, 3] per-slot latency [icache, m0, m1]
    progress: jax.Array      # int32[] events this iteration
    # miss-service completions THIS call (fills consumed by phase 6).
    # A whole miss transaction can start and fill within one engine call
    # (message timestamps model the latency, not iteration count), so
    # callers observing only the entry/exit requester phase undercount;
    # these carry the per-call events for the round-21 latency
    # histograms.  fill_lat_ps is the filled slot's end-to-end latency
    # (lookup + protocol round trip — the same value the slot_lat_ps
    # algebra records).  Over a drained run, total fills == total miss
    # starts (l2_misses for `msi`, the three L1 miss counters for
    # `pr_l1_sh_l2*`) — the conservation pairing obs/hist checks.
    # Opt-in via `fill_events=True`: None (the default) contributes no
    # pytree leaves and no equations, so hist-off programs keep lowering
    # the historical trace byte-identically (the `hist-off` audit lint
    # and the pre-existing PROGRAMS.lock fingerprints).
    fill_now: "jax.Array | None" = None      # bool[T] miss completed this call
    fill_lat_ps: "jax.Array | None" = None   # int64[T] its slot latency


def slots_present(mp: MemParams, rec: "RecView", enabled) -> jax.Array:
    """bool[T, 3]: which of [icache, mem0, mem1] this record carries.

    icache fetches for static/branch records (op < DYNAMIC_MISC) and
    compressed BBLOCK runs (one fetch for the block's first line — a
    documented approximation); dynamic ops (15-19) commit without waiting
    on mem_ok, so they get no fetch slot."""
    is_instr = (rec.op < 15) | (rec.op == int(Op.BBLOCK))
    icache_present = (
        jnp.asarray(mp.icache_modeling) & jnp.asarray(enabled) & is_instr
    )
    mem0 = (rec.flags & FLAG_MEM0_VALID) != 0
    mem1 = (rec.flags & FLAG_MEM1_VALID) != 0
    return jnp.stack([icache_present, mem0, mem1], axis=1)


def next_present_slot(present: jax.Array, slot: jax.Array) -> jax.Array:
    """First present slot index >= slot, else 3."""
    k = np.arange(3)[None, :]
    cand = jnp.where(present & (k >= slot[:, None]), k, 3)
    return cand.min(axis=1).astype(jnp.int32)


def protocol_live(ms, *extra) -> jax.Array:
    """Any protocol state outstanding (messages, transactions, waiting
    requesters)?  Shared by both engines so the mem_gate's wake-up
    condition cannot drift between them; engine-specific terms (e.g. the
    shared-L2 engine's in-flight DRAM fetches) come in via *extra."""
    mail = ms.mail
    live = (
        (mail.req_type != MSG_NONE).any()
        | (mail.evict_type != MSG_NONE).any()
        | (mail.fwd_type != MSG_NONE).any()
        | (mail.ack_type != MSG_NONE).any()
        | (mail.rep_type != MSG_NONE).any()
        | ms.txn.active.any()
        | ms.txn.saved_valid.any()
        | (ms.req.phase != PHASE_IDLE).any()
    )
    for term in extra:
        live = live | term
    return live


# phase order of the private-L2 engine's skip vector (MemState.phase_skips)
PHASE_NAMES = ("requester", "home_evict", "home_start", "sharer",
               "home_finish", "requester_fill")


# MemState.base_skips order: iterations whose base (the directory
# working-set gather and the merged scatter) was skipped, inner blocks
# whose staging flush was skipped
BASE_SKIP_NAMES = ("base", "flush")
BASE_SKIPPED = np.array([1, 0], np.int64)
FLUSH_SKIPPED = np.array([0, 1], np.int64)


def _run_if(live, fn, stores):
    """`fn(stores)` where `live`, else `stores` untouched: the in-place
    gate for a WRITER of the big directory stores.

    A `lax.cond` that returned the stores would double-buffer them (its
    branch outputs are fresh buffers — the round-2 pathology,
    `dir_store_avals`).  A `lax.while_loop` carry is aliased in place, so
    the gate is a zero-or-one-trip loop over `(flag, stores)`: the body
    clears the flag and applies `fn`; a closed gate runs no body, and
    the scatter and the relayouts XLA puts around it are instructions of
    the body.  `live=None` is the forced-live form (gates off): `fn`
    inline, today's program."""
    if live is None:
        return fn(stores)
    _, out = jax.lax.while_loop(
        lambda c: c[0],
        lambda c: (jnp.zeros((), jnp.bool_), fn(c[1])),
        (live, stores))
    return out


def dir_store_avals(ms) -> tuple:
    """(shape, dtype) signatures of the big directory stores — the
    packed entry words (int64 [T, DS, DW], or u32 [T, 2*DW, DS]:
    memory/state.py) and the [T, DS, DW*SW] sharers bitvector — that a
    gated home phase must NEVER return as lax.cond outputs
    (they'd be double-buffered; the `_DirAcc` delta plan exists so the
    cond carries compact per-lane deltas instead).  The program
    auditor's cond-payload rule (analysis/rules.py) enforces this for
    every cond in the lowered program."""
    d = ms.directory
    return (
        (tuple(d.entry.shape), str(d.entry.dtype)),
        (tuple(d.sharers.shape), str(d.sharers.dtype)),
    )


def mem_idle_out(mp: MemParams, ms, rec: "RecView", enabled,
                 fill_events: bool = False) -> MemStepOut:
    """The engine step's result when there is provably nothing to do —
    no lane's record carries memory slots and no protocol state is live
    (`ms.live`).  Lets the caller skip the whole engine under a lax.cond
    on compute-only iterations (the engine costs ~600 us/iteration in
    small kernels; see PERF.md).  A whole-engine skip counts as a skip of
    every phase in the gate-observability vector."""
    present = slots_present(mp, rec, enabled)
    final_slot = next_present_slot(present, ms.req.slot)
    mem_complete = (ms.req.phase == PHASE_IDLE) & (final_slot >= 3)
    if ms.phase_skips is not None:
        ms = ms.replace(phase_skips=ms.phase_skips + 1)
    if getattr(ms, "base_skips", None) is not None:
        ms = ms.replace(base_skips=ms.base_skips + BASE_SKIPPED)
    T = ms.req.phase.shape[0]
    return MemStepOut(
        ms=ms, mem_complete=mem_complete, acc_ps=ms.req.acc_ps,
        slot_lat_ps=ms.req.slot_lat_ps,
        progress=jnp.zeros((), jnp.int32),
        fill_now=jnp.zeros((T,), jnp.bool_) if fill_events else None,
        fill_lat_ps=jnp.zeros((T,), I64) if fill_events else None)


# --------------------------------------------------------------------------
# directory-entry helpers (structured [T, DS, DW(, SW)] arrays — a flat
# entry-major repack was built and measured 1.6x slower; see PERF.md
# round-3 findings and the DirectoryArrays docstring).
#
# Sharers write-staging (dir_stage_cap > 0): XLA TPU lowers every
# per-lane scatter on the big [T, DS, DW*SW] sharers store as a
# FULL-ARRAY dense pass (measured ~8 ms each at 1024 tiles, three per
# iteration — the coherence-storm floor, PERF.md round-4 findings; the
# same writes on the [T, DS, DW] entry words stay direct, one merged
# landing an iteration: `_entry_land`).  Staged mode: writes land in the
# small per-LANE (skey, sval) rows (`_stage_put`); the engine's sharers
# reads overlay them; `dir_stage_flush` applies the rows to the big
# store once per inner_block iterations (engine/step._quantum_loop), one
# amortized dense pass instead of 3*inner_block — and, where the program
# is lowered for a TPU, no pass at all: a kernel lands the staged slots'
# tiles alone (`row_landing.flush_staged`, PR 43).
#
# The overlay has two halves (PR 46).  The INDEX — for every way of the
# three gathered set rows a lane, the latest staged slot: a compare and a
# max over `skey` (`_stage_index`) — is taken once an iteration, where
# the working set is gathered.  The VALUE is fetched out of `sval` where
# a home phase reads a way (`_DirRowView.sharers_at` → `_stage_fetch`:
# one gather of T rows, inside the phase's cond): a phase reads ONE way
# a lane, so three or four `[T]`-row gathers replace the `T * 3 * DW`
# rows an eager overlay fetches (49,152 rows of 128 bytes at 1,024 tiles:
# 0.56 ms an open iteration, priced by the row).  Slots a phase appends
# lie at or past the cursor the index was taken at, so a slot the index
# names holds the head-of-iteration value whatever ran in between.  Under
# shard_map the rows ride one collective that no cond may hold, so the
# sharded working set keeps the eager form (`_stage_overlay_rows`).
#
# The table is [T, c] per home lane (c = writes_per_iter *
# inner_block).  Every directory write is home-lane-local, so a put is
# a single append-at-cursor scatter with no dedup scan, and every
# staging operation's cost scales with the per-lane staged-entry count.
# Keys may repeat within a lane row; reads take the LATEST slot and the
# flush applies only each key's last slot, so the big-store values are
# those of a unique-key table.  Lane-locality also makes the table
# block-local under shard_map (each device stages its own home rows),
# which is what lets big sharded directories stage at all.  Reference
# hot path this lifts: `dram_directory_cntlr.cc:44-559` per-message
# directory updates.


def _stage_put(d, sets, way, mask, new_sh, dw: int):
    """Append a masked per-lane sharers write at each lane's cursor,
    under the within-lane key `sets * dw + way` (`dw` = the directory's
    way count: the entry store is detached from a gated phase's cond).

    ONE out-of-bounds-dropping scatter per table array — no dedup scan,
    no cond.  Masked-off lanes target slot c (dropped); capacity
    c = writes_per_iter * inner_block makes mid-block overflow
    impossible, so in-bounds appends never collide."""
    C = d.skey.shape[1]
    T = d.skey.shape[0]
    tiles = np.arange(T, dtype=np.int32)
    key = sets * dw + way
    pos = jnp.where(mask, d.sn, C)
    return d.replace(
        skey=d.skey.at[tiles, pos].set(key, mode="drop",
                                       unique_indices=True),
        sval=d.sval.at[tiles, pos].set(new_sh, mode="drop",
                                       unique_indices=True),
        sn=d.sn + mask.astype(jnp.int32))


def _stage_index(d, sets, dw: int):
    """int32[T, K, DW]: for every way of each lane's set rows `sets`
    (int32[T, K]) ONE PLUS its LATEST staged slot, 0 where it has none —
    append order is program order, so a later write overwrites an earlier
    one.  A compare and a max over the per-lane capacity c; no value
    leaves `sval`."""
    C = d.skey.shape[1]
    valid = d.skey >= 0                                       # [T, c]
    key = jnp.where(valid, d.skey, 0)
    s_of = nn_div(key, dw)
    w_of = nn_mod(key, dw)
    m = valid[:, None, :] & (s_of[:, None, :] == sets[:, :, None])
    mw = m[:, :, None, :] & (
        w_of[:, None, None, :]
        == np.arange(dw, dtype=np.int32)[None, None, :, None])
    rank = np.arange(1, C + 1, dtype=np.int32)
    return jnp.max(jnp.where(mw, rank, 0), axis=3)


def _stage_fetch(sval, best):
    """uint32[T, SW]: each lane's staged value at slot `best` (int32[T],
    as `_stage_index` numbers them), zeros where it names none: ONE
    gather of T rows of the table."""
    has = best > 0
    vals = sval[np.arange(sval.shape[0], dtype=np.int32),
                jnp.where(has, best - 1, 0)]
    return jnp.where(has[:, None], vals, jnp.zeros_like(vals))


def _stage_overlay_rows(d, sets, rows):
    """Overlay each lane's staged writes onto gathered sharers SET rows,
    eagerly: the value of every way of every row that has a staged slot
    is fetched (`T * K * DW` rows of `sval`, whatever is read of them).
    The sharded working set's form, and the oracle of the lazy one.

    `sets` int32[T, K] (the gathered rows' set indices), `rows`
    uint32[T, K, DW*SW]."""
    if d.skey is None:
        return rows
    T = d.skey.shape[0]
    SW = d.sval.shape[2]
    K = sets.shape[1]
    DW = rows.shape[2] // SW
    best = _stage_index(d, sets, DW)                          # [T, K, DW]
    has = best > 0
    idx = jnp.where(has, best - 1, 0)
    vals = d.sval[np.arange(T, dtype=np.int32)[:, None, None], idx]
    rows3 = rows.reshape(T, K, DW, SW)
    out = jnp.where(has[..., None], vals, rows3)
    return out.reshape(T, K, DW * SW)


def dir_stage_flush(d, live=None):
    """Apply the staging rows to the big sharers store and reset them.

    `live` (a scalar bool, or None = forced live) gates the whole flush
    in place (`_run_if`): the caller passes a predicate that is false
    only where no slot was staged since the last flush, and then every
    key is -1, every slot is dropped and the reset writes what is there.

    Only each key's LAST slot within its lane row counts (later slots
    overwrite earlier ones, the append-order analog of the old layout's
    in-place overwrite).  `row_landing.flush_staged` owns the two forms
    and the choice between them: a scatter-add of row deltas, a pass
    over the store whatever was staged (19 ms at 1,024 tiles), and where
    the program is lowered for a TPU a kernel that moves the staged
    slots' tiles alone; under a campaign's sim axis its batching rule
    folds the sims into the lane axis and makes the same choice of the
    folded store (`row_landing._fold_sims`), so nobody tells it."""
    if d.skey is None:
        return d

    def flush(stores):
        sharers, skey, sn = stores
        return (row_landing.flush_staged(sharers, skey, d.sval, sn),
                jnp.full_like(skey, -1), jnp.zeros_like(sn))

    sharers, skey, sn = _run_if(live, flush, (d.sharers, d.skey, d.sn))
    return d.replace(sharers=sharers, skey=skey, sn=sn)


class _DirAcc:
    """Deferred directory writes of one home phase: its delta plan.

    A gated home phase (MemParams.phase_gate) runs inside a lax.cond
    that must not carry the big [T, DS, DW] entry / [T, DS, DW*SW]
    sharers stores (a cond's branch outputs are double-buffered — the
    round-2 pathology that disabled the whole-engine gate above 1 GB).
    `_dir_update` therefore accumulates its writes here as compact
    per-lane deltas, replicated full-width — one int64 entry-word delta
    and one [T, DW*SW] sharers set-row delta, recorded in staged mode
    too because later phases' views forward it — which the phase
    returns as its plan (`pack`); `_dir_apply_merged` lands all three
    phases' plans in one scatter per store at the end of the iteration.
    Staged sharers writes also go through the small (skey, sval) table
    inside the cond.

    Invariants (hold by construction in the three home phases):
     - every `_dir_update` call of one phase targets the SAME per-lane
       (sets, way) pair (checked by object identity on the operands at
       trace time);
     - the calls' masks are pairwise disjoint per lane, so summing
       new-minus-cur deltas read against the phase's forwarded view is
       exact.
    """

    def __init__(self):
        self.sets = None
        self.way = None
        self.entry_delta = None
        self.sharers_delta = None

    def _bind(self, sets, way):
        # holding the (sets, way) operands themselves pins their
        # identity for the check's lifetime (a bare id() tuple could be
        # recycled after gc)
        if self.sets is None:
            self.sets, self.way = sets, way
        elif not (self.sets is sets and self.way is way):
            raise AssertionError(
                "_DirAcc: a gated home phase issued _dir_update calls "
                "with different (sets, way) operands — the deferred "
                "delta plan assumes one target entry per lane per phase")

    def add_entry(self, sets, way, delta):
        self._bind(sets, way)
        self.entry_delta = (delta if self.entry_delta is None
                            else self.entry_delta + delta)

    def add_sharers(self, sets, way, row_delta):
        self._bind(sets, way)
        self.sharers_delta = (row_delta if self.sharers_delta is None
                              else self.sharers_delta + row_delta)

    def pack(self, d, n_tiles: int):
        """The phase's plan: (sets, way, entry_delta, sharers_row
        _delta) — replicated full-width [T(, DW*SW)], zeros when the
        phase made no writes of that kind."""
        sets = (self.sets if self.sets is not None
                else jnp.zeros(n_tiles, jnp.int32))
        way = (self.way if self.way is not None
               else jnp.zeros(n_tiles, jnp.int32))
        ed = (self.entry_delta if self.entry_delta is not None
              else jnp.zeros(n_tiles, I64))
        shd = (self.sharers_delta if self.sharers_delta is not None
               else jnp.zeros((n_tiles, d.sharers.shape[2]), U32))
        return (sets, way, ed, shd)

    @staticmethod
    def zero_pack(d, n_tiles: int):
        return (jnp.zeros(n_tiles, jnp.int32),
                jnp.zeros(n_tiles, jnp.int32),
                jnp.zeros(n_tiles, I64),
                jnp.zeros((n_tiles, d.sharers.shape[2]), U32))


class _DirRowView:
    """ONE pre-gathered (and delta-forwarded) directory set row per home
    lane — all a home phase reads of the directory; the big stores are
    gathered once an iteration (`_DirWorkingSet`).  Earlier phases'
    pending deltas were forwarded in (`_DirWorkingSet.view`), so
    `lookup()` and `rows()` are pure register math.

    The sharers row has two forms.  Eager (`best` None: an unstaged
    program, or a sharded one, whose staged writes were overlaid at
    gather time): the row is current as it stands.  Lazy (a staged
    single-device program): the row holds ZERO at every way with a
    staged slot and `best` int32[T, DW] names that slot
    (`_stage_index`); the current value of a way is the row's plus the
    slot's, fetched out of the phase's own `d.sval` when the way is read
    (`sharers_at`) — once a `way` operand, as `_DirAcc._bind` pins
    operands by identity, so a phase that reads and updates one way
    fetches once."""

    def __init__(self, line, sets, entry_row, sharers_row, dw, best=None):
        self.sets = sets
        self._line = line
        self._word = entry_row      # int64[T, DW]
        self._sh = sharers_row      # uint32[T, DW*SW]
        self._dw = dw
        self._best = best           # int32[T, DW] | None (eager)
        self._staged = None         # (way, uint32[T, SW]) last fetched

    def rows(self):
        """(tag_row, nsharers_row) — the [T, DW] set rows the allocation
        decisions (free way / min-sharer victim) need."""
        return dir_tag(self._word), dir_nsh(self._word)

    def lookup(self):
        """(found, way) of `line` within the set."""
        tag_row = dir_tag(self._word)
        way_hits = tag_row == self._line[:, None]
        found = way_hits.any(axis=1)
        way = jnp.argmax(way_hits, axis=1).astype(jnp.int32)
        return found, way

    def word_at(self, way):
        return jnp.take_along_axis(self._word, way[:, None], axis=1)[:, 0]

    def sharers_row3(self):
        return self._sh.reshape(self._sh.shape[0], self._dw, -1)

    def _staged_at(self, d, way):
        """The staged value of `way` (zeros where it has no slot),
        fetched from `d.sval` once per `way` operand."""
        if self._staged is None or self._staged[0] is not way:
            with scope("gt.mem.stage_overlay"):
                # (a masked max, not a take_along_axis: one small gather
                # operation less a fetch on a TPU)
                at_way = (np.arange(self._dw, dtype=np.int32)[None, :]
                          == way[:, None])
                best = jnp.max(jnp.where(at_way, self._best, 0), axis=1)
                self._staged = (way, _stage_fetch(d.sval, best))
        return self._staged[1]

    def sharers_at(self, d, way):
        """uint32[T, SW]: the current sharers of `way` (`d`: the phase's
        directory, for its staging table)."""
        sharers = jnp.take_along_axis(
            self.sharers_row3(), way[:, None, None], axis=1)[:, 0]
        if self._best is None:
            return sharers
        return sharers + self._staged_at(d, way)

    def current_row3(self, d, way):
        """The [T, DW, SW] row `_dir_update` takes its delta against:
        current at `way` (the other ways of a lazy row are never read)."""
        row3 = self.sharers_row3()
        if self._best is None:
            return row3
        at_way = (np.arange(self._dw, dtype=np.int32)[None, :, None]
                  == way[:, None, None])
        return row3 + jnp.where(at_way, self._staged_at(d, way)[:, None, :],
                                jnp.zeros_like(row3))

    def entry(self, d, way):
        """(tags, dstate, owner, sharers, nsh) at `way`."""
        sharers = self.sharers_at(d, way)
        word = self.word_at(way)
        return (dir_tag(word), dir_state(word), dir_owner(word),
                sharers, dir_nsh(word))


class _DirWorkingSet:
    """The iteration's packed directory working set.

    After the requester phase, every set the three home phases can
    touch is known: the earliest EVICT cell's line, the earliest
    REQUEST lane's line (or the saved post-NULLIFY original), and the
    transaction line.  A transaction STARTED this iteration carries the
    effective request line, whose set equals the request row's set
    (directory tags are congruent to their set mod DS by construction),
    so THREE set rows cover phase 5 too — `view_finish` selects by set
    equality, where any ambiguity is harmless because equal sets mean
    identical row content.

    ONE packed [T, 3, DW] entry-row + [T, 3, DW*SW] sharers-row gather
    (one collective under shard_map, with the per-lane staging rows
    overlaid block-locally first; in a staged single-device program the
    staging table's INDEX alone is taken here, `best_rows`, and a view
    fetches the staged value of the way its phase reads) serves all
    three phases; each phase's view forwards the pending delta plans of
    the phases before it, and `_dir_apply_merged` lands every plan in
    ONE scatter per store at the end of the iteration.  This is the packed CacheRow exchange
    form promoted to the iteration's working set: the six phases
    operate on rows-in-registers, and the big stores see exactly one
    gather and one scatter per iteration."""

    def __init__(self, px: ParallelCtx, d: "DirectoryArrays", mp, select,
                 live=None):
        """`select()` picks the three lines (earliest EVICT cell's,
        earliest REQUEST's or the saved original, the transaction's);
        `live` is the home-activity gate (None = forced live).  With the
        gate closed no home phase runs, so no view is read: selection,
        gather and the staging table's index are all skipped and the
        rows are zeros."""
        self._dw = mp.dir_ways
        self._dir_sets = mp.dir_sets

        def rows(lo=lambda x: x):
            lines = tuple(select())
            sets3 = jnp.stack(
                [nn_mod(ln, mp.dir_sets).astype(jnp.int32)
                 for ln in lines], axis=1)                    # [T, 3]
            sets = lo(sets3)
            lt = np.arange(d.entry.shape[0], dtype=np.int32)[:, None]
            ew = _entry_rows(d.entry, lt, sets)               # [Tl, 3, DW]
            sh = d.sharers[lt, sets]                          # [Tl, 3, DW*SW]
            if d.skey is None:
                return lines, sets3, (ew, sh, None)
            if px.sharded:
                return lines, sets3, (ew, _stage_overlay_rows(d, sets, sh),
                                      None)
            with scope("gt.mem.stage_overlay"):
                # the index alone; a way with a staged slot reads zero
                # in the row, and its value is fetched where it is read
                best = _stage_index(d, sets, self._dw)        # [T, 3, DW]
                sh = jnp.where(
                    (best > 0)[..., None], jnp.zeros((), U32),
                    sh.reshape(best.shape + (-1,))).reshape(sh.shape)
            return lines, sets3, (ew, sh, best)

        if px.sharded:
            # the rows ride one collective, which must not sit inside a
            # lax.cond (engine/step.py, the whole-engine gate): ungated
            self.lines, self.sets3, local = rows(px.lo)
            self.entry_rows, self.sharer_rows, self.best_rows = px.ag(local)
            return
        if live is None:
            out = rows()
        else:
            # the stores are cond INPUTS only and the outputs are the
            # gathered rows — nothing big is double-buffered
            out = jax.lax.cond(
                live, rows,
                lambda: jax.tree.map(jnp.zeros_like, jax.eval_shape(rows)))
        self.lines, self.sets3, (self.entry_rows, self.sharer_rows,
                                 self.best_rows) = out

    def _forward(self, sets, ew, sh, packs):
        """Add earlier phases' pending deltas where their target set is
        this view's set (all directory writes are home-lane-local, so a
        per-lane set compare decides).  Deltas were computed against the
        then-current forwarded view, so the adds chain exactly."""
        DW = self._dw
        for (psets, pway, ped, pshd) in packs:
            m = psets == sets
            onehot = (np.arange(DW, dtype=np.int32)[None, :]
                      == pway[:, None])
            ew = ew + jnp.where(m[:, None] & onehot, ped[:, None],
                                jnp.zeros_like(ew))
            sh = sh + jnp.where(m[:, None], pshd, jnp.zeros_like(sh))
        return ew, sh

    def view(self, k: int, line, packs) -> _DirRowView:
        ew, sh = self._forward(self.sets3[:, k], self.entry_rows[:, k],
                               self.sharer_rows[:, k], packs)
        best = None if self.best_rows is None else self.best_rows[:, k]
        return _DirRowView(line, self.sets3[:, k], ew, sh, self._dw, best)

    def view_finish(self, line, packs) -> _DirRowView:
        sets = nn_mod(line, self._dir_sets).astype(jnp.int32)
        use1 = sets == self.sets3[:, 1]

        def pick(rows):
            return jnp.where(use1[:, None], rows[:, 1], rows[:, 2])

        ew, sh = self._forward(sets, pick(self.entry_rows),
                               pick(self.sharer_rows), packs)
        best = None if self.best_rows is None else pick(self.best_rows)
        return _DirRowView(line, sets, ew, sh, self._dw, best)


# The entry store has two forms (memory/state.py: DirectoryArrays), told
# apart by dtype: these two functions are all of the engine that knows.


def _entry_rows(entry, lanes, sets):
    """The int64[Tl, K, DW] packed words of each lane's set rows `sets`
    (int32[Tl, K]; `lanes` the block's int32[Tl, 1] lane indices): rows
    out of the store.  On the u32 words a set is a column - a lane's low
    words over its high words - and the int64 word is assembled from the
    gathered columns alone, register math."""
    if entry.dtype != U32:
        return entry[lanes, sets]
    cols = entry[lanes, :, sets]                          # [Tl, K, 2 * DW]
    dw = cols.shape[2] // 2
    return cols[..., :dw].astype(I64) | (cols[..., dw:].astype(I64) << 32)


def _entry_land(entry, t_e, s_all, w_all, ed_all):
    """The folded plan's deltas `ed_all` added to the words (t_e, s_all,
    w_all), distinct or out of bounds (`t_e` = Tl: folded away): a plan
    into the store.  int64: one scatter-add, a pass over the store on a
    TPU.  u32 words: `row_landing.apply_entry` - where the program is
    lowered for a TPU a kernel that moves the tiles of the plan's nonzero
    words alone, a lane's phases in turn; a campaign's sims folded into
    its lanes by `apply_entry`'s own batching rule."""
    if entry.dtype != U32:
        return entry.at[t_e, s_all, w_all].add(
            ed_all, mode="drop", unique_indices=True)
    Tl = entry.shape[0]
    with scope("gt.mem.entry_land"):
        return row_landing.apply_entry(
            entry, s_all.reshape(-1, Tl), w_all.reshape(-1, Tl),
            ed_all.reshape(-1, Tl), (t_e < Tl).reshape(-1, Tl))


def _dir_apply_merged(d, px: ParallelCtx, packs, live=None):
    """ONE merged scatter per big directory store per iteration: the
    home phases' delta plans land together at the end of
    the engine step.  Duplicate targets (two phases updating the same
    per-lane entry) are folded into the earliest plan and the duplicate
    slot redirected out of bounds, so the scatters keep unique indices
    (in-place friendly) and the summed deltas stay exact — each phase's
    delta was computed against the forwarded view, so the fold telescopes
    to final-minus-initial.  The entry store's plan lands through
    `_entry_land`, by the store's form.  Sharers deltas apply only in
    unstaged mode (staged writes ride the per-lane table and flush per
    block).

    `live` (the home-activity gate, or None = forced live) gates the
    fold and the scatters in place (`_run_if`): where it is false every
    plan is a skipped phase's zero pack, and adding zeros writes what is
    there."""
    packs = [tuple(px.lo(p)) for p in packs]
    Tl = d.entry.shape[0]
    t = np.arange(Tl, dtype=np.int32)
    staged = d.skey is not None

    def land(stores):
        sets = [p[0] for p in packs]
        way = [p[1] for p in packs]
        ed = [p[2] for p in packs]
        shd = [p[3] for p in packs]
        n = len(packs)
        drop_e = [jnp.zeros(Tl, jnp.bool_) for _ in range(n)]
        drop_s = [jnp.zeros(Tl, jnp.bool_) for _ in range(n)]
        for j in range(1, n):
            for i in range(j):
                eq_e = ((sets[i] == sets[j]) & (way[i] == way[j])
                        & ~drop_e[i] & ~drop_e[j])
                ed[i] = ed[i] + jnp.where(eq_e, ed[j], 0)
                drop_e[j] = drop_e[j] | eq_e
                eq_s = (sets[i] == sets[j]) & ~drop_s[i] & ~drop_s[j]
                shd[i] = shd[i] + jnp.where(eq_s[:, None], shd[j],
                                            jnp.zeros_like(shd[j]))
                drop_s[j] = drop_s[j] | eq_s
        t_e = jnp.concatenate([jnp.where(dr, Tl, t) for dr in drop_e])
        s_all = jnp.concatenate(sets)
        w_all = jnp.concatenate(way)
        ed_all = jnp.concatenate(ed)
        entry = _entry_land(stores[0], t_e, s_all, w_all, ed_all)
        if staged:
            return (entry,)
        t_s = jnp.concatenate([jnp.where(dr, Tl, t) for dr in drop_s])
        shd_all = jnp.concatenate(shd)
        return (entry, stores[1].at[t_s, s_all].add(
            shd_all, mode="drop", unique_indices=True))

    out = _run_if(live, land,
                  (d.entry,) if staged else (d.entry, d.sharers))
    if staged:
        return d.replace(entry=out[0])
    return d.replace(entry=out[0], sharers=out[1])


def _cond_dir(pred, fn, ms, n_tiles: int):
    """Run a home-side phase (evictions / starts / acks+finish) under a
    scalar-predicate lax.cond.  The phase reads the directory only
    through its pre-gathered `_DirRowView` (closed over by `fn` — cond
    inputs), so BOTH big stores detach from the cond entirely; the cond
    returns the phase's delta plan for forwarding and the
    end-of-iteration merged scatter.  The per-lane staging rows (small,
    lane-local) stay carried — staged puts happen inside.
    `fn(ms, acc) -> (ms, progress)` defers every directory write via
    acc."""
    d0 = ms.directory

    def detach(m):
        return m.replace(directory=m.directory.replace(
            entry=None, sharers=None))

    def run(m):
        # the phase runs with BOTH big stores detached — its only
        # directory reads are the view rows, its only writes the plan
        acc = _DirAcc()
        m2, prog = fn(m, acc)
        return m2, prog, acc.pack(d0, n_tiles)

    def skip(m):
        return m, jnp.zeros((), jnp.int32), _DirAcc.zero_pack(d0, n_tiles)

    ms2, prog, pack = jax.lax.cond(pred, run, skip, detach(ms))
    d = ms2.directory.replace(entry=d0.entry, sharers=d0.sharers)
    return ms2.replace(directory=d), prog, pack


def _cond_nodir(pred, fn, ms):
    """Run a directory-free engine phase (requester start, sharer serve,
    requester fill) under a scalar-predicate lax.cond.  The directory is
    detached from the carried operands entirely — these phases neither
    read nor write it — so the cond cannot double-buffer the big
    stores."""
    d0 = ms.directory

    def run(m):
        return fn(m)

    def skip(m):
        return m, jnp.zeros((), jnp.int32)

    ms2, prog = jax.lax.cond(pred, run, skip, ms.replace(directory=None))
    return ms2.replace(directory=d0), prog


def _dir_update(d, sets, way, mask, *, view: _DirRowView, acc: _DirAcc,
                px: ParallelCtx = IDENT, tags=None, dstate=None,
                owner=None, sharers=None, nsharers=None):
    """Masked per-lane write of one directory entry, deferred.

    Add-a-delta (new = cur + (new - cur) under mask): the current values
    are read from the phase's forwarded working-set row `view` (the big
    stores are detached from a gated phase's cond entirely; a staged
    way's value comes out of `d.sval`: `_DirRowView.current_row3`), and the
    entry-word and sharers-row deltas are accumulated in `acc`,
    replicated full-width — `_dir_apply_merged` lands every phase's plan
    in one scatter per store at the end of the iteration.  The sharers
    row delta is recorded in staged mode too, so that later phases'
    views can forward it; there the write itself goes to this device's
    home rows of the staging table (`px` serves nothing else here)."""
    cur = view.word_at(way)
    new = cur
    if tags is not None:
        new = _dir_set_field(new, tags.astype(I64) + 1, 0, _TAG_MASK)
    if dstate is not None:
        new = _dir_set_field(new, jnp.asarray(dstate, jnp.uint8),
                             DIR_STATE_SHIFT, 7)
    if owner is not None:
        new = _dir_set_field(new, owner.astype(I64) + 1,
                             DIR_OWNER_SHIFT, _ID_MASK)
    if nsharers is not None:
        new = _dir_set_field(new, nsharers, DIR_NSH_SHIFT, _ID_MASK)
    if new is not cur:
        delta = jnp.where(mask, new - cur, jnp.zeros_like(cur))
        acc.add_entry(sets, way, delta)
    if sharers is not None:
        DW = view._dw
        row3 = view.current_row3(d, way)
        onehot = (np.arange(DW, dtype=np.int32)[None, :, None]
                  == way[:, None, None]) & mask[:, None, None]
        new3 = jnp.where(onehot, sharers[:, None, :], row3)
        row_delta = (new3 - row3).reshape(row3.shape[0], -1)
        acc.add_sharers(sets, way, row_delta)
        if d.skey is not None:
            d = _stage_put(d, *px.lo((sets, way, mask, sharers)), DW)
    return d


# --------------------------------------------------------------------------
# the engine step


def memory_engine_step(
    mp: MemParams,
    ms: MemState,
    rec: RecView,
    clock_ps: jax.Array,      # int64[T] core clocks (base of slot accesses)
    freq_mhz: jax.Array,      # int32[T] per-tile core/cache frequency
    active: jax.Array,        # bool[T] lane may start new work this iter
    enabled,                  # bool[] models enabled
    px: ParallelCtx = IDENT,  # shard_map exchange context (parallel/px.py)
    fill_events: bool = False,  # emit per-call MemStepOut.fill_now/_lat_ps
    home_gate: bool = True,   # False: the caller gates the whole engine
) -> MemStepOut:
    T = mp.n_tiles
    tiles = np.arange(T, dtype=np.int32)
    progress = jnp.zeros((), jnp.int32)
    fmhz = freq_mhz.astype(I64)

    mc = jnp.asarray(mp.mc_tiles, jnp.int32)

    def home_of(line):
        return mc[nn_mod(line, len(mp.mc_tiles)).astype(jnp.int32)]

    def ccycles(n, f=None):
        """cycles→ps at per-tile cache frequency (or given), model-gated."""
        n = jnp.asarray(n, I64)
        ps = cycles_to_ps(n, fmhz if f is None else f)
        return jnp.where(enabled, ps, 0)

    dram_lat_ps = jnp.where(
        enabled, (mp.dram_latency_ns + mp.dram_processing_ns) * 1000, 0
    ).astype(I64)
    dir_access_ps = jnp.where(
        enabled, cycles_to_ps(jnp.asarray(mp.dir_access_cycles, I64),
                              mp.dir_freq_mhz), 0
    ).astype(I64)

    sync_core_l1d = ccycles(mp.sync_cycles(MOD_CORE, MOD_L1D))
    sync_core_l1i = ccycles(mp.sync_cycles(MOD_CORE, MOD_L1I))
    sync_l1d_l2 = ccycles(mp.sync_cycles(MOD_L1D, MOD_L2))
    sync_l1i_l2 = ccycles(mp.sync_cycles(MOD_L1I, MOD_L2))
    sync_l2_net = ccycles(mp.sync_cycles(MOD_L2, MOD_NET_MEM))
    sync_dir_l2 = jnp.where(
        enabled,
        cycles_to_ps(jnp.asarray(mp.sync_cycles(MOD_DIR, MOD_L2), I64),
                     mp.dir_freq_mhz), 0).astype(I64)
    sync_dir_net = jnp.where(
        enabled,
        cycles_to_ps(jnp.asarray(mp.sync_cycles(MOD_DIR, MOD_NET_MEM), I64),
                     mp.dir_freq_mhz), 0).astype(I64)

    # ---- slot decomposition of the current record -------------------------
    flags = rec.flags
    present = slots_present(mp, rec, enabled)

    def next_present(slot):
        return next_present_slot(present, slot)

    # ======================================================================
    # (1) requester slot starts (app-thread L1/L2 path) — unrolled
    # mp.requester_unroll times per engine iteration: records whose
    # next slots HIT the L1 complete several slots per iteration (the
    # repeat is ~15 cheap L1/L2-row kernels vs a whole extra engine
    # iteration per slot).  A lane that misses sets PHASE_WAIT_REPLY
    # and later repeats are no-ops for it; within-iteration repeats
    # see no intervening protocol messages — the serialization the
    # golden oracle itself uses (whole records at once).
    # ======================================================================
    def _requester_once(ms, progress):
        # ======================================================================
        # (1) requester slot starts (app-thread L1/L2 path)
        # ======================================================================
        slot = next_present(ms.req.slot)
        has_slot = slot < 3
        idle = ms.req.phase == PHASE_IDLE
        starting = active & idle & has_slot

        # slot attributes
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
        s_comp_l1i = s_is_icache

        # instruction-buffer fast path (`core.cc:205-220`): hit = 1 cycle
        ibuf_hit = starting & s_is_icache & (s_line == ms.req.instr_buf)
        new_instr_buf = jnp.where(starting & s_is_icache, s_line, ms.req.instr_buf)

        # L1 lookups (both caches, masked by component) — each lane's set rows
        # are gathered ONCE per cache level here and scattered back once below
        # (the engine is op-count-bound; see cache_array.py).  Under a
        # sharded px the gathers read this device's block.
        do_l1 = starting & ~ibuf_hit

        # The L1 path reads no L2 row, so it runs first and block-local,
        # on this device's lanes: look-ups, the hit's recency refresh, the
        # miss's invalidate, and the way (with the line in it) that an L2
        # hit would then fill.  Whether that fill HAPPENS is the L2's to
        # say — a mask, not an index — so the victim's line is known
        # before the L2 store is read and its L2 set rides the phase's one
        # gather of that store.  The L1 rows never travel: the replicated
        # control needs only `l1_hit_now` / `l1_miss` of them.
        s_line_l, comp_l, write_l, do_l1_l = px.lo(
            (s_line, s_comp_l1i, s_write, do_l1))
        l1i_row = ca.gather_row(ms.l1i, s_line_l,
                                px.lo_const(mp.l1i.sets_mod), nonneg=True)
        l1d_row = ca.gather_row(ms.l1d, s_line_l,
                                px.lo_const(mp.l1d.sets_mod), nonneg=True)
        _, l1i_way, l1i_state = ca.row_lookup(l1i_row, s_line_l)
        _, l1d_way, l1d_state = ca.row_lookup(l1d_row, s_line_l)
        l1_state = jnp.where(comp_l, l1i_state, l1d_state)
        l1_permit = jnp.where(write_l, state_writable(l1_state),
                              state_readable(l1_state))
        hit_l = do_l1_l & l1_permit
        miss_l = do_l1_l & ~l1_permit
        # hits refresh recency under LRU; round_robin's update is a no-op
        if mp.l1i.replacement != "round_robin":
            l1i_row = ca.row_touch(l1i_row, l1i_way, hit_l & comp_l)
        if mp.l1d.replacement != "round_robin":
            l1d_row = ca.row_touch(l1d_row, l1d_way, hit_l & ~comp_l)
        # L1 line invalidated on miss before L2 is consulted
        # (`l1_cache_cntlr.cc:137`) — must precede the L2-hit fill below, so
        # the fill lands in the just-freed way and survives
        l1i_row = ca.row_invalidate(l1i_row, s_line_l, miss_l & comp_l)
        l1d_row = ca.row_invalidate(l1d_row, s_line_l, miss_l & ~comp_l)
        l1i_fway, l1d_fway, ev_valid_l, ev_line_l = _l1_fill_ways(
            mp, px, l1i_row, l1d_row, comp_l)
        # the L2 store's ONE reader in this phase: the request's set row
        # and the candidate victim's (cache_array.gather_row_pair).  Only
        # the request's row travels; the victim's look-up feeds the local
        # cloc scatter and nothing else.
        l2_mod_l = px.lo_const(mp.l2.sets_mod)
        l2_row_l, ev_row_l = ca.gather_row_pair(
            ms.l2, s_line_l, ev_line_l, l2_mod_l)
        ev_hit_l, ev_way_l, _ = ca.row_lookup(ev_row_l, ev_line_l)
        if mp.l2.track_miss_types:
            mt_bits_l = (_mt_test(ms.mt, MT_EVICTED, s_line_l),
                         _mt_test(ms.mt, MT_INVALIDATED, s_line_l),
                         _mt_test(ms.mt, MT_FETCHED, s_line_l))
        else:
            mt_bits_l = ()
        if mp.l2.track_line_utilization:
            mt_bits_l = mt_bits_l + (_util_row_local(
                ms.l2_util, s_line_l, l2_mod_l),)
        # ONE packed all-gather under a sharded px: the L2 row, the L1
        # path's two verdicts, and the pre-update miss-type test bits
        # (read before this phase's own writes)
        (l2_row,), mt_bits = _rows_exchange(
            px, (l2_row_l,), (hit_l, miss_l) + mt_bits_l)
        l1_hit_now, l1_miss, mt_bits = mt_bits[0], mt_bits[1], mt_bits[2:]
        if mp.l2.track_line_utilization:
            lu_row, mt_bits = mt_bits[-1], mt_bits[:-1]

        sync_core = jnp.where(s_comp_l1i, sync_core_l1i, sync_core_l1d)
        l1_dat = jnp.where(
            s_comp_l1i, ccycles(mp.l1i.data_and_tags_cycles),
            ccycles(mp.l1d.data_and_tags_cycles))
        l1_tag = jnp.where(
            s_comp_l1i, ccycles(mp.l1i.tags_cycles), ccycles(mp.l1d.tags_cycles))
        sync_l1_l2 = jnp.where(s_comp_l1i, sync_l1i_l2, sync_l1d_l2)

        # L2 lookup for L1 misses
        l2_hit, l2_way, l2_state = ca.row_lookup(l2_row, s_line)
        l2_permit = jnp.where(s_write, state_writable(l2_state),
                              state_readable(l2_state))
        l2_hit_now = l1_miss & l2_permit
        l2_miss = l1_miss & ~l2_permit

        # upgrade (write to a readable-but-not-writable L2 line): invalidate L2
        # + eviction message to home, then a full EX_REQ refetch
        # (`l2_cache_cntlr.cc:261-282 processExReqFromL1Cache`; documented
        # simplification: the reference's UPGRADE_REP without data is modeled
        # as a refetch, same message count, slightly larger data serialization).
        # MOSI: an OWNED line is dirty, so its upgrade eviction must FLUSH.
        upgrade = l2_miss & s_write & (
            (l2_state == SHARED) | (l2_state == OWNED))
        upgrade_dirty = upgrade & (l2_state == OWNED)
        s_home = home_of(s_line)
        evict_cell_busy = ms.mail.evict_type[s_home, tiles] != MSG_NONE
        stall_start = upgrade & evict_cell_busy
        l2_miss_go = l2_miss & ~stall_start

        # --- the L1-hit path (its rows were refreshed above) ------------------
        sclock = clock_ps + sync_core           # processMemOpFromCore entry
        l1_hit_done_ps = sclock + l1_dat

        # --- apply the L2-hit path (fill L1 from L2) -------------------------
        # timing: L1 tags (miss) + L2 sync + L2 data+tags + L1 data+tags
        l2_hit_done_ps = sclock + l1_tag + sync_l1_l2 + ccycles(
            mp.l2.data_and_tags_cycles) + l1_dat
        # L1 fill state = L2 state (`insertCacheLineInL1`), into the way
        # picked above; block-local like the rest of the L1 path
        fill_l, l2_state_l = px.lo((l2_hit_now, l2_state))
        l1i_row = ca.row_insert(l1i_row, s_line_l, l1i_fway, l2_state_l,
                                fill_l & comp_l)
        l1d_row = ca.row_insert(l1d_row, s_line_l, l1d_fway, l2_state_l,
                                fill_l & ~comp_l)
        # L1 victims: clear their cached-loc in L2 (line stays valid in L2).
        # The whole read-modify-write chain is block-local: its only
        # consumer is the local cloc scatter, so nothing travels.
        l2_cloc = px.entry_set(ms.l2_cloc, ev_row_l.sets, ev_way_l,
                               fill_l & ev_valid_l & ev_hit_l, 0)
        # record new cached-loc for the filled line
        f_sets = nn_mod(s_line, jnp.asarray(mp.l2.sets_mod)).astype(jnp.int32)
        new_cloc = jnp.where(s_comp_l1i, MOD_L1I, MOD_L1D).astype(jnp.uint8)
        l2_cloc = px.entry_set(
            l2_cloc, *px.lo((f_sets, l2_way, l2_hit_now, new_cloc)))
        if mp.l2.replacement != "round_robin":
            l2_row = ca.row_touch(l2_row, l2_way, l2_hit_now)

        # --- apply the L2-miss path (send request) ---------------------------
        # `processExReqFromL1Cache`/`processShReqFromL1Cache`: request time =
        # entry sync + L1 tags + L2 tags
        req_send_ps = sclock + l1_tag + ccycles(mp.l2.tags_cycles)
        # upgrade: invalidate L2 + eviction message (INV_REP clean, FLUSH_REP
        # for a dirty OWNED line)
        up_go = upgrade & ~stall_start
        l2_row = ca.row_invalidate(l2_row, s_line, up_go)
        if mp.l2.track_line_utilization:
            # L2 hit: count the access; upgrade invalidate: the line
            # leaves the L2 — classify its counters and zero them
            en = jnp.asarray(enabled, bool)
            lu_cur = jnp.take_along_axis(lu_row, l2_way[:, None],
                                         axis=1)[:, 0]
            lu_new = _util_inc(lu_cur, s_write, l2_hit_now & en)
            lu_new = jnp.where(up_go & en, jnp.uint32(0), lu_new)
            ms = ms.replace(l2_util=_util_scatter(
                px, ms.l2_util, s_line, mp.l2.sets_mod, l2_way,
                lu_cur, lu_new))
            ms = ms.replace(counters=_util_classify(
                ms.counters, lu_cur, up_go, enabled))
        # scatter the three set rows back — ONE scatter per cache level,
        # each device taking its own lanes' rows
        l1i_upd = ca.scatter_row(ms.l1i, l1i_row)
        l1d_upd = ca.scatter_row(ms.l1d, l1d_row)
        l2_upd = ca.scatter_row(ms.l2, px.lo(l2_row))
        mail = ms.mail
        noc = ms.noc
        up_msg = jnp.where(upgrade_dirty, MSG_FLUSH_REP,
                           MSG_INV_REP).astype(jnp.uint8)
        w_home = jnp.where(up_go, s_home, 0)
        noc, up_arrival = mem_net_send(
            mp, noc, tiles, s_home, mp.req_bits, req_send_ps, up_go, enabled)
        mail = mail.replace(
            evict_type=mail.evict_type.at[w_home, tiles].set(
                jnp.where(up_go, up_msg, mail.evict_type[w_home, tiles])),
            evict_line=mail.evict_line.at[w_home, tiles].set(
                jnp.where(up_go, s_line, mail.evict_line[w_home, tiles])),
            evict_time=mail.evict_time.at[w_home, tiles].set(
                jnp.where(up_go, up_arrival,
                          mail.evict_time[w_home, tiles])),
        )
        rq_type = jnp.where(s_write, MSG_EX_REQ, MSG_SH_REQ).astype(jnp.uint8)
        noc, rq_arrival = mem_net_send(
            mp, noc, tiles, s_home, mp.req_bits, req_send_ps, l2_miss_go,
            enabled)
        # per-requester lane (one outstanding miss per tile): plain
        # masked selects, no matrix scatter
        mail = mail.replace(
            req_type=jnp.where(l2_miss_go, rq_type, mail.req_type),
            req_home=jnp.where(l2_miss_go, s_home, mail.req_home),
            req_line=jnp.where(l2_miss_go, s_line, mail.req_line),
            req_time=jnp.where(l2_miss_go, rq_arrival, mail.req_time),
        )

        # --- requester bookkeeping for this iteration's starts ----------------
        slot_done_now = ibuf_hit | l1_hit_now | l2_hit_now
        slot_done_ps = jnp.where(
            ibuf_hit, clock_ps + ccycles(1),
            jnp.where(l1_hit_now, l1_hit_done_ps, l2_hit_done_ps))

        req_state = ms.req.replace(
            phase=jnp.where(l2_miss_go, PHASE_WAIT_REPLY, ms.req.phase),
            line=jnp.where(l2_miss_go, s_line, ms.req.line),
            is_write=jnp.where(l2_miss_go, s_write, ms.req.is_write),
            component=jnp.where(
                l2_miss_go, jnp.where(s_comp_l1i, MOD_L1I, MOD_L1D),
                ms.req.component).astype(jnp.uint8),
            clock_ps=jnp.where(l2_miss_go, req_send_ps, ms.req.clock_ps),
            acc_ps=ms.req.acc_ps
            + jnp.where(slot_done_now, slot_done_ps - clock_ps, 0),
            # per-slot latency for the iocoom operand algebra
            slot_lat_ps=jnp.where(
                (slot_done_now[:, None]
                 & (np.arange(3)[None, :] == slot[:, None])),
                (slot_done_ps - clock_ps)[:, None], ms.req.slot_lat_ps),
            instr_buf=new_instr_buf,
            # slot advances on completion; on miss it stays (the reply path
            # advances it); skipped-over absent slots jump to the live one
            slot=jnp.where(slot_done_now, slot + 1,
                           jnp.where(starting, slot, ms.req.slot)),
        )

        # count misses only when the miss actually proceeds: a lane stalled on
        # a busy evict cell (stall_start) retries `starting` every iteration
        # and must not re-count
        miss_go = l1_miss & ~stall_start
        # L2 miss-type classification (`cache.cc getMissType` priority:
        # evicted -> CAPACITY, else invalidated/fetched -> SHARING, else
        # COLD), read BEFORE this access's own set updates
        if mp.l2.track_miss_types:
            cls = l2_miss_go & jnp.asarray(enabled, bool)
            in_e, in_i, in_f = mt_bits  # pre-update reads (exchanged above)
            mt_cap = cls & in_e
            mt_sha = cls & ~in_e & (in_i | in_f)
            mt_cold = cls & ~in_e & ~in_i & ~in_f
            # the upgrade's local L2 invalidate feeds the invalidated set
            # (`setCacheLineInfo` INVALID transition)
            new_mt = _mt_update(ms.mt, MT_INVALIDATED, s_line_l,
                                px.lo(up_go), True)
            ms = ms.replace(mt=new_mt)
        else:
            mt_cap = mt_sha = mt_cold = jnp.zeros((T,), jnp.bool_)
        counters = ms.counters.replace(
            l1i_hits=ms.counters.l1i_hits
            + ((l1_hit_now | ibuf_hit) & s_comp_l1i & enabled).astype(I64),
            l1i_misses=ms.counters.l1i_misses
            + (miss_go & s_comp_l1i & enabled).astype(I64),
            l1d_read_hits=ms.counters.l1d_read_hits
            + (l1_hit_now & ~s_comp_l1i & ~s_write & enabled).astype(I64),
            l1d_read_misses=ms.counters.l1d_read_misses
            + (miss_go & ~s_comp_l1i & ~s_write & enabled).astype(I64),
            l1d_write_hits=ms.counters.l1d_write_hits
            + (l1_hit_now & ~s_comp_l1i & s_write & enabled).astype(I64),
            l1d_write_misses=ms.counters.l1d_write_misses
            + (miss_go & ~s_comp_l1i & s_write & enabled).astype(I64),
            l2_hits=ms.counters.l2_hits + (l2_hit_now & enabled).astype(I64),
            l2_misses=ms.counters.l2_misses + (l2_miss_go & enabled).astype(I64),
            l2_cold_misses=ms.counters.l2_cold_misses + mt_cold.astype(I64),
            l2_capacity_misses=ms.counters.l2_capacity_misses
            + mt_cap.astype(I64),
            l2_sharing_misses=ms.counters.l2_sharing_misses
            + mt_sha.astype(I64),
        )
        progress = progress + jnp.sum(slot_done_now | l2_miss_go, dtype=jnp.int32)

        ms = ms.replace(
            l1i=l1i_upd, l1d=l1d_upd, l2=l2_upd, l2_cloc=l2_cloc,
            mail=mail, req=req_state, counters=counters, noc=noc,
        )

        # functional effect of slots completed via L1/L2 (loads/stores)
        ms = _apply_functional(mp, ms, rec, slot, s_addr, s_write,
                               slot_done_now & ~s_is_icache)
        return ms, progress

    # The phase ORDER is chosen so a miss resolves in ONE engine iteration
    # when no queued transaction is ahead of it: the request written by
    # phase (1) is popped by (3), whose INV/FLUSH/WB fan-out is
    # served by (4), whose acks finish the transaction in (5), whose reply
    # fills the requester in (6) — all mailbox hand-offs are visible
    # same-iteration because each phase reads the matrices its predecessor
    # just wrote.  Simulated time rides IN the messages, so this ordering
    # only compresses wall-clock iterations (the old order needed 2 per
    # fan-out miss); the timing algebra is unchanged.
    #
    # Per-phase activity gating (mp.phase_gate): each phase runs under its
    # OWN scalar-predicate lax.cond, computed from replicated control
    # state (mailboxes, txn, requester phase) at that point in the
    # sequence — so a phase a predecessor just fed still fires
    # same-iteration, and under shard_map every device takes the same
    # branch with no new collectives.  A phase with its predicate false is
    # a provable no-op (every write is masked by the very condition the
    # predicate disjoins over), so gating is bit-exact; the conds carry
    # only small per-phase state — see _cond_nodir/_cond_dir.  Under a
    # campaign's sim axis every predicate, `home_live` too, is OR-ed
    # over the sims of the program (px.any_sim), so it stays a scalar
    # under `vmap` and the cond stays a cond: a sim with nothing for a
    # phase that a sibling needs runs it with every lane masked off,
    # and the skip counters count what the PROGRAM skipped.

    gate = bool(getattr(mp, "phase_gate", False))

    def _phase_requester(ms):
        prog = jnp.zeros((), jnp.int32)
        for _ in range(max(int(mp.requester_unroll), 1)):
            ms, prog = _requester_once(ms, prog)
        return ms, prog

    # ======================================================================
    # (1) requester slot starts (app-thread L1/L2 path)
    # ======================================================================
    # a lane that cannot start at block entry cannot start mid-unroll
    # either (only phase 6 returns a lane to PHASE_IDLE), so one
    # predicate covers the whole unrolled block
    pred1 = px.any_sim(jnp.any(active & (ms.req.phase == PHASE_IDLE)
                               & (next_present(ms.req.slot) < 3)))
    with scope("gt.mem." + PHASE_NAMES[0]):
        if gate:
            ms, p = _cond_nodir(pred1, _phase_requester, ms)
        else:
            ms, p = _phase_requester(ms)
    progress = progress + p

    # ======================================================================
    # (2) homes consume one EVICT per iteration
    # ======================================================================
    # The base: after the requester phase every set the home phases can
    # touch is known, so ONE packed working-set gather (entry + sharers
    # rows, staging overlaid) serves phases 2/3/5, each phase returns
    # its delta plan for forwarding, and the plans land in ONE merged
    # scatter per store after phase 5.
    #
    # The home-activity gate (phase_gate regime): one scalar, evaluated
    # HERE, under which that base runs — the gather under a lax.cond
    # (its outputs are the small rows), the merged scatter under an
    # in-place zero-or-one-trip loop (`_run_if`).  `home_live` is a
    # superset of pred2 | pred3 | pred5 at their own evaluation points.
    # With it false there is no EVICT cell, so pred2 is false and phase
    # 2 is a no-op; then no REQ cell and no saved transaction, so pred3
    # is false, phase 3 is a no-op and emits no FWD; there was no FWD
    # cell, so phase 4 is a no-op and emits no ACK; there was no ACK
    # cell and no active transaction, so pred5 is false.  Every view
    # then goes unread and every plan is a zero pack: the gather's rows
    # are dead and the scatter adds zeros.  Replicated control state
    # only, so every device of a mesh takes the same arm.  None =
    # forced live (gates off, or `home_gate` false because the caller
    # already has the whole engine under one cond: today's program).
    packs = []
    home_live = None
    mail0, txn0 = ms.mail, ms.txn
    if gate and home_gate:
        home_live = px.any_sim((mail0.evict_type != MSG_NONE).any()
                               | (mail0.req_type != MSG_NONE).any()
                               | (mail0.fwd_type != MSG_NONE).any()
                               | (mail0.ack_type != MSG_NONE).any()
                               | txn0.active.any()
                               | txn0.saved_valid.any())

    def _ws_lines():
        src_e0, _ = _row_earliest(mail0.evict_type, mail0.evict_time)
        eline0 = mail0.evict_line[tiles, src_e0]
        use_saved0 = ~txn0.active & txn0.saved_valid
        r_col0, _ = _req_earliest(mail0)
        rline0 = jnp.where(use_saved0, txn0.saved_line,
                           mail0.req_line[r_col0])
        return eline0, rline0, txn0.line

    ws = _DirWorkingSet(px, ms.directory, mp, _ws_lines, live=home_live)
    eline0, rline0, _ = ws.lines

    def _run_dir_phase(pred, fn):
        """One home phase, gated (the cond returns its delta plan) or
        not (the plan straight); the plan joins `packs`."""
        nonlocal ms
        if gate:
            ms, p, pk = _cond_dir(pred, fn, ms, T)
        else:
            a = _DirAcc()
            d0 = ms.directory
            ms, p = fn(ms, a)
            pk = a.pack(d0, T)
        packs.append(pk)
        return p

    pred2 = px.any_sim((ms.mail.evict_type != MSG_NONE).any())
    view2 = ws.view(0, eline0, packs)
    with scope("gt.mem." + PHASE_NAMES[1]):
        p = _run_dir_phase(
            pred2,
            lambda m, a: _home_evictions(
                mp, m, dir_access_ps, enabled, jnp.zeros((), jnp.int32),
                view2, a, px))
    progress = progress + p

    # ======================================================================
    # (3) homes start transactions (pop request / resume saved)
    # ======================================================================
    pred3 = px.any_sim((ms.mail.req_type != MSG_NONE).any()
                       | (ms.txn.saved_valid & ~ms.txn.active).any())
    view3 = ws.view(1, rline0, list(packs))
    with scope("gt.mem." + PHASE_NAMES[2]):
        p = _run_dir_phase(
            pred3,
            lambda m, a: _home_starts(
                mp, m, dram_lat_ps, dir_access_ps, sync_dir_l2,
                sync_dir_net, enabled, jnp.zeros((), jnp.int32), view3, a,
                px))
    progress = progress + p

    # ======================================================================
    # (4) sharers consume one FWD per iteration
    # ======================================================================
    pred4 = px.any_sim((ms.mail.fwd_type != MSG_NONE).any())
    with scope("gt.mem." + PHASE_NAMES[3]):
        if gate:
            ms, p = _cond_nodir(
                pred4,
                lambda m: _sharer_step(mp, m, fmhz, enabled,
                                       jnp.zeros((), jnp.int32),
                                       sync_l2_net, sync_l1d_l2, px),
                ms)
        else:
            ms, p = _sharer_step(mp, ms, fmhz, enabled,
                                 jnp.zeros((), jnp.int32),
                                 sync_l2_net, sync_l1d_l2, px)
    progress = progress + p

    # ======================================================================
    # (5) homes consume ACKs, finish transactions
    # ======================================================================
    pred5 = px.any_sim((ms.mail.ack_type != MSG_NONE).any()
                       | ms.txn.active.any())
    view5 = ws.view_finish(ms.txn.line, list(packs))
    with scope("gt.mem." + PHASE_NAMES[4]):
        p = _run_dir_phase(
            pred5,
            lambda m, a: _home_acks_and_finish(
                mp, m, dram_lat_ps, dir_access_ps, enabled,
                jnp.zeros((), jnp.int32), view5, a, px))
    progress = progress + p
    # the ONE merged scatter per big store for this iteration
    ms = ms.replace(directory=_dir_apply_merged(
        ms.directory, px, packs, live=home_live))

    # ======================================================================
    # (6) requesters consume replies (fill L2+L1, complete slot)
    # ======================================================================
    pred6 = px.any_sim(((ms.req.phase == PHASE_WAIT_REPLY)
                        & (ms.mail.rep_type != MSG_NONE)).any())
    # fill observability: only phase 6's fill advances req.slot / adds to
    # req.acc_ps, so the pre/post delta IS the per-call fill event — exact
    # even when the whole miss started in phase 1 of this same call
    slot_pre6 = ms.req.slot
    acc_pre6 = ms.req.acc_ps
    with scope("gt.mem." + PHASE_NAMES[5]):
        if gate:
            ms, p = _cond_nodir(
                pred6,
                lambda m: _requester_fill(mp, m, rec, clock_ps, fmhz, enabled,
                                          jnp.zeros((), jnp.int32),
                                          sync_l2_net, px),
                ms)
        else:
            ms, p = _requester_fill(mp, ms, rec, clock_ps, fmhz, enabled,
                                    jnp.zeros((), jnp.int32), sync_l2_net, px)
    progress = progress + p

    # ---- completion signal ----------------------------------------------
    final_slot = next_present(ms.req.slot)
    mem_complete = (ms.req.phase == PHASE_IDLE) & (final_slot >= 3)
    # protocol-liveness flag: lets the caller skip the whole engine on
    # iterations with no memory work (see mem_idle_out)
    ms = ms.replace(live=protocol_live(ms))
    if gate:
        skipped = 1 - jnp.stack(
            [pred1, pred2, pred3, pred4, pred5, pred6]).astype(I64)
        ms = ms.replace(phase_skips=ms.phase_skips + skipped)
    if home_live is not None:
        ms = ms.replace(base_skips=ms.base_skips
                        + jnp.where(home_live, 0, BASE_SKIPPED))
    return MemStepOut(
        ms=ms, mem_complete=mem_complete, acc_ps=ms.req.acc_ps,
        slot_lat_ps=ms.req.slot_lat_ps,
        progress=progress,
        fill_now=(ms.req.slot != slot_pre6) if fill_events else None,
        fill_lat_ps=(ms.req.acc_ps - acc_pre6) if fill_events else None,
    )


# --------------------------------------------------------------------------
# functional memory


def _apply_functional(mp, ms: MemState, rec: RecView, slot, s_addr, s_write,
                      mask):
    if mp.func_mem_words <= 0:
        return ms
    word = ((s_addr.astype(jnp.uint32) >> 2) % mp.func_mem_words).astype(
        jnp.int32)
    value = jnp.where(slot == 1, rec.aux0, rec.aux1).astype(jnp.uint32)
    wr = mask & s_write
    # masked-off lanes write a dedicated scratch slot (the last word) so a
    # dummy write can never clobber a live one
    tgt = jnp.where(wr, word, mp.func_mem_words)
    fm = ms.func_mem.at[tgt].set(jnp.where(wr, value, 0))
    check = mask & ~s_write & (slot == 1) & ((rec.flags & FLAG_CHECK) != 0)
    loaded = fm[word]
    errs = jnp.sum(check & (loaded != rec.aux0.astype(jnp.uint32)),
                   dtype=I64)
    return ms.replace(func_mem=fm, func_errors=ms.func_errors + errs)


# --------------------------------------------------------------------------
# sharer-side FWD service (`l2_cache_cntlr.cc:295-503`)


def _sharer_step(mp, ms: MemState, fmhz, enabled, progress,
                 sync_l2_net, sync_l1d_l2, px: ParallelCtx = IDENT):
    T = mp.n_tiles
    tiles = np.arange(T, dtype=np.int32)
    mail = ms.mail

    def ccyc(n):
        ps = cycles_to_ps(jnp.asarray(n, I64), fmhz)
        return jnp.where(enabled, ps, 0)

    h, found = _row_earliest(mail.fwd_type, mail.fwd_time)
    ftype = mail.fwd_type[tiles, h]
    fline = mail.fwd_line[tiles, h]
    ftime = mail.fwd_time[tiles, h]

    # block-local row gathers at the served line (+ the cached-loc SET row
    # — way selection happens replicated after the exchange; single-device
    # keeps the direct element read)
    fline_l = px.lo(fline)
    l2_mod_l = px.lo_const(mp.l2.sets_mod)
    sets_l = nn_mod(fline_l, jnp.asarray(l2_mod_l)).astype(jnp.int32)
    lt = np.arange(ms.l2.meta.shape[0], dtype=np.int32)
    rows_l = (ca.gather_row(ms.l2, fline_l, l2_mod_l, nonneg=True),
              ca.gather_row(ms.l1i, fline_l, px.lo_const(mp.l1i.sets_mod),
                            nonneg=True),
              ca.gather_row(ms.l1d, fline_l, px.lo_const(mp.l1d.sets_mod),
                            nonneg=True))
    util_row_l = (_util_row_local(ms.l2_util, fline_l, l2_mod_l)
                  if mp.l2.track_line_utilization else None)
    if px.sharded:
        extras = (ms.l2_cloc[lt, sets_l],)
        if util_row_l is not None:
            extras = extras + (util_row_l,)
        (l2_r, l1i_r, l1d_r), extras = _rows_exchange(px, rows_l, extras)
        cloc_row = extras[0]
        lu_row = extras[1] if util_row_l is not None else None
    else:
        l2_r, l1i_r, l1d_r = rows_l
        cloc_row = None
        lu_row = util_row_l
    l2_hit, l2_way, l2_state = ca.row_lookup(l2_r, fline)
    serve = found & l2_hit & (l2_state != INVALID)
    silent = found & ~serve  # already evicted; eviction msg satisfies home

    # time: network sync + L2 access + L1 tag access + domain syncs
    # (`processInvReqFromDramDirectory` / Flush / Wb)
    is_inv = ftype == MSG_INV_REQ
    l2_cost = jnp.where(is_inv, ccyc(mp.l2.tags_cycles),
                        ccyc(mp.l2.data_and_tags_cycles))
    l1_cost = ccyc(mp.l1d.tags_cycles)
    done_ps = ftime + sync_l2_net + l2_cost + l1_cost + 2 * sync_l1d_l2

    # invalidate / downgrade L1 (whichever L1 holds it, by cached-loc)
    sets = nn_mod(fline, jnp.asarray(mp.l2.sets_mod)).astype(jnp.int32)
    if cloc_row is not None:
        cloc = jnp.take_along_axis(cloc_row, l2_way[:, None], axis=1)[:, 0]
    else:
        cloc = ms.l2_cloc[tiles, sets, l2_way]
    inv_l1 = serve & (ftype != MSG_WB_REQ)
    wb_l1 = serve & (ftype == MSG_WB_REQ)
    l1i_r = ca.row_invalidate(l1i_r, fline, inv_l1 & (cloc == MOD_L1I))
    l1d_r = ca.row_invalidate(l1d_r, fline, inv_l1 & (cloc == MOD_L1D))
    l1i_hit, l1i_way, _ = ca.row_lookup(l1i_r, fline)
    l1d_hit, l1d_way, _ = ca.row_lookup(l1d_r, fline)
    # WB downgrade: MSI M→SHARED; MOSI M→OWNED, O→O, S→S (the owner keeps
    # the dirty line — mosi `l2_cache_cntlr.cc:538-566`)
    if mp.is_mosi:
        wb_state = jnp.where(l2_state == MODIFIED, OWNED,
                             l2_state).astype(jnp.uint8)
    else:
        wb_state = jnp.full_like(l2_state, SHARED)
    l1i_r = ca.row_set_state(l1i_r, l1i_way, wb_state,
                             wb_l1 & (cloc == MOD_L1I) & l1i_hit)
    l1d_r = ca.row_set_state(l1d_r, l1d_way, wb_state,
                             wb_l1 & (cloc == MOD_L1D) & l1d_hit)
    l1i = ca.scatter_row(ms.l1i, px.lo(l1i_r))
    l1d = ca.scatter_row(ms.l1d, px.lo(l1d_r))

    # L2: invalidate (INV/FLUSH) or downgrade (WB)
    l2_r = ca.row_invalidate(l2_r, fline, inv_l1)
    l2_r = ca.row_set_state(l2_r, l2_way, wb_state, wb_l1)
    l2 = ca.scatter_row(ms.l2, px.lo(l2_r))
    if mp.l2.track_line_utilization:
        # the INV/FLUSH'd line leaves the L2: classify + zero its counters
        en = jnp.asarray(enabled, bool)
        lu_cur = jnp.take_along_axis(lu_row, l2_way[:, None], axis=1)[:, 0]
        ms = ms.replace(
            l2_util=_util_scatter(
                px, ms.l2_util, fline, mp.l2.sets_mod, l2_way, lu_cur,
                jnp.where(inv_l1 & en, jnp.uint32(0), lu_cur)),
            counters=_util_classify(ms.counters, lu_cur, inv_l1, enabled))
    if mp.l2.track_miss_types:
        ms = ms.replace(mt=_mt_update(ms.mt, MT_INVALIDATED, fline_l,
                                      px.lo(inv_l1), True))
    # `cloc` is this very element, read above: the store's one reader
    l2_cloc = px.entry_set(ms.l2_cloc, sets_l, px.lo(l2_way),
                           px.lo(inv_l1), 0, cur=px.lo(cloc))

    # ack message back to the home
    ack = jnp.where(
        ftype == MSG_INV_REQ, MSG_INV_REP,
        jnp.where(ftype == MSG_FLUSH_REQ, MSG_FLUSH_REP, MSG_WB_REP),
    ).astype(jnp.uint8)
    # serialization differs per type (INV acks are header-only, FLUSH/WB
    # carry the line)
    ack_bits = jnp.where(is_inv, mp.req_bits, mp.rep_bits)
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
    # consume the fwd cell
    ch = jnp.where(found, h, 0)
    mail = mail.replace(
        fwd_type=mail.fwd_type.at[tiles, ch].set(
            jnp.where(found, MSG_NONE, mail.fwd_type[tiles, ch])),
    )
    counters = ms.counters.replace(
        invalidations=ms.counters.invalidations
        + (serve & is_inv & enabled).astype(I64),
    )
    progress = progress + jnp.sum(found, dtype=jnp.int32)
    return ms.replace(l1i=l1i, l1d=l1d, l2=l2, l2_cloc=l2_cloc, mail=mail,
                      counters=counters, noc=noc), progress


# --------------------------------------------------------------------------
# home-side: evictions (`processInvRepFromL2Cache` / `processFlushRep...`
# "just an eviction" branches)


def _home_evictions(mp, ms: MemState, dir_access_ps, enabled, progress,
                    dsv: _DirRowView, acc: _DirAcc,
                    px: ParallelCtx = IDENT):
    T = mp.n_tiles
    tiles = np.arange(T, dtype=np.int32)
    mail = ms.mail

    src, found = _row_earliest(mail.evict_type, mail.evict_time)
    etype = mail.evict_type[tiles, src]
    eline = mail.evict_line[tiles, src]
    etime = mail.evict_time[tiles, src]

    d = ms.directory
    sets = dsv.sets
    dfound, way = dsv.lookup()
    apply = found & dfound
    _, dstate, owner, sharers, nsh = dsv.entry(d, way)

    was_sharer = test_bit(sharers, src)
    new_sharers = clear_bit(sharers, src, apply)
    new_nsh = nsh - (apply & was_sharer).astype(jnp.int32)
    is_flush = etype == MSG_FLUSH_REP
    new_owner = jnp.where(apply & is_flush, -1, owner)
    # empty entry → UNCACHED; a dirty (owner) departure with sharers left
    # behind → SHARED (the MOSI O→S downgrade; MSI flushes always empty the
    # entry so the same formula holds)
    new_dstate = jnp.where(
        apply,
        jnp.where(new_nsh == 0, DIR_UNCACHED,
                  jnp.where(is_flush, DIR_SHARED, dstate)),
        dstate,
    ).astype(jnp.uint8)
    d = _dir_update(d, sets, way, apply, px=px, dstate=new_dstate,
                    owner=new_owner, sharers=new_sharers, nsharers=new_nsh,
                    acc=acc, view=dsv)

    # active same-line transaction: treat the eviction as the ack
    txn = ms.txn
    txn_match = txn.active & found & (txn.line == eline)
    txn = txn.replace(
        pending=clear_bit(txn.pending, src, txn_match),
        time_ps=jnp.where(txn_match,
                          jnp.maximum(txn.time_ps, etime + dir_access_ps),
                          txn.time_ps),
        data_cached=txn.data_cached | (txn_match & is_flush),
        # park flushed data in the home's one-entry buffer
        # (`_cached_data_list`): a later request for the line skips DRAM
        cdata_line=jnp.where(found & is_flush, eline, txn.cdata_line),
        cdata_valid=txn.cdata_valid | (found & is_flush),
    )

    csrc = jnp.where(found, src, 0)
    mail = mail.replace(
        evict_type=mail.evict_type.at[tiles, csrc].set(
            jnp.where(found, MSG_NONE, mail.evict_type[tiles, csrc])),
    )
    counters = ms.counters.replace(
        evictions=ms.counters.evictions + (found & enabled).astype(I64),
        dram_writes=ms.counters.dram_writes
        + (found & is_flush & enabled).astype(I64),
    )
    progress = progress + jnp.sum(found, dtype=jnp.int32)
    return ms.replace(directory=d, txn=txn, mail=mail,
                      counters=counters), progress


# --------------------------------------------------------------------------
# home-side: ack consumption + transaction finish


def _home_acks_and_finish(mp, ms: MemState, dram_lat_ps, dir_access_ps,
                          enabled, progress, dsv: _DirRowView,
                          acc: _DirAcc, px: ParallelCtx = IDENT):
    T = mp.n_tiles
    tiles = np.arange(T, dtype=np.int32)
    mail = ms.mail
    txn = ms.txn

    # consume ALL matching acks per home row at once (row-wise reduction;
    # each ack clears a distinct pending bit, times are max-reduced)
    match = (mail.ack_type != MSG_NONE) & txn.active[:, None] & (
        mail.ack_line == txn.line[:, None])
    any_match = match.any(axis=1)
    max_ack = jnp.where(match, mail.ack_time, 0).max(axis=1)
    got_data = (match & ((mail.ack_type == MSG_FLUSH_REP)
                         | (mail.ack_type == MSG_WB_REP))).any(axis=1)
    wb_any = (match & (mail.ack_type == MSG_WB_REP)).any(axis=1)

    # clear pending bits for acked sharers: pack match row back to words
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
                          jnp.maximum(txn.time_ps, max_ack + dir_access_ps),
                          txn.time_ps),
        data_cached=txn.data_cached | got_data,
    )
    # drop every ack cell (matched = consumed; stale = dropped)
    mail = mail.replace(ack_type=jnp.where(
        mail.ack_type != MSG_NONE, MSG_NONE, mail.ack_type))

    # ---- finish transactions whose pending set is empty ------------------
    no_pending = (txn.pending == 0).all(axis=1)
    finish = txn.active & no_pending
    is_ex = txn.mtype == MSG_EX_REQ
    is_sh = txn.mtype == MSG_SH_REQ
    is_nullify = txn.mtype == MSG_NULLIFY

    d = ms.directory
    sets = dsv.sets
    dfound, way = dsv.lookup()
    r = txn.requester
    rbit_words = jnp.zeros((T, mp.sharer_words), U32)
    rbit_words = set_bit(rbit_words, r, finish)

    # EX finish: M, owner=r, sharers={r} (`processExReqFromL2Cache` UNCACHED
    # branch after invalidations).  SH finish: add r as sharer.  MSI: entry
    # becomes SHARED ownerless (`processWbRepFromL2Cache`).  MOSI: a dirty
    # source keeps the line — M/O entries become/stay OWNED with the owner
    # retained (mosi `processWbRepFromL2Cache` M→OWNED, `restartShmemReq`).
    # The two cases are disjoint masks on the SAME entry, merged into ONE
    # _dir_update: every scatter on the directory arrays that XLA fails to
    # alias costs a whole-array copy per iteration (the [T, DS, DW, SW]
    # sharers tensor is 2 GB at 1024 tiles — see PERF.md).
    exf = finish & is_ex & dfound
    _, cur_dstate, cur_owner, cur_sharers, cur_nsh = dsv.entry(d, way)
    shf = finish & is_sh & dfound
    had = test_bit(cur_sharers, r)
    if mp.is_mosi:
        from_dirty = (cur_dstate == DIR_MODIFIED) | (cur_dstate == DIR_OWNED)
        sh_dstate = jnp.where(from_dirty, DIR_OWNED,
                              DIR_SHARED).astype(jnp.uint8)
        sh_owner = jnp.where(from_dirty, cur_owner, -1)
    else:
        sh_dstate = jnp.full(T, DIR_SHARED, jnp.uint8)
        sh_owner = jnp.full(T, -1, jnp.int32)
    fin_upd = exf | shf
    d = _dir_update(
        d, sets, way, fin_upd, px=px,
        dstate=jnp.where(exf, DIR_MODIFIED, sh_dstate).astype(jnp.uint8),
        owner=jnp.where(exf, r, sh_owner),
        sharers=jnp.where(exf[:, None], rbit_words,
                          set_bit(cur_sharers, r, shf)),
        nsharers=jnp.where(exf, 1, cur_nsh + (~had).astype(jnp.int32)),
        acc=acc, view=dsv)
    # NULLIFY finish: the entry was already replaced at allocation; nothing
    # directory-side remains (`processNullifyReq` UNCACHED branch)

    # reply to requester (dram read only if the data did not come back
    # cached via FLUSH/WB or sit in the home's flushed-data buffer —
    # `retrieveDataAndSendToL2Cache` checks `_cached_data_list` first)
    cdata_hit = txn.cdata_valid & (txn.cdata_line == txn.line)
    data_avail = txn.data_cached | cdata_hit
    need_dram = finish & ~data_avail & ~is_nullify
    rep_ready_ps = txn.time_ps + jnp.where(need_dram, dram_lat_ps, 0)
    rep_msg = jnp.where(is_ex, MSG_EX_REP, MSG_SH_REP).astype(jnp.uint8)
    rep_go = finish & ~is_nullify
    noc, rep_arrival = mem_net_send(
        mp, ms.noc, tiles, r, mp.rep_bits, rep_ready_ps, rep_go, enabled)
    # add-delta scatter: target cells are zero (the requester resets both
    # fields on consumption), so masked-off dummy writes to cell 0 add 0
    # and can never clobber a live reply
    wr = jnp.where(rep_go, r, 0)
    mail = mail.replace(
        rep_type=mail.rep_type.at[wr].add(
            jnp.where(rep_go, rep_msg, 0).astype(jnp.uint8)),
        rep_time=mail.rep_time.at[wr].add(
            jnp.where(rep_go, rep_arrival, 0)),
    )
    # clear our FWD column so stale multicasts cannot leak into the next
    # transaction (see module docstring)
    mail = mail.replace(
        fwd_type=jnp.where(finish[None, :], MSG_NONE, mail.fwd_type))

    txn = txn.replace(
        active=txn.active & ~finish,
        last_line=jnp.where(finish, txn.line, txn.last_line),
        last_done_ps=jnp.where(finish, rep_ready_ps, txn.last_done_ps),
        cdata_valid=txn.cdata_valid & ~(finish & cdata_hit),  # consumed
    )
    # MSI writes WB data through to DRAM (the entry turns SHARED clean);
    # MOSI keeps it dirty at the owner (entry turns OWNED) — DRAM is only
    # written when dirty lines are evicted/flushed
    wb_writes_dram = (jnp.zeros_like(wb_any) if mp.is_mosi else wb_any)
    counters = ms.counters.replace(
        dram_reads=ms.counters.dram_reads + (need_dram & enabled).astype(I64),
        dram_writes=ms.counters.dram_writes
        + (wb_writes_dram & enabled).astype(I64),
        dram_total_lat_ps=ms.counters.dram_total_lat_ps
        + jnp.where(need_dram & enabled, dram_lat_ps, 0),
    )
    progress = progress + jnp.sum(finish, dtype=jnp.int32) + jnp.sum(
        any_match, dtype=jnp.int32)
    return ms.replace(directory=d, txn=txn, mail=mail,
                      counters=counters, noc=noc), progress


# --------------------------------------------------------------------------
# home-side: transaction start (pop request or resume saved original)


def _home_starts(mp, ms: MemState, dram_lat_ps, dir_access_ps,
                 sync_dir_l2, sync_dir_net, enabled, progress,
                 dsv: _DirRowView, acc: _DirAcc, px: ParallelCtx = IDENT):
    T = mp.n_tiles
    tiles = np.arange(T, dtype=np.int32)
    mail = ms.mail
    txn = ms.txn

    can_start = ~txn.active
    # source 1: saved original request (after a NULLIFY completed)
    use_saved = can_start & txn.saved_valid
    # source 2: earliest pending request lane targeting this home
    r_col, r_found = _req_earliest(mail)
    use_pop = can_start & ~use_saved & r_found

    starting = use_saved | use_pop
    rtype = jnp.where(use_saved, txn.saved_type,
                      mail.req_type[r_col]).astype(jnp.uint8)
    rline = jnp.where(use_saved, txn.saved_line, mail.req_line[r_col])
    rreq = jnp.where(use_saved, txn.saved_requester, r_col)
    rtime = jnp.where(use_saved, txn.saved_time_ps,
                      mail.req_time[r_col])
    # message sync at the directory (`handleMsgFromL2Cache` entry) —
    # charged once per message: saved_time_ps already includes it, so
    # resumed requests (post-NULLIFY) must not pay it again
    rtime = rtime + jnp.where(
        use_saved, 0, jnp.where(rreq == tiles, sync_dir_l2, sync_dir_net)
    )
    # same-address serialization floor (`processNextReqFromL2Cache` time
    # update for queued same-address requests)
    rtime = jnp.where(starting & (rline == txn.last_line),
                      jnp.maximum(rtime, txn.last_done_ps), rtime)

    # consume the popped lane
    mail = _req_consume(mail, use_pop, r_col)
    txn = txn.replace(saved_valid=txn.saved_valid & ~use_saved)

    # ---- directory entry lookup / allocation -----------------------------
    d = ms.directory
    sets = dsv.sets
    dfound, way = dsv.lookup()
    tag_row, nsh_row = dsv.rows()
    # free way if no match (tags == -1)
    free_ways = tag_row == -1
    any_free = free_ways.any(axis=1)
    free_way = jnp.argmax(free_ways, axis=1).astype(jnp.int32)
    # victim: min sharers (`processDirectoryEntryAllocationReq`)
    victim_way = jnp.argmin(nsh_row, axis=1).astype(jnp.int32)
    alloc_way = jnp.where(dfound, way, jnp.where(any_free, free_way,
                                                 victim_way)).astype(jnp.int32)
    need_nullify = starting & ~dfound & ~any_free

    # victim entry contents (for the NULLIFY transaction)
    v_line, v_dstate, v_owner, v_sharers, v_nsh = dsv.entry(d, alloc_way)

    # the new entry's install (the reference's `replaceDirectoryEntry`
    # immediate swap) is merged into the immediate-finish update below —
    # one scatter on the directory arrays instead of two (each unaliased
    # scatter costs a whole-array copy; see _dir_update)
    is_new = starting & ~dfound

    # ---- NULLIFY path ----------------------------------------------------
    # save the original request; run the nullify on the victim line
    nullify_live = need_nullify & (v_dstate != DIR_UNCACHED)
    txn = txn.replace(
        saved_valid=jnp.where(nullify_live, True, txn.saved_valid),
        saved_type=jnp.where(nullify_live, rtype, txn.saved_type),
        saved_line=jnp.where(nullify_live, rline, txn.saved_line),
        saved_requester=jnp.where(nullify_live, rreq, txn.saved_requester),
        saved_time_ps=jnp.where(nullify_live, rtime, txn.saved_time_ps),
    )

    # ---- state branch for the (non-nullify) request ----------------------
    run_req = starting & ~nullify_live
    dstate = jnp.where(dfound, v_dstate, DIR_UNCACHED).astype(jnp.uint8)
    # entry state for nullify runs is the *victim's*
    eff_line = jnp.where(nullify_live, v_line, rline)
    eff_type = jnp.where(nullify_live, MSG_NULLIFY, rtype).astype(jnp.uint8)
    eff_dstate = jnp.where(nullify_live, v_dstate, dstate).astype(jnp.uint8)
    eff_time = rtime + dir_access_ps

    is_ex = eff_type == MSG_EX_REQ
    is_sh = eff_type == MSG_SH_REQ

    uncached = eff_dstate == DIR_UNCACHED
    shared = eff_dstate == DIR_SHARED
    modified = eff_dstate == DIR_MODIFIED
    owned = eff_dstate == DIR_OWNED

    # ---- directory-scheme variants (`directory_schemes/directory_entry_*.cc`,
    # `directory_type.h:3`).  full_map tracks every sharer exactly; the
    # other schemes cap the hardware sharer list at k = max_hw_sharers:
    #  - limited_no_broadcast: a (k+1)-th sharer cannot be tracked — the
    #    home invalidates one tracked sharer first (addSharer failure →
    #    getSharerToInvalidate → INV, buffered request then proceeds);
    #  - ackwise / limited_broadcast: beyond k the precise list degrades
    #    (AckWise keeps the exact *count*); invalidation sweeps become a
    #    broadcast to every tile, but the home still awaits acks only from
    #    true holders (non-holders drop the INV silently);
    #  - limitless: overflow handled in software — full_map behavior plus a
    #    software-trap penalty on accesses to overflowed entries
    #    (`[limitless] software_trap_penalty`, `carbon_sim.cfg:260-263`).
    k = mp.max_hw_sharers
    already = test_bit(v_sharers, rreq)
    if mp.dir_type == "limited_no_broadcast":
        sh_over = run_req & is_sh & (shared | owned) & (v_nsh >= k) & ~already
        # MODIFIED entry already at capacity (k=1): the owner cannot stay a
        # tracked sharer alongside the requester — its WB becomes a FLUSH
        # (data + invalidation) and the entry empties before the SH finish
        # adds the requester (addSharer failure on the M→S transition)
        sh_over_m = run_req & is_sh & modified & (v_nsh >= k) & ~already
    else:
        sh_over = jnp.zeros((T,), jnp.bool_)
        sh_over_m = jnp.zeros((T,), jnp.bool_)
    if mp.dir_type == "limitless":
        sw_mode = (v_nsh > k) | (is_sh & ~already & (v_nsh >= k)
                                 & (shared | owned))
        trap_ps = jnp.where(
            enabled & starting & dfound & sw_mode,
            cycles_to_ps(jnp.asarray(mp.limitless_trap_cycles, I64),
                         mp.dir_freq_mhz),
            0,
        )
        eff_time = eff_time + trap_ps

    # (a) immediate finishes: UNCACHED requests; MSI also serves SHARED+SH
    # straight from DRAM, while MOSI fetches cache-to-cache (below)
    imm_ex = run_req & is_ex & uncached
    if mp.is_mosi:
        imm_sh = run_req & is_sh & uncached
    else:
        imm_sh = run_req & is_sh & (uncached | shared) & ~sh_over
    imm = imm_ex | imm_sh
    rbit = set_bit(jnp.zeros((T, mp.sharer_words), U32), rreq, imm)
    cur_sh = jnp.where(imm_sh[:, None] & shared[:, None], v_sharers,
                       jnp.zeros_like(v_sharers))
    had = test_bit(cur_sh, rreq)
    # ONE merged scatter: new-entry install (UNCACHED empty, including the
    # entry swapped in under a pending NULLIFY) + immediate finishes; the
    # two overlap on is_new & imm lanes where the finish value wins.  For
    # imm-on-found lanes tags rewrite their current value (v_line == rline
    # when dfound).
    upd = is_new | imm
    d = _dir_update(
        d, sets, alloc_way, upd, px=px, acc=acc, view=dsv,
        tags=jnp.where(is_new, rline, v_line),
        dstate=jnp.where(
            imm, jnp.where(imm_ex, DIR_MODIFIED, DIR_SHARED),
            DIR_UNCACHED).astype(jnp.uint8),
        owner=jnp.where(imm_ex, rreq, -1),
        sharers=jnp.where(imm[:, None], cur_sh | rbit,
                          jnp.zeros((T, mp.sharer_words), U32)),
        nsharers=jnp.where(
            imm_ex, 1,
            jnp.where(imm, popcount(cur_sh) + (~had).astype(jnp.int32), 0)))
    # UNCACHED/SHARED reads hit DRAM unless the home's flushed-data buffer
    # holds the line (`retrieveDataAndSendToL2Cache` cached-data lookup)
    cdata_imm = txn.cdata_valid & (txn.cdata_line == eff_line) & imm
    rep_ready = eff_time + jnp.where(cdata_imm, 0, dram_lat_ps)
    txn = txn.replace(cdata_valid=txn.cdata_valid & ~cdata_imm)
    noc = ms.noc
    noc, imm_arrival = mem_net_send(
        mp, noc, tiles, rreq, mp.rep_bits, rep_ready, imm, enabled)
    # add-delta scatter (cells zero before a live write; see finish path)
    wr = jnp.where(imm, rreq, 0)
    mail = mail.replace(
        rep_type=mail.rep_type.at[wr].add(
            jnp.where(imm, jnp.where(imm_ex, MSG_EX_REP, MSG_SH_REP), 0
                      ).astype(jnp.uint8)),
        rep_time=mail.rep_time.at[wr].add(
            jnp.where(imm, imm_arrival, 0)),
    )
    txn = txn.replace(
        last_line=jnp.where(imm, eff_line, txn.last_line),
        last_done_ps=jnp.where(imm, rep_ready, txn.last_done_ps),
    )

    # (b) fan-out transactions: EX/NULLIFY on SHARED (INV multicast; in
    #     MOSI also on OWNED, where the owner gets FLUSH and the rest INV),
    #     anything on MODIFIED (FLUSH/WB to owner), and — MOSI only — SH on
    #     SHARED/OWNED fetching the data cache-to-cache from one sharer
    #     (mosi `dram_directory_cntlr.cc:430-520`)
    if mp.is_mosi:
        fan_inv = ((run_req & is_ex) | nullify_live) & (shared | owned)
        sh_fetch = run_req & is_sh & (shared | owned) & ~sh_over
    else:
        fan_inv = (run_req & is_ex & shared) | (nullify_live & shared)
        sh_fetch = jnp.zeros((T,), jnp.bool_)
    fan_owner = ((run_req | nullify_live) & modified)
    fan = fan_inv | fan_owner | sh_fetch | sh_over
    owner_bits = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                         jnp.clip(v_owner, 0, T - 1), fan_owner)
    # cache-to-cache source: the owner when the entry is OWNED (it has the
    # dirty line), else the lowest-id sharer (deterministic getOneSharer)
    fetch_src = jnp.where(owned & (v_owner >= 0), v_owner,
                          lowest_sharer(v_sharers))
    fetch_bits = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                         jnp.clip(fetch_src, 0, T - 1),
                         sh_fetch & (fetch_src >= 0))
    pending = jnp.where(
        fan_inv[:, None], v_sharers,
        jnp.where(sh_fetch[:, None], fetch_bits, owner_bits))
    fwd_msg = jnp.where(
        fan_inv, MSG_INV_REQ,
        jnp.where(is_sh, MSG_WB_REQ, MSG_FLUSH_REQ)).astype(jnp.uint8)

    if mp.dir_type == "limited_no_broadcast":
        # victim sharer to evict so the requester fits in the hw list:
        # lowest non-owner sharer (the owner holds dirty data); when the
        # owner is the only sharer, it is flushed instead (data + invalidate)
        owner_word = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                             jnp.clip(v_owner, 0, T - 1),
                             owned & (v_owner >= 0))
        victim0 = lowest_sharer(v_sharers & ~owner_word)
        victim_is_owner = sh_over & (victim0 < 0)
        victim = jnp.where(victim0 >= 0, victim0,
                           jnp.clip(v_owner, 0, T - 1)).astype(jnp.int32)
        victim_bits = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                              victim, sh_over)
        # drop the victim from the entry now — its INV/FLUSH ack is consumed
        # by this transaction, not the eviction path (one txn per home)
        d = _dir_update(
            d, sets, alloc_way, sh_over, px=px, acc=acc, view=dsv,
            sharers=v_sharers & ~victim_bits,
            nsharers=v_nsh - 1,
            owner=jnp.where(victim_is_owner, -1, v_owner),
            dstate=jnp.where(victim_is_owner, DIR_SHARED,
                             eff_dstate).astype(jnp.uint8))
        # acks awaited: the victim, plus the data-supplying owner (MOSI
        # OWNED entries fetch cache-to-cache alongside the invalidation)
        ow_pend = set_bit(jnp.zeros((T, mp.sharer_words), U32),
                          jnp.clip(v_owner, 0, T - 1),
                          sh_over & owned & ~victim_is_owner & (v_owner >= 0))
        pending = jnp.where(sh_over[:, None], victim_bits | ow_pend, pending)
        fwd_msg = jnp.where(sh_over, MSG_INV_REQ, fwd_msg).astype(jnp.uint8)
        # M→S at capacity: FLUSH the owner instead of WB and empty the
        # entry now (the SH finish then installs {requester} alone)
        fwd_msg = jnp.where(sh_over_m, MSG_FLUSH_REQ, fwd_msg).astype(
            jnp.uint8)
        d = _dir_update(
            d, sets, alloc_way, sh_over_m, px=px, acc=acc, view=dsv,
            sharers=jnp.zeros((T, mp.sharer_words), U32),
            nsharers=jnp.zeros(T, jnp.int32),
            owner=jnp.full(T, -1, jnp.int32),
            dstate=jnp.full(T, DIR_UNCACHED, jnp.uint8))

    txn = txn.replace(
        active=txn.active | fan,
        mtype=jnp.where(fan, eff_type, txn.mtype).astype(jnp.uint8),
        line=jnp.where(fan, eff_line, txn.line),
        requester=jnp.where(fan, rreq, txn.requester),
        time_ps=jnp.where(fan, eff_time, txn.time_ps),
        pending=jnp.where(fan[:, None], pending, txn.pending),
        data_cached=jnp.where(fan, False, txn.data_cached),
    )

    # dense multicast into the FWD matrix: [sharer, home]
    targets = unpack_sharers(pending, T)          # [home, sharer]
    send = fan[:, None] & targets                 # [home, sharer]
    send_t = send.T                               # [sharer, home]
    msg_hs = jnp.broadcast_to(fwd_msg[:, None], (T, T))  # [home, sharer]
    if mp.is_mosi:
        # one target of an invalidation sweep supplies the data by FLUSH
        # (`INV_FLUSH_COMBINED_REQ`, mosi `dram_directory_cntlr.cc:385-395`):
        # the owner when the entry is OWNED (dirty), else one sharer for an
        # EX on SHARED — the EX then completes cache-to-cache with no DRAM
        # read.  NULLIFY on SHARED keeps plain INVs (data is clean in DRAM).
        flush_pick = jnp.where(owned & (v_owner >= 0), v_owner,
                               lowest_sharer(v_sharers))
        pick_col = tiles[None, :] == flush_pick[:, None]  # [home, sharer]
        pick_rows = (fan_inv & (owned | (run_req & is_ex & shared)))
        msg_hs = jnp.where(
            pick_rows[:, None] & pick_col,
            jnp.uint8(MSG_FLUSH_REQ), msg_hs)
    if mp.dir_type == "limited_no_broadcast" and mp.is_mosi:
        # data supplier for the displaced SH: the victim FLUSHes when it
        # must both leave and supply (clean c2c pick, or the owner-is-victim
        # corner); otherwise the owner WBs alongside the victim's INV
        victim_col = tiles[None, :] == victim[:, None]
        owner_col = tiles[None, :] == jnp.clip(v_owner, 0, T - 1)[:, None]
        msg_hs = jnp.where(
            (sh_over & (shared | victim_is_owner))[:, None] & victim_col,
            jnp.uint8(MSG_FLUSH_REQ), msg_hs)
        msg_hs = jnp.where(
            (sh_over & owned & ~victim_is_owner)[:, None] & owner_col,
            jnp.uint8(MSG_WB_REQ), msg_hs)
    if mp.dir_type in ("ackwise", "limited_broadcast"):
        # overflowed entries lose sharer precision: the INV sweep goes to
        # every tile (`directory_entry_ackwise.cc` / `..._limited_broadcast`);
        # `pending` (acks awaited) stays the true holder set — non-holders
        # drop the INV silently, exactly the sharer-side `silent` path
        over_bc = fan_inv & (v_nsh > k)
        send = send | over_bc[:, None]
        send_t = send.T
    noc, arrive = mem_net_fanout(
        mp, noc, send, mp.req_bits, eff_time, enabled)  # [home, sharer]
    mail = mail.replace(
        fwd_type=jnp.where(send_t, msg_hs.T, mail.fwd_type),
        fwd_line=jnp.where(send_t, eff_line[None, :], mail.fwd_line),
        fwd_time=jnp.where(send_t, arrive.T, mail.fwd_time),
    )

    counters = ms.counters.replace(
        dir_accesses=ms.counters.dir_accesses
        + (starting & enabled).astype(I64),
        dram_reads=ms.counters.dram_reads
        + (imm & ~cdata_imm & enabled).astype(I64),
        dram_total_lat_ps=ms.counters.dram_total_lat_ps
        + jnp.where(imm & ~cdata_imm & enabled, dram_lat_ps, 0),
    )
    if mp.dir_type in ("ackwise", "limited_broadcast"):
        counters = counters.replace(
            dir_broadcasts=counters.dir_broadcasts
            + (over_bc & enabled).astype(I64))
    progress = progress + jnp.sum(starting, dtype=jnp.int32)
    return ms.replace(directory=d, txn=txn, mail=mail,
                      counters=counters, noc=noc), progress


# --------------------------------------------------------------------------
# requester-side reply fill (`handleMsgFromDramDirectory` EX_REP/SH_REP +
# `insertCacheLineInHierarchy`)


def _requester_fill(mp, ms: MemState, rec: RecView, clock_ps, fmhz, enabled,
                    progress, sync_l2_net, px: ParallelCtx = IDENT):
    T = mp.n_tiles
    tiles = np.arange(T, dtype=np.int32)
    mail = ms.mail

    def ccyc(n):
        ps = cycles_to_ps(jnp.asarray(n, I64), fmhz)
        return jnp.where(enabled, ps, 0)

    have_rep = (ms.req.phase == PHASE_WAIT_REPLY) & (mail.rep_type != MSG_NONE)
    line = ms.req.line
    comp_l1i = ms.req.component == MOD_L1I

    # block-local row gathers at the filled line (+ the pre-update
    # miss-type test bits — the victim's own bitmap write is folded back
    # in below via the bucket-collision correction)
    line_l = px.lo(line)
    l2_mod_l = px.lo_const(mp.l2.sets_mod)
    # The L1 rows stay block-local: the fill's L1 way and its victim are
    # the L1 rows' to say and nothing replicated reads them.  So the
    # victim's line is known before the L2 store is read, and the store's
    # ONE reader fetches the filled line's set row and the candidate
    # victim's (cache_array.gather_row_pair; only the first travels).
    comp_l = px.lo(comp_l1i)
    l1i_r = ca.gather_row(ms.l1i, line_l, px.lo_const(mp.l1i.sets_mod),
                          nonneg=True)
    l1d_r = ca.gather_row(ms.l1d, line_l, px.lo_const(mp.l1d.sets_mod),
                          nonneg=True)
    l1i_way, l1d_way, ev_valid_l, ev_line_l = _l1_fill_ways(
        mp, px, l1i_r, l1d_r, comp_l)
    l2_row_l, ev_row_l = ca.gather_row_pair(ms.l2, line_l, ev_line_l,
                                            l2_mod_l)
    if mp.l2.track_miss_types:
        mt_bits_l = (_mt_test(ms.mt, MT_EVICTED, line_l),
                     _mt_test(ms.mt, MT_INVALIDATED, line_l))
    else:
        mt_bits_l = ()
    if mp.l2.track_line_utilization:
        mt_bits_l = mt_bits_l + (_util_row_local(
            ms.l2_util, line_l, px.lo_const(mp.l2.sets_mod)),)
    (l2_r,), mt_bits = _rows_exchange(px, (l2_row_l,), mt_bits_l)
    if mp.l2.track_line_utilization:
        lu_row, mt_bits = mt_bits[-1], mt_bits[:-1]

    # L2 victim for the fill; a valid victim emits an eviction message that
    # needs its (home, us) EVICT cell free — else stall this iteration
    way, v_valid, v_line, v_state = ca.row_pick_victim(
        l2_r, mp.l2.replacement, mp.l2.ways_limit)
    v_home_all = jnp.asarray(mp.mc_tiles, jnp.int32)[
        (v_line % len(mp.mc_tiles)).astype(jnp.int32)]
    need_evict = have_rep & v_valid
    evict_busy = mail.evict_type[v_home_all, tiles] != MSG_NONE
    fill = have_rep & ~(need_evict & evict_busy)
    evict_go = need_evict & fill

    new_state = jnp.where(mail.rep_type == MSG_EX_REP, MODIFIED, SHARED)
    l2_new_l = px.lo(ca.row_insert(l2_r, line, way, new_state, fill))
    l2 = ca.scatter_row(ms.l2, l2_new_l)
    if mp.l2.track_line_utilization:
        # the victim leaves the L2 (classify); the filled line's counter
        # restarts with the miss access itself as its first use
        en = jnp.asarray(enabled, bool)
        lu_cur = jnp.take_along_axis(lu_row, way[:, None], axis=1)[:, 0]
        init = jnp.where(ms.req.is_write, jnp.uint32(1) << 16,
                         jnp.uint32(1))
        ms = ms.replace(
            l2_util=_util_scatter(
                px, ms.l2_util, line, mp.l2.sets_mod, way, lu_cur,
                jnp.where(fill & en, init, lu_cur)),
            counters=_util_classify(ms.counters, lu_cur, evict_go,
                                    enabled))
    sets = nn_mod(line, jnp.asarray(mp.l2.sets_mod)).astype(jnp.int32)
    l2_cloc = px.entry_set(
        ms.l2_cloc, *px.lo((
            sets, way, fill,
            jnp.where(comp_l1i, MOD_L1I, MOD_L1D).astype(jnp.uint8))))

    # eviction message (FLUSH_REP if dirty — MODIFIED, or OWNED in MOSI —
    # else INV_REP; `insertCacheLine`, `l2_cache_cntlr.cc:75-116`, mosi
    # `l2_cache_cntlr.cc:116-138`)
    v_dirty = (v_state == MODIFIED) | (v_state == OWNED)
    e_msg = jnp.where(v_dirty, MSG_FLUSH_REP,
                      MSG_INV_REP).astype(jnp.uint8)
    e_bits = jnp.where(v_dirty, mp.rep_bits, mp.req_bits)
    # fill timing: reply arrival + net sync + L2 insert (data+tags), then
    # second L1 pass: L2 sync + L1 data+tags (`processMemOpFromCore` loop)
    fill_l2_ps = mail.rep_time + sync_l2_net + ccyc(mp.l2.data_and_tags_cycles)
    l1_dat = jnp.where(comp_l1i, ccyc(mp.l1i.data_and_tags_cycles),
                       ccyc(mp.l1d.data_and_tags_cycles))
    done_ps = fill_l2_ps + l1_dat

    noc, e_arrival = mem_net_send(
        mp, ms.noc, tiles, v_home_all, e_bits, fill_l2_ps, evict_go,
        enabled)
    wh = jnp.where(evict_go, v_home_all, 0)
    mail = mail.replace(
        evict_type=mail.evict_type.at[wh, tiles].set(
            jnp.where(evict_go, e_msg, mail.evict_type[wh, tiles])),
        evict_line=mail.evict_line.at[wh, tiles].set(
            jnp.where(evict_go, v_line, mail.evict_line[wh, tiles])),
        evict_time=mail.evict_time.at[wh, tiles].set(
            jnp.where(evict_go, e_arrival,
                      mail.evict_time[wh, tiles])),
        # reset BOTH fields so home-side add-delta reply writes stay exact
        rep_type=jnp.where(fill, MSG_NONE, mail.rep_type),
        rep_time=jnp.where(fill, 0, mail.rep_time),
    )

    # L1 fill, block-local (rows and ways picked above)
    l1_state = new_state  # L1 gets the L2 state (`insertCacheLineInL1`)
    fill_l, l1_state_l = px.lo((fill, l1_state))
    l1i = ca.scatter_row(
        ms.l1i, ca.row_insert(l1i_r, line_l, l1i_way, l1_state_l,
                              fill_l & comp_l))
    l1d = ca.scatter_row(
        ms.l1d, ca.row_insert(l1d_r, line_l, l1d_way, l1_state_l,
                              fill_l & ~comp_l))
    # clear cached-loc of L1 victims in L2 (block-local RMW chain).  The
    # victim is looked up in the L2 as this phase's fill leaves it: its
    # row as gathered, or the filled row where both lines share a set
    same_set = (ev_row_l.sets == l2_new_l.sets)[:, None]
    ev_hit_l, ev_way_l, _ = ca.row_lookup(
        ev_row_l.replace(tag=jnp.where(same_set, l2_new_l.tag, ev_row_l.tag),
                         st=jnp.where(same_set, l2_new_l.st, ev_row_l.st)),
        ev_line_l)
    l2_cloc = px.entry_set(l2_cloc, ev_row_l.sets, ev_way_l,
                           fill_l & ev_valid_l & ev_hit_l, 0)

    if mp.l2.track_miss_types:
        mt = ms.mt
        # victim -> evicted set (`insertCacheLine` eviction branch)
        mt = _mt_update(mt, MT_EVICTED, px.lo(v_line), px.lo(evict_go), True)
        # inserted line: clearMissTypeTrackingSets erases from exactly
        # ONE set (evicted elif invalidated elif fetched), then the
        # fetched set gains the line.  The tests must see the victim's
        # just-applied EVICTED bit; the exchanged pre-write bit is
        # corrected for a same-bucket victim write instead of re-reading.
        e_in = mt_bits[0] | (evict_go & _mt_same_bucket(v_line, line))
        i_in = mt_bits[1]
        mt = _mt_update(mt, MT_EVICTED, line_l, px.lo(fill & e_in), False)
        mt = _mt_update(mt, MT_INVALIDATED, line_l,
                        px.lo(fill & ~e_in & i_in), False)
        mt = _mt_update(mt, MT_FETCHED, line_l, px.lo(fill), True)
        ms = ms.replace(mt=mt)

    req = ms.req.replace(
        phase=jnp.where(fill, PHASE_IDLE, ms.req.phase),
        slot=jnp.where(fill, ms.req.slot + 1, ms.req.slot),
        acc_ps=ms.req.acc_ps + jnp.where(fill, done_ps - clock_ps, 0),
        slot_lat_ps=jnp.where(
            (fill[:, None]
             & (np.arange(3)[None, :] == ms.req.slot[:, None])),
            (done_ps - clock_ps)[:, None], ms.req.slot_lat_ps),
    )
    ms = ms.replace(l1i=l1i, l1d=l1d, l2=l2, l2_cloc=l2_cloc, mail=mail,
                    req=req, noc=noc)
    # functional effect of the completed slot
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


def line_census(ms: MemState, mp: MemParams, lines) -> dict:
    """Abstract per-line coherence view of a (fetched) MemState.

    Pure host-side numpy over the packed arrays — the model checker
    compares this against the golden interpreter's abstract state after
    replaying the same access sequence.  Returns, per line:
    ``{"l1d": (state per tile), "l2": (state per tile),
       "dir": (dstate, owner, frozenset(sharers)) | None,
       "cdata": bool}`` (states are cache_array constants, 0 = absent).
    """
    l1d_tag = np.asarray(ms.l1d.tags)
    l1d_st = np.asarray(ms.l1d.state)
    l2_tag = np.asarray(ms.l2.tags)
    l2_st = np.asarray(ms.l2.state)
    entry = np.asarray(row_landing.entry_int64(ms.directory.entry))
    sharers = np.asarray(ms.directory.sharers)
    cdata_line = np.asarray(ms.txn.cdata_line)
    cdata_valid = np.asarray(ms.txn.cdata_valid)
    T = mp.n_tiles
    sw = mp.sharer_words

    def cache_state(tag, st, line):
        out = []
        for t in range(T):
            s = line % tag.shape[1]
            hit = tag[t, s, :] == line
            out.append(int(st[t, s, hit.argmax()]) if hit.any() else 0)
        return tuple(out)

    out = {}
    for line in lines:
        home = mp.mc_tiles[line % len(mp.mc_tiles)]
        dset = line % mp.dir_sets
        dent = None
        for w in range(mp.dir_ways):
            word = int(entry[home, dset, w])
            if (word & ((1 << DIR_TAG_BITS) - 1)) - 1 != line:
                continue
            dstate = (word >> DIR_STATE_SHIFT) & 7
            owner = ((word >> DIR_OWNER_SHIFT) & ((1 << DIR_ID_BITS) - 1)) - 1
            bits = sharers[home, dset, w * sw:(w + 1) * sw]
            shset = frozenset(
                i * 32 + b for i in range(sw) for b in range(32)
                if (int(bits[i]) >> b) & 1)
            dent = (int(dstate), int(owner), shset)
            break
        out[line] = {
            "l1d": cache_state(l1d_tag, l1d_st, line),
            "l2": cache_state(l2_tag, l2_st, line),
            "dir": dent,
            "cdata": bool(
                cdata_valid[home] and int(cdata_line[home]) == line),
        }
    return out
