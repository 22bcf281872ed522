"""Vectorized set-associative cache arrays (packed representation).

The reference `Cache` (`common/tile/memory_subsystem/cache/cache.h:26-135`)
is a per-tile C++ object: tag store + state + replacement policy, accessed
one address at a time under a lock.  Here a cache *level* across all tiles
is ONE dense tensor

    meta int64[T, S, W] = line(32 bits, signed; -1 = free) << 16
                        | state(8) << 8 | lru(8)

and every operation is a masked gather/scatter over the tile axis.  The
three logical fields live in one word so a lookup is a single gather and
an insert a single scatter — the memory engine is op-count-bound on TPU
(hundreds of small kernels per subquantum iteration), so each saved
gather/scatter kernel is wall-clock (see PERF.md "Engine cost model").

Two API levels:
 - element ops (`lookup`/`touch_lru`/`insert_at`/...) — one gather or
   scatter each, used by the shared-L2 engine and tests;
 - row ops (`gather_row`/`scatter_row` + `row_*`) — fetch each lane's set
   row ONCE per engine phase, do every lookup/victim/insert decision as
   [T, W] elementwise math, write the row back once.  The private-L2
   engine phases use these.

Set index = line % num_sets, matching the reference `CacheHashFn` modulo
mapping (`cache/cache_hash_fn.cc`).  Replacement is LRU with
invalid-way-first victim selection (`cache/lru_replacement_policy.cc`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from graphite_tpu.intmath import nn_mod

# CacheState (`common/tile/memory_subsystem/cache_state.h`).
INVALID = 0
SHARED = 1
MODIFIED = 2
EXCLUSIVE = 3   # MESI protocols
OWNED = 4       # MOSI protocols

# readable: S/E/M/O; writable: E/M (`cache_state.h` readable()/writable()).
_READABLE = (1 << SHARED) | (1 << MODIFIED) | (1 << EXCLUSIVE) | (1 << OWNED)
_WRITABLE = (1 << MODIFIED) | (1 << EXCLUSIVE)

I64 = jnp.int64


def state_readable(state: jax.Array) -> jax.Array:
    return ((_READABLE >> state.astype(jnp.int32)) & 1).astype(jnp.bool_)


def state_writable(state: jax.Array) -> jax.Array:
    return ((_WRITABLE >> state.astype(jnp.int32)) & 1).astype(jnp.bool_)


def _pack(line, state, lru):
    return ((jnp.asarray(line).astype(I64) << 16)
            | (jnp.asarray(state).astype(I64) << 8)
            | jnp.asarray(lru).astype(I64))


def _unpack(meta):
    # arithmetic >> keeps line == -1 working (sign-extends through int32)
    return (
        (meta >> 16).astype(jnp.int32),
        ((meta >> 8) & 0xFF).astype(jnp.uint8),
        (meta & 0xFF).astype(jnp.int32),
    )


@struct.dataclass
class CacheArrays:
    meta: jax.Array   # int64[T, S, W]

    @property
    def num_sets(self) -> int:
        return self.meta.shape[1]

    @property
    def num_ways(self) -> int:
        return self.meta.shape[2]

    # host-side convenience views (statistics sampling, tests)
    @property
    def tags(self) -> jax.Array:
        return (self.meta >> 16).astype(jnp.int32)

    @property
    def state(self) -> jax.Array:
        return ((self.meta >> 8) & 0xFF).astype(jnp.uint8)

    @property
    def lru(self) -> jax.Array:
        return (self.meta & 0xFF).astype(jnp.uint8)


def make_cache(n_tiles: int, num_sets: int, num_ways: int) -> CacheArrays:
    shape = (n_tiles, num_sets, num_ways)
    # lru ranks start as a strict permutation 0..W-1 per set; touches
    # preserve the permutation (bump-below-rank + zero the way)
    lru0 = jnp.broadcast_to(jnp.arange(num_ways, dtype=I64), shape)
    return CacheArrays(meta=(jnp.asarray(-1, I64) << 16) | lru0)


# ---------------------------------------------------------------------------
# row-level API: one gather per phase, [T, W] elementwise math, one scatter


@struct.dataclass
class CacheRow:
    """One set row per lane: each lane's (line % S) row of a cache level."""

    tag: jax.Array   # int32[T, W]
    st: jax.Array    # int32[T, W]  (int32 for arithmetic convenience)
    lru: jax.Array   # int32[T, W]
    sets: jax.Array  # int32[T]
    meta0: jax.Array  # int64[T, W] packed words as gathered (delta base)


def gather_row(cache: CacheArrays, line: jax.Array,
               sets_mod=None, *, nonneg: bool = False) -> CacheRow:
    """`sets_mod`: per-tile set count (int or int32[T]) for heterogeneous
    geometries; defaults to the array's (max) set dimension.

    `nonneg=True`: the caller guarantees `line >= 0` (record-derived and
    mailbox-carried lines), so the set index uses the one-equation
    `intmath.nn_mod` instead of the floor-mod fixup chain — bit-identical
    there.  Victim lines read off an invalid way can be -1 and must keep
    the default."""
    T = cache.meta.shape[0]
    tiles = np.arange(T, dtype=np.int32)
    mod = cache.num_sets if sets_mod is None else jnp.asarray(sets_mod)
    sets = (nn_mod(line, mod) if nonneg else line % mod).astype(jnp.int32)
    meta = cache.meta[tiles, sets]                 # [T, W] — ONE gather
    tag, st, lru = _unpack(meta)
    return CacheRow(tag=tag, st=st.astype(jnp.int32), lru=lru, sets=sets,
                    meta0=meta)


def gather_row_pair(cache: CacheArrays, line: jax.Array, line2: jax.Array,
                    sets_mod=None) -> "tuple[CacheRow, CacheRow]":
    """`gather_row(cache, line, nonneg=True)` plus the same lanes' rows
    at a second line (`line2` may be -1: the floor-mod path) — ONE
    gather, [T, 2, W].

    For a phase that consults a second set of the store it scatters
    into.  A second `gather_row` there is a reader of the carried store
    that is no data-dependence predecessor of the phase's `scatter_row`:
    XLA cannot order it before the in-place write and copies the whole
    store every iteration instead (see `scatter_row`).  Both rows of one
    gather precede the scatter through the first row's `meta0`; the
    second row is read-only (never scatter it: the two rows of a lane
    may be the same set).

    Keep the [T, 2, W] result.  Laid [2T, W] (lane-major halves) the TPU
    compiler drops three small relayout copies a phase, and on the v5e
    the 1024-tile program is 7.6% slower a run and the 64-tile one 0.7%
    (PERF.md, PR 32): measured, not understood."""
    T = cache.meta.shape[0]
    tiles = np.arange(T, dtype=np.int32)[:, None]
    mod = cache.num_sets if sets_mod is None else jnp.asarray(sets_mod)
    sets = jnp.stack([nn_mod(line, mod), line2 % mod],
                     axis=1).astype(jnp.int32)
    meta = cache.meta[tiles, sets]                 # [T, 2, W] — ONE gather
    return (row_from_meta(meta[:, 0], sets[:, 0]),
            row_from_meta(meta[:, 1], sets[:, 1]))


def row_from_meta(meta: jax.Array, sets: jax.Array) -> CacheRow:
    """Rebuild a CacheRow from its packed (meta, sets) pair — the compact
    form a row travels in through the shard_map phase exchange (pack ∘
    unpack is the identity, so the rebuilt row is bit-equal to the
    gather_row original)."""
    tag, st, lru = _unpack(meta)
    return CacheRow(tag=tag, st=st.astype(jnp.int32), lru=lru, sets=sets,
                    meta0=meta)


def scatter_row(cache: CacheArrays, row: CacheRow) -> CacheArrays:
    """Write each lane's row back — ONE scatter, no masking: the row_*
    ops are themselves masked per lane, so an untouched lane's row packs
    back to exactly the live value.  Written add-a-delta against the
    gathered words (per-lane rows are distinct, so the add is exact):
    the scatter is then the meta array's only remaining use and XLA
    updates the loop-carried buffer in place instead of copying it."""
    T = cache.meta.shape[0]
    tiles = np.arange(T, dtype=np.int32)
    new_meta = _pack(row.tag, row.st, row.lru)
    return cache.replace(meta=cache.meta.at[tiles, row.sets].add(
        new_meta - row.meta0, unique_indices=True, indices_are_sorted=True))


def row_lookup(row: CacheRow, line: jax.Array):
    """(hit bool[T], way int32[T], state uint8[T]) within the row."""
    way_hits = (row.tag == line[:, None]) & (row.st != INVALID)
    hit = way_hits.any(axis=1)
    way = jnp.argmax(way_hits, axis=1).astype(jnp.int32)
    st = jnp.where(
        hit, jnp.take_along_axis(row.st, way[:, None], axis=1)[:, 0], INVALID
    ).astype(jnp.uint8)
    return hit, way, st


def row_touch(row: CacheRow, way: jax.Array, mask: jax.Array) -> CacheRow:
    """Make `way` the MRU of its row where mask (ranks below it shift up)."""
    rank = jnp.take_along_axis(row.lru, way[:, None], axis=1)
    bumped = row.lru + (row.lru < rank).astype(jnp.int32)
    onehot = np.arange(row.lru.shape[1])[None, :] == way[:, None]
    new_lru = jnp.where(onehot, 0, bumped)
    return row.replace(lru=jnp.where(mask[:, None], new_lru, row.lru))


def row_set_state(row: CacheRow, way: jax.Array, new_state,
                  mask: jax.Array) -> CacheRow:
    onehot = np.arange(row.st.shape[1])[None, :] == way[:, None]
    sel = onehot & mask[:, None]
    return row.replace(st=jnp.where(
        sel, jnp.broadcast_to(jnp.asarray(new_state, jnp.int32)[..., None],
                              row.st.shape), row.st))


def row_invalidate(row: CacheRow, line: jax.Array,
                   mask: jax.Array) -> CacheRow:
    hit, way, _ = row_lookup(row, line)
    return row_set_state(row, way, INVALID, mask & hit)


def row_pick_victim(row: CacheRow, policy: str = "lru", ways=None):
    """(way, victim_valid, victim_line, victim_state).

    lru (`lru_replacement_policy.cc`): first invalid way, else the
    max-rank way.  round_robin (`round_robin_replacement_policy.cc`): the
    set's rotating index regardless of validity — the rank permutation
    doubles as the rotation state (ranks only move on insertion, so the
    max-rank way IS the current index and inserting rotates it), and
    victim_valid reflects whether the chosen way held a live line.

    `ways` (int32[T] or None): per-tile way count for heterogeneous
    geometries — padded ways beyond it are never picked (their initial
    ranks sit above every usable rank and are masked here; touches never
    move them)."""
    usable = None
    if ways is not None:
        usable = (np.arange(row.lru.shape[1], dtype=np.int32)[None, :]
                  < jnp.asarray(ways)[:, None])
    lru_eff = row.lru if usable is None else jnp.where(usable, row.lru, -1)
    lru_way = jnp.argmax(lru_eff, axis=1)
    if policy == "round_robin":
        way = lru_way.astype(jnp.int32)
        victim_state = jnp.take_along_axis(
            row.st, way[:, None], axis=1)[:, 0].astype(jnp.uint8)
        victim_valid = victim_state != INVALID
    else:
        inv = row.st == INVALID
        if usable is not None:
            inv = inv & usable
        any_inv = inv.any(axis=1)
        inv_way = jnp.argmax(inv, axis=1)
        way = jnp.where(any_inv, inv_way, lru_way).astype(jnp.int32)
        victim_state = jnp.take_along_axis(
            row.st, way[:, None], axis=1)[:, 0].astype(jnp.uint8)
        victim_valid = ~any_inv
    victim_line = jnp.take_along_axis(row.tag, way[:, None], axis=1)[:, 0]
    return way, victim_valid, victim_line, victim_state


def row_insert(row: CacheRow, line: jax.Array, way: jax.Array, new_state,
               mask: jax.Array) -> CacheRow:
    """Install `line` at `way` with `new_state` where mask, making it MRU."""
    onehot = np.arange(row.tag.shape[1])[None, :] == way[:, None]
    sel = onehot & mask[:, None]
    out = row.replace(
        tag=jnp.where(sel, line[:, None], row.tag),
        st=jnp.where(
            sel,
            jnp.broadcast_to(jnp.asarray(new_state, jnp.int32)[..., None],
                             row.st.shape),
            row.st),
    )
    return row_touch(out, way, mask)


# ---------------------------------------------------------------------------
# element-level API (one gather/scatter per call) — shared-L2 engine, tests


def lookup(cache: CacheArrays, line: jax.Array, sets_mod=None):
    """Per-lane lookup: (hit bool[T], way int32[T], state uint8[T]).

    `Cache::getCacheLineInfo` (`cache.h:92`) vectorized: way is valid only
    where hit; state is INVALID where miss.
    """
    row = gather_row(cache, line, sets_mod)
    return row_lookup(row, line)


def touch_lru(cache: CacheArrays, line: jax.Array, way: jax.Array,
              mask: jax.Array, sets_mod=None) -> CacheArrays:
    """Make `way` the MRU of its set where mask (LRU ranks shift up)."""
    row = gather_row(cache, line, sets_mod)
    return scatter_row(cache, row_touch(row, way, mask))


def set_state(cache: CacheArrays, line: jax.Array, way: jax.Array,
              new_state: jax.Array, mask: jax.Array,
              sets_mod=None) -> CacheArrays:
    """Set the state of (line, way) where mask (`Cache::setCacheLineInfo`)."""
    row = gather_row(cache, line, sets_mod)
    return scatter_row(cache, row_set_state(row, way, new_state, mask))


def invalidate(cache: CacheArrays, line: jax.Array,
               mask: jax.Array, sets_mod=None) -> CacheArrays:
    """Invalidate `line` where mask & present (`Cache::invalidateCacheLine`)."""
    row = gather_row(cache, line, sets_mod)
    hit, way, _ = row_lookup(row, line)
    m = mask & hit
    return scatter_row(cache, row_set_state(row, way, INVALID, m))


def pick_victim(cache: CacheArrays, line: jax.Array, policy: str = "lru",
                sets_mod=None, ways=None):
    """Victim way per lane (see row_pick_victim for policy semantics).

    Returns (way int32[T], victim_valid bool[T], victim_line int32[T],
    victim_state uint8[T]).
    """
    row = gather_row(cache, line, sets_mod)
    return row_pick_victim(row, policy, ways)


def insert_at(cache: CacheArrays, line: jax.Array, way: jax.Array,
              new_state: jax.Array, mask: jax.Array,
              sets_mod=None) -> CacheArrays:
    """Install `line` in `way` with `new_state` where mask, making it MRU.

    `Cache::insertCacheLine` (`cache.h:90`) minus the eviction message
    (the caller handles the victim it got from pick_victim).
    """
    row = gather_row(cache, line, sets_mod)
    return scatter_row(cache, row_insert(row, line, way, new_state, mask))
