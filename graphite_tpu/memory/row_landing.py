"""Land sparse updates on a resident store, touching only the tiles they
name: the Pallas TPU kernels behind `engine_shl2._dir_apply_rows`
(`land_rows`: row deltas, one row a slab), `engine.dir_stage_flush`
(`flush_staged`: the private-L2 staging table) and `engine._entry_land`
(`apply_entry`, at the end: the entry store's TWO forms, int64 | u32 words,
chosen by `state.entry_as_words`) - each with its XLA form and the choice,
made from the lowering target and the operands' shapes alone.  The two
private-L2 entry points take a campaign's sim axis as more lanes
(`_fold_sims`: `[B, T, ...]` is `[B * T, ...]`, and the choice is made of
the folded shape); `land_rows` under a sim axis is still the scatter.

An XLA scatter-add of 1,024 rows of 1 KB onto the `u32[1048576, 256]`
sharers store is in place and still costs what streaming the 1.07 GB
store costs: 3.5 ms on a v5e, 3.2 ms for 128 rows, 0.46 ms on a store an
eighth the size (`_hand/landing39.py`; PERF.md §6, PR 39).  This kernel
leaves the store in HBM, aliased to its output, and moves the plan's rows
alone: 0.085 ms for the 1,024 rows, 0.012 ms for 128, whatever the store.
HBM is tiled (8, 128) and a DMA cannot cut a tile, so a row travels with
its aligned GROUP of 8 rows: one group DMA in per plan row, the row's
delta added in VMEM, one group DMA back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from graphite_tpu.intmath import nn_div, nn_mod

GROUP = 8   # rows of one (8, 128) HBM tile: the unit a DMA can move

# plan rows landed per grid step: the VMEM scratch is [step * GROUP, W]
# (4 MB at the 1,024-tile cell's shape), under the v5e's 16 MB scoped limit;
# 256, 512 and 1,024 cost the same on the chip (0.084-0.087 ms a landing)
ROWS_PER_STEP = 512


def _landing_kernel(rows_ref, store_ref, delta_ref, out_ref, buf, sem_in,
                    sem_out):
    # (x64 is on package-wide: a Python int would trace as int64, which
    # Mosaic has no type for)
    n = jnp.int32(delta_ref.shape[0])
    base = pl.program_id(0) * n
    zero = jnp.int32(0)

    def in_group(r):
        # (lax.rem, not `%`: jnp's remainder carries its constant as int64)
        return jax.lax.rem(rows_ref[base + r], jnp.int32(GROUP))

    def group(r):
        return pl.ds(pl.multiple_of(rows_ref[base + r] - in_group(r), GROUP),
                     GROUP)

    def slot(r):
        return buf.at[pl.ds(pl.multiple_of(r * GROUP, GROUP), GROUP)]

    def fetch(r):
        return pltpu.make_async_copy(store_ref.at[group(r)], slot(r), sem_in)

    def write(r):
        return pltpu.make_async_copy(slot(r), out_ref.at[group(r)], sem_out)

    def loop(body):
        jax.lax.fori_loop(zero, n, lambda r, c: (body(r), c)[1], zero)

    # all of a step's groups in flight on ONE semaphore, then all waited
    # for: DMAs may finish in any order, so no row is touched before the
    # last wait
    loop(lambda r: fetch(r).start())
    loop(lambda r: fetch(r).wait())

    def add(r):
        at = pl.ds(r * GROUP + in_group(r), 1)
        buf[at, :] = buf[at, :] + delta_ref[pl.ds(r, 1), :]

    loop(add)
    loop(lambda r: write(r).start())
    loop(lambda r: write(r).wait())


def _step(n_rows, rows_per_step):
    """Plan rows a grid step: all of them, or a block of whole tiles."""
    if n_rows <= rows_per_step:
        return n_rows
    return rows_per_step if n_rows % rows_per_step == 0 else 0


def can_land(n_rows, slab, width) -> bool:
    """Whether `land_rows` takes a plan of `n_rows` rows of `width` words,
    one in each `slab` consecutive rows of the store: lane-aligned rows,
    and slabs of whole groups, so that no two plan rows share a group."""
    return (width % 128 == 0 and slab % GROUP == 0
            and _step(n_rows, ROWS_PER_STEP) > 0)


def land_rows(store, rows, delta, *, rows_per_step=ROWS_PER_STEP,
              interpret=False):
    """`store.at[rows].add(delta)`, in place, priced by the rows it touches.

    store: u32[N, W] with N a multiple of 8 and W of 128; rows: int32[R],
    no two of them in one aligned group of 8 rows (they would race);
    delta: u32[R, W].  The adds wrap as the scatter's do."""
    n_rows, width = delta.shape
    step = _step(n_rows, rows_per_step)
    if (not step or width % 128 or store.shape[0] % GROUP
            or store.shape[1] != width):
        raise ValueError(f"land_rows: store {store.shape}, delta "
                         f"{delta.shape}")
    return pl.pallas_call(
        _landing_kernel,
        out_shape=jax.ShapeDtypeStruct(store.shape, store.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_rows // step,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((step, width),
                             lambda i, rows: (i, jnp.int32(0))),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((step * GROUP, width), store.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        # operand 0 is the scalar-prefetched `rows`
        input_output_aliases={1: 0},
        name="dir_row_landing",
        interpret=interpret,
    )(rows.astype(jnp.int32), store, delta)


# ---------------------------------------------------------------------------
# the private-L2 directory's staging flush (engine.dir_stage_flush)
#
# As one XLA scatter-add of row deltas (`scatter_staged`) a flush of the
# `u32[1024, 1024, 512]` sharers store gathers and expands a 2 KB row for
# each of the table's 98,304 slots (201 MB each) and passes over the 2.1 GB
# store: 19-20 ms on a v5e whatever was staged, an empty table too
# (`_hand/flush43.py`; PERF.md §6, PR 43).  A block of `memstress1024-coh`
# stages ~2,100 slots.  `land_staged` moves those alone: a slot's way is 32
# words of one (8, 128) HBM tile, so one 4 KB tile DMA in, the way's words
# overwritten in VMEM, one tile DMA back - 0.28 ms for 2,161 slots, 0.41 ms
# for 3,577, 0.08 ms + 90 ns a slot.  A lane's slots may share a tile (two
# ways of a set, two sets of a group) and a key may repeat, the LATEST slot
# winning; lanes never share one.  So slot index c runs outermost and in
# turn - step c drained before step c + 1 fetches - and lanes go together
# within a step.
# ---------------------------------------------------------------------------

# home lanes landed per grid step: the VMEM scratch is [lanes * GROUP, 128]
# (4 MB at 1,024), under the v5e's 16 MB scoped limit
LANES_PER_STEP = 1024


def can_land_staged(n_lanes, n_sets, n_ways, way_width) -> bool:
    """Whether `land_staged` takes a `[n_lanes, C]` staging table of
    `way_width`-word slots for a `[n_lanes, n_sets, n_ways * way_width]`
    sharers store: lane-aligned rows, ways that tile a 128-word column
    exactly, and lanes of whole groups, so that no two LANES share a
    group (a lane's own slots do: the kernel takes them in turn)."""
    return ((n_ways * way_width) % 128 == 0 and 128 % way_width == 0
            and n_sets % GROUP == 0
            and (n_lanes <= LANES_PER_STEP
                 or n_lanes % LANES_PER_STEP == 0))


def _staged_kernel(count_ref, store_ref, code_ref, val_ref, out_ref, buf,
                   sem_in, sem_out, *, n_ways, way_width):
    del store_ref   # aliased to `out_ref`
    # (x64 is on package-wide: every Python int is wrapped, see above)
    i32 = jnp.int32
    lanes = i32(val_ref.shape[1])
    c, j = pl.program_id(0), pl.program_id(1)
    zero = i32(0)
    # the lanes come sorted by how many slots they staged, so the lanes
    # with a slot `c` are the first `count[c]`
    n = jnp.clip(count_ref[c] - j * lanes, zero, lanes)
    ways_per_col = i32(128 // way_width)

    def entry(r):
        # slot r's flat entry index -> (row of the row-flat store, way)
        code = code_ref[0, 0, r]
        return jax.lax.div(code, i32(n_ways)), jax.lax.rem(code, i32(n_ways))

    def tile(r):
        # the (GROUP, 128) HBM tile slot r's way lies in
        row, way = entry(r)
        col = jax.lax.div(way, ways_per_col) * i32(128)
        return (pl.ds(pl.multiple_of(row - jax.lax.rem(row, i32(GROUP)),
                                     GROUP), GROUP),
                pl.ds(pl.multiple_of(col, 128), 128))

    def slot(r):
        return buf.at[pl.ds(pl.multiple_of(r * i32(GROUP), GROUP), GROUP)]

    def fetch(r):
        # from the OUTPUT, which is the store (aliased): a later slot of a
        # lane has to find the earlier ones landed
        return pltpu.make_async_copy(out_ref.at[tile(r)], slot(r), sem_in)

    def write(r):
        return pltpu.make_async_copy(slot(r), out_ref.at[tile(r)], sem_out)

    def loop(body):
        jax.lax.fori_loop(zero, n, lambda r, carry: (body(r), carry)[1], zero)

    # a step's tiles all in flight on ONE semaphore, then all waited for
    # (every copy is one tile: a wait is told apart by its size alone);
    # the step's writes are drained before the next step fetches, so slot
    # c + 1 of a lane finds slot c landed
    loop(lambda r: fetch(r).start())
    loop(lambda r: fetch(zero).wait())

    way_of_word = jax.lax.div(
        jax.lax.broadcasted_iota(i32, (1, 128), 1), i32(way_width))

    def overwrite(r):
        row, way = entry(r)
        at = pl.ds(r * i32(GROUP) + jax.lax.rem(row, i32(GROUP)), 1)
        buf[at, :] = jnp.where(
            way_of_word == jax.lax.rem(way, ways_per_col),
            val_ref[0, pl.ds(r, 1), :], buf[at, :])

    loop(overwrite)
    loop(lambda r: write(r).start())
    loop(lambda r: write(zero).wait())


def scatter_staged(sharers, skey, sval):
    """The staging table applied as ONE scatter-add of row deltas: the
    XLA form, a pass over the store whatever was staged.

    ROW-form add-a-delta: gather each staged slot's whole [DW*SW] set
    row (structured [t, s] row indexing — the fast TPU gather path; the
    3D element-index form measured 90 ms/flush, PERF.md round-5), expand
    the slot's delta into its way's column, and scatter-add rows back.
    Only each key's LAST slot within its lane row applies; two applied
    slots in the same set touch disjoint way columns, so duplicate
    (t, s) row adds stay exact; empty and superseded slots add zero out
    of bounds (dropped).  The add aliases the loop-carried buffer in
    place."""
    T, DS, _ = sharers.shape
    C, SW = sval.shape[1:]
    DW = sharers.shape[2] // SW
    tiles = np.arange(T, dtype=np.int32)[:, None]
    valid = skey >= 0                                     # [T, c]
    key = jnp.where(valid, skey, 0)
    w = nn_mod(key, DW)
    s = nn_div(key, DW)
    # a slot applies iff no LATER slot in its lane row stages the
    # same key
    later = (valid[:, :, None] & valid[:, None, :]
             & (key[:, :, None] == key[:, None, :])
             & (np.arange(C)[None, None, :]
                > np.arange(C)[None, :, None]))
    is_last = valid & ~later.any(axis=2)
    row = sharers[tiles, s]                               # [T, c, DW*SW]
    row3 = row.reshape(T, C, DW, SW)
    cur = jnp.take_along_axis(
        row3, w[:, :, None, None], axis=2)[:, :, 0]
    delta = jnp.where(is_last[..., None], sval - cur, jnp.uint32(0))
    onehot = (np.arange(DW, dtype=np.int32)[None, None, :, None]
              == w[:, :, None, None])
    row_delta = jnp.where(onehot, delta[:, :, None, :],
                          jnp.uint32(0)).reshape(T, C, DW * SW)
    s_oob = jnp.where(is_last, s, DS)          # dropped when superseded
    return sharers.at[tiles, s_oob].add(row_delta, mode="drop")


def land_staged(sharers, skey, sval, sn, *, lanes_per_step=LANES_PER_STEP,
                interpret=False):
    """The sharers store with the staging table applied: every staged
    slot's `way_width` words overwrite its way's column of its set row,
    slot index outermost and in turn, so a key's LATEST slot wins — in
    place, priced by the slots staged.

    sharers: u32[T, DS, DW * SW]; skey: int32[T, C] (set * DW + way, the
    first `sn[t]` of a lane's slots live); sval: u32[T, C, SW]; sn:
    int32[T].  `can_land_staged(T, DS, DW, SW)` must hold."""
    n_lanes, n_sets, width = sharers.shape
    cap, way_width = sval.shape[1:]
    n_ways = width // way_width
    step = min(n_lanes, lanes_per_step)
    if (not can_land_staged(n_lanes, n_sets, n_ways, way_width)
            or n_lanes % step):
        raise ValueError(f"land_staged: store {sharers.shape}, table "
                         f"{sval.shape}")
    i32 = jnp.int32
    # lanes by falling `sn` (a stable sort: a function of the table
    # alone), so that step c's live lanes are a prefix
    order = jnp.argsort(-sn, stable=True).astype(i32)
    count = jnp.sum(sn[None, :] > jnp.arange(cap, dtype=i32)[:, None],
                    axis=1, dtype=i32)
    # a slot's flat ENTRY index: (lane * DS + set) * DW + way
    code = (order[:, None] * i32(n_sets * n_ways) + skey[order]).T
    # a slot's words, repeated across a 128-word column: the kernel keeps
    # the way's copy
    val = jnp.tile(jnp.swapaxes(sval[order], 0, 1), (1, 1, 128 // way_width))

    def block(c, j, count):
        # a step with no live lane reads nothing: all of them name block
        # (0, 0), so past the deepest lane the pipeline fetches no more
        live = count[c] > j * i32(step)
        return jnp.where(live, c, i32(0)), jnp.where(live, j, i32(0))

    def code_block(c, j, count):
        c, j = block(c, j, count)
        return c, i32(0), j

    def val_block(c, j, count):
        return (*block(c, j, count), i32(0))

    flat = pl.pallas_call(
        functools.partial(_staged_kernel, n_ways=n_ways,
                          way_width=way_width),
        out_shape=jax.ShapeDtypeStruct((n_lanes * n_sets, width),
                                       sharers.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(cap, n_lanes // step),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                # (a step's codes, not the table's: all [C, T] of them
                # as a scalar-prefetch operand are 393 KB of the 1 MB of
                # SMEM at the cell's shape, and over it at C = 384)
                pl.BlockSpec((1, 1, step), code_block,
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((1, step, 128), val_block),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((step * GROUP, 128), sharers.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        # operand 0 is the scalar-prefetched `count`
        input_output_aliases={1: 0},
        name="dir_stage_landing",
        interpret=interpret,
    )(count, sharers.reshape(n_lanes * n_sets, width), code[:, None, :], val)
    return flat.reshape(sharers.shape)


def _fold_sims(*lane_axis):
    """A landing, whose operand i has the lanes on axis `lane_axis[i]` and
    whose result has them on axis 0, given a batching rule for a
    campaign's sim axis (sweep/runner.py): B sims of T lanes are a store
    of B * T lanes.  Under `vmap` every operand goes to `[B, ...]`
    (broadcast if it is not batched), the sim axis is merged into the lane
    axis - of the stores a merge of leading dimensions, a bitcast under
    the (8, 128) tiling - the landing itself is asked again, of the FOLDED
    shapes, and the result's lanes are split back.  Without a `vmap` the
    landing is its body."""
    def fold(x, batched, axis, n_sims):
        if not batched:
            x = jnp.broadcast_to(x, (n_sims, *x.shape))
        x = jnp.moveaxis(x, 0, axis)
        return x.reshape(*x.shape[:axis], n_sims * x.shape[axis + 1],
                         *x.shape[axis + 2:])

    def with_rule(solo):
        landing = jax.custom_batching.custom_vmap(solo)

        @landing.def_vmap
        def rule(n_sims, in_batched, *operands):
            out = landing(*(fold(x, b, axis, n_sims) for x, b, axis
                            in zip(operands, in_batched, lane_axis)))
            return out.reshape(n_sims, -1, *out.shape[1:]), True

        return landing

    return with_rule


@_fold_sims(0, 0, 0, 0)
def flush_staged(sharers, skey, sval, sn):
    """The sharers store after a staging flush, in place: where the
    program is lowered for a TPU and the table's shape allows it
    (`can_land_staged`: 512 tiles and up under the default directory), the
    kernel, priced by the slots staged; everywhere else the scatter-add,
    priced by the store.  Under a campaign's sim axis the same is asked of
    the B * T folded lanes (`_fold_sims`): four sims of 256 tiles take the
    1,024-lane kernel."""
    n_lanes, n_sets, width = sharers.shape
    way_width = sval.shape[2]
    if not can_land_staged(n_lanes, n_sets, width // way_width, way_width):
        return scatter_staged(sharers, skey, sval)
    return jax.lax.platform_dependent(
        sharers, skey, sval, sn, tpu=land_staged,
        default=lambda sharers, skey, sval, sn: scatter_staged(
            sharers, skey, sval))


# ---------------------------------------------------------------------------
# the private-L2 directory's entry words (engine._dir_apply_merged)
#
# The home phases' plan is at most three 8-byte words a lane, a few hundred
# live ones an iteration.  As an XLA scatter-add onto the `int64[1024, 1024,
# 16]` entry store it costs five passes over a 64 MB half: the store is two
# u32 halves tiled (8, 128), an element scatter wants them linear, so each is
# copied flat, scattered on and reshaped back - 0.97 ms an open iteration on
# a v5e (`_hand/entry45.py`; PERF.md section 6, PR 45).  Mosaic has no int64
# and a split in front of a kernel brings the passes back, so where this
# kernel may engage the store is CARRIED as u32 words, `u32[T, 2 * DW, DS]`
# (`entry_words`): ways on sublanes, sets on lanes, a lane's low words in
# rows 0..DW-1 and its high words in rows DW..2*DW-1 - the layout XLA gave
# the halves anyway, so the working-set gather reads what it read.  A plan
# word (t, set, way) is lane `set % 128` of sublane `way % 8` of TWO tiles;
# `land_entry` moves those alone and adds the 64-bit delta as a u32 pair
# with carry.  Only LIVE words travel (a nonzero delta that no earlier
# phase folded): they are sorted to a prefix of their phase and the
# kernel's loops run to the count.  Lanes never share a tile (a lane owns
# whole groups of rows); a lane's phases may (two ways of one group, two
# sets of one 128-lane column).  So the phase index runs outermost and in
# turn - phase p drained before phase p + 1 fetches - and lanes go together
# within a phase.
# ---------------------------------------------------------------------------

# live words landed per grid step: the VMEM scratch is two tiles a word,
# [lanes * 2 * GROUP, 128] (4 MB at 512), under the v5e's 16 MB scoped limit
ENTRY_LANES_PER_STEP = 512


def entry_words(entry):
    """An `int64[T, DS, DW]` entry store as the u32 words `land_entry`
    lands on: `u32[T, 2 * DW, DS]`, low words above high words."""
    word = jnp.swapaxes(entry, 1, 2)
    return jnp.concatenate([word.astype(jnp.uint32),
                            (word >> 32).astype(jnp.uint32)], axis=1)


def entry_int64(entry):
    """The `int64[..., DS, DW]` words of an entry store in either form:
    `entry_words`' inverse on u32, the identity on int64."""
    if entry.dtype != jnp.uint32:
        return entry
    n_ways = entry.shape[-2] // 2
    lo = entry[..., :n_ways, :].astype(jnp.int64)
    hi = entry[..., n_ways:, :].astype(jnp.int64)
    return jnp.swapaxes(lo | (hi << 32), -1, -2)


def can_land_entry(n_lanes, n_sets, n_ways) -> bool:
    """Whether `land_entry` takes a `[P, n_lanes]` plan for the
    `u32[n_lanes, 2 * n_ways, n_sets]` words of an entry store: sets that
    fill whole 128-word columns and ways that fill whole groups, so that
    a word's halves lie in two tiles and no two LANES share one (a lane's
    own phases do: the kernel takes them in turn)."""
    return (n_sets % 128 == 0 and n_ways % GROUP == 0
            and (n_lanes <= ENTRY_LANES_PER_STEP
                 or n_lanes % ENTRY_LANES_PER_STEP == 0))


def _entry_kernel(count_ref, row_ref, col_ref, dlo_ref, dhi_ref, store_ref,
                  out_ref, buf, sem_in, sem_out, *, n_lanes, n_ways):
    del store_ref   # aliased to `out_ref`
    # (x64 is on package-wide: every Python int is wrapped, see above)
    i32 = jnp.int32
    step = i32(buf.shape[0] // (2 * GROUP))
    p, j = pl.program_id(0), pl.program_id(1)
    zero = i32(0)
    # phase p's live words come first: the first `count[p]` of its row
    n = jnp.clip(count_ref[p] - j * step, zero, step)
    base = p * i32(n_lanes) + j * step

    def tile(r, half):
        # the (GROUP, 128) HBM tile word r's low (0) / high (1) half lies in
        row = row_ref[base + r] + i32(half * n_ways)
        col = col_ref[base + r]
        return (pl.ds(pl.multiple_of(row - jax.lax.rem(row, i32(GROUP)),
                                     GROUP), GROUP),
                pl.ds(pl.multiple_of(col - jax.lax.rem(col, i32(128)), 128),
                      128))

    def slot(r, half):
        return buf.at[pl.ds(pl.multiple_of((r * i32(2) + i32(half))
                                           * i32(GROUP), GROUP), GROUP)]

    def fetch(r, half):
        # from the OUTPUT, which is the store (aliased): a later phase of
        # a lane has to find the earlier ones landed
        return pltpu.make_async_copy(out_ref.at[tile(r, half)],
                                     slot(r, half), sem_in)

    def write(r, half):
        return pltpu.make_async_copy(slot(r, half),
                                     out_ref.at[tile(r, half)], sem_out)

    def loop(body):
        jax.lax.fori_loop(zero, n, lambda r, carry: (body(r), carry)[1], zero)

    def both(copy):
        return lambda r: (copy(r, 0), copy(r, 1))

    # a phase's tiles all in flight on ONE semaphore, then all waited for
    # (every copy is one tile: a wait is told apart by its size alone);
    # the phase's writes are drained before the next phase fetches
    loop(both(lambda r, half: fetch(r, half).start()))
    loop(both(lambda r, half: fetch(zero, 0).wait()))

    lane = jax.lax.broadcasted_iota(i32, (1, 128), 1)

    def add(r):
        # the 64-bit add as a u32 pair with carry, under a one-lane mask
        sub = jax.lax.rem(row_ref[base + r], i32(GROUP))
        at_lo = pl.ds(r * i32(2 * GROUP) + sub, 1)
        at_hi = pl.ds(r * i32(2 * GROUP) + i32(GROUP) + sub, 1)
        here = lane == jax.lax.rem(col_ref[base + r], i32(128))
        lo = buf[at_lo, :]
        new_lo = lo + jnp.where(here, dlo_ref[base + r],
                                zero).astype(jnp.uint32)
        buf[at_lo, :] = new_lo
        buf[at_hi, :] = (buf[at_hi, :]
                         + jnp.where(here, dhi_ref[base + r],
                                     zero).astype(jnp.uint32)
                         + (new_lo < lo).astype(jnp.uint32))

    loop(add)
    loop(both(lambda r, half: write(r, half).start()))
    loop(both(lambda r, half: write(zero, 0).wait()))


def _entry_plan(store, sets, way, delta, live):
    """(lanes, ways, which words travel) of a `[P, T]` plan on `store`:
    a word whose delta is zero is as good as folded away."""
    n_lanes = store.shape[0]
    lanes = jnp.broadcast_to(jnp.arange(n_lanes, dtype=jnp.int32),
                             sets.shape)
    return lanes, way.astype(jnp.int32), live & (delta != 0)


def pack_entry_plan(store, sets, way, delta, live):
    """What the kernel reads of a plan, five int32 arrays: each phase's
    count of live words, and `[P, T]` rows of the words' low-half row in
    the row-flat store, their column and their delta's halves, the live
    words a prefix of their phase (ONE stable sort: a function of the
    plan alone, the coordinates and halves riding along)."""
    i32 = jnp.int32
    lanes, way, live = _entry_plan(store, sets, way, delta, live)
    _, row, col, dlo, dhi = jax.lax.sort(
        ((~live).astype(i32), lanes * i32(store.shape[1]) + way,
         sets.astype(i32), delta.astype(i32), (delta >> 32).astype(i32)),
        dimension=1, is_stable=True, num_keys=1)
    return jnp.sum(live, axis=1, dtype=i32), row, col, dlo, dhi


def scatter_entry(store, sets, way, delta, live):
    """The plan landed on the u32 words by XLA: the plan's current words
    gathered, the deltas added in int64, both halves set (the live words'
    indices are unique; the others go out of bounds and are dropped).  A
    pass over the store on a TPU, whatever the plan holds."""
    n_lanes, rows, _ = store.shape
    n_ways = rows // 2
    lanes, way, live = _entry_plan(store, sets, way, delta, live)
    new = (store[lanes, way, sets].astype(jnp.int64)
           | (store[lanes, way + n_ways, sets].astype(jnp.int64) << 32)
           ) + delta
    lanes = jnp.where(live, lanes, n_lanes)

    def both(lo, hi):
        return jnp.concatenate([lo.ravel(), hi.ravel()])

    return store.at[both(lanes, lanes), both(way, way + n_ways),
                    both(sets, sets)].set(
        both(new.astype(jnp.uint32), (new >> 32).astype(jnp.uint32)),
        mode="drop", unique_indices=True)


def land_entry(store, sets, way, delta, live, *,
               lanes_per_step=ENTRY_LANES_PER_STEP, interpret=False):
    """`delta[p, t]` added to the 64-bit entry word (t, sets[p, t],
    way[p, t]) wherever `live[p, t]`, phase p = 0, 1, ... in turn - in
    place, priced by the live words.

    store: u32[T, 2 * DW, DS] (`entry_words`); sets, way: int32[P, T];
    delta: int64[P, T]; live: bool[P, T], the live words of a lane naming
    distinct words.  `can_land_entry(T, DS, DW)` must hold.  Exact where
    a lane's words share a tile because phases land in turn, each drained
    before the next fetches; within a phase every word is another lane's
    and lanes share no tile."""
    n_lanes, rows, n_sets = store.shape
    n_ways = rows // 2
    n_plans = sets.shape[0]
    step = min(n_lanes, lanes_per_step)
    if (not can_land_entry(n_lanes, n_sets, n_ways) or n_lanes % step
            or sets.shape != (n_plans, n_lanes)):
        raise ValueError(f"land_entry: store {store.shape}, plan "
                         f"{sets.shape}")
    count, *words = pack_entry_plan(store, sets, way, delta, live)
    flat = pl.pallas_call(
        functools.partial(_entry_kernel, n_lanes=n_lanes, n_ways=n_ways),
        out_shape=jax.ShapeDtypeStruct((n_lanes * rows, n_sets),
                                       store.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_plans, n_lanes // step),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((step * 2 * GROUP, 128), store.dtype),
                pltpu.SemaphoreType.DMA(()),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        # operands 0..4 are the scalar-prefetched plan
        input_output_aliases={5: 0},
        name="dir_entry_landing",
        interpret=interpret,
    )(count, *(w.ravel() for w in words),
      store.reshape(n_lanes * rows, n_sets))
    return flat.reshape(store.shape)


@_fold_sims(0, 1, 1, 1, 1)
def apply_entry(store, sets, way, delta, live):
    """The u32 entry words with a plan landed, in place: where the
    program is lowered for a TPU and the store's shape allows it
    (`can_land_entry`), the kernel, priced by the live words; everywhere
    else `scatter_entry`, priced by the store.  Under a campaign's sim
    axis the same is asked of the B * T folded lanes, the `[B, P, T]` plan
    as `[P, B * T]` (`_fold_sims`)."""
    n_lanes, rows, n_sets = store.shape
    if not can_land_entry(n_lanes, n_sets, rows // 2):
        return scatter_entry(store, sets, way, delta, live)
    return jax.lax.platform_dependent(
        store, sets, way, delta, live, tpu=land_entry,
        default=scatter_entry)
