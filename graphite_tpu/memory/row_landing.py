"""Land a plan of row deltas on a resident row-flat store, touching only
the plan's rows: the Pallas TPU kernel behind `engine_shl2._dir_apply_rows`.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
