"""The packed shard_map exchange context (``ParallelCtx``).

The multi-chip form of the engine runs ONE program per device under
``jax.shard_map``: big per-tile arrays (trace, cache meta words, the
directory, branch-predictor bits, miss-type bitmaps) live block-local —
each device holds rows ``[i*Tl, (i+1)*Tl)`` of the tile axis — while every
per-lane ``[T]`` control vector, the ``[T, T]`` mailbox matrices, the sync
tables and the NoC state stay REPLICATED and are recomputed identically on
every device (integer math, deterministic, so the replicas cannot diverge).

Cross-device data motion is then exactly the engine's phase structure:
each protocol phase gathers its lanes' rows from the block-local arrays,
packs every gathered field into ONE ``[Tl, K]`` int64 descriptor, and
all-gathers it — a handful of collectives per subquantum iteration instead
of the ~270 tiny per-scatter collectives GSPMD inserts for the same
program (PERF.md "Multi-device step wall-clock"; the reference's analog of
this exchange is the process-striped directory traffic over
`common/transport/socktransport.cc`, one TCP message per protocol hop).

``ParallelCtx`` is threaded through `engine/step.py` and
`memory/engine.py`; the default ``IDENT`` context makes every operation an
identity, so the single-device path compiles to exactly the program it
always was.

The context also names the SIM axis of a campaign (``sim_axis``: the
``axis_name`` under which `sweep/runner.py` maps `run_simulation` over B
sims).  It carries no data motion: ``any_sim`` ORs an activity predicate
over the sims of one program so that the predicate stays a scalar and the
``lax.cond`` it keys stays a cond under ``vmap`` (a batched predicate
would turn it into both branches and a select).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from graphite_tpu.obs.scopes import scope

I64 = jnp.int64

# the `vmap` axis name a campaign maps its sims under (sweep/runner.py)
SIM_AXIS = "sim"


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """Identity (single-device) or shard_map (per-device block) context.

    axis: mesh axis name the tile dimension is sharded over, or None.
    n_dev: number of devices on that axis.
    sim_axis: the `vmap` axis name the program is mapped over B sims
        under (sweep/runner.py), or None for a program of one sim.
    """

    axis: str | None = None
    n_dev: int = 1
    sim_axis: str | None = None

    @property
    def sharded(self) -> bool:
        # n_dev > 1: a 1-device tile axis needs no exchange — ag()/lo()
        # must be identities so solo programs lower to ZERO collective
        # equations and provably pay no fabric tax (the comms analyzer
        # pins this; a size-1 all_gather would still round-trip every
        # field through the int64 descriptor packing)
        return self.axis is not None and self.n_dev > 1

    # -- activity gates under a sim axis ----------------------------------

    def any_sim(self, pred):
        """`pred` (bool[]) OR-ed over the sims of this program: the
        predicate itself when there is no sim axis (nothing is traced),
        else a reduction over it, which `vmap` leaves UNBATCHED — a
        `reduce_max` over [B], no collective.  A gate keyed on it runs its
        block when ANY sim needs it; a sim that does not runs the block
        with every lane masked off, which must return its inputs."""
        if self.sim_axis is None:
            return pred
        return jax.lax.pmax(pred.astype(jnp.int32), self.sim_axis) > 0

    # -- local block addressing ------------------------------------------

    def lo(self, tree):
        """Slice full [T, ...] arrays down to this device's [Tl, ...] block
        (identity when single-device).  Works on pytrees."""
        if not self.sharded:
            return tree

        def f(x):
            T = x.shape[0]
            Tl = T // self.n_dev
            i = jax.lax.axis_index(self.axis)
            return jax.lax.dynamic_slice_in_dim(x, i * Tl, Tl, axis=0)

        return jax.tree.map(f, tree)

    # -- the packed exchange ---------------------------------------------

    def ag(self, tree):
        """All-gather local [Tl, ...] arrays to full [T, ...] via ONE
        packed [Tl, K] int64 collective (identity when single-device).

        Every leaf is flattened to [Tl, k_i], widened to int64, and
        concatenated; the single tiled all_gather moves the whole
        descriptor; leaves are then split back out and narrowed.  One
        collective per call regardless of how many fields ride it —
        per-collective latency, not bytes, is what the virtual mesh (and
        real ICI) charges for."""
        if not self.sharded:
            return tree
        leaves, tdef = jax.tree.flatten(tree)
        if not leaves:
            return tree
        with scope("gt.px"):
            cols = []
            meta = []
            for leaf in leaves:
                k = 1
                for d in leaf.shape[1:]:
                    k *= d
                meta.append((leaf.shape, leaf.dtype, k))
                flat = leaf.reshape(leaf.shape[0], k)
                if leaf.dtype == jnp.uint32:
                    # widen via uint64 so values >= 2^31 survive the round trip
                    flat = flat.astype(jnp.uint64).astype(I64)
                else:
                    flat = flat.astype(I64)
                cols.append(flat)
            buf = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
            full = jax.lax.all_gather(buf, self.axis, axis=0, tiled=True)
            out = []
            off = 0
            for shape, dtype, k in meta:
                piece = full[:, off:off + k]
                off += k
                if dtype == jnp.uint32:
                    piece = piece.astype(jnp.uint64).astype(dtype)
                elif dtype == jnp.bool_:
                    piece = piece != 0
                else:
                    piece = piece.astype(dtype)
                out.append(piece.reshape((full.shape[0],) + tuple(shape[1:])))
        return jax.tree.unflatten(tdef, out)

    def lo_const(self, x):
        """lo() for compile-time per-tile constants: ints and None pass
        through, [T]-shaped tables are sliced (e.g. heterogeneous cache
        set moduli)."""
        if x is None or isinstance(x, int) or not hasattr(x, "shape"):
            return x
        if len(getattr(x, "shape", ())) == 0:
            return x
        return self.lo(jnp.asarray(x))

    # -- local per-lane writes (operands already block-local) ------------

    def lane_col_add(self, arr, col, delta):
        """``arr[t, col[t]] += delta[t]`` on this device's rows; arr is
        block-local [Tl, K] and col/delta are block-local [Tl] (callers
        px.lo replicated operands first)."""
        lt = jnp.arange(arr.shape[0], dtype=jnp.int32)
        return arr.at[lt, col].add(delta.astype(arr.dtype))

    def entry_set(self, arr, sets, way, mask, value, cur=None):
        """``arr[t, sets[t], way[t]] = value[t] where mask[t]`` on this
        device's rows; arr is block-local [Tl, S, W] and every operand is
        block-local [Tl] (callers px.lo replicated operands first; value
        may be a scalar).  Written add-a-delta so the scatter aliases in
        place (per-lane rows are unique).

        `cur`: the elements as the caller has ALREADY read them from
        `arr` (block-local [Tl]).  A phase that has looked at the element
        passes it here, so that the store keeps one reader and that
        reader feeds the scatter: a second read that the scatter does not
        depend on makes XLA copy the whole carried store
        (`cache_array.scatter_row`)."""
        lt = jnp.arange(arr.shape[0], dtype=jnp.int32)
        if cur is None:
            cur = arr[lt, sets, way]
        value = jnp.broadcast_to(jnp.asarray(value, arr.dtype), cur.shape)
        return arr.at[lt, sets, way].add(
            jnp.where(mask, value - cur, jnp.zeros_like(cur)),
            unique_indices=True, indices_are_sorted=True)


IDENT = ParallelCtx()
