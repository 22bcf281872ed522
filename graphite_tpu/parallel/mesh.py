"""Device-mesh construction and state/trace sharding rules.

Sharding policy: every array whose leading dimension is the tile count is
sharded on that axis (`PartitionSpec("tiles")`); everything else (sync-object
tables, scalars) is replicated.  The mailbox tensor [dst, src, depth] is
sharded on dst — a tile's inbox lives with its shard, like Graphite's
per-tile `_netQueue` living in the owning process (`network.cc:358-460`) —
and cross-shard sends become XLA scatter collectives over ICI, replacing the
full-mesh TCP of `socktransport.cc`.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from graphite_tpu.engine.state import DeviceTrace, SimState

TILE_AXIS = "tiles"


def _shard_map(f, *, mesh, in_specs, out_specs):
    """jax.shard_map with the varying-manual-axes checker off: control
    state is replicated by construction and the checker cannot see it
    (see make_shard_map_runner)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_tile_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """A 1D mesh over the tile axis.

    On a real multi-chip slice this is the ICI ring/torus; in tests it is
    the virtual 8-device CPU platform.
    """
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (TILE_AXIS,))


def _tile_spec(leaf: jax.Array) -> P:
    return P(TILE_AXIS, *([None] * (leaf.ndim - 1)))


# Fields whose leading axis is NOT the tile axis and must be replicated:
# the sync-object tables (the MCP SyncServer analog, `sync_server.h:86-114`)
# and global scalars.
_REPLICATED_STATE_FIELDS = {
    "barrier_count", "barrier_arrived", "barrier_time_ps",
    "barrier_gen", "barrier_release_ps",
    "mutex_locked", "mutex_owner", "mutex_time_ps",
    "cond_sig_time_ps", "cond_bcast_time_ps",
    "cond_sig_seq", "cond_sig_seq_ps",
    "models_enabled", "overflow",
    # functional word store: a global address space, replicated (the
    # coherence protocol serializes conflicting writes)
    "func_mem", "func_errors",
    # gate observability: the [6] per-phase skip-count vector is global
    # control state (and at 6-tile counts would otherwise be mistaken
    # for a tile-major array by the shape heuristic below)
    "phase_skips", "base_skips",
}


def state_shardings(state: SimState, mesh: Mesh, n_tiles: int):
    def spec_for(path, leaf):
        name = path[-1].name if path else ""
        if (
            name in _REPLICATED_STATE_FIELDS
            or leaf.ndim == 0
            # Anything not tile-major is replicated — e.g. the hop-by-hop
            # NoC per-port queue arrays, which are [n_tiles*ports+1] flat
            # (router state is small; replication trades memory for the
            # scatter locality of contention updates)
            or leaf.shape[0] != n_tiles
        ):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _tile_spec(leaf))

    return jax.tree_util.tree_map_with_path(spec_for, state)


def trace_shardings(trace: DeviceTrace, mesh: Mesh, n_tiles: int):
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, _tile_spec(leaf)), trace
    )


def shard_state(state: SimState, mesh: Mesh) -> SimState:
    """Place the state alone on the mesh (streamed runs: the trace
    arrives later as per-window uploads sharded by shard_window)."""
    n_tiles = state.core.clock_ps.shape[0]
    n_dev = mesh.devices.size
    if n_tiles % n_dev != 0:
        raise ValueError(
            f"tile count {n_tiles} not divisible by mesh size {n_dev}"
        )
    return jax.device_put(state, state_shardings(state, mesh, n_tiles))


def shard_window(window: DeviceTrace, mesh: Mesh, bases) -> tuple:
    """Shard one streamed [T, W] trace window + its per-tile base vector
    onto the mesh (row t of the window lives with tile t's shard)."""
    n_tiles = window.op.shape[0]
    window = jax.device_put(
        window, trace_shardings(window, mesh, n_tiles))
    import jax.numpy as jnp

    bases = jax.device_put(
        jnp.asarray(bases), NamedSharding(mesh, P(TILE_AXIS)))
    return window, bases


# --------------------------------------------------------------------------
# The packed shard_map path (the default multi-chip runner).
#
# Unlike the GSPMD specs above — which shard every tile-major array and let
# the partitioner insert one small collective per scatter (~270/iteration,
# measured 16x SLOWER than single-device at 8 devices; PERF.md) — the
# shard_map program keeps exactly the BIG per-tile arrays block-local and
# recomputes all [T]-vector control state replicated on every device, so
# the only collectives are the engine's packed per-phase row exchanges
# (parallel/px.py; ~7 per subquantum iteration).  This is the TPU-native
# form of the reference's process striping: big state partitioned like the
# per-process tile models (`config.cc` computeProcessToTileMapping), small
# control traffic exchanged like its TCP messages (`socktransport.cc`).

# state leaves that are block-local under shard_map (dotted field paths);
# everything else is replicated
_SHARD_MAP_LOCAL = {
    "core.bp_bits",
    "mem.l1i.meta", "mem.l1d.meta", "mem.l2.meta",
    "mem.l2_cloc", "mem.l2_util", "mem.mt",
    "mem.directory.entry", "mem.directory.sharers",
    # round-12 per-HOME-LANE staging rows: lane-local by construction,
    # so they shard with the directory they stage for
    "mem.directory.skey", "mem.directory.sval", "mem.directory.sn",
    # shared-L2 engine: the L2-slice-embedded directory (engine_shl2)
    "mem.dir.word", "mem.dir.sharers",
}


def _path_name(path) -> str:
    names = []
    for p in path:
        n = getattr(p, "name", None)
        if n is not None:
            names.append(str(n))
    return ".".join(names)


def shard_map_state_specs(state: SimState):
    """PartitionSpec tree for the shard_map path: big arrays block-local
    on the tile axis, everything else replicated."""

    def spec(path, leaf):
        if _path_name(path) in _SHARD_MAP_LOCAL:
            return P(TILE_AXIS, *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, state)


def shard_map_trace_specs(trace: DeviceTrace):
    return jax.tree.map(lambda leaf: P(TILE_AXIS, None), trace)


def place_shard_map(state: SimState, mesh: Mesh, trace=None):
    """Device-put state (and optionally the trace) with the shard_map
    layout so the jitted runner starts without a resharding pass."""
    n_tiles = state.core.clock_ps.shape[0]
    n_dev = mesh.devices.size
    if n_tiles % n_dev != 0:
        raise ValueError(
            f"tile count {n_tiles} not divisible by mesh size {n_dev}")
    state = jax.device_put(state, jax.tree.map(
        lambda s: NamedSharding(mesh, s), shard_map_state_specs(state),
        is_leaf=lambda x: isinstance(x, P)))
    if trace is None:
        return state
    trace = jax.device_put(trace, jax.tree.map(
        lambda s: NamedSharding(mesh, s), shard_map_trace_specs(trace),
        is_leaf=lambda x: isinstance(x, P)))
    return state, trace


def place_shard_map_window(window: DeviceTrace, mesh: Mesh, bases):
    """Place one streamed [T, W] trace window (block-local rows) + its
    per-tile base vector (replicated control state — the engine lo()s it
    for local reads) for the shard_map runner."""
    import jax.numpy as jnp

    window = jax.device_put(window, jax.tree.map(
        lambda s: NamedSharding(mesh, s), shard_map_trace_specs(window),
        is_leaf=lambda x: isinstance(x, P)))
    bases = jax.device_put(jnp.asarray(bases), NamedSharding(mesh, P()))
    return window, bases


def make_shard_map_runner(params, quantum_ps, max_quanta: int, mesh: Mesh,
                          state_example: SimState, trace_example,
                          streamed: bool = False):
    """The jitted multi-chip runner: run_simulation under jax.shard_map
    with the packed px exchange.  Takes (state, trace[, trace_base]) —
    the trace is an argument (not a closure) so streamed windows shard.

    check_vma=False: control state is replicated by construction (same
    deterministic integer math from identical inputs on every device) and
    the big arrays' collectives are the explicit px exchanges — the
    varying-axis checker cannot see either invariant."""
    from graphite_tpu.engine.step import run_simulation
    from graphite_tpu.parallel.px import ParallelCtx

    px = ParallelCtx(axis=TILE_AXIS, n_dev=int(mesh.devices.size))
    state_specs = shard_map_state_specs(state_example)
    trace_specs = shard_map_trace_specs(trace_example)

    if streamed:
        def body(st, tr, base):
            return run_simulation(params, tr, st, quantum_ps, max_quanta,
                                  trace_base=base, px=px)

        sm = _shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, trace_specs, P()),
            out_specs=(state_specs, P(), P(), P(), P()))
        return jax.jit(sm)

    def body(st, tr):
        return run_simulation(params, tr, st, quantum_ps, max_quanta, px=px)

    sm = _shard_map(
        body, mesh=mesh,
        in_specs=(state_specs, trace_specs),
        out_specs=(state_specs, P(), P(), P(), P()))
    return jax.jit(sm)


# --------------------------------------------------------------------------
# The 2D batch x tile campaign layout (round 18).
#
# A Mesh(('batch', 'tile')) program: each device holds a TILE BLOCK of a
# SUBSET of sims — the batch axis stays embarrassingly parallel (the
# round-7 campaign semantics per cell) while the tile axis runs the
# round-12 packed per-phase exchange (parallel/px.py: one working-set
# gather + one merged scatter per iteration) WITHIN each batch cell.
# This is Graphite's process striping (config.cc
# computeProcessToTileMapping) crossed with campaign batching: one
# compiled artifact serving pod-sized grids of sims too big for one
# device's budget.  Specs follow the shard_map policy above — the big
# per-tile arrays (_SHARD_MAP_LOCAL) are block-local on the tile axis,
# control state is replicated per batch cell — plus the round-16
# per-tile profile ring, whose [S, T, m] tile axis shards with the
# directory (obs/profile.profile_tick slices the row to local lanes).

BATCH_AXIS = "batch"
TILE_AXIS_2D = "tile"

# ProfileState leaves whose tile axis shards under the 2D layout, and
# WHICH axis of the unbatched leaf it is (buf is [S, T, m]; prev is
# [T, m]); the [S] times ring and the scalar cursors stay replicated.
_PROFILE_TILE_AXES = {"profile.buf": 1, "profile.prev": 0}

# The round-21 latency-histogram ring: a PER-TILE [T, H, B] buffer
# shards its tile axis (obs/hist._scatter lo()s the masks to local
# lanes); the aggregate [H, B] buffer stays replicated — the commit
# masks are the replicated full-[T] control vectors, so every shard
# accumulates the identical fleet-wide counts.  Distinguished by ndim
# (3 = per-tile) since both layouts share the leaf name.
_HIST_TILE_AXES = {"hist.buf": 0}


def make_batch_tile_mesh(batch_shards: int, tile_shards: int,
                         devices=None, abstract: bool = False):
    """A Mesh(('batch', 'tile')) over batch_shards x tile_shards
    devices.  `abstract=True` returns a device-less AbstractMesh — the
    tracing form `SweepRunner.lower()` uses so the 2D program can be
    audited/fingerprinted on any host (including 1-device CI) without
    the forced-device platform the execution mesh needs."""
    db, dt = int(batch_shards), int(tile_shards)
    if db < 1 or dt < 1:
        raise ValueError(
            f"mesh shards must be positive (got batch={db}, tile={dt})")
    if abstract:
        from jax.sharding import AbstractMesh

        return AbstractMesh((db, dt), (BATCH_AXIS, TILE_AXIS_2D))
    if devices is None:
        devices = jax.devices()
    if len(devices) < db * dt:
        raise ValueError(
            f"2D campaign layout needs {db}x{dt}={db * dt} devices but "
            f"only {len(devices)} are visible — force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N on "
            f"CPU, or shrink the layout")
    return Mesh(np.asarray(devices[:db * dt]).reshape(db, dt),
                (BATCH_AXIS, TILE_AXIS_2D))


def campaign_state_specs(state: SimState):
    """PartitionSpec tree for a BATCHED [B, ...] state under the 2D
    layout, built from the UNBATCHED per-sim example: every leaf gains
    a leading 'batch' axis; the big per-tile arrays additionally shard
    their tile axis (the same _SHARD_MAP_LOCAL policy as the 1D
    multi-chip runner); the profile ring's tile axis shards with them;
    everything else — control vectors, sync tables, the telemetry ring
    (scalar series, replicated-identical on every tile shard) — rides
    the batch axis only."""

    def spec(path, leaf):
        name = _path_name(path)
        if name in _SHARD_MAP_LOCAL:
            return P(BATCH_AXIS, TILE_AXIS_2D,
                     *([None] * (leaf.ndim - 1)))
        t_axis = _PROFILE_TILE_AXES.get(name)
        if t_axis is None and name in _HIST_TILE_AXES and leaf.ndim == 3:
            t_axis = _HIST_TILE_AXES[name]
        if t_axis is not None:
            dims = [None] * leaf.ndim
            dims[t_axis] = TILE_AXIS_2D
            return P(BATCH_AXIS, *dims)
        return P(BATCH_AXIS)

    return jax.tree_util.tree_map_with_path(spec, state)


def campaign_trace_specs(trace: DeviceTrace):
    """Specs for the packed [B, T, L] campaign traces: each device
    holds its batch cells' tile-block rows."""
    return jax.tree.map(lambda leaf: P(BATCH_AXIS, TILE_AXIS_2D, None),
                        trace)


def shard_split_bytes(state: SimState) -> "dict[str, int]":
    """Split one sim's state bytes into the 2D layout's residency
    classes: {'tile_local': bytes of the _SHARD_MAP_LOCAL arrays (each
    device holds 1/tile_shards of them), 'replicated': everything else
    (every tile shard holds a full copy)}.  Telemetry/profile/hist ring
    leaves are excluded — they are priced separately through their
    specs' own ring_bytes (the one size model)."""
    from graphite_tpu.analysis.walk import aval_bytes

    out = {"tile_local": 0, "replicated": 0}

    def visit(path, leaf):
        name = _path_name(path)
        if name.startswith("telemetry.") or name.startswith("profile.") \
                or name.startswith("hist."):
            return
        b = aval_bytes(leaf)
        if name in _SHARD_MAP_LOCAL:
            out["tile_local"] += b
        else:
            out["replicated"] += b

    jax.tree_util.tree_map_with_path(visit, state)
    return out


def shard_sim(
    state: SimState, trace: DeviceTrace, mesh: Mesh
) -> tuple[SimState, DeviceTrace]:
    """Place state + trace on the mesh, tile axis sharded.

    The tile count must divide the mesh size.  Returns device-placed
    pytrees; subsequent jitted steps follow the input shardings, with XLA
    inserting the cross-shard collectives for mailbox scatters (the
    TPU-native replacement for SockTransport's TCP full mesh).
    """
    n_tiles = state.core.clock_ps.shape[0]
    n_dev = mesh.devices.size
    if n_tiles % n_dev != 0:
        raise ValueError(
            f"tile count {n_tiles} not divisible by mesh size {n_dev}"
        )
    state = jax.device_put(state, state_shardings(state, mesh, n_tiles))
    trace = jax.device_put(trace, trace_shardings(trace, mesh, n_tiles))
    return state, trace
