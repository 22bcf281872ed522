"""Synthetic trace generators — the NoC/memory stress frontends.

Reproduces the reference's synthetic benchmark generators as trace producers:
 - traffic patterns from `tests/benchmarks/synthetic_network/
   synthetic_network.cc:16-25,215-341`: uniform_random (LCG permutation
   matrix), bit_complement, shuffle, transpose, tornado, nearest_neighbor;
 - a synthetic memory-stress generator (`tests/benchmarks/synthetic_memory`):
   random/strided load/store streams over a configurable working set;
 - a ping-pong CAPI latency microbenchmark (`tests/apps/ping_pong`);
 - a generic compute-mix generator for core-model unit tests.
"""

from __future__ import annotations

import numpy as np

from graphite_tpu.models.network_emesh import is_tile_count_permissible, mesh_dims
from graphite_tpu.trace.schema import Op, TraceBatch, TraceBuilder, generator

TRAFFIC_PATTERNS = (
    "uniform_random",
    "bit_complement",
    "shuffle",
    "transpose",
    "tornado",
    "nearest_neighbor",
)


def _mesh_dims(n_tiles: int) -> tuple[int, int]:
    # same factorization as the NoC models (`network_emesh.py`), asserted
    # like the reference generator (`synthetic_network.cc:344-349`)
    assert is_tile_count_permissible(n_tiles), \
        "synthetic mesh patterns need w*h tile counts"
    return mesh_dims(n_tiles)


def uniform_random_matrix(n_tiles: int) -> np.ndarray:
    """The reference's LCG permutation schedule, reproduced exactly.

    `synthetic_network.cc:235-286`: send_matrix[slot][sender] with
    send_matrix[0][0] = n/2, row-chained seed send_matrix[i][0] =
    send_matrix[i-1][1], recurrence s[i][j] = (13*s[i][j-1] + 5) % n.
    Every row and every column is a permutation of 0..n-1 (asserted, as in
    the reference).  Returns [n_slots=n_tiles, n_senders=n_tiles].
    """
    n = n_tiles
    send = np.zeros((n, n), dtype=np.int32)
    send[0][0] = n // 2
    for i in range(n):
        if i != 0:
            send[i][0] = send[i - 1][1]
        for j in range(1, n):
            send[i][j] = (13 * send[i][j - 1] + 5) % n
    for i in range(n):
        assert sorted(send[i]) == list(range(n)), "row not a permutation"
    for j in range(n):
        assert sorted(send[:, j]) == list(range(n)), "column not a permutation"
    return send


def destinations(pattern: str, n_tiles: int) -> np.ndarray:
    """Per-tile destination schedule, shape [n_slots, n_tiles].

    Deterministic patterns have one slot; uniform_random has n_tiles slots
    (`synthetic_network.cc:281-286`).
    """
    tile = np.arange(n_tiles, dtype=np.int32)
    if pattern == "uniform_random":
        return uniform_random_matrix(n_tiles)
    if pattern == "bit_complement":
        # `synthetic_network.cc:288-295`
        assert n_tiles & (n_tiles - 1) == 0, "bit_complement needs power of 2"
        return (~tile & (n_tiles - 1))[None, :]
    if pattern == "shuffle":
        # `synthetic_network.cc:297-305`
        assert n_tiles & (n_tiles - 1) == 0, "shuffle needs power of 2"
        nbits = n_tiles.bit_length() - 1
        return (((tile >> (nbits - 1)) & 1) | ((tile << 1) & (n_tiles - 1)))[None, :]
    w, h = _mesh_dims(n_tiles)
    sx, sy = tile % w, tile // w
    if pattern == "transpose":
        # `synthetic_network.cc:307-317`: (x,y) -> (y,x)
        return (sx * w + sy)[None, :]
    if pattern == "tornado":
        # `synthetic_network.cc:319-329`
        return (((sy + h // 2) % h) * w + ((sx + w // 2) % w))[None, :]
    if pattern == "nearest_neighbor":
        # `synthetic_network.cc:331-341`
        return (((sy + 1) % h) * w + ((sx + 1) % w))[None, :]
    raise ValueError(f"unknown traffic pattern: {pattern}")


@generator
def network_traffic_trace(
    n_tiles: int,
    pattern: str = "uniform_random",
    total_packets: int = 100,
    packet_size: int = 8,
    offered_load: float = 0.1,
    seed: int = 0,
) -> TraceBatch:
    """The synthetic_network benchmark as a trace program.

    Mirrors `sendNetworkTraffic` (`synthetic_network.cc:136-213`): each tile
    sends `total_packets` packets following the pattern schedule and receives
    the packets addressed to it; injection is Bernoulli(offered_load) per
    cycle, modeled as STALL records between sends (the reference advances
    `time` one cycle per loop iteration).  Receives are appended after sends
    (the reference drains receives with an outstanding window; ordering
    within a tile does not affect network timing because receives do not
    inject traffic).
    """
    dest = destinations(pattern, n_tiles)
    n_slots = dest.shape[0]
    rng = np.random.default_rng(seed)
    builders = [TraceBuilder() for _ in range(n_tiles)]

    # Precompute per-tile inter-send gaps (geometric with p=offered_load).
    for t in range(n_tiles):
        b = builders[t]
        for k in range(total_packets):
            if offered_load < 1.0:
                gap = int(rng.geometric(offered_load)) - 1
                if gap > 0:
                    # STALL cost accounted in ps at 1 GHz nominal; the engine
                    # rescales by tile frequency at replay.
                    b.dynamic(Op.STALL, cost_ps=gap * 1000)
            b.send(int(dest[k % n_slots][t]), packet_size)
        # Receive the packets addressed to this tile: one per slot from the
        # sender whose dest[slot] == t.
        recv_from = np.argwhere(dest == t)
        reps = total_packets // n_slots + (1 if total_packets % n_slots else 0)
        count = 0
        for rep in range(reps):
            for slot, sender in recv_from:
                if count >= total_packets:
                    break
                if (slot + rep * n_slots) < total_packets or n_slots == 1:
                    b.recv(int(sender), packet_size)
                    count += 1
        while count < total_packets:  # deterministic patterns: 1 sender
            b.recv(int(recv_from[0][1]), packet_size)
            count += 1
    return TraceBatch.from_builders(builders)


@generator
def memory_stress_trace(
    n_tiles: int,
    n_accesses: int = 1000,
    working_set_bytes: int = 1 << 20,
    write_fraction: float = 0.3,
    stride: int | None = None,
    shared_fraction: float = 0.0,
    cache_line_size: int = 64,
    seed: int = 0,
) -> TraceBatch:
    """Random/strided load-store streams (synthetic_memory analog).

    Each tile touches a private working set based at tile*working_set plus an
    optional shared region (for coherence stress).  Addresses are cache-line
    aligned +offset, never crossing a line.
    """
    rng = np.random.default_rng(seed)
    builders = []
    shared_base = (n_tiles + 1) * working_set_bytes
    for t in range(n_tiles):
        b = TraceBuilder()
        base = t * working_set_bytes
        for i in range(n_accesses):
            if stride is not None:
                offset = (i * stride) % working_set_bytes
            else:
                offset = int(rng.integers(0, working_set_bytes // 8)) * 8
            if shared_fraction > 0 and rng.random() < shared_fraction:
                addr = shared_base + offset % (working_set_bytes // 4)
            else:
                addr = base + offset
            addr -= addr % 8  # keep within one line
            if rng.random() < write_fraction:
                b.store(addr, 8, pc=0x1000 + (i % 256) * 4)
            else:
                b.load(addr, 8, pc=0x1000 + (i % 256) * 4)
        builders.append(b)
    return TraceBatch.from_builders(builders)


@generator
def ping_pong_trace(
    n_tiles: int = 2, n_rounds: int = 100, packet_size: int = 8
) -> TraceBatch:
    """tests/apps/ping_pong: tile 0 and 1 bounce a message back and forth."""
    assert n_tiles >= 2
    builders = [TraceBuilder() for _ in range(n_tiles)]
    for r in range(n_rounds):
        builders[0].send(1, packet_size)
        builders[0].recv(1, packet_size)
        builders[1].recv(0, packet_size)
        builders[1].send(0, packet_size)
    return TraceBatch.from_builders(builders)


def _batch_from_columns(op, *, flags=None, pc=None, aux0=None, aux1=None,
                        dyn_ps=None) -> TraceBatch:
    """Assemble a TraceBatch from [n_tiles, L] numpy columns (fast path)."""
    n, L = op.shape
    # append THREAD_EXIT column
    op = np.concatenate(
        [op, np.full((n, 1), int(Op.THREAD_EXIT), np.uint8)], axis=1
    )

    def pad(col, dtype, fill=0):
        if col is None:
            return np.full((n, L + 1), fill, dtype)
        return np.concatenate([col.astype(dtype),
                               np.full((n, 1), fill, dtype)], axis=1)

    from graphite_tpu.trace.schema import NO_REG

    return TraceBatch(
        op=op.astype(np.uint8),
        flags=pad(flags, np.uint8),
        pc=pad(pc, np.uint32),
        addr0=pad(None, np.uint32),
        addr1=pad(None, np.uint32),
        size0=pad(None, np.uint8),
        size1=pad(None, np.uint8),
        aux0=pad(aux0, np.int32),
        aux1=pad(aux1, np.int32),
        dyn_ps=pad(dyn_ps, np.int64),
        rreg0=pad(None, np.uint16, NO_REG),
        rreg1=pad(None, np.uint16, NO_REG),
        wreg=pad(None, np.uint16, NO_REG),
    )


@generator
def compute_mix_batch(
    n_tiles: int, n_instructions: int, seed: int = 0, branch_fraction: float = 0.1
) -> TraceBatch:
    """Vectorized large-scale compute mix (no per-record Python loop).

    The benchmark-scale analog of compute_mix_trace: ialu/mov/fmul/falu +
    branches with random outcomes.
    """
    rng = np.random.default_rng(seed)
    pool = np.array([int(Op.IALU), int(Op.MOV), int(Op.FMUL), int(Op.FALU)],
                    np.uint8)
    op = rng.choice(pool, size=(n_tiles, n_instructions))
    is_branch = rng.random((n_tiles, n_instructions)) < branch_fraction
    op = np.where(is_branch, np.uint8(int(Op.BRANCH)), op)
    taken = rng.random((n_tiles, n_instructions)) < 0.5
    from graphite_tpu.trace.schema import FLAG_BRANCH_TAKEN

    flags = np.where(is_branch & taken, np.uint8(FLAG_BRANCH_TAKEN), np.uint8(0))
    pc = (0x400000 + 4 * (np.arange(n_instructions, dtype=np.uint32) % 4096))[
        None, :
    ].repeat(n_tiles, axis=0)
    return _batch_from_columns(op, flags=flags, pc=pc)


@generator
def message_ring_batch(
    n_tiles: int,
    n_rounds: int,
    compute_per_round: int = 16,
    packet_size: int = 8,
    pattern: str = "nearest_neighbor",
    seed: int = 0,
    compressed: bool = False,
    cycles_per_instr: int = 1,
) -> TraceBatch:
    """Vectorized compute+communicate workload (the bench kernel).

    Each round: `compute_per_round` ialu instructions, one send following
    the traffic pattern, one receive (from whichever sender targets this
    tile) — a trace-program reduction of the synthetic_network send/recv
    loop (`synthetic_network.cc:136-213`).

    With `compressed=True` the per-round compute run is emitted as a single
    Op.BBLOCK record (aux0=count, aux1=count*cycles_per_instr) — identical
    simulated timing when the ialu static cost equals `cycles_per_instr`,
    at basic-block replay granularity.
    """
    dest = destinations(pattern, n_tiles)  # [n_slots, n_tiles]
    n_slots = dest.shape[0]
    # inverse: for slot s, sender[t] = who sends to t
    senders = np.empty_like(dest)
    for s in range(n_slots):
        senders[s, dest[s]] = np.arange(n_tiles, dtype=dest.dtype)

    n_compute_recs = 1 if compressed else compute_per_round
    L_round = n_compute_recs + 2
    L = n_rounds * L_round
    op = np.full((n_tiles, L), int(Op.IALU), np.uint8)
    aux0 = np.zeros((n_tiles, L), np.int32)
    aux1 = np.zeros((n_tiles, L), np.int32)
    send_cols = np.arange(n_rounds) * L_round + n_compute_recs
    recv_cols = send_cols + 1
    rounds = np.arange(n_rounds)
    if compressed:
        bblock_cols = np.arange(n_rounds) * L_round
        op[:, bblock_cols] = int(Op.BBLOCK)
        aux0[:, bblock_cols] = compute_per_round
        aux1[:, bblock_cols] = compute_per_round * cycles_per_instr
    op[:, send_cols] = int(Op.SEND)
    op[:, recv_cols] = int(Op.NET_RECV)
    aux0[:, send_cols] = dest[rounds % n_slots].T          # [n_tiles, n_rounds]
    aux0[:, recv_cols] = senders[rounds % n_slots].T
    aux1[:, send_cols] = packet_size
    aux1[:, recv_cols] = packet_size
    return _batch_from_columns(op, aux0=aux0, aux1=aux1)


@generator
def compute_mix_trace(
    n_tiles: int,
    n_instructions: int = 1000,
    mix: dict[Op, float] | None = None,
    seed: int = 0,
) -> TraceBatch:
    """A pure-compute instruction mix for core-model unit tests."""
    if mix is None:
        mix = {Op.IALU: 0.4, Op.MOV: 0.3, Op.FMUL: 0.1, Op.FALU: 0.1,
               Op.BRANCH: 0.1}
    ops = np.array([int(o) for o in mix], dtype=np.int32)
    probs = np.array(list(mix.values()))
    probs = probs / probs.sum()
    rng = np.random.default_rng(seed)
    builders = []
    for t in range(n_tiles):
        b = TraceBuilder()
        choices = rng.choice(ops, size=n_instructions, p=probs)
        takens = rng.random(n_instructions) < 0.5
        for i, op in enumerate(choices):
            pc = 0x400000 + 4 * i
            if op == int(Op.BRANCH):
                b.branch(bool(takens[i]), pc=pc)
            else:
                b.instr(Op(int(op)), pc=pc)
        builders.append(b)
    return TraceBatch.from_builders(builders)
