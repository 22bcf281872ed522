"""Vectorized contention queue models.

Reference: `common/shared_models/queue_models/` (SURVEY §2.8) — used by the
DRAM controller (`dram_perf_model.cc:95-100`) and the per-port NoC router
contention models (`components/router/router_model.h`).

Four reference models:
 - **basic** (`queue_model_basic.cc`): delay = max(0, queue_time - ref);
   queue_time = max(queue_time, ref) + processing; ref optionally a moving
   average of recent packet times (`[queue_model/basic]`).
 - **m_g_1** (`queue_model_m_g_1.cc`): analytical M/G/1 waiting time from
   running service-time moments.
 - **history_list / history_tree** (`queue_model_history_list.cc`,
   `queue_model_history_tree.cc:44-128`): free-interval bookkeeping with an
   M/G/1 fallback for packets older than the tracked window.  The interval
   list/tree is inherently sequential (SURVEY §7 hard part 3); the
   TPU-native form here is a **windowed tail** model: in-window packets get
   exact tail-append delays (equal to the list model when packets arrive in
   nondecreasing order, which the quantum engine's earliest-first message
   draining approximates), and packets that fall entirely before the
   tracked window use the same M/G/1 fallback.  Divergence is validated on
   synthetic traffic sweeps (tests/test_queue_models.py).

All state is struct-of-arrays over a leading queue axis; one call services
one packet per queue lane (masked), which is how the engines drive it (one
DRAM access per controller per subquantum iteration, one packet per router
port per iteration).

Masked-no-op invariant (load-bearing for the memory engines' per-phase
activity gating): a call whose mask is all-False leaves the queue state
BIT-IDENTICAL — masked lanes route to the scratch queue / contribute
zero deltas and max-with-zero against nonnegative times, never a real
mutation.  The gated engine phases (memory/engine.py, MemParams.
phase_gate) skip whole calls whose masks are provably all-False; that
skip is only bit-exact because of this invariant, so any new queue-state
write added here must preserve it.

Times are integer ns (the reference computes queue delays in ns/cycles at
1 GHz — `dram_perf_model.cc:80-91`).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from flax import struct

I64 = jnp.int64


@dataclasses.dataclass(frozen=True)
class QueueParams:
    kind: str = "history_tree"   # basic | m_g_1 | history_list | history_tree
    # [queue_model/basic]
    moving_avg_enabled: bool = True
    moving_avg_window: int = 64
    # [queue_model/history_list] / [queue_model/history_tree]
    max_list_size: int = 100
    analytical_enabled: bool = True
    # minimum processing time: sizes the tracked-history span
    min_processing_time: int = 1

    @classmethod
    def from_config(cls, cfg, kind: str, min_processing_time: int = 1):
        if kind in ("history_list", "history_tree"):
            sec = f"queue_model/{kind}"
            return cls(
                kind=kind,
                max_list_size=cfg.get_int(f"{sec}/max_list_size", 100),
                analytical_enabled=cfg.get_bool(
                    f"{sec}/analytical_model_enabled", True),
                min_processing_time=min_processing_time,
            )
        if kind == "basic":
            return cls(
                kind="basic",
                moving_avg_enabled=cfg.get_bool(
                    "queue_model/basic/moving_avg_enabled", False),
                moving_avg_window=cfg.get_int(
                    "queue_model/basic/moving_avg_window_size", 1),
                min_processing_time=min_processing_time,
            )
        if kind == "m_g_1":
            return cls(kind="m_g_1", min_processing_time=min_processing_time)
        raise ValueError(f"unknown queue model {kind!r}")

    @property
    def history_span(self) -> int:
        """Approximate span of the reference's interval list: at least
        max_list_size busy intervals of >= min_processing_time each."""
        return self.max_list_size * max(self.min_processing_time, 1)


# column layout of QueueArrays.data — one packed [N, 10] tensor.  The
# lane-per-queue path (`compute_queue_delay`) is elementwise column math
# and one stack.  `scatter_queue_delay`, where L lanes address N queues
# freely, reads by one-hot selection over the queue axis and commits by
# max / sum reductions over the lane axis and one stack of ten [N]
# columns: no gather, no scatter (a conflicting-index scatter runs one
# update after another on the TPU)
COL_QT = 0        # queue_time: end of the busy tail
COL_WS = 1        # window_start: oldest tracked time (history_*)
COL_NEWEST = 2    # newest_arrival (M/G/1 moments)
COL_SUM_ST = 3
COL_SUM_ST2 = 4
COL_N_ARR = 5
COL_REQS = 6      # total_requests (`updateQueueUtilizationCounters`)
COL_UTIL = 7      # total_utilized
COL_DELAY = 8     # total_delay
COL_ANA = 9       # analytical_used
N_COLS = 10


@struct.dataclass
class QueueArrays:
    """State for N independent queues (packed; see column layout above)."""

    data: jax.Array             # int64[N, 10]
    # moving average of packet times (basic, arithmetic mean over W)
    mavg_buf: jax.Array         # int64[N, W]
    mavg_pos: jax.Array         # int32[N]
    mavg_cnt: jax.Array         # int32[N]

    # read-only views (summaries, tests)
    @property
    def queue_time(self) -> jax.Array:
        return self.data[:, COL_QT]

    @property
    def window_start(self) -> jax.Array:
        return self.data[:, COL_WS]

    @property
    def newest_arrival(self) -> jax.Array:
        return self.data[:, COL_NEWEST]

    @property
    def sum_st(self) -> jax.Array:
        return self.data[:, COL_SUM_ST]

    @property
    def sum_st2(self) -> jax.Array:
        return self.data[:, COL_SUM_ST2]

    @property
    def n_arrivals(self) -> jax.Array:
        return self.data[:, COL_N_ARR]

    @property
    def total_requests(self) -> jax.Array:
        return self.data[:, COL_REQS]

    @property
    def total_utilized(self) -> jax.Array:
        return self.data[:, COL_UTIL]

    @property
    def total_delay(self) -> jax.Array:
        return self.data[:, COL_DELAY]

    @property
    def analytical_used(self) -> jax.Array:
        return self.data[:, COL_ANA]


def make_queues(n: int, params: QueueParams) -> QueueArrays:
    W = params.moving_avg_window if (
        params.kind == "basic" and params.moving_avg_enabled) else 1
    return QueueArrays(
        data=jnp.zeros((n, N_COLS), I64),
        mavg_buf=jnp.zeros((n, W), I64),
        mavg_pos=jnp.zeros(n, jnp.int32),
        mavg_cnt=jnp.zeros(n, jnp.int32),
    )


def _mg1_wait(n_arrivals, sum_st, sum_st2, newest_arrival) -> jax.Array:
    """`queue_model_m_g_1.cc:18-47` waiting-time formula, elementwise over
    running moments (shared by the lane-per-queue and scatter paths),
    evaluated EXACTLY in integers.

    With mu = n / sum_st, lambda = min(n / newest, 0.999 mu) and
    1 / mu^2 + var = sum_st2 / n, the reference's
    ceil(0.5 mu lambda (1 / mu^2 + var) / (mu - lambda)) is

        ceil(sum_st2 / (2 (newest - sum_st)))   while 1000 sum_st <= 999 newest
        ceil(999 sum_st2 / (2 sum_st))          saturated (the 0.999 cap)

    and the two agree where the cap sets in.  The reference evaluates it
    in doubles; so did this function until PR 42, whose first chip run
    found the TPU's emulated float64 a cycle off the CPU backend's on some
    reads (PERF.md section 6) - a statistic that depends on the backend is
    no statistic.  Where the exact value is a whole number the double
    version mostly read one cycle more (its roundings land just above)."""
    have = n_arrivals > 0
    st = jnp.maximum(sum_st, 1)
    below_cap = 1000 * st <= 999 * newest_arrival
    num = jnp.where(below_cap, sum_st2, 999 * sum_st2)
    den = jnp.where(below_cap, 2 * (newest_arrival - st), 2 * st)
    return jnp.where(have, _ceil_div_bounded(num, den), 0).astype(I64)


_WAIT_BITS = 32


def _ceil_div_bounded(num, den) -> jax.Array:
    """ceil(num / den) for int64 num >= 0, den >= 1 and a quotient below
    2^32 (a wait is at most 500 times the longest service time: 2^32
    cycles would take a packet of 2^23 flits), saturating there: restoring
    division over the quotient's 32 bits, shifts, compares and subtracts
    only.  XLA's own int64 division is emulated on the TPU at a cost the
    compiler shows (six of them: 2.7 times the compile of this program,
    +668 KB of code: PERF.md section 6)."""
    num = num + den - 1
    over = (num >> _WAIT_BITS) >= den
    q = jnp.zeros_like(num)
    r = num
    for bit in reversed(range(_WAIT_BITS)):
        fits = (r >> bit) >= den
        r = jnp.where(fits, r - (den << bit), r)
        q = jnp.where(fits, q | (1 << bit), q)
    return jnp.where(over, (1 << _WAIT_BITS) - 1, q)


def _mg1_delay(q: QueueArrays) -> jax.Array:
    return _mg1_wait(q.n_arrivals, q.sum_st, q.sum_st2, q.newest_arrival)


def compute_queue_delay(
    params: QueueParams,
    q: QueueArrays,
    pkt_time: jax.Array,      # int64[N]
    processing_time: jax.Array,  # int64[N]
    mask: jax.Array,          # bool[N] lanes with a packet this call
):
    """Vectorized `QueueModel::computeQueueDelay` (`queue_model.h:20`).

    Returns (new_state, delay int64[N]).  Each lane services its own queue
    (pure elementwise column math on the packed state — one fused kernel).
    """
    pkt_time = jnp.asarray(pkt_time, I64)
    proc = jnp.maximum(jnp.asarray(processing_time, I64), 1)
    qt = q.queue_time
    ws = q.window_start
    newest = q.newest_arrival

    if params.kind == "basic":
        if params.moving_avg_enabled:
            W = params.moving_avg_window
            n = q.mavg_buf.shape[0]
            lanes = jnp.arange(n)
            buf = q.mavg_buf.at[lanes, q.mavg_pos].set(
                jnp.where(mask, pkt_time, q.mavg_buf[lanes, q.mavg_pos]))
            cnt = jnp.minimum(q.mavg_cnt + mask.astype(jnp.int32), W)
            ref = jnp.where(
                cnt > 0, buf.sum(axis=1) // jnp.maximum(cnt, 1), pkt_time
            ).astype(I64)
            q = q.replace(
                mavg_buf=buf,
                mavg_pos=jnp.where(mask, (q.mavg_pos + 1) % W, q.mavg_pos),
                mavg_cnt=cnt,
            )
        else:
            ref = pkt_time
        delay = jnp.maximum(qt - ref, 0)
        new_qt = jnp.where(mask, jnp.maximum(qt, ref) + proc, qt)
        new_ws = ws
        mg1_mask = jnp.zeros_like(mask)
        analytical = jnp.zeros_like(mask)

    elif params.kind == "m_g_1":
        delay = _mg1_delay(q)
        new_qt = qt
        new_ws = ws
        mg1_mask = mask
        analytical = mask

    else:  # history_list / history_tree (windowed tail + M/G/1 fallback)
        too_old = params.analytical_enabled & (
            (pkt_time + proc) < ws)
        mg1 = _mg1_delay(q)
        tail = jnp.maximum(qt - pkt_time, 0)
        delay = jnp.where(too_old, mg1, tail)
        in_window = mask & ~too_old
        cand_qt = jnp.maximum(qt, pkt_time) + proc
        new_qt = jnp.where(in_window, cand_qt, qt)
        new_ws = jnp.where(
            in_window,
            jnp.maximum(ws, cand_qt - params.history_span), ws)
        mg1_mask = mask
        analytical = mask & too_old

    end = pkt_time + delay + proc
    new_data = jnp.stack([
        new_qt,
        new_ws,
        jnp.where(mg1_mask, jnp.maximum(newest, end), newest),
        q.sum_st + jnp.where(mg1_mask, proc, 0),
        q.sum_st2 + jnp.where(mg1_mask, proc * proc, 0),
        q.n_arrivals + mg1_mask.astype(I64),
        q.total_requests + mask.astype(I64),
        q.total_utilized + jnp.where(mask, proc, 0),
        q.total_delay + jnp.where(mask, delay, 0),
        q.analytical_used + analytical.astype(I64),
    ], axis=1)
    return q.replace(data=new_data), jnp.where(mask, delay, 0)


def scatter_queue_delay(
    params: QueueParams,
    q: QueueArrays,
    qid: jax.Array,           # int32[L] queue index per lane
    pkt_time: jax.Array,      # int64[L]
    processing_time: jax.Array,  # int64[L]
    mask: jax.Array,          # bool[L]
):
    """Queue delay where lanes address arbitrary (possibly shared) queues.

    Used by the NoC router ports and the ATAC hubs: several packets can
    traverse the same output port in one vectorized hop step.  Same-call
    conflicts read the same pre-state (each gets the tail delay as of the
    call) while occupancy accumulates exactly (the max of the arrivals,
    then the sum of every processing time), so the busy tail — and
    therefore every *later* packet's delay — stays exact; only
    simultaneous arrivals at one port underestimate each other's mutual
    wait.  Bounded, documented divergence vs the reference's strictly
    serial `computeQueueDelay` (`queue_model.h:20`).

    Lanes must route masked-off traffic to a scratch queue (last index).
    A live lane whose `qid` lies outside [0, N) addresses no queue: it
    reads a delay of 0 and commits nothing.

    Lowered dense (PR 49; `_hand/hubq49.py` has the v5e's price of a call
    against the gather / scatter form it replaced, which
    `tests/test_queue_models.py` keeps as the reference — the two are
    BIT-IDENTICAL on every column of every row and on every lane's delay,
    integer max and add being associative and commutative): a lane reads
    its queue by one-hot selection over the queue axis, the M/G/1 wait is
    evaluated once per QUEUE, and every column commits by a max or sum
    reduction over the lane axis — each reduction a fusion of its own over
    the [L, N] compare, which is never materialised.  A row no live lane
    addresses keeps its bits: a masked lane sits on the scratch row with
    the identities 0 for a sum and for the two time maxima and -2^62 for
    the window start, and a row no lane addresses at all sees the int64
    minimum.
    """
    pkt_time = jnp.asarray(pkt_time, I64)
    proc = jnp.maximum(jnp.asarray(processing_time, I64), 1)
    data = q.data
    N = data.shape[0]
    qid = jnp.where(mask, qid, N - 1).astype(jnp.int32)
    oh = qid[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]   # [L, N]
    lowest = jnp.iinfo(I64).min

    def of_queue(col):       # [N] -> [L]: the value at the lane's queue
        return jnp.where(oh, col[None, :], 0).sum(axis=1)

    def lane_sum(val):       # [L] -> [N]
        return jnp.where(oh, val[:, None], 0).sum(axis=0)

    def lane_max(val):       # [L] -> [N]
        return jnp.where(oh, val[:, None], lowest).max(axis=0)

    qt, ws, newest = data[:, COL_QT], data[:, COL_WS], data[:, COL_NEWEST]
    if params.kind in ("history_list", "history_tree"):
        # M/G/1 fallback from the queue's running moments, once a QUEUE
        mg1 = _mg1_wait(data[:, COL_N_ARR], data[:, COL_SUM_ST],
                        data[:, COL_SUM_ST2], newest)              # [N]
        qt_lane, ws_lane, mg1_lane = of_queue(qt), of_queue(ws), of_queue(mg1)
        tail = jnp.maximum(qt_lane - pkt_time, 0)
        too_old = params.analytical_enabled & ((pkt_time + proc) < ws_lane)
        delay = jnp.where(too_old, mg1_lane, tail)
        in_window = mask & ~too_old
    else:  # basic semantics (no moving average in scatter form)
        delay = jnp.maximum(of_queue(qt) - pkt_time, 0)
        in_window = mask
        too_old = jnp.zeros_like(mask)

    # occupancy: the max of the arrivals, then the sum of every processing
    busy = lane_sum(jnp.where(in_window, proc, 0))
    qt_new = jnp.maximum(
        qt, lane_max(jnp.where(in_window, pkt_time, 0))) + busy
    # every in-window lane of a queue reads the same new tail, and books
    # proc >= 1 there: `busy > 0` is "an in-window lane addressed it"
    ws_new = jnp.maximum(
        ws, jnp.where(busy > 0, qt_new - params.history_span, -(2**62)))
    end = pkt_time + delay + proc
    st = lane_sum(jnp.where(mask, proc, 0))
    count = lane_sum(mask.astype(I64))
    data = jnp.stack([
        qt_new,
        ws_new,
        jnp.maximum(newest, lane_max(jnp.where(mask, end, 0))),
        data[:, COL_SUM_ST] + st,
        data[:, COL_SUM_ST2] + lane_sum(jnp.where(mask, proc * proc, 0)),
        data[:, COL_N_ARR] + count,
        data[:, COL_REQS] + count,
        data[:, COL_UTIL] + st,
        data[:, COL_DELAY] + lane_sum(jnp.where(mask, delay, 0)),
        data[:, COL_ANA] + lane_sum((mask & too_old).astype(I64)),
    ], axis=1)
    return q.replace(data=data), jnp.where(mask, delay, 0)
