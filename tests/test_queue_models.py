"""Queue-model tests: reference-behavior checks + contention sweeps.

Mirrors the reference's queue-model usage: back-to-back packets on one
queue must serialize (`queue_model_basic.cc:36-61`), idle queues add no
delay, and the M/G/1 fallback reproduces the analytical waiting time
(`queue_model_m_g_1.cc:18-47`).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile
from graphite_tpu.models.queue_models import (
    COL_ANA, COL_DELAY, COL_N_ARR, COL_NEWEST, COL_QT, COL_REQS, COL_SUM_ST,
    COL_SUM_ST2, COL_UTIL, COL_WS, QueueParams, _ceil_div_bounded, _mg1_wait,
    compute_queue_delay, make_queues, scatter_queue_delay,
)


def drive(params, arrivals, procs):
    """Drive one queue (lane 0) through a packet sequence; returns delays."""
    q = make_queues(1, params)
    m = jnp.asarray([True])
    out = []
    for t, p in zip(arrivals, procs):
        q, d = compute_queue_delay(
            params, q, jnp.asarray([t], jnp.int64), jnp.asarray([p], jnp.int64), m)
        out.append(int(d[0]))
    return out, q


class TestBasic:
    def test_idle_queue_no_delay(self):
        p = QueueParams(kind="basic", moving_avg_enabled=False)
        delays, _ = drive(p, [100, 300, 600], [10, 10, 10])
        assert delays == [0, 0, 0]

    def test_back_to_back_serializes(self):
        # pkt at t=0 (proc 10) -> queue busy till 10; pkt at t=0 waits 10;
        # pkt at t=5 waits 15 (`queue_time - ref_time`)
        p = QueueParams(kind="basic", moving_avg_enabled=False)
        delays, q = drive(p, [0, 0, 5], [10, 10, 10])
        assert delays == [0, 10, 15]
        assert int(q.total_delay[0]) == 25
        assert int(q.total_utilized[0]) == 30

    def test_vectorized_lanes_independent(self):
        p = QueueParams(kind="basic", moving_avg_enabled=False)
        q = make_queues(2, p)
        t = jnp.asarray([0, 0], jnp.int64)
        pr = jnp.asarray([10, 20], jnp.int64)
        m = jnp.asarray([True, True])
        q, d0 = compute_queue_delay(p, q, t, pr, m)
        q, d1 = compute_queue_delay(p, q, t, pr, m)
        assert d0.tolist() == [0, 0]
        assert d1.tolist() == [10, 20]

    def test_mask_skips_lane(self):
        p = QueueParams(kind="basic", moving_avg_enabled=False)
        q = make_queues(1, p)
        q, d = compute_queue_delay(
            p, q, jnp.asarray([0], jnp.int64), jnp.asarray([10], jnp.int64),
            jnp.asarray([False]))
        assert int(q.queue_time[0]) == 0
        assert int(q.total_requests[0]) == 0


class TestMG1:
    def test_first_packet_free(self):
        p = QueueParams(kind="m_g_1")
        delays, _ = drive(p, [0], [10])
        assert delays == [0]

    def test_matches_reference_formula(self):
        # Constant service time s, arrivals at rate lambda: M/D/1 wait =
        # 0.5 * mu * lam * (1/mu^2) / (mu - lam)
        p = QueueParams(kind="m_g_1")
        s = 10
        arrivals = list(range(0, 2000, 40))  # lam = 1/40, mu = 1/10
        delays, q = drive(p, arrivals, [s] * len(arrivals))
        mu, lam_exp = 1.0 / s, 1.0 / 40
        # after warmup the delay settles near the analytical value
        # (arrival rate estimated from newest_arrival)
        expect = 0.5 * mu * lam_exp * (1 / mu**2) / (mu - lam_exp)
        tail = delays[-5:]
        assert all(abs(d - expect) <= 2 for d in tail), (tail, expect)


    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_wait_is_the_formulas_exact_value(self, seed):
        """The waiting time is integer arithmetic (PR 42: the TPU's
        emulated float64 read another cycle than the CPU's on some
        inputs), equal to the reference's formula taken as a fraction -
        the double evaluation reads one cycle more on most inputs whose
        exact value is a whole number."""
        from fractions import Fraction
        from math import ceil

        rng = np.random.default_rng(seed)
        n = rng.integers(1, 3000, 400)
        procs = [rng.choice([2, 9, 65, 513], k) for k in n]
        st = np.array([p.sum() for p in procs])
        st2 = np.array([(p * p).sum() for p in procs])
        newest = (st * rng.uniform(0.3, 3.0, 400)).astype(np.int64)
        got = np.asarray(_mg1_wait(*map(jnp.asarray, (n, st, st2, newest))))
        for i in range(400):
            mu = Fraction(int(n[i]), int(st[i]))
            lam = min(Fraction(int(n[i]), max(int(newest[i]), 1))
                      if newest[i] else 2 * mu, Fraction(999, 1000) * mu)
            var = Fraction(int(st2[i]), int(n[i])) - 1 / (mu * mu)
            want = ceil(Fraction(1, 2) * mu * lam * (1 / (mu * mu) + var)
                        / (mu - lam))
            assert got[i] == want, (i, n[i], st[i], st2[i], newest[i])
        assert int(_mg1_wait(*map(jnp.asarray, ([0], [0], [0], [0])))[0]) == 0

    def test_bounded_division(self):
        rng = np.random.default_rng(3)
        num = rng.integers(0, 2**44, 4000)
        den = np.concatenate([rng.integers(1, 1000, 2000),
                              rng.integers(1, 2**34, 2000)])
        got = np.asarray(_ceil_div_bounded(jnp.asarray(num), jnp.asarray(den)))
        want = np.minimum(-(-num // den), 2**32 - 1)
        np.testing.assert_array_equal(got, want)
        assert (want == 2**32 - 1).any() and (want < 1000).any()


class TestHistoryWindowed:
    def test_in_window_matches_basic_tail(self):
        ph = QueueParams(kind="history_tree", max_list_size=100,
                         min_processing_time=10)
        pb = QueueParams(kind="basic", moving_avg_enabled=False)
        seq = [(0, 10), (0, 10), (5, 10), (100, 10), (101, 10)]
        dh, _ = drive(ph, [a for a, _ in seq], [p for _, p in seq])
        db, _ = drive(pb, [a for a, _ in seq], [p for _, p in seq])
        assert dh == db

    def test_old_packet_uses_analytical(self):
        p = QueueParams(kind="history_tree", max_list_size=2,
                        min_processing_time=5)
        # push window far ahead, then send an ancient packet
        arrivals = [1000, 1005, 1010, 1015]
        q = make_queues(1, p)
        m = jnp.asarray([True])
        for t in arrivals:
            q, _ = compute_queue_delay(
                p, q, jnp.asarray([t], jnp.int64), jnp.asarray([5], jnp.int64), m)
        assert int(q.window_start[0]) > 0
        q, d = compute_queue_delay(
            p, q, jnp.asarray([1], jnp.int64), jnp.asarray([5], jnp.int64), m)
        assert int(q.analytical_used[0]) == 1

    def test_config_resolution(self):
        cfg = ConfigFile.from_string("""
[queue_model/history_tree]
max_list_size = 77
analytical_model_enabled = false
""")
        p = QueueParams.from_config(cfg, "history_tree", 13)
        assert p.max_list_size == 77
        assert not p.analytical_enabled
        assert p.history_span == 77 * 13


class TestContentionSweep:
    @pytest.mark.parametrize("load", [0.2, 0.5, 0.8])
    def test_utilization_tracks_offered_load(self, load):
        """Windowed-tail delay grows with offered load and stays near the
        exact sequential free-list computation for in-order arrivals."""
        rng = np.random.default_rng(42)
        s = 10
        gap = s / load
        arrivals = np.cumsum(rng.exponential(gap, 500)).astype(np.int64)
        p = QueueParams(kind="history_tree", min_processing_time=s)
        delays, q = drive(p, arrivals.tolist(), [s] * len(arrivals))
        # exact sequential reference (tail model is exact for sorted input)
        qt, exact = 0, []
        for t in arrivals:
            d = max(0, qt - t)
            exact.append(d)
            qt = max(qt, t) + s
        assert delays == exact


def _gather_scatter_reference(params, q, qid, pkt_time, proc, mask):
    """`scatter_queue_delay` as it was lowered up to PR 48, the reference
    of the dense form: ONE gather of the lanes' rows, the M/G/1 wait per
    LANE, four scatters with conflicting indices."""
    N = q.data.shape[0]
    proc = jnp.maximum(proc, 1)
    qid = jnp.where(mask, qid, N - 1).astype(jnp.int32)
    row = q.data[qid]
    qt = row[:, COL_QT]
    if params.kind in ("history_list", "history_tree"):
        too_old = params.analytical_enabled & (
            (pkt_time + proc) < row[:, COL_WS])
        mg1 = _mg1_wait(row[:, COL_N_ARR], row[:, COL_SUM_ST],
                        row[:, COL_SUM_ST2], row[:, COL_NEWEST])
        delay = jnp.where(too_old, mg1, jnp.maximum(qt - pkt_time, 0))
        in_window = mask & ~too_old
    else:
        delay = jnp.maximum(qt - pkt_time, 0)
        in_window = mask
        too_old = jnp.zeros_like(mask)
    data = q.data.at[qid, COL_QT].max(jnp.where(in_window, pkt_time, 0))
    data = data.at[qid, COL_QT].add(jnp.where(in_window, proc, 0))
    qt_new = data[qid, COL_QT]
    end = pkt_time + delay + proc
    data = data.at[qid[:, None], jnp.asarray([COL_WS, COL_NEWEST])[None, :]
                   ].max(jnp.stack([
                       jnp.where(in_window, qt_new - params.history_span,
                                 -(2**62)),
                       jnp.where(mask, end, 0)], axis=1))
    live = mask.astype(jnp.int64)
    data = data.at[qid[:, None], jnp.asarray(
        [COL_SUM_ST, COL_SUM_ST2, COL_N_ARR, COL_REQS, COL_UTIL, COL_DELAY,
         COL_ANA])[None, :]].add(jnp.stack([
             live * proc, live * proc * proc, live, live, live * proc,
             live * delay, (mask & too_old).astype(jnp.int64)], axis=1))
    return q.replace(data=data), jnp.where(mask, delay, 0)


@functools.lru_cache(maxsize=None)
def _forms(kind):
    p = QueueParams(kind=kind, max_list_size=100, min_processing_time=1)
    return p, tuple(jax.jit(functools.partial(form, p)) for form in
                    (scatter_queue_delay, _gather_scatter_reference))


def _traffic(case, rng, step, N, L):
    """(qid, pkt_time, proc, mask) of one call: lanes on random queues at
    times around a clock that moves ~150 cycles a call, under a 3% or a
    70% mask, masked lanes on the scratch row as the callers put them."""
    mask = rng.random(L) < (0.03 if step % 2 else 0.7)
    qid = rng.integers(0, N - 1, L)
    t = np.maximum(150 * step + rng.integers(-400, 50, L), 0)
    proc = rng.choice([1, 9], L)
    if case == "one_queue":
        qid[:] = N // 2
    elif case == "all_masked" and step >= 10:
        mask[:] = False
    elif case == "old_packets":
        t = np.where(rng.random(L) < 0.5, t // 8, t)
    elif case == "sweep_1008":
        proc = np.where(rng.random(L) < 0.1, 1008, proc)
    qid = np.where(mask, qid, N - 1)
    return (jnp.asarray(qid, jnp.int32), jnp.asarray(t, jnp.int64),
            jnp.asarray(proc, jnp.int64), jnp.asarray(mask))


class TestDenseArm:
    """`scatter_queue_delay` is lowered dense since PR 49: reductions over
    the lane axis against the gather / scatter form it replaced, which is
    the reference here as the loop version is of a vectorized one."""

    @pytest.mark.parametrize("case", ["mixed", "one_queue", "all_masked",
                                      "old_packets", "sweep_1008"])
    @pytest.mark.parametrize("shape", [(129, 1024), (5, 64)],
                             ids=["129x1024", "5x64"])
    @pytest.mark.parametrize("kind", ["history_tree", "basic"])
    def test_equals_the_gather_scatter_reference(self, kind, shape, case):
        """All ten columns of every row (the scratch row too) and every
        lane's delay, through 60 chained calls with conflicting indices."""
        N, L = shape
        p, (dense, scatter) = _forms(kind)
        rng = np.random.default_rng(49)
        d = s = make_queues(N, p)
        for step in range(60):
            args = _traffic(case, rng, step, N, L)
            before = np.asarray(d.data)
            d, delay_d = dense(d, *args)
            s, delay_s = scatter(s, *args)
            np.testing.assert_array_equal(np.asarray(d.data),
                                          np.asarray(s.data))
            np.testing.assert_array_equal(np.asarray(delay_d),
                                          np.asarray(delay_s))
            if not bool(args[3].any()):
                # the masked-no-op invariant the phase gates rest on
                np.testing.assert_array_equal(np.asarray(d.data), before)
        d = np.asarray(d.data)
        assert d[:, COL_REQS].sum() > 0
        if case == "all_masked":
            assert not bool(args[3].any())
        if case == "one_queue":
            assert (d[:, COL_REQS] > 0).sum() == 1
        if case == "old_packets":
            # the M/G/1 arm is taken (history_*) / does not exist (basic)
            assert (d[:, COL_ANA].sum() > 0) == (kind != "basic")

    def test_a_lane_outside_the_table_addresses_no_queue(self):
        """A live lane whose queue index is not in [0, N) reads a delay of
        0 and commits nothing (no wrap onto a real row, no clamp)."""
        p, (dense, _) = _forms("history_tree")
        q = make_queues(5, p)
        proc, live = jnp.full(4, 9, jnp.int64), jnp.ones(4, bool)
        q, _ = dense(q, jnp.asarray([0, 1, 2, 3], jnp.int32),
                     jnp.full(4, 100, jnp.int64), proc, live)
        after, delay = dense(q, jnp.asarray([-1, 5, 2, -5], jnp.int32),
                             jnp.full(4, 101, jnp.int64), proc, live)
        assert delay.tolist() == [0, 0, 8, 0]
        rest = [0, 1, 3, 4]
        np.testing.assert_array_equal(np.asarray(after.data)[rest],
                                      np.asarray(q.data)[rest])
        assert int(after.data[2, COL_REQS]) == 2


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
