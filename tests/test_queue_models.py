"""Queue-model tests: reference-behavior checks + contention sweeps.

Mirrors the reference's queue-model usage: back-to-back packets on one
queue must serialize (`queue_model_basic.cc:36-61`), idle queues add no
delay, and the M/G/1 fallback reproduces the analytical waiting time
(`queue_model_m_g_1.cc:18-47`).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.config import ConfigFile
from graphite_tpu.models.queue_models import (
    QueueParams, _ceil_div_bounded, _mg1_wait, compute_queue_delay,
    make_queues,
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


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
