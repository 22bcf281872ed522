"""Co-located thread synchronization.

Threads sharing a tile serialize onto one engine lane; the live
frontend's completion-time recording + split sync ops
(BARRIER_ARRIVE/SYNC, COND_JOIN — `trace/schema.py`) make barriers,
condvars, mutexes, and CAPI pairs work between co-located threads (the
reference's ThreadScheduler allows arbitrary sync among queued threads,
`thread_scheduler.cc`).  Replays are also cross-checked against the
golden interpreter, which implements the split ops independently.
"""

import numpy as np

from graphite_tpu.config import ConfigFile, SimConfig
from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.frontend import (
    CAPI_message_receive_w,
    CAPI_message_send_w,
    CarbonApp,
    CarbonBarrier,
    CarbonCond,
    CarbonMutex,
    carbon_join_thread,
    carbon_spawn_thread,
    carbon_work,
)
from graphite_tpu.golden import run_golden
from graphite_tpu.trace.schema import TraceBatch, TraceBuilder


def make_config(n_tiles):
    text = f"""
[general]
total_cores = {n_tiles}
mode = lite
max_frequency = 1.0
enable_shared_mem = false
[network]
user = magic
memory = magic
[core/static_instruction_costs]
generic = 1
ialu = 1
[clock_skew_management]
scheme = lax_barrier
[clock_skew_management/lax_barrier]
quantum = 1000
"""
    return SimConfig(ConfigFile.from_string(text))


def run_app(n_tiles, main, max_threads=None):
    app = CarbonApp(make_config(n_tiles), max_threads=max_threads)
    batch = app.start(main)
    res = app.run()
    return app, batch, res


class TestColocatedBarrier:
    def test_barrier_across_colocated_threads(self):
        """3 threads on 1 tile + 1 on the other meet at one barrier."""
        hits = []

        def worker(bar):
            carbon_work(5)
            bar.wait()
            carbon_work(3)
            hits.append(1)

        def main():
            bar = CarbonBarrier(4)
            ts = [carbon_spawn_thread(worker, bar) for _ in range(3)]
            bar.wait()
            carbon_work(2)
            for t in ts:
                carbon_join_thread(t)
            hits.append(1)

        app, batch, res = run_app(2, main)
        assert len(hits) == 4
        assert (np.asarray(res.clock_ps) > 0).all()
        # at least two worker threads shared tile 1's lane
        assert res.sync_instructions.sum() >= 1

    def test_repeated_barrier_generations(self):
        """The generation rendezvous survives barrier reuse."""

        def worker(bar, rounds):
            for _ in range(rounds):
                carbon_work(4)
                bar.wait()

        def main():
            bar = CarbonBarrier(3)
            ts = [carbon_spawn_thread(worker, bar, 5) for _ in range(2)]
            for _ in range(5):
                carbon_work(2)
                bar.wait()
            for t in ts:
                carbon_join_thread(t)

        app, batch, res = run_app(2, main)
        assert (np.asarray(res.clock_ps) > 0).all()


class TestColocatedCond:
    def test_cond_between_colocated_threads(self):
        """Producer signals a condvar consumed by a co-located waiter."""
        got = []

        def consumer(mux, cond, box):
            with mux:
                while not box:
                    cond.wait()
                got.append(box.pop())

        def main():
            mux = CarbonMutex()
            cond = CarbonCond(mux)
            box = []
            t = carbon_spawn_thread(consumer, mux, cond, box)
            carbon_work(10)
            with mux:
                box.append(42)
                cond.signal()
            carbon_join_thread(t)

        app, batch, res = run_app(1, main)  # ONE tile: fully co-located
        assert got == [42]
        assert (np.asarray(res.clock_ps) > 0).all()


class TestColocatedCapiAndMutex:
    def test_capi_pair_colocated(self):
        """Send/recv between two threads on the same tile."""
        out = []

        def receiver():
            out.append(CAPI_message_receive_w(0, 0))

        def main():
            t = carbon_spawn_thread(receiver)
            carbon_work(6)
            CAPI_message_send_w(0, 0, 7)
            carbon_join_thread(t)

        app, batch, res = run_app(1, main)
        assert out == [7]
        assert (np.asarray(res.clock_ps) > 0).all()

    def test_mutex_contention_colocated(self):
        """Lock held by one co-located thread, contended by another."""
        order = []

        def worker(mux, k):
            with mux:
                carbon_work(8)
                order.append(k)

        def main():
            mux = CarbonMutex()
            ts = [carbon_spawn_thread(worker, mux, k) for k in range(3)]
            with mux:
                carbon_work(8)
            for t in ts:
                carbon_join_thread(t)

        app, batch, res = run_app(1, main)
        assert sorted(order) == [0, 1, 2]
        assert (np.asarray(res.clock_ps) > 0).all()


class TestSplitOpsGolden:
    """The split ops as trace programs, differential vs the oracle."""

    def test_arrive_sync_differential(self):
        bs = [TraceBuilder() for _ in range(3)]
        bs[0].barrier_init(0, 3)
        for r in range(4):
            for i, b in enumerate(bs):
                b.bblock(3 + i, 3 + i)
                b.barrier_arrive(0)
                b.barrier_sync(0, r + 1)
        batch = TraceBatch.from_builders(bs)
        sc = make_config(3)
        res = Simulator(sc, batch).run()
        gold = run_golden(sc, batch)
        np.testing.assert_array_equal(res.clock_ps, gold.clock_ps)
        np.testing.assert_array_equal(res.sync_instructions,
                                      gold.sync_instructions)

    def test_cond_join_differential(self):
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].cond_init(0)
        bs[0].barrier_init(1, 2)
        for b in bs:
            b.barrier_wait(1)
        # tile 0 publishes two signals; tile 1 joins each in turn
        bs[0].bblock(10, 10)
        bs[0].cond_signal(0, publish=True)
        bs[0].bblock(10, 10)
        bs[0].cond_broadcast(0, publish=True)
        bs[1].cond_join(0, 1)
        bs[1].bblock(2, 2)
        bs[1].cond_join(0, 2)
        batch = TraceBatch.from_builders(bs)
        sc = make_config(2)
        res = Simulator(sc, batch).run()
        gold = run_golden(sc, batch)
        np.testing.assert_array_equal(res.clock_ps, gold.clock_ps)

    def test_cond_join_lagging_reads_its_own_generation(self):
        """A joiner that replays after SEVERAL publishes must take its
        requested sequence's time, not the latest (per-generation ring)."""
        bs = [TraceBuilder() for _ in range(2)]
        bs[0].cond_init(0)
        bs[0].barrier_init(1, 2)
        for b in bs:
            b.barrier_wait(1)
        bs[0].bblock(10, 10)
        bs[0].cond_signal(0, publish=True)    # seq 1 at ~10 cycles
        bs[0].bblock(10, 10)
        bs[0].cond_signal(0, publish=True)    # seq 2 at ~20 cycles
        # tile 1 runs long compute first: by the time its joins replay,
        # both publishes already executed on tile 0's lane
        bs[1].bblock(100, 100)
        bs[1].cond_join(0, 1)
        bs[1].cond_join(0, 2)
        batch = TraceBatch.from_builders(bs)
        sc = make_config(2)
        res = Simulator(sc, batch).run()
        gold = run_golden(sc, batch)
        np.testing.assert_array_equal(res.clock_ps, gold.clock_ps)


class TestRotatingParticipants:
    def test_barrier_generations_with_skipping_threads(self):
        """A barrier reused by DIFFERENT thread pairs per round: the
        release generation is global, not per-thread arrival count."""
        def pair(bar):
            carbon_work(4)
            bar.wait()
            carbon_work(2)

        def main():
            bar = CarbonBarrier(2)
            # round 1: A + B; round 2: C + D (each thread waits once)
            a = carbon_spawn_thread(pair, bar)
            b = carbon_spawn_thread(pair, bar)
            carbon_join_thread(a)
            carbon_join_thread(b)
            c = carbon_spawn_thread(pair, bar)
            d = carbon_spawn_thread(pair, bar)
            carbon_join_thread(c)
            carbon_join_thread(d)

        app, batch, res = run_app(2, main, max_threads=8)
        assert (np.asarray(res.clock_ps) > 0).all()
