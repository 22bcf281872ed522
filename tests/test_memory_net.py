"""The MEMORY network under emesh_hop_by_hop: coherence traffic sees
per-port contention.

Reference: every ShmemMsg routes through the configured memory network
model (`carbon_sim.cfg:281-282` memory_model_1; per-hop queues
`network_model_emesh_hop_by_hop.cc:146-265`); `tests/benchmarks/
synthetic_memory` is the reference's stress generator for exactly this.

Contract (BASELINE.md carve-outs):
 - serialized coherence traffic is BIT-EXACT vs the golden oracle's
   independent serial per-hop net (unicast flows fully independent;
   fan-out multicasts share the engine's documented inject+rank
   approximation);
 - hop_by_hop must CHANGE measured completion vs hop_counter (the
   round-2 gap was that `memory = emesh_hop_by_hop` silently degraded
   to zero-load);
 - memory = atac routes coherence messages over the optical NoC
   (clusters/hubs/waveguide, hub contention) — serialized-bit-exact vs
   the serial `_AtacNet` oracle, including ackwise broadcast sweeps.
"""

import numpy as np
import pytest

import functools

from graphite_tpu.engine.simulator import Simulator
from graphite_tpu.golden import run_golden
from graphite_tpu.trace import synthetic
from graphite_tpu.trace.schema import TraceBatch, TraceBuilder

from targets import (
    MOSI, MSI, fresh_mem_noc, mem_net_at, memory_config,
)

make_config = functools.partial(memory_config, net="emesh_hop_by_hop")


def assert_exact(sc, batch):
    res = Simulator(sc, batch).run()
    gold = run_golden(sc, batch)
    np.testing.assert_array_equal(res.clock_ps, gold.clock_ps,
                                  err_msg="clock")
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)
    return res, gold


def mutex_rmw(n, rounds, base=0x900000, lines=2):
    """Mutex-serialized shared-line read-modify-writes: at any moment one
    tile touches the shared data, so engine iteration order and oracle
    clock order coincide — the bit-exactness regime.  (Every line under
    each lock: not `targets.mutex_rmw`, which takes one a round.)"""
    bs = [TraceBuilder() for _ in range(n)]
    bs[0].mutex_init(0)
    bs[0].barrier_init(9, n)
    for b in bs:
        b.barrier_wait(9)
    for r in range(n * rounds):
        b = bs[r % n]
        b.mutex_lock(0)
        for ln in range(lines):
            addr = base + 64 * ln
            b.load(addr, 8)
            b.store(addr, 8)
        b.mutex_unlock(0)
    return TraceBatch.from_builders(bs)


def disjoint_stream(n, accesses=60):
    """Line-disjoint per-tile streams (capacity misses, no sharing)."""
    bs = [TraceBuilder() for _ in range(n)]
    for t, b in enumerate(bs):
        for i in range(accesses):
            addr = 0x100000 + (t * accesses + i) * 64
            (b.store if i % 3 == 0 else b.load)(addr, 8)
    return TraceBatch.from_builders(bs)


@pytest.mark.parametrize("proto", [MSI, MOSI])
def test_serialized_bit_exact_vs_oracle(proto):
    sc = make_config(4, proto)
    assert_exact(sc, mutex_rmw(4, rounds=6))


@pytest.mark.parametrize("proto", [MSI, MOSI])
def test_disjoint_concurrent_envelope(proto):
    """Line-disjoint CONCURRENT streams are exact under zero-load nets
    (test_memory_golden), but under hop_by_hop they contend for router
    ports, so the same-call batching contract applies (packets of one
    subquantum iteration see each other's occupancy only next iteration
    — `scatter_queue_delay` contract): measured 4.8%, pinned at 7%
    (BASELINE.md carve-outs; the USER net's adversarial case pins 15%).
    Counters stay exact — contention shifts time, never traffic."""
    sc = make_config(4, proto)
    batch = disjoint_stream(4)
    res = Simulator(sc, batch).run()
    gold = run_golden(sc, batch)
    rel = np.abs(res.clock_ps.astype(float) - gold.clock_ps.astype(float))
    rel = rel / np.maximum(gold.clock_ps.astype(float), 1.0)
    assert rel.max() <= 0.07, (
        f"divergence {rel.max():.4f}: engine={res.clock_ps.tolist()} "
        f"golden={gold.clock_ps.tolist()}")
    for k, g in gold.mem_counters.items():
        np.testing.assert_array_equal(np.asarray(res.mem_counters[k]), g,
                                      err_msg=k)
    assert int(gold.mem_counters["l2_misses"].sum()) > 0


def test_hbh_memory_changes_completion():
    """The contention-modeled memory net must produce different (higher)
    completion times than zero-load hop-counter under load — the silent
    hop_by_hop -> hop_counter degrade would make these equal."""
    batch = synthetic.memory_stress_trace(
        16, n_accesses=80, working_set_bytes=1 << 13,
        write_fraction=0.4, shared_fraction=0.5, seed=3)
    r_zero = Simulator(make_config(16, net="emesh_hop_counter"),
                       batch).run()
    r_hbh = Simulator(make_config(16, net="emesh_hop_by_hop"),
                      batch).run()
    assert r_hbh.completion_time_ps != r_zero.completion_time_ps
    # contention only ever adds latency on top of an identical zero-load
    # basis... but hop_by_hop's zero-load basis itself differs (router
    # charge + per-hop router+link on the SELF hop), so just require a
    # strictly larger completion under heavy shared traffic
    assert r_hbh.completion_time_ps > r_zero.completion_time_ps


def test_racy_envelope_vs_oracle():
    """Free-running shared traffic under the contention-modeled memory
    net compounds BOTH carve-outs (same-line race resolution ~3% +
    same-call port batching ~7%; BASELINE.md): measured 5.2%, pinned at
    their sum's ballpark, 8%."""
    sc = make_config(4, MSI)
    batch = synthetic.memory_stress_trace(
        4, n_accesses=150, working_set_bytes=1 << 13,
        write_fraction=0.4, shared_fraction=0.3, seed=5)
    res = Simulator(sc, batch).run()
    gold = run_golden(sc, batch)
    rel = np.abs(res.clock_ps.astype(float) - gold.clock_ps.astype(float))
    rel = rel / np.maximum(gold.clock_ps.astype(float), 1.0)
    assert rel.max() <= 0.08, (
        f"clock divergence {rel.max():.4f} exceeds envelope: "
        f"engine={res.clock_ps.tolist()} golden={gold.clock_ps.tolist()}")
    for k in ("l2_misses", "dram_reads"):
        e = int(np.asarray(res.mem_counters[k]).sum())
        g = int(gold.mem_counters[k].sum())
        assert abs(e - g) <= max(2, 0.02 * max(e, g)), f"{k}: {e} vs {g}"


ATAC_EXTRA = """
[network/atac]
flit_width = 64
cluster_size = 4
receive_network_type = star
global_routing_strategy = cluster_based
unicast_distance_threshold = 4
[network/atac/queue_model]
enabled = true
type = history_tree
[network/atac/enet/router]
delay = 1
[network/atac/onet/send_hub/router]
delay = 1
[network/atac/onet/receive_hub/router]
delay = 1
[network/atac/star_net/router]
delay = 1
[link_model/optical]
waveguide_delay_per_mm = 10e-3
E-O_conversion_delay = 1
O-E_conversion_delay = 1
"""


def test_atac_memory_serialized_bit_exact():
    """`[network] memory = atac` (any-model-per-net factory,
    `network.cc:21-40`): coherence messages ride the clusters/hubs/
    waveguide with hub contention on the memory NoC's own state.
    Serialized traffic is bit-exact vs the serial hub-queue oracle
    (`_AtacNet`), crossing clusters so the ONet path carries real
    protocol messages."""
    sc = make_config(16, MSI, net="atac", extra=ATAC_EXTRA)
    res, gold = assert_exact(sc, mutex_rmw(16, rounds=3, lines=2))
    assert int(np.asarray(res.mem_counters["l2_misses"]).sum()) > 0


def test_atac_memory_ackwise_broadcast_exact():
    """Overflowed-entry INV sweep under memory = atac: the broadcast
    charges the home's SEND HUB with its ONet copies and ranks every
    copy by tile id — mirrored exactly by `_AtacNet.fanout` on
    serialized traffic."""
    extra = ATAC_EXTRA + \
        "[dram_directory]\ndirectory_type = ackwise\nmax_hw_sharers = 2\n"
    sc = make_config(16, MSI, net="atac", extra=extra)
    bs = [TraceBuilder() for _ in range(16)]
    bs[0].mutex_init(0)
    bs[0].barrier_init(9, 16)
    for b in bs:
        b.barrier_wait(9)
    for t, b in enumerate(bs):
        b.mutex_lock(0)
        b.load(0x900000, 8)
        b.mutex_unlock(0)
    for b in bs:
        b.barrier_wait(9)
    # the writer sits in a DIFFERENT cluster than the home tile and
    # still holds the line: its own sweep copy and the cross-cluster
    # hub charge must match the oracle exactly (the engine's broadcast
    # row is holders | (all tiles except the requester))
    bs[10].mutex_lock(0)
    bs[10].store(0x900000, 8)
    bs[10].mutex_unlock(0)
    # follow-on cross-cluster traffic reads the hub queue the sweep
    # occupied — catches under-charged hub occupancy, not just arrivals
    for b in bs:
        b.barrier_wait(9)
    for t in (1, 5, 10, 15):
        bs[t].mutex_lock(0)
        bs[t].load(0x900000 + 64, 8)
        bs[t].mutex_unlock(0)
    res, gold = assert_exact(sc, TraceBatch.from_builders(bs))
    assert int(gold.mem_counters["dir_broadcasts"].sum()) > 0


def test_atac_memory_changes_timing():
    """The ATAC wiring is live: completion differs from the zero-load
    hop-counter memory net on the same workload."""
    batch = synthetic.memory_stress_trace(
        16, n_accesses=30, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=3)
    r_hc = Simulator(make_config(16, net="emesh_hop_counter"), batch).run()
    r_at = Simulator(make_config(16, net="atac", extra=ATAC_EXTRA),
                     batch).run()
    assert r_at.completion_time_ps != r_hc.completion_time_ps


def test_shl2_hbh_runs():
    """The shared-L2 engines route through the same contention net; smoke
    that the wiring compiles and produces traffic-dependent times."""
    batch = synthetic.memory_stress_trace(
        8, n_accesses=40, working_set_bytes=1 << 12,
        write_fraction=0.4, shared_fraction=0.5, seed=2)
    r_zero = Simulator(make_config(8, proto="pr_l1_sh_l2_msi",
                                   net="emesh_hop_counter"), batch).run()
    r_hbh = Simulator(make_config(8, proto="pr_l1_sh_l2_msi",
                                  net="emesh_hop_by_hop"), batch).run()
    assert r_hbh.completion_time_ps > r_zero.completion_time_ps


def test_ackwise_broadcast_fanout_exact():
    """Overflowed-entry INV sweep under the contention-modeled memory
    net: the broadcast occupies the home's inject port with T copies and
    each holder's copy ranks by tile id among ALL copies (engine's
    `send | over_bc` row).  Serialized (mutex-ordered) accesses keep it
    bit-exact vs the oracle, which mirrors the copy count and ranks
    (n_copies/ranks in `_HbhNet.fanout`)."""
    extra = "[dram_directory]\ndirectory_type = ackwise\nmax_hw_sharers = 2\n"
    sc = make_config(4, MSI, extra=extra)
    bs = [TraceBuilder() for _ in range(4)]
    bs[0].mutex_init(0)
    bs[0].barrier_init(9, 4)
    for b in bs:
        b.barrier_wait(9)
    # 4 readers (> max_hw_sharers=2 overflows the entry), serialized
    for t, b in enumerate(bs):
        b.mutex_lock(0)
        b.load(0x900000, 8)
        b.mutex_unlock(0)
    for b in bs:
        b.barrier_wait(9)
    # one writer: EX on the overflowed entry -> broadcast INV sweep
    bs[0].mutex_lock(0)
    bs[0].store(0x900000, 8)
    bs[0].mutex_unlock(0)
    res, gold = assert_exact(sc, TraceBatch.from_builders(bs))
    assert int(gold.mem_counters["dir_broadcasts"].sum()) > 0


def test_fanout_single_target_matches_unicast():
    """Formula self-consistency: a fan-out with exactly ONE target on an
    idle NoC must charge the same arrival time as the unicast path for
    that (src, dst) pair — the inject+rank approximation only diverges
    from per-hop routing when queues are occupied or k > 1.  Checked for
    both the hop-counter (zero-load closed form) and hop_by_hop nets."""
    import jax.numpy as jnp

    from graphite_tpu.memory.engine import mem_net_fanout, mem_net_send
    from graphite_tpu.models.network_hop_by_hop import init_noc_state

    batch = disjoint_stream(9, accesses=4)
    for net in ("emesh_hop_counter", "emesh_hop_by_hop"):
        sim = Simulator(make_config(9, net=net), batch)
        mp = sim.params.mem
        T = mp.n_tiles
        t0 = jnp.full((T,), 1_000_000, jnp.int64)
        for src, dst in ((0, 5), (4, 4), (8, 1)):
            noc = (None if mp.net_hbh is None
                   else init_noc_state(mp.net_hbh))
            send_hs = jnp.zeros((T, T), bool).at[src, dst].set(True)
            _, arr_fan = mem_net_fanout(mp, noc, send_hs, 128, t0, True)
            noc = (None if mp.net_hbh is None
                   else init_noc_state(mp.net_hbh))
            srcs = jnp.full((T,), src, jnp.int32)
            dsts = jnp.full((T,), dst, jnp.int32)
            mask = jnp.zeros((T,), bool).at[src].set(True)
            _, arr_uni = mem_net_send(
                mp, noc, srcs, dsts, 128, t0, mask, True)
            assert int(arr_fan[src, dst]) == int(arr_uni[src]), (
                net, src, dst)


@pytest.mark.parametrize("freq_mhz", [1000, 870],
                         ids=["divides-1e6", "does-not"])
@pytest.mark.parametrize("net", ["emesh_hop_counter", "emesh_hop_by_hop",
                                 "atac"])
def test_fanout_matrix_matches_unicast_on_an_idle_network(net, freq_mhz):
    """The fan-out's WHOLE [T, T] arrival matrix equals the unicast
    path's zero-load arrival for every (home, target) pair, with the
    models enabled and disabled, at a frequency that divides 10^6 (since
    PR 50 the conversion multiplies) and at one that does not (it divides
    by the reduced constant).  One target a home and a fresh network a call, so
    no copy has a rank or a queue to wait in: call s sends home h to tile
    (h + s) % T, and the T calls cover the matrix."""
    import jax
    import jax.numpy as jnp

    from graphite_tpu.memory.engine import mem_net_fanout, mem_net_send
    from graphite_tpu.memory.params import MemParams

    T = 16
    mp = mem_net_at(MemParams.from_config(make_config(
        T, net=net, extra=ATAC_EXTRA if net == "atac" else "")), freq_mhz)
    noc = fresh_mem_noc(mp)
    homes = np.arange(T, dtype=np.int32)
    # whole cycles at both frequencies (87,000 / 100,000 a step): the
    # per-hop unicast path keeps its clock in cycles
    t0 = jnp.asarray(100_000_000 * (1 + homes), jnp.int64)

    @jax.jit
    @functools.partial(jax.vmap, in_axes=(0, None))
    def both(s, enabled):
        dsts = (homes + s) % T
        send_hs = homes[None, :] == dsts[:, None]        # [home, target]
        _, fan = mem_net_fanout(mp, noc, send_hs, 128, t0, enabled)
        _, uni = mem_net_send(mp, noc, homes, dsts, 128, t0,
                              jnp.ones(T, bool), enabled)
        return jnp.take_along_axis(fan, dsts[:, None], axis=1)[:, 0], uni

    for enabled in (True, False):
        fan, uni = both(jnp.arange(T, dtype=jnp.int32), jnp.asarray(enabled))
        np.testing.assert_array_equal(np.asarray(fan), np.asarray(uni),
                                      err_msg=f"{net} enabled={enabled}")
        if enabled:
            assert (np.asarray(fan)[1:] > np.asarray(t0)).all()  # s > 0
        else:
            assert (np.asarray(fan) == np.asarray(t0)).all()


def test_shl2_atac_memory_serialized_bit_exact():
    """The shared-L2 engine routes through the same mem_net_send, so
    `memory = atac` serves it too — serialized traffic bit-exact vs the
    shl2 oracle riding the same `_AtacNet`."""
    sc = make_config(16, proto="pr_l1_sh_l2_msi", net="atac",
                     extra=ATAC_EXTRA)
    res, gold = assert_exact(sc, mutex_rmw(16, rounds=3, lines=2))
    assert int(np.asarray(res.mem_counters["l2_misses"]).sum()) > 0


def test_shl2_atac_ackwise_broadcast_exact():
    """Shared-L2 overflowed-entry INV sweep under memory = atac: the
    shl2 engine's broadcast row (holders | all-except-requester) and hub
    charge mirror `memory_model_shl2`'s oracle exactly on serialized
    traffic — the writer sits in a different cluster than the home and
    still holds the line."""
    extra = ATAC_EXTRA + \
        "[dram_directory]\ndirectory_type = ackwise\nmax_hw_sharers = 2\n"
    sc = make_config(16, proto="pr_l1_sh_l2_msi", net="atac", extra=extra)
    bs = [TraceBuilder() for _ in range(16)]
    bs[0].mutex_init(0)
    bs[0].barrier_init(9, 16)
    for b in bs:
        b.barrier_wait(9)
    for t, b in enumerate(bs):
        b.mutex_lock(0)
        b.load(0x900000, 8)
        b.mutex_unlock(0)
    for b in bs:
        b.barrier_wait(9)
    bs[10].mutex_lock(0)
    bs[10].store(0x900000, 8)
    bs[10].mutex_unlock(0)
    for b in bs:
        b.barrier_wait(9)
    for t in (1, 5, 10, 15):
        bs[t].mutex_lock(0)
        bs[t].load(0x900000 + 64, 8)
        bs[t].mutex_unlock(0)
    res, gold = assert_exact(sc, TraceBatch.from_builders(bs))
    assert int(gold.mem_counters["dir_broadcasts"].sum()) > 0
