"""ATAC optical NoC model (`common/network/models/network_model_atac.cc`).

The ATAC network clusters the tile mesh: intra-cluster traffic rides an
electrical mesh (ENet); inter-cluster traffic goes through the sender
cluster's optical hub onto a WDM waveguide (ONet) to the receiver
cluster's hub, then down an electrical receive network (star/htree) to the
destination (`network_model_atac.h:18-60`, routing `:337-500`).  Routing
strategy `cluster_based` sends every inter-cluster unicast optically;
`distance_based` uses ONet only above `unicast_distance_threshold`
(`carbon_sim.cfg:315-352`, `computeGlobalRoute` `:798-830`).

Timing:
 - ENet hop: router + link cycles per XY hop (`routePacketOnENet`);
 - ONet: ENet to the cluster's optical access point, send-hub router (+
   contention queue), the optical link — waveguide delay per mm x length +
   E-O + O-E conversion cycles (`optical_link_model.cc:52-55`) — then the
   receive-hub router (+ contention) and one receive-net router hop
   (star; htree adds log2(cluster) levels);
 - receive-side serialization flits, as in every NetworkModel
   (`network_model.cc:143-149`).

Hub contention uses the shared queue models, one queue per send hub and
per receive hub (the reference attaches QueueModels to both hub routers);
WDM gives each sender cluster its own wavelength, so the waveguide itself
is contention-free.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from flax import struct

from graphite_tpu.models.network_hop_by_hop import NOC_COUNTERS
from graphite_tpu.models.queue_models import (
    QueueArrays, QueueParams, make_queues, scatter_queue_delay,
)
from graphite_tpu.obs.scopes import scope
from graphite_tpu.time_types import cycles_to_ps, ps_to_cycles

I64 = jnp.int64

# the per-hub event counters a run reports (`SimResults.atac_counters`),
# each with its QueueArrays column: the four the mesh's ports keep, as the
# reference's hub router models do (`router_model.h:15-79`)
ATAC_COUNTERS = NOC_COUNTERS


@dataclasses.dataclass(frozen=True)
class AtacParams:
    n_tiles: int
    mesh_width: int
    mesh_height: int
    cluster_size: int          # tiles per cluster (square sub-mesh)
    cluster_width: int         # sub-mesh dims (cluster_width x cluster_height)
    cluster_height: int
    n_clusters: int
    flit_width_bits: int
    freq_mhz: int
    enet_hop_cycles: int       # enet router + link
    send_hub_cycles: int
    receive_hub_cycles: int
    receive_net_cycles: int    # per receive-net router
    receive_net_levels: int    # 1 for star, log2(cluster_size) for htree
    optical_link_ps: int       # waveguide + E-O + O-E, precomputed
    global_routing_strategy: str   # cluster_based | distance_based
    unicast_distance_threshold: int
    queue: QueueParams
    contention_enabled: bool = True

    @classmethod
    def from_config(cls, sc, network: str = "user") -> "AtacParams":
        from graphite_tpu.models.network_emesh import mesh_dims
        from graphite_tpu.models.network_user import _network_domain_freq_mhz

        cfg = sc.cfg
        sec = "network/atac"
        w, h = mesh_dims(sc.application_tiles)
        cluster_size = cfg.get_int(f"{sec}/cluster_size", 4)
        if sc.application_tiles % cluster_size != 0:
            raise ValueError(
                f"atac cluster_size {cluster_size} does not divide "
                f"{sc.application_tiles} tiles")
        n_clusters = sc.application_tiles // cluster_size
        # clusters are 2-D sub-meshes (`getClusterID`,
        # `network_model_atac.cc:659-674`): cw x ch tiles, as square as
        # cluster_size allows
        cw = int(math.isqrt(cluster_size))
        while cluster_size % cw != 0:
            cw -= 1
        ch = cluster_size // cw
        if w % cw != 0 or h % ch != 0:
            raise ValueError(
                f"atac cluster {cw}x{ch} does not tile the {w}x{h} mesh")
        freq_mhz = _network_domain_freq_mhz(
            sc, "NETWORK_USER" if network == "user" else "NETWORK_MEMORY")
        recv_type = cfg.get_string(f"{sec}/receive_network_type", "star")
        levels = (1 if recv_type == "star"
                  else max(1, int(math.log2(cluster_size))))
        # waveguide length: the serpentine visits every cluster hub — scale
        # with the chip's span (`computeOpticalLinkLength`): tile_width x
        # (mesh perimeter/2) mm
        tile_width_mm = cfg.get_float("general/tile_width", 1.0)
        length_mm = tile_width_mm * (w + h)
        wg_ns_per_mm = cfg.get_float(
            "link_model/optical/waveguide_delay_per_mm", 10e-3)
        eo = cfg.get_int("link_model/optical/E-O_conversion_delay", 1)
        oe = cfg.get_int("link_model/optical/O-E_conversion_delay", 1)
        from graphite_tpu.time_types import cycles_to_ps

        optical_link_ps = int(
            math.ceil(wg_ns_per_mm * length_mm * 1000)
            + cycles_to_ps(eo + oe, freq_mhz))
        qtype = cfg.get_string(f"{sec}/queue_model/type", "history_tree")
        return cls(
            n_tiles=sc.application_tiles,
            mesh_width=w,
            mesh_height=h,
            cluster_size=cluster_size,
            cluster_width=cw,
            cluster_height=ch,
            n_clusters=n_clusters,
            flit_width_bits=cfg.get_int(f"{sec}/flit_width", 64),
            freq_mhz=freq_mhz,
            enet_hop_cycles=(cfg.get_int(f"{sec}/enet/router/delay", 1)
                             + cfg.get_int(f"{sec}/enet/link/delay", 1)),
            send_hub_cycles=cfg.get_int(
                f"{sec}/onet/send_hub/router/delay", 1),
            receive_hub_cycles=cfg.get_int(
                f"{sec}/onet/receive_hub/router/delay", 1),
            receive_net_cycles=cfg.get_int(
                f"{sec}/star_net/router/delay", 1),
            receive_net_levels=levels,
            optical_link_ps=optical_link_ps,
            global_routing_strategy=cfg.get_string(
                f"{sec}/global_routing_strategy", "cluster_based"),
            unicast_distance_threshold=cfg.get_int(
                f"{sec}/unicast_distance_threshold", 4),
            queue=QueueParams.from_config(cfg, qtype, 1),
            contention_enabled=cfg.get_bool(
                f"{sec}/queue_model/enabled", True),
        )


@struct.dataclass
class AtacState:
    # [send hubs | receive hubs | scratch]: one queue per cluster hub
    hub_queues: QueueArrays


def init_atac_state(p: AtacParams) -> AtacState:
    return AtacState(hub_queues=make_queues(2 * p.n_clusters + 1, p.queue))


def atac_counters(data, n_clusters: int) -> dict:
    """{name: int64[2 * n_clusters]}, send hubs then receive hubs, from a
    fetched `AtacState.hub_queues.data` (host-side: the scratch queue's
    row is dropped)."""
    hubs = data[: 2 * n_clusters]
    return {name: hubs[:, col] for name, col in ATAC_COUNTERS}


def _cluster_of(p: AtacParams, tile):
    """2-D sub-mesh cluster id (`getClusterID`)."""
    x = tile % p.mesh_width
    y = tile // p.mesh_width
    cx = x // p.cluster_width
    cy = y // p.cluster_height
    clusters_per_row = p.mesh_width // p.cluster_width
    return (cy * clusters_per_row + cx).astype(jnp.int32)


def _hub_tile(p: AtacParams, cluster):
    """The tile hosting the cluster's optical hub (the sub-mesh's top-left
    corner — `getTileIDWithOpticalHub`)."""
    clusters_per_row = p.mesh_width // p.cluster_width
    cx = cluster % clusters_per_row
    cy = cluster // clusters_per_row
    return (cy * p.cluster_height * p.mesh_width
            + cx * p.cluster_width).astype(jnp.int32)


def _enet_hops(p: AtacParams, a, b):
    w = p.mesh_width
    return (jnp.abs(a % w - b % w) + jnp.abs(a // w - b // w)).astype(I64)


def route_atac(p: AtacParams, state: AtacState, src, dst, bits, clock_ps,
               mask, enabled):
    """Route one packet per lane; returns (state, arrival_ps, used_onet).

    Mirrors `routePacket` (`network_model_atac.cc:337-368`): intra-cluster
    (or short-distance) unicasts ride the ENet; everything else goes
    hub → waveguide → hub → receive net.
    """
    src = jnp.asarray(src)
    dst = jnp.asarray(dst)
    csrc = _cluster_of(p, src)
    cdst = _cluster_of(p, dst)
    same_cluster = csrc == cdst
    hops_direct = _enet_hops(p, src, dst)
    if p.global_routing_strategy == "distance_based":
        use_enet = same_cluster | (hops_direct <= p.unicast_distance_threshold)
    else:
        use_enet = same_cluster
    use_onet = mask & ~use_enet
    # queue-state updates only when models are enabled (disabled-phase
    # traffic must not pollute contention history — `state.models_enabled`)
    onet_live = use_onet & jnp.asarray(enabled)

    def cyc(n):
        return cycles_to_ps(jnp.asarray(n, I64), p.freq_mhz)

    flits = ((jnp.asarray(bits) + p.flit_width_bits - 1)
             // p.flit_width_bits).astype(I64)
    ser_ps = jnp.where(src == dst, 0, cyc(flits))

    # --- ENet path -------------------------------------------------------
    enet_ps = cyc(hops_direct * p.enet_hop_cycles)

    # --- ONet path -------------------------------------------------------
    to_hub = _enet_hops(p, src, _hub_tile(p, csrc))
    from_hub = cyc(p.receive_net_levels * p.receive_net_cycles)
    sendhub_arrive = clock_ps + cyc(to_hub * p.enet_hop_cycles)
    # send-hub contention + router
    if p.contention_enabled:
        qid = jnp.where(onet_live, csrc, 2 * p.n_clusters).astype(jnp.int32)
        service = jnp.maximum(flits, 1)  # serialization cycles per packet
        with scope("gt.net.atac.hub"):
            queues, delay_cyc = scatter_queue_delay(
                p.queue, state.hub_queues, qid,
                ps_to_cycles(sendhub_arrive, p.freq_mhz),
                service, onet_live)
        sendhub_done = sendhub_arrive + cyc(delay_cyc + p.send_hub_cycles)
    else:
        queues = state.hub_queues
        sendhub_done = sendhub_arrive + cyc(p.send_hub_cycles)
    # optical traversal
    recvhub_arrive = sendhub_done + jnp.where(enabled, p.optical_link_ps, 0)
    # receive-hub contention + router
    if p.contention_enabled:
        qid2 = jnp.where(onet_live, p.n_clusters + cdst,
                         2 * p.n_clusters).astype(jnp.int32)
        with scope("gt.net.atac.hub"):
            queues, delay2 = scatter_queue_delay(
                p.queue, queues, qid2,
                ps_to_cycles(recvhub_arrive, p.freq_mhz),
                jnp.maximum(flits, 1), onet_live)
        recvhub_done = recvhub_arrive + cyc(delay2 + p.receive_hub_cycles)
    else:
        recvhub_done = recvhub_arrive + cyc(p.receive_hub_cycles)
    onet_ps = (recvhub_done - clock_ps) + from_hub

    route_ps = jnp.where(use_onet, onet_ps, enet_ps)
    total_ps = jnp.where(enabled, route_ps + ser_ps, 0)
    arrival = clock_ps + jnp.where(mask, total_ps, 0)
    return AtacState(hub_queues=queues), arrival, use_onet


def atac_use_onet(p: AtacParams, src, dst):
    """Which (src, dst) pairs ride the ONet (broadcastable bool)."""
    src = jnp.asarray(src)
    dst = jnp.asarray(dst)
    same_cluster = _cluster_of(p, src) == _cluster_of(p, dst)
    if p.global_routing_strategy == "distance_based":
        return ~(same_cluster
                 | (_enet_hops(p, src, dst) <= p.unicast_distance_threshold))
    return ~same_cluster


def atac_zeroload_ps(p: AtacParams, src, dst, bits, enabled):
    """Contention-free ATAC latency (broadcastable [.., ..] math): the
    route_atac path costs with zero hub-queue delay — what a packet pays
    on idle hubs (`test_atac.py` pins route_atac == this on fresh state).
    Used for the MEMORY net's zero-load call sites (shl2 DRAM round trip,
    fan-out per-target legs).

    `src` / `dst` go to the device whatever they are (the fan-out's numpy
    `arange`s too); every `cyc()` converts at the static `p.freq_mhz`, so
    where that divides 1e6 the [T, T] legs multiply and hold no int64
    division (`time_types._ps_per_cycle`)."""
    src = jnp.asarray(src)
    dst = jnp.asarray(dst)

    def cyc(n):
        return cycles_to_ps(jnp.asarray(n, I64), p.freq_mhz)

    flits = ((jnp.asarray(bits) + p.flit_width_bits - 1)
             // p.flit_width_bits).astype(I64)
    ser_ps = jnp.where(src == dst, 0, cyc(flits))
    use_onet = atac_use_onet(p, src, dst)
    enet_ps = cyc(_enet_hops(p, src, dst) * p.enet_hop_cycles)
    to_hub = cyc(_enet_hops(p, src, _hub_tile(p, _cluster_of(p, src)))
                 * p.enet_hop_cycles)
    onet_ps = (to_hub + cyc(p.send_hub_cycles)
               + jnp.where(jnp.asarray(enabled, bool), p.optical_link_ps, 0)
               + cyc(p.receive_hub_cycles)
               + cyc(p.receive_net_levels * p.receive_net_cycles))
    total = jnp.where(use_onet, onet_ps, enet_ps) + ser_ps
    return jnp.where(jnp.asarray(enabled, bool), total, 0)
