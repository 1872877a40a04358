"""Datacenter topologies for the netsim engine (paper §IV).

Two builders, matching the paper's evaluation setups:

  * ``leaf_spine``  — 2-tier Clos (testbed: 2 leaves x 4 spines x 3 hosts
    @40G; large sim: 8 leaves x 12 spines x 16 hosts @100G).  A path between
    two leaves is identified by the spine it crosses -> n_paths = n_spine.
  * ``three_tier``  — the paper's "FatTree" (16 core / 20 agg / 20 ToR /
    16 hosts per ToR; ToR-agg 400G, others 100G).  We model it as a folded
    Clos with full bipartite ToR<->Agg and Agg<->Core and symmetric
    up/down routing, so a path is (agg, core): n_paths = n_agg * n_core =
    320 <= 1023, which — pleasingly — fits the paper's 10-bit PathTag.

Links live in one flat capacity vector; every (sub-)flow touches at most
``MAX_HOPS`` links: [host_tx, up1, (up2), (dn1), dn2, host_rx] (-1 = hop
absent; 2-tier emits the compact 4-hop form).  The engine scatter-adds
offered rates over these ids (the same computation the linkload Pallas
kernel implements for the TPU target).

Asymmetry (paper Fig. 8b/11): ``capacity_overrides`` rescales individual
links — e.g. kill spine 3 and double spine 2's leaf links to 80G.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

MAX_HOPS = 6


@dataclasses.dataclass(frozen=True, eq=False)  # eq=False: identity hash so the
class Topology:  # instance can be a jit static argument (fields hold arrays)
    """Static topology description (closed over by the jitted engine)."""

    kind: str
    n_leaf: int
    n_paths: int
    hosts_per_leaf: int
    n_links: int
    capacity: jax.Array  # f32[n_links + 1] bps; last slot = dummy sink for -1
    # f(src_host, dst_host, path) -> int32[..., MAX_HOPS] link ids (-1 pad)
    subflow_links: Callable
    # NIC-tiered view of the same hop vector (dataplane.cascade_nic): every
    # sub-flow of a flow shares its first (host_tx) and last (host_rx) hop,
    # and the fabric hops depend only on (src_leaf, dst_leaf, path) — so the
    # NIC hops can be pre-reduced over N and the fabric hops rebuilt without
    # touching host ids.
    # f(src_host, dst_host) -> (tx i32[...], rx i32[...])
    nic_links: Callable
    # f(src_leaf, dst_leaf, path) -> i32[..., n_fabric_hops] (-1 = absent)
    fabric_links: Callable
    n_fabric_hops: int
    # the half-open range of link ids each hop of that view can emit, in
    # cascade order: host_tx, fabric hop 0..n_fabric_hops-1, host_rx.  The
    # ranges partition [0, n_links); the Pallas cascade builds each hop's
    # one-hot over its own range only (kernels/linkload.py)
    bands: tuple[tuple[int, int], ...]
    # fabric-only view used for congestion metrics / imbalance:
    uplink_ids: np.ndarray  # int32[n_leaf, n_uplinks] — ToR uplink link ids
    base_rtt_s: float
    # (leaf, path) -> util: engine computes from link loads via these ids
    path_link_table: np.ndarray  # int32[n_leaf, n_leaf, n_paths, MAX_HOPS-2] fabric hops

    @property
    def n_hosts(self) -> int:
        return self.n_leaf * self.hosts_per_leaf

    def leaf_of(self, host):
        return host // self.hosts_per_leaf


def _apply_overrides(cap: np.ndarray, overrides):
    for link_id, new_cap in (overrides or {}).items():
        cap[link_id] = new_cap
    return cap


def leaf_spine(
    n_leaf: int,
    n_spine: int,
    hosts_per_leaf: int,
    link_bw: float,
    host_bw: float | None = None,
    base_rtt_s: float = 4e-6,
    capacity_overrides: dict[int, float] | None = None,
) -> Topology:
    """2-tier Clos.  Link layout:
    up[l,s]   = l*S + s
    down[s,l] = L*S + s*L + l
    host_tx[h]= L*S + S*L + h
    host_rx[h]= L*S + S*L + H + h
    """
    L, S, H = n_leaf, n_spine, n_leaf * hosts_per_leaf
    host_bw = link_bw if host_bw is None else host_bw
    n_links = L * S + S * L + 2 * H
    cap = np.zeros(n_links + 1, np.float32)
    cap[: L * S] = link_bw
    cap[L * S : 2 * L * S] = link_bw
    cap[2 * L * S : 2 * L * S + 2 * H] = host_bw
    cap[-1] = np.float32(1e30)  # dummy sink — -1 hops land here
    cap = _apply_overrides(cap, capacity_overrides)

    up0, dn0, tx0, rx0 = 0, L * S, 2 * L * S, 2 * L * S + H

    def nic_links(src_host, dst_host):
        tx = jnp.asarray(tx0 + src_host, jnp.int32)
        rx = jnp.asarray(rx0 + dst_host, jnp.int32)
        return jnp.broadcast_arrays(tx, rx)

    def fabric_links(src_leaf, dst_leaf, path):
        shp = jnp.broadcast_shapes(jnp.shape(src_leaf), jnp.shape(dst_leaf), jnp.shape(path))
        src_leaf, dst_leaf, path = (jnp.broadcast_to(a, shp) for a in (src_leaf, dst_leaf, path))
        inter = src_leaf != dst_leaf
        up = jnp.where(inter, up0 + src_leaf * S + path, -1)
        dn = jnp.where(inter, dn0 + path * L + dst_leaf, -1)
        return jnp.stack([up, dn], axis=-1).astype(jnp.int32)

    def subflow_links(src_host, dst_host, path):
        # 4 real hops (no -1 padding columns): the dataplane cascade cost is
        # linear in the hop count, so 2-tier flows carry a [.., 4] hop
        # vector while three_tier keeps the full MAX_HOPS = 6.
        shp = jnp.broadcast_shapes(jnp.shape(src_host), jnp.shape(dst_host), jnp.shape(path))
        src_host, dst_host, path = (jnp.broadcast_to(a, shp) for a in (src_host, dst_host, path))
        tx, rx = nic_links(src_host, dst_host)
        fab = fabric_links(src_host // hosts_per_leaf, dst_host // hosts_per_leaf, path)
        return jnp.concatenate(
            [tx[..., None], fab, rx[..., None]], axis=-1
        ).astype(jnp.int32)

    uplink_ids = (np.arange(L)[:, None] * S + np.arange(S)[None, :]).astype(np.int32)

    plt = np.full((L, L, S, MAX_HOPS - 2), -1, np.int32)
    for sl in range(L):
        for dl in range(L):
            if sl == dl:
                continue
            for p in range(S):
                plt[sl, dl, p, 0] = up0 + sl * S + p
                plt[sl, dl, p, 3] = dn0 + p * L + dl
    return Topology(
        kind="leaf_spine",
        n_leaf=L,
        n_paths=S,
        hosts_per_leaf=hosts_per_leaf,
        n_links=n_links,
        capacity=jnp.asarray(cap),
        subflow_links=subflow_links,
        nic_links=nic_links,
        fabric_links=fabric_links,
        n_fabric_hops=2,
        bands=((tx0, tx0 + H), (up0, dn0), (dn0, tx0), (rx0, rx0 + H)),
        uplink_ids=uplink_ids,
        base_rtt_s=base_rtt_s,
        path_link_table=plt,
    )


def three_tier(
    n_tor: int = 20,
    n_agg: int = 20,
    n_core: int = 16,
    hosts_per_tor: int = 16,
    bw_tor_agg: float = 400e9,
    bw_agg_core: float = 100e9,
    host_bw: float = 100e9,
    base_rtt_s: float = 8e-6,
    capacity_overrides: dict[int, float] | None = None,
) -> Topology:
    """3-tier folded Clos (paper Fig. 14 setup).  Path id = agg*n_core+core.
    Link layout:
      ta_up[t,a] = t*A + a
      ac_up[a,c] = T*A + a*C + c
      ca_dn[c,a] = T*A + A*C + c*A + a
      at_dn[a,t] = T*A + 2*A*C + a*T + t
      host_tx[h], host_rx[h] appended.
    """
    T, A, C = n_tor, n_agg, n_core
    H = T * hosts_per_tor
    n_links = T * A + 2 * A * C + A * T + 2 * H
    cap = np.zeros(n_links + 1, np.float32)
    ta0, ac0 = 0, T * A
    ca0 = T * A + A * C
    at0 = T * A + 2 * A * C
    tx0 = T * A + 2 * A * C + A * T
    rx0 = tx0 + H
    cap[ta0 : ta0 + T * A] = bw_tor_agg
    cap[ac0 : ac0 + A * C] = bw_agg_core
    cap[ca0 : ca0 + C * A] = bw_agg_core
    cap[at0 : at0 + A * T] = bw_tor_agg
    cap[tx0 : tx0 + 2 * H] = host_bw
    cap[-1] = np.float32(1e30)
    cap = _apply_overrides(cap, capacity_overrides)

    def nic_links(src_host, dst_host):
        tx = jnp.asarray(tx0 + src_host, jnp.int32)
        rx = jnp.asarray(rx0 + dst_host, jnp.int32)
        return jnp.broadcast_arrays(tx, rx)

    def fabric_links(src_tor, dst_tor, path):
        shp = jnp.broadcast_shapes(jnp.shape(src_tor), jnp.shape(dst_tor), jnp.shape(path))
        src_tor, dst_tor, path = (jnp.broadcast_to(a, shp) for a in (src_tor, dst_tor, path))
        inter = src_tor != dst_tor
        agg = path // C
        core = path % C
        up1 = jnp.where(inter, ta0 + src_tor * A + agg, -1)
        up2 = jnp.where(inter, ac0 + agg * C + core, -1)
        dn1 = jnp.where(inter, ca0 + core * A + agg, -1)
        dn2 = jnp.where(inter, at0 + agg * T + dst_tor, -1)
        return jnp.stack([up1, up2, dn1, dn2], axis=-1).astype(jnp.int32)

    def subflow_links(src_host, dst_host, path):
        shp = jnp.broadcast_shapes(jnp.shape(src_host), jnp.shape(dst_host), jnp.shape(path))
        src_host, dst_host, path = (jnp.broadcast_to(a, shp) for a in (src_host, dst_host, path))
        tx, rx = nic_links(src_host, dst_host)
        fab = fabric_links(src_host // hosts_per_tor, dst_host // hosts_per_tor, path)
        return jnp.concatenate(
            [tx[..., None], fab, rx[..., None]], axis=-1
        ).astype(jnp.int32)

    uplink_ids = (np.arange(T)[:, None] * A + np.arange(A)[None, :]).astype(np.int32)

    # path_link_table would be [20,20,320,4] = 512k int32 — built lazily by
    # schemes that need it (CONGA is 2-tier-only per the paper, so none do).
    plt = np.zeros((0,), np.int32)
    return Topology(
        kind="three_tier",
        n_leaf=T,
        n_paths=A * C,
        hosts_per_leaf=hosts_per_tor,
        n_links=n_links,
        capacity=jnp.asarray(cap),
        subflow_links=subflow_links,
        nic_links=nic_links,
        fabric_links=fabric_links,
        n_fabric_hops=4,
        bands=((tx0, rx0), (ta0, ac0), (ac0, ca0), (ca0, at0), (at0, tx0),
               (rx0, rx0 + H)),
        uplink_ids=uplink_ids,
        base_rtt_s=base_rtt_s,
        path_link_table=plt,
    )


def spine_links(topo: Topology, spine: int) -> tuple[int, ...]:
    """Flat link ids that die with one fabric switch — the unit of the
    co-sim fault schedules (``dist.cosim``).

    * ``leaf_spine``: ``spine`` is a spine switch — its leaf uplinks
      up[l, spine] and downlinks down[spine, l] for every leaf l.
    * ``three_tier``: ``spine`` is an AGGREGATION switch a — the ToR
      uplinks ta[t, a], agg-core links ac[a, c] / ca[c, a], and ToR
      downlinks at[a, t].  Killing it takes out ToR uplink a on every ToR,
      i.e. exactly the ``n_core`` paths (a, *) that
      ``dist.netfeed._paths_for_uplink`` quarantines.
    """
    if topo.kind == "leaf_spine":
        L, S = topo.n_leaf, topo.n_paths
        assert 0 <= spine < S, (spine, S)
        return tuple(l * S + spine for l in range(L)) + tuple(
            L * S + spine * L + l for l in range(L))
    assert topo.kind == "three_tier", topo.kind
    T = topo.n_leaf
    A = topo.uplink_ids.shape[1]
    C = topo.n_paths // A
    assert 0 <= spine < A, (spine, A)
    ta0, ac0 = 0, T * A
    ca0 = T * A + A * C
    at0 = T * A + 2 * A * C
    return (
        tuple(ta0 + t * A + spine for t in range(T))
        + tuple(ac0 + spine * C + c for c in range(C))
        + tuple(ca0 + c * A + spine for c in range(C))
        + tuple(at0 + spine * T + t for t in range(T))
    )


def paths_for_link(topo: Topology, link: int) -> tuple[int, ...]:
    """Inverse of the fabric hop layout: which path ids traverse flat link
    ``link``.  Host tx/rx links (and the dummy sink) belong to no path ->
    empty tuple.  Used by the fault layer to turn a per-LINK event (a
    flapping port, a lossy optic) into the per-PATH quarantine set the
    planner speaks (``dist.netfeed.report_congestion``, in-epoch
    replanning in ``dist.cosim``).

    * ``leaf_spine``: up[l, s] and down[s, l] both map to path s.
    * ``three_tier`` (path = agg * C + core): ToR up/downlinks of agg a
      cover all C paths (a, *); agg<->core links pin a single (agg, core).
    """
    if topo.kind == "leaf_spine":
        L, S = topo.n_leaf, topo.n_paths
        if link < L * S:  # up[l, s] = l*S + s
            return (link % S,)
        if link < 2 * L * S:  # down[s, l] = L*S + s*L + l
            return ((link - L * S) // L,)
        return ()
    assert topo.kind == "three_tier", topo.kind
    T = topo.n_leaf
    A = topo.uplink_ids.shape[1]
    C = topo.n_paths // A
    ta0, ac0 = 0, T * A
    ca0 = T * A + A * C
    at0 = T * A + 2 * A * C
    tx0 = at0 + A * T
    if link < ac0:  # ta[t, a] = t*A + a
        a = link % A
        return tuple(a * C + c for c in range(C))
    if link < ca0:  # ac[a, c] = ac0 + a*C + c
        i = link - ac0
        return ((i // C) * C + (i % C),)
    if link < at0:  # ca[c, a]
        i = link - ca0
        c, a = i // A, i % A
        return (a * C + c,)
    if link < tx0:  # at[a, t] = at0 + a*T + t
        a = (link - at0) // T
        return tuple(a * C + c for c in range(C))
    return ()


def testbed_symmetric() -> Topology:
    """Paper Fig. 8(a): 2 leaves x 4 spines, 3 hosts/leaf, all 40G."""
    return leaf_spine(2, 4, 3, 40e9, base_rtt_s=4e-6)


def testbed_asymmetric() -> Topology:
    """Paper Fig. 8(b): one spine deactivated and its links redirected to a
    neighbour -> 3 usable paths, one of them 80G while the rest stay 40G.
    ECMP still hashes uniformly over the 3 paths (it cannot see the extra
    capacity); SeqBalance's congestion feedback steers load toward the fat
    path — the paper measures +37.6 % total throughput from this."""
    L, S = 2, 3
    overrides = {}
    for leaf in range(L):
        overrides[leaf * S + 2] = 80e9  # up[l,2] doubled
        overrides[L * S + 2 * L + leaf] = 80e9  # down[2,l] doubled
    return leaf_spine(2, 3, 3, 40e9, base_rtt_s=4e-6, capacity_overrides=overrides)


def sim_2tier() -> Topology:
    """Paper §IV.B: 8 leaves x 12 spines x 16 hosts, 100G everywhere."""
    return leaf_spine(8, 12, 16, 100e9, base_rtt_s=4e-6)


def hetero_leaf_spine(
    n_leaf: int = 4,
    n_spine: int = 4,
    hosts_per_leaf: int = 4,
    slow_bw: float = 100e9,
    fast_bw: float = 400e9,
    n_fast_spines: int = 1,
    host_bw: float | None = None,
    base_rtt_s: float = 4e-6,
) -> Topology:
    """Mixed-speed 2-tier Clos: the last ``n_fast_spines`` spine planes run
    at ``fast_bw`` (both the leaf uplinks up[l, s] and the downlinks
    down[s, l]), the rest at ``slow_bw`` — the 100G/400G mixed-uplink
    fabrics that mid-upgrade clusters actually run.  Hosts stay at
    ``slow_bw`` unless overridden, so the fabric asymmetry (not the edge)
    is the bottleneck the balancer must exploit.

    Hash-based schemes (ECMP, per-flowcell spraying) split uniformly over
    the planes and leave the fast spines underfed; capacity-weighted
    flowlet rerouting (``flowlet_timeout``, WCMP weights from these link
    speeds) and SeqBalance's congestion feedback both see the extra
    headroom.  The inter-path delivery-time skew that the flowcell
    reordering-cost model (``dataplane.reorder_gbn_factor``) charges for is
    also largest here: a cell on a 100G plane trails its 400G sibling 4x.
    """
    assert 0 <= n_fast_spines <= n_spine, (n_fast_spines, n_spine)
    L, S = n_leaf, n_spine
    overrides: dict[int, float] = {}
    for s in range(S - n_fast_spines, S):
        for leaf in range(L):
            overrides[leaf * S + s] = fast_bw  # up[l, s]
            overrides[L * S + s * L + leaf] = fast_bw  # down[s, l]
    return leaf_spine(L, S, hosts_per_leaf, slow_bw, host_bw=host_bw,
                      base_rtt_s=base_rtt_s, capacity_overrides=overrides)
