"""Fused switch dataplane: offered-load -> queue -> RED/ECN-mark pipeline.

This is the per-step work of every ToR/spine in the fluid simulator
(DESIGN.md §8/§9), extracted from ``engine.step_fn`` so that one module owns
the hop cascade and both engines (dense oracle and active-window compact)
share bit-identical math:

  hop h arrivals are the UPSTREAM-scaled rates (NIC serializes first, then
  fabric), so for h = 0..H-1:
      load_h[l]  = sum of sub-flow rates (scaled by hops < h) entering l
      scale_h[l] = min(1, cap[l] / load_h[l])
      r         <- r * scale_h[lid_h]
  arrival[l]   = sum_h load_h[l]
  queue[l]    <- clip(queue + (arrival - cap) * dt/8, 0, qmax) * queue_mask
  p_mark[l]    = RED ramp on queue (kmin/kmax/pmax)

Both engines route through the NIC-TIERED form (``cascade_nic``): the N
sub-flows of a flow always share their first (host_tx) and last (host_rx)
hop, so those two hops pre-reduce over N and cost O(W) instead of O(W*N);
only the fabric hops stay per sub-flow.  The flat form (identical physics,
no pre-reduction) is ``kernels/ref.py::linkload_cascade_ref``, the oracle
the tiered form is checked against in tests/test_netsim_compact.py and the
hypothesis property suite; they agree to float round-off (summation
grouping differs).

Backends of ``cascade_nic``
  * ``xla``    — ``kernels/ref.py::linkload_cascade_tiered_ref``, a
    ``jax.ops.segment_sum`` per hop; also the kernel's oracle.
  * ``pallas`` — one fused ``kernels/linkload.py::linkload_cascade_tiered``
    call: the scatter-adds become one-hot matmuls on the MXU, the cascade
    walks hops in the grid, and queue/mark fuse into the final grid step.
  * ``pallas_interpret`` — the same kernel interpreted on CPU (tests).
  * ``auto``   — pallas (compiled) on TPU, xla everywhere else; the
    interpreter is never picked implicitly.

DRILL's per-packet spray does not fit the per-path cascade (it splits one
sub-flow over ALL paths by queue-depth weights), so its 2-tier dataplane
lives here too (``drill_spray``) and is shared by both engines.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import linkload as ll, ref
from repro.netsim.topology import Topology

_BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")


def resolve_backend(backend: str) -> str:
    assert backend in _BACKENDS, backend
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def cascade_nic(
    fab_links: jax.Array,  # i32[..., N, Hf] fabric link ids, -1 = hop absent
    tx_link: jax.Array,  # i32[...] host_tx link id (shared by the N sub-flows)
    rx_link: jax.Array,  # i32[...] host_rx link id (shared by the N sub-flows)
    rates: jax.Array,  # f32[..., N] offered rate per sub-flow (bps)
    queue: jax.Array,  # f32[n_links + 1]
    capacity: jax.Array,  # f32[n_links + 1] bps (sentinel = 1e30)
    queue_mask: jax.Array,  # f32[n_links + 1]
    *,
    n_links: int,
    kmin: float,
    kmax: float,
    pmax: float,
    dt: float,
    qmax_bytes: float,
    bands: tuple[tuple[int, int], ...] | None = None,
    backend: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """NIC-tiered hop cascade: the flat cascade's physics, exploiting
    that the N sub-flows of a flow share their first (host_tx) and last
    (host_rx) hop — those two segment-sums run over flows (O(W)) instead of
    sub-flows (O(W*N)), and their scale gathers are per flow.  ``bands``
    (``Topology.bands``) narrows each hop's one-hot in the Pallas kernel to
    the link ids that hop can touch; the XLA backend does not need it.

    Both backends take the real links ``[:n_links]`` and append a zero
    sentinel slot to the per-link outputs.  Returns (arrival[n_links+1],
    new_queue[n_links+1], p_mark[n_links+1], thr[..., N])."""
    shape = rates.shape  # [..., N]
    N = shape[-1]
    hf = fab_links.shape[-1]
    args = (fab_links.reshape(-1, N, hf), tx_link.reshape(-1),
            rx_link.reshape(-1), rates.reshape(-1, N))
    backend = resolve_backend(backend)
    if backend == "xla":
        arrival_l, newq_l, mark_l, thr = ref.linkload_cascade_tiered_ref(
            *args, n_links, kmin, kmax, pmax, queue[:n_links],
            capacity[:n_links], queue_mask[:n_links], dt, qmax_bytes)
    else:
        arrival_l, newq_l, mark_l, thr = ll.linkload_cascade_tiered(
            *args, queue[:n_links], capacity[:n_links], queue_mask[:n_links],
            n_links=n_links, bands=bands, kmin=kmin, kmax=kmax, pmax=pmax,
            dt=dt, qmax_bytes=qmax_bytes,
            interpret=(backend == "pallas_interpret"),
        )
    zero = jnp.zeros((1,), jnp.float32)
    arrival = jnp.concatenate([arrival_l, zero])
    new_queue = jnp.concatenate([newq_l, zero])
    p_mark = jnp.concatenate([mark_l, zero])
    return arrival, new_queue, p_mark, thr.reshape(shape)


def subflow_mark_probs(
    links: jax.Array,  # i32[..., H]
    p_mark: jax.Array,  # f32[n_links + 1]
    n_links: int,
) -> tuple[jax.Array, jax.Array]:
    """(p_sub, p_sub_fabric): probability a packet of the sub-flow is marked
    on any hop / on any FABRIC hop (hops 1..H-2 — the marks the destination
    ToR mirrors back as Congestion Packets)."""
    lid = jnp.where(links >= 0, links, n_links)
    hop_mark = jnp.where(links >= 0, p_mark[lid], 0.0)
    p_sub = 1.0 - jnp.prod(1.0 - hop_mark, axis=-1)
    p_sub_fabric = 1.0 - jnp.prod(1.0 - hop_mark[..., 1:-1], axis=-1)
    return p_sub, p_sub_fabric


def subflow_mark_probs_nic(
    fab_links: jax.Array,  # i32[..., N, Hf]
    tx_link: jax.Array,  # i32[...]
    rx_link: jax.Array,  # i32[...]
    p_mark: jax.Array,  # f32[n_links + 1]
    n_links: int,
) -> tuple[jax.Array, jax.Array]:
    """NIC-tiered twin of ``subflow_mark_probs``: the host hops are shared
    by the N sub-flows, so their mark gathers run per flow; only the fabric
    hops gather per sub-flow.  p_sub_fabric is exactly the fabric product
    (hops 1..H-2 in the flat layout)."""
    lid = jnp.where(fab_links >= 0, fab_links, n_links)
    hop_mark = jnp.where(fab_links >= 0, p_mark[lid], 0.0)
    p_sub_fabric = 1.0 - jnp.prod(1.0 - hop_mark, axis=-1)  # [..., N]
    keep = (1.0 - p_mark[tx_link]) * (1.0 - p_mark[rx_link])  # [...]
    p_sub = 1.0 - keep[..., None] * (1.0 - p_sub_fabric)
    return p_sub, p_sub_fabric


def lossy_gbn_factor(
    fab_links: jax.Array,  # i32[..., N, Hf] fabric link ids, -1 = hop absent
    tx_link: jax.Array,  # i32[...]
    rx_link: jax.Array,  # i32[...]
    loss: jax.Array,  # f32[n_links + 1] per-link packet-loss rate
    *,
    n_links: int,
    window_pkts: float,
) -> jax.Array:
    """Goodput multiplier f32[..., N] for sub-flows crossing LOSSY links
    (faults.LossyLink): each drop rewinds a half go-back-N window on
    average, so goodput deflates by ``gbn_goodput_factor(p_loss, W)``
    while the DCQCN-offered rate keeps riding the wire — the retransmitted
    bytes ARE offered load, which is why the engine applies this factor to
    delivered throughput only (``thr``), never to the rates entering the
    hop cascade.  Per-path p_loss composes across hops exactly like the
    NIC-tiered mark product (``subflow_mark_probs_nic``): survival is the
    product of per-hop survivals, host hops shared by the N sub-flows."""
    from repro.core import gbn

    lid = jnp.where(fab_links >= 0, fab_links, n_links)
    hop_loss = jnp.where(fab_links >= 0, loss[lid], 0.0)
    surv_fab = jnp.prod(1.0 - hop_loss, axis=-1)  # [..., N]
    surv_host = (1.0 - loss[tx_link]) * (1.0 - loss[rx_link])  # [...]
    p_loss = 1.0 - surv_host[..., None] * surv_fab
    return gbn.gbn_goodput_factor(p_loss, window_pkts)


def reorder_gbn_factor(
    topo: Topology,
    pq: jax.Array,  # f32[n, P] per-path queue bytes (path_queue_2tier)
    spray: jax.Array,  # i32[n] paths a flowcell-split chunk straddles (1 = pinned)
    rc0: jax.Array,  # f32[n] per-flow offered rate (sub-flow 0)
    reorder: jax.Array,  # f32 scalar reorder budget in packets (traced operand)
    *,
    mtu_bytes: float,
    jitter_mtus: float,
    window_pkts: float,
    capacity: jax.Array | None = None,  # traced override of topo.capacity
) -> jax.Array:
    """Effective-bytes AMPLIFICATION >= 1 for flowcell-split flows: a chunk
    sprayed over ``spray`` paths sees inter-path skew (queue divergence
    across the straddled paths), and RoCE's go-back-N rewinds a half window
    per out-of-order arrival — so every delivered byte costs
    ``1 + p_ooo * W/2`` wire bytes.  The engine divides delivered ``thr``
    by this factor (retransmitted bytes ARE offered load, exactly the
    ``lossy_gbn_factor`` convention, just spelled as amplification so the
    no-reordering invariant reads ``factor == 1``).

    The skew model is ``drill_gbn_factor``'s, scaled by straddle coverage:
    spraying over k of P paths sees fraction (k-1)/(P-1) of the full
    inter-path spread (k=1 -> no skew, k=P -> the DRILL worst case).  The
    NIC's ``reorder`` budget (packets it can re-sequence before a go-back-N
    fires) buys back ``reorder * MTU / rate`` seconds of skew.  ``reorder``
    is a TRACED scalar so one compiled program covers every budget;
    ``spray`` is traced per-flow data so one program covers every split
    factor.  Exactly 1.0 wherever ``spray <= 1`` (all flowcells on one
    path: no reordering possible, the paper's invariant)."""
    from repro.core import gbn

    P = topo.n_paths
    cap = topo.capacity if capacity is None else capacity
    up_cap = cap[0]  # uplink block starts at 0 (2-tier layout)
    d_path = pq * 8.0 / jnp.maximum(up_cap, 1.0)  # [n, P] seconds
    dmax = jnp.max(d_path, -1)
    dmin = jnp.min(d_path, -1)
    full_spread = dmax - dmin  # skew across ALL P paths
    k = jnp.clip(spray.astype(jnp.float32), 1.0, float(P))
    frac = (k - 1.0) / jnp.float32(max(P - 1, 1))  # [n] straddle coverage
    mean_q = jnp.mean(pq, -1)
    jitter_bytes = jnp.minimum(0.5 * mean_q, jitter_mtus * mtu_bytes)
    jitter = jitter_bytes * 8.0 / jnp.maximum(up_cap, 1.0)
    skew = jnp.maximum(full_spread, jitter) * frac
    budget_s = reorder * mtu_bytes * 8.0 / jnp.maximum(rc0, 1.0)
    eff = jnp.maximum(skew - budget_s, 0.0)
    p_ooo = gbn.ooo_probability(eff, rc0, mtu_bytes)
    amp = 1.0 + p_ooo * (window_pkts / 2.0)
    return jnp.where(spray > 1, amp, 1.0)


def queue_mask_for(topo: Topology) -> jax.Array:
    """1.0 on links that queue and ECN-mark, 0.0 on host_tx (NIC-internal
    backlog, no ECN there) and on the -1 sentinel slot."""
    nl = topo.n_links
    h0 = nl - 2 * topo.n_hosts
    mask = jnp.ones((nl + 1,), jnp.float32)
    mask = mask.at[h0 : h0 + topo.n_hosts].set(0.0)
    return mask.at[nl].set(0.0)


def integrate_queue(
    queue: jax.Array,  # f32[n_links + 1]
    arrival: jax.Array,  # f32[n_links + 1]
    capacity: jax.Array,  # f32[n_links + 1]
    queue_mask: jax.Array,  # f32[n_links + 1]
    dparams,
    *,
    dt: float,
    qmax_bytes: float,
    n_links: int,
) -> tuple[jax.Array, jax.Array]:
    """Queue integration + RED/ECN marks for dataplanes that compute their
    own arrival vector (DRILL's spray).  cascade_nic() fuses the same update."""
    from repro.netsim import dcqcn as dcqcn_mod

    new_queue = jnp.clip(
        queue + (arrival - capacity) * dt / 8.0, 0.0, qmax_bytes
    ) * queue_mask
    p_mark = dcqcn_mod.mark_probability(new_queue, dparams).at[n_links].set(0.0)
    return new_queue, p_mark


def queue_fast_forward(
    queue: jax.Array,  # f32[n_links + 1]
    arrival: jax.Array,  # f32[n_links + 1] offered bps, constant over the span
    capacity: jax.Array,  # f32[n_links + 1]
    queue_mask: jax.Array,  # f32[n_links + 1]
    *,
    dt: float,
    n_steps: int,  # static span length
    qmax_bytes: float,
    n_links: int,
) -> tuple[jax.Array, jax.Array]:
    """Analytic ``n_steps``-step queue trajectory under CONSTANT arrivals.

    The per-step update ``q <- clip(q + delta, 0, qmax) * mask`` with a
    constant ``delta = (arrival - capacity) * dt/8`` is monotone in the
    step count, so clipping commutes with accumulation and step ``m`` is
    exactly ``clip(q0 + m*delta, 0, qmax) * mask`` (modulo f32 rounding of
    the product vs the iterated sum).  Used by the compact engine's
    quiescence fast-forward (DESIGN.md §15), whose predicate additionally
    guarantees no masked queue crosses the ECN kmin margin mid-span.

    Returns ``(q_final[n_links+1], max_queue_traj[n_steps])`` where the
    trajectory entry ``m`` is the max over real links after ``m+1`` steps
    (matching the per-step ``max_queue`` StepOutputs channel).
    """
    delta = (arrival - capacity) * (dt / 8.0)
    m = jnp.arange(1, n_steps + 1, dtype=jnp.float32)[:, None]
    traj = jnp.clip(queue[None, :] + m * delta[None, :], 0.0, qmax_bytes)
    traj = traj * queue_mask[None, :]
    return traj[-1], jnp.max(traj[:, :n_links], axis=1)


# ------------------------------------------------------------------ DRILL
def drill_spray(
    topo: Topology,
    queue: jax.Array,  # f32[n_links + 1]
    rc0: jax.Array,  # f32[n] per-flow offered rate (sub-flow 0)
    src: jax.Array,  # i32[n] source hosts
    dst: jax.Array,  # i32[n]
    src_leaf: jax.Array,  # i32[n]
    dst_leaf: jax.Array,  # i32[n]
    active0: jax.Array,  # bool[n, 1]
    drill_q0: float,
    capacity: jax.Array | None = None,  # traced override of topo.capacity
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """DRILL's per-packet spray on a 2-tier Clos: inverse-queue weights over
    all paths, cascaded host_tx -> uplink -> downlink -> host_rx.

    The per-leaf reductions and gathers run as one-hot matmuls over the
    (tiny) leaf axis — [n, L] gemms beat XLA:CPU's serial scatter-add on
    the [n, P] operands by ~2x at DRILL's collapsed-window sizes, and the
    one-hot gather back is exact (one 1.0 term, L-1 exact +0.0 terms).
    The matmuls run at ``Precision.HIGHEST``: on the TPU an f32 ``@``
    defaults to bf16 passes, which would round the bps rates.

    Returns (arrival[n_links+1], thr[n] delivered rate before the go-back-N
    penalty, w[n, P] path weights, pq[n, P] per-path queue bytes).
    """
    from repro.core import baselines

    cap = topo.capacity if capacity is None else capacity
    nl = topo.n_links
    L_, S_ = topo.n_leaf, topo.n_paths
    h0 = nl - 2 * topo.n_hosts
    up0 = 0
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    pq = path_queue_2tier(topo, queue, src_leaf, dst_leaf)  # [n, P]
    w = baselines.drill_weights(pq, drill_q0) * active0
    oh_s = (src_leaf[:, None] == jnp.arange(L_)[None, :]).astype(jnp.float32)
    oh_d = (dst_leaf[:, None] == jnp.arange(L_)[None, :]).astype(jnp.float32)
    arrival = jnp.zeros((nl + 1,), jnp.float32)
    # hop 0: host NIC
    tx_load = jax.ops.segment_sum(rc0, src, num_segments=topo.n_hosts)
    arrival = arrival.at[h0 : h0 + topo.n_hosts].add(tx_load)
    s_tx = jnp.minimum(1.0, cap[h0 + src] / jnp.maximum(tx_load[src], 1.0))
    r0 = rc0 * s_tx  # [n]
    # hop 1: uplinks (per-path split)
    r0w = r0[:, None] * w  # [n, P]
    up_load = dot(oh_s.T, r0w)  # [L, P]
    arrival = arrival.at[up0 : up0 + L_ * S_].add(up_load.reshape(-1))
    cap_up = cap[up0 : up0 + L_ * S_].reshape(L_, S_)
    s_up = jnp.minimum(1.0, cap_up / jnp.maximum(up_load, 1.0))
    r1 = r0w * dot(oh_s, s_up)  # [n, P]
    # hop 2: downlinks
    dn_load = dot(oh_d.T, r1)  # [L, P] (by dst)
    arrival = arrival.at[L_ * S_ : 2 * L_ * S_].add(dn_load.T.reshape(-1))
    cap_dn = cap[L_ * S_ : 2 * L_ * S_].reshape(S_, L_)
    s_dn = jnp.minimum(1.0, cap_dn.T / jnp.maximum(dn_load, 1.0))  # [L, P]
    r2 = r1 * dot(oh_d, s_dn)  # [n, P]
    # hop 3: receiver NIC
    r2sum = jnp.sum(r2, -1)
    rx_load = jax.ops.segment_sum(r2sum, dst, num_segments=topo.n_hosts)
    arrival = arrival.at[h0 + topo.n_hosts : h0 + 2 * topo.n_hosts].add(rx_load)
    s_rx = jnp.minimum(
        1.0, cap[h0 + topo.n_hosts + dst] / jnp.maximum(rx_load[dst], 1.0)
    )
    thr = r2sum * s_rx  # [n]
    return arrival, thr, w, pq


def drill_mark_probs(
    topo: Topology,
    p_mark: jax.Array,  # f32[n_links + 1]
    w: jax.Array,  # f32[n, P]
    src_leaf: jax.Array,
    dst_leaf: jax.Array,
    dst: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """(p_sub[n, 1], p_sub_fabric[n, 1]) for DRILL's weighted spray."""
    nl = topo.n_links
    L_, S_ = topo.n_leaf, topo.n_paths
    h0 = nl - 2 * topo.n_hosts
    pm_up = p_mark[0 : L_ * S_].reshape(L_, S_)[src_leaf]
    pm_dn = p_mark[L_ * S_ : 2 * L_ * S_].reshape(S_, L_).T[dst_leaf]
    pm_fab = 1.0 - (1.0 - pm_up) * (1.0 - pm_dn)  # [n, P]
    p_sub_fabric = jnp.sum(w * pm_fab, -1, keepdims=True)
    p_host = p_mark[h0 + topo.n_hosts + dst]
    p_sub = 1.0 - (1.0 - p_sub_fabric) * (1.0 - p_host[:, None])
    return p_sub, p_sub_fabric


def drill_gbn_factor(
    topo: Topology,
    pq: jax.Array,  # f32[n, P] per-path queue bytes
    w: jax.Array,  # f32[n, P] spray weights
    rc0: jax.Array,  # f32[n] offered rate
    *,
    mtu_bytes: float,
    jitter_mtus: float,
    window_pkts: float,
    capacity: jax.Array | None = None,  # traced override of topo.capacity
) -> jax.Array:
    """Go-back-N goodput penalty for DRILL's spray: packets of ONE QP sprayed
    over paths whose queueing delays differ get reordered; even with equal
    AVERAGE queues, per-packet occupancy jitter of O(queue) reorders at high
    rate.  spread = max over used paths of |delay - min|, floored by the
    jitter of the mean queue.  Returns the goodput multiplier f32[n]."""
    from repro.core import gbn

    P = topo.n_paths
    cap = topo.capacity if capacity is None else capacity
    up_cap = cap[0]  # uplink block starts at 0 (2-tier layout)
    d_path = pq * 8.0 / jnp.maximum(up_cap, 1.0)  # [n, P] seconds
    used = w > (0.5 / P)
    dmax = jnp.max(jnp.where(used, d_path, -jnp.inf), -1)
    dmin = jnp.min(jnp.where(used, d_path, jnp.inf), -1)
    spread = jnp.where(jnp.isfinite(dmax) & jnp.isfinite(dmin), dmax - dmin, 0.0)
    mean_q = jnp.sum(jnp.where(used, pq, 0.0), -1) / jnp.maximum(jnp.sum(used, -1), 1)
    jitter_bytes = jnp.minimum(0.5 * mean_q, jitter_mtus * mtu_bytes)
    jitter = jitter_bytes * 8.0 / jnp.maximum(up_cap, 1.0)
    p_ooo = gbn.ooo_probability(jnp.maximum(spread, jitter), rc0, mtu_bytes)
    return gbn.gbn_goodput_factor(p_ooo, window_pkts)


def path_queue_2tier(topo: Topology, queue, src_leaf, dst_leaf) -> jax.Array:
    """Queue bytes along each (up, down) path for every flow: f32[n, P]."""
    S, L = topo.n_paths, topo.n_leaf
    q_up = queue[0 : L * S].reshape(L, S)
    q_dn = queue[L * S : 2 * L * S].reshape(S, L)
    return q_up[src_leaf] + q_dn[:, :].T[dst_leaf]
