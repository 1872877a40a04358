"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

``linkload_cascade_tiered_ref`` is also the dataplane's path off the TPU
(``netsim/dataplane.cascade_nic``, backend ``xla``); ``linkload_cascade_ref``
is the flat cascade, the oracle the tiered layout is checked against."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, S, K, hd]
    v: jax.Array,  # [B, S, K, hd]
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32)) * (hd**-0.5)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= j <= i
    if window:
        mask &= (i - j) < window
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def linkload_cascade_ref(
    link_ids: jax.Array,  # i32[n, hops]  (-1 = no hop)
    rates: jax.Array,  # f32[n]
    n_links: int,
    kmin: float,
    kmax: float,
    pmax: float,
    queue: jax.Array,  # f32[n_links]
    capacity: jax.Array,  # f32[n_links]
    queue_mask: jax.Array,  # f32[n_links] 0 on queueless (host_tx) links
    dt: float,
    qmax_bytes: float = 8e6,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(arrival, new_queue, mark_prob, thr) — the full hop-cascaded dataplane
    step (netsim/dataplane.py §9): hop h's arrivals are the upstream-scaled
    rates, queues integrate total arrival, RED marks on the new queue."""
    hops = link_ids.shape[1]
    cap_ext = jnp.concatenate([capacity, jnp.full((1,), 1e30, jnp.float32)])
    lid = jnp.where(link_ids >= 0, link_ids, n_links)
    r = rates
    arrival = jnp.zeros((n_links + 1,), jnp.float32)
    for h in range(hops):
        lh = lid[:, h]
        load_h = jax.ops.segment_sum(r, lh, num_segments=n_links + 1)
        arrival = arrival + load_h.at[n_links].set(0.0)
        s_h = jnp.minimum(1.0, cap_ext[lh] / jnp.maximum(load_h[lh], 1.0))
        r = r * jnp.where(link_ids[:, h] >= 0, s_h, 1.0)
    arrival = arrival[:n_links]
    new_queue = jnp.clip(queue + (arrival - capacity) * dt / 8.0, 0.0, qmax_bytes)
    new_queue = new_queue * queue_mask
    ramp = (new_queue - kmin) / (kmax - kmin)
    mark = jnp.where(new_queue < kmin, 0.0, jnp.where(new_queue > kmax, 1.0, ramp * pmax))
    return arrival, new_queue, mark.astype(jnp.float32), r


def linkload_cascade_tiered_ref(
    fab_links: jax.Array,  # i32[n, N, Hf]  (-1 = no hop)
    tx_link: jax.Array,  # i32[n]
    rx_link: jax.Array,  # i32[n]
    rates: jax.Array,  # f32[n, N]
    n_links: int,
    kmin: float,
    kmax: float,
    pmax: float,
    queue: jax.Array,  # f32[n_links]
    capacity: jax.Array,  # f32[n_links]
    queue_mask: jax.Array,  # f32[n_links]
    dt: float,
    qmax_bytes: float = 8e6,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """(arrival, new_queue, mark_prob, thr[n, N]) — the NIC-tiered cascade
    (netsim/dataplane.cascade_nic): host_tx/host_rx hops pre-reduce the N
    sub-flows sharing a NIC, fabric hops stay per sub-flow."""
    n, N, hf = fab_links.shape
    cap_ext = jnp.concatenate([capacity, jnp.full((1,), 1e30, jnp.float32)])
    r = rates  # [n, N]
    tx_load = jax.ops.segment_sum(r.sum(-1), tx_link, num_segments=n_links + 1)
    arrival = tx_load.at[n_links].set(0.0)
    s_tx = jnp.minimum(1.0, cap_ext / jnp.maximum(tx_load, 1.0))
    r = r * s_tx[tx_link][:, None]
    lid = jnp.where(fab_links >= 0, fab_links, n_links).reshape(-1, hf)
    rf = r.reshape(-1)
    for h in range(hf):
        lh = lid[:, h]
        load_h = jax.ops.segment_sum(rf, lh, num_segments=n_links + 1)
        arrival = arrival + load_h.at[n_links].set(0.0)
        s_h = jnp.minimum(1.0, cap_ext / jnp.maximum(load_h, 1.0))
        rf = rf * s_h[lh]
    r = rf.reshape(n, N)
    rx_load = jax.ops.segment_sum(r.sum(-1), rx_link, num_segments=n_links + 1)
    arrival = arrival + rx_load.at[n_links].set(0.0)
    s_rx = jnp.minimum(1.0, cap_ext / jnp.maximum(rx_load, 1.0))
    thr = r * s_rx[rx_link][:, None]
    arrival = arrival[:n_links]
    new_queue = jnp.clip(queue + (arrival - capacity) * dt / 8.0, 0.0, qmax_bytes)
    new_queue = new_queue * queue_mask
    ramp = (new_queue - kmin) / (kmax - kmin)
    mark = jnp.where(new_queue < kmin, 0.0, jnp.where(new_queue > kmax, 1.0, ramp * pmax))
    return arrival, new_queue, mark.astype(jnp.float32), thr
