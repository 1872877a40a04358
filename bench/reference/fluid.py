"""Plain reference of the fluid fabric model, independent of the program.

A straightforward dense simulator over every flow of a trace: per-step path
choice (SeqBalance double hashing around the source ToR's congestion table,
or plain ECMP), the Shaper's equal split into N sub-flows, the hop-by-hop
link-load cascade (host NIC, fabric hops, receiver NIC) with per-link
``min(1, cap / load)`` service, queue integration with RED/ECN marks, the
expected-value DCQCN update, congestion packets mirrored to the source ToR,
and completion when every sub-flow is down to an eighth of a byte.  It
implements the semantics the paper describes (SeqBalance, arXiv:2407.09808,
sections III-IV) in the fluid form the simulator under test uses, and imports
nothing of it: the fabric's link numbering is built here from the
configuration file, and every link-load sum is a float32 ``segment_sum``.

``precision`` picks how the link-load sums and the per-link values gathered
back to flows are rounded before they are summed or applied:

* ``"f32"``  — plain float32 (the reference);
* ``"high"`` — each value rounded to a bf16 pair (hi + lo), which is what a
  one-hot matrix product at ``Precision.HIGH`` (three bf16 passes) gives,
  the one-hot side being exact (the control of the comparison).

The benchmark runs the reference after the measured window, on traces the
window's program served, and compares their trajectories (``compare``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

DONE_EPS_BYTES = 0.125
SENTINEL_CAP = np.float32(1e30)


class Fabric(NamedTuple):
    """Static link tables of one fabric (hashable: tuples and ints only)."""

    kind: str
    n_leaf: int
    n_paths: int
    hosts_per_leaf: int
    n_links: int
    tx0: int
    rx0: int
    capacity: tuple  # n_links + 1 floats, last = sentinel
    uplink_ids: tuple  # n_leaf rows of ToR uplink link ids
    n_fabric_hops: int
    layout: tuple  # kind-specific link-block offsets and widths

    @property
    def n_hosts(self) -> int:
        return self.n_leaf * self.hosts_per_leaf


def build_fabric(spec: dict) -> Fabric:
    """Link tables from a configuration's ``fabric`` block."""
    kind = spec["kind"]
    if kind == "leaf_spine":
        L, S, hpl = spec["n_leaf"], spec["n_spine"], spec["hosts_per_leaf"]
        H = L * hpl
        bw, hbw = spec["link_bw"], spec.get("host_bw", spec["link_bw"])
        n_links = 2 * L * S + 2 * H
        cap = np.zeros(n_links + 1, np.float32)
        cap[: 2 * L * S] = bw
        cap[2 * L * S: n_links] = hbw
        cap[-1] = SENTINEL_CAP
        up = [[l * S + s for s in range(S)] for l in range(L)]
        return Fabric(kind, L, S, hpl, n_links, 2 * L * S, 2 * L * S + H,
                      tuple(float(c) for c in cap), tuple(map(tuple, up)), 2,
                      (L, S))
    if kind == "three_tier":
        T, A, C = spec["n_tor"], spec["n_agg"], spec["n_core"]
        hpl = spec["hosts_per_tor"]
        H = T * hpl
        ta0, ac0 = 0, T * A
        ca0, at0 = T * A + A * C, T * A + 2 * A * C
        tx0 = at0 + A * T
        n_links = tx0 + 2 * H
        cap = np.zeros(n_links + 1, np.float32)
        cap[ta0:ac0] = spec["bw_tor_agg"]
        cap[ac0:at0] = spec["bw_agg_core"]
        cap[at0:tx0] = spec["bw_tor_agg"]
        cap[tx0:n_links] = spec["host_bw"]
        cap[-1] = SENTINEL_CAP
        up = [[t * A + a for a in range(A)] for t in range(T)]
        return Fabric(kind, T, A * C, hpl, n_links, tx0, tx0 + H,
                      tuple(float(c) for c in cap), tuple(map(tuple, up)), 4,
                      (T, A, C))
    raise ValueError(f"unknown fabric kind {kind!r}")


def fabric_hops(fab: Fabric, src_leaf, dst_leaf, path):
    """i32[..., n_fabric_hops] link ids of a path (-1 = absent: same leaf)."""
    inter = src_leaf != dst_leaf
    if fab.kind == "leaf_spine":
        L, S = fab.layout
        hops = [src_leaf * S + path, L * S + path * L + dst_leaf]
    else:
        T, A, C = fab.layout
        agg, core = path // C, path % C
        hops = [src_leaf * A + agg, T * A + agg * C + core,
                T * A + A * C + core * A + agg, T * A + 2 * A * C + agg * T + dst_leaf]
    return jnp.stack([jnp.where(inter, h, -1) for h in hops], -1).astype(jnp.int32)


# ------------------------------------------------------------- hashing
def _u32(x):
    return jnp.asarray(x, jnp.uint32)


def fmix32(h):
    h = _u32(h)
    h = h ^ (h >> 16)
    h = h * _u32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _u32(0xC2B2AE35)
    return h ^ (h >> 16)


def _rotl(x, r):
    return (x << _u32(r)) | (x >> _u32(32 - r))


def hash_tuple(src, dst, sport, dport, salt: int = 0):
    """murmur3-style hash of a five-tuple (the protocol is fixed: RoCEv2)."""
    h = _u32(salt) * _u32(0x9E3779B9) + _u32(0x2545F491)
    h = jnp.broadcast_to(h, jnp.broadcast_shapes(jnp.shape(src), jnp.shape(sport)))
    for k in (src, dst, sport, dport):
        k = _rotl(_u32(k) * _u32(0xCC9E2D51), 15) * _u32(0x1B873593)
        h = _rotl(h ^ k, 13) * _u32(5) + _u32(0xE6546B64)
    return fmix32(h ^ _u32(16))


# ------------------------------------------------------------- rounding
def _bf16(x):
    """float32 -> nearest bfloat16 (ties to even), kept in float32.  Done on
    the bits: a compiler that may keep excess precision (XLA on TPU) drops
    an f32 -> bf16 -> f32 convert pair, which would make the control a
    no-op."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    b = b + _u32(0x7FFF) + ((b >> 16) & _u32(1))
    return jax.lax.bitcast_convert_type(b & _u32(0xFFFF0000), jnp.float32)


def _round(x, precision: str):
    if precision == "f32":
        return x
    assert precision == "high", precision
    hi = _bf16(x)
    return hi + _bf16(x - hi)


class Params(NamedTuple):
    """Scheme and fluid-model settings (hashable)."""

    scheme: str  # "seqbalance" | "ecmp"
    n_sub: int
    min_split_bytes: float
    phi: float
    dt: float
    qmax_bytes: float
    cong_threshold_pkts: float
    kmin: float
    kmax: float
    pmax: float
    g: float
    r_ai: float
    min_rate: float
    cnp_interval: float
    alpha_interval: float
    rate_interval: float
    mtu: float


class Outputs(NamedTuple):
    goodput: jax.Array  # f32[T] delivered bps, summed over sub-flows
    max_queue: jax.Array  # f32[T] bytes, deepest queue after the step
    cnp: jax.Array  # f32[T] expected congestion packets mirrored
    uplink: jax.Array  # f32[T // s, n_leaf, n_up] ToR uplink arrivals, window means
    finish: jax.Array  # f32[F] completion time (+inf if not done)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def simulate(fab: Fabric, p: Params, n_steps: int, sample_every: int,
             precision: str, sizes, arrivals, src, dst, fid, valid) -> Outputs:
    """Run ``n_steps`` dense steps of one trace (arrays of one length F)."""
    F, N, P, nl = sizes.shape[0], p.n_sub, fab.n_paths, fab.n_links
    cap = jnp.asarray(np.asarray(fab.capacity, np.float32))
    qmask = jnp.ones((nl + 1,), jnp.float32).at[fab.tx0:fab.rx0].set(0.0).at[nl].set(0.0)
    line_rate = jnp.float32(fab.capacity[fab.tx0])
    hpl = fab.hosts_per_leaf
    src_leaf, dst_leaf = src // hpl, dst // hpl
    tx, rx = fab.tx0 + src, fab.rx0 + dst
    uplinks = jnp.asarray(np.asarray(fab.uplink_ids, np.int32))

    # Shaper: N equal sub-WQEs, only for messages worth splitting
    if N > 1:
        split = sizes >= p.min_split_bytes
        sub = jnp.where(split[:, None], jnp.broadcast_to(sizes[:, None] / N, (F, N)),
                        jnp.concatenate([sizes[:, None], jnp.zeros((F, N - 1))], 1))
        j = jnp.arange(N, dtype=jnp.uint32)
        qpn = _u32(fid)[:, None] * _u32(N) + j + _u32(0x1000)
        sport = _u32(0xC000) + fmix32(qpn) % _u32(0x3FFF)
    else:
        sub = sizes[:, None]
        sport = (_u32(0xB000) + fmix32(fid) % _u32(0x3FFF))[:, None]
    sub = sub.astype(jnp.float32)
    s_src = jnp.broadcast_to(_u32(src)[:, None], (F, N))
    s_dst = jnp.broadcast_to(_u32(dst)[:, None], (F, N))
    h1 = hash_tuple(s_src, s_dst, sport, _u32(4791))
    if p.scheme == "seqbalance":
        h2 = hash_tuple(s_src, s_dst, sport, _u32(4791), salt=0x5EED)
        i = jnp.arange(P, dtype=jnp.uint32)
        probes = ((h1[..., None] + i * (h2 * _u32(2) + _u32(1))[..., None])
                  % _u32(P)).astype(jnp.int32)  # [F, N, P]
    else:
        assert p.scheme == "ecmp", p.scheme
        ecmp_path = (h1 % _u32(P)).astype(jnp.int32)

    def seg(x, ids):
        return jax.ops.segment_sum(_round(x, precision), ids, num_segments=nl + 1).at[nl].set(0.0)

    def scale_of(load):
        return jnp.minimum(1.0, cap / jnp.maximum(load, 1.0))

    def gather(s, ids):
        return _round(s, precision)[ids]

    def step(st, _):
        rem, path, assigned, sub_done, finish, cc, inactive_until, queue, cnp, k = st
        t = k.astype(jnp.float32) * p.dt
        newly = valid & (t >= arrivals) & ~assigned
        if p.scheme == "seqbalance":
            inact = t < inactive_until  # [n_leaf, P]
            stale = inact.sum(-1, keepdims=True) > (P // 2)
            inact = jnp.where(stale, False, inact)
            rows = inact[src_leaf][:, None, :]
            probe_inact = jnp.take_along_axis(jnp.broadcast_to(rows, (F, N, P)), probes, -1)
            first = jnp.argmax(~probe_inact, -1)
            pick = jnp.where(jnp.any(~probe_inact, -1), first, 0)
            choice = jnp.take_along_axis(probes, pick[..., None], -1)[..., 0]
        else:
            choice = ecmp_path
        path = jnp.where(newly[:, None], choice, path)
        assigned = assigned | newly
        active = assigned[:, None] & ~sub_done & jnp.isinf(finish)[:, None]
        rc = jnp.where(active, jnp.minimum(cc[0], rem * 8.0 / p.dt), 0.0)

        # hop cascade: host NIC (shared by the N sub-flows), fabric, host NIC
        lid = fabric_hops(fab, src_leaf[:, None], dst_leaf[:, None], path)
        lid = jnp.where(lid >= 0, lid, nl)  # [F, N, hf]
        load = seg(rc.sum(-1), tx)
        arrival = load
        r = rc * gather(scale_of(load), tx)[:, None]
        for h in range(fab.n_fabric_hops):
            load = seg(r.reshape(-1), lid[..., h].reshape(-1))
            arrival = arrival + load
            r = r * gather(scale_of(load), lid[..., h])
        load = seg(r.sum(-1), rx)
        arrival = arrival + load
        thr = r * gather(scale_of(load), rx)[:, None]
        new_q = jnp.clip(queue + (arrival - cap) * p.dt / 8.0, 0.0, p.qmax_bytes) * qmask
        ramp = (new_q - p.kmin) / (p.kmax - p.kmin)
        mark = jnp.where(new_q < p.kmin, 0.0, jnp.where(new_q > p.kmax, 1.0, ramp * p.pmax))
        mark = mark.at[nl].set(0.0)
        fab_keep = jnp.prod(1.0 - mark[lid], -1)  # sentinel marks 0
        p_fab = 1.0 - fab_keep
        p_sub = 1.0 - ((1.0 - mark[tx]) * (1.0 - mark[rx]))[:, None] * fab_keep

        new_rem = jnp.maximum(rem - jnp.where(active, thr * p.dt / 8.0, 0.0), 0.0)
        sub_done = assigned[:, None] & (new_rem <= DONE_EPS_BYTES)
        done = jnp.all(sub_done, -1) & assigned & valid
        finish = jnp.where(jnp.isinf(finish) & done, t + p.dt, finish)

        # DCQCN, expected-value form: CNP and recovery branches blended by
        # the probability that a CNP fires this step
        rcur, rt, alpha, t_cnp, t_rate, stage = cc
        pk = jnp.maximum(rcur * p.dt / (8.0 * p.mtu), 1.0)
        e = jnp.where((t_cnp >= p.cnp_interval) & active,
                      1.0 - jnp.exp(pk * jnp.log1p(-jnp.minimum(p_sub, 0.999))), 0.0)
        e = e.astype(jnp.float32)
        rc_c = jnp.maximum(rcur * (1.0 - alpha / 2.0), p.min_rate)
        alpha_c = (1.0 - p.g) * alpha + p.g
        t_r = t_rate + p.dt
        do_rate = t_r >= p.rate_interval
        rc_n = jnp.minimum(jnp.where(do_rate, (rcur + rt) / 2.0, rcur), line_rate)
        rt_n = jnp.minimum(jnp.where(do_rate & (stage >= 5.0), rt + p.r_ai, rt), line_rate)
        stage_n = jnp.where(do_rate, stage + 1.0, stage)
        alpha_n = alpha * jnp.float32(1.0 - p.g) ** jnp.float32(p.dt / p.alpha_interval)
        mix = lambda c, n: e * c + (1.0 - e) * n
        new_cc = (mix(rc_c, rc_n), mix(rcur, rt_n), mix(alpha_c, alpha_n),
                  mix(0.0, t_cnp + p.dt), mix(0.0, jnp.where(do_rate, 0.0, t_r)),
                  mix(0.0, stage_n))
        cc = tuple(jnp.where(active, a, b) for a, b in zip(new_cc, cc))

        # congestion packets: expected fabric marks mirrored to the source ToR
        pkts = jnp.where(active, rc * p.dt / (8.0 * p.mtu), 0.0) * p_fab
        if p.scheme == "seqbalance":
            intensity = jnp.zeros((fab.n_leaf, P), jnp.float32).at[
                jnp.broadcast_to(src_leaf[:, None], (F, N)).reshape(-1),
                jnp.clip(path, 0, P - 1).reshape(-1)].add(pkts.reshape(-1))
            inactive_until = jnp.maximum(
                inactive_until,
                jnp.where(intensity >= p.cong_threshold_pkts, t + jnp.float32(p.phi), -jnp.inf))
        out = (jnp.sum(jnp.where(active, thr, 0.0)), jnp.max(new_q[:nl]), jnp.sum(pkts),
               arrival[uplinks])
        return (new_rem, path, assigned, sub_done, finish, cc, inactive_until, new_q,
                cnp + jnp.sum(pkts), k + 1), out

    full = lambda v: jnp.full((F, N), v, jnp.float32)
    cc0 = (full(line_rate), full(line_rate), full(1.0), full(1.0), full(0.0), full(0.0))
    st0 = (sub, jnp.full((F, N), -1, jnp.int32), jnp.zeros((F,), bool), sub <= 0.0,
           jnp.full((F,), jnp.inf, jnp.float32), cc0,
           jnp.full((fab.n_leaf, P), -jnp.inf, jnp.float32),
           jnp.zeros((nl + 1,), jnp.float32), jnp.zeros((), jnp.float32),
           jnp.zeros((), jnp.int32))
    final, (gp, mq, cnp, up) = jax.lax.scan(step, st0, None, length=n_steps)
    n_win = n_steps // sample_every
    up = up[: n_win * sample_every].reshape((n_win, sample_every) + up.shape[1:]).mean(1)
    return Outputs(gp, mq, cnp, up, final[4])


@functools.lru_cache(maxsize=None)
def batched(fab: Fabric, p: Params, n_steps: int, sample_every: int, precision: str):
    """``simulate`` over a leading batch of traces, jitted once per
    setting."""
    return jax.jit(jax.vmap(functools.partial(simulate, fab, p, n_steps, sample_every,
                                              precision)))


def pad_to(n: int, multiple: int = 2048) -> int:
    """Flow count padded to a multiple, so that traces of similar size share
    one compiled reference."""
    return -(-max(n, 1) // multiple) * multiple


def params_from(config: dict, traffic: dict) -> Params:
    """Reference settings from a configuration and a traffic file."""
    d = config["dcqcn"]
    sim = traffic["sim"]
    n_sub = sim.get("n_sub", 4) if sim["scheme"] == "seqbalance" else 1
    return Params(
        scheme=sim["scheme"], n_sub=n_sub,
        min_split_bytes=sim.get("min_split_bytes", 16e3), phi=sim.get("phi", 32e-6),
        dt=sim["dt"], qmax_bytes=sim.get("qmax_bytes", 8e6),
        cong_threshold_pkts=sim.get("cong_threshold_pkts", 1.0),
        kmin=d["kmin_bytes"], kmax=d["kmax_bytes"], pmax=d["pmax"], g=d["g"],
        r_ai=d["r_ai"], min_rate=d["min_rate"], cnp_interval=d["cnp_interval"],
        alpha_interval=d["alpha_interval"], rate_interval=d["rate_interval"],
        mtu=d["mtu_bytes"])
