"""Fluid flow-level datacenter simulator — the NS3-equivalent (paper §IV).

One jitted ``lax.scan`` over time steps of ``dt``.  The whole datacenter is
a pytree: per-sub-flow transfer state, per-link queues, DCQCN rate state,
and (for SeqBalance) the source-ToR Congestion Tables.  Five schemes share
the step function; scheme choice is a *static* argument so each scheme
compiles to its own specialized program.

Fluid model recap (DESIGN.md §8):
  offered[l]  = sum of sub-flow DCQCN rates crossing link l
  scale[l]    = min(1, cap[l]/offered[l])           (switch serves at cap)
  goodput_sf  = rc * min over the sub-flow's hops of scale
  q[l]       += (offered[l] - cap[l])+ * dt          (congestion signal)
  ECN mark    : RED ramp on q;   DCQCN reacts per sub-flow
  SeqBalance  : fabric marks are mirrored to the source ToR as Congestion
                Packets -> CongestionTable inactive for phi; NEW sub-flows
                double-hash around inactive paths; placed sub-flows never
                move (=> no reordering by construction).
  DRILL       : per-packet spray -> per-step inverse-queue weights over all
                paths; pays the go-back-N goodput penalty (core/gbn.py).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, congestion_table as ctab, hashing, routing, shaper
from repro.netsim import dataplane, dcqcn as dcqcn_mod
from repro.netsim.compile_cache import enable_compile_cache
from repro.netsim.topology import Topology
from repro.netsim.workloads import Trace

SCHEMES = ("seqbalance", "ecmp", "letflow", "conga", "drill", "flowlet_timeout")

# A sub-flow is complete when its remaining bytes drop below this.  The
# ``rc <= remaining*8/dt`` cap makes the last bytes decay geometrically, so
# an exact-zero test would tail for ~8 steps on f32 underflow — and WHICH
# step it underflows on is 1-ulp sensitive to summation order, which would
# make dense vs active-window finish times diverge.  An eighth of a byte is
# far below one packet, so cutting there changes nothing physical, and even
# MAX_SUBFLOWS-many sub-flow residues stay under one byte per WQE.
DONE_EPS_BYTES = 0.125


@dataclasses.dataclass(frozen=True)
class SimConfig:
    scheme: str = "seqbalance"
    n_sub: int = 4  # N (SeqBalance Shaper); forced to 1 for other schemes
    min_split_bytes: float = 16e3  # Shaper floor: WQEs below this stay whole
    phi: float = 32e-6
    flowlet_timeout: float = 100e-6
    dt: float = 10e-6
    duration_s: float = 20e-3
    dcqcn: dcqcn_mod.DCQCNParams = dcqcn_mod.DCQCNParams()
    gbn_window_pkts: float = 16.0
    drill_jitter_mtus: float = 4.0
    drill_q0: float = 1500.0
    mark_salt: int = 0xA5A5
    qmax_bytes: float = 8e6
    # a path is declared congested when at least this many ECN-marked
    # packets are mirrored back to the source ToR within one step (the
    # expected-marks intensity; deterministic, avoids mark-noise herding)
    cong_threshold_pkts: float = 1.0
    # dataplane backend: "auto" (Pallas on TPU, XLA elsewhere), "xla",
    # "pallas", or "pallas_interpret" (tests) — see netsim/dataplane.py
    dataplane: str = "auto"
    # compact engine (netsim/compact.py) only: the per-step while_loop runs
    # in lax.scan chunks of this many steps (early exit checked per chunk)
    chunk_steps: int = 32
    # compact engine only: window-average the [T, L, S] uplink trace over
    # this many steps inside the scan — sweeps that only need sampled
    # imbalance stats (metrics.throughput_imbalance's sample_every) stop
    # materializing the full per-step trace.  1 = keep every step (exact
    # dense-engine layout).
    uplink_sample_every: int = 1
    # compact engine only: event-driven adaptive dt (DESIGN.md §15).  When
    # True, each chunk boundary evaluates a quiescence predicate (no
    # arrival / finish / capacity edge / ECN crossing possible inside the
    # macro-step, DCQCN pinned at line rate) and a lax.cond fast-forwards
    # the whole macro-step in closed form instead of scanning it.  False
    # keeps the step loop bit-identical to the fixed-dt engine.
    adaptive: bool = False
    # macro-step cap, in scan chunks: the fast-forward span is
    # ff_macro_chunks * chunk_steps worth of dt steps (chunk boundaries are
    # the event grid, so spans stay chunk-aligned).  1 = one chunk.
    ff_macro_chunks: int = 1
    # quiescence margins: queues must stay below ff_kmin_frac * kmin for
    # the whole span (conservative headroom under the ECN ramp), and no
    # active sub-flow may finish within span + ff_margin_steps steps.
    ff_kmin_frac: float = 0.9
    ff_margin_steps: int = 2

    def __post_init__(self):
        assert self.scheme in SCHEMES, self.scheme
        assert self.dataplane in ("auto", "xla", "pallas", "pallas_interpret")
        assert self.chunk_steps >= 1 and self.uplink_sample_every >= 1
        assert self.ff_macro_chunks >= 1 and self.ff_margin_steps >= 0
        assert 0.0 < self.ff_kmin_frac <= 1.0
        if self.scheme != "seqbalance":
            object.__setattr__(self, "n_sub", 1)


class SimState(NamedTuple):
    remaining: jax.Array  # f32[F, N] bytes
    path: jax.Array  # i32[F, N]
    assigned: jax.Array  # bool[F]
    sub_done: jax.Array  # bool[F, N]
    finish: jax.Array  # f32[F] (+inf until CQE)
    cc: dcqcn_mod.DCQCNState  # [F, N]
    table: ctab.CongestionTable  # [n_leaf, n_paths]
    queue: jax.Array  # f32[n_links+1]
    cqe: shaper.CQEState  # [F]
    cnp_pkts: jax.Array  # f32 scalar — Congestion Packet counter (Table II)
    step: jax.Array  # i32


class StepOutputs(NamedTuple):
    uplink_load: jax.Array  # f32[n_leaf, n_uplinks] offered bps
    goodput_total: jax.Array  # f32 scalar bps (sum of delivered)
    cnp_rate: jax.Array  # f32 congestion packets this step
    max_queue: jax.Array  # f32 bytes


def _u32(x):
    return jnp.asarray(x, jnp.uint32)


class FlowConsts(NamedTuple):
    """Per-flow constants derived once from the trace (shared by the dense
    oracle here and the active-window engine in netsim/compact.py)."""

    sub_sizes: jax.Array  # f32[F, N] Shaper split (min_split floor applied)
    s5: tuple  # 4 x u32[F, N] per-sub-flow five-tuples (SeqBalance QPs)
    f5: tuple  # 4 x u32[F] per-flow five-tuple (other schemes)
    sub_salt: jax.Array  # u32[F, N] DCQCN mark-draw salt
    src_leaf: jax.Array  # i32[F]
    dst_leaf: jax.Array  # i32[F]


def flow_constants(topo: Topology, cfg: SimConfig, sizes, src, dst, fid) -> FlowConsts:
    F = sizes.shape[0]
    N = cfg.n_sub
    sub_sizes = shaper.split_wqe(sizes, N)  # f32[F, N]
    if N > 1:
        # The Shaper only segments WQEs worth segmenting: below the floor a
        # message rides a single QP (sub-WQE 0); its sibling slots carry
        # zero bytes and are born completed (their CQE bits set trivially).
        whole = jnp.concatenate(
            [sizes[:, None], jnp.zeros((F, N - 1), sizes.dtype)], axis=1
        )
        split_mask = (sizes >= cfg.min_split_bytes)[:, None]
        sub_sizes = jnp.where(split_mask, sub_sizes, whole)
    # five-tuples: SeqBalance -> per-sub-flow QPs; others -> per-flow
    s5 = shaper.subflow_five_tuples(src, dst, fid, N)  # each [F, N]
    f5 = (_u32(src), _u32(dst), _u32(0xB000) + (hashing.fmix32(fid) % _u32(0x3FFF)),
          jnp.full((F,), 4791, jnp.uint32))
    sub_salt = hashing.fmix32(s5[2] ^ (_u32(fid)[:, None] * _u32(2246822519)))  # [F,N]
    hpl = topo.hosts_per_leaf
    return FlowConsts(sub_sizes, s5, f5, sub_salt, src // hpl, dst // hpl)


def line_rate_of(topo: Topology) -> jax.Array:
    return topo.capacity[topo.n_links - 2 * topo.n_hosts]  # host_tx[0] bw


def build_sim(topo: Topology, cfg: SimConfig, trace: Trace, reorder=None):
    """Returns (init_state, step_fn, static) for the given scheme/topo/trace.

    ``reorder`` (traced f32 scalar or None) switches on the flowcell
    reordering-cost model: delivered throughput divides by
    ``dataplane.reorder_gbn_factor`` wherever the trace's ``spray`` column
    says a flow's parent chunk straddles >1 path.  ``None`` compiles the
    exact pre-flowcell program (the Python-level gate, same convention as
    the compact engine's ``loss``)."""
    F = len(trace.sizes)
    N = cfg.n_sub
    P = topo.n_paths

    sizes = jnp.asarray(trace.sizes)
    arrivals = jnp.asarray(trace.arrivals)
    src = jnp.asarray(trace.src)
    dst = jnp.asarray(trace.dst)
    fid = jnp.asarray(trace.flow_id)
    valid = jnp.asarray(trace.valid)
    spray = jnp.asarray(trace.spray)

    fc = flow_constants(topo, cfg, sizes, src, dst, fid)
    sub_sizes, s5, f5, sub_salt = fc.sub_sizes, fc.s5, fc.f5, fc.sub_salt
    src_leaf, dst_leaf = fc.src_leaf, fc.dst_leaf
    line_rate = line_rate_of(topo)
    qmask = dataplane.queue_mask_for(topo)

    if cfg.scheme in ("conga", "drill", "flowlet_timeout"):
        assert topo.kind == "leaf_spine", f"{cfg.scheme} is 2-tier only (paper §IV.B)"
    if reorder is not None:
        assert topo.kind == "leaf_spine", "reorder cost model is 2-tier only"
    if cfg.scheme == "flowlet_timeout":
        # WCMP re-draw weights: the per-leaf uplink capacities (the
        # asymmetric-topology flowlet controller — fat uplinks absorb
        # proportionally more flowlets; uniform capacities -> LetFlow).
        cap_up = topo.capacity[: topo.n_leaf * P].reshape(topo.n_leaf, P)
        up_w = baselines.wcmp_weights(cap_up)  # [L, P]

    nl = topo.n_links
    tx_link, rx_link = topo.nic_links(src, dst)  # i32[F] — path-independent

    def init_state() -> SimState:
        return SimState(
            remaining=sub_sizes,
            path=jnp.full((F, N), -1, jnp.int32),
            assigned=jnp.zeros((F,), bool),
            sub_done=sub_sizes <= 0.0,
            finish=jnp.full((F,), jnp.inf, jnp.float32),
            cc=dcqcn_mod.init_state((F, N), line_rate),
            table=ctab.CongestionTable.create(topo.n_leaf, P),
            queue=jnp.zeros((nl + 1,), jnp.float32),
            cqe=shaper.CQEState.create(F, N),
            cnp_pkts=jnp.zeros((), jnp.float32),
            step=jnp.zeros((), jnp.int32),
        )

    dparams = cfg.dcqcn

    def step_fn(state: SimState, _=None):
        t = state.step.astype(jnp.float32) * cfg.dt
        arrived = valid & (t >= arrivals)
        newly = arrived & ~state.assigned
        active_flow = state.assigned & jnp.isinf(state.finish)

        # ---------------- path (re)assignment ----------------
        path = state.path
        if cfg.scheme == "seqbalance":
            inact = ctab.inactive_matrix(state.table, t)  # [L, P]
            # Congestion that is GLOBAL carries no routing signal: if more
            # than half of a ToR's paths are marked, avoiding the marked
            # ones just herds arrivals onto the remainder.  Treat the table
            # as stale in that case and fall back to the plain hash (the
            # paper's table is only ever differential: "the stored
            # information pertains only to paths experiencing congestion").
            stale = inact.sum(-1, keepdims=True) > (P // 2)
            inact = jnp.where(stale, False, inact)
            rows = inact[src_leaf][:, None, :]  # [F,1,P]
            rows = jnp.broadcast_to(rows, (F, N, P))
            p_new = routing.select_paths(*s5, rows, P)  # [F,N]
            path = jnp.where(newly[:, None], p_new, path)
        elif cfg.scheme == "ecmp":
            p_new = routing.ecmp_paths(*f5, P)[:, None]
            path = jnp.where(newly[:, None], p_new, path)
        elif cfg.scheme in ("letflow", "conga", "flowlet_timeout"):
            rng = hashing.fmix32(fid ^ _u32(state.step) * _u32(0x85EBCA77))
            p_init = routing.ecmp_paths(*f5, P)
            gap = baselines.flowlet_gap_occurs(
                state.cc.rc[:, 0], dparams.mtu_bytes, cfg.flowlet_timeout
            )
            if cfg.scheme == "letflow":
                p_re = baselines.letflow_paths(path[:, 0], gap, rng, P)
            elif cfg.scheme == "flowlet_timeout":
                p_re = baselines.flowlet_wcmp_paths(path[:, 0], gap, rng, up_w[src_leaf])
            else:
                # CONGA reroutes to the least-congested path, but only at a
                # flowlet boundary; initial placement stays hash-based (the
                # fluid model would otherwise herd every same-step arrival
                # onto one path, which the real per-flowlet DRE feedback
                # does not do).
                pq = dataplane.path_queue_2tier(topo, state.queue, src_leaf, dst_leaf)
                p_re = baselines.conga_paths(path[:, 0], gap, pq)
            p_next = jnp.where(newly, p_init, jnp.where(active_flow, p_re, path[:, 0]))
            path = p_next[:, None]
        else:  # drill: nominal path 0; real split via weights below
            path = jnp.where(newly[:, None], 0, path)
        assigned = state.assigned | newly

        active = assigned[:, None] & ~state.sub_done & jnp.isinf(state.finish)[:, None]
        # a sub-flow can never offer more than the bytes it still has to send
        # (a 4 KB message is a 0.3 us burst at 100G, not a full dt of line rate)
        rc = jnp.where(
            active, jnp.minimum(state.cc.rc, state.remaining * 8.0 / cfg.dt), 0.0
        )  # [F,N]

        # -------- offered load, cascaded hop-by-hop (NIC serializes first,
        # then fabric: a hop's arrivals are the UPSTREAM-scaled rates, so a
        # host can never inject more than its NIC line rate into the fabric).
        # The pipeline lives in netsim/dataplane.py, shared with the
        # active-window engine and the linkload_cascade Pallas kernels; the
        # NIC-tiered form pre-reduces the N sub-flows sharing a host NIC.
        if cfg.scheme == "drill":
            arrival, thr, w, pq = dataplane.drill_spray(
                topo, state.queue, rc[:, 0], src, dst, src_leaf, dst_leaf,
                active[:, 0:1], cfg.drill_q0,
            )
            new_queue, p_mark = dataplane.integrate_queue(
                state.queue, arrival, topo.capacity, qmask, dparams,
                dt=cfg.dt, qmax_bytes=cfg.qmax_bytes, n_links=nl,
            )
            p_sub, p_sub_fabric = dataplane.drill_mark_probs(
                topo, p_mark, w, src_leaf, dst_leaf, dst
            )
            thr = thr * dataplane.drill_gbn_factor(
                topo, pq, w, rc[:, 0], mtu_bytes=dparams.mtu_bytes,
                jitter_mtus=cfg.drill_jitter_mtus, window_pkts=cfg.gbn_window_pkts,
            )
            thr = thr[:, None]  # [F,1]
        else:
            fab = topo.fabric_links(src_leaf[:, None], dst_leaf[:, None], path)
            arrival, new_queue, p_mark, thr = dataplane.cascade_nic(
                fab, tx_link, rx_link, rc, state.queue, topo.capacity, qmask,
                n_links=nl, kmin=dparams.kmin_bytes, kmax=dparams.kmax_bytes,
                pmax=dparams.pmax, dt=cfg.dt, qmax_bytes=cfg.qmax_bytes,
                bands=topo.bands, backend=cfg.dataplane,
            )
            p_sub, p_sub_fabric = dataplane.subflow_mark_probs_nic(
                fab, tx_link, rx_link, p_mark, nl
            )
            if reorder is not None:
                pq = dataplane.path_queue_2tier(topo, state.queue, src_leaf, dst_leaf)
                amp = dataplane.reorder_gbn_factor(
                    topo, pq, spray, rc[:, 0], reorder,
                    mtu_bytes=dparams.mtu_bytes,
                    jitter_mtus=cfg.drill_jitter_mtus,
                    window_pkts=cfg.gbn_window_pkts,
                )
                thr = thr / amp[:, None]

        # ---------------- transfer progress & CQE ----------------
        delivered = thr * cfg.dt / 8.0  # bytes
        new_remaining = jnp.maximum(state.remaining - jnp.where(active, delivered, 0.0), 0.0)
        sub_done = assigned[:, None] & (new_remaining <= DONE_EPS_BYTES)
        cqe = shaper.ack_mask(state.cqe, sub_done)
        all_done = shaper.cqe_ready(cqe) & assigned & valid
        finish = jnp.where(jnp.isinf(state.finish) & all_done, t + cfg.dt, state.finish)

        # ---------------- DCQCN ----------------
        flow_salt = sub_salt if cfg.scheme == "seqbalance" else sub_salt[:, :1]
        flow_salt = jnp.broadcast_to(flow_salt, (F, N))
        cc, _ = dcqcn_mod.step(
            state.cc, p_sub, active, cfg.dt, line_rate, dparams, state.step, flow_salt
        )

        # ---------------- SeqBalance Congestion Packets ----------------
        table = state.table
        pkts = jnp.where(active, rc * cfg.dt / (8.0 * dparams.mtu_bytes), 0.0)
        exp_cong_pkts = jnp.sum(pkts * p_sub_fabric)  # mirrored-packet count
        if cfg.scheme == "seqbalance":
            # expected number of marked data packets per (source ToR, path)
            # this step = expected Congestion Packets mirrored back; the
            # source ToR marks the path inactive when at least one arrives.
            intensity = jnp.zeros((topo.n_leaf, P), jnp.float32)
            idx_leaf = jnp.broadcast_to(src_leaf[:, None], (F, N)).reshape(-1)
            idx_path = jnp.clip(path, 0, P - 1).reshape(-1)
            intensity = intensity.at[idx_leaf, idx_path].add(
                (pkts * p_sub_fabric).reshape(-1)
            )
            dense = intensity >= cfg.cong_threshold_pkts
            table = ctab.mark_congested_dense(table, dense, t, cfg.phi)

        new_state = SimState(
            remaining=new_remaining,
            path=path,
            assigned=assigned,
            sub_done=sub_done,
            finish=finish,
            cc=cc,
            table=table,
            queue=new_queue,
            cqe=cqe,
            cnp_pkts=state.cnp_pkts + exp_cong_pkts,
            step=state.step + 1,
        )
        out = StepOutputs(
            uplink_load=arrival[jnp.asarray(topo.uplink_ids)],
            goodput_total=jnp.sum(jnp.where(active, thr, 0.0)),
            cnp_rate=exp_cong_pkts,
            max_queue=jnp.max(new_queue[:nl]),
        )
        return new_state, out

    return init_state, step_fn


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run(topo: Topology, cfg: SimConfig, trace_arrays):
    trace = Trace(*trace_arrays)
    init_state, step_fn = build_sim(topo, cfg, trace)
    n_steps = int(round(cfg.duration_s / cfg.dt))
    final, outs = jax.lax.scan(step_fn, init_state(), None, length=n_steps)
    return final, outs


@functools.partial(jax.jit, static_argnums=(0, 1))
def _run_reorder(topo: Topology, cfg: SimConfig, trace_arrays, reorder):
    trace = Trace(*trace_arrays)
    init_state, step_fn = build_sim(topo, cfg, trace, reorder=reorder)
    n_steps = int(round(cfg.duration_s / cfg.dt))
    final, outs = jax.lax.scan(step_fn, init_state(), None, length=n_steps)
    return final, outs


def simulate(
    topo: Topology, cfg: SimConfig, trace: Trace, reorder=None
) -> tuple[SimState, StepOutputs]:
    """Run the fluid simulation; returns (final_state, per-step outputs).

    ``reorder`` (float packets or None) enables the flowcell reordering
    cost as a TRACED budget: one compiled program per (topo, cfg) covers
    every budget value.  ``None`` dispatches the pre-flowcell program."""
    enable_compile_cache()
    arrays = (trace.sizes, trace.arrivals, trace.src, trace.dst,
              trace.flow_id, trace.valid, trace.spray)
    arrays = tuple(jnp.asarray(a) for a in arrays)
    if reorder is None:
        return _run(topo, cfg, arrays)
    return _run_reorder(topo, cfg, arrays, jnp.float32(reorder))
