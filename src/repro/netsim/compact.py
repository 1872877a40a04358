"""Active-window compacted fluid simulator (DESIGN.md §9/§10).

The dense engine (netsim/engine.py) does O(F) work per ``dt`` step over all
flows in the trace — but at any instant only a small working set is in
flight (most flows already finished or not yet arrived).  This engine sorts
flows by arrival and carries a compact ``[W, N]`` working set of *slots*:

  * admit   — each step, flows whose arrival time has passed are gathered
    into free slots in arrival order (``searchsorted`` on the sorted arrival
    vector gives the arrived count; free slots are ranked by cumsum).
    Admission also snapshots everything the per-step physics needs about
    the flow into the slot-indexed ``SlotCache`` (NIC/fabric link ids, leaf
    ids, DCQCN salts, host ids) — placed sub-flows never move, so none of
    it has to be re-derived from the trace or topology per step.
  * run     — the per-step physics (path choice, DCQCN, hop cascade, ECN)
    is byte-identical to the dense engine but over W slots, via the shared
    netsim/dataplane.py pipeline (NIC-tiered cascade).
  * finish  — completed slots scatter their finish time into a global
    ``[F]`` vector (scatter-min, drop-mode for empty slots) and free up.

W is a precomputed max-concurrency bound from the trace
(``max_concurrency_bound``), padded up.  If the bound is ever exceeded the
engine does not lose flows: arrivals queue at the NIC and admit as slots
free (``spill_steps`` in the result counts the steps where that happened,
so callers can verify the bound held — it should be 0 for results that
must match the dense oracle bit-for-bit-ish).

The step loop runs as ``cfg.chunk_steps``-long ``lax.scan`` chunks inside
an early-exit ``while_loop`` (once every flow has admitted and finished
and the queues have drained, the remaining steps are exact no-ops), and
``cfg.uplink_sample_every`` folds the imbalance window-averaging into the
scan so sweeps stop materializing the full ``[T, L, S]`` uplink trace.

The dense engine stays available as the correctness oracle
(``benchmarks/common.run_sim(dense=True)``); equivalence is asserted in
tests/test_netsim_compact.py and recorded per-sweep in BENCH_netsim.json.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, congestion_table as ctab, hashing, routing
from repro.netsim import dataplane, dcqcn as dcqcn_mod
from repro.netsim.engine import (
    DONE_EPS_BYTES, SimConfig, StepOutputs, flow_constants, line_rate_of,
)
from repro.netsim.topology import Topology
from repro.netsim.workloads import Trace
from repro.obs.scopes import scope


class SlotCache(NamedTuple):
    """Admit-time route cache: per-slot constants snapshotted when a flow
    lands in its slot, so the per-step physics never gathers from the
    ``[F]`` trace arrays or re-derives link ids from the topology.  Stale
    entries of freed slots are harmless — their offered rate is 0, so they
    contribute exact +0.0 to every segment-sum they touch."""

    tx: jax.Array  # i32[W] host_tx link id
    rx: jax.Array  # i32[W] host_rx link id
    fab: jax.Array  # i32[W, N, Hf] fabric link ids (schemes with pinned paths)
    sleaf: jax.Array  # i32[W]
    dleaf: jax.Array  # i32[W]
    salt: jax.Array  # u32[W, N] DCQCN mark-draw salt
    fid: jax.Array  # u32[W] flow id (flowlet reroute rng)
    src: jax.Array  # i32[W] source host (DRILL spray)
    dst: jax.Array  # i32[W]
    spray: jax.Array  # i32[W] straddled-path count (flowcell reorder cost)


class CompactState(NamedTuple):
    slot_fid: jax.Array  # i32[W] sorted-flow index; F_pad = empty sentinel
    remaining: jax.Array  # f32[W, N]
    path: jax.Array  # i32[W, N]
    sub_done: jax.Array  # bool[W, N]
    cc: dcqcn_mod.DCQCNState  # [W, N]
    cqe_bitmap: jax.Array  # u32[W]
    admitted: jax.Array  # i32 — flows admitted so far (prefix of sorted order)
    finish: jax.Array  # f32[F_pad] global (+inf until CQE)
    table: ctab.CongestionTable  # [n_leaf, n_paths]
    queue: jax.Array  # f32[n_links + 1]
    cnp_pkts: jax.Array  # f32 scalar
    spill_steps: jax.Array  # i32 — steps where an arrived flow found no slot
    step: jax.Array  # i32
    ff_steps: jax.Array  # i32 — steps advanced by quiescence fast-forward
    cache: SlotCache


class CompactResult(NamedTuple):
    """Duck-types the SimState fields the metrics layer reads."""

    finish: np.ndarray  # f32[F] original trace order
    cnp_pkts: np.ndarray  # f32 scalar
    spill_steps: int
    window_slots: int = 0  # W the (final) run used
    ff_steps: int = 0  # dt steps covered by closed-form fast-forward
    ring: object = None  # obs.recorder.RingState when recording was on


def max_concurrency_bound(
    sizes: np.ndarray,
    arrivals: np.ndarray,
    valid: np.ndarray,
    line_rate: float,
    *,
    slack_slowdown: float = 12.0,
    slack_s: float = 150e-6,
    safety: float = 1.2,
) -> int:
    """Estimated bound on concurrently-active flows: assume every flow lives
    ``slack_slowdown`` x its line-rate serialization plus ``slack_s`` of
    fixed queueing/RTT headroom, then take the max interval overlap.

    This is a heuristic, not a guarantee — the engine reports
    ``spill_steps > 0`` when it was exceeded, and netsim/sweep.py reruns
    with a doubled window in that case (the spilled run stays physically
    sensible — admission is just delayed — but only a spill-free run matches
    the dense oracle exactly)."""
    a = np.asarray(arrivals, np.float64)[np.asarray(valid, bool)]
    s = np.asarray(sizes, np.float64)[np.asarray(valid, bool)]
    if a.size == 0:
        return 64
    order = np.argsort(a, kind="stable")
    a = a[order]
    end = np.sort(a + s[order] * 8.0 / line_rate * slack_slowdown + slack_s)
    # flows started minus flows (optimistically) ended at each arrival
    started = np.arange(1, a.size + 1)
    ended = np.searchsorted(end, a, side="left")
    conc = int((started - ended).max())
    return int(conc * safety) + 64


def max_admits_per_step(arrivals: np.ndarray, valid: np.ndarray, dt: float) -> int:
    """Exact peak number of arrivals in any one ``dt`` step (the admission
    lane width A: per-step path selection runs on [A], not [W])."""
    a = np.asarray(arrivals, np.float64)[np.asarray(valid, bool)]
    if a.size == 0:
        return 1
    steps = np.ceil(a / dt).astype(np.int64)
    return int(np.bincount(steps - steps.min()).max())


def plan_single_window(topo: Topology, cfg: SimConfig, arrays: tuple,
                       F_pad: int) -> tuple[int, int]:
    """(W, A) for a single sorted trace: the concurrency-bound window
    (128-bucketed, floored at min(128, F_pad)) and the exact-peak admission
    lane (32-bucketed).  Shared by ``simulate_compact`` and
    ``profile.quiescence_profile``, so the replay runs the production
    shapes."""
    line_rate = float(np.asarray(line_rate_of(topo)))
    bound = max_concurrency_bound(arrays[0], arrays[1], arrays[5], line_rate)
    W = int(min(((bound + 127) // 128) * 128, F_pad))
    W = max(W, min(128, F_pad))
    A = min(((max_admits_per_step(arrays[1], arrays[5], cfg.dt) + 31) // 32) * 32,
            F_pad)
    return W, A


def init_compact_state(
    topo: Topology, cfg: SimConfig, W: int, F_pad: int,
    finish0: jax.Array | None = None, capacity: jax.Array | None = None,
) -> CompactState:
    """Fresh all-slots-empty state.  ``finish0`` (f32[F_pad] of +inf) may be
    built OUTSIDE the jitted run and donated — it is the one state buffer
    large enough to matter, and it aliases the finish output exactly.
    ``capacity`` optionally overrides ``topo.capacity`` as a TRACED operand
    (co-sim fault schedules; see ``run_core``) — either f32[n_links + 1] or
    a wall-clock schedule f32[K, n_links + 1] (row 0 seeds the DCQCN line
    rate)."""
    N = cfg.n_sub
    if capacity is None:
        line_rate = line_rate_of(topo)
    else:
        cap0 = capacity[0] if capacity.ndim == 2 else capacity
        line_rate = cap0[topo.n_links - 2 * topo.n_hosts]
    if finish0 is None:
        finish0 = jnp.full((F_pad,), jnp.inf, jnp.float32)
    hf = topo.n_fabric_hops
    cache = SlotCache(
        tx=jnp.zeros((W,), jnp.int32),
        rx=jnp.zeros((W,), jnp.int32),
        fab=jnp.zeros((W, N, hf), jnp.int32),
        sleaf=jnp.zeros((W,), jnp.int32),
        dleaf=jnp.zeros((W,), jnp.int32),
        salt=jnp.zeros((W, N), jnp.uint32),
        fid=jnp.zeros((W,), jnp.uint32),
        src=jnp.zeros((W,), jnp.int32),
        dst=jnp.zeros((W,), jnp.int32),
        spray=jnp.ones((W,), jnp.int32),
    )
    return CompactState(
        slot_fid=jnp.full((W,), F_pad, jnp.int32),
        remaining=jnp.zeros((W, N), jnp.float32),
        path=jnp.full((W, N), -1, jnp.int32),
        sub_done=jnp.zeros((W, N), bool),
        cc=dcqcn_mod.init_state((W, N), line_rate),
        cqe_bitmap=jnp.zeros((W,), jnp.uint32),
        admitted=jnp.zeros((), jnp.int32),
        finish=finish0,
        table=ctab.CongestionTable.create(topo.n_leaf, topo.n_paths),
        queue=jnp.zeros((topo.n_links + 1,), jnp.float32),
        cnp_pkts=jnp.zeros((), jnp.float32),
        spill_steps=jnp.zeros((), jnp.int32),
        step=jnp.zeros((), jnp.int32),
        ff_steps=jnp.zeros((), jnp.int32),
        cache=cache,
    )


def build_compact_sim(topo: Topology, cfg: SimConfig, trace_arrays, W: int, F_pad: int,
                      A: int = 256, gate_admission: bool = False,
                      capacity: jax.Array | None = None,
                      loss: jax.Array | None = None,
                      cap_seg_steps: int = 0,
                      reorder: jax.Array | None = None):
    """trace_arrays = (sizes, arrivals, src, dst, fid, valid[, spray]),
    SORTED by arrival (invalid flows last, arrival=+inf), padded to F_pad;
    the optional 7th ``spray`` column (i32, defaulted to ones) is the
    straddled-path count flowcell splitting stamps on each flow.
    ``A`` is the admission lane width: at most A flows admit per step, and
    admission-time work (path selection, route-cache fills, slot resets)
    runs on [A]-shaped rank arrays rather than the full [W] window.
    ``gate_admission`` wraps the admission block in a ``lax.cond`` on
    "every flow already admitted" — paper traces stop arriving at 1/4 of
    the horizon, so un-vmapped runs then skip the whole O(W) block.  Only
    set it for programs that will NOT be vmapped: vmap lowers cond to
    both-branches-plus-select, which pays instead of saves.
    ``capacity`` (f32[n_links + 1], sentinel slot included) overrides
    ``topo.capacity`` as a TRACED operand: co-sim fault schedules mutate
    link capacities every planning epoch, and a traced capacity lets all
    epochs share ONE compiled program instead of recompiling per fault
    state.  A 2-D schedule f32[K, n_links + 1] extends that to WALL-CLOCK
    granularity (faults.FaultCampaign): the step loop reads row
    ``min(step // cap_seg_steps, K - 1)``, so link flaps / PFC pauses land
    mid-horizon while K and ``cap_seg_steps`` stay static — shapes fixed,
    still one compiled program for the whole campaign.  ``None`` keeps the
    topology's capacity baked in as a constant (bit-identical to the
    pre-traced-capacity programs).
    ``loss`` (f32[n_links + 1], traced) is the per-link packet-loss vector
    (faults.LossyLink): delivered throughput deflates by the go-back-N
    goodput factor along each sub-flow's hops while offered load stays at
    the DCQCN rate — retransmissions ride the wire (paper Table 1).
    ``reorder`` (f32 scalar, traced) is the flowcell reordering budget in
    packets: delivered throughput divides by
    ``dataplane.reorder_gbn_factor`` wherever the spray column says a
    flow's parent chunk straddles more than one path.  ``None`` (Python
    gate, same convention as ``loss``) traces the exact pre-flowcell
    program — the degenerate pin AND the "cost-free reordering" bench arm.
    Returns (init_state, step_fn, phases) — ``phases`` maps each phase's
    name (admit / cascade / dcqcn / finish, and the adaptive-dt quiesce /
    fast_forward) to its closure.  Each closure traces under the
    ``jax.named_scope`` of its name (``obs.scopes``), so the compiled
    program's instructions carry the phase they came from."""
    arrs = tuple(jnp.asarray(a) for a in trace_arrays)
    if len(arrs) == 6:  # legacy 6-tuple: no flowcell splitting anywhere
        arrs = arrs + (jnp.ones_like(arrs[2]),)
    sizes, arrivals, src, dst, fid, valid, spray_f = arrs
    N = cfg.n_sub
    P = topo.n_paths
    nl = topo.n_links

    fc = flow_constants(topo, cfg, sizes, src, dst, fid)
    if capacity is None:
        cap0 = topo.capacity

        def cap_of(step):
            return topo.capacity
    else:
        cap_arr = jnp.asarray(capacity)
        if cap_arr.ndim == 2:
            cap0 = cap_arr[0]
            seg = max(int(cap_seg_steps), 1)
            Kseg = cap_arr.shape[0]

            def cap_of(step):
                return cap_arr[jnp.minimum(step // seg, Kseg - 1)]
        else:
            cap0 = cap_arr

            def cap_of(step):
                return cap_arr
    loss_vec = None if loss is None else jnp.asarray(loss)
    line_rate = cap0[nl - 2 * topo.n_hosts]  # host_tx[0] bw
    qmask = dataplane.queue_mask_for(topo)
    dparams = cfg.dcqcn

    if cfg.scheme in ("conga", "drill", "flowlet_timeout"):
        assert topo.kind == "leaf_spine", f"{cfg.scheme} is 2-tier only (paper §IV.B)"
    if loss_vec is not None:
        assert cfg.scheme != "drill", \
            "lossy links + DRILL spray unsupported (spray has no pinned hops)"
    if reorder is not None:
        assert topo.kind == "leaf_spine", "reorder cost model is 2-tier only"
        assert cfg.scheme != "drill", \
            "DRILL carries its own gbn factor (drill_gbn_factor)"

    def init_state() -> CompactState:
        return init_compact_state(topo, cfg, W, F_pad, capacity=capacity)

    full_cqe = (jnp.uint32(1) << jnp.uint32(N)) - jnp.uint32(1)
    # schemes whose sub-flow paths are pinned at admission carry their
    # fabric link ids in the SlotCache; flowlet schemes may reroute any
    # slot any step, so their (N=1) fabric row is rebuilt from the cached
    # leaf ids — pure arithmetic, no [F]-sized gathers
    cached_fab = cfg.scheme in ("seqbalance", "ecmp")

    n_valid_total = jnp.sum(valid.astype(jnp.int32))

    def _admission(state: CompactState):
        """The gated part of admit_phase: gather-on-admit, slot resets,
        route-cache fill, and NEW-flow path placement.  Runs under a
        ``lax.cond`` — once every flow has admitted (arrivals stop early in
        paper traces) this whole O(W) block is skipped for the rest of the
        run (a real branch in un-vmapped runs; both-branches-plus-select
        under vmap, which costs one cheap select per state leaf)."""
        t = state.step.astype(jnp.float32) * cfg.dt
        occ_prev = state.slot_fid < F_pad
        n_arr = jnp.searchsorted(arrivals, t, side="right").astype(jnp.int32)
        backlog = n_arr - state.admitted
        free = ~occ_prev
        free_rank = jnp.cumsum(free) - 1  # i32[W]
        m = jnp.minimum(jnp.minimum(backlog, free.sum()), A)
        newly = free & (free_rank < m)
        slot_fid = jnp.where(newly, state.admitted + free_rank, state.slot_fid)

        # admission lane: rank k in [0, A) takes flow admitted+k and lands
        # in the k-th free slot.  All admission-time work happens on these
        # [A]-shaped arrays and scatters into the [W] window (mode="drop"
        # discards ranks beyond m via the W sentinel).
        ranks = jnp.arange(A, dtype=jnp.int32)
        rank_fid = jnp.minimum(state.admitted + ranks, F_pad - 1)  # [A]
        slot_of_rank = jnp.full((A,), W, jnp.int32).at[
            jnp.where(newly, free_rank, A)
        ].set(jnp.arange(W, dtype=jnp.int32), mode="drop")

        # route cache: one [F]-gather per constant at admission, never again
        src_a, dst_a = src[rank_fid], dst[rank_fid]
        sleaf_a, dleaf_a = fc.src_leaf[rank_fid], fc.dst_leaf[rank_fid]
        tx_a, rx_a = topo.nic_links(src_a, dst_a)
        ca = state.cache
        cache = ca._replace(
            tx=ca.tx.at[slot_of_rank].set(tx_a, mode="drop"),
            rx=ca.rx.at[slot_of_rank].set(rx_a, mode="drop"),
            sleaf=ca.sleaf.at[slot_of_rank].set(sleaf_a, mode="drop"),
            dleaf=ca.dleaf.at[slot_of_rank].set(dleaf_a, mode="drop"),
            salt=ca.salt.at[slot_of_rank].set(fc.sub_salt[rank_fid], mode="drop"),
            fid=ca.fid.at[slot_of_rank].set(fid[rank_fid], mode="drop"),
            src=ca.src.at[slot_of_rank].set(src_a, mode="drop"),
            dst=ca.dst.at[slot_of_rank].set(dst_a, mode="drop"),
            spray=ca.spray.at[slot_of_rank].set(spray_f[rank_fid], mode="drop"),
        )

        # reset admitted slots (rank -> slot scatters)
        remaining = state.remaining.at[slot_of_rank].set(
            fc.sub_sizes[rank_fid], mode="drop")
        sub_done = state.sub_done.at[slot_of_rank].set(False, mode="drop")
        cqe_bitmap = state.cqe_bitmap.at[slot_of_rank].set(
            jnp.uint32(0), mode="drop")
        cc = jax.tree.map(
            lambda old, init: old.at[slot_of_rank].set(init, mode="drop"),
            state.cc, dcqcn_mod.init_state((A, N), line_rate),
        )

        # ---------------- NEW-flow path placement (dense-engine logic) --
        # new flows route on the [A] admission lane; the flowlet schemes'
        # per-step reroute of EXISTING slots lives in admit_phase below
        # (it must run even when this block is skipped)
        path = state.path
        if cfg.scheme == "seqbalance":
            inact = ctab.inactive_matrix(state.table, t)  # [L, P]
            stale = inact.sum(-1, keepdims=True) > (P // 2)
            inact = jnp.where(stale, False, inact)
            rows = inact[sleaf_a][:, None, :]  # [A, 1, P]
            rows = jnp.broadcast_to(rows, (A, N, P))
            s5_a = tuple(a[rank_fid] for a in fc.s5)  # each [A, N]
            p_new = routing.select_paths(*s5_a, rows, P)  # [A, N]
            path = path.at[slot_of_rank].set(p_new, mode="drop")
        elif cfg.scheme in ("ecmp", "letflow", "conga", "flowlet_timeout"):
            f5_a = tuple(a[rank_fid] for a in fc.f5)  # each [A]
            p_new = routing.ecmp_paths(*f5_a, P)[:, None]  # [A, 1]
            path = path.at[slot_of_rank].set(p_new, mode="drop")
        else:  # drill: nominal path 0; real split via weights below
            path = path.at[slot_of_rank].set(0, mode="drop")

        if cached_fab:
            fab_a = topo.fabric_links(
                sleaf_a[:, None], dleaf_a[:, None], p_new)  # [A, N, Hf]
            cache = cache._replace(
                fab=cache.fab.at[slot_of_rank].set(fab_a, mode="drop"))

        return state._replace(
            slot_fid=slot_fid, remaining=remaining, path=path,
            sub_done=sub_done, cc=cc, cqe_bitmap=cqe_bitmap,
            admitted=state.admitted + m,
            spill_steps=state.spill_steps + (backlog > m).astype(jnp.int32),
            cache=cache,
        )

    @scope("admit")
    def admit_phase(state: CompactState):
        """Admission (optionally gated: skipped once every flow has
        admitted) plus the flowlet schemes' per-step reroute.  Step time
        is derived from ``state.step`` inside ``_admission`` (the lax.cond
        branch takes the state as its only operand)."""
        occ_prev = state.slot_fid < F_pad
        if gate_admission:
            st = jax.lax.cond(
                state.admitted < n_valid_total, _admission, lambda s: s, state)
        else:
            st = _admission(state)
        if cfg.scheme in ("letflow", "conga", "flowlet_timeout"):
            # reroute EXISTING slots at flowlet gaps; newly admitted slots
            # keep their ECMP placement (occ_prev is pre-admission)
            rng = hashing.fmix32(
                st.cache.fid ^ st.step.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
            )
            gap = baselines.flowlet_gap_occurs(
                st.cc.rc[:, 0], dparams.mtu_bytes, cfg.flowlet_timeout
            )
            if cfg.scheme == "letflow":
                p_re = baselines.letflow_paths(st.path[:, 0], gap, rng, P)
            elif cfg.scheme == "flowlet_timeout":
                # WCMP flowlet re-draw weighted by the CURRENT per-leaf
                # uplink capacities (traced schedules included) — the
                # asymmetric-topology flowlet controller: fat uplinks
                # absorb proportionally more flowlets.
                capv_a = cap_of(st.step)
                cap_up = capv_a[: topo.n_leaf * P].reshape(topo.n_leaf, P)
                w_leaf = baselines.wcmp_weights(cap_up)  # [L, P]
                p_re = baselines.flowlet_wcmp_paths(
                    st.path[:, 0], gap, rng, w_leaf[st.cache.sleaf])
            else:
                pq = dataplane.path_queue_2tier(
                    topo, st.queue, st.cache.sleaf, st.cache.dleaf)
                p_re = baselines.conga_paths(st.path[:, 0], gap, pq)
            path = jnp.where(occ_prev, p_re, st.path[:, 0])[:, None]  # [W, 1]
            st = st._replace(path=path)
        return st

    @scope("cascade")
    def cascade_phase(state: CompactState):
        """Offered rates -> NIC-tiered hop cascade -> queue/ECN marks.
        Returns (arrival, new_queue, thr, p_sub, p_sub_fabric, rc, active)."""
        occupied = state.slot_fid < F_pad
        active = occupied[:, None] & ~state.sub_done
        rc = jnp.where(
            active, jnp.minimum(state.cc.rc, state.remaining * 8.0 / cfg.dt), 0.0
        )  # [W, N]
        ca = state.cache
        capv = cap_of(state.step)  # wall-clock schedule row (or the vector)
        if cfg.scheme == "drill":
            arrival, thr, w_spray, pq = dataplane.drill_spray(
                topo, state.queue, rc[:, 0], ca.src, ca.dst, ca.sleaf, ca.dleaf,
                active[:, 0:1], cfg.drill_q0, capacity=capv,
            )
            new_queue, p_mark = dataplane.integrate_queue(
                state.queue, arrival, capv, qmask, dparams,
                dt=cfg.dt, qmax_bytes=cfg.qmax_bytes, n_links=nl,
            )
            p_sub, p_sub_fabric = dataplane.drill_mark_probs(
                topo, p_mark, w_spray, ca.sleaf, ca.dleaf, ca.dst
            )
            thr = thr * dataplane.drill_gbn_factor(
                topo, pq, w_spray, rc[:, 0], mtu_bytes=dparams.mtu_bytes,
                jitter_mtus=cfg.drill_jitter_mtus, window_pkts=cfg.gbn_window_pkts,
                capacity=capv,
            )
            thr = thr[:, None]  # [W, 1]
        else:
            if cached_fab:
                fab = ca.fab  # admit-time snapshot: paths never move
            else:  # flowlet reroute: rebuild from cached leaf ids (no gathers)
                fab = topo.fabric_links(
                    ca.sleaf, ca.dleaf, state.path[:, 0])[:, None, :]
            arrival, new_queue, p_mark, thr = dataplane.cascade_nic(
                fab, ca.tx, ca.rx, rc, state.queue, capv, qmask,
                n_links=nl, kmin=dparams.kmin_bytes, kmax=dparams.kmax_bytes,
                pmax=dparams.pmax, dt=cfg.dt, qmax_bytes=cfg.qmax_bytes,
                bands=topo.bands, backend=cfg.dataplane,
            )
            p_sub, p_sub_fabric = dataplane.subflow_mark_probs_nic(
                fab, ca.tx, ca.rx, p_mark, nl)
            if loss_vec is not None:
                # GBN amplification on lossy links: goodput deflates, the
                # offered rate (already in the cascade above) does not
                thr = thr * dataplane.lossy_gbn_factor(
                    fab, ca.tx, ca.rx, loss_vec, n_links=nl,
                    window_pkts=cfg.gbn_window_pkts,
                )
            if reorder is not None:
                # flowcell reordering cost: every delivered byte of a
                # path-straddling chunk costs 1 + p_ooo*W/2 wire bytes
                # (go-back-N rewinds); offered load stays at the DCQCN
                # rate — the retransmitted bytes ride the wire, exactly
                # the lossy_gbn_factor convention
                pq = dataplane.path_queue_2tier(
                    topo, state.queue, ca.sleaf, ca.dleaf)
                thr = thr / dataplane.reorder_gbn_factor(
                    topo, pq, ca.spray, rc[:, 0], reorder,
                    mtu_bytes=dparams.mtu_bytes,
                    jitter_mtus=cfg.drill_jitter_mtus,
                    window_pkts=cfg.gbn_window_pkts, capacity=capv,
                )[:, None]
        return arrival, new_queue, thr, p_sub, p_sub_fabric, rc, active

    @scope("dcqcn")
    def dcqcn_phase(state: CompactState, p_sub, active):
        flow_salt = state.cache.salt if cfg.scheme == "seqbalance" \
            else state.cache.salt[:, :1]
        flow_salt = jnp.broadcast_to(flow_salt, (W, N))
        cc, _ = dcqcn_mod.step(
            state.cc, p_sub, active, cfg.dt, line_rate, dparams, state.step,
            flow_salt,
        )
        return cc

    @scope("finish")
    def finish_phase(state: CompactState, t, thr, active, rc, p_sub_fabric):
        """Transfer progress, bitmap CQE, scatter-on-finish, Congestion
        Packet bookkeeping.  Returns (remaining, sub_done, cqe_bitmap,
        slot_fid, finish, table, exp_cong_pkts)."""
        occupied = state.slot_fid < F_pad
        delivered = thr * cfg.dt / 8.0  # bytes
        new_remaining = jnp.maximum(
            state.remaining - jnp.where(active, delivered, 0.0), 0.0)
        sub_done = occupied[:, None] & (new_remaining <= DONE_EPS_BYTES)
        bits = (sub_done.astype(jnp.uint32) << jnp.arange(N, dtype=jnp.uint32)).sum(
            axis=-1, dtype=jnp.uint32
        )
        cqe_bitmap = state.cqe_bitmap | bits
        all_done = ((cqe_bitmap & full_cqe) == full_cqe) & occupied
        # scatter-on-finish: empty slots carry the F_pad sentinel -> dropped
        finish = state.finish.at[state.slot_fid].min(
            jnp.where(all_done, t + cfg.dt, jnp.inf), mode="drop"
        )

        table = state.table
        pkts = jnp.where(active, rc * cfg.dt / (8.0 * dparams.mtu_bytes), 0.0)
        exp_cong_pkts = jnp.sum(pkts * p_sub_fabric)
        if cfg.scheme == "seqbalance":
            intensity = jnp.zeros((topo.n_leaf, P), jnp.float32)
            idx_leaf = jnp.broadcast_to(
                state.cache.sleaf[:, None], (W, N)).reshape(-1)
            idx_path = jnp.clip(state.path, 0, P - 1).reshape(-1)
            intensity = intensity.at[idx_leaf, idx_path].add(
                (pkts * p_sub_fabric).reshape(-1)
            )
            dense_mask = intensity >= cfg.cong_threshold_pkts
            table = ctab.mark_congested_dense(table, dense_mask, t, cfg.phi)
        slot_fid = jnp.where(all_done, F_pad, state.slot_fid)  # free slots
        return (new_remaining, sub_done, cqe_bitmap, slot_fid, finish, table,
                exp_cong_pkts)

    def step_fn(state: CompactState, _=None):
        t = state.step.astype(jnp.float32) * cfg.dt
        st = admit_phase(state)
        arrival, new_queue, thr, p_sub, p_sub_fabric, rc, active = \
            cascade_phase(st)
        cc = dcqcn_phase(st, p_sub, active)
        (remaining, sub_done, cqe_bitmap, slot_fid, finish, table,
         exp_cong_pkts) = finish_phase(st, t, thr, active, rc, p_sub_fabric)

        new_state = st._replace(
            slot_fid=slot_fid,
            remaining=remaining,
            sub_done=sub_done,
            cc=cc,
            cqe_bitmap=cqe_bitmap,
            finish=finish,
            table=table,
            queue=new_queue,
            cnp_pkts=state.cnp_pkts + exp_cong_pkts,
            step=state.step + 1,
        )
        return new_state, step_outputs(arrival, active, thr, exp_cong_pkts,
                                       new_queue)

    @scope("outputs")
    def step_outputs(arrival, active, thr, exp_cong_pkts, new_queue):
        return StepOutputs(
            uplink_load=arrival[jnp.asarray(topo.uplink_ids)],
            goodput_total=jnp.sum(jnp.where(active, thr, 0.0)),
            cnp_rate=exp_cong_pkts,
            max_queue=jnp.max(new_queue[:nl]),
        )

    # ---------------- event-driven adaptive dt (DESIGN.md §15) ----------
    uplink_ids = jnp.asarray(topo.uplink_ids)
    s_win = cfg.uplink_sample_every

    @scope("quiesce")
    def quiesce_phase(state: CompactState, span: int):
        """Quiescence predicate for a ``span``-step macro-step starting at
        ``state.step``: True iff every one of those steps is provably
        reproducible in closed form, i.e. (a) no flow arrives inside the
        span (so admission is an exact no-op, spill counter included — a
        spill backlog implies the next unadmitted arrival is already in
        the past, which fails this check), (b) the capacity-schedule row is
        constant across the span, and (c) the fabric is either fully idle
        (stale slots offer exact +0.0; marks may exist but nothing consumes
        them) or in steady state: every active sub-flow pinned at
        ``rc == rt == line rate`` (an exact fixed point of the DCQCN
        recovery branch), no masked queue able to reach the
        ``ff_kmin_frac * kmin`` ECN margin under the constant offered
        load, no sub-flow able to finish within ``span + ff_margin_steps``
        steps (which also keeps the remaining-bytes rc cap non-binding),
        and — for the flowlet schemes — no occupied slot at a flowlet gap
        (so the per-step reroute keeps every path fixed).  DRILL's spray
        weights depend on instantaneous queues, so it only fast-forwards
        idle spans.

        Returns the boolean alone.  The steady-state checks cost one hop
        cascade, so they hide behind a ``lax.cond`` on the O(1) arrival and
        capacity-edge checks: event-dense chunks (every chunk of a loaded
        Poisson trace) pay two scalar compares and nothing else, and only
        plausibly quiescent boundaries pay the ~1/span cascade."""
        t_end = (state.step + span).astype(jnp.float32) * cfg.dt
        nxt = arrivals[jnp.clip(state.admitted, 0, F_pad - 1)]
        p_arr = (state.admitted >= n_valid_total) | (nxt >= t_end)
        if capacity is not None and jnp.asarray(capacity).ndim == 2:
            r0 = jnp.minimum(state.step // seg, Kseg - 1)
            r1 = jnp.minimum((state.step + span - 1) // seg, Kseg - 1)
            p_cap = r0 == r1
        else:
            p_cap = jnp.bool_(True)

        def steady_or_idle(st: CompactState):
            occupied = st.slot_fid < F_pad
            idle = ~jnp.any(occupied)
            if cfg.scheme == "drill" or reorder is not None:
                # spray/reorder throughput depends on instantaneous queues,
                # which drift inside a span — only idle spans fast-forward
                return idle
            arrival, _, _, _, _, rc, active = cascade_phase(st)
            capv = cap_of(st.step)
            delta = (arrival - capv) * (cfg.dt / 8.0)
            q_hi = jnp.maximum(st.queue, st.queue + delta * span) * qmask
            p_q = jnp.all(q_hi[:nl] < cfg.ff_kmin_frac * dparams.kmin_bytes)
            margin = span + max(cfg.ff_margin_steps, 1)
            need = rc * (margin * cfg.dt / 8.0) + DONE_EPS_BYTES
            p_fin = jnp.all(jnp.where(active, st.remaining > need, True))
            p_cc = jnp.all(jnp.where(
                active,
                (st.cc.rc == line_rate) & (st.cc.rt == line_rate),
                True,
            ))
            steady = p_q & p_fin & p_cc
            if cfg.scheme in ("letflow", "conga", "flowlet_timeout"):
                gap = baselines.flowlet_gap_occurs(
                    st.cc.rc[:, 0], dparams.mtu_bytes, cfg.flowlet_timeout)
                steady &= ~jnp.any(gap & occupied)
            return idle | steady

        return jax.lax.cond(
            p_arr & p_cap, steady_or_idle, lambda st: jnp.bool_(False), state)

    @scope("fast_forward")
    def fast_forward_phase(state: CompactState, span: int):
        """Advance ``span`` steps in closed form — valid exactly when
        ``quiesce_phase(state, span)`` holds.  Queues follow the analytic
        clip trajectory, remaining bytes decrement linearly at the frozen
        delivered rate, DCQCN reduces to timer bookkeeping
        (dcqcn.fast_forward), and every discrete structure (slots, CQE
        bitmaps, finish times, congestion table, CNP counter, spill) is
        untouched.  Step outputs are the frozen per-step values broadcast
        over the span; the uplink slab is emitted at sample-window
        granularity directly (a window average of a constant).  Runs its
        own cascade — one extra hop cascade per fast-forwarded macro-step,
        amortised over the ``span`` scanned steps it replaces."""
        arrival, _, thr, _, _, _, active = cascade_phase(state)
        capv = cap_of(state.step)
        q_final, mq_traj = dataplane.queue_fast_forward(
            state.queue, arrival, capv, qmask,
            dt=cfg.dt, n_steps=span, qmax_bytes=cfg.qmax_bytes, n_links=nl,
        )
        delivered = thr * (span * cfg.dt / 8.0)
        remaining = jnp.maximum(
            state.remaining - jnp.where(active, delivered, 0.0), 0.0)
        cc = dcqcn_mod.fast_forward(state.cc, active, span, cfg.dt, dparams)
        new_state = state._replace(
            remaining=remaining, cc=cc, queue=q_final,
            step=state.step + span, ff_steps=state.ff_steps + span,
        )
        up = jnp.broadcast_to(
            arrival[uplink_ids][None],
            (span // s_win,) + np.asarray(topo.uplink_ids).shape)
        outs = StepOutputs(
            uplink_load=up,
            goodput_total=jnp.broadcast_to(
                jnp.sum(jnp.where(active, thr, 0.0)), (span,)),
            cnp_rate=jnp.zeros((span,), jnp.float32),
            max_queue=mq_traj,
        )
        return new_state, outs

    phases = dict(admit=admit_phase, cascade=cascade_phase,
                  dcqcn=dcqcn_phase, finish=finish_phase,
                  quiesce=quiesce_phase, fast_forward=fast_forward_phase)
    return init_state, step_fn, phases


def plan_chunks(cfg: SimConfig, n_steps: int) -> tuple[int, int, int]:
    """(K, n_chunks, tail): scan-chunk length (a multiple of the uplink
    sample window, capped at the horizon), full chunks, and leftover steps.

    Prefers a K that divides the horizon: a nonzero tail needs its own
    lax.cond'd scan, which compiles the step body a SECOND time — a pure
    compile-latency tax that a slightly shorter chunk avoids entirely.
    The search runs from the requested chunk size all the way down to one
    sample window, so the tail only survives when the sample window itself
    does not divide the horizon (then no valid K can)."""
    s = cfg.uplink_sample_every
    K0 = max(1, cfg.chunk_steps // s) * s
    K0 = min(K0, max(n_steps, 1))
    for k in range(K0, 0, -1):
        if k % s == 0 and n_steps % k == 0:
            return k, n_steps // k, 0
    return K0, n_steps // K0, n_steps % K0


def event_grid(cfg: SimConfig, n_steps: int, arrivals=None, valid=None,
               cap_seg_steps: int = 0) -> np.ndarray:
    """Mandatory step boundaries for one sim, host-side: flow-arrival
    steps, fault/capacity segment edges, and uplink sample-window
    boundaries.  The adaptive engine honors this grid by construction —
    macro-steps are whole scan chunks (K a multiple of the sample window,
    via ``plan_chunks``), the quiescence predicate refuses any span
    containing an arrival or a capacity edge, and finishes/ECN crossings
    are excluded dynamically.  Exposed for planning and for the
    ``--profile`` quiescence-occupancy report."""
    edges = [np.array([0, n_steps], np.int64)]
    if arrivals is not None:
        a = np.asarray(arrivals, np.float64)
        if valid is not None:
            a = a[np.asarray(valid, bool)]
        a = a[np.isfinite(a)]
        steps = np.ceil(a / cfg.dt).astype(np.int64)
        edges.append(steps[(steps >= 0) & (steps <= n_steps)])
    if cap_seg_steps and cap_seg_steps > 0:
        edges.append(np.arange(0, n_steps + 1, cap_seg_steps, dtype=np.int64))
    if cfg.uplink_sample_every > 1:
        edges.append(np.arange(0, n_steps + 1, cfg.uplink_sample_every,
                               dtype=np.int64))
    return np.unique(np.concatenate(edges))


def run_core(topo: Topology, cfg: SimConfig, W: int, F_pad: int, A: int,
             n_steps: int, trace_arrays, finish0: jax.Array,
             capacity: jax.Array | None = None,
             loss: jax.Array | None = None,
             cap_seg_steps: int = 0,
             gate_admission: bool = False,
             record=None,
             reorder: jax.Array | None = None):
    """Jit-friendly core: sorted/padded trace arrays + a donatable +inf
    finish buffer in, (finish[F_pad] in sorted order, cnp_pkts, spill_steps,
    ff_steps, per-step outputs) out.  Wrapped and cached by netsim/sweep.py;
    vmap-able over a leading batch axis of (trace_arrays, finish0).
    ``capacity`` (f32[n_links + 1], or a wall-clock schedule
    f32[K, n_links + 1] stepped every ``cap_seg_steps`` — static — steps)
    is the TRACED link-capacity operand for co-sim fault schedules, and
    ``loss`` (f32[n_links + 1], traced) the per-link loss rates driving
    go-back-N goodput amplification — see ``build_compact_sim``; None
    keeps ``topo.capacity`` baked in as a compile-time constant.

    The horizon runs as K-step ``lax.scan`` chunks inside a ``while_loop``
    with EARLY EXIT: once every flow has been admitted and finished and the
    queues have fully drained, the remaining steps of the horizon are exact
    no-ops (zero offered load, zero queues — also in the dense engine), so
    whole chunks are skipped and the preallocated per-step outputs keep
    their zeros.  Typical paper sweeps (arrivals stop at 1/4 of the
    horizon) skip 30-50 % of steps this way.  With
    ``cfg.uplink_sample_every > 1`` the uplink trace is window-averaged
    inside the chunk before it is written out, so only ``[T/s, L, S]`` is
    ever materialized.

    With ``cfg.adaptive`` every chunk boundary additionally evaluates the
    quiescence predicate and a ``lax.cond`` fast-forwards the whole
    macro-step (``cfg.ff_macro_chunks`` chunks) in closed form when it
    holds — the event grid (arrivals, capacity segment edges, sample
    windows; see ``event_grid``) is respected by construction because
    macro-steps are chunk-aligned and the predicate refuses spans
    containing an event.  The cond is a REAL branch exactly on the
    un-vmapped dispatch paths (B=1 / one-sim-per-device), which is where
    the sweep runner lands on CPU; under vmap it lowers to
    both-branches-plus-select and saves nothing.  ``adaptive=False``
    traces the identical step loop as before (bit-identical results).

    ``record`` (an ``obs.recorder.RecordSpec``, static/hashable) appends a
    per-chunk summary row to a fixed-shape ring buffer carried alongside
    the loop state and returns it as a sixth output.  All gating is at
    Python trace time: ``record=None`` traces the identical program as
    before the recorder existed (bit-identical, sha-pinned), and because
    the ring's shapes depend only on the spec, recording costs exactly one
    extra executable per (shape bucket, spec) — never a rebuild across
    epochs (DESIGN.md §16).

    ``reorder`` (f32 scalar, traced) switches on the flowcell
    reordering-cost model — see ``build_compact_sim``; ``None`` traces the
    identical pre-flowcell program (sha-pinned)."""
    _, step_fn, phases = build_compact_sim(topo, cfg, trace_arrays, W, F_pad,
                                           A, gate_admission=gate_admission,
                                           capacity=capacity, loss=loss,
                                           cap_seg_steps=cap_seg_steps,
                                           reorder=reorder)
    init = init_compact_state(topo, cfg, W, F_pad, finish0, capacity=capacity)
    n_valid = jnp.sum(jnp.asarray(trace_arrays[5]).astype(jnp.int32))
    nl = topo.n_links
    uplink_shape = np.asarray(topo.uplink_ids).shape
    s = cfg.uplink_sample_every
    K, n_chunks, tail = plan_chunks(cfg, n_steps)
    n_samples = n_steps // s
    outs0 = StepOutputs(
        uplink_load=jnp.zeros((n_samples,) + uplink_shape, jnp.float32),
        goodput_total=jnp.zeros((n_steps,), jnp.float32),
        cnp_rate=jnp.zeros((n_steps,), jnp.float32),
        max_queue=jnp.zeros((n_steps,), jnp.float32),
    )

    @scope("chunk")
    def alive(st):
        return (
            (st.admitted < n_valid)
            | jnp.any(st.slot_fid < F_pad)
            | (jnp.max(st.queue[:nl]) > 0.0)
        )

    @scope("chunk")
    def splice(outs, o, k0, length):
        """Write a block's per-step output slab into the preallocated
        horizon outputs at the (chunk-aligned, so sample-window-aligned)
        offset ``k0``."""
        gp = jax.lax.dynamic_update_slice(outs.goodput_total, o.goodput_total, (k0,))
        cn = jax.lax.dynamic_update_slice(outs.cnp_rate, o.cnp_rate, (k0,))
        mq = jax.lax.dynamic_update_slice(outs.max_queue, o.max_queue, (k0,))
        up = outs.uplink_load
        nw = length // s
        if nw:
            slab = o.uplink_load[: nw * s]
            if s > 1:
                slab = slab.reshape((nw, s) + slab.shape[1:]).mean(axis=1)
            up = jax.lax.dynamic_update_slice(
                up, slab, (k0 // s,) + (0,) * len(uplink_shape))
        return StepOutputs(up, gp, cn, mq)

    def run_block(st, outs, length):
        """Scan ``length`` (static) steps; returns the block's raw output
        slab too (only the recorder consumes it — discarded otherwise, at
        Python level, so the traced program is unchanged)."""
        k0 = st.step
        st2, o = jax.lax.scan(step_fn, st, None, length=length)
        return st2, splice(outs, o, k0, length), o

    if record is not None:
        from repro.obs import recorder

        uplink_flat = jnp.asarray(np.asarray(topo.uplink_ids).ravel())
        ring0 = recorder.ring_init(record, int(uplink_flat.size))
        if capacity is None:
            cap_row = jnp.asarray(topo.capacity)[uplink_flat]

            def cap_row_of(step):
                return cap_row
        else:
            cap_arr_r = jnp.asarray(capacity)
            if cap_arr_r.ndim == 2:
                seg_r = max(int(cap_seg_steps), 1)
                kseg_r = cap_arr_r.shape[0]

                def cap_row_of(step):
                    row = cap_arr_r[jnp.minimum(step // seg_r, kseg_r - 1)]
                    return row[uplink_flat]
            else:
                cap_row_r = cap_arr_r[uplink_flat]

                def cap_row_of(step):
                    return cap_row_r

        @scope("chunk")
        def rec_chunk(ring, st0, st2, o, length, ff):
            """One ring row from a block's raw slab + boundary state.
            ``o.uplink_load`` is per-step for scanned blocks and per-window
            for fast-forwarded ones — the mean over axis 0 is the chunk
            mean either way (a window average of constants)."""
            occupied = st2.slot_fid < F_pad
            active = occupied[:, None] & ~st2.sub_done
            return recorder.record_chunk(
                record, ring, step0=st0.step, steps=length, ff=ff,
                queue_max=jnp.max(o.max_queue),
                queue_mean=jnp.mean(o.max_queue),
                cnp=jnp.sum(o.cnp_rate), goodput=jnp.mean(o.goodput_total),
                offered=o.uplink_load.mean(axis=0).reshape(-1),
                cap=cap_row_of(st0.step), rc=st2.cc.rc, active=active)

    if cfg.adaptive:
        macro = K * cfg.ff_macro_chunks
        horizon = n_chunks * K
        quiesce, fast_forward = phases["quiesce"], phases["fast_forward"]

        @scope("chunk")
        def ff_splice(o0, o, k0):
            gp = jax.lax.dynamic_update_slice(
                o0.goodput_total, o.goodput_total, (k0,))
            cn = jax.lax.dynamic_update_slice(o0.cnp_rate, o.cnp_rate, (k0,))
            mq = jax.lax.dynamic_update_slice(o0.max_queue, o.max_queue, (k0,))
            up = jax.lax.dynamic_update_slice(
                o0.uplink_load, o.uplink_load,
                (k0 // s,) + (0,) * len(uplink_shape))
            return StepOutputs(up, gp, cn, mq)

        def ff_block(st0, o0):
            st2, o = fast_forward(st0, macro)
            return st2, ff_splice(o0, o, st0.step), o

        if record is None:
            def body(c):
                st, outs = c
                quiet = quiesce(st, macro) & ((st.step + macro) <= horizon)

                def do_ff(c2):
                    st2, outs2, _ = ff_block(c2[0], c2[1])
                    return st2, outs2

                def do_run(c2):
                    st2, outs2, _ = run_block(c2[0], c2[1], K)
                    return st2, outs2

                return jax.lax.cond(quiet, do_ff, do_run, c)
        else:
            def body(c):
                st, outs, ring = c
                quiet = quiesce(st, macro) & ((st.step + macro) <= horizon)

                def do_ff(c2):
                    st2, outs2, o = ff_block(c2[0], c2[1])
                    return st2, outs2, rec_chunk(c2[2], c2[0], st2, o,
                                                 macro, 1)

                def do_run(c2):
                    st2, outs2, o = run_block(c2[0], c2[1], K)
                    return st2, outs2, rec_chunk(c2[2], c2[0], st2, o, K, 0)

                return jax.lax.cond(quiet, do_ff, do_run, c)
    else:
        if record is None:
            def body(c):
                st2, outs2, _ = run_block(c[0], c[1], K)
                return st2, outs2
        else:
            def body(c):
                st2, outs2, o = run_block(c[0], c[1], K)
                return st2, outs2, rec_chunk(c[2], c[0], st2, o, K, 0)

    carry = (init, outs0) if record is None else (init, outs0, ring0)
    if n_chunks:
        carry = jax.lax.while_loop(
            lambda c: (c[0].step < n_chunks * K) & alive(c[0]),
            body,
            carry,
        )
    if tail:  # horizon not divisible by K: one short block, same early exit
        if record is None:
            def tail_block(c):
                st2, outs2, _ = run_block(c[0], c[1], tail)
                return st2, outs2
        else:
            def tail_block(c):
                st2, outs2, o = run_block(c[0], c[1], tail)
                return st2, outs2, rec_chunk(c[2], c[0], st2, o, tail, 0)
        carry = jax.lax.cond(alive(carry[0]), tail_block, lambda c: c, carry)
    final, outs = carry[0], carry[1]
    base = (final.finish, final.cnp_pkts, final.spill_steps, final.ff_steps,
            outs)
    return base if record is None else base + (carry[2],)


def sort_trace(trace: Trace) -> tuple[tuple, np.ndarray, int]:
    """Sort a trace by arrival (invalid flows last at +inf).  Returns
    (sorted arrays tuple, inverse permutation, n_flows)."""
    valid = np.asarray(trace.valid, bool)
    arr = np.asarray(trace.arrivals, np.float32).copy()
    arr[~valid] = np.inf
    order = np.argsort(arr, kind="stable")
    inv = np.empty_like(order)
    inv[order] = np.arange(order.size)
    arrays = (
        np.asarray(trace.sizes, np.float32)[order],
        arr[order],
        np.asarray(trace.src, np.int32)[order],
        np.asarray(trace.dst, np.int32)[order],
        np.asarray(trace.flow_id, np.uint32)[order],
        valid[order],
        np.asarray(trace.spray, np.int32)[order],
    )
    return arrays, inv, order.size


def pad_trace_arrays(arrays: tuple, F_pad: int) -> tuple:
    sizes, arr, src, dst, fid, valid, spray = arrays
    pad = F_pad - sizes.shape[0]
    assert pad >= 0, (sizes.shape[0], F_pad)
    if pad == 0:
        return arrays
    return (
        np.concatenate([sizes, np.ones(pad, np.float32)]),
        np.concatenate([arr, np.full(pad, np.inf, np.float32)]),
        np.concatenate([src, np.zeros(pad, np.int32)]),
        np.concatenate([dst, np.zeros(pad, np.int32)]),
        np.concatenate([fid, np.zeros(pad, np.uint32)]),
        np.concatenate([valid, np.zeros(pad, bool)]),
        np.concatenate([spray, np.ones(pad, np.int32)]),
    )


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5), donate_argnums=(7,))
def _run_single(topo, cfg, W, F_pad, A, n_steps, trace_arrays, finish0):
    return run_core(topo, cfg, W, F_pad, A, n_steps, trace_arrays, finish0,
                    gate_admission=True)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5), donate_argnums=(7,))
def _run_single_reorder(topo, cfg, W, F_pad, A, n_steps, trace_arrays,
                        finish0, reorder):
    return run_core(topo, cfg, W, F_pad, A, n_steps, trace_arrays, finish0,
                    gate_admission=True, reorder=reorder)


def simulate_compact(
    topo: Topology, cfg: SimConfig, trace: Trace, *,
    window_slots: int | None = None, reorder=None,
) -> tuple[CompactResult, StepOutputs]:
    """One-shot compact run (single trace; for sweeps use netsim/sweep.py).

    Drop-in for ``engine.simulate`` where only finish times / CNP counts /
    per-step outputs are consumed.  ``reorder`` (float packets or None)
    enables the flowcell reordering cost as a traced budget."""
    arrays, inv, F = sort_trace(trace)
    F_pad = max(F, 1)
    W, A = plan_single_window(topo, cfg, arrays, F_pad)
    if window_slots is not None:  # explicit window: honor it exactly
        W = max(8, min(int(window_slots), F_pad))  # (tests probe spill)
    n_steps = int(round(cfg.duration_s / cfg.dt))
    if reorder is None:
        finish, cnp, spill, ff, outs = _run_single(
            topo, cfg, W, F_pad, A, n_steps,
            tuple(jnp.asarray(a) for a in arrays),
            jnp.full((F_pad,), jnp.inf, jnp.float32),
        )
    else:
        finish, cnp, spill, ff, outs = _run_single_reorder(
            topo, cfg, W, F_pad, A, n_steps,
            tuple(jnp.asarray(a) for a in arrays),
            jnp.full((F_pad,), jnp.inf, jnp.float32), jnp.float32(reorder),
        )
    res = CompactResult(
        finish=np.asarray(finish)[:F][inv],
        cnp_pkts=np.asarray(cnp),
        spill_steps=int(spill),
        window_slots=W,
        ff_steps=int(ff),
    )
    return res, outs
