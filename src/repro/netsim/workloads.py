"""Traffic workloads (paper Fig. 9): AliCloud-Storage and WebSearch.

Flow sizes are drawn from empirical CDFs; arrivals are Poisson at a rate
chosen to hit a target average load on the host-uplink capacity:

    lambda = load * n_hosts * host_bw / (8 * mean_size_bytes)

The CDF tables are the published ones: WebSearch from the DCTCP paper
(Alizadeh et al., SIGCOMM'10) and AliCloud Storage digitized from HPCC
(Li et al., SIGCOMM'19) — both are the sources the paper itself cites for
its Fig. 9.  Sampling happens in numpy up front; the engine consumes plain
arrays (sizes, arrival times, src/dst hosts).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hashing, routing

# (size_bytes, cumulative_probability)
WEBSEARCH_CDF = np.array(
    [
        (1_000, 0.00),
        (10_000, 0.15),
        (20_000, 0.20),
        (30_000, 0.30),
        (50_000, 0.40),
        (80_000, 0.53),
        (200_000, 0.60),
        (1_000_000, 0.70),
        (2_000_000, 0.80),
        (5_000_000, 0.90),
        (10_000_000, 0.97),
        (30_000_000, 1.00),
    ],
    dtype=np.float64,
)

ALISTORAGE_CDF = np.array(
    [
        (1_000, 0.00),
        (2_000, 0.10),
        (4_000, 0.30),
        (8_000, 0.50),
        (16_000, 0.65),
        (32_000, 0.80),
        (64_000, 0.90),
        (100_000, 0.95),
        (256_000, 0.98),
        (1_000_000, 0.99),
        (2_000_000, 1.00),
    ],
    dtype=np.float64,
)

WORKLOADS = {"websearch": WEBSEARCH_CDF, "alistorage": ALISTORAGE_CDF}


def cdf_mean(cdf: np.ndarray) -> float:
    """Mean flow size implied by the piecewise-linear CDF."""
    sizes, probs = cdf[:, 0], cdf[:, 1]
    mids = (sizes[1:] + sizes[:-1]) / 2
    masses = np.diff(probs)
    return float((mids * masses).sum())


def sample_sizes(cdf: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    """Inverse-transform sampling with linear interpolation between knots."""
    u = rng.uniform(0.0, 1.0, n)
    return np.interp(u, cdf[:, 1], cdf[:, 0]).astype(np.float32)


@dataclasses.dataclass
class TraceConfig:
    workload: str  # "websearch" | "alistorage" | "fixed:<bytes>"
    load: float  # fraction of ``load_base_bw`` (defaults to host aggregate)
    duration_s: float
    n_hosts: int
    host_bw: float
    seed: int = 0
    inter_rack_only: bool = True
    hosts_per_leaf: int = 16
    max_flows: int | None = None  # cap (padded arrays); None = exact
    # aggregate bps that ``load`` multiplies.  For fabric-bound topologies
    # (e.g. 128 hosts over 96 uplinks) pass the bisection capacity so that
    # "80% load" means 80% MEAN FABRIC UTILIZATION, as in the paper's sims.
    load_base_bw: float | None = None


@dataclasses.dataclass
class Trace:
    sizes: np.ndarray  # f32[F] bytes
    arrivals: np.ndarray  # f32[F] seconds
    src: np.ndarray  # i32[F]
    dst: np.ndarray  # i32[F]
    flow_id: np.ndarray  # u32[F]
    valid: np.ndarray  # bool[F] (padding mask)
    # paths the flow's parent chunk straddles (flowcell splitting): 1 means
    # the flow is alone on its path — no reordering possible, and the
    # dataplane's reorder_gbn_factor is exactly 1 there.  Defaults to all
    # ones so every pre-flowcell constructor keeps its meaning.
    spray: np.ndarray | None = None  # i32[F]

    def __post_init__(self):
        if self.spray is None:
            self.spray = np.ones(np.shape(self.src)[0], np.int32)


def poisson_trace(cfg: TraceConfig) -> Trace:
    rng = np.random.default_rng(cfg.seed)
    if cfg.workload.startswith("fixed:"):
        mean = float(cfg.workload.split(":", 1)[1])
        sampler = lambda n: np.full(n, mean, np.float32)
    else:
        cdf = WORKLOADS[cfg.workload]
        mean = cdf_mean(cdf)
        sampler = lambda n: sample_sizes(cdf, n, rng)

    base = cfg.load_base_bw if cfg.load_base_bw is not None else cfg.n_hosts * cfg.host_bw
    lam = cfg.load * base / (8.0 * mean)  # flows/sec
    n = max(1, int(lam * cfg.duration_s * 1.05) + 16)
    gaps = rng.exponential(1.0 / lam, n)
    arrivals = np.cumsum(gaps)
    keep = arrivals < cfg.duration_s
    arrivals = arrivals[keep].astype(np.float32)
    n = len(arrivals)
    sizes = sampler(n)
    src = rng.integers(0, cfg.n_hosts, n).astype(np.int32)
    if cfg.inter_rack_only:
        # redraw dst until on a different leaf (vectorized rejection)
        dst = rng.integers(0, cfg.n_hosts, n).astype(np.int32)
        for _ in range(64):
            same = (src // cfg.hosts_per_leaf) == (dst // cfg.hosts_per_leaf)
            if not same.any():
                break
            dst[same] = rng.integers(0, cfg.n_hosts, int(same.sum())).astype(np.int32)
        if cfg.n_hosts > cfg.hosts_per_leaf:
            # deterministic fallback: shift any survivor of the rejection
            # loop to the same offset on the next leaf (never silently keep
            # an intra-rack pair — it would vanish from the fabric stats).
            # The shift moves the LEAF index, not the host index, so a
            # ragged final leaf (n_hosts % hosts_per_leaf != 0) can't wrap
            # a survivor back into its own rack; the clamp only engages
            # when the target is that ragged final leaf.
            hpl = cfg.hosts_per_leaf
            n_leaf = -(-cfg.n_hosts // hpl)
            same = (src // hpl) == (dst // hpl)
            shifted = ((dst // hpl + 1) % n_leaf) * hpl + dst % hpl
            shifted = np.minimum(shifted, cfg.n_hosts - 1)
            dst = np.where(same, shifted, dst).astype(np.int32)
    else:
        dst = rng.integers(0, cfg.n_hosts, n).astype(np.int32)
        dst = np.where(dst == src, (dst + 1) % cfg.n_hosts, dst).astype(np.int32)

    flow_id = np.arange(n, dtype=np.uint32) * np.uint32(2654435761) + np.uint32(cfg.seed)

    if cfg.max_flows is not None and n > cfg.max_flows:
        sizes, arrivals, src, dst, flow_id = (
            a[: cfg.max_flows] for a in (sizes, arrivals, src, dst, flow_id)
        )
        n = cfg.max_flows
    pad = 0
    if cfg.max_flows is not None and n < cfg.max_flows:
        pad = cfg.max_flows - n

    def padded(a, fill):
        return np.concatenate([a, np.full(pad, fill, a.dtype)]) if pad else a

    valid = padded(np.ones(n, bool), False)
    return Trace(
        sizes=padded(sizes, 1.0),
        arrivals=padded(arrivals, np.float32(1e30)),
        src=padded(src, 0),
        dst=padded(dst, 0),
        flow_id=padded(flow_id, 0),
        valid=valid,
    )


def _ecmp_steered_fids(src: np.ndarray, dst: np.ndarray, base_fid: np.ndarray,
                       target_path: np.ndarray, n_paths: int) -> np.ndarray:
    """Per-QP flow ids whose engine-side ECMP hash lands on the planned
    fabric path — the fluid-model analog of steering a RoCE QP's UDP source
    port so the fabric's five-tuple hash picks the path the planner chose
    (how PathTag-less deployments pin multipath today).  Mirrors
    ``engine.flow_constants``'s per-flow five-tuple (sport from
    ``fmix32(fid)``, dport 4791); ``tests/test_cosim.py`` pins the two
    against each other so they cannot drift silently.

    For each QP the candidate ids ``base + k * golden`` are hashed in one
    jitted sweep and the first hit wins; a QP with no hit in the ~32x
    oversampled candidate set (probability ~(1-1/P)^(32P) ~ 1e-14) keeps
    its base id."""
    K = int(min(32 * n_paths, 16384))
    ks = np.arange(K, dtype=np.uint32) * np.uint32(0x9E3779B1)
    k = np.asarray(_first_steered_candidate(
        np.asarray(src).astype(np.uint32), np.asarray(dst).astype(np.uint32),
        np.asarray(base_fid).astype(np.uint32), np.asarray(target_path, np.int32), ks,
        n_paths=n_paths))
    return base_fid.astype(np.uint32) + ks[k]


@functools.partial(jax.jit, static_argnames="n_paths")
def _first_steered_candidate(src, dst, base_fid, target_path, ks, n_paths: int):
    """The index k into ``ks`` of each QP's first candidate id
    ``base + ks[k]`` (uint32 wrap) that ECMP hashes onto its target path,
    0 where none does.  One dispatch in place of one per hashing op."""
    cand = base_fid[:, None] + ks[None, :]  # [Q, K] wraps
    sport = jnp.uint32(0xB000) + (hashing.fmix32(cand) % jnp.uint32(0x3FFF))
    dport = jnp.full(cand.shape, 4791, jnp.uint32)
    p = routing.ecmp_paths(src[:, None], dst[:, None], sport, dport, n_paths)
    hit = p == target_path[:, None]
    return jnp.where(hit.any(axis=1), jnp.argmax(hit, axis=1), 0)


def collective_trace(
    plan,
    hosts: list[int] | np.ndarray,
    size_bytes: float,
    *,
    link_bw: float,
    start_s: float = 0.0,
    rounds: int | None = None,
    round_gap_s: float | None = None,
    seed: int = 0,
    steer_paths: int | None = None,
    steer_targets: np.ndarray | None = None,
) -> Trace:
    """AI-training traffic mode: the ring schedule of a grad-sync PathPlan
    (``repro.dist.collectives.PathPlan`` — duck-typed: anything with
    ``n_chunks``, ``directions`` and ``chunk_paths()``) rendered as a
    sweepable Trace.

    ``hosts`` are the ring members (e.g. one host per leaf — the pod
    gateways).  A chunked bidirectional ring all-reduce of ``size_bytes``
    per member runs ``2*(n-1)`` rounds; in every round each member sends
    one segment of each chunk to its ring neighbor in that chunk's
    direction.  The result is the paper's motivating pattern: a handful of
    huge, synchronized, long-lived flows between fixed host pairs — ECMP
    collapses them onto few fabric paths, SeqBalance's sub-flows spread
    them.  Each (chunk, ring member) pair keeps ONE flow id across all
    rounds — the persistent QP of that chunk-ring segment — so hash-based
    schemes pin it to one path for the whole collective (re-hashing per
    round would both reorder the chunk and accidentally load-balance the
    very hotspots this traffic mode exists to demonstrate).

    ``round_gap_s`` defaults to the segment serialization time at
    ``link_bw`` (the idealized bulk-synchronous cadence).

    ``steer_paths`` (= the topology's ``n_paths``) turns the plan into a
    BINDING route: each QP's flow id is chosen so the engine's ECMP
    five-tuple hash maps it onto its planned fabric path
    (``_ecmp_steered_fids`` — UDP-source-port steering in the fluid
    model).  The chunk -> path map supplies the ring DIRECTIONS; the
    steered fabric target is additionally diversified per member —
    member i's chunk-c QP rides active_path[(i * n_chunks + c) % n_active]
    — because on a 3-tier fabric a globally shared per-chunk path would
    funnel every member's chunk-c flow through one 100G agg-core link
    (n-fold overload by construction), while per-member spreading is
    exactly what per-QP source ports give a real deployment.  Quarantined
    paths are excluded from the spread, so the co-sim loop can actually
    route AROUND them — the whole Fig. 11 convergence story.  Without
    ``steer_paths`` the plan only shapes the traffic matrix and the
    fabric re-rolls paths by hash.

    ``steer_targets`` (int [n_chunks, n], needs ``steer_paths``) overrides
    the default spread with an EXPLICIT per-QP fabric target.  This is the
    in-epoch replanning hook (``dist.cosim``): the caller pins every
    surviving QP to exactly the target it had before a mid-collective
    fault — keeping its flow id, hence its path, hence its packet order —
    and re-steers only the QPs whose target died.  The default spread
    formula recomputes from the ACTIVE set, which shifts every QP's target
    when the set shrinks; that is fine between collectives but would be a
    mass reorder inside one.
    """
    hosts = np.asarray(hosts, np.int64)
    n = int(hosts.size)
    assert n >= 2, "a ring needs at least two members"
    n_chunks = int(plan.n_chunks)
    paths = tuple(plan.chunk_paths())
    dirs = tuple(int(plan.directions[p]) for p in paths)  # per-chunk ring dir
    seg_bytes = float(size_bytes) / (n * n_chunks)
    if round_gap_s is None:
        round_gap_s = seg_bytes * 8.0 / link_bw
    n_rounds = 2 * (n - 1) if rounds is None else int(rounds)

    base = (seed * 0x9E3779B9) & 0xFFFFFFFF
    fcells = int(getattr(plan, "flowcells", 1))
    if fcells > 1:
        # token-based flowcell splitting (RDMACell): each (chunk, member)
        # segment is cut into `fcells` cells on DISTINCT QPs, each steered
        # to its own path from plan.flowcell_paths()'s round-robin — the
        # rendered trace carries spray = straddled-path count so the
        # dataplane can charge the reordering cost.  Kept as a separate
        # branch so the fcells == 1 path below stays byte-identical to the
        # pre-flowcell construction (pinned by the sha-golden twins).
        return _flowcell_trace(
            plan, hosts, n, n_chunks, dirs, seg_bytes, round_gap_s, n_rounds,
            start_s, base, fcells, steer_paths, steer_targets)
    # one QP per (chunk, member), persistent across rounds
    qp_fid = np.array(
        [[((c * n + i) * 2654435761 + base) & 0xFFFFFFFF for i in range(n)]
         for c in range(n_chunks)], np.uint32)
    if steer_paths is not None:
        assert max(paths) < steer_paths, (paths, steer_paths)
        active = [p for p, dead in enumerate(plan.inactive)
                  if not dead and p < steer_paths] or [0]
        q_src = np.array([[hosts[i] for i in range(n)]
                          for c in range(n_chunks)], np.int64)
        q_dst = np.array([[hosts[(i + dirs[c]) % n] for i in range(n)]
                          for c in range(n_chunks)], np.int64)
        if steer_targets is not None:
            q_target = np.asarray(steer_targets, np.int32).reshape(n_chunks, n)
            assert int(q_target.max()) < steer_paths, (q_target, steer_paths)
        else:
            q_target = np.array(
                [[active[(i * n_chunks + c) % len(active)] for i in range(n)]
                 for c in range(n_chunks)], np.int32)
        qp_fid = _ecmp_steered_fids(
            q_src.reshape(-1), q_dst.reshape(-1), qp_fid.reshape(-1),
            q_target.reshape(-1), steer_paths).reshape(n_chunks, n)
    sizes, arrivals, src, dst, flow_id = [], [], [], [], []
    for r in range(n_rounds):
        t = start_s + r * round_gap_s
        for c, d in enumerate(dirs):
            for i in range(n):
                sizes.append(seg_bytes)
                arrivals.append(t)
                src.append(hosts[i])
                dst.append(hosts[(i + d) % n])
                flow_id.append(qp_fid[c, i])
    f = len(sizes)
    flow_id = np.asarray(flow_id, np.uint32)
    return Trace(
        sizes=np.asarray(sizes, np.float32),
        arrivals=np.asarray(arrivals, np.float32),
        src=np.asarray(src, np.int32),
        dst=np.asarray(dst, np.int32),
        flow_id=flow_id,
        valid=np.ones(f, bool),
    )


def _flowcell_trace(plan, hosts, n, n_chunks, dirs, seg_bytes, round_gap_s,
                    n_rounds, start_s, base, fcells, steer_paths,
                    steer_targets) -> Trace:
    """Flowcell rendering of a collective: one QP per (chunk, member, cell),
    cell sizes ``seg_bytes / fcells`` (bytes per chunk conserved), cell j
    steered to the j-th active path after the chunk's own (the
    ``PathPlan.flowcell_paths`` round-robin, diversified per member exactly
    like the chunk-granularity default).  Every row carries
    ``spray = min(fcells, n_active)`` — the straddle count the dataplane's
    ``reorder_gbn_factor`` turns into a go-back-N amplification."""
    active = [p for p, dead in enumerate(plan.inactive) if not dead]
    if steer_paths is not None:
        active = [p for p in active if p < steer_paths]
    if not active:
        active = [0]
    A = len(active)
    spray_val = min(fcells, A)
    qp_fid = np.array(
        [[[((c * n + i) * 2654435761 + base + j * 0x85EBCA77) & 0xFFFFFFFF
           for j in range(fcells)] for i in range(n)] for c in range(n_chunks)],
        np.uint32)
    if steer_paths is not None:
        q_src = np.array([[[hosts[i]] * fcells for i in range(n)]
                          for c in range(n_chunks)], np.int64)
        q_dst = np.array([[[hosts[(i + dirs[c]) % n]] * fcells
                           for i in range(n)] for c in range(n_chunks)], np.int64)
        if steer_targets is not None:
            # in-epoch replanning: cell 0 keeps the EXPLICIT pinned target
            # (same five-tuple -> same path -> no reorder); later cells walk
            # the active paths from it.
            pinned = np.asarray(steer_targets, np.int32).reshape(n_chunks, n)
            assert int(pinned.max()) < steer_paths, (pinned, steer_paths)
            q_target = np.empty((n_chunks, n, fcells), np.int32)
            for c in range(n_chunks):
                for i in range(n):
                    p0 = int(pinned[c, i])
                    b = active.index(p0) if p0 in active else 0
                    q_target[c, i, 0] = p0
                    for j in range(1, fcells):
                        q_target[c, i, j] = active[(b + j) % A]
        else:
            q_target = np.array(
                [[[active[(i * n_chunks + c + j) % A] for j in range(fcells)]
                  for i in range(n)] for c in range(n_chunks)], np.int32)
        qp_fid = _ecmp_steered_fids(
            q_src.reshape(-1), q_dst.reshape(-1), qp_fid.reshape(-1),
            q_target.reshape(-1), steer_paths).reshape(n_chunks, n, fcells)
    cell_bytes = seg_bytes / fcells
    sizes, arrivals, src, dst, flow_id = [], [], [], [], []
    for r in range(n_rounds):
        t = start_s + r * round_gap_s
        for c, d in enumerate(dirs):
            for i in range(n):
                for j in range(fcells):
                    sizes.append(cell_bytes)
                    arrivals.append(t)
                    src.append(hosts[i])
                    dst.append(hosts[(i + d) % n])
                    flow_id.append(qp_fid[c, i, j])
    f = len(sizes)
    return Trace(
        sizes=np.asarray(sizes, np.float32),
        arrivals=np.asarray(arrivals, np.float32),
        src=np.asarray(src, np.int32),
        dst=np.asarray(dst, np.int32),
        flow_id=np.asarray(flow_id, np.uint32),
        valid=np.ones(f, bool),
        spray=np.full(f, spray_val, np.int32),
    )


def merge_traces(*traces: Trace) -> Trace:
    """Concatenate traces into one (the engine sorts by arrival itself).

    The in-epoch replanning path (``dist.cosim``) renders a collective as
    two segments — rounds before the fault onset under the original plan,
    rounds after under the replanned one — and merges them into the single
    Trace the sweep runner consumes.  Flow ids are NOT remapped: a chunk
    whose path survived the replan keeps the same QP fid in both segments,
    which is exactly the no-reordering invariant (same five-tuple -> same
    fabric path before and after the cut)."""
    assert traces, "nothing to merge"
    return Trace(
        sizes=np.concatenate([t.sizes for t in traces]),
        arrivals=np.concatenate([t.arrivals for t in traces]),
        src=np.concatenate([t.src for t in traces]),
        dst=np.concatenate([t.dst for t in traces]),
        flow_id=np.concatenate([t.flow_id for t in traces]),
        valid=np.concatenate([t.valid for t in traces]),
        spray=np.concatenate([t.spray for t in traces]),
    )


def permanent_senders_trace(
    pairs: list[tuple[int, int]], start_times: list[float], size_bytes: float
) -> Trace:
    """Fig. 10/11 scenario: long-lived full-rate flows (ib_write_bw), one
    activated per interval."""
    n = len(pairs)
    return Trace(
        sizes=np.full(n, size_bytes, np.float32),
        arrivals=np.asarray(start_times, np.float32),
        src=np.asarray([p[0] for p in pairs], np.int32),
        dst=np.asarray([p[1] for p in pairs], np.int32),
        flow_id=np.arange(n, dtype=np.uint32) * np.uint32(0x9E3779B9),
        valid=np.ones(n, bool),
    )
