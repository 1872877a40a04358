"""SeqBalance multipath collective engine (paper §III applied to grad sync).

The paper's Shaper splits one elephant WQE into N sub-flows on distinct
QPs so the fabric can spread them over N paths with no reordering inside
any one of them.  ``seqbalance_all_reduce`` is the same idea one layer up:
the gradient bucket is cut into ``n_chunks`` chunks and each chunk runs its
OWN ring all-reduce (reduce-scatter + all-gather over ``lax.ppermute``)
whose ring *direction* is the chunk's path.  A congestion-quarantined path
(``PathPlan.inactive``, fed by ``dist.elastic.LinkHealth`` /
``dist.netfeed``) is simply skipped by the round-robin chunk->path map —
in-flight chunks never migrate, mirroring the paper's
"placed sub-flows never move" no-reordering rule.

Wire dtype is orthogonal: chunks can cross the fabric as float32,
bfloat16, or int8 (per-segment absmax scale), with accumulation always in
float32.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PathPlan:
    """Static multipath plan for one collective.

    ``directions`` holds one ring direction (+1 / -1) per available path;
    ``inactive`` flags paths currently quarantined by congestion feedback.
    The plan is a *static* (hashable) argument: a new plan means a new
    compile, which is the point — path changes happen between steps, never
    inside one (no reordering).

    ``version`` is the plan's monotonic generation number (the planning
    epoch that produced it).  Plans travel from the planner to the QPs over
    the same imperfect control plane as the congestion reports, so a
    delivery can arrive late or twice; ``apply_plan`` refuses any candidate
    whose version does not EXCEED the plan currently applied — a reordered
    or duplicated delivery can never regress a QP to an older path table,
    which would silently move in-flight chunks (a reorder).
    """

    n_chunks: int = 4
    directions: tuple[int, ...] = (1, -1)
    inactive: tuple[bool, ...] | None = None
    wire_dtype: str = "float32"
    version: int = 0
    # token-based flowcell splitting BELOW the chunk (RDMACell's granularity,
    # the "other side" of the paper's no-reordering trade): each chunk's wire
    # traffic is cut into `flowcells` equal token cells, round-robined over
    # the active paths — so one chunk STRADDLES min(flowcells, n_active)
    # paths and pays the reordering cost the fluid model charges via
    # dataplane.reorder_gbn_factor.  flowcells=1 is bit-exactly the classic
    # per-chunk plan.  `reorder_budget` is the NIC's out-of-order absorption
    # in packets (0 = strict go-back-N); it rides along to the sim as the
    # traced `reorder` operand.
    flowcells: int = 1
    reorder_budget: float = 0.0

    def __post_init__(self):
        assert self.n_chunks >= 1
        assert all(d in (1, -1) for d in self.directions), self.directions
        if self.inactive is None:
            object.__setattr__(self, "inactive", (False,) * len(self.directions))
        assert len(self.inactive) == len(self.directions)
        assert self.wire_dtype in ("float32", "bfloat16", "int8"), self.wire_dtype
        assert self.flowcells >= 1, self.flowcells
        assert self.reorder_budget >= 0.0, self.reorder_budget

    @property
    def n_paths(self) -> int:
        return len(self.directions)

    def chunk_paths(self) -> tuple[int, ...]:
        """Round-robin chunk -> path assignment over the active paths.

        When every path is quarantined the table carries no routing signal
        (the paper: traffic must still flow) — fall back to the primary
        path rather than stalling the collective.
        """
        active = [p for p, dead in enumerate(self.inactive) if not dead]
        if not active:
            active = [0]
        return tuple(active[c % len(active)] for c in range(self.n_chunks))

    def flowcell_paths(self) -> tuple[tuple[int, ...], ...]:
        """Per-chunk flowcell -> path table: chunk c's cell j rides path
        ``active[(c + j) % n_active]`` — cell 0 is the chunk's classic
        round-robin path (so ``flowcells=1`` degenerates exactly to
        ``chunk_paths``), later cells walk the remaining active paths."""
        active = [p for p, dead in enumerate(self.inactive) if not dead]
        if not active:
            active = [0]
        return tuple(
            tuple(active[(c + j) % len(active)] for j in range(self.flowcells))
            for c in range(self.n_chunks)
        )


@dataclasses.dataclass(frozen=True)
class PinnedPlan:
    """A PathPlan whose chunk -> path table is EXPLICIT rather than derived
    round-robin — the output of in-epoch replanning (``replan_chunk_paths``).
    Duck-types ``PathPlan`` for everything that consumes plans
    (``workloads.collective_trace``, the ring engine): same ``n_chunks`` /
    ``directions`` / ``inactive`` / ``wire_dtype`` fields, but
    ``chunk_paths()`` returns the pinned table verbatim."""

    n_chunks: int
    directions: tuple[int, ...]
    inactive: tuple[bool, ...]
    paths: tuple[int, ...]  # chunk c -> path paths[c]
    wire_dtype: str = "float32"
    version: int = 0
    flowcells: int = 1
    reorder_budget: float = 0.0

    def __post_init__(self):
        assert len(self.paths) == self.n_chunks, (self.paths, self.n_chunks)
        assert len(self.inactive) == len(self.directions)
        assert all(0 <= p < len(self.directions) for p in self.paths)
        assert self.flowcells >= 1, self.flowcells
        assert self.reorder_budget >= 0.0, self.reorder_budget

    @property
    def n_paths(self) -> int:
        return len(self.directions)

    def chunk_paths(self) -> tuple[int, ...]:
        return tuple(self.paths)

    def flowcell_paths(self) -> tuple[tuple[int, ...], ...]:
        """Cell 0 keeps the PINNED path verbatim (replanning decided it);
        later cells walk the active paths from the pinned one."""
        active = [p for p, dead in enumerate(self.inactive) if not dead]
        if not active:
            active = [0]
        out = []
        for c, p0 in enumerate(self.paths):
            base = active.index(p0) if p0 in active else 0
            cells = (p0,) + tuple(
                active[(base + j) % len(active)] for j in range(1, self.flowcells)
            )
            out.append(cells)
        return tuple(out)


def apply_plan(current, candidate) -> tuple[object, bool]:
    """Versioned plan application: the no-reordering rule ACROSS plans.

    Returns ``(applied, took_candidate)``.  The candidate replaces the
    current plan only when its ``version`` strictly exceeds the applied
    one; a stale (reordered) or repeated (duplicated) delivery is refused
    and the current table stays in force.  Applying an OLDER table would
    retroactively move chunks whose packets are already committed to the
    newer table's paths — the cross-version spelling of "placed sub-flows
    never move".  Refusal is idempotence, not an error: the caller counts
    refusals (``dist.cosim`` records them) but keeps running."""
    if candidate.version <= current.version:
        return current, False
    return candidate, True


def replan_chunk_paths(paths: tuple[int, ...], directions: tuple[int, ...],
                       inactive: tuple[bool, ...],
                       in_flight: tuple[int, ...] = ()) -> tuple[int, ...]:
    """Mid-collective replan: move chunks off newly-quarantined paths onto
    surviving ones WITHOUT ever reordering a chunk.

    The no-reordering rule, per chunk:

      * a chunk in ``in_flight`` keeps its path unconditionally — its
        packets are already interleaved on the wire, and a migration would
        race them (exactly the per-sub-flow rule of the paper's Shaper);
      * a migrating chunk may only move to a path with the SAME ring
        direction — flipping direction renumbers every segment the chunk
        has already reduced, which is a reorder of its own stream;
      * if no same-direction path survives, the chunk STAYS on its
        quarantined path (graceful degradation: a slow path delivers late
        but in order; a direction flip delivers wrong).

    Surviving chunks on healthy paths are untouched.  Migrants spread
    round-robin over the same-direction survivors."""
    assert len(directions) == len(inactive)
    in_flight_set = set(in_flight)
    survivors: dict[int, list[int]] = {}
    for p, d in enumerate(directions):
        if not inactive[p]:
            survivors.setdefault(d, []).append(p)
    out: list[int] = []
    rr: dict[int, int] = {}
    for c, p in enumerate(paths):
        if c in in_flight_set or not inactive[p]:
            out.append(p)
            continue
        same_dir = survivors.get(directions[p], [])
        if not same_dir:
            out.append(p)  # degraded: in-order on a slow path beats a flip
            continue
        k = rr.get(directions[p], 0)
        out.append(same_dir[k % len(same_dir)])
        rr[directions[p]] = k + 1
    return tuple(out)


# ------------------------------------------------------------- wire dtypes
def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Absmax int8 quantization: returns (q int8, scale f32 scalar) with
    x ~= q * scale and |x - q*scale| <= scale/2 (round-to-nearest)."""
    scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0
    scale = jnp.maximum(scale, jnp.float32(1e-30))
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _encode(x, wire: str):
    if wire == "bfloat16":
        # ship the raw bf16 bits: bitcasting to uint16 pins the 2-byte wire
        # format in the lowered HLO (a plain astype round-trip gets hoisted
        # across the ppermute by XLA's simplifier, silently widening the
        # wire back to 4 bytes)
        return jax.lax.bitcast_convert_type(x.astype(jnp.bfloat16), jnp.uint16)
    if wire == "int8":
        return quantize_int8(x)
    return x


def _decode(y, wire: str):
    if wire == "bfloat16":
        return jax.lax.bitcast_convert_type(y, jnp.bfloat16).astype(jnp.float32)
    if wire == "int8":
        return dequantize_int8(*y)
    return y


def _permute(payload, axis_name, perm):
    return jax.tree.map(lambda a: jax.lax.ppermute(a, axis_name, perm), payload)


# ------------------------------------------------------------ ring engine
def _ring_all_reduce(v: jax.Array, axis_name: str, d: int, n: int, wire: str):
    """One chunk's ring all-reduce.  ``v`` is f32[n, seg] (one segment per
    ring member); direction ``d`` is the chunk's path.  2*(n-1) ppermute
    rounds: reduce-scatter then all-gather, exactly the bandwidth-optimal
    schedule the fabric sees as one long-lived flow per neighbor pair."""
    if n == 1:
        return v
    i = jax.lax.axis_index(axis_name)
    perm = [(src, (src + d) % n) for src in range(n)]

    def seg(arr, idx):
        return jax.lax.dynamic_index_in_dim(arr, idx % n, axis=0, keepdims=False)

    def put(arr, val, idx):
        return jax.lax.dynamic_update_index_in_dim(arr, val, idx % n, axis=0)

    # reduce-scatter: after step s, device i holds the partial sum of s+1
    # contributions in segment (i - (s+1)*d); after n-1 steps its segment
    # (i + d) is fully reduced.
    for s in range(n - 1):
        send = seg(v, i - s * d)
        recv = _decode(_permute(_encode(send, wire), axis_name, perm), wire)
        ridx = i - (s + 1) * d
        v = put(v, seg(v, ridx) + recv, ridx)

    # all-gather: circulate the reduced segments the opposite way around
    # the same ring (send what you last received).
    for s in range(n - 1):
        send = seg(v, i + d - s * d)
        recv = _decode(_permute(_encode(send, wire), axis_name, perm), wire)
        v = put(v, recv, i - s * d)
    return v


def seqbalance_all_reduce(x: jax.Array, axis_name: str, plan: PathPlan | None = None):
    """Multipath chunked ring all-reduce of ``x`` over ``axis_name``.

    Must be called inside ``shard_map`` (manual over ``axis_name``).
    Returns the full sum with ``x``'s shape and dtype; equals
    ``lax.psum(x, axis_name)`` up to wire-dtype rounding.
    """
    plan = PathPlan() if plan is None else plan
    n = jax.lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.astype(jnp.float32).reshape(-1)
    m = flat.size
    c = plan.n_chunks
    seg = -(-max(m, 1) // (c * n))
    flat = jnp.pad(flat, (0, c * n * seg - m))
    chunks = flat.reshape(c, n, seg)
    paths = plan.chunk_paths()
    reduced = [
        _ring_all_reduce(chunks[k], axis_name, int(plan.directions[paths[k]]),
                         int(n), plan.wire_dtype)
        for k in range(c)
    ]
    out = jnp.stack(reduced).reshape(-1)[:m].reshape(shape)
    return out.astype(dtype)


# ----------------------------------------------------------- conveniences
def baseline_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Stock XLA all-reduce — the single-path elephant flow the paper's
    motivation describes (one fat all-reduce per gradient)."""
    return jax.lax.psum(x, axis_name)


def tree_all_reduce_mean(tree, axis_name: str, plan: PathPlan | None = None):
    """Grad sync: SeqBalance all-reduce each leaf, then divide by the axis
    size (data-parallel mean)."""
    n = jax.lax.axis_size(axis_name)

    def one(g):
        s = seqbalance_all_reduce(g, axis_name, plan)
        return (s.astype(jnp.float32) / n).astype(g.dtype)

    return jax.tree.map(one, tree)
