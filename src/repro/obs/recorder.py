"""In-sim flight recorder: a traced, fixed-shape ring buffer of per-chunk
summaries carried through ``compact.run_core``'s chunk loop (DESIGN.md §16).

The compact engine runs the horizon as K-step ``lax.scan`` chunks inside an
early-exit ``while_loop``; per-``dt``-step traces at paper scale are far too
large to keep, but one summary row per *chunk* is nearly free: the scan
already materializes the chunk's output slab, so the recorder just reduces
it (max/mean/sum) plus a handful of state statistics (active sub-flows,
DCQCN rate quantiles, per-uplink offered-vs-capacity) into a fixed-shape
ring written with ``dynamic_update_slice`` at ``count % R``.  Fixed shapes
mean the ring joins the executable-cache key exactly like the traced
capacity operand (PR 5): one extra compiled program per ``RecordSpec``,
ZERO rebuilds across epochs — asserted in tests/test_obs.py.

All gating happens at Python trace time: ``record=None`` traces the
identical program as before recording existed (bit-identical results,
pinned by the sha goldens in tests/test_obs.py).

Quantiles are sort-based rank statistics (``sort`` + gather at
``(n_active - 1) * q``), not ``nanpercentile`` — deterministic, no data-
dependent shapes, exact on the active sub-flow population.

Host-side, ``drain`` unrolls the ring into chronological order (the newest
``R`` chunks survive a wraparound; the exact boundary chunk is included —
tested) and ``epoch_summary`` reduces it to the JSON-able per-epoch record
the flight log and ``obs.features.epoch_matrix`` consume.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class RecordSpec:
    """Recorder knobs.  Frozen + hashable: the spec joins the sweep
    executable-cache key (netsim/sweep.py), so two runs with the same spec
    share one compiled program."""

    ring_chunks: int = 64  # R: per-chunk summary rows retained (newest win)
    quantiles: tuple = (0.1, 0.5, 0.9)  # DCQCN rc rank quantiles


#: scalar summary columns of ``RingState.meta`` (before the per-spec
#: ``rc_q*`` quantile columns appended by ``meta_fields``)
META_FIELDS = (
    "step0",  # first dt step of the chunk
    "steps",  # chunk length in dt steps
    "ff",  # 1.0 if the chunk was covered by a quiescence fast-forward
    "queue_max",  # max over the chunk of the per-step max queue (bytes)
    "queue_mean",  # mean over the chunk of the per-step max queue (bytes)
    "cnp_pkts",  # expected congestion packets generated in the chunk
    "goodput_mean",  # mean total delivered rate over the chunk (bit/s)
    "active_subflows",  # active sub-flows at the chunk boundary
)


def meta_fields(spec: RecordSpec) -> tuple:
    return META_FIELDS + tuple(
        f"rc_q{int(round(q * 100))}" for q in spec.quantiles)


class RingState(NamedTuple):
    """Fixed-shape recorder state (a pytree: vmap/pmap batch it like any
    other sim output)."""

    meta: jax.Array  # f32[R, M] per-chunk scalar summaries
    uplink: jax.Array  # f32[R, U, 2] per-uplink (offered, capacity) bit/s
    count: jax.Array  # i32[] chunks written so far (monotonic, may exceed R)


def ring_init(spec: RecordSpec, n_uplinks: int) -> RingState:
    R = int(spec.ring_chunks)
    return RingState(
        meta=jnp.zeros((R, len(meta_fields(spec))), jnp.float32),
        uplink=jnp.zeros((R, int(n_uplinks), 2), jnp.float32),
        count=jnp.zeros((), jnp.int32),
    )


def record_chunk(spec: RecordSpec, ring: RingState, *, step0, steps, ff,
                 queue_max, queue_mean, cnp, goodput, offered, cap, rc,
                 active) -> RingState:
    """Append one chunk-summary row (traced; fixed shapes only).

    ``offered``/``cap`` are f32[U] per-uplink rates at chunk granularity;
    ``rc`` f32[W, N] DCQCN rates and ``active`` bool[W, N] the live mask at
    the chunk boundary; everything else is scalar."""
    f32 = jnp.float32
    n_act = jnp.sum(active.astype(jnp.int32))
    n_act_f = n_act.astype(f32)
    vals = jnp.sort(jnp.where(active, rc, jnp.inf).ravel())
    size = int(vals.shape[0])
    qs = []
    for q in spec.quantiles:
        idx = jnp.clip(((n_act_f - 1.0) * f32(q)).astype(jnp.int32),
                       0, size - 1)
        qs.append(jnp.where(n_act > 0, vals[idx], f32(0.0)))
    row = jnp.stack([
        jnp.asarray(step0).astype(f32), f32(steps), f32(ff),
        jnp.asarray(queue_max).astype(f32),
        jnp.asarray(queue_mean).astype(f32),
        jnp.asarray(cnp).astype(f32),
        jnp.asarray(goodput).astype(f32),
        n_act_f,
    ] + qs)
    slot = ring.count % spec.ring_chunks
    meta = jax.lax.dynamic_update_slice(ring.meta, row[None], (slot, 0))
    up = jnp.stack([jnp.asarray(offered).astype(f32),
                    jnp.asarray(cap).astype(f32)], axis=-1)  # [U, 2]
    uplink = jax.lax.dynamic_update_slice(ring.uplink, up[None], (slot, 0, 0))
    return RingState(meta=meta, uplink=uplink, count=ring.count + 1)


def drain(spec: RecordSpec, ring: RingState) -> dict:
    """Host-side: unroll one sim's ring into chronological order.

    After ``count`` writes the oldest retained chunk sits at slot
    ``count % R`` (write ``i`` lands at ``i % R``), so the chronological
    index is ``(count % R + arange(R)) % R`` — the newest ``R`` chunks
    survive, boundary chunk included."""
    R = int(spec.ring_chunks)
    count = int(np.asarray(ring.count))
    n = min(count, R)
    meta = np.asarray(ring.meta)
    uplink = np.asarray(ring.uplink)
    idx = np.arange(n) if count <= R else (count % R + np.arange(R)) % R
    return dict(
        fields=list(meta_fields(spec)),
        meta=meta[idx],
        uplink=uplink[idx],
        chunks_recorded=count,
        chunks_kept=int(n),
    )


def epoch_summary(spec: RecordSpec, drained: dict) -> dict:
    """Reduce a drained ring to the JSON-able per-epoch record the flight
    log stores (``EpochRecord.insim``): chunk-weighted scalar aggregates,
    per-uplink offered/capacity/utilization vectors, and the raw per-chunk
    table (R rows at most — small by construction)."""
    meta = np.asarray(drained["meta"], np.float64)
    uplink = np.asarray(drained["uplink"], np.float64)
    fields = list(drained["fields"])
    out = dict(schema="insim_v1",
               chunks_recorded=int(drained["chunks_recorded"]),
               chunks_kept=int(drained["chunks_kept"]))
    if meta.shape[0] == 0:
        return out
    col = {f: meta[:, i] for i, f in enumerate(fields)}
    steps = col["steps"]
    w = steps / max(float(steps.sum()), 1e-9)  # chunk-length weights
    offered = uplink[:, :, 0]
    cap = np.maximum(uplink[:, :, 1], 1e-9)
    util = np.minimum(offered / cap, 1e6)  # dead links read huge, not inf
    rnd = lambda a: np.round(np.asarray(a, np.float64), 6).tolist()
    out.update(
        steps_covered=int(steps.sum()),
        ff_chunks=int(col["ff"].sum()),
        ff_steps=int((col["ff"] * steps).sum()),
        queue_max_bytes=float(col["queue_max"].max()),
        queue_mean_bytes=float((col["queue_mean"] * w).sum()),
        cnp_pkts=float(col["cnp_pkts"].sum()),
        goodput_mean_bps=float((col["goodput_mean"] * w).sum()),
        active_subflows_max=float(col["active_subflows"].max()),
        uplink=dict(
            offered_mean_gbps=rnd((offered * w[:, None]).sum(0) / 1e9),
            offered_max_gbps=rnd(offered.max(0) / 1e9),
            cap_mean_gbps=rnd((cap * w[:, None]).sum(0) / 1e9),
            util_mean=rnd((util * w[:, None]).sum(0)),
            util_max=rnd(util.max(0)),
        ),
        chunks={f: rnd(col[f]) for f in fields},
    )
    return out
