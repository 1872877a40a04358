"""Source-ToR routing decision (paper §III.B).

Only the FIRST packet of a sub-flow is routed: it is hashed on its
five-tuple to a candidate path; if the Congestion Table marks that path
inactive, the hash is re-iterated (double hashing) until an active path is
found; if every path is inactive the original hash choice is used (the
paper: an inactive path still carries its in-flight sub-flows, it only
"restricts the entry of new flows" — when there is no alternative the flow
must enter somewhere).  All subsequent packets stick to the chosen path, so
a sub-flow's packets can never be reordered by the fabric split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hashing


def select_paths(
    src: jax.Array,
    dst: jax.Array,
    sport: jax.Array,
    dport: jax.Array,
    inactive: jax.Array,
    n_paths: int,
    max_probes: int | None = None,
    salt: int = 0,
) -> jax.Array:
    """Vectorized SeqBalance path selection for a batch of new sub-flows.

    inactive: bool[..., n_paths] — the source ToR's current inactive mask
    for each sub-flow (rows already gathered per sub-flow's source ToR).
    Returns int32[...] chosen path ids.
    """
    if max_probes is None:
        max_probes = n_paths
    h1 = hashing.hash_five_tuple(src, dst, sport, dport, salt=salt)
    h2 = hashing.hash_five_tuple(src, dst, sport, dport, salt=salt + 0x5EED)
    probes = hashing.double_hash_sequence(h1, h2, max_probes, n_paths)  # [..., K]
    # inactive[probes] without an element gather (serialised on the TPU, one
    # element per ~13 ns): compare each probe with every path id and OR the
    # matches' marks.  Paths sit on the second-minor axis, so the OR runs
    # across vregs, not lanes; XLA fuses the [..., P, K] compare into it.
    path_ids = jnp.arange(n_paths, dtype=jnp.int32)[:, None]  # [P, 1]
    probe_inactive = jnp.any(
        (probes[..., None, :] == path_ids) & inactive[..., :, None], axis=-2)
    # index of first ACTIVE probe; if none, fall back to probe 0 (= plain hash)
    first_active = jnp.argmax(~probe_inactive, axis=-1)
    any_active = jnp.any(~probe_inactive, axis=-1)
    pick = jnp.where(any_active, first_active, 0)
    # the chosen probe, recomputed as double_hash_sequence computes it
    return hashing.double_hash_probe(h1, h2, pick, n_paths)


def ecmp_paths(
    src: jax.Array, dst: jax.Array, sport: jax.Array, dport: jax.Array,
    n_paths: int, salt: int = 0,
) -> jax.Array:
    """Plain ECMP: hash once, no congestion awareness (baseline)."""
    h1 = hashing.hash_five_tuple(src, dst, sport, dport, salt=salt)
    return (h1 % jnp.uint32(n_paths)).astype(jnp.int32)
