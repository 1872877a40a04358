"""Deterministic five-tuple hashing, vectorized.

SeqBalance's source ToR hashes the *first packet* of every sub-flow on its
five-tuple to pick an uplink/path (paper §III.B).  Sub-flows of the same WQE
differ in their QP number (the Shaper gives each sub-WQE its own QP), so the
five-tuples differ and the sub-flows spread across paths — this is exactly
the "entropy multiplication" the paper describes for AI-training traffic.

We implement a murmur3-style 32-bit finalizer.  Everything is uint32 and
fully vectorized so the netsim engine can hash millions of sub-flows per
step inside jit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_C1 = jnp.uint32(0xCC9E2D51)
_C2 = jnp.uint32(0x1B873593)
_F1 = jnp.uint32(0x85EBCA6B)
_F2 = jnp.uint32(0xC2B2AE35)


def _rotl32(x: jax.Array, r: int) -> jax.Array:
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def fmix32(h: jax.Array) -> jax.Array:
    """murmur3 finalizer: avalanche a uint32."""
    h = h.astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    h = h * _F1
    h = h ^ (h >> jnp.uint32(13))
    h = h * _F2
    h = h ^ (h >> jnp.uint32(16))
    return h


def _mix_word(h: jax.Array, k: jax.Array) -> jax.Array:
    k = k.astype(jnp.uint32) * _C1
    k = _rotl32(k, 15)
    k = k * _C2
    h = h ^ k
    h = _rotl32(h, 13)
    return h * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def hash_five_tuple(
    src: jax.Array,
    dst: jax.Array,
    sport: jax.Array,
    dport: jax.Array,
    salt: jax.Array | int = 0,
) -> jax.Array:
    """Vectorized five-tuple hash -> uint32.

    ``salt`` distinguishes independent hash functions (h1 vs h2 for the
    double-hashing probe sequence, or per-switch seeds).
    """
    h = jnp.uint32(salt) * jnp.uint32(0x9E3779B9) + jnp.uint32(0x2545F491)
    h = jnp.broadcast_to(h, jnp.broadcast_shapes(jnp.shape(src), jnp.shape(dst)))
    h = _mix_word(h, jnp.asarray(src))
    h = _mix_word(h, jnp.asarray(dst))
    h = _mix_word(h, jnp.asarray(sport))
    h = _mix_word(h, jnp.asarray(dport))
    return fmix32(h ^ jnp.uint32(4 * 4))


def double_hash_probe(h1: jax.Array, h2: jax.Array, i: jax.Array, n_paths: int) -> jax.Array:
    """Probe ``i`` of the double-hash sequence, (h1 + i * (2*h2+1)) mod n_paths,
    in uint32 with its wrap-around.  ``h1``, ``h2`` and ``i`` broadcast; int32."""
    stride = h2.astype(jnp.uint32) * jnp.uint32(2) + jnp.uint32(1)
    seq = h1.astype(jnp.uint32) + jnp.asarray(i).astype(jnp.uint32) * stride
    return (seq % jnp.uint32(n_paths)).astype(jnp.int32)


def double_hash_sequence(h1: jax.Array, h2: jax.Array, n_probes: int, n_paths: int) -> jax.Array:
    """Probe sequence path_i = (h1 + i * (2*h2+1)) mod n_paths.

    The 2*h2+1 forces an odd stride so the probe sequence visits every path
    when n_paths is a power of two (classic open-addressing trick); for
    non-power-of-two path counts it still cycles well.  Shape: [..., n_probes].
    """
    i = jnp.arange(n_probes, dtype=jnp.uint32)
    return double_hash_probe(h1[..., None], h2[..., None], i, n_paths)
