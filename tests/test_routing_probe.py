"""``routing.select_paths`` against a plain NumPy loop over the probe
sequence, and a guard that the choice lowers without an element gather.

The reference reads ``inactive[probe]`` one probe at a time, in the order
the double-hash sequence visits the paths, takes the first active one and
falls back to probe 0 (the plain hash) when every probe is inactive.  The
probe sequence is computed in uint64 and reduced mod 2**32, so it holds the
program to the uint32 wrap-around of ``h1 + i * (2*h2 + 1)``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashing, routing

A, N = 32, 4


def _tuples(rng, shape):
    return tuple(rng.integers(0, 2**32, shape, dtype=np.uint64).astype(np.uint32)
                 for _ in range(4))


def _probe(h1, h2, i, n_paths):
    """Probe ``i`` in uint64, wrapped to 32 bits as the program wraps it."""
    seq = (h1.astype(np.uint64) + np.uint64(i) * (2 * h2.astype(np.uint64) + 1)) % 2**32
    return (seq % n_paths).astype(np.int64), seq


def _hashes(s5):
    h1 = np.asarray(hashing.hash_five_tuple(*s5))
    h2 = np.asarray(hashing.hash_five_tuple(*s5, salt=0x5EED))
    return h1, h2


def _reference(s5, inactive, n_paths):
    """First active probe, else probe 0, reading one probe at a time."""
    h1, h2 = _hashes(s5)
    inactive = np.broadcast_to(inactive, h1.shape + (n_paths,))
    chosen = _probe(h1, h2, 0, n_paths)[0]
    found = np.zeros(h1.shape, bool)
    for i in range(n_paths):
        p = _probe(h1, h2, i, n_paths)[0]
        active = ~np.take_along_axis(inactive, p[..., None], -1)[..., 0]
        take = active & ~found
        chosen = np.where(take, p, chosen)
        found |= take
    return chosen


@pytest.mark.parametrize("density", [0.0, 0.1, 0.5, 0.9, 1.0])
@pytest.mark.parametrize("n_paths", [8, 12, 320, 1024])
def test_select_paths_matches_probe_loop(n_paths, density):
    rng = np.random.default_rng(n_paths * 100 + int(density * 10))
    s5 = _tuples(rng, (A, N))
    # each admission rank's row of its source ToR, shared by its N sub-flows
    rows = rng.random((A, 1, n_paths)) < density
    got = routing.select_paths(*s5, jnp.broadcast_to(rows, (A, N, n_paths)), n_paths)
    assert got.dtype == jnp.int32 and got.shape == (A, N)
    np.testing.assert_array_equal(np.asarray(got), _reference(s5, rows, n_paths))
    # the probe sequences these strides take wrap past 2**32
    h1, h2 = _hashes(s5)
    assert (_probe(h1, h2, n_paths - 1, n_paths)[1]
            != h1.astype(np.uint64) + np.uint64(n_paths - 1) * (2 * h2.astype(np.uint64) + 1)).any()


@pytest.mark.parametrize("n_paths", [12, 320])
def test_select_paths_partial_cycle_all_inactive_falls_back(n_paths):
    """A non-power-of-two path count whose probe sequence misses paths: with
    every visited path inactive and the missed ones active, the sub-flow
    takes probe 0 and not a missed path."""
    rng = np.random.default_rng(n_paths)
    s5 = _tuples(rng, (4096,))
    h1, h2 = _hashes(s5)
    visited = np.zeros((4096, n_paths), bool)
    for i in range(n_paths):
        visited[np.arange(4096), _probe(h1, h2, i, n_paths)[0]] = True
    partial = np.flatnonzero(visited.sum(-1) < n_paths)[:64]
    assert partial.size > 0
    s5 = tuple(a[partial] for a in s5)
    got = np.asarray(routing.select_paths(*s5, jnp.asarray(visited[partial]), n_paths))
    np.testing.assert_array_equal(got, _probe(h1[partial], h2[partial], 0, n_paths)[0])
    np.testing.assert_array_equal(got, _reference(s5, visited[partial], n_paths))


def test_select_paths_lowers_without_gather():
    """The probe test is a compare-and-reduce: an element gather here runs
    serially on the TPU, one element per ~13 ns, at every admission step."""
    P = 320
    u32 = jax.ShapeDtypeStruct((A, N), jnp.uint32)
    inactive = jax.ShapeDtypeStruct((A, N, P), jnp.bool_)
    text = jax.jit(routing.select_paths, static_argnums=(5,)).lower(
        u32, u32, u32, u32, inactive, P).as_text()
    assert "gather" not in text
    # the guard sees the element gather it keeps out
    probes = jax.ShapeDtypeStruct((A, N, P), jnp.int32)
    assert "gather" in jax.jit(lambda x, i: jnp.take_along_axis(x, i, -1)).lower(
        inactive, probes).as_text()
