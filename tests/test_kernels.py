"""Per-kernel allclose vs the ref.py oracles, swept over shapes/dtypes
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa, linkload as ll, ref


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("S,H,K,hd", [(128, 4, 4, 32), (256, 4, 2, 64), (256, 8, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(S, H, K, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B = 2
    q = _rand(ks[0], (B, S, H, hd), dtype)
    k = _rand(ks[1], (B, S, K, hd), dtype)
    v = _rand(ks[2], (B, S, K, hd), dtype)
    out = fa.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("window", [0, 32, 100])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, hd = 1, 256, 2, 32
    q, k, v = (_rand(ks[i], (B, S, H, hd), jnp.float32) for i in range(3))
    out = fa.flash_attention(q, k, v, window=window, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("softcap", [0.0, 10.0, 50.0])
def test_flash_attention_softcap(softcap):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, S, H, hd = 1, 128, 2, 32
    q, k, v = (_rand(ks[i], (B, S, H, hd), jnp.float32) for i in range(3))
    out = fa.flash_attention(q, k, v, softcap=softcap, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v, softcap=softcap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-5, rtol=3e-5)


def test_flash_attention_block_shape_invariance():
    """Different BlockSpec tilings must give identical results."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, S, H, hd = 1, 256, 2, 32
    q, k, v = (_rand(ks[i], (B, S, H, hd), jnp.float32) for i in range(3))
    o1 = fa.flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    o2 = fa.flash_attention(q, k, v, block_q=128, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n_sub", [1, 4])
def test_linkload_cascade_tiered_absent_hops_add_nothing(n_sub):
    """-1 fabric hops (an intra-leaf flow, a route with fewer tiers) must
    not contribute to any link: with capacity to spare, every link's
    arrival is exactly the rates of the sub-flows that name it."""
    n, hf, L = 6, 2, 10
    i = np.arange(n)[:, None]
    j = np.arange(n_sub)[None, :]
    fab = np.stack([np.where((i + j) % 2 == 0, 2 + i % 2, -1),
                    np.where(i < 3, -1, 4 + j % 2)], axis=-1)
    tx, rx = np.arange(n) % 2, 8 + np.arange(n) % 2
    rates = (1.0 + i * n_sub + j) * 1e9
    want = np.zeros(L)
    np.add.at(want, tx, rates.sum(-1))
    np.add.at(want, rx, rates.sum(-1))
    for h in range(hf):
        hop = fab[..., h]
        np.add.at(want, hop[hop >= 0], rates[hop >= 0])
    assert want[6:8].sum() == 0 and (fab == -1).any()
    args = (jnp.asarray(fab, jnp.int32), jnp.asarray(tx, jnp.int32),
            jnp.asarray(rx, jnp.int32), jnp.asarray(rates, jnp.float32),
            jnp.zeros(L), jnp.full((L,), 1e12), jnp.ones(L))
    arrival, _, _, thr = ll.linkload_cascade_tiered(
        *args, n_links=L, block_n=128, interpret=True)
    np.testing.assert_allclose(np.asarray(arrival), want, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(thr), rates.astype(np.float32))
