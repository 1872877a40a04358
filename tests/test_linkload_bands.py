"""Per-pass link-id bands of the tiered cascade kernel.

``Topology.bands`` gives each hop of the NIC-tiered view (host tx, the
fabric hops, host rx) the range of link ids it can emit, and
``kernels/linkload.py::linkload_cascade_tiered`` builds each pass's one-hot
over that range only.  The kernel's outputs must not change: every output
column sums the same terms in the same order, so banded and unbanded runs
are compared bit for bit.  That holds only if every id a hop can emit lies
in its band, which is checked here exhaustively at the paper's sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import linkload as ll, ref
from repro.netsim import dataplane, topology

# the tolerances of the existing kernel-vs-reference tests
# (tests/test_netsim_compact.py): arrival, queue, mark, thr
REF_TOL = [dict(rtol=2e-5, atol=1e-3), dict(rtol=1e-4, atol=1.0),
           dict(rtol=0.0, atol=1e-6), dict(rtol=2e-5, atol=1e-2)]

# every topology the repo builds, by its factory.  The reduced fabrics hold
# over 128 links, so an absent hop's sentinel id (n_links) lies past the
# padded one-hot of every fabric band and must gather a factor of 1 by
# itself, as it does at the paper's sizes
FACTORIES = {
    "leaf_spine": lambda: topology.leaf_spine(6, 8, 8, 100e9),
    "three_tier": lambda: topology.three_tier(8, 4, 4, 8),
    "testbed_symmetric": topology.testbed_symmetric,
    "testbed_asymmetric": topology.testbed_asymmetric,
    "sim_2tier": topology.sim_2tier,  # Fig. 12
    "hetero_leaf_spine": lambda: topology.hetero_leaf_spine(6, 8, 8),
    "three_tier_fig14": topology.three_tier,  # Fig. 14
}


def _pass_ids(topo):
    """Every id each pass can emit, over all (src, dst, path): NIC hops
    depend on the hosts only, fabric hops on the leaves and the path."""
    h = jnp.arange(topo.n_hosts)
    tx, rx = topo.nic_links(h[:, None], h[None, :])
    leaf = jnp.arange(topo.n_leaf)
    fab = topo.fabric_links(leaf[:, None, None], leaf[None, :, None],
                            jnp.arange(topo.n_paths)[None, None, :])
    fab = np.asarray(fab).reshape(-1, topo.n_fabric_hops)
    return [np.asarray(tx).ravel()] + list(fab.T) + [np.asarray(rx).ravel()]


@pytest.mark.parametrize("name", sorted(FACTORIES))
def test_bands_partition_links(name):
    topo = FACTORIES[name]()
    assert len(topo.bands) == topo.n_fabric_hops + 2
    covered = np.zeros(topo.n_links, int)
    for lo, hi in topo.bands:
        assert 0 <= lo < hi <= topo.n_links
        covered[lo:hi] += 1
    np.testing.assert_array_equal(covered, 1)


@pytest.mark.parametrize("name", sorted(FACTORIES))
def test_every_hop_id_lies_in_its_band(name):
    topo = FACTORIES[name]()
    for k, (ids, (lo, hi)) in enumerate(zip(_pass_ids(topo), topo.bands)):
        off = ids[(ids != -1) & ((ids < lo) | (ids >= hi))]
        assert off.size == 0, (k, (lo, hi), np.unique(off)[:10])
        # the hop reaches every link of its band
        np.testing.assert_array_equal(np.unique(ids[ids >= 0]), np.arange(lo, hi))


@pytest.mark.parametrize("fabric,n_sub,want", [
    ("sim_2tier", 4, (2560, 10240)),  # Fig. 12, SeqBalance
    ("three_tier", 4, (15872, 78336)),  # Fig. 14, SeqBalance
    ("three_tier", 1, (5120, 26112)),  # the co-sim's steered ECMP
])
def test_onehot_rows_paper_fabrics(fabric, n_sub, want):
    topo = getattr(topology, fabric)()
    hf, L = topo.n_fabric_hops, topo.n_links
    assert ll.onehot_rows(topo.bands, n_sub, hf, L) == want
    rows, full = ll.onehot_rows(None, n_sub, hf, L)
    assert rows == full == want[1]


def _inputs(topo, n_sub, n, seed):
    """Routes of ``n`` flows (a quarter of them inside one leaf, so with
    absent fabric hops), rates, queues, and one fabric switch's links at
    capacity 0, as a killed switch leaves them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    hpl = topo.hosts_per_leaf
    src = jax.random.randint(ks[0], (n,), 0, topo.n_hosts)
    other = jax.random.randint(ks[1], (n,), 0, topo.n_hosts)
    same_leaf = (src // hpl) * hpl + (src + 1) % hpl
    dst = jnp.where(jnp.arange(n) % 4 == 0, same_leaf, other)
    path = jax.random.randint(ks[2], (n, n_sub), 0, topo.n_paths)
    tx, rx = topo.nic_links(src, dst)
    fab = topo.fabric_links((src // hpl)[:, None], (dst // hpl)[:, None], path)
    assert bool(jnp.any(fab < 0)), "no intra-leaf flow drawn"
    L = topo.n_links
    killed = jnp.asarray(topology.spine_links(topo, 1))
    cap = topo.capacity[:L].at[killed].set(0.0)
    rates = jax.random.uniform(ks[3], (n, n_sub)) * 100e9 / n_sub
    queue = jax.random.uniform(ks[4], (L,)) * 2e6
    qmask = dataplane.queue_mask_for(topo)[:L]
    return fab, tx, rx, rates, queue, cap, qmask


@pytest.mark.parametrize("n_sub", [1, 4])
@pytest.mark.parametrize("name", ["leaf_spine", "hetero_leaf_spine", "three_tier"])
def test_banded_kernel_bit_identical(name, n_sub):
    """n = 300 pads the last 128-flow tile; killed links scale to 0."""
    topo = FACTORIES[name]()
    args = _inputs(topo, n_sub, 300, seed=n_sub)
    L = topo.n_links
    kw = dict(n_links=L, block_n=128, interpret=True)
    banded = ll.linkload_cascade_tiered(*args, bands=topo.bands, **kw)
    full = ll.linkload_cascade_tiered(*args, **kw)
    for b, f in zip(banded, full):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(f))
    fab, tx, rx, rates, queue, cap, qmask = args
    want = ref.linkload_cascade_tiered_ref(fab, tx, rx, rates, L, 400e3, 1600e3,
                                           0.2, queue, cap, qmask, 10e-6)
    for b, w, tol in zip(banded, want, REF_TOL):
        np.testing.assert_allclose(np.asarray(b), np.asarray(w), **tol)
    # the killed links deliver nothing past them
    assert float(jnp.max(jnp.where(jnp.isin(fab, jnp.asarray(
        topology.spine_links(topo, 1))).any(-1), banded[3], 0.0))) == 0.0


def test_cascade_nic_passes_bands():
    """The engines' entry point: bands in, the same four outputs out."""
    topo = FACTORIES["three_tier"]()
    fab, tx, rx, rates, queue, cap, qmask = _inputs(topo, 4, 200, seed=7)
    L = topo.n_links
    pad = lambda x: jnp.concatenate([x, jnp.zeros((1,), x.dtype)])
    kw = dict(n_links=L, kmin=400e3, kmax=1600e3, pmax=0.2, dt=10e-6,
              qmax_bytes=8e6, backend="pallas_interpret")
    args = (fab, tx, rx, rates, pad(queue), pad(cap).at[L].set(1e30), pad(qmask))
    banded = dataplane.cascade_nic(*args, bands=topo.bands, **kw)
    full = dataplane.cascade_nic(*args, **kw)
    for b, f in zip(banded, full):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(f))
