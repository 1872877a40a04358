"""Hypothesis property tests for the perf-critical equivalences (ISSUE 3):

  * NIC-tiered cascade == flat cascade on arbitrary random "topologies"
    (random link ids, hop-absence masks, rates, queues) — the tiered
    layout is a pure regrouping of the same segment-sums;
  * tiered Pallas kernel (interpret mode) == its jnp oracle on the same
    random instances;
  * cached-route compact step == recompute-route dense step: the admit-time
    SlotCache must be behaviorally invisible (routes are immutable once
    placed), so finish times agree exactly across random traces.

Hypothesis is an optional dependency (not in the CI image) — these skip
when it is absent; seeded spot checks of the same properties run
unconditionally in tests/test_netsim_compact.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")

from hypothesis import example, given, settings, strategies as st  # noqa: E402

from repro.kernels import linkload as ll, ref  # noqa: E402
from repro.netsim import compact, dataplane, engine, topology, workloads  # noqa: E402
from tests.test_netsim_compact import flat_cascade  # noqa: E402


def _random_instance(seed, n, n_sub, hf, L):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    fab = jax.random.randint(ks[0], (n, n_sub, hf), -1, L).astype(jnp.int32)
    tx = jax.random.randint(ks[1], (n,), 0, L).astype(jnp.int32)
    rx = jax.random.randint(ks[2], (n,), 0, L).astype(jnp.int32)
    rates = jax.random.uniform(ks[3], (n, n_sub)) * 1e9
    queue = jax.random.uniform(ks[4], (L + 1,)) * 2e6
    queue = queue.at[L].set(0.0)
    cap = jnp.concatenate([jnp.full((L,), 4e9), jnp.full((1,), 1e30)])
    qmask = jnp.ones((L + 1,)).at[:2].set(0.0).at[L].set(0.0)
    return fab, tx, rx, rates, queue, cap, qmask


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(1, 200),
    n_sub=st.integers(1, 6),
    hf=st.integers(1, 4),
    L=st.integers(3, 60),
)
def test_tiered_cascade_equals_flat(seed, n, n_sub, hf, L):
    fab, tx, rx, rates, queue, cap, qmask = _random_instance(seed, n, n_sub, hf, L)
    links = jnp.concatenate(
        [jnp.broadcast_to(tx[:, None, None], (n, n_sub, 1)), fab,
         jnp.broadcast_to(rx[:, None, None], (n, n_sub, 1))], axis=-1)
    kw = dict(n_links=L, kmin=400e3, kmax=1600e3, pmax=0.2, dt=10e-6,
              qmax_bytes=8e6)
    out_flat = flat_cascade(links, rates, queue, cap, qmask, **kw)
    out_nic = dataplane.cascade_nic(fab, tx, rx, rates, queue, cap, qmask,
                                    backend="xla", **kw)
    tols = [dict(rtol=2e-5, atol=1e-3), dict(rtol=1e-4, atol=1.0),
            dict(atol=1e-6), dict(rtol=2e-5, atol=1e-2)]
    for x, y, tol in zip(out_flat, out_nic, tols):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)
    pm = jnp.concatenate(
        [jax.random.uniform(jax.random.PRNGKey(seed), (L,)) * 0.5,
         jnp.zeros((1,))])
    ps1, pf1 = dataplane.subflow_mark_probs(links, pm, L)
    ps2, pf2 = dataplane.subflow_mark_probs_nic(fab, tx, rx, pm, L)
    np.testing.assert_allclose(np.asarray(ps1), np.asarray(ps2),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(pf1), np.asarray(pf2),
                               rtol=1e-5, atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    n=st.integers(1, 150),
    n_sub=st.integers(1, 4),
    hf=st.integers(1, 4),
    L=st.integers(3, 50),
)
@example(seed=0, n=1, n_sub=1, hf=1, L=3)  # smallest instance: one flow, one hop
def test_tiered_kernel_interpret_equals_ref(seed, n, n_sub, hf, L):
    fab, tx, rx, rates, queue, cap, qmask = _random_instance(seed, n, n_sub, hf, L)
    a1, q1, m1, t1 = ll.linkload_cascade_tiered(
        fab, tx, rx, rates, queue[:L], cap[:L], qmask[:L], n_links=L,
        block_n=64, interpret=True,
    )
    a2, q2, m2, t2 = ref.linkload_cascade_tiered_ref(
        fab, tx, rx, rates, L, 400e3, 1600e3, 0.2, queue[:L], cap[:L],
        qmask[:L], 10e-6,
    )
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=1e-4, atol=1.0)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), rtol=2e-5, atol=1e-2)


@settings(max_examples=4, deadline=None)
@given(
    seed=st.integers(0, 2**16),
    load=st.sampled_from([0.4, 0.7]),
    scheme=st.sampled_from(engine.SCHEMES),
)
@example(seed=0, load=0.7, scheme="drill")  # the known DRILL divergence
def test_cached_route_step_equals_recompute(seed, load, scheme):
    """The compact engine snapshots routes/link-ids at admission; the dense
    oracle re-derives them from the topology every step.  Random traces
    must finish at identical times (spill-free => bit-exact)."""
    topo = topology.leaf_spine(2, 4, 4, 100e9)
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="alistorage", load=load, duration_s=0.8e-3,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=seed,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=2 * 4 * 100e9,
    ))
    cfg = engine.SimConfig(scheme=scheme, duration_s=3e-3)
    st_dense, _ = engine.simulate(topo, cfg, trace)
    st_comp, _ = compact.simulate_compact(topo, cfg, trace)
    assert st_comp.spill_steps == 0
    fd = np.asarray(st_dense.finish)
    np.testing.assert_array_equal(np.isfinite(fd), np.isfinite(st_comp.finish))
    done = np.isfinite(fd)
    np.testing.assert_array_equal(st_comp.finish[done], fd[done])
