"""Active-window engine vs dense oracle, fused dataplane vs ref oracle,
and vmapped sweep vs serial runs (DESIGN.md §9/§10)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import linkload as ll, ref
from repro.netsim import compact, dataplane, engine, sweep, topology, workloads


def small_topo():
    return topology.leaf_spine(2, 4, 4, 100e9)


def small_trace(topo, load=0.5, dur=1.5e-3, wl="alistorage", seed=0):
    return workloads.poisson_trace(workloads.TraceConfig(
        workload=wl, load=load, duration_s=dur, n_hosts=topo.n_hosts,
        host_bw=100e9, seed=seed, hosts_per_leaf=topo.hosts_per_leaf,
        load_base_bw=2 * 4 * 100e9,
    ))


# ------------------------------------------- compacted vs dense equivalence
@pytest.mark.parametrize("scheme", engine.SCHEMES)
def test_compact_matches_dense_oracle(scheme):
    """The active-window engine is the same physics over a compacted state:
    finish times must agree with the dense oracle exactly (both engines cut
    transfers at the same DONE_EPS_BYTES threshold, so no underflow-tail
    float sensitivity is left)."""
    topo = small_topo()
    trace = small_trace(topo)
    cfg = engine.SimConfig(scheme=scheme, duration_s=6e-3)
    st_dense, _ = engine.simulate(topo, cfg, trace)
    st_comp, _ = compact.simulate_compact(topo, cfg, trace)
    fd = np.asarray(st_dense.finish)
    fc = st_comp.finish
    assert st_comp.spill_steps == 0
    np.testing.assert_array_equal(np.isfinite(fd), np.isfinite(fc))
    done = np.isfinite(fd)
    assert done.any()
    np.testing.assert_array_equal(fc[done], fd[done])
    np.testing.assert_allclose(
        float(st_comp.cnp_pkts), float(st_dense.cnp_pkts), rtol=1e-5, atol=1e-3
    )


def test_compact_window_independent():
    """With no spill, results must not depend on the window size."""
    topo = small_topo()
    trace = small_trace(topo)
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=6e-3)
    a, _ = compact.simulate_compact(topo, cfg, trace, window_slots=512)
    b, _ = compact.simulate_compact(topo, cfg, trace, window_slots=1024)
    assert a.spill_steps == 0 and b.spill_steps == 0
    np.testing.assert_array_equal(a.finish, b.finish)


def test_compact_tiny_window_spills_but_degrades_gracefully():
    """An undersized window must not lose flows: admission is delayed (NIC
    backpressure), spill_steps reports it, and nearly as many flows still
    complete as in an amply-sized run."""
    topo = small_topo()
    trace = small_trace(topo, dur=0.5e-3)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=8e-3)
    st, _ = compact.simulate_compact(topo, cfg, trace, window_slots=16)
    ample, _ = compact.simulate_compact(topo, cfg, trace, window_slots=2048)
    assert st.spill_steps > 0 and ample.spill_steps == 0
    done_small = np.isfinite(st.finish[trace.valid]).mean()
    done_ample = np.isfinite(ample.finish[trace.valid]).mean()
    assert done_small >= 0.9 * done_ample > 0.5


def test_sweep_retries_spill_to_match_oracle():
    """run_batch re-plans an undersized window until spill-free, so its
    output always matches the dense oracle."""
    topo = small_topo()
    trace = small_trace(topo)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=6e-3)
    res, _ = sweep.run_batch(topo, cfg, [trace], window_slots=64)
    assert res[0].spill_steps == 0
    assert res[0].window_slots > 64
    st_dense, _ = engine.simulate(topo, cfg, trace)
    fd = np.asarray(st_dense.finish)
    done = np.isfinite(fd)
    np.testing.assert_array_equal(res[0].finish[done], fd[done])


def test_compact_results_chunk_invariant():
    """The K-step scan chunking (and its early-exit-at-chunk-boundary
    semantics) must not change any result: skipped steps are exact no-ops."""
    import dataclasses

    topo = small_topo()
    trace = small_trace(topo)
    # 601-step horizon: no divisor near either chunk size, so both runs
    # exercise the lax.cond'd tail block too
    for dur in (6e-3, 6.01e-3):
        cfg = engine.SimConfig(scheme="seqbalance", duration_s=dur,
                               chunk_steps=32)
        odd = dataclasses.replace(cfg, chunk_steps=7)
        a, oa = compact.simulate_compact(topo, cfg, trace)
        b, ob = compact.simulate_compact(topo, odd, trace)
        np.testing.assert_array_equal(a.finish, b.finish)
        np.testing.assert_array_equal(
            np.asarray(oa.uplink_load), np.asarray(ob.uplink_load))
        np.testing.assert_array_equal(
            np.asarray(oa.goodput_total), np.asarray(ob.goodput_total))


def test_compact_sampled_uplink_outputs():
    """cfg.uplink_sample_every folds the imbalance window-averaging into
    the scan: finish times stay identical and the sampled trace equals the
    window means of the full one."""
    import dataclasses

    topo = small_topo()
    trace = small_trace(topo)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=4e-3)
    samp = dataclasses.replace(cfg, uplink_sample_every=10)
    a, oa = compact.simulate_compact(topo, cfg, trace)
    b, ob = compact.simulate_compact(topo, samp, trace)
    np.testing.assert_array_equal(a.finish, b.finish)
    up = np.asarray(oa.uplink_load)
    T = up.shape[0] // 10 * 10
    want = up[:T].reshape(-1, 10, *up.shape[1:]).mean(axis=1)
    got = np.asarray(ob.uplink_load)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e3)
    # per-step scalars stay full-resolution either way
    np.testing.assert_array_equal(
        np.asarray(oa.goodput_total), np.asarray(ob.goodput_total))
    from repro.netsim import metrics

    imb_full = metrics.throughput_imbalance(oa, sample_every=10)
    imb_samp = metrics.throughput_imbalance(ob, sample_every=10, trace_stride=10)
    np.testing.assert_allclose(imb_samp, imb_full, rtol=1e-4)


# ------------------------------------------------ fused dataplane kernels
# N = 1 (the co-sim's steered ECMP) at the shapes of the flat single-path
# routes: hops = 2 + hf, and a route with no fabric hop (hf = 1, absent)
@pytest.mark.parametrize("n,n_sub,hf,L", [(100, 4, 2, 50), (513, 1, 4, 30),
                                          (64, 2, 2, 5), (100, 1, 4, 50),
                                          (513, 1, 2, 30), (64, 1, 1, 5),
                                          (1000, 1, 4, 200), (513, 1, 2, 300)])
def test_linkload_cascade_tiered_kernel_vs_ref(n, n_sub, hf, L):
    """Interpret-mode check of the NIC-tiered kernel layout."""
    ks = jax.random.split(jax.random.PRNGKey(n), 6)
    fab = jax.random.randint(ks[0], (n, n_sub, hf), -1, L).astype(jnp.int32)
    if (n_sub, hf) == (1, 1):
        fab = jnp.full_like(fab, -1)
    tx = jax.random.randint(ks[1], (n,), 0, L).astype(jnp.int32)
    rx = jax.random.randint(ks[2], (n,), 0, L).astype(jnp.int32)
    rates = jax.random.uniform(ks[3], (n, n_sub)) * 1e9
    queue = jax.random.uniform(ks[4], (L,)) * 2e6
    cap = jnp.full((L,), 4e9)
    qmask = jnp.ones((L,)).at[:2].set(0.0)
    a1, q1, m1, t1 = ll.linkload_cascade_tiered(
        fab, tx, rx, rates, queue, cap, qmask, n_links=L, block_n=64,
        interpret=True,
    )
    a2, q2, m2, t2 = ref.linkload_cascade_tiered_ref(
        fab, tx, rx, rates, L, 400e3, 1600e3, 0.2, queue, cap, qmask, 10e-6
    )
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2), rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), rtol=1e-4, atol=1.0)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(t1), np.asarray(t2), rtol=2e-5, atol=1e-2)


def flat_cascade(links, rates, queue, capacity, queue_mask, *, n_links, kmin,
                 kmax, pmax, dt, qmax_bytes):
    """``ref.linkload_cascade_ref`` over every sub-flow's hop vector, in
    ``dataplane.cascade_nic``'s layout: a zero sentinel slot appended to
    the per-link outputs, thr in the rates' shape."""
    out = ref.linkload_cascade_ref(
        links.reshape(-1, links.shape[-1]), rates.reshape(-1), n_links, kmin,
        kmax, pmax, queue[:n_links], capacity[:n_links], queue_mask[:n_links],
        dt, qmax_bytes)
    zero = jnp.zeros((1,), jnp.float32)
    return tuple(jnp.concatenate([x, zero]) for x in out[:3]) + (
        out[3].reshape(rates.shape),)


@pytest.mark.parametrize("kind,seed", [("leaf_spine", 0), ("three_tier", 1),
                                       ("leaf_spine", 2)])
def test_cascade_nic_matches_flat(kind, seed):
    """The NIC-tiered cascade is the same physics as the flat one — only
    the summation grouping differs (pre-reduce over N on the host hops), so
    results agree to float round-off on both topology families."""
    if kind == "leaf_spine":
        topo = topology.leaf_spine(2, 4, 4, 100e9)
    else:
        topo = topology.three_tier(4, 4, 2, 3, bw_tor_agg=400e9,
                                   bw_agg_core=100e9)
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    n, N = 128, 4
    src = jax.random.randint(ks[0], (n,), 0, topo.n_hosts)
    dst = (src + 1 + jax.random.randint(ks[1], (n,), 0, topo.n_hosts - 1)) \
        % topo.n_hosts
    path = jax.random.randint(ks[2], (n, N), 0, topo.n_paths)
    links = topo.subflow_links(src[:, None], dst[:, None], path)
    tx, rx = topo.nic_links(src, dst)
    hpl = topo.hosts_per_leaf
    fab = topo.fabric_links((src // hpl)[:, None], (dst // hpl)[:, None], path)
    # the flat hop vector and the tiered builders describe the same routes
    np.testing.assert_array_equal(np.asarray(links[:, 0, 0]), np.asarray(tx))
    np.testing.assert_array_equal(np.asarray(links[:, 0, -1]), np.asarray(rx))
    np.testing.assert_array_equal(np.asarray(links[:, :, 1:-1]), np.asarray(fab))
    rates = jax.random.uniform(ks[3], (n, N)) * 50e9
    queue = jnp.zeros((topo.n_links + 1,))
    qmask = dataplane.queue_mask_for(topo)
    kw = dict(n_links=topo.n_links, kmin=400e3, kmax=1600e3, pmax=0.2,
              dt=10e-6, qmax_bytes=8e6)
    out_flat = flat_cascade(links, rates, queue, topo.capacity, qmask, **kw)
    out_nic = dataplane.cascade_nic(fab, tx, rx, rates, queue, topo.capacity,
                                    qmask, backend="xla", **kw)
    out_nic_p = dataplane.cascade_nic(fab, tx, rx, rates, queue, topo.capacity,
                                      qmask, backend="pallas_interpret", **kw)
    tols = [dict(rtol=2e-5, atol=1e-3), dict(rtol=1e-4, atol=1.0),
            dict(atol=1e-6), dict(rtol=2e-5, atol=1e-2)]
    for x, y, tol in zip(out_flat, out_nic, tols):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)
    for x, y, tol in zip(out_nic, out_nic_p, tols):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), **tol)
    pm = jnp.concatenate(
        [jax.random.uniform(key, (topo.n_links,)) * 0.3, jnp.zeros((1,))])
    ps1, pf1 = dataplane.subflow_mark_probs(links, pm, topo.n_links)
    ps2, pf2 = dataplane.subflow_mark_probs_nic(fab, tx, rx, pm, topo.n_links)
    np.testing.assert_allclose(np.asarray(ps1), np.asarray(ps2), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(pf1), np.asarray(pf2), rtol=1e-5,
                               atol=1e-7)


def test_dense_engine_uses_same_dataplane():
    """The dense oracle routes through netsim/dataplane.py: a one-step run
    must reproduce linkload_cascade_ref on its own offered load."""
    topo = small_topo()
    trace = small_trace(topo, dur=0.3e-3)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=10e-6)  # single step
    st, outs = engine.simulate(topo, cfg, trace)
    assert np.asarray(outs.uplink_load).shape[0] == 1


# --------------------------------------------------------- vmapped sweeps
@pytest.mark.parametrize("mode", ["persim", "vmap"])
def test_sweep_batch_equals_serial(mode, monkeypatch):
    """Both single-device dispatch modes (per-sim B=1 loop on cpu, one
    jitted vmap elsewhere) must reproduce the serial per-trace runs."""
    monkeypatch.setenv("REPRO_SWEEP_BATCH", mode)
    topo = small_topo()
    traces = [small_trace(topo, seed=s) for s in (0, 1, 2)]
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=4e-3)
    batch, bouts = sweep.run_batch(topo, cfg, traces)
    for i, t in enumerate(traces):
        single, souts = sweep.run_one(topo, cfg, t)
        np.testing.assert_array_equal(batch[i].finish, single.finish)
        np.testing.assert_allclose(
            np.asarray(bouts[i].max_queue), np.asarray(souts.max_queue)
        )
        np.testing.assert_allclose(
            np.asarray(bouts[i].uplink_load), np.asarray(souts.uplink_load)
        )


def test_sweep_groups_mixed_sizes():
    """Traces of very different sizes run in separate shape buckets but
    return in input order, each matching its own serial run."""
    topo = small_topo()
    big = small_trace(topo, dur=1.5e-3)
    tiny = small_trace(topo, wl="websearch", dur=0.3e-3, seed=5)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=4e-3)
    batch, _ = sweep.run_batch(topo, cfg, [tiny, big])
    for res, t in zip(batch, [tiny, big]):
        single, _ = sweep.run_one(topo, cfg, t)
        np.testing.assert_array_equal(res.finish, single.finish)


def test_sweep_jobs_match_serial():
    topo = small_topo()
    trace = small_trace(topo)
    cfgs = [engine.SimConfig(scheme=s, duration_s=4e-3) for s in ("ecmp", "letflow")]
    jobs = [(topo, c, [trace]) for c in cfgs]
    out = sweep.run_jobs(jobs, workers=2)
    for cfg, (res, _) in zip(cfgs, out):
        single, _ = sweep.run_one(topo, cfg, trace)
        np.testing.assert_array_equal(res[0].finish, single.finish)


def test_sweep_sharded_matches_single_device():
    """With >1 local device the runner dispatches pmap-of-vmap shards; the
    results must equal the single-device vmap path bit-for-bit.  CPU CI has
    one device, so the sharded path runs in a subprocess with XLA's forced
    host-device partitioning."""
    import subprocess
    import sys

    code = """
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 " + os.environ.get("XLA_FLAGS", ""))
import numpy as np
import jax
assert jax.local_device_count() == 2
from repro.netsim import engine, sweep, topology, workloads

topo = topology.leaf_spine(2, 4, 4, 100e9)
traces = [workloads.poisson_trace(workloads.TraceConfig(
    workload="alistorage", load=0.5, duration_s=0.8e-3, n_hosts=topo.n_hosts,
    host_bw=100e9, seed=s, hosts_per_leaf=topo.hosts_per_leaf,
    load_base_bw=2 * 4 * 100e9)) for s in (0, 1, 2)]
cfg = engine.SimConfig(scheme="ecmp", duration_s=2.5e-3)
sharded, souts = sweep.run_batch(topo, cfg, traces)  # B=3 padded onto 2 devices
os.environ["REPRO_SWEEP_DEVICES"] = "1"  # force the plain vmap path
single, vouts = sweep.run_batch(topo, cfg, traces)
for i in range(3):
    np.testing.assert_array_equal(sharded[i].finish, single[i].finish)
    np.testing.assert_allclose(
        np.asarray(souts[i].max_queue), np.asarray(vouts[i].max_queue))
print("SHARDED_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"),) if p]
        + [os.path.join(os.path.dirname(__file__), "..", "src")]
    )
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SHARDED_OK" in out.stdout


def test_max_concurrency_bound_sane():
    topo = small_topo()
    trace = small_trace(topo)
    arrays, _, F = compact.sort_trace(trace)
    w = compact.max_concurrency_bound(arrays[0], arrays[1], arrays[5], 100e9)
    assert 0 < w
    a = compact.max_admits_per_step(arrays[1], arrays[5], 10e-6)
    assert 1 <= a <= F
