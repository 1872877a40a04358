"""Event-driven adaptive dt (DESIGN.md §15): seeded-twin bit-identity of
the fixed-dt path, adaptive-vs-fixed tolerance on the sparse collective
workload, the chunk/event-grid planner, DCQCN closed-form fast-forward,
and the executable-cache build-count contract."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.netsim import compact, dcqcn, engine, faults, sweep, topology, \
    workloads


# ------------------------------------------------- seeded-twin goldens
# Captured on the PR 7 tree (before any adaptive-dt code existed): the
# fig12-style sweep and the killed-spine co-sim must reproduce these
# EXACTLY with adaptive=False — the fixed-dt step loop is untouched.
FIG12_GOLD = {
    "seqbalance": ("97c5e5a8c9da4589", 78.61827087402344, 1076029.875),
    "ecmp": ("1ee9c2ede7c595b6", 75.699951171875, 473117.84375),
    "letflow": ("1ee9c2ede7c595b6", 75.699951171875, 473117.84375),
}
COSIM_GOLD = dict(
    p99=[8.999995770864189e-05, 0.0019099999917671084,
         0.0019099999917671084, 8.999995770864189e-05],
    p50=[4.999998782295734e-05, 0.0003299999807495624,
         0.0003599999472498894, 4.999998782295734e-05],
    quarantined=[(), (), (2,), (2,)],
    conv=3,
)


def _fig12_trace(topo):
    fabric = topo.n_leaf * topo.n_paths * 100e9
    return workloads.poisson_trace(workloads.TraceConfig(
        workload="alistorage", load=0.8, duration_s=2.5e-3,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=1,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=fabric))


@pytest.mark.parametrize("scheme", sorted(FIG12_GOLD))
def test_fixed_dt_bit_identical_fig12(scheme):
    topo = topology.sim_2tier()
    cfg = engine.SimConfig(scheme=scheme, duration_s=10e-3,
                           uplink_sample_every=10)
    res, _ = sweep.run_one(topo, cfg, _fig12_trace(topo))
    f = np.asarray(res.finish)
    fin = f[np.isfinite(f)]
    sha, fsum, cnp = FIG12_GOLD[scheme]
    assert hashlib.sha1(f.tobytes()).hexdigest()[:16] == sha
    assert float(fin.sum()) == fsum
    assert float(res.cnp_pkts) == cnp


def test_fixed_dt_bit_identical_cosim():
    from repro.dist import cosim

    topo = topology.leaf_spine(4, 4, 4, 100e9)
    hosts = cosim.ring_hosts(topo, 8)
    h = cosim.run_cosim(
        topo, hosts, 4e6, scheme="seqbalance", epochs=4, phi_steps=2,
        n_chunks=4, seed=0,
        faults=(cosim.kill_spine(topo, 2, epoch=1, recover_epoch=3),))
    assert [r.fct_p99_s for r in h.records] == COSIM_GOLD["p99"]
    assert [r.fct_p50_s for r in h.records] == COSIM_GOLD["p50"]
    assert [r.quarantined for r in h.records] == COSIM_GOLD["quarantined"]
    assert h.convergence_epoch(1) == COSIM_GOLD["conv"]
    assert all(r.ff_steps == 0 for r in h.records)  # adaptive off


# ------------------------------------- adaptive vs fixed-dt (tolerance)
def _collective(topo, gap=800e-6, size=4e6, seed=0):
    from repro.dist import collectives, cosim

    plan = collectives.PathPlan(n_chunks=4, directions=(1, -1, 1, -1))
    hosts = cosim.ring_hosts(topo, 8)
    return workloads.collective_trace(plan, hosts, size, link_bw=100e9,
                                      round_gap_s=gap, seed=seed,
                                      steer_paths=topo.n_paths)


def _twin(topo, cfg, trace):
    res_f, _ = sweep.run_one(topo, cfg, trace)
    res_a, _ = sweep.run_one(topo, dataclasses.replace(cfg, adaptive=True),
                             trace)
    return res_f, res_a


def test_adaptive_fast_forwards_sparse_collective():
    """Compute gaps between all-reduce rounds are quiescent: the adaptive
    engine must skip them in closed form (ff_steps > 0) and still land
    every finish time and CNP count exactly."""
    topo = topology.leaf_spine(4, 4, 4, 100e9)
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=14e-3,
                           uplink_sample_every=10)
    res_f, res_a = _twin(topo, cfg, _collective(topo))
    assert int(res_a.ff_steps) > 0
    assert int(res_f.ff_steps) == 0
    assert np.array_equal(np.asarray(res_f.finish), np.asarray(res_a.finish))
    assert float(res_f.cnp_pkts) == float(res_a.cnp_pkts)


def test_adaptive_dense_trace_is_exact_noop():
    """Event-dense Poisson traffic: every chunk holds arrivals/finishes,
    so the predicate must never fire and the outputs stay bit-identical
    (same executable semantics, different program)."""
    topo = topology.leaf_spine(2, 4, 4, 100e9)
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="alistorage", load=0.6, duration_s=1.2e-3,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=0,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=2 * 4 * 100e9))
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=4e-3,
                           uplink_sample_every=10)
    res_f, res_a = _twin(topo, cfg, trace)
    assert np.array_equal(np.asarray(res_f.finish), np.asarray(res_a.finish))
    assert float(res_f.cnp_pkts) == float(res_a.cnp_pkts)


def test_adaptive_uplink_outputs_match():
    """The fast-forward path emits its uplink slab analytically at sample
    granularity; window averages of a frozen cascade must equal the
    scanned averages."""
    topo = topology.leaf_spine(4, 4, 4, 100e9)
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=14e-3,
                           uplink_sample_every=10)
    trace = _collective(topo)
    (_, outs_f), (_, outs_a) = (sweep.run_one(topo, cfg, trace),
                                sweep.run_one(
                                    topo,
                                    dataclasses.replace(cfg, adaptive=True),
                                    trace))
    uf, ua = np.asarray(outs_f.uplink_load), np.asarray(outs_a.uplink_load)
    assert uf.shape == ua.shape
    np.testing.assert_allclose(ua, uf, rtol=1e-5, atol=1.0)
    np.testing.assert_allclose(np.asarray(outs_a.max_queue),
                               np.asarray(outs_f.max_queue),
                               rtol=1e-5, atol=1.0)


def test_adaptive_property_delivered_bytes_conserved():
    """Hypothesis sweep over gap/size/seed: adaptive and fixed dt finish
    the same flows, conserve total delivered bytes exactly, and every
    per-flow completion diverges by at most one dt step (the closed-form
    linear decrement vs the iterated f32 sum)."""
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    topo = topology.leaf_spine(4, 4, 4, 100e9)
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=14e-3,
                           uplink_sample_every=10)

    @settings(max_examples=4, deadline=None)
    @given(gap=st.sampled_from([400e-6, 800e-6, 1200e-6]),
           size=st.sampled_from([2e6, 4e6, 6e6]),
           seed=st.integers(min_value=0, max_value=3))
    def prop(gap, size, seed):
        trace = _collective(topo, gap=gap, size=size, seed=seed)
        res_f, res_a = _twin(topo, cfg, trace)
        f = np.asarray(res_f.finish)
        a = np.asarray(res_a.finish)
        valid = np.asarray(trace.valid, bool)
        done_f = np.isfinite(f) & valid
        done_a = np.isfinite(a) & valid
        assert np.array_equal(done_f, done_a)
        sizes = np.asarray(trace.sizes)
        assert float(sizes[done_f].sum()) == float(sizes[done_a].sum())
        assert np.all(np.abs(f[done_f] - a[done_f]) <= cfg.dt + 1e-9)

    prop()


# --------------------------------------------- planner: chunks + grid
def test_plan_chunks_tail_folds_away():
    """K must be a sample-window multiple, and a tail (second compiled
    scan body) may only survive when the sample window itself does not
    divide the horizon."""
    for chunk, s, n in [(32, 10, 1000), (32, 1, 1000), (20, 10, 1400),
                        (32, 8, 1000), (7, 3, 21), (32, 10, 995),
                        (16, 5, 1005), (32, 32, 64), (1, 1, 7)]:
        cfg = engine.SimConfig(chunk_steps=chunk, uplink_sample_every=s)
        K, n_chunks, tail = compact.plan_chunks(cfg, n)
        assert K % s == 0 and K >= 1
        assert K * n_chunks + tail == n
        if n % s == 0:
            assert tail == 0, (chunk, s, n, K, tail)


def test_event_grid_boundaries():
    cfg = engine.SimConfig(dt=10e-6, uplink_sample_every=10)
    arrivals = np.array([0.0, 95e-6, 1e-3, 2.0])  # last beyond horizon
    grid = compact.event_grid(cfg, 500, arrivals=arrivals,
                              valid=np.array([1, 1, 1, 1], bool),
                              cap_seg_steps=125)
    assert grid[0] == 0 and grid[-1] == 500
    for step in (10, 100, 125, 250):  # arrival ceils + seg + sample edges
        assert step in grid
    assert np.all(np.diff(grid) > 0)


def test_seg_steps_chunk_alignment():
    ev = faults.LinkFlap(links=(0,), start_epoch=1, end_epoch=2,
                         duty=0.5, scale=0.0)
    camp = faults.FaultCampaign(events=(ev,), n_segments=8)
    # PR 6 pins (align default): unchanged
    assert camp.seg_steps(100) == 13 and camp.seg_steps(3) == 1
    assert camp.seg_steps(100, align=20) == 20
    assert camp.seg_steps(1000, align=20) == 140  # ceil(125 -> 140)
    assert camp.seg_steps(1000, align=1) == 125


# ------------------------------------------- DCQCN closed-form forward
@pytest.mark.parametrize("n_steps", [1, 5, 6, 17, 64])
def test_dcqcn_fast_forward_matches_iterated_steps(n_steps):
    """With no marks and rc == rt == line rate, ``dcqcn.fast_forward``
    must reproduce n iterated ``dcqcn.step`` calls: alpha decay, CNP/rate
    timers (including periodic rate-timer firings), recovery stage."""
    p = dcqcn.DCQCNParams()
    line = 100e9
    dt = 10e-6
    st0 = dcqcn.init_state((3,), line)
    st0 = st0._replace(t_since_rate=jnp.array([0.0, 30e-6, 54e-6]),
                       recovery_stage=jnp.array([0.0, 2.0, 7.0]))
    active = jnp.array([True, True, False])
    it = st0
    for _ in range(n_steps):
        new, _ = dcqcn.step(it, jnp.zeros(3), active, dt, line, p)
        # inactive sub-flows hold state like the compact engine's masked
        # update (dcqcn_phase applies the step only where active)
        it = type(st0)(*(jnp.where(active, a, b) for a, b in zip(new, it)))
    ff = dcqcn.fast_forward(st0, active, n_steps, dt, p)
    for name in st0._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(ff, name)), np.asarray(getattr(it, name)),
            rtol=2e-4, atol=1e-9, err_msg=f"{name} @ n={n_steps}")


def test_queue_fast_forward_matches_integrated():
    """Closed-form clip trajectory == n iterated integrate_queue steps
    under a frozen arrival/capacity vector."""
    from repro.netsim import dataplane

    rng = np.random.default_rng(0)
    L = 16
    q0 = jnp.asarray(rng.uniform(0, 2e6, L + 1).astype(np.float32))
    arr = jnp.asarray(rng.uniform(0, 2e11, L + 1).astype(np.float32))
    cap = jnp.full((L + 1,), 1e11, jnp.float32)
    qmask = jnp.ones((L + 1,), jnp.float32)
    q_ff, mq = dataplane.queue_fast_forward(
        q0, arr, cap, qmask, dt=10e-6, n_steps=9, qmax_bytes=8e6, n_links=L)
    q = q0
    mq_it = []
    for _ in range(9):
        q = jnp.clip(q + (arr - cap) * (10e-6 / 8.0), 0.0, 8e6) * qmask
        mq_it.append(float(jnp.max(q[:L])))
    np.testing.assert_allclose(np.asarray(q_ff), np.asarray(q), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(mq), np.asarray(mq_it), rtol=1e-6)


# --------------------------------------------- executable-cache builds
def test_cache_build_counts_pinned():
    """One executable per (cfg, shape): the second dispatch of the same
    sim must add zero builds, and toggling ``adaptive`` compiles its own
    program without evicting the first."""
    topo = topology.leaf_spine(2, 4, 4, 100e9)
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="websearch", load=0.3, duration_s=0.6e-3,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=3,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=2 * 4 * 100e9))
    cfg = engine.SimConfig(scheme="ecmp", duration_s=2e-3,
                           uplink_sample_every=10)
    sweep.clear_cache()
    sweep.run_one(topo, cfg, trace)
    b1 = sweep.cache_stats()["builds"]
    assert b1 == 1
    sweep.run_one(topo, cfg, trace)
    assert sweep.cache_stats()["builds"] == b1
    assert sweep.cache_stats()["hits"] >= 1
    sweep.run_one(topo, dataclasses.replace(cfg, adaptive=True), trace)
    b2 = sweep.cache_stats()["builds"]
    assert b2 == b1 + 1
    sweep.run_one(topo, dataclasses.replace(cfg, adaptive=True), trace)
    sweep.run_one(topo, cfg, trace)
    assert sweep.cache_stats()["builds"] == b2


# ----------------------------------------------- quiescence profiling
def test_quiescence_profile_smoke():
    """Replay the fixed-dt run chunk by chunk through the production
    shapes and evaluate the adaptive predicate at every boundary, without
    fast-forwarding: the sparse collective's compute gaps make some
    chunks quiescent, and the first chunk (arrivals at step 0) is not."""
    topo = topology.leaf_spine(4, 4, 4, 100e9)
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=14e-3,
                           uplink_sample_every=10)
    arrays, _, F = compact.sort_trace(_collective(topo))
    W, A = compact.plan_single_window(topo, cfg, arrays, F)
    _, step_fn, phases = compact.build_compact_sim(
        topo, cfg, tuple(jnp.asarray(a) for a in arrays), W, F, A)
    n_steps = int(round(cfg.duration_s / cfg.dt))
    K, n_chunks, _ = compact.plan_chunks(cfg, n_steps)
    assert K % cfg.uplink_sample_every == 0 and K * n_chunks >= n_steps

    @jax.jit
    def replay(st):
        def one(st, _):
            quiet = phases["quiesce"](st, K)
            return jax.lax.scan(step_fn, st, None, length=K)[0], quiet

        return jax.lax.scan(one, st, None, length=n_chunks)[1]

    quiet = np.asarray(replay(compact.init_compact_state(topo, cfg, W, F)))
    assert quiet.shape == (n_chunks,) and not quiet[0]
    assert 0.0 < quiet.mean() < 1.0
