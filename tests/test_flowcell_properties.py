"""Flowcell-granularity plans + explicit reordering-cost model (ISSUE 10).

Property harness for the token-based flowcell split below the chunk and
the go-back-N reordering amplification it pays for:

  * flowcell splitting CONSERVES bytes per (round, chunk, member) and
    inherits arrival/src/dst verbatim — only sizes, flow ids and the
    ``spray`` column change;
  * ``flowcells=1`` (and ``reorder_budget`` alone) degenerates BIT-EXACTLY
    to the classic chunk-granularity trace, and ``reorder=0.0`` on an
    unsprayed trace is bit-identical to ``reorder=None``;
  * ``dataplane.reorder_gbn_factor`` is always >= 1, exactly 1 whenever a
    flow straddles a single path, monotone in the budget, and exactly 1
    under an infinite budget;
  * dense oracle == compact engine on flowcell traces with the reorder
    operand, and for the ``flowlet_timeout`` WCMP scheme;
  * the hetero 100G/400G fabric factory wires its asymmetry into the flat
    capacity vector exactly where ``nic_links``/``fabric_links`` point,
    and ECMP five-tuple steering lands every flowcell on its planned path
    under the ENGINE's own hash (``flow_constants`` -> ``ecmp_paths``);
  * with flowcells disabled the fig12 sweep and the killed-spine co-sim
    reproduce the pre-flowcell goldens exactly (seeded sha twins).

Hypothesis is an optional dependency (not in the CI image) — the ``@given``
widenings skip when it is absent; the seeded spot checks of the same
properties run unconditionally.
"""
import dataclasses
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, routing
from repro.dist import collectives
from repro.netsim import compact, dataplane, engine, sweep, topology, \
    workloads

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI image has no hypothesis
    HAVE_HYPOTHESIS = False


def _plan(n_chunks=4, n_paths=4, inactive=None, fcells=1, budget=0.0):
    dirs = tuple(1 if p % 2 == 0 else -1 for p in range(n_paths))
    return collectives.PathPlan(n_chunks=n_chunks, directions=dirs,
                                inactive=inactive, flowcells=fcells,
                                reorder_budget=budget)


def _traces(fcells, *, inactive=None, steer_paths=None, n_chunks=4,
            n_paths=4, seed=3):
    hosts = [0, 4, 8, 12]
    kw = dict(link_bw=100e9, round_gap_s=1e-4, seed=seed,
              steer_paths=steer_paths)
    base = workloads.collective_trace(
        _plan(n_chunks, n_paths, inactive), hosts, 4e6, **kw)
    fc = workloads.collective_trace(
        _plan(n_chunks, n_paths, inactive, fcells), hosts, 4e6, **kw)
    return base, fc


# --------------------------------------------- trace-level conservation
@pytest.mark.parametrize("fcells,steer", [(2, None), (3, None), (3, 4),
                                          (5, 4), (8, None)])
def test_flowcell_split_conserves_chunk_bytes(fcells, steer):
    """Cells of one (round, chunk, member) QP sum to the chunk segment and
    inherit its arrival/src/dst — the split only changes granularity."""
    base, fc = _traces(fcells, steer_paths=steer)
    assert fc.sizes.size == base.sizes.size * fcells
    np.testing.assert_allclose(fc.sizes.reshape(-1, fcells).sum(axis=1),
                               base.sizes, rtol=1e-6)
    for field in ("arrivals", "src", "dst"):
        grouped = getattr(fc, field).reshape(-1, fcells)
        assert (grouped == grouped[:, :1]).all()
        np.testing.assert_array_equal(grouped[:, 0], getattr(base, field))
    # distinct five-tuples per cell (each cell is its own QP stream)
    fid = fc.flow_id.reshape(-1, fcells)
    assert all(len(set(row.tolist())) == fcells for row in fid)
    assert np.array_equal(np.unique(base.spray), [1])
    assert np.array_equal(np.unique(fc.spray), [min(fcells, 4)])


def test_flowcell_spray_counts_active_paths_only():
    """A quarantined path shrinks the straddle count: spray is
    min(flowcells, n_active), not min(flowcells, n_paths)."""
    inactive = (False, True, False, True)
    _, fc = _traces(4, inactive=inactive, steer_paths=4)
    assert np.array_equal(np.unique(fc.spray), [2])


def test_flowcells_one_is_bit_identical():
    """flowcells=1 (with or without a reorder budget on the plan) renders
    the EXACT pre-flowcell trace — all seven arrays, bit for bit."""
    base, _ = _traces(2)
    plan = _plan(fcells=1, budget=7.0)
    twin = workloads.collective_trace(plan, [0, 4, 8, 12], 4e6,
                                      link_bw=100e9, round_gap_s=1e-4,
                                      seed=3)
    for field in ("sizes", "arrivals", "src", "dst", "flow_id", "valid",
                  "spray"):
        np.testing.assert_array_equal(getattr(base, field),
                                      getattr(twin, field))


def test_flowcell_paths_tables():
    """Cell 0 of every chunk keeps the classic round-robin (PathPlan) or
    pinned (PinnedPlan) path; later cells walk the active set only."""
    inactive = (False, True, False, False)
    plan = _plan(inactive=inactive, fcells=3)
    tbl = plan.flowcell_paths()
    assert tuple(row[0] for row in tbl) == plan.chunk_paths()
    active = {0, 2, 3}
    assert all(p in active for row in tbl for p in row)
    assert _plan(fcells=1).flowcell_paths() == tuple(
        (p,) for p in _plan().chunk_paths())
    pinned = collectives.PinnedPlan(
        n_chunks=4, directions=(1, -1, 1, -1), inactive=inactive,
        paths=(3, 0, 2, 3), flowcells=2)
    tbl2 = pinned.flowcell_paths()
    assert tuple(row[0] for row in tbl2) == (3, 0, 2, 3)
    assert all(p in active for row in tbl2 for p in row)


# ------------------------------------------- reorder-factor invariants
def _factor(topo, pq, spray, rc0, budget):
    return np.asarray(dataplane.reorder_gbn_factor(
        topo, jnp.asarray(pq), jnp.asarray(spray), jnp.asarray(rc0),
        jnp.float32(budget), mtu_bytes=4096.0, jitter_mtus=4.0,
        window_pkts=64.0))


def _factor_instance(seed, F=64):
    topo = topology.leaf_spine(4, 4, 4, 100e9)
    rng = np.random.default_rng(seed)
    pq = rng.uniform(0.0, 2e6, (F, topo.n_paths)).astype(np.float32)
    rc0 = rng.uniform(1e9, 100e9, F).astype(np.float32)
    spray = rng.integers(1, topo.n_paths + 1, F).astype(np.int32)
    return topo, pq, rc0, spray


def _check_factor_invariants(topo, pq, rc0, spray):
    amp0 = _factor(topo, pq, spray, rc0, 0.0)
    assert (amp0 >= 1.0).all()
    assert (amp0[spray <= 1] == 1.0).all()
    ones = np.ones(spray.shape, np.int32)
    assert (_factor(topo, pq, ones, rc0, 0.0) == 1.0).all()
    amp8 = _factor(topo, pq, spray, rc0, 8.0)
    assert (amp8 <= amp0 + 1e-6).all()  # budget only absorbs skew
    assert (_factor(topo, pq, spray, rc0, 1e9) == 1.0).all()


@pytest.mark.parametrize("seed", range(8))
def test_reorder_factor_invariants(seed):
    _check_factor_invariants(*_factor_instance(seed))


def test_reorder_factor_skew_monotone():
    """More inter-path skew can only cost more (same spray, same budget)."""
    topo = topology.leaf_spine(4, 4, 4, 100e9)
    F = 32
    rc0 = np.full(F, 25e9, np.float32)
    spray = np.full(F, 4, np.int32)
    flat = np.full((F, topo.n_paths), 1e6, np.float32)
    skewed = flat.copy()
    skewed[:, 0] += 4e6  # one hot path
    assert (_factor(topo, skewed, spray, rc0, 0.0)
            >= _factor(topo, flat, spray, rc0, 0.0) - 1e-6).all()


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), F=st.integers(1, 200))
    def test_reorder_factor_invariants_hyp(seed, F):
        topo, pq, rc0, spray = _factor_instance(seed, F=F)
        _check_factor_invariants(topo, pq, rc0, spray)

    @settings(max_examples=15, deadline=None)
    @given(fcells=st.integers(2, 12), n_chunks=st.integers(1, 6),
           seed=st.integers(0, 2**16), steered=st.booleans())
    def test_flowcell_split_conserves_bytes_hyp(fcells, n_chunks, seed,
                                                steered):
        base, fc = _traces(fcells, steer_paths=4 if steered else None,
                           n_chunks=n_chunks, seed=seed)
        assert fc.sizes.size == base.sizes.size * fcells
        np.testing.assert_allclose(fc.sizes.reshape(-1, fcells).sum(axis=1),
                                   base.sizes, rtol=1e-6)
        np.testing.assert_array_equal(fc.arrivals.reshape(-1, fcells)[:, 0],
                                      base.arrivals)


# -------------------------------------------------- engine equivalences
def test_reorder_zero_budget_noop_on_unsprayed_trace():
    """The reorder operand must be behaviorally invisible when no flow
    straddles paths: reorder=0.0 on an all-ones-spray trace is bit-exact
    against reorder=None (the factor is EXACTLY 1 there, not just ~1)."""
    topo = topology.leaf_spine(2, 4, 4, 100e9)
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="alistorage", load=0.6, duration_s=0.8e-3,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=5,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=2 * 4 * 100e9))
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=3e-3)
    r_none, _ = sweep.run_one(topo, cfg, trace)
    r_zero, _ = sweep.run_one(topo, cfg, trace, reorder=0.0)
    np.testing.assert_array_equal(np.asarray(r_none.finish),
                                  np.asarray(r_zero.finish))


@pytest.mark.parametrize("scheme", ["seqbalance", "ecmp"])
def test_dense_compact_agree_on_flowcell_reorder(scheme):
    """Cached-route compact step == recompute-route dense step with the
    spray column populated and the reorder operand live."""
    topo = topology.leaf_spine(2, 4, 4, 100e9)
    plan = _plan(fcells=3)
    trace = workloads.collective_trace(plan, [0, 1, 16, 17], 2e6,
                                       link_bw=100e9, round_gap_s=2e-4,
                                       seed=1, steer_paths=topo.n_paths)
    cfg = engine.SimConfig(scheme=scheme, duration_s=2e-3)
    st_dense, _ = engine.simulate(topo, cfg, trace, reorder=2.0)
    st_comp, _ = compact.simulate_compact(topo, cfg, trace, reorder=2.0)
    assert st_comp.spill_steps == 0
    fd = np.asarray(st_dense.finish)
    np.testing.assert_array_equal(np.isfinite(fd),
                                  np.isfinite(st_comp.finish))
    done = np.isfinite(fd)
    np.testing.assert_array_equal(st_comp.finish[done], fd[done])


def test_dense_compact_agree_flowlet_timeout_hetero():
    """The WCMP flowlet scheme must agree across engines on the asymmetric
    fabric (the compact engine recomputes weights from the traced capacity
    schedule; with a static topology that is the same vector)."""
    topo = topology.hetero_leaf_spine(2, 4, 4, 40e9, 160e9, n_fast_spines=1)
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="alistorage", load=0.5, duration_s=0.8e-3,
        n_hosts=topo.n_hosts, host_bw=40e9, seed=2,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=2 * 4 * 40e9))
    cfg = engine.SimConfig(scheme="flowlet_timeout", duration_s=3e-3)
    st_dense, _ = engine.simulate(topo, cfg, trace)
    st_comp, _ = compact.simulate_compact(topo, cfg, trace)
    assert st_comp.spill_steps == 0
    fd = np.asarray(st_dense.finish)
    np.testing.assert_array_equal(np.isfinite(fd),
                                  np.isfinite(st_comp.finish))
    done = np.isfinite(fd)
    np.testing.assert_array_equal(st_comp.finish[done], fd[done])


# --------------------------------------------- hetero topology factory
def test_hetero_factory_capacity_layout():
    """The 400G planes sit exactly where up[l,s]/down[s,l] say they do, and
    nic_links/fabric_links point flows at the asymmetric capacities."""
    L, S, hpl = 4, 4, 4
    topo = topology.hetero_leaf_spine(L, S, hpl, 100e9, 400e9,
                                      n_fast_spines=2)
    cap = np.asarray(topo.capacity)
    for leaf in range(L):
        for s in range(S):
            want = 400e9 if s >= S - 2 else 100e9
            assert cap[leaf * S + s] == np.float32(want)  # up[l, s]
            assert cap[L * S + s * L + leaf] == np.float32(want)  # down[s,l]
    tx, rx = (np.asarray(a) for a in topo.nic_links(0, 15))
    assert cap[int(tx)] == np.float32(100e9)  # hosts stay at slow_bw
    assert cap[int(rx)] == np.float32(100e9)
    fab_fast = np.asarray(topo.fabric_links(0, 1, S - 1))
    fab_slow = np.asarray(topo.fabric_links(0, 1, 0))
    assert (cap[fab_fast] == np.float32(400e9)).all()
    assert (cap[fab_slow] == np.float32(100e9)).all()
    # WCMP weights derived from these uplinks favor the fast planes 4:1
    w = np.asarray(baselines.wcmp_weights(
        jnp.asarray(cap[topo.uplink_ids[0]])))
    np.testing.assert_allclose(w, [0.1, 0.1, 0.4, 0.4], rtol=1e-6)
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)


def test_hetero_steering_lands_flowcells_on_planned_paths():
    """On the mixed-speed fabric, every flowcell's steered flow id must
    land on its planned path under the ENGINE's own five-tuple hash
    (flow_constants -> ecmp_paths) — the flowcell_paths round-robin,
    diversified per member, repeated every round."""
    topo = topology.hetero_leaf_spine(4, 4, 4, 100e9, 400e9,
                                      n_fast_spines=1)
    P = topo.n_paths
    fcells = 3
    inactive = (False, False, True, False)  # quarantine a slow plane
    plan = _plan(inactive=inactive, fcells=fcells)
    hosts = [0, 4, 8, 12]
    tr = workloads.collective_trace(plan, hosts, 2e6, link_bw=100e9,
                                    round_gap_s=1e-4, seed=0,
                                    steer_paths=P)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=1e-3)
    fc = engine.flow_constants(topo, cfg, jnp.asarray(tr.sizes),
                               jnp.asarray(tr.src), jnp.asarray(tr.dst),
                               jnp.asarray(tr.flow_id))
    realized = np.asarray(routing.ecmp_paths(*fc.f5, P))
    active = [p for p in range(P) if not inactive[p]]
    n, n_chunks, A = len(hosts), plan.n_chunks, len(active)
    per_round = [active[(i * n_chunks + c + j) % A]
                 for c in range(n_chunks) for i in range(n)
                 for j in range(fcells)]
    expect = np.asarray(per_round * (2 * (n - 1)), np.int32)
    np.testing.assert_array_equal(realized, expect)
    assert 2 not in realized  # the quarantined plane carries nothing


@pytest.mark.parametrize("fabric", ["symmetric", "hetero"])
def test_flowcell_spray_wins_only_when_reordering_is_free(fabric):
    """The paper's no-reordering motivation, quantified on a fabric-bound
    ring (100G hosts over a 25G fabric, 8 members, 16 MB in 4 chunks):
    spraying each chunk over every path beats SeqBalance's censored p99
    only while reordering is free (reorder=None); at a strict go-back-N
    budget (0 packets) it loses to SeqBalance."""
    from repro.dist import cosim
    from repro.netsim import metrics

    if fabric == "symmetric":
        topo = topology.leaf_spine(4, 4, 4, 25e9, host_bw=100e9)
    else:
        topo = topology.hetero_leaf_spine(4, 4, 4, 25e9, 100e9,
                                          n_fast_spines=1, host_bw=100e9)
    dur = 10e-3
    hosts = cosim.ring_hosts(topo, 8)
    kw = dict(link_bw=25e9, round_gap_s=3e-4, seed=0,
              steer_paths=topo.n_paths)
    trace = workloads.collective_trace(_plan(), hosts, 16e6, **kw)
    sprayed = workloads.collective_trace(_plan(fcells=4), hosts, 16e6, **kw)

    def p99(scheme, tr, reorder):
        res, _ = sweep.run_one(
            topo, engine.SimConfig(scheme=scheme, duration_s=dur), tr,
            reorder=reorder)
        f, _ = metrics.fct_samples(res, tr, horizon_s=dur)
        return float(np.percentile(f, 99))

    seq = p99("seqbalance", trace, None)
    assert p99("ecmp", sprayed, None) < seq
    assert p99("ecmp", sprayed, 0.0) > seq


# ------------------------------------- degenerate sha-golden twin pins
def test_flowcell_disabled_fig12_bit_identical():
    """The fig12 sweep with the flowcell plumbing in its default state
    (reorder=None) reproduces the pre-flowcell golden exactly — the 7th
    trace column and the operand gating must be dead code there."""
    from tests.test_adaptive_dt import FIG12_GOLD, _fig12_trace

    topo = topology.sim_2tier()
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=10e-3,
                           uplink_sample_every=10)
    res, _ = sweep.run_one(topo, cfg, _fig12_trace(topo), reorder=None)
    f = np.asarray(res.finish)
    sha, fsum, cnp = FIG12_GOLD["seqbalance"]
    assert hashlib.sha1(f.tobytes()).hexdigest()[:16] == sha
    assert float(f[np.isfinite(f)].sum()) == fsum
    assert float(res.cnp_pkts) == cnp


def test_flowcell_disabled_cosim_bit_identical():
    """Killed-spine co-sim with flowcells=1 / reorder_budget=None passed
    EXPLICITLY (plans stamped, kwargs threaded) matches the pre-flowcell
    golden epoch for epoch."""
    from repro.dist import cosim
    from tests.test_adaptive_dt import COSIM_GOLD

    topo = topology.leaf_spine(4, 4, 4, 100e9)
    hosts = cosim.ring_hosts(topo, 8)
    h = cosim.run_cosim(
        topo, hosts, 4e6, scheme="seqbalance", epochs=4, phi_steps=2,
        n_chunks=4, seed=0, flowcells=1, reorder_budget=None,
        faults=(cosim.kill_spine(topo, 2, epoch=1, recover_epoch=3),))
    assert [r.fct_p99_s for r in h.records] == COSIM_GOLD["p99"]
    assert [r.fct_p50_s for r in h.records] == COSIM_GOLD["p50"]
    assert [r.quarantined for r in h.records] == COSIM_GOLD["quarantined"]
    assert h.convergence_epoch(1) == COSIM_GOLD["conv"]


def test_flowcell_spec_key_only_when_used(tmp_path):
    """Journal compatibility: the ``flowcell`` spec entry exists only when
    the feature is on — pre-flowcell journals keep matching."""
    import json

    from repro.dist import cosim

    topo = topology.leaf_spine(2, 4, 2, 100e9)
    hosts = cosim.ring_hosts(topo, 4)
    j_off = tmp_path / "off.jsonl"
    j_on = tmp_path / "on.jsonl"
    cosim.run_cosim(topo, hosts, 1e6, scheme="ecmp", epochs=1, n_chunks=2,
                    journal=str(j_off))
    cosim.run_cosim(topo, hosts, 1e6, scheme="ecmp", epochs=1, n_chunks=2,
                    journal=str(j_on), flowcells=2, reorder_budget=4.0)
    head_off = json.loads(j_off.read_text().splitlines()[0])
    head_on = json.loads(j_on.read_text().splitlines()[0])
    assert "flowcell" not in head_off["spec"]
    assert head_on["spec"]["flowcell"] == dict(flowcells=2,
                                               reorder_budget=4.0)
