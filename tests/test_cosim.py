"""Multi-epoch co-simulation driver (dist.cosim) + the traced-capacity
sweep contract it rides on:

  * capacity as a traced sweep operand is bit-identical to the baked-in
    constant and reuses ONE compiled program across capacity changes;
  * collective_trace's ECMP steering pins QPs onto their planned fabric
    paths exactly as the engine's own five-tuple hash will route them
    (drift between the two would silently unbind every plan);
  * the killed-spine round trip on a forced 8-device host platform:
    failure -> quarantine/reroute within an epoch -> recovery -> phi
    release -> plan churn settles to zero.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ------------------------------------------------------- traced capacity
def test_traced_capacity_matches_static_and_reuses_program():
    from repro.netsim import sweep, topology, workloads
    from repro.netsim.engine import SimConfig

    topo = topology.leaf_spine(2, 4, 2, 40e9)
    tr = workloads.poisson_trace(workloads.TraceConfig(
        workload="fixed:1e6", load=0.5, duration_s=1e-3, n_hosts=topo.n_hosts,
        host_bw=40e9, seed=3, hosts_per_leaf=2))
    cfg = SimConfig(scheme="seqbalance", duration_s=1e-3)
    r_static, o_static = sweep.run_one(topo, cfg, tr)
    cap = np.asarray(topo.capacity).copy()
    r_traced, o_traced = sweep.run_one(topo, cfg, tr, capacity=cap)
    np.testing.assert_array_equal(r_static.finish, r_traced.finish)
    np.testing.assert_array_equal(np.asarray(o_static.uplink_load),
                                  np.asarray(o_traced.uplink_load))

    # capacity changes reuse the SAME executable (the whole point): two
    # more runs with different fault states add zero builds ...
    before = sweep.cache_stats()["builds"]
    cap_dead = cap.copy()
    cap_dead[[1, 2 * 4 + 1]] = 0.0  # kill spine 1 both directions (leaf 0)
    r_dead, _ = sweep.run_one(topo, cfg, tr, capacity=cap_dead)
    cap_brown = cap.copy()
    cap_brown[:8] *= 0.5
    sweep.run_one(topo, cfg, tr, capacity=cap_brown)
    assert sweep.cache_stats()["builds"] == before
    # ... and the physics actually responded to the degraded fabric
    assert not np.array_equal(r_traced.finish, r_dead.finish)


def test_static_capacity_keys_the_executable():
    """Without a capacity operand the topology's capacities are baked into
    the executable, so two topologies that differ only in capacity must not
    share one: the second builds its own and sees its own fabric."""
    from repro.netsim import sweep, topology, workloads
    from repro.netsim.engine import SimConfig

    healthy = topology.leaf_spine(2, 4, 2, 40e9)
    cap = np.asarray(healthy.capacity).copy()
    cap[[1, 2 * 4 + 1]] = 0.0  # spine 1, leaf 0, both directions
    dead = topology.leaf_spine(2, 4, 2, 40e9, capacity_overrides={1: 0.0, 9: 0.0})
    np.testing.assert_array_equal(np.asarray(dead.capacity), cap)
    tr = workloads.poisson_trace(workloads.TraceConfig(
        workload="fixed:1e6", load=0.5, duration_s=1e-3, n_hosts=healthy.n_hosts,
        host_bw=40e9, seed=3, hosts_per_leaf=2))
    cfg = SimConfig(scheme="seqbalance", duration_s=1e-3)
    r_healthy, _ = sweep.run_one(healthy, cfg, tr)
    before = sweep.cache_stats()["builds"]
    r_dead, _ = sweep.run_one(dead, cfg, tr)
    assert sweep.cache_stats()["builds"] == before + 1
    r_traced, _ = sweep.run_one(healthy, cfg, tr, capacity=cap)
    np.testing.assert_array_equal(r_dead.finish, r_traced.finish)
    assert not np.array_equal(r_dead.finish, r_healthy.finish)


# ------------------------------------------------------------ epoch spans
COSIM_SPANS = ("repro.cosim.trace", "repro.cosim.sim", "repro.cosim.feedback",
               "repro.cosim.record")


def _small_cosim():
    from repro.dist import cosim
    from repro.netsim import topology

    topo = topology.three_tier(n_tor=4, n_agg=4, n_core=2, hosts_per_tor=2)
    return cosim.run_cosim(
        topo, cosim.ring_hosts(topo, 4), 1e6, scheme="ecmp", epochs=2, n_chunks=2,
        phi_steps=2, faults=(cosim.kill_spine(topo, 1, epoch=1),))


def test_cosim_epoch_spans_in_order_and_results_unchanged(tmp_path):
    """Each epoch of ``run_cosim`` records ``repro.cosim.epoch`` holding
    its stages in order, on the profiler's clock; the records are the same
    to the bit with the profiler on and off."""
    import glob

    import jax

    from bench.harness import xtrace

    off = _small_cosim()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = _small_cosim()
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    for a, b in zip(off.records, on.records):
        np.testing.assert_array_equal(a.fct, b.fct)
        np.testing.assert_array_equal(a.imbalance, b.imbalance)
        assert (a.quarantined, a.reported_slow, a.completion, a.plan_version) == \
            (b.quarantined, b.reported_slow, b.completion, b.plan_version)
    assert [r.quarantined for r in on.records] == [(), ()]
    assert on.records[1].reported_slow and on.final_plan.inactive == \
        tuple(p in on.records[1].reported_slow for p in range(len(on.final_plan.inactive)))
    host = sorted(xtrace.load(str(tmp_path)).host, key=lambda h: h[1])
    epochs = [(s, s + d) for n, s, d in host if n == "repro.cosim.epoch"]
    assert len(epochs) == 2
    stages = [(n, s, s + d) for n, s, d in host if n in COSIM_SPANS]
    assert [n for n, _, _ in stages] == list(COSIM_SPANS) * 2
    for k, (e0, e1) in enumerate(epochs):
        inside = stages[4 * k: 4 * k + 4]
        assert all(e0 <= s and e <= e1 for _, s, e in inside)
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    # the sweep's own spans sit inside each epoch's sim span
    sims = [(s, e) for n, s, e in stages if n == "repro.cosim.sim"]
    dispatch = [s for n, s, d in host if n == "repro.sweep.dispatch"]
    assert len(dispatch) == 2 and all(s0 <= t <= s1 for t, (s0, s1) in zip(dispatch, sims))


def test_run_jobs_callable_and_kwargs_spellings():
    from repro.netsim import sweep, topology, workloads
    from repro.netsim.engine import SimConfig

    topo = topology.leaf_spine(2, 4, 2, 40e9)
    tr = workloads.poisson_trace(workloads.TraceConfig(
        workload="fixed:1e6", load=0.4, duration_s=5e-4, n_hosts=topo.n_hosts,
        host_bw=40e9, seed=5, hosts_per_leaf=2))
    cfg = SimConfig(scheme="ecmp", duration_s=5e-4)
    cap = np.asarray(topo.capacity).copy()
    ref = sweep.run_batch(topo, cfg, [tr], capacity=cap)
    out = sweep.run_jobs([
        (topo, cfg, [tr]),                          # classic triple
        (topo, cfg, [tr], dict(capacity=cap)),      # kwargs spelling
        lambda: sweep.run_batch(topo, cfg, [tr], capacity=cap),  # callable
    ])
    assert len(out) == 3
    np.testing.assert_array_equal(out[1][0][0].finish, ref[0][0].finish)
    np.testing.assert_array_equal(out[2][0][0].finish, ref[0][0].finish)


# ------------------------------------------------------------ steering
def test_collective_trace_steering_matches_engine_hash():
    """The steered flow ids must land on their planned fabric paths under
    the ENGINE's own five-tuple construction (flow_constants -> ecmp_paths)
    — this pins workloads._ecmp_steered_fids against engine.flow_constants
    so the two cannot drift apart silently."""
    import jax.numpy as jnp

    from repro.core import routing
    from repro.dist.elastic import LinkHealth
    from repro.netsim import engine, topology, workloads

    topo = topology.three_tier(n_tor=4, n_agg=4, n_core=2, hosts_per_tor=2,
                               bw_tor_agg=40e9, bw_agg_core=10e9,
                               host_bw=10e9)
    P = topo.n_paths
    health = LinkHealth(n_paths=P, phi_steps=2)
    health.report_slow(3, step=0)  # quarantine path 3
    plan = health.plan(1, n_chunks=4)
    hosts = [(i % 4) * 2 + (i // 4) for i in range(6)]
    tr = workloads.collective_trace(plan, hosts, 2e6, link_bw=40e9,
                                    steer_paths=P)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=1e-3)
    fc = engine.flow_constants(topo, cfg, jnp.asarray(tr.sizes),
                               jnp.asarray(tr.src), jnp.asarray(tr.dst),
                               jnp.asarray(tr.flow_id))
    realized = np.asarray(routing.ecmp_paths(*fc.f5, P))
    # the plan's active spread: member i's chunk-c QP targets
    # active[(i * n_chunks + c) % n_active], repeated every round
    active = [p for p in range(P) if not plan.inactive[p]]
    n, n_chunks = len(hosts), plan.n_chunks
    per_round = [active[(i * n_chunks + c) % len(active)]
                 for c in range(n_chunks) for i in range(n)]
    expect = np.asarray(per_round * (2 * (n - 1)), np.int32)
    np.testing.assert_array_equal(realized, expect)
    assert 3 not in realized  # the quarantined path carries nothing


# ----------------------------------------------- driver round trip (8 dev)
def test_cosim_driver_killed_spine_round_trip_8dev():
    """Fig. 11 as a regression: spine killed at epoch 2 (recovering at 5)
    on a forced 8-device host platform.  The driver must (1) degrade then
    re-converge within one epoch of the kill, (2) quarantine the dead
    spine's path while it is down, (3) release it exactly phi epochs after
    the last report, and (4) settle to zero plan churn — all epochs after
    the first reusing one compiled sweep program."""
    code = textwrap.dedent("""
        import json
        import numpy as np
        from repro.dist import cosim
        from repro.netsim import topology

        topo = topology.leaf_spine(4, 4, 2, 40e9)
        dead, kill, recover = 2, 2, 5
        hist = cosim.run_cosim(
            topo, cosim.ring_hosts(topo, 4), 4e6, scheme="ecmp", epochs=10,
            faults=(cosim.kill_spine(topo, dead, epoch=kill,
                                     recover_epoch=recover),),
            phi_steps=2, n_chunks=4)
        rs = hist.records
        out = dict(
            conv=hist.convergence_epoch(kill),
            baseline_p99=hist.baseline_p99(kill),
            p99=[r.fct_p99_s for r in rs],
            completion=[r.completion for r in rs],
            quarantined=[list(r.quarantined) for r in rs],
            churn=[r.plan_churn for r in rs],
            builds=[r.new_builds for r in rs],
            expiry=hist.health.expiry(dead),
            final_inactive=list(hist.final_plan.inactive),
        )
        print(json.dumps(out))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    dead, kill, recover = 2, 2, 5
    # (1) the kill epoch hurts; re-routed within one epoch of the kill
    assert out["completion"][kill] < 1.0 or \
        out["p99"][kill] > 2.0 * out["baseline_p99"]
    assert out["conv"] is not None and out["conv"] - kill <= 2
    # (2) quarantined from the epoch after the kill until recovery
    for e in range(kill + 1, recover + 1):
        assert dead in out["quarantined"][e], (e, out["quarantined"])
    # (3) the last report refreshes while the spine is down (capacity rule)
    # -> release exactly phi epochs after the last down epoch
    assert out["expiry"] == (recover - 1) + 2
    released = out["expiry"]
    for e in range(released, len(out["quarantined"])):
        assert dead not in out["quarantined"][e]
    # (4) after release: churn settles to zero and the final plan is clean
    assert all(c == 0 for c in out["churn"][released:])
    assert not any(out["final_inactive"])
    # p99 recovered and stays recovered after the reroute epoch
    for e in range(kill + 1, len(out["p99"])):
        assert out["p99"][e] <= 1.10 * out["baseline_p99"], (e, out["p99"])
        assert out["completion"][e] == 1.0
    # traced capacity: one program, zero rebuilds after epoch 0
    assert out["builds"][0] >= 1 and sum(out["builds"][1:]) == 0


def test_fct_samples_censors_unfinished_flows():
    from repro.netsim import metrics
    from repro.netsim.workloads import Trace

    class _S:
        finish = np.array([2e-4, np.inf, 5e-4, np.inf], np.float32)

    tr = Trace(sizes=np.ones(4, np.float32),
               arrivals=np.array([0.0, 1e-4, 2e-4, 9e-4], np.float32),
               src=np.zeros(4, np.int32), dst=np.zeros(4, np.int32),
               flow_id=np.arange(4, dtype=np.uint32),
               valid=np.array([True, True, True, False]))
    fct, completion = metrics.fct_samples(_S(), tr, horizon_s=1e-3)
    np.testing.assert_allclose(fct, [2e-4, 9e-4, 3e-4], rtol=1e-6)
    assert completion == pytest.approx(2 / 3)
