"""Observability plane (DESIGN.md §16): in-sim ring-buffer recorder
semantics (wraparound, chronology, quantiles), the recording-changes-
nothing bit-identity contract against the PR 7 seeded-twin goldens, the
zero-rebuild contract under the co-sim epoch loop, the flight-log schema
/ torn-tail reader, and the exporters (perfetto trace, epoch matrix)."""
import hashlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.netsim import engine, sweep, topology, workloads
from tests.test_adaptive_dt import FIG12_GOLD, _collective, _fig12_trace


# ------------------------------------------------ ring-buffer semantics
def _fill(spec, n_uplinks, n_chunks, K=10):
    ring = obs.ring_init(spec, n_uplinks)
    for i in range(n_chunks):
        ring = obs.record_chunk(
            spec, ring, step0=jnp.int32(i * K), steps=jnp.int32(K),
            ff=jnp.bool_(i % 2 == 0), queue_max=jnp.float32(100.0 + i),
            queue_mean=jnp.float32(10.0 + i), cnp=jnp.float32(i),
            goodput=jnp.float32(1e9 * i),
            offered=jnp.full((n_uplinks,), 1e9 * (i + 1)),
            cap=jnp.full((n_uplinks,), 100e9),
            rc=jnp.arange(1.0, 6.0), active=jnp.ones(5, bool))
    return ring


def test_ring_wraparound_keeps_newest_chronological():
    spec = obs.RecordSpec(ring_chunks=4)
    d = obs.drain(spec, _fill(spec, 2, 10))
    assert d["chunks_recorded"] == 10 and d["chunks_kept"] == 4
    step0 = d["meta"][:, d["fields"].index("step0")]
    # the NEWEST 4 of 10 chunks, oldest-first — wraparound rotated out 0..5
    assert step0.tolist() == [60.0, 70.0, 80.0, 90.0]
    q = d["meta"][:, d["fields"].index("queue_max")]
    assert q.tolist() == [106.0, 107.0, 108.0, 109.0]
    assert d["uplink"].shape == (4, 2, 2)
    assert d["uplink"][-1, 0, 0] == pytest.approx(10e9)  # offered, chunk 9


def test_ring_no_wrap_partial_fill():
    spec = obs.RecordSpec(ring_chunks=8)
    d = obs.drain(spec, _fill(spec, 1, 3))
    assert d["chunks_recorded"] == 3 and d["chunks_kept"] == 3
    assert d["meta"][:, 0].tolist() == [0.0, 10.0, 20.0]


def test_rank_quantiles_and_summary():
    spec = obs.RecordSpec(ring_chunks=2, quantiles=(0.1, 0.5, 0.9))
    d = obs.drain(spec, _fill(spec, 2, 2))
    # rc = [1..5] all active: rank idx = clip(4*q) -> sorted[0]/[2]/[3]
    f = d["fields"]
    assert f[-3:] == ["rc_q10", "rc_q50", "rc_q90"]
    assert d["meta"][0, f.index("rc_q10")] == 1.0
    assert d["meta"][0, f.index("rc_q50")] == 3.0
    assert d["meta"][0, f.index("rc_q90")] == 4.0
    s = obs.epoch_summary(spec, d)
    json.dumps(s)  # flight-log bound: must be strict-JSON serializable
    assert s["chunks_recorded"] == 2 and s["ff_chunks"] == 1
    assert s["queue_max_bytes"] == 101.0
    assert len(s["uplink"]["util_mean"]) == 2
    assert s["chunks"]["step0"] == [0.0, 10.0]


def test_quantiles_all_inactive_are_zero():
    spec = obs.RecordSpec(ring_chunks=2, quantiles=(0.5,))
    ring = obs.ring_init(spec, 1)
    ring = obs.record_chunk(
        spec, ring, step0=jnp.int32(0), steps=jnp.int32(5),
        ff=jnp.bool_(False), queue_max=jnp.float32(0), queue_mean=jnp.float32(0),
        cnp=jnp.float32(0), goodput=jnp.float32(0), offered=jnp.zeros(1),
        cap=jnp.ones(1), rc=jnp.arange(5.0), active=jnp.zeros(5, bool))
    d = obs.drain(spec, ring)
    assert d["meta"][0, d["fields"].index("rc_q50")] == 0.0


# ------------------------------- recording changes nothing (bit identity)
def test_recording_bit_identical_fig12_golden():
    """A recorded run must land the EXACT PR 7 golden finish times — the
    ring buffer rides along, it never perturbs the dynamics (same pattern
    as the adaptive=False seeded-twin goldens)."""
    topo = topology.sim_2tier()
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=10e-3,
                           uplink_sample_every=10)
    res, _ = sweep.run_one(topo, cfg, _fig12_trace(topo),
                           record=obs.RecordSpec(ring_chunks=32))
    f = np.asarray(res.finish)
    sha, fsum, cnp = FIG12_GOLD["seqbalance"]
    assert hashlib.sha1(f.tobytes()).hexdigest()[:16] == sha
    assert float(f[np.isfinite(f)].sum()) == fsum
    assert float(res.cnp_pkts) == cnp
    assert res.ring is not None
    d = obs.drain(obs.RecordSpec(ring_chunks=32), res.ring)
    assert d["chunks_recorded"] > 0


def test_unrecorded_result_has_no_ring():
    topo = topology.leaf_spine(2, 2, 2, 100e9)
    cfg = engine.SimConfig(scheme="ecmp", duration_s=0.5e-3)
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="websearch", load=0.4, duration_s=0.2e-3,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=0,
        hosts_per_leaf=topo.hosts_per_leaf))
    res, _ = sweep.run_one(topo, cfg, trace)
    assert res.ring is None


def test_recording_wraparound_in_sim_keeps_tail():
    """Sim-level wraparound: a tiny ring on the long collective run must
    rotate out the oldest chunks but keep the FINAL chunk (the boundary
    chunk covering the end of the horizon)."""
    topo = topology.leaf_spine(4, 4, 4, 100e9)
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=14e-3,
                           uplink_sample_every=10)
    trace = _collective(topo)
    small = obs.RecordSpec(ring_chunks=4)
    big = obs.RecordSpec(ring_chunks=256)
    res_s, _ = sweep.run_one(topo, cfg, trace, record=small)
    res_b, _ = sweep.run_one(topo, cfg, trace, record=big)
    d_s = obs.drain(small, res_s.ring)
    d_b = obs.drain(big, res_b.ring)
    assert d_s["chunks_recorded"] == d_b["chunks_recorded"] > 4
    assert d_b["chunks_kept"] == d_b["chunks_recorded"]
    assert d_s["chunks_kept"] == 4
    # the small ring's 4 rows are exactly the big drain's last 4 rows
    np.testing.assert_array_equal(d_s["meta"], d_b["meta"][-4:])
    last = d_b["meta"][-1]
    assert last[0] + last[1] == pytest.approx(
        d_b["meta"][0, 0] + d_b["meta"][:, 1].sum())  # covers the horizon end


# --------------------------------------- cosim: flight log, zero rebuilds
def test_cosim_recording_zero_rebuilds_and_flight(tmp_path):
    from repro.dist import cosim

    topo = topology.leaf_spine(4, 4, 4, 100e9)
    hosts = cosim.ring_hosts(topo, 8)
    kw = dict(scheme="ecmp", epochs=3, phi_steps=2, n_chunks=4, seed=0,
              faults=(cosim.kill_spine(topo, 2, epoch=1),))
    fl = tmp_path / "flight.jsonl"
    rec = obs.RecordSpec(ring_chunks=32)
    h0 = cosim.run_cosim(topo, hosts, 4e6, **kw)
    h1 = cosim.run_cosim(topo, hosts, 4e6, record=rec, flight=str(fl), **kw)
    # driver observables bit-identical with recording on
    assert [r.fct_p99_s for r in h0.records] == \
        [r.fct_p99_s for r in h1.records]
    assert [r.quarantined for r in h0.records] == \
        [r.quarantined for r in h1.records]
    # the one-extra-executable contract: epoch 0 builds, nothing after
    assert sum(r.new_builds for r in h1.records[1:]) == 0
    assert all(r.insim is not None for r in h1.records)
    assert all(r.insim is None for r in h0.records)

    header, recs = obs.read_flight(str(fl))
    assert header["schema_version"] == obs.SCHEMA_VERSION
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "campaign" and kinds[-1] == "run_end"
    eps = [r for r in recs if r["kind"] == "epoch"]
    assert [r["epoch"] for r in eps] == [0, 1, 2]
    assert all(r["insim"]["chunks_recorded"] > 0 for r in eps)
    assert eps[1]["faults"][0]["kind"] == "FaultEvent"
    assert eps[0]["hot_uplinks"] and "util" in eps[0]["hot_uplinks"][0]
    assert recs[-1]["total_new_builds"] == sum(
        r.new_builds for r in h1.records)

    # exporters round-trip off the same file
    from repro.obs import trace_export
    from repro.obs.features import epoch_matrix

    out = tmp_path / "trace.json"
    trace = trace_export.export_chrome_trace(str(fl), str(out))
    names = {e.get("name") for e in trace["traceEvents"]}
    assert {"epoch 0", "epoch 1", "epoch 2"} <= names
    assert "FaultEvent" in names
    json.loads(out.read_text())  # strict JSON on disk
    m = epoch_matrix(str(fl))
    assert m["matrix"].shape == (3, topo.uplink_ids.size, len(m["features"]))
    assert m["epochs"] == [0, 1, 2]
    assert np.isfinite(m["matrix"]).all()


def test_flight_log_instance_shared_not_closed(tmp_path):
    from repro.dist import cosim

    topo = topology.leaf_spine(4, 4, 4, 100e9)
    hosts = cosim.ring_hosts(topo, 8)
    fl = obs.FlightLog(str(tmp_path / "shared.jsonl"), meta=dict(who="test"))
    cosim.run_cosim(topo, hosts, 4e6, scheme="ecmp", epochs=1, n_chunks=4,
                    seed=0, flight=fl)
    fl.event("custom", note="caller still owns the log")
    fl.close()
    header, recs = obs.read_flight(str(tmp_path / "shared.jsonl"))
    assert header["meta"]["who"] == "test"
    assert [r["kind"] for r in recs][-1] == "custom"


# ------------------------------------------------- flight-log schema
def test_flight_schema_version_shared_with_journal():
    from repro.dist import cosim

    assert obs.SCHEMA_VERSION == cosim.JOURNAL_SCHEMA_VERSION


def test_flight_reader_tolerates_torn_tail(tmp_path):
    p = tmp_path / "torn.jsonl"
    with obs.FlightLog(str(p)) as fl:
        fl.event("epoch", epoch=0)
        fl.event("epoch", epoch=1)
    with open(p, "a") as fh:
        fh.write('{"kind": "epoch", "epo')  # interrupted mid-write
    header, recs = obs.read_flight(str(p))
    assert len(recs) == 2 and recs[-1]["epoch"] == 1


def test_flight_reader_rejects_wrong_schema(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"journal": "flight", "schema_version": 999}) + "\n")
    with pytest.raises(obs.FlightLogError):
        obs.read_flight(str(p))
    (tmp_path / "empty.jsonl").write_text("")
    with pytest.raises(obs.FlightLogError):
        obs.read_flight(str(tmp_path / "empty.jsonl"))


def test_runmeta_keys_stable():
    m = obs.runmeta()
    assert set(m) == {"run_id", "git_sha", "host", "n_devices", "backend",
                      "time_utc"}
    assert obs.runmeta()["run_id"] == m["run_id"]  # per-process constant
    json.dumps(m)


def test_watchdog_transition_counters_roundtrip():
    from repro.dist.elastic import TelemetryWatchdog

    wd = TelemetryWatchdog(blackout_epochs=2)
    assert [wd.observe(n) for n in (3, 0, 0, 0, 5, 1)] == \
        ["ok", "silent", "safe", "safe", "recovered", "ok"]
    st = wd.state()
    assert st["transitions"] == dict(ok=2, silent=1, safe=2, recovered=1)
    wd2 = TelemetryWatchdog(blackout_epochs=2)
    wd2.restore(st)
    assert wd2.state() == st
    wd2.restore(dict(silent=0, safe=False))  # pre-counter journals: fine
