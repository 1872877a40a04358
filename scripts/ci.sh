#!/usr/bin/env bash
# CI smoke: tier-1 test suite + one fast end-to-end paper bench.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# CI is a CPU run (interpret-mode kernels, forced host devices); the chip
# run is chip_smoke.py.  Pinning the backend also keeps the subprocesses
# the tests start off any TPU their parent would hold.
export JAX_PLATFORMS=cpu

python -m pytest -x -q --durations=15

# dist layer under a forced 8-device host platform: re-runs the planning /
# sharding / co-sim tests with the sweep runner actually sharding over 8
# local devices (the pmap-of-vmap dispatch path).  The subprocess-based
# collective tests pin their own child XLA_FLAGS, so rerunning them here
# would add compile minutes for zero new coverage — deselect them.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m pytest -x -q tests/test_collectives.py tests/test_system.py \
  tests/test_dist_extra.py -k "not equals_psum and not across_mesh_sizes"

# bench_fig10 fast mode: exercises trace generation, the sweep runner, the
# compact engine, and the metrics layer end to end in under a minute.
python -m benchmarks.run --only fig10 --json /tmp/BENCH_smoke.json

# 2-epoch co-sim smoke on the forced 8-device platform: the training-side
# plan -> fluid-sim -> quarantine -> plan loop (dist.cosim via launch.train
# --cosim-epochs), healthy fabric — just the loop plumbing, the sharded
# dispatch, and the traced-capacity compile reuse.
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m repro.launch.train --cosim-epochs 2 --cosim-kill-spine -1 \
  --cosim-only

# perf regression gate: rerun the fig12 fast sweep (compact + dense oracle)
# and fail if the compact per-step cost regressed >30% vs the committed
# baseline, if the compact-vs-dense stat divergence exceeds 0.01%, or if
# the sweep spilled.  Skip with REPRO_CI_SKIP_BENCH_GATE=1 (e.g. on a
# machine unrelated to the committed baseline's).
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only netsim_speedup --json /tmp/BENCH_gate.json
  python scripts/check_bench.py /tmp/BENCH_gate.json BENCH_netsim.json
fi

# co-sim convergence gate: rerun the fast killed-spine scenarios and fail
# if any scenario's convergence-epoch count regressed by more than 1 vs
# the committed record, if one stopped converging, or if epochs after the
# first rebuilt sweep executables (the traced-capacity reuse contract).
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only cosim --json /tmp/BENCH_cosim.json
  python scripts/check_bench.py /tmp/BENCH_cosim.json BENCH_netsim.json \
    --cosim
fi

# chaos smoke on the forced 8-device platform: a seeded 3-fault random
# campaign (flap / lossy / straggler mix) runs end to end through the
# crash-proof pool — the driver must reconverge and salvage ZERO cells
# (a JobFailure here means a worker crashed, the one thing the chaos
# framework exists to make impossible).  The campaign spans the first 6
# epochs; the run gets 2 clean trailing epochs so BOTH schemes can
# reconverge (seqbalance sub-flows spray over every path, so it cannot
# dodge a fault that persists to the final epoch).
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF'
from repro.dist import cosim
from repro.netsim import faults, sweep, topology

topo = topology.leaf_spine(4, 4, 4, 100e9)
camp = faults.random_campaign(topo, seed=11, epochs=6, n_faults=3, n_ranks=8)
print("chaos smoke campaign:", *camp.summary(), sep="\n  ")
hists = cosim.run_cosim_grid(
    [dict(topo=topo, hosts=cosim.ring_hosts(topo, 8), size_bytes=4e6,
          scheme=s, epochs=8, phi_steps=2, cooldown_steps=2, n_chunks=4,
          seed=0, campaign=camp) for s in ("ecmp", "seqbalance")],
    salvage=True, retries=1)
crashed = [h for h in hists if h is None or getattr(h, "failed", False)]
assert not crashed, f"chaos smoke: {len(crashed)} crashed cells: {crashed}"
for h in hists:
    conv = h.convergence_epoch(1)
    assert conv is not None, f"{h.scheme}: no reconvergence after campaign"
    print(f"chaos smoke: {h.scheme} reconverged at epoch {conv}, "
          f"0 crashed cells")
EOF

# chaos-campaign gate: rerun the fast campaign bench and fail on crashed
# (salvaged) cells, lost reconvergence, or a >30% worst censored-p99
# regression vs the committed record.
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only faults --json /tmp/BENCH_faults.json
  python scripts/check_bench.py /tmp/BENCH_faults.json BENCH_netsim.json \
    --faults
fi

# degraded-telemetry smoke on the forced 8-device platform: the same
# killed-spine scenario with its congestion reports pushed through a
# seeded 30%-loss / 1-epoch-delay / duplicating channel — the planner
# must still quarantine the dead paths and reconverge, plan versions must
# stay strictly monotone (a replayed older plan is refused, never
# applied), and a full blackout must trip the safe-mode fallback and
# recover once the channel heals.
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF2'
from repro.dist import cosim
from repro.netsim import faults, topology

topo = topology.leaf_spine(4, 4, 4, 100e9)
hosts = cosim.ring_hosts(topo, 8)
kw = dict(scheme="ecmp", epochs=8, phi_steps=2, n_chunks=4, seed=0,
          faults=(cosim.kill_spine(topo, 2, epoch=1, recover_epoch=5),))
h = cosim.run_cosim(topo, hosts, 4e6, staleness_bound=2,
                    telemetry=faults.TelemetryChannel(
                        loss=0.3, delay_epochs=1, dup=0.2, seed=7), **kw)
conv = h.convergence_epoch(1)
assert conv is not None, "lossy telemetry: no reconvergence"
vs = [r.plan_version for r in h.records]
assert all(b > a for a, b in zip(vs, vs[1:])), f"non-monotone plans: {vs}"
assert h.plan_refused == 0, f"{h.plan_refused} newer plans refused"
assert any(r.reported_slow for r in h.records), "no reports admitted"
print(f"telemetry smoke: lossy channel reconverged at epoch {conv}, "
      f"plan versions monotone, 0 refusals")
hb = cosim.run_cosim(topo, hosts, 4e6, blackout_epochs=2,
                     telemetry=faults.TelemetryChannel(blackout=(0, 4),
                                                       seed=1), **kw)
safe = [r.epoch for r in hb.records if r.safe_mode]
assert safe, "blackout never tripped safe mode"
assert not hb.records[-1].safe_mode, "never recovered from safe mode"
print(f"telemetry smoke: blackout safe-mode epochs {safe}, recovered")
EOF2

# degraded-telemetry gate: rerun the telemetry bench and fail on a broken
# perfect-channel bit-identity, unbounded lossy/delayed reconvergence,
# non-monotone plan versions, a blackout that misses safe mode, or a >1
# convergence-epoch regression vs the committed record.
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only telemetry --json /tmp/BENCH_telemetry.json
  python scripts/check_bench.py /tmp/BENCH_telemetry.json BENCH_netsim.json \
    --telemetry
fi

# adaptive-dt co-sim smoke on the forced 8-device platform: the killed-
# spine scenario with the event-driven adaptive engine enabled must
# reconverge at the same epoch as fixed dt with bit-identical FCT curves
# (the cosim ring is back-to-back, so every chunk holds an event and the
# quiescence predicate correctly never fires), must not rebuild any
# executable after epoch 0, and the sparse collective workload (compute
# gaps between rounds) must actually fast-forward with identical results.
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF3'
import numpy as np
from repro.dist import cosim
from repro.netsim import sweep, topology, workloads
from repro.netsim.engine import SimConfig

topo = topology.leaf_spine(4, 4, 4, 100e9)
hosts = cosim.ring_hosts(topo, 8)
kw = dict(scheme="ecmp", epochs=6, phi_steps=2, n_chunks=4, seed=0,
          faults=(cosim.kill_spine(topo, 2, epoch=1, recover_epoch=4),))
h_f = cosim.run_cosim(topo, hosts, 4e6, **kw)
h_a = cosim.run_cosim(topo, hosts, 4e6, adaptive=True, **kw)
assert h_a.convergence_epoch(1) == h_f.convergence_epoch(1), (
    h_a.convergence_epoch(1), h_f.convergence_epoch(1))
p99_f = [r.fct_p99_s for r in h_f.records]
p99_a = [r.fct_p99_s for r in h_a.records]
assert p99_f == p99_a, "adaptive cosim diverged from fixed dt"
builds_late = sum(r.new_builds for r in h_a.records[1:])
assert builds_late == 0, f"{builds_late} rebuilds after epoch 0"
from repro.dist import collectives
plan = collectives.PathPlan(n_chunks=4, directions=(1, -1, 1, -1))
trace = workloads.collective_trace(plan, hosts, 4e6, link_bw=100e9,
                                   round_gap_s=800e-6, seed=0,
                                   steer_paths=topo.n_paths)
cfg = SimConfig(scheme="seqbalance", duration_s=14e-3,
                uplink_sample_every=10)
import dataclasses
res_f, _ = sweep.run_one(topo, cfg, trace)
res_a, _ = sweep.run_one(topo, dataclasses.replace(cfg, adaptive=True), trace)
assert res_a.ff_steps > 0, "sparse collective never fast-forwarded"
assert np.array_equal(np.asarray(res_f.finish), np.asarray(res_a.finish))
print(f"adaptive smoke: cosim reconverged at epoch "
      f"{h_a.convergence_epoch(1)} (p99 == fixed dt, 0 rebuilds), "
      f"collective ff {res_a.ff_steps} steps, finish times identical")
EOF3

# adaptive-dt gate: rerun the adaptive bench and fail on adaptive-vs-fixed
# stat divergence, a speedup below the committed floors (collective >= 2x,
# fig12 parity), a collective run that never fast-forwards, or any
# executable rebuild after the first adaptive dispatch.
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only adaptive --json /tmp/BENCH_adaptive.json
  python scripts/check_bench.py /tmp/BENCH_adaptive.json BENCH_netsim.json \
    --adaptive
fi

# observability smoke on the forced 8-device platform: a 2-epoch recorded
# co-sim must produce a schema-v2 flight log covering both epochs (with
# the in-sim ring-buffer drain on each), export to a perfetto-loadable
# Chrome trace, and round-trip through the [epoch, uplink, feature]
# matrix — while staying bit-identical to the unrecorded driver and
# building ZERO executables after epoch 0.
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF4'
import json, os, tempfile
from repro import obs
from repro.dist import cosim
from repro.netsim import topology

topo = topology.leaf_spine(4, 4, 4, 100e9)
hosts = cosim.ring_hosts(topo, 8)
kw = dict(scheme="ecmp", epochs=2, phi_steps=2, n_chunks=4, seed=0,
          faults=(cosim.kill_spine(topo, 2, epoch=1),))
fd, fl = tempfile.mkstemp(suffix=".jsonl"); os.close(fd)
tr_path = fl + ".trace.json"
h0 = cosim.run_cosim(topo, hosts, 4e6, **kw)
h1 = cosim.run_cosim(topo, hosts, 4e6, record=obs.RecordSpec(ring_chunks=32),
                     flight=fl, **kw)
assert [r.fct_p99_s for r in h0.records] == [r.fct_p99_s for r in h1.records]
assert sum(r.new_builds for r in h1.records[1:]) == 0
header, recs = obs.read_flight(fl)
eps = [r for r in recs if r["kind"] == "epoch"]
assert len(eps) == 2 and all(r.get("insim") for r in eps), eps
from repro.obs import trace_export
from repro.obs.features import epoch_matrix
trace = trace_export.export_chrome_trace(fl, tr_path)
assert len(json.load(open(tr_path))["traceEvents"]) == len(trace["traceEvents"])
m = epoch_matrix((header, recs))
assert m["matrix"].shape == (2, topo.uplink_ids.size, len(m["features"]))
os.unlink(fl); os.unlink(tr_path)
print(f"obs smoke: 2-epoch flight log, {len(trace['traceEvents'])} trace "
      f"events, matrix {m['matrix'].shape}, driver bit-identical, 0 rebuilds")
EOF4

# observability gate: rerun the obs bench and fail if warm recording
# overhead exceeds the committed floor (5%), if the recorder rebuilt an
# executable after its first dispatch, or if the killed-agg-spine flight
# log missed an epoch / its in-sim drain.
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only obs --json /tmp/BENCH_obs.json
  python scripts/check_bench.py /tmp/BENCH_obs.json BENCH_netsim.json --obs
fi

# flowcell smoke on the forced 8-device platform: a flowcell-split plan
# (chunks sprayed over every active path) plus a live go-back-N reorder
# budget must run through the co-sim loop with ZERO executable rebuilds
# after epoch 0 (spray is a traced trace column, the budget a traced
# scalar operand — one compiled program covers every split factor and
# budget), and the degenerate settings (flowcells=1, budget unset) must
# leave the driver bit-identical to the classic path.
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF5'
from repro.dist import cosim
from repro.netsim import topology

topo = topology.leaf_spine(4, 4, 4, 100e9)
hosts = cosim.ring_hosts(topo, 8)
kw = dict(scheme="seqbalance", epochs=3, phi_steps=2, n_chunks=4, seed=0,
          faults=(cosim.kill_spine(topo, 2, epoch=1),))
h_fc = cosim.run_cosim(topo, hosts, 4e6, flowcells=4, reorder_budget=16.0,
                       **kw)
builds_late = sum(r.new_builds for r in h_fc.records[1:])
assert builds_late == 0, f"{builds_late} rebuilds after epoch 0"
h0 = cosim.run_cosim(topo, hosts, 4e6, **kw)
h1 = cosim.run_cosim(topo, hosts, 4e6, flowcells=1, reorder_budget=None,
                     **kw)
assert [r.fct_p99_s for r in h0.records] == [r.fct_p99_s for r in h1.records]
print(f"flowcell smoke: 3-epoch co-sim with flowcells=4 / budget=16 MTU, "
      f"0 rebuilds after epoch 0, degenerate knobs bit-identical")
EOF5

# flowcell gate: rerun the flowcell bench and fail if spraying stops
# beating SeqBalance in the cost-free arm, stops losing at the strict
# go-back-N budget on the symmetric fabric (the paper's no-reordering
# motivation, quantified), if the hetero-fabric grid goes missing, if the
# co-sim rebuilt an executable after epoch 0, or if the degenerate arms'
# stat diff is not EXACTLY zero.
if [ -z "${REPRO_CI_SKIP_BENCH_GATE:-}" ]; then
  python -m benchmarks.run --only flowcell --json /tmp/BENCH_flowcell.json
  python scripts/check_bench.py /tmp/BENCH_flowcell.json BENCH_netsim.json \
    --flowcell
fi
