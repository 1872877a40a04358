#!/usr/bin/env python3
"""On-chip smoke test: the simulator's main path on a TPU, at the paper's
fabric sizes, through the entry points a user calls.

    python chip_smoke.py             # phases a-e on one chip
    python chip_smoke.py --chips 4   # the two multi-chip paths, on four chips

One chip (paper §V sizes):
  a  kernel parity: the compiled Pallas ``linkload_cascade_tiered``, over the
     topology's link-id bands, against ``kernels/ref.py`` and bit for bit
     against the kernel over all link ids, at sim_2tier and three_tier
     width (N = 4 and N = 1); per-call times and one-hot rows of both
  b  Fig. 12: sim_2tier, websearch at 80 % load, 10 ms of arrivals, 40 ms
     horizon, five schemes through ``sweep.run_jobs`` (dataplane "auto",
     i.e. Pallas), then seqbalance and ecmp again on the XLA dataplane
  c  Fig. 14: three_tier (320 hosts), websearch at 60 % load, 8 ms of
     arrivals, ecmp and seqbalance
  d  the killed-agg-spine co-sim: three_tier, ring 20, ecmp, 16 MB
     collectives, spine 3 killed at epoch 2 and back at epoch 6, 10 epochs
  e  the compact engine against the dense oracle at the testbed scale
Four chips:
  sharded  a Fig. 12 batch sharded over the four chips against the same
           batch on one device (REPRO_SWEEP_DEVICES=1)
  allreduce  ``seqbalance_all_reduce`` on a 1-D 4-chip ``pod`` mesh against
           ``lax.psum`` at a 4 MB-per-device bucket

Each phase prints one line; any failed check exits non-zero.  The last
line of a passing run is the device record
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import jax

# f32 tolerances of the kernel-vs-reference comparison (phase a): the
# interpret-mode test tolerances.  Round-off only: a bf16 pass would be off
# by ~1e-3 relative.
KERNEL_TOL = dict(arrival=dict(rtol=2e-5, atol=1e-3), queue=dict(rtol=1e-4, atol=1.0),
                  mark=dict(rtol=0.0, atol=1e-6), thr=dict(rtol=2e-5, atol=1e-2))
# Pallas vs XLA dataplane (phase b): the two sum link loads in different
# orders, and ECN marking and DCQCN feedback amplify that f32 round-off
# over 4000 steps.  At this size on the CPU, summation order alone moved
# the FCT stats by up to 3.61 % (interpret-mode Pallas vs XLA, seeds 1-4,
# seqbalance and ecmp; 2.27 % at seed 1, the seed used here), and by up
# to 1.56 % when the XLA path merely sums in reverse flow order.  A bf16
# pass moved them by up to 5.58 %, the same size, so this check guards
# against gross errors (a lost hop, a wrong scale); phase a guards the
# precision.
DATAPLANE_REL_TOL = 0.05
# compact vs dense oracle (phase e): ROADMAP's correctness rule, percent
ORACLE_MAX_DIV_PCT = 0.01
FCT_STATS = ("completion_rate", "avg_slowdown", "p99_slowdown")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (summed over
    threads), read from ``jax.monitoring`` duration events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in self.EVENTS:
            with self._lock:
                self.total += secs


class Phase:
    """Times one phase and prints its line: device kind, wall and compile
    seconds, executables built, flows simulated, FCT stats per scheme."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock
        self.fields: dict = {}

    def __enter__(self):
        from repro.netsim import sweep

        self.t0, self.c0 = time.perf_counter(), self.clock.total
        self.b0 = sweep.cache_stats()["builds"]
        return self

    def __exit__(self, *exc):
        from repro.netsim import sweep

        if exc[0] is None:
            line = dict(
                device=jax.devices()[0].device_kind,
                wall_s=time.perf_counter() - self.t0,
                compile_s=self.clock.total - self.c0,
                builds=sweep.cache_stats()["builds"] - self.b0,
            )
            line.update(self.fields)
            print(f"phase {self.name} {json.dumps(line)}", flush=True)
        return False


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"CHECK FAILED: {what}")


def _stats(st, trace, topo) -> dict:
    from repro.netsim import metrics

    s = metrics.fct_stats(st, trace, topo, 100e9)
    return {k: s[k] for k in FCT_STATS}


def _websearch(topo, load: float, arrivals_s: float, seed: int, base_bw: float):
    from repro.netsim import workloads

    return workloads.poisson_trace(workloads.TraceConfig(
        workload="websearch", load=load, duration_s=arrivals_s,
        n_hosts=topo.n_hosts, host_bw=100e9, seed=seed,
        hosts_per_leaf=topo.hosts_per_leaf, load_base_bw=base_bw))


FIG12_ARRIVALS_S = 10e-3  # the --full Fig. 12 size; the horizon is 4x


def _fig12_trace(seed: int = 1):
    """benchmarks/paper_benches.py's Fig. 12 websearch-80 % trace."""
    from repro.netsim import topology

    topo = topology.sim_2tier()
    return topo, _websearch(topo, 0.8, FIG12_ARRIVALS_S, seed,
                            topo.n_leaf * topo.n_paths * 100e9)


def _sweep_schemes(ph: Phase, topo, trace, schemes, horizon_s, dataplane,
                   tag: str = "", **cfg_kw) -> dict:
    from repro.netsim import engine, sweep

    jobs = [(topo, engine.SimConfig(scheme=s, duration_s=horizon_s,
                                    dataplane=dataplane, **cfg_kw), [trace])
            for s in schemes]
    out = {}
    for s, (res, _) in zip(schemes, sweep.run_jobs(jobs)):
        check(res[0].spill_steps == 0, f"{s}{tag}: the sweep left spill")
        out[s + tag] = _stats(res[0], trace, topo)
    ph.fields["flows"] = ph.fields.get("flows", 0) + int(trace.valid.sum()) * len(schemes)
    ph.fields.update(out)
    return out


# ------------------------------------------------------------------ phases
def _call_us(fn, *args, reps: int = 100) -> float:
    """Device-bound microseconds per call: ``reps`` calls queued back to
    back, timed to the last one's completion."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def _routes(topo, n: int, n_sub: int, key):
    """Tiered hop ids of ``n`` random flows, a quarter of them inside one
    leaf (absent fabric hops), as ``cascade_nic`` receives them."""
    import jax.numpy as jnp

    ks = jax.random.split(key, 3)
    hpl = topo.hosts_per_leaf
    src = jax.random.randint(ks[0], (n,), 0, topo.n_hosts)
    other = jax.random.randint(ks[1], (n,), 0, topo.n_hosts)
    dst = jnp.where(jnp.arange(n) % 4 == 0, (src // hpl) * hpl + (src + 1) % hpl, other)
    path = jax.random.randint(ks[2], (n, n_sub), 0, topo.n_paths)
    tx, rx = topo.nic_links(src, dst)
    fab = topo.fabric_links((src // hpl)[:, None], (dst // hpl)[:, None], path)
    return fab, tx, rx


def phase_kernel(clock):
    """a: compiled Pallas kernel, with the topology's link-id bands as the
    engines pass them, against its jnp oracle and, bit for bit, against
    the same kernel over all link ids; per-call times of both."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import linkload as ll, ref
    from repro.netsim import dataplane, topology

    widths = [("sim_2tier_N4", topology.sim_2tier(), 4),
              ("sim_2tier_N1", topology.sim_2tier(), 1),
              ("three_tier_N4", topology.three_tier(), 4),
              ("three_tier_N1", topology.three_tier(), 1)]
    n = 4096  # flows per call
    with Phase("a", clock) as ph:
        for name, topo, N in widths:
            L, hf = topo.n_links, topo.n_fabric_hops
            ks = jax.random.split(jax.random.PRNGKey(L + N), 3)
            args = _routes(topo, n, N, ks[0]) + (
                jax.random.uniform(ks[1], (n, N)) * 100e9 / N,
                jax.random.uniform(ks[2], (L,)) * 2e6,
                jnp.asarray(topo.capacity[:L]),
                dataplane.queue_mask_for(topo)[:L],
            )
            kern = jax.jit(lambda *a: ll.linkload_cascade_tiered(
                *a, n_links=L, bands=topo.bands))
            full = jax.jit(lambda *a: ll.linkload_cascade_tiered(*a, n_links=L))
            hlo = kern.lower(*args).compile().as_text()
            check("tpu_custom_call" in hlo, f"{name}: no compiled Pallas kernel")
            got = kern(*args)
            for key, g, f in zip(KERNEL_TOL, got, full(*args)):
                check(np.array_equal(np.asarray(g), np.asarray(f)),
                      f"{name} {key}: bands change the kernel's output")
            want = jax.jit(lambda *a: ref.linkload_cascade_tiered_ref(
                *a[:4], L, 400e3, 1600e3, 0.2, *a[4:], 10e-6))(*args)
            errs = {}
            for key, g, w in zip(KERNEL_TOL, got, want):
                g, w = np.asarray(g), np.asarray(w)
                tol = KERNEL_TOL[key]
                bad = np.abs(g - w) > tol["atol"] + tol["rtol"] * np.abs(w)
                check(not bad.any(), f"{name} {key}: {int(bad.sum())} elements "
                      f"outside {tol}")
                errs[key] = float(np.max(np.abs(g - w) / (np.abs(w) + 1.0)))
            ph.fields[name] = dict(
                n_links=L, flows=n, n_sub=N, max_rel_err=errs,
                onehot_rows=ll.onehot_rows(topo.bands, N, hf, L),
                kernel_us=_call_us(kern, *args), full_width_us=_call_us(full, *args))


def phase_fig12(clock):
    """b: the Fig. 12 deployment, five schemes, then Pallas vs XLA."""
    topo, trace = _fig12_trace()
    horizon = 4 * FIG12_ARRIVALS_S
    with Phase("b", clock) as ph:
        auto = _sweep_schemes(ph, topo, trace,
                              ("drill", "ecmp", "seqbalance", "letflow", "conga"),
                              horizon, "auto", uplink_sample_every=10)
        xla = _sweep_schemes(ph, topo, trace, ("seqbalance", "ecmp"), horizon,
                             "xla", tag="_xla", uplink_sample_every=10)
        for s in ("seqbalance", "ecmp"):
            for k in FCT_STATS:
                a, x = auto[s][k], xla[s + "_xla"][k]
                check(abs(a - x) <= DATAPLANE_REL_TOL * abs(x),
                      f"{s} {k}: Pallas {a} vs XLA {x} beyond "
                      f"{DATAPLANE_REL_TOL:.0%}")


def phase_fig14(clock):
    """c: the Fig. 14 fabric at paper scale."""
    from repro.netsim import topology

    topo, arrivals_s = topology.three_tier(), 8e-3
    trace = _websearch(topo, 0.6, arrivals_s, 2, topo.n_leaf * 4 * 100e9)
    with Phase("c", clock) as ph:
        _sweep_schemes(ph, topo, trace, ("ecmp", "seqbalance"), 4 * arrivals_s,
                       "auto")


def phase_cosim(clock):
    """d: the killed-agg-spine co-sim acceptance row (bench_cosim)."""
    from repro.dist import cosim
    from repro.netsim import topology

    topo, ring, size_bytes = topology.three_tier(), 20, 16e6
    kill, recover = 2, 6
    with Phase("d", clock) as ph:
        hist = cosim.run_cosim(
            topo, cosim.ring_hosts(topo, ring), size_bytes, scheme="ecmp",
            epochs=10, phi_steps=2, n_chunks=4, seed=0,
            faults=(cosim.kill_spine(topo, 3, epoch=kill, recover_epoch=recover),))
        builds = [r.new_builds for r in hist.records]
        conv = hist.convergence_epoch(kill)
        ph.fields.update(
            flows=int(sum(r.fct.size for r in hist.records)),
            builds_per_epoch=builds, convergence_epochs=None if conv is None
            else conv - kill, baseline_p99_us=hist.baseline_p99(kill) * 1e6,
            completion=[r.completion for r in hist.records],
            p99_us=[r.fct_p99_s * 1e6 for r in hist.records])
        check(sum(builds[1:]) == 0, f"executables built after epoch 0: {builds}")
        check(conv is not None and conv - kill <= 1,
              f"plan did not converge within 1 epoch of the kill (epoch {conv})")


def phase_oracle(clock):
    """e: compact engine (sweep.run_one) against the dense oracle."""
    from repro.netsim import engine, sweep, topology
    from repro.netsim.dcqcn import DCQCNParams

    topo, arrivals_s = topology.testbed_symmetric(), 2e-3
    trace = _websearch(topo, 0.5, arrivals_s, 3,
                       topo.n_leaf * topo.n_paths * 40e9)
    # benchmarks/paper_benches.py::_dc40: the testbed's 40G DCQCN settings
    dc40 = DCQCNParams(kmin_bytes=160e3, kmax_bytes=520e3, r_ai=400e6,
                       min_rate=400e6)
    with Phase("e", clock) as ph:
        worst = 0.0
        for scheme in ("ecmp", "seqbalance"):
            cfg = engine.SimConfig(scheme=scheme, duration_s=4 * arrivals_s,
                                   dcqcn=dc40)
            comp, _ = sweep.run_one(topo, cfg, trace)
            dense, _ = engine.simulate(topo, cfg, trace)
            sc, sd = _stats(comp, trace, topo), _stats(dense, trace, topo)
            div = {k: abs(sc[k] / sd[k] - 1.0) * 100 for k in FCT_STATS}
            worst = max(worst, *div.values())
            ph.fields[scheme] = sc
            ph.fields[scheme + "_divergence_pct"] = div
        ph.fields["flows"] = int(trace.valid.sum()) * 2
        check(worst <= ORACLE_MAX_DIV_PCT,
              f"compact vs dense divergence {worst}% > {ORACLE_MAX_DIV_PCT}%")


def phase_sharded(clock):
    """4 chips: the Fig. 12 batch sharded over every chip vs one device."""
    import numpy as np

    from repro.netsim import engine, sweep

    batch = 8  # seeds, two per chip
    topo, _ = _fig12_trace()
    traces = [_fig12_trace(seed=s)[1] for s in range(1, batch + 1)]
    cfg = engine.SimConfig(scheme="seqbalance", duration_s=4 * FIG12_ARRIVALS_S,
                           uplink_sample_every=10)
    with Phase("sharded", clock) as ph:
        check(sweep.sweep_devices() == len(jax.devices()),
              "the sweep does not shard over every local device")
        sharded, _ = sweep.run_batch(topo, cfg, traces)
        os.environ["REPRO_SWEEP_DEVICES"] = "1"
        try:
            single, _ = sweep.run_batch(topo, cfg, traces)
        finally:
            del os.environ["REPRO_SWEEP_DEVICES"]
        same = [bool(np.array_equal(a.finish, b.finish)) for a, b in zip(sharded, single)]
        ph.fields.update(devices=len(jax.devices()), batch=batch,
                         flows=int(sum(t.valid.sum() for t in traces)),
                         identical_finish=same,
                         seqbalance=_stats(sharded[0], traces[0], topo))
        check(all(same), "sharded finish arrays differ from one device")


def phase_allreduce(clock):
    """4 chips: seqbalance_all_reduce vs lax.psum on a 1-D pod mesh."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.dist.collectives import PathPlan, seqbalance_all_reduce

    n, per_device = len(jax.devices()), 1 << 20  # 4 MB of f32 per device
    mesh = jax.make_mesh((n,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    # integer-valued f32: every partial sum is exact, so any ring order
    # must reproduce psum bit for bit
    x = jax.random.randint(jax.random.PRNGKey(0), (n, per_device), -1000, 1000)
    x = x.astype(jnp.float32)

    def run(fn):
        g = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("pod"),
                                  out_specs=P("pod")))
        return np.asarray(g(x))

    with Phase("allreduce", clock) as ph:
        want = run(lambda v: jax.lax.psum(v, "pod"))
        got = {w: run(lambda v, w=w: seqbalance_all_reduce(
            v, "pod", PathPlan(n_chunks=4, wire_dtype=w)))
            for w in ("float32", "bfloat16")}
        # bf16 wire: each of the ring's n hops rounds a partial sum to 8
        # mantissa bits, so the error is at most n * 2^-8 * sum_d |x_d|
        bound = n * 2.0 ** -8 * np.abs(np.asarray(x)).sum(0, keepdims=True)
        bf16_ratio = float(np.max(np.abs(got["bfloat16"] - want) / np.maximum(bound, 1.0)))
        ph.fields.update(devices=n, bytes_per_device=per_device * 4, flows=0,
                         f32_max_abs_err=float(np.max(np.abs(got["float32"] - want))),
                         bf16_err_over_bound=bf16_ratio)
        check(np.array_equal(got["float32"], want), "f32 wire differs from psum")
        check(bf16_ratio <= 1.0, "bf16 wire error beyond its rounding bound")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees {len(devices)} chips")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.netsim.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    if args.chips == 1:
        for phase in (phase_kernel, phase_fig12, phase_fig14, phase_cosim,
                      phase_oracle):
            phase(clock)
    else:
        phase_sharded(clock)
        phase_allreduce(clock)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}}))


if __name__ == "__main__":
    main()
