"""One benchmark per paper table/figure (DESIGN.md §7 index).

Each function prints ``name,us_per_call,derived`` CSV rows.  ``fast=True``
(default) runs reduced durations/scales that preserve the paper's trends;
``--full`` in run.py uses the paper-scale parameters.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import (
    PERF, emit, fct, run_sim, run_sim_batch, run_sim_jobs, timed,
)


# ------------------------------------------------------------- Table I
def bench_table1_gbn(fast=True):
    import jax.numpy as jnp
    from repro.core import gbn

    sizes = jnp.array([64e3, 1e6], jnp.float32)
    (ratios, us) = timed(lambda: np.asarray(gbn.table1_inflation(sizes)), repeat=10)
    emit("table1_gbn_64KB_avg_inflation", us, f"{ratios[0]:.2f}x_paper_5.77x")
    emit("table1_gbn_1MB_avg_inflation", us, f"{ratios[1]:.2f}x_paper_3.01x")
    emit("table1_min_threefold", us, f"min_inflation_{ratios.min():.2f}_paper_claims_>=3x")


# ------------------------------------------------------------- Fig. 1
def bench_fig1_flowlet(fast=True):
    """Flowlet sizes under inactivity thresholds: TCP (bursty, ack-clocked)
    vs RDMA (continuous line-rate).  Packet-trace synthesis + gap scan."""
    rng = np.random.default_rng(0)
    mtu = 1500.0
    line = 40e9

    def flowlet_sizes(inter_arrival_s, thresh):
        gaps = inter_arrival_s > thresh
        sizes, cur = [], 0.0
        for g in gaps:
            cur += mtu
            if g:
                sizes.append(cur)
                cur = 0.0
        if cur:
            sizes.append(cur)
        return np.array(sizes)

    n = 40000 if fast else 400000
    # TCP: cwnd-sized bursts every RTT (100us), ack-clocked spacing inside
    rtt = 100e-6
    cwnd = 64
    intra = mtu * 8 / line
    tcp_ia = np.tile(np.r_[np.full(cwnd - 1, intra), rtt - (cwnd - 1) * intra], n // cwnd)
    # RDMA: continuous line-rate stream with tiny jitter
    rdma_ia = np.full(n, intra) * rng.uniform(0.9, 1.1, n)

    def med(ia, th):
        s = flowlet_sizes(ia, th)
        return float(np.median(s)) if len(s) else float(ia.size * mtu)

    for th_us in (10, 100, 500):
        th = th_us * 1e-6
        (m_tcp, us) = timed(med, tcp_ia, th)
        m_rdma = med(rdma_ia, th)
        emit(f"fig1_flowlet_tcp_{th_us}us", us, f"median_{m_tcp/1e3:.1f}KB")
        emit(f"fig1_flowlet_rdma_{th_us}us", us,
             f"median_{m_rdma/1e6:.1f}MB_ratio_{m_rdma/max(m_tcp,1):.0f}x")


# ---------------------------------------------------------- Fig. 6 / 7
def bench_fig6_fig7_nsweep(fast=True):
    from repro.netsim import metrics, topology, workloads

    topo = topology.leaf_spine(4, 8, 8, 100e9)
    dur = 5e-3 if fast else 20e-3
    trace = workloads.poisson_trace(workloads.TraceConfig(
        workload="fixed:10e6", load=0.6, duration_s=dur, n_hosts=topo.n_hosts,
        host_bw=100e9, seed=3, hosts_per_leaf=topo.hosts_per_leaf,
        load_base_bw=4 * 8 * 100e9,
    ))
    base = None
    for n in (1, 2, 4, 6):
        st, outs, us = run_sim(topo, trace, "seqbalance", dur * 4, n_sub=n)
        s = fct(st, trace, topo, 100e9)
        imb = float(np.median(metrics.throughput_imbalance(outs)))
        if n == 2:
            base = s["avg_slowdown"]
        rel = "" if base is None else f"_vs_N2_{(1 - s['avg_slowdown']/base)*100:+.1f}%"
        emit(f"fig6_fct_N{n}", us,
             f"avg_slow_{s['avg_slowdown']:.3f}_p99_{s['p99_slowdown']:.2f}{rel}")
        emit(f"fig7_imbalance_N{n}", us, f"median_imbalance_{imb:.3f}")


# ---------------------------------------------------------- Fig. 10/11
def _pairs_trace(n_qp=4, size=1e12, starts=(0.0, 5e-3, 10e-3)):
    from repro.netsim import workloads

    pairs, st = [], []
    for i, t0 in enumerate(starts):
        for _ in range(n_qp):
            pairs.append((i, 3 + i))
            st.append(t0)
    return workloads.permanent_senders_trace(pairs, st, size / n_qp)


def _dc40():
    from repro.netsim.dcqcn import DCQCNParams

    return DCQCNParams(kmin_bytes=160e3, kmax_bytes=520e3, r_ai=400e6, min_rate=400e6)


def bench_fig10_symmetric(fast=True):
    from repro.netsim import topology

    topo = topology.testbed_symmetric()
    for scheme in ("ecmp", "seqbalance"):
        st, outs, us = run_sim(topo, _pairs_trace(), scheme, 15e-3, dcqcn=_dc40())
        up = np.asarray(outs.uplink_load)[:, 0, :]
        late = up[1000:].mean(0) / 1e9
        tot = late.sum()
        spread = late.max() - late.min()
        emit(f"fig10_sym_{scheme}", us,
             f"total_{tot:.1f}Gbps_perpath_{'/'.join(f'{v:.0f}' for v in late)}_spread_{spread:.1f}")


def bench_fig11_asymmetric(fast=True):
    from repro.netsim import topology

    topo = topology.testbed_asymmetric()
    res = {}
    for scheme in ("ecmp", "seqbalance"):
        st, outs, us = run_sim(topo, _pairs_trace(), scheme, 15e-3, dcqcn=_dc40())
        up = np.asarray(outs.uplink_load)[:, 0, :]
        late = up[1000:].mean(0) / 1e9
        res[scheme] = late
        emit(f"fig11_asym_{scheme}", us,
             f"total_{late.sum():.1f}Gbps_fatpath_{late[2]:.1f}Gbps")
    fat_gain = res["seqbalance"][2] / max(res["ecmp"][2], 1e-9)
    emit("fig11_asym_fatpath_gain", 0.0, f"seqbalance_uses_80G_path_{fat_gain:.2f}x_of_ecmp")


# ------------------------------------------------------------- Table II
def bench_table2_overhead(fast=True):
    from repro.netsim import metrics, topology, workloads

    topo = topology.testbed_symmetric()
    for nsend, label in ((1, 25), (2, 50), (3, 75)):
        pairs = [(i, 3 + i) for i in range(nsend) for _ in range(4)]
        trace = workloads.permanent_senders_trace(pairs, [0.0] * len(pairs), 2.5e8)
        st, outs, us = run_sim(topo, trace, "seqbalance", 10e-3, dcqcn=_dc40())
        bw = metrics.congestion_packet_bandwidth(st, 10e-3)
        data_bw = np.asarray(outs.goodput_total).mean()
        emit(f"table2_load{label}", us,
             f"cong_pkt_{bw/1e3:.2f}Kbps_data_{data_bw/1e9:.1f}Gbps_paper_0/4Kbps/0.05Gbps")


# ---------------------------------------------------- Fig. 12/13 (2-tier)
def _poisson(topo, wl, load, dur, seed=1):
    from repro.netsim import workloads

    fabric = topo.n_leaf * topo.n_paths * 100e9
    return workloads.poisson_trace(workloads.TraceConfig(
        workload=wl, load=load, duration_s=dur, n_hosts=topo.n_hosts,
        host_bw=100e9, seed=seed, hosts_per_leaf=topo.hosts_per_leaf,
        load_base_bw=fabric,
    ))


def fig12_cases(fast=True):
    loads = (0.5, 0.8) if fast else (0.3, 0.4, 0.5, 0.6, 0.7, 0.8)
    return [(wl, load) for wl in ("alistorage", "websearch") for load in loads]


def bench_fig12_fct_2tier(fast=True):
    from repro.netsim import topology

    topo = topology.sim_2tier()
    arr = 2.5e-3 if fast else 10e-3
    cases = fig12_cases(fast)
    traces = {c: _poisson(topo, c[0], c[1], arr) for c in cases}
    # drill first: its spill-retry makes it the longest job by far, so it
    # anchors one worker while the cheap schemes pack onto the others
    schemes = ("drill", "ecmp", "seqbalance", "letflow", "conga")
    # one vmapped sweep job per scheme over every (workload, load) trace,
    # all five jobs running concurrently; FCT-only consumers sample the
    # uplink trace at the imbalance stride instead of materializing [T,L,S]
    results, us = run_sim_jobs(topo, [traces[c] for c in cases], schemes, arr * 4,
                               uplink_sample_every=10)
    stats = {}
    for scheme in schemes:
        for c, (st, outs) in zip(cases, results[scheme]):
            stats[(scheme, c)] = fct(st, traces[c], topo, 100e9)
        for c in cases:
            s = stats[(scheme, c)]
            emit(f"fig12_{c[0]}_{int(c[1]*100)}_{scheme}",
                 us / (len(cases) * len(schemes)),
                 f"avg_slow_{s['avg_slowdown']:.2f}_p99_{s['p99_slowdown']:.1f}_comp_{s['completion_rate']:.3f}")
    for c in cases:
        g = (1 - stats[("seqbalance", c)]["p99_slowdown"]
             / stats[("ecmp", c)]["p99_slowdown"]) * 100
        emit(f"fig12_{c[0]}_{int(c[1]*100)}_gain", 0.0, f"seq_vs_ecmp_p99_{g:+.1f}%")


def bench_fig13_imbalance(fast=True):
    from repro.netsim import metrics, topology

    topo = topology.sim_2tier()
    arr = 2e-3 if fast else 10e-3
    wls = ("alistorage", "websearch")
    schemes = ("drill", "ecmp", "seqbalance", "conga")  # longest job first
    traces = [_poisson(topo, wl, 0.8, arr) for wl in wls]
    results, us = run_sim_jobs(topo, traces, schemes, arr * 2,
                               uplink_sample_every=10)
    for scheme in schemes:
        for wl, (st, outs) in zip(wls, results[scheme]):
            imb = metrics.throughput_imbalance(outs, trace_stride=10)
            med = float(np.median(imb)) if len(imb) else -1
            p90 = float(np.percentile(imb, 90)) if len(imb) else -1
            emit(f"fig13_{wl}_{scheme}", us / (len(wls) * len(schemes)),
                 f"imb_median_{med:.3f}_p90_{p90:.3f}")


# ------------------------------------------------------- Fig. 14 (3-tier)
def bench_fig14_fct_3tier(fast=True):
    from repro.netsim import topology, workloads

    if fast:
        topo = topology.three_tier(n_tor=4, n_agg=4, n_core=2, hosts_per_tor=3,
                                   bw_tor_agg=400e9, bw_agg_core=100e9)
    else:
        topo = topology.three_tier()  # paper scale: 20/20/16, 320 hosts
    arr = 1.5e-3 if fast else 8e-3
    fabric = topo.n_leaf * 4 * 100e9
    wls = ("alistorage", "websearch")
    traces = [workloads.poisson_trace(workloads.TraceConfig(
        workload=wl, load=0.6, duration_s=arr, n_hosts=topo.n_hosts,
        host_bw=100e9, seed=2, hosts_per_leaf=topo.hosts_per_leaf,
        load_base_bw=fabric,
    )) for wl in wls]
    schemes = ("ecmp", "letflow", "seqbalance")
    results, us = run_sim_jobs(topo, traces, schemes, arr * 4)
    stats = {}
    for scheme in schemes:
        for wl, trace, (st, outs) in zip(wls, traces, results[scheme]):
            s = fct(st, trace, topo, 100e9)
            stats[(scheme, wl)] = s
            emit(f"fig14_{wl}_{scheme}", us / (len(wls) * len(schemes)),
                 f"avg_slow_{s['avg_slowdown']:.2f}_p99_{s['p99_slowdown']:.1f}")
    for wl in wls:
        g = (1 - stats[("seqbalance", wl)]["p99_slowdown"]
             / stats[("ecmp", wl)]["p99_slowdown"]) * 100
        emit(f"fig14_{wl}_gain", 0.0, f"seq_vs_ecmp_p99_{g:+.1f}%")


# ------------------------------------------------- §Perf (DESIGN.md §9)
def bench_netsim_speedup(fast=True):
    """Acceptance bench: the Fig. 12 fast sweep on the active-window
    vmapped engine vs the dense oracle — wall clock, per-step cost, and the
    FCT-slowdown agreement between the two.  Records PERF["fig12_sweep"]
    for BENCH_netsim.json."""
    import time

    from repro.netsim import sweep, topology

    topo = topology.sim_2tier()
    arr = 2.5e-3 if fast else 10e-3
    dur = arr * 4
    cases = fig12_cases(fast)
    schemes = ("drill", "ecmp", "seqbalance", "letflow", "conga")  # longest first
    traces = {c: _poisson(topo, c[0], c[1], arr) for c in cases}
    n_steps = int(round(dur / 10e-6))
    n_sims = len(cases) * len(schemes)

    sweep.clear_cache()  # time cold compiles like the dense path pays them
    t0 = time.time()
    compact_stats, spill = {}, 0
    results, _ = run_sim_jobs(topo, [traces[c] for c in cases], schemes, dur,
                              uplink_sample_every=10)
    for scheme in schemes:
        for c, (st, _) in zip(cases, results[scheme]):
            compact_stats[(scheme, c)] = fct(st, traces[c], topo, 100e9)
            spill = max(spill, st.spill_steps)
    compact_wall = time.time() - t0

    t0 = time.time()
    dense_stats = {}
    for scheme in schemes:
        for c in cases:
            st, _, _ = run_sim(topo, traces[c], scheme, dur, dense=True)
            dense_stats[(scheme, c)] = fct(st, traces[c], topo, 100e9)
    dense_wall = time.time() - t0

    diffs = {}
    for key in compact_stats:
        for stat in ("avg_slowdown", "p99_slowdown"):
            d = abs(compact_stats[key][stat] / dense_stats[key][stat] - 1) * 100
            diffs[f"{key[0]}_{key[1][0]}_{int(key[1][1]*100)}_{stat}"] = d
    max_diff = max(diffs.values())
    speedup = dense_wall / compact_wall
    emit("netsim_sweep_compact", compact_wall * 1e6 / n_sims,
         f"wall_{compact_wall:.1f}s_{n_sims}sims_per_step_us_{compact_wall*1e6/(n_sims*n_steps):.1f}")
    emit("netsim_sweep_dense", dense_wall * 1e6 / n_sims,
         f"wall_{dense_wall:.1f}s_per_step_us_{dense_wall*1e6/(n_sims*n_steps):.1f}")
    emit("netsim_sweep_speedup", 0.0,
         f"{speedup:.1f}x_max_stat_diff_{max_diff:.3f}%_spill_{spill}")
    PERF["fig12_sweep"] = dict(
        fast=fast, n_sims=n_sims, n_steps=n_steps,
        compact_wall_s=round(compact_wall, 2), dense_wall_s=round(dense_wall, 2),
        speedup=round(speedup, 2),
        per_step_us_compact=round(compact_wall * 1e6 / (n_sims * n_steps), 2),
        per_step_us_dense=round(dense_wall * 1e6 / (n_sims * n_steps), 2),
        max_stat_diff_pct=round(max_diff, 4), spill_steps=int(spill),
        stat_diff_pct={k: round(v, 4) for k, v in diffs.items()},
    )
    # reproducibility: how the sweep was dispatched on this machine
    from repro.netsim import compile_cache, dataplane

    PERF["sweep_config"] = dict(
        workers=sweep.default_workers(len(schemes)),
        dataplane_backend=dataplane.resolve_backend("auto"),
        devices=sweep.sweep_devices(),
        # persistent XLA compile cache: the recorded sweep is warm from the
        # second process on (production sweeps relaunch identical programs)
        compile_cache=compile_cache.enable_compile_cache(),
    )


# ------------------------------------------- adaptive dt (DESIGN.md §15)
def _collective_setup():
    """The sparse AI-training workload the adaptive engine targets: a
    ring all-reduce with 800 µs compute gaps between rounds — most chunk
    boundaries are quiescent (flows done, queues drained, next round's
    arrival still in the future)."""
    from repro.dist import collectives, cosim
    from repro.netsim import topology, workloads
    from repro.netsim.engine import SimConfig

    topo = topology.leaf_spine(4, 4, 4, 100e9)
    hosts = cosim.ring_hosts(topo, 8)
    plan = collectives.PathPlan(n_chunks=4, directions=(1, -1, 1, -1))
    trace = workloads.collective_trace(plan, hosts, 4e6, link_bw=100e9,
                                       round_gap_s=800e-6, seed=0,
                                       steer_paths=topo.n_paths)
    cfg = SimConfig(scheme="seqbalance", duration_s=14e-3,
                    uplink_sample_every=10)
    return topo, cfg, trace


def bench_adaptive_dt(fast=True):
    """Acceptance bench for the event-driven adaptive-dt engine
    (DESIGN.md §15).  Two workload regimes, both adaptive-vs-fixed-dt on
    the SAME compact engine (warm executables — this isolates the step
    loop, not compile time):

      * sparse collective trace — rounds separated by compute gaps; the
        quiescence fast-forward must cover the gaps (>= 2x wall clock);
      * the Fig. 12 fast sweep — loaded Poisson traffic where every chunk
        contains arrivals or finishes, so nothing CAN fast-forward; the
        predicate short-circuit must keep adaptive at parity (the floor
        guards the overhead, not a win).

    Also records the adaptive-vs-fixed FCT stat divergence (tolerance
    model: <= 0.01 %) and the executable-reuse contract (zero cache builds
    after the first adaptive dispatch of each shape).  The recorded
    ``floors`` are what scripts/check_bench.py --adaptive gates future
    runs against."""
    import dataclasses
    import time

    from repro.netsim import sweep

    topoL, cfg_f, trc = _collective_setup()
    cfg_a = dataclasses.replace(cfg_f, adaptive=True)
    iters = 3 if fast else 5

    def wall_one(topo, c, tr):
        res, _ = sweep.run_one(topo, c, tr)  # compile + warm
        t0 = time.time()
        for _ in range(iters):
            res, _ = sweep.run_one(topo, c, tr)
        return (time.time() - t0) / iters, res

    sweep.clear_cache()
    wall_f, res_f = wall_one(topoL, cfg_f, trc)
    wall_a, res_a = wall_one(topoL, cfg_a, trc)
    builds_warm = sweep.cache_stats()["builds"]
    sweep.run_one(topoL, cfg_a, trc)
    rebuilds = sweep.cache_stats()["builds"] - builds_warm

    n_steps = int(round(cfg_f.duration_s / cfg_f.dt))
    stats_f = fct(res_f, trc, topoL, 100e9)
    stats_a = fct(res_a, trc, topoL, 100e9)
    col_diff = max(
        abs(stats_a[s] / stats_f[s] - 1) * 100
        for s in ("avg_slowdown", "p99_slowdown"))
    col_speedup = wall_f / wall_a
    ff = int(res_a.ff_steps)
    emit("adaptive_collective_speedup", wall_a * 1e6,
         f"{col_speedup:.2f}x_ff_{ff}of{n_steps}_stat_diff_{col_diff:.4f}%")

    # Fig. 12 fast sweep, warm-vs-warm (the fixed-dt cold-compile cost is
    # already recorded in PERF["fig12_sweep"])
    from repro.netsim import topology

    topo2 = topology.sim_2tier()
    arr = 2.5e-3 if fast else 10e-3
    dur = arr * 4
    cases = fig12_cases(fast)
    schemes = ("drill", "ecmp", "seqbalance", "letflow", "conga")
    traces = {c: _poisson(topo2, c[0], c[1], arr) for c in cases}

    def sweep_once(**cfg_kw):
        t0 = time.time()
        results, _ = run_sim_jobs(topo2, [traces[c] for c in cases], schemes,
                                  dur, uplink_sample_every=10, **cfg_kw)
        wall = time.time() - t0
        stats, ff_total = {}, 0
        for scheme in schemes:
            for c, (st, _) in zip(cases, results[scheme]):
                stats[(scheme, c)] = fct(st, traces[c], topo2, 100e9)
                ff_total += int(getattr(st, "ff_steps", 0))
        return wall, stats, ff_total

    # warm both variants, then interleave and keep the per-variant minimum
    # — worker-thread contention spikes hit whichever sweep is running,
    # so back-to-back single measurements systematically smear the ratio
    sweep_once()
    sweep_once(adaptive=True)
    fig_wall_f, fig_wall_a = float("inf"), float("inf")
    for _ in range(2):
        w, fig_stats_f, _ = sweep_once()
        fig_wall_f = min(fig_wall_f, w)
        w, fig_stats_a, fig_ff = sweep_once(adaptive=True)
        fig_wall_a = min(fig_wall_a, w)
    fig_diff = max(
        abs(fig_stats_a[k][s] / fig_stats_f[k][s] - 1) * 100
        for k in fig_stats_f for s in ("avg_slowdown", "p99_slowdown"))
    fig_speedup = fig_wall_f / fig_wall_a
    emit("adaptive_fig12_sweep", fig_wall_a * 1e6 / (len(cases) * len(schemes)),
         f"{fig_speedup:.2f}x_vs_fixed_ff_{fig_ff}_stat_diff_{fig_diff:.4f}%")
    emit("adaptive_rebuilds_after_first", 0.0, f"{rebuilds}_new_executables")

    max_diff = max(col_diff, fig_diff)
    PERF["adaptive_dt"] = dict(
        fast=fast,
        collective=dict(
            fixed_wall_s=round(wall_f, 3), adaptive_wall_s=round(wall_a, 3),
            speedup=round(col_speedup, 2), ff_steps=ff, n_steps=n_steps,
            ff_fraction=round(ff / n_steps, 3),
            max_stat_diff_pct=round(col_diff, 4)),
        fig12=dict(
            fixed_wall_s=round(fig_wall_f, 2),
            adaptive_wall_s=round(fig_wall_a, 2),
            speedup=round(fig_speedup, 2), ff_steps=fig_ff,
            max_stat_diff_pct=round(fig_diff, 4)),
        max_stat_diff_pct=round(max_diff, 4),
        rebuilds_after_first=int(rebuilds),
        # gate floors (scripts/check_bench.py --adaptive): the collective
        # win is the acceptance bar; the fig12 floor guards predicate
        # overhead on event-dense traffic, where ff_steps == 0 by design
        # (every chunk has arrivals/finishes — there is nothing to skip,
        # so parity IS the win; see DESIGN.md §15)
        floors=dict(collective_speedup=2.0, fig12_speedup=0.85),
    )


# ------------------------------------------- --profile (run.py flag)
def bench_quiescence_profile(fast=True):
    """Quiescence occupancy of the compact engine (DESIGN.md §15).  Not
    part of ALL — enabled by ``run.py --profile``.  Per-phase step time is
    read from a profiler trace on the chip (``bench/``), not timed here."""
    from repro.netsim import profile, topology
    from repro.netsim.engine import SimConfig

    topo = topology.sim_2tier()
    arr = 2.5e-3 if fast else 10e-3
    trace = _poisson(topo, "alistorage", 0.8, arr)
    record = {}

    # quiescence occupancy (DESIGN.md §15): replay the fixed-dt oracle and
    # record which chunk boundaries the adaptive engine would fast-forward
    # — the sparse collective trace (where the win lives) and the dense
    # fig12 trace (where the occupancy shows why there is none)
    topoL, cfgL, trcL = _collective_setup()
    for name, (t_, c_, tr_) in (
            ("collective", (topoL, cfgL, trcL)),
            ("fig12_ali80", (topo, SimConfig(scheme="seqbalance",
                                             duration_s=arr * 4,
                                             uplink_sample_every=10), trace))):
        q = profile.quiescence_profile(t_, c_, tr_)
        hist = "/".join(f"{k}x{v}" for k, v in sorted(q["macro_hist"].items()))
        emit(f"profile_quiescence_{name}", q["predicate_us"],
             f"ff_fraction_{q['ff_fraction']:.3f}_macro_hist_{hist or 'none'}"
             f"_K_{q['chunk_steps']}")
        pred = q["predicate_us"]
        record[f"quiescence_{name}"] = dict(
            ff_fraction=round(q["ff_fraction"], 4),
            predicate_us=pred.stats() if isinstance(pred, profile.TimeUs)
            else round(pred, 2),
            macro_hist={str(k): v for k, v in sorted(q["macro_hist"].items())},
            chunk_steps=q["chunk_steps"], n_chunks=q["n_chunks"])
    PERF["profile"] = record


ALL = [
    bench_table1_gbn,
    bench_fig1_flowlet,
    bench_fig6_fig7_nsweep,
    bench_fig10_symmetric,
    bench_fig11_asymmetric,
    bench_table2_overhead,
    bench_fig12_fct_2tier,
    bench_fig13_imbalance,
    bench_fig14_fct_3tier,
    bench_netsim_speedup,
    bench_adaptive_dt,
]
