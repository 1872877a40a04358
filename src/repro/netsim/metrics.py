"""FCT / slowdown / imbalance metrics (paper §IV performance metrics)."""
from __future__ import annotations

import numpy as np

from repro.netsim.engine import SimState, StepOutputs
from repro.netsim.topology import Topology
from repro.netsim.workloads import Trace


def fct_stats(state: SimState, trace: Trace, topo: Topology, host_bw: float) -> dict:
    """FCT slowdown: actual FCT normalized to the FCT in an idle network
    (serialization at host line rate + one base RTT)."""
    finish = np.asarray(state.finish)
    arrivals = trace.arrivals
    done = np.isfinite(finish) & trace.valid
    if not done.any():
        return dict(n=0, avg_fct=np.nan, p99_fct=np.nan, avg_slowdown=np.nan,
                    p99_slowdown=np.nan, completion_rate=0.0)
    fct = finish[done] - arrivals[done]
    ideal = trace.sizes[done] * 8.0 / host_bw + topo.base_rtt_s
    slow = fct / ideal
    return dict(
        n=int(done.sum()),
        completion_rate=float(done.sum() / max(trace.valid.sum(), 1)),
        avg_fct=float(fct.mean()),
        p99_fct=float(np.percentile(fct, 99)),
        avg_slowdown=float(slow.mean()),
        p99_slowdown=float(np.percentile(slow, 99)),
    )


def throughput_imbalance(outs: StepOutputs, sample_every: int = 10, *,
                         trace_stride: int = 1) -> np.ndarray:
    """Paper's imbalance metric per ToR: (max uplink tput - min)/avg, sampled
    every ``sample_every`` steps (=100 us at dt=10 us).  Returns the flat
    sample population (for CDF plotting).  ToR/sample points with zero
    traffic are dropped.

    ``trace_stride`` is the window-averaging the engine already applied to
    ``outs.uplink_load`` (``SimConfig.uplink_sample_every``); the remaining
    averaging window here is ``sample_every // trace_stride``."""
    assert sample_every % max(trace_stride, 1) == 0, (
        f"engine stride {trace_stride} must divide sample_every "
        f"{sample_every} or the imbalance windows silently shift")
    up = np.asarray(outs.uplink_load)  # [T / trace_stride, L, S]
    k = max(1, sample_every // max(trace_stride, 1))
    T = (up.shape[0] // k) * k
    up = up[:T].reshape(-1, k, *up.shape[1:]).mean(axis=1)  # [T', L, S]
    avg = up.mean(axis=-1)
    imb = (up.max(axis=-1) - up.min(axis=-1)) / np.maximum(avg, 1e-9)
    return imb[avg > 1e6].ravel()


def fct_samples(state, trace: Trace,
                horizon_s: float | None = None) -> tuple[np.ndarray, float]:
    """Per-flow FCT population for CDFs / convergence curves.

    Unlike ``fct_stats`` (completed flows only), flows still unfinished at
    the end of the horizon are CENSORED at it (fct = horizon - arrival)
    rather than dropped: a killed spine starves its flows outright, and a
    p99 over survivors would report the disaster epoch as healthy.  Returns
    (fct[n_valid], completion_rate); with ``horizon_s=None`` unfinished
    flows keep +inf (caller beware of percentile poisoning).
    """
    finish = np.asarray(state.finish)
    valid = np.asarray(trace.valid, bool)
    arrivals = np.asarray(trace.arrivals)
    done = np.isfinite(finish) & valid
    completion = float(done.sum() / max(valid.sum(), 1))
    f = finish[valid]
    if horizon_s is not None:
        f = np.minimum(f, np.float32(horizon_s))
    return (f - arrivals[valid]).astype(np.float64), completion


def congestion_packet_bandwidth(state: SimState, duration_s: float,
                                pkt_bytes: float = 64.0) -> float:
    """Table II: bps consumed by mirrored Congestion Packets."""
    return float(state.cnp_pkts) * pkt_bytes * 8.0 / duration_s


def port_rate_timeseries(outs: StepOutputs, leaf: int, dt: float,
                         window_s: float = 1e-3, *,
                         trace_stride: int = 1) -> np.ndarray:
    """Per-uplink offered rate for one leaf, window-averaged (Fig. 10/11).
    ``trace_stride`` = window-averaging already applied by the engine."""
    steps = int(window_s / dt)
    assert steps % max(trace_stride, 1) == 0, (
        f"engine stride {trace_stride} must divide the {steps}-step window "
        f"or the rate windows silently shift")
    up = np.asarray(outs.uplink_load)[:, leaf, :]  # [T / trace_stride, S]
    k = max(1, steps // max(trace_stride, 1))
    T = (up.shape[0] // k) * k
    return up[:T].reshape(-1, k, up.shape[1]).mean(axis=1)
