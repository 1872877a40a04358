"""Quiescence occupancy of the compact engine (benchmarks/run.py --profile).

``quiescence_profile`` replays a fixed-dt run chunk by chunk and records
which chunk boundaries the adaptive engine would have fast-forwarded — the
quiescence occupancy (fraction of the horizon coverable in closed form) and
the macro-step length histogram — and times the predicate jitted alone.

Where the step's device time goes, phase by phase, is read from a profiler
trace of the real fused program instead: each phase runs under a named
scope (``obs.scopes``), and the benchmark sums device time per scope
(``bench/harness/phases.py``).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.netsim import compact
from repro.netsim.engine import SimConfig
from repro.netsim.topology import Topology
from repro.netsim.workloads import Trace


class TimeUs(float):
    """A per-call time in µs that is still a float (the value is the MIN
    over iterations — the least-noise estimator)
    but carries the full per-iteration sample distribution for the flight
    log / bench JSON: ``.min_us`` / ``.mean_us`` / ``.std_us`` /
    ``.samples``, or all four via ``.stats()``."""

    __slots__ = ("samples",)

    def __new__(cls, samples):
        samples = [float(s) for s in samples]
        self = super().__new__(cls, min(samples))
        self.samples = samples
        return self

    @property
    def min_us(self) -> float:
        return float(self)

    @property
    def mean_us(self) -> float:
        return sum(self.samples) / len(self.samples)

    @property
    def std_us(self) -> float:
        m = self.mean_us
        return (sum((s - m) ** 2 for s in self.samples)
                / len(self.samples)) ** 0.5

    def stats(self) -> dict:
        """JSON-able {min_us, mean_us, std_us, iters}."""
        return dict(min_us=round(self.min_us, 3),
                    mean_us=round(self.mean_us, 3),
                    std_us=round(self.std_us, 3), iters=len(self.samples))


def _time_us(fn, *args, iters: int) -> TimeUs:
    jax.block_until_ready(fn(*args))  # compile + warm
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append((time.perf_counter() - t0) * 1e6)
    return TimeUs(samples)


def quiescence_profile(
    topo: Topology, cfg: SimConfig, trace: Trace, *, iters: int = 30,
) -> dict:
    """Quiescence occupancy of one sim: replay the fixed-dt trajectory in
    scan chunks, evaluating the adaptive engine's predicate at every chunk
    boundary (without fast-forwarding, so the trajectory stays the exact
    oracle).  Returns:

      ff_fraction   — fraction of the horizon whose chunks were quiescent
                      (what adaptive mode would cover in closed form)
      macro_hist    — {macro-step length in dt steps: count} from runs of
                      consecutive quiescent chunks
      predicate_us  — one predicate evaluation, jitted in isolation (the
                      per-chunk overhead adaptive mode pays on top of the
                      scan)
      chunk_steps / n_chunks — the event-grid geometry used
    """
    arrays, _, F = compact.sort_trace(trace)
    F_pad = max(F, 1)
    W, A = compact.plan_single_window(topo, cfg, arrays, F_pad)
    jarrays = tuple(jnp.asarray(a) for a in arrays)
    _, step_fn, phases = compact.build_compact_sim(topo, cfg, jarrays, W,
                                                   F_pad, A)
    n_steps = int(round(cfg.duration_s / cfg.dt))
    K, n_chunks, _ = compact.plan_chunks(cfg, n_steps)
    quiesce = phases["quiesce"]

    @jax.jit
    def replay(st):
        def one(st, _):
            quiet = quiesce(st, K)
            st2, _ = jax.lax.scan(step_fn, st, None, length=K)
            return st2, quiet

        return jax.lax.scan(one, st, None, length=n_chunks)[1]

    st0 = compact.init_compact_state(topo, cfg, W, F_pad)
    quiet = np.asarray(jax.block_until_ready(replay(st0)))
    hist: dict[int, int] = {}
    run = 0
    for q in list(quiet) + [False]:  # trailing False flushes the last run
        if q:
            run += 1
        elif run:
            hist[run * K] = hist.get(run * K, 0) + 1
            run = 0
    pred = jax.jit(lambda s: quiesce(s, K))
    return {
        "ff_fraction": float(quiet.mean()) if quiet.size else 0.0,
        "macro_hist": hist,
        "predicate_us": _time_us(pred, st0, iters=iters),
        "chunk_steps": K,
        "n_chunks": n_chunks,
    }
