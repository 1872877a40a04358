"""The benchmark's own traffic generator: Poisson arrivals of flows whose
sizes follow a published CDF, between hosts on different racks.

A copy of the simulator's ``workloads.poisson_trace`` and its two CDFs,
kept here so that the traffic of a benchmark cell cannot change with the
program.  WebSearch is from DCTCP (Alizadeh et al., SIGCOMM'10), the
Alibaba storage mix from HPCC (Li et al., SIGCOMM'19), as the SeqBalance
paper's Fig. 9 cites them.  Rates follow

    lambda = load * load_base_bps / (8 * mean_size_bytes)

where ``load_base_bps`` is the fabric's bisection capacity for the paper's
"load" (mean fabric utilisation).
"""
from __future__ import annotations

import numpy as np

# (size_bytes, cumulative_probability)
CDFS = {
    "websearch": np.array(
        [(1_000, 0.00), (10_000, 0.15), (20_000, 0.20), (30_000, 0.30),
         (50_000, 0.40), (80_000, 0.53), (200_000, 0.60), (1_000_000, 0.70),
         (2_000_000, 0.80), (5_000_000, 0.90), (10_000_000, 0.97),
         (30_000_000, 1.00)], np.float64),
    "alistorage": np.array(
        [(1_000, 0.00), (2_000, 0.10), (4_000, 0.30), (8_000, 0.50),
         (16_000, 0.65), (32_000, 0.80), (64_000, 0.90), (100_000, 0.95),
         (256_000, 0.98), (1_000_000, 0.99), (2_000_000, 1.00)], np.float64),
}


def cdf_mean(cdf: np.ndarray) -> float:
    """Mean flow size of the piecewise-linear CDF."""
    mids = (cdf[1:, 0] + cdf[:-1, 0]) / 2
    return float((mids * np.diff(cdf[:, 1])).sum())


def poisson_flows(gen: dict, n_hosts: int, hosts_per_leaf: int, work_seed: int) -> dict:
    """One trace as arrays: sizes f32 bytes, arrivals f32 s, src/dst i32,
    flow_id u32.  ``gen`` is a traffic file's ``generator`` block.  Every
    array, the hosts and flow ids (hence the paths the flows hash to)
    included, comes from ``work_seed`` (below 2**32), so that a pool slot
    holds the same work in every run."""
    rng = np.random.default_rng(work_seed)
    cdf = CDFS[gen["workload"]]
    lam = gen["load"] * gen["load_base_bps"] / (8.0 * cdf_mean(cdf))
    duration = gen["arrivals_s"]
    n = max(1, int(lam * duration * 1.05) + 16)
    arrivals = np.cumsum(rng.exponential(1.0 / lam, n))
    arrivals = arrivals[arrivals < duration].astype(np.float32)
    n = len(arrivals)
    sizes = np.interp(rng.uniform(0.0, 1.0, n), cdf[:, 1], cdf[:, 0]).astype(np.float32)
    src = rng.integers(0, n_hosts, n).astype(np.int32)
    # destinations on another rack: redraw, then shift any survivor one leaf on
    dst = rng.integers(0, n_hosts, n).astype(np.int32)
    hpl = hosts_per_leaf
    for _ in range(64):
        same = (src // hpl) == (dst // hpl)
        if not same.any():
            break
        dst[same] = rng.integers(0, n_hosts, int(same.sum())).astype(np.int32)
    n_leaf = -(-n_hosts // hpl)
    same = (src // hpl) == (dst // hpl)
    shifted = np.minimum(((dst // hpl + 1) % n_leaf) * hpl + dst % hpl, n_hosts - 1)
    dst = np.where(same, shifted, dst).astype(np.int32)
    salt = np.uint32(rng.integers(0, 2**32, dtype=np.uint64))
    flow_id = np.arange(n, dtype=np.uint32) * np.uint32(2654435761) + salt
    return dict(sizes=sizes, arrivals=arrivals, src=src, dst=dst, flow_id=flow_id)


def run_order(seed: int, n_batches: int, batch: int) -> list[list[int]]:
    """The order in which a run sends its pool: batch b holds slots
    ``b * batch .. (b + 1) * batch - 1`` and the batches go in that order;
    the run's seed (which may exceed 32 bits) permutes the slots over the
    lanes of each vmapped batch.  A batch runs until its slowest sim exits,
    whatever lane that sim takes, so every run does the same work in the
    same time: the seed changes which lane serves which trace, and which
    sims the reference checks, and not how many steps the window runs."""
    rng = np.random.default_rng(int(seed))
    return [[b * batch + j for j in rng.permutation(batch).tolist()]
            for b in range(n_batches)]
