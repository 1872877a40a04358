"""Work counted from shapes, for the per-layer metrics: the bytes one call
of the link-load cascade must move, and the lane-steps a vmapped batch
spends on sims that have already finished."""
from __future__ import annotations

import numpy as np

I32 = F32 = 4


def cascade_call_bytes(n_flows: int, n_sub: int, n_fabric_hops: int,
                       n_links: int, batch: int = 1) -> int:
    """Least bytes one ``linkload_cascade_tiered`` call moves: every input
    read once and every output written once, at the call's logical shapes.

    Inputs:  fabric link ids i32[n, N, hf], host tx / rx link ids i32[n]
             each, sub-flow rates f32[n, N], and three per-link rows
             (queue, capacity, queue mask) f32[L].
    Outputs: arrival, new queue and mark rows f32[L] each, and the
             delivered rates f32[n, N].
    ``batch`` sims served by one call multiply all of it."""
    n, N, hf, L = n_flows, n_sub, n_fabric_hops, n_links
    inputs = n * N * hf * I32 + 2 * n * I32 + n * N * F32 + 3 * L * F32
    outputs = 3 * L * F32 + n * N * F32
    return batch * (inputs + outputs)


def exit_steps(finish: np.ndarray, dt: float, chunk: int, n_steps: int) -> int:
    """Step at which a sim's early exit stops it: the end of the chunk in
    which its last flow finished (the horizon if a flow never finished)."""
    f = np.asarray(finish, np.float64)
    if f.size == 0:
        return 0
    if not np.all(np.isfinite(f)):
        return n_steps
    last = int(np.ceil(np.round(f.max() / dt, 6)))
    return min(n_steps, -(-last // chunk) * chunk)


def lane_waste(batches: list[list[int]]) -> float | None:
    """Share of the lane-steps of vmapped batches spent on sims past their
    own exit: each batch (a list of its sims' exit steps) runs until its
    slowest sim exits.  None when no lane-step ran."""
    used = sum(sum(exits) for exits in batches)
    total = sum(len(exits) * max(exits, default=0) for exits in batches)
    return None if total == 0 else 1.0 - used / total


def chunk_steps(requested: int, sample_every: int, n_steps: int) -> int:
    """Scan-chunk length of a horizon: the largest multiple of the sample
    window, at most ``requested``, that divides the horizon (else the
    requested length rounded down to the sample window)."""
    k0 = min(max(1, requested // sample_every) * sample_every, max(n_steps, 1))
    for k in range(k0, 0, -1):
        if k % sample_every == 0 and n_steps % k == 0:
            return k
    return k0
