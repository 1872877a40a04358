"""lane_waste_pct — Dispatch layer (``sweep._dispatch``, vmap).

Share of a window's lane-steps spent on sims that had already passed their
own early exit: a vmapped batch runs until its slowest sim exits, so every
other sim's lanes idle through the rest.  Counted from the finish arrays
and the scan-chunk length (``bench.harness.cost``), with no timing."""
from bench.harness import cost


def read(ctx):
    batches = ctx["record"].get("batches")
    waste = cost.lane_waste([b["exits"] for b in batches]) if batches else None
    return None if waste is None else 100.0 * waste
