"""batch_host_ms — Study driver (``netsim/sweep.py::run_batch``).

Mean over the window's ``run_batch`` calls of the benchmark's span around
the call minus the device-busy time inside it: the host's own share of a
batch (sort, window planning, padding, stacking, transfer, dispatch, result
fetch)."""
from bench.harness.units import unit_spans


def read(ctx):
    tr = ctx["trace"]
    spans = unit_spans(ctx)
    if not spans or tr.busy_s <= 0:
        return None
    host = [(e - s) * 1e-9 - tr.busy_within(s, e) for s, e in spans]
    return 1e3 * sum(host) / len(host)
