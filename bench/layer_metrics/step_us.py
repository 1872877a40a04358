"""step_us — Engine step (``netsim/compact.py::run_core``).

Device time of the sim executable over the steps it executed, in
microseconds.  In each timed unit (a ``run_batch`` call) the
sim executable is the module that ran longest on the device; its loop
runs until the unit's slowest sim exits, so the steps it executed are the
unit's largest exit step (``bench.harness.cost.exit_steps``), which the
record keeps."""
from bench.harness.units import unit_spans


def read(ctx):
    tr = ctx["trace"]
    units = ctx["record"].get("units")
    spans = unit_spans(ctx)
    if not units or not spans:
        return None
    dev = steps = 0.0
    for (s, e), u in zip(spans, units):
        per: dict = {}
        for n, _, d in tr.modules_within(s, e):
            per[n] = per.get(n, 0) + d
        if per:
            dev += max(per.values()) * 1e-9
            steps += u["steps"]
    if dev <= 0 or steps <= 0:
        return None
    return 1e6 * dev / steps
