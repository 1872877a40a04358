"""cosim_step_us — Engine step, B = 1 (``netsim/compact.py::run_core`` through ``sweep.run_one``, span ``repro.cosim.sim``).

Device time of the sim executable over the steps it executed, in
microseconds, over the traced window's co-sim epochs.  In each
``repro.cosim.sim`` span the sim executable is the module that ran
longest on the device; it runs until the epoch's last flow finishes, or to
the horizon, so its steps are the epoch's exit step
(``bench.harness.cost.exit_steps``), which the record keeps per epoch in
window order.  None where the program records no such span, or where the
spans and the record's epochs do not pair up."""


def read(ctx):
    tr = ctx["trace"]
    epochs = ctx["record"].get("epochs")
    sims = [] if tr is None else tr.spans("repro.cosim.sim")
    if not sims or not epochs or len(sims) != len(epochs):
        return None
    dev = steps = 0.0
    for (s, e), ep in zip(sims, epochs):
        per: dict = {}
        for n, _, d in tr.modules_within(s, e):
            per[n] = per.get(n, 0) + d
        if per:
            dev += max(per.values()) * 1e-9
            steps += ep["steps"]
    if dev <= 0 or steps <= 0:
        return None
    return 1e6 * dev / steps
