"""epoch_control_ms — Co-sim control plane (``dist/cosim.py::run_cosim``, spans ``repro.cosim.trace`` / ``.feedback`` / ``.record``).

Mean over the traced window's co-sim epochs of the summed host spans of
the epoch's control plane inside each ``repro.cosim.epoch`` span: fault
state and the ring schedule (``trace``), congestion reports, the next plan
and its versioned application (``feedback``), FCT and imbalance statistics
and the epoch record (``record``).  None where the program records no such
span."""

PARTS = ("repro.cosim.trace", "repro.cosim.feedback", "repro.cosim.record")


def read(ctx):
    tr = ctx["trace"]
    epochs = [] if tr is None else tr.spans("repro.cosim.epoch")
    if not epochs:
        return None
    parts = [p for name in PARTS for p in tr.spans(name)]
    per = [sum(b - a for a, b in parts if s <= a and b <= e) for s, e in epochs]
    return 1e-6 * sum(per) / len(per)
