"""epoch_host_ms — Co-sim epoch (``dist/cosim.py::run_cosim``, span ``repro.cosim.epoch``).

Mean over the traced window's co-sim epochs of the program's
``repro.cosim.epoch`` span minus the device-busy time inside it: the host's
own share of an epoch (ring schedule, planning, input transfer, dispatch,
result fetch, congestion feedback, statistics).  Busy time is the union of
the device operations inside the span on any chip: the co-sim runs on one
chip, and the host's idle chips would otherwise dilute it (``xtrace``
averages busy time over the devices it sees).  None where the program
records no such span."""


def busy_ns(tr, t0: int, t1: int) -> int:
    """Union of the device operations inside [t0, t1] ns, over every chip."""
    total, end = 0, t0
    for _, s, d in sorted(tr.ops(None, t0, t1), key=lambda op: op[1]):
        if s + d > end:
            total += s + d - max(s, end)
            end = s + d
    return total


def read(ctx):
    tr = ctx["trace"]
    spans = [] if tr is None else tr.spans("repro.cosim.epoch")
    if not spans or tr.busy_s <= 0:
        return None
    host = [(e - s) - busy_ns(tr, s, e) for s, e in spans]
    return 1e-6 * sum(host) / len(host)
