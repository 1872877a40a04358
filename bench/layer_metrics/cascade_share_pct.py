"""cascade_share_pct — Dataplane (``kernels/linkload.py::linkload_cascade_tiered``).

The Pallas cascade kernel's device time over the device-busy time of the
traced window."""

KERNEL = "linkload_cascade_tiered"


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.busy_s <= 0:
        return None
    k = tr.op_seconds(KERNEL)
    if k <= 0:
        return None
    return 100.0 * k / tr.busy_s
