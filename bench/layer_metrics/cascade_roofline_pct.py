"""cascade_roofline_pct — Dataplane (``kernels/linkload.py::linkload_cascade_tiered``).

Least time for the bytes the kernel's calls must move, over the kernel's
device time.  The kernel is memory-bound (its one-hot products are counted
as the scatter-adds and gathers they stand for, which do no arithmetic to
speak of), so the least time is bytes over the chip's HBM bandwidth.  The
bytes of a call are its inputs read once and outputs written once at the
call's shapes (``bench.harness.cost.cascade_call_bytes``): one call per
step serves a dispatch's B sims with W window slots each.  Calls are
attributed to dispatches by the sim executable each ran in, in dispatch
order; a dispatch whose shapes the record does not hold (a spill retry)
is left out."""

from bench.harness import cost
from bench.harness.units import unit_spans

KERNEL = "linkload_cascade_tiered"


def read(ctx):
    tr, rec, peaks = ctx["trace"], ctx["record"], ctx["peaks"]
    units = rec.get("units")
    spans = unit_spans(ctx)
    if not units or not spans or not peaks:
        return None
    need = took = 0.0
    for (s, e), u in zip(spans, units):
        calls = tr.ops(KERNEL, s, e)
        mods = sorted((m for m in tr.modules_within(s, e)
                       if any(m[1] <= c[1] and c[1] + c[2] <= m[1] + m[2] for c in calls)),
                      key=lambda m: m[1])
        for (_, m0, md), (W, B) in zip(mods, u["dispatches"]):
            inside = [c for c in calls if m0 <= c[1] and c[1] + c[2] <= m0 + md]
            per_call = cost.cascade_call_bytes(W, rec["n_sub"], rec["n_fabric_hops"],
                                               rec["n_links"], batch=B)
            need += len(inside) * per_call / peaks["hbm_bytes_per_s"]
            took += sum(c[2] for c in inside) * 1e-9
    if took <= 0:
        return None
    return 100.0 * need / took
