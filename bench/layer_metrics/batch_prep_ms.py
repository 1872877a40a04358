"""batch_prep_ms — Study driver (``netsim/sweep.py::run_batch``, span ``repro.sweep.prep``).

Mean over the window's ``run_batch`` calls of the host time in the
program's ``repro.sweep.prep`` spans: sorting, shape bucketing, window and
admission-lane planning, padding, stacking and the host-to-device
transfer (``bench.harness.phases``)."""
from bench.harness.phases import span_ms


def read(ctx):
    return span_ms(ctx, "repro.sweep.prep")
