"""batch_unpack_ms — Study driver (``netsim/sweep.py::run_batch``, span ``repro.sweep.unpack``).

Mean over the window's ``run_batch`` calls of the host time in the
program's ``repro.sweep.unpack`` spans: building each sim's result and
slicing its per-step outputs out of the batch (``bench.harness.phases``)."""
from bench.harness.phases import span_ms


def read(ctx):
    return span_ms(ctx, "repro.sweep.unpack")
