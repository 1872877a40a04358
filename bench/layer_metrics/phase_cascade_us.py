"""phase_cascade_us — Engine step (``netsim/compact.py::build_compact_sim``, scope ``cascade``).

Device time per executed step of the leaf operations the program puts
under its ``cascade`` scope: offered rates, the NIC-tiered hop cascade (the Pallas kernel on a TPU), queue and ECN marks, per-sub-flow mark gathers.  Summed over the sim module of each
timed unit and divided by the unit's steps, as ``step_us``
(``bench.harness.phases``)."""
from bench.harness.phases import phase_us


def read(ctx):
    return phase_us(ctx, "cascade")
