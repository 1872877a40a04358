"""phase_admit_us — Engine step (``netsim/compact.py::build_compact_sim``, scope ``admit``).

Device time per executed step of the leaf operations the program puts
under its ``admit`` scope: admission: searchsorted on arrivals, gather-on-admit into free slots, slot resets, route-cache fill, path placement.  Summed over the sim module of each
timed unit and divided by the unit's steps, as ``step_us``
(``bench.harness.phases``)."""
from bench.harness.phases import phase_us


def read(ctx):
    return phase_us(ctx, "admit")
