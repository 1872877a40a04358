"""phase_finish_us — Engine step (``netsim/compact.py::build_compact_sim``, scope ``finish``).

Device time per executed step of the leaf operations the program puts
under its ``finish`` scope: transfer progress, CQE bitmaps, scatter-on-finish, the congestion table's scatter-add.  Summed over the sim module of each
timed unit and divided by the unit's steps, as ``step_us``
(``bench.harness.phases``)."""
from bench.harness.phases import phase_us


def read(ctx):
    return phase_us(ctx, "finish")
