"""repro.obs — the observability plane (DESIGN.md §16).

Three layers, one package:

  * ``recorder``     — traced in-sim ring buffer of per-chunk summaries
    (carried through ``compact.run_core``; zero rebuilds across epochs,
    ``record=None`` bit-identical to no recorder at all);
  * ``flightlog``    — schema-versioned JSONL control-plane event log
    (journal schema v2, ``journal: "flight"``), fed by ``dist/cosim.py``,
    ``netsim/faults.py`` activations, and ``netsim/sweep.py`` counters;
  * ``trace_export`` / ``features`` — perfetto Chrome-trace exporter and
    the [epoch, uplink, feature] matrix for the predictive planner.

``runmeta()`` stamps records (bench JSON sections, flight-log headers)
with run id / git sha / host / device count so perf trajectories are
attributable across machines.
"""
from __future__ import annotations

import functools
import os
import socket
import subprocess
import time
import uuid

from repro.obs.flightlog import (  # noqa: F401
    SCHEMA_VERSION, FlightLog, FlightLogError, read_flight,
)
from repro.obs.recorder import (  # noqa: F401
    META_FIELDS, RecordSpec, RingState, drain, epoch_summary, meta_fields,
    record_chunk, ring_init,
)

#: one run id per process: every runmeta()/FlightLog/bench section written
#: by this process carries the same id, which is what makes them joinable
_RUN_ID = uuid.uuid4().hex[:12]


@functools.lru_cache(maxsize=1)
def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"],
            capture_output=True, text=True, timeout=5,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def runmeta() -> dict:
    """Provenance stamp: run id, git sha, host, jax device count/backend,
    UTC wall clock.  Cheap after the first call (sha is cached; jax is
    already initialized by any caller that simulates)."""
    import jax

    return dict(
        run_id=_RUN_ID,
        git_sha=_git_sha(),
        host=socket.gethostname(),
        n_devices=jax.local_device_count(),
        backend=jax.default_backend(),
        time_utc=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    )
