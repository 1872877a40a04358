#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, the control and planted
faults, seed by seed, at the cell's own size, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--control high] [--fault stop_quarter]

For each seed it runs one batch of the run's traffic through the program's
timed path and prints one JSON line with the numbers ``correct`` compares:
for the program (the lower reading), for each control (the plain reference
at ``high``, three bf16 passes, the step below float32 at ``highest``, put
in the program's place) and for each fault planted in the program.  The
benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def cut_horizon(run_batch, share: float):
    """``run_batch`` with every sim stopped at ``share`` of its horizon (a
    fault)."""

    def broken(topo, cfg, traces, **kw):
        return run_batch(topo, dataclasses.replace(cfg, duration_s=cfg.duration_s * share),
                         traces, **kw)

    return broken


def stopped_at(share: float):
    """A context in which the program's ``run_batch`` stops every sim at
    ``share`` of its horizon."""

    @contextlib.contextmanager
    def fault():
        from repro.netsim import sweep

        orig = sweep.run_batch
        sweep.run_batch = cut_horizon(orig, share)
        try:
            yield
        finally:
            sweep.run_batch = orig

    return fault


# a fault has to stop sims before their last flow: at the cells' sizes
# sims exit between two fifths and three fifths of their horizon, and
# arrivals end at a quarter
FAULTS = {"stop_quarter": stopped_at(0.25)}


def readings(cell, seed: int, controls=("high",), faults=(), log=print) -> dict:
    """The compared numbers of one seed: program, each control, each fault."""
    import jax

    def one(control=None, fault=contextlib.nullcontext):
        study = cell.driver().Study(cell, seed, log)
        study.build_pool(1)  # the first batch of the run's order
        with fault():
            study.window(0.0, jax.profiler.TraceAnnotation)
        return {k: v for k, v, _ in study.check(control=control)}

    out = {"seed": seed, "program": one()}
    for c in controls:
        out[c] = one(control=c)
    for f in faults:
        out[f] = one(fault=FAULTS[f])
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="high")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    import jax

    from bench.harness import registry

    cell = registry.find_cell(args.workload)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    d = jax.devices()[0]
    split = lambda s: tuple(x for x in s.split(",") if x)
    for seed in [int(s) for s in args.seeds.split(",")]:
        details: list = []
        row = readings(cell, seed, split(args.control), split(args.fault), log=details.append)
        row["details"] = details
        row["device"] = {"platform": d.platform, "kind": d.device_kind}
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
