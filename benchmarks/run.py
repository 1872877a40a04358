"""Benchmark harness: one function per paper table/figure + §Perf benches.

Prints ``name,us_per_call,derived`` CSV (DESIGN.md §7 maps names to paper
artifacts) and writes a machine-readable BENCH_netsim.json (CSV rows plus
the netsim perf records from benchmarks/common.PERF: per-step µs, sweep
wall-clock, compact-vs-dense speedup).  ``--full`` switches to paper-scale
simulation parameters; ``--only <substr>`` filters benches; ``--json ''``
disables the JSON dump.  A bench that raises does not stop the others,
but the run then exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale runs")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default="BENCH_netsim.json",
                    help="output path for the machine-readable record")
    ap.add_argument("--profile", action="store_true",
                    help="quiescence-occupancy rows of the compact engine "
                         "(adaptive-dt fast-forward coverage)")
    args = ap.parse_args()

    from benchmarks import common, paper_benches
    from benchmarks.bench_collectives import bench_collectives
    from benchmarks.bench_cosim import bench_cosim, bench_faults, \
        bench_telemetry
    from benchmarks.bench_flowcell import bench_flowcell
    from benchmarks.bench_kernels import bench_kernels
    from benchmarks.bench_obs import bench_obs

    benches = list(paper_benches.ALL) + [bench_collectives, bench_kernels,
                                         bench_cosim, bench_faults,
                                         bench_telemetry, bench_obs,
                                         bench_flowcell]
    if args.profile:
        benches.append(paper_benches.bench_quiescence_profile)
    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for b in benches:
        if args.only and args.only not in b.__name__:
            continue
        try:
            b(fast=not args.full)
        except Exception as e:  # run the other benches, then fail the run
            failed.append(b.__name__)
            print(f"{b.__name__},0.0,ERROR_{type(e).__name__}:_{str(e)[:120]}",
                  file=sys.stdout, flush=True)
            traceback.print_exc()
    wall = time.time() - t0
    print(f"# total_wall_s,{wall:.1f},", flush=True)

    if args.json:
        from repro import obs

        record = dict(common.PERF)
        record["total_wall_s"] = round(wall, 1)
        record["rows"] = common.ROWS
        # provenance stamp on the file AND every dict section, so sections
        # merged across runs/machines stay individually attributable
        meta = obs.runmeta()
        for sec in record.values():
            if isinstance(sec, dict):
                sec.setdefault("runmeta", meta)
        record["runmeta"] = meta
        try:
            with open(args.json, "w") as f:
                json.dump(record, f, indent=2)
            print(f"# wrote {args.json}", flush=True)
        except OSError as e:  # never lose a long bench run to a bad path
            print(f"# could not write {args.json}: {e}", file=sys.stderr)
    if failed:
        sys.exit(f"# {len(failed)} bench(es) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
