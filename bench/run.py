#!/usr/bin/env python3
"""Benchmark of the SeqBalance fabric simulator on a TPU.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: set-up
(trace pool from the seed, every shape the pool uses compiled or read from
the persistent compile cache at ``<checkout>/.jax_cache``, one warm run),
then a closed loop for ``--seconds``, then the comparison of a sample of
the window's results with the plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  Counters (compiles in the window, spill
retries, pool reuses) go on earlier ``bench:`` lines; the numbers compared
for ``correct`` are the last lines on standard error; the last line on
standard output is the result.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


class CompileWatch:
    """XLA compiles and their seconds, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += secs

    def snapshot(self) -> tuple[int, float]:
        with self._lock:
            return self.count, self.seconds


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_record(devs, n: int) -> dict:
    d = devs[0]
    rec = {"platform": d.platform, "kind": d.device_kind, "count": n}
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use") for x in devs[:n]]
    peaks = [p for p in peaks if p is not None]
    rec["memory_peak_bytes"] = max(peaks) if peaks else None
    return rec


def run(argv=None, *, require_tpu: bool = True, root: str = ROOT) -> dict:
    """One benchmark run; returns the result (also printed last).
    ``require_tpu=False`` lets the tests drive a run on the CPU."""
    args = parse(argv)
    from bench.harness import registry, xtrace

    cell = registry.find_cell(args.workload, root=root)
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU: JAX sees {devs[0].platform} devices")
    if len(devs) < cell.chips:
        raise SystemExit(f"bench: {cell.name} needs {cell.chips} chips, JAX sees {len(devs)}")
    peaks = registry.peaks(devs[0].device_kind) if require_tpu else None

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    from repro.netsim.compile_cache import enable_compile_cache
    from repro.netsim import sweep

    enable_compile_cache()
    watch = CompileWatch()
    study = cell.driver().Study(cell, args.seed, log)
    study.setup()
    setup_s = time.time() - T_START
    n_setup, s_setup = watch.snapshot()
    stats0 = sweep.obs_stats()
    log(f"setup: setup_s={setup_s!r} compiles={n_setup} compile_s={s_setup!r} "
        f"builds={stats0['builds']} spill_retries={stats0['spill_retries']}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # spans and device ops only: less host cost
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # a traced run traces a shorter window: traces are large, and tracing
    # slows the host
    seconds = min(args.seconds, cell.traffic.get("trace_seconds", args.seconds)) \
        if args.trace else args.seconds
    try:
        record = study.window(seconds, jax.profiler.TraceAnnotation)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    n_win, s_win = watch.snapshot()
    stats1 = sweep.obs_stats()
    counters = {k: stats1[k] - stats0.get(k, 0) for k in stats1}
    log(f"window: window_s={record['window_s']!r} sims={record['sims']} "
        f"compiles_in_window={n_win - n_setup} compile_s_in_window={s_win - s_setup!r} "
        f"builds_in_window={counters['builds']} spill_retries_in_window="
        f"{counters['spill_retries']} pool_reuses={record.get('pool_reuses', 0)}")
    device = device_record(devs, cell.chips)

    metrics: dict = {}
    breakdown = None
    if trace_dir:
        try:
            summary = xtrace.reduce(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(record=record, trace=summary, counters=counters, peaks=peaks, cell=cell)
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
    else:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else record["metrics"].get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = study.check()
    correct = all(v <= lim for _, v, lim in checks) and study.failed == 0
    result = {"correct": correct, "attempted": record["sims"], "failed": study.failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    run()
