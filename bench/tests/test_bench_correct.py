"""The comparison that decides ``correct``, at a size a test run holds, on
the CPU: the Fig. 12 fabric at its full widths with 1 ms of arrivals and a
6 ms horizon, long enough for every flow to finish.

* the program agrees with the plain reference within the cell's limits,
  and delivers every byte of every flow within the horizon;
* the control, the reference at ``Precision.HIGH`` (three bf16 passes) in
  the program's place, does not agree;
* a whole run of the harness, its look for a chip skipped, comes out
  correct, and comes out not correct when the timed path is broken
  underneath: a step that returns its state unchanged, half of a batch
  left out, an answer altered where it is produced, every sim stopped at
  half its horizon.
"""
from __future__ import annotations

import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench.harness import registry

ROOT = registry.ROOT
CELL = "fig12_short"


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose BENCHMARK.json adds a short twin of the Fig. 12
    cell, made of files alone, with its limits and metrics."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    tr = json.load(open(root / "bench" / "traffic" / "websearch80.json"))
    tr.update(name="websearch80_short", batch=2, pool_batches=2,
              generator=dict(tr["generator"], arrivals_s=1e-3),
              sim=dict(tr["sim"], horizon_s=6e-3), check=dict(tr["check"], sims=2))
    (root / "bench" / "traffic" / "websearch80_short.json").write_text(json.dumps(tr))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["workloads"].append({"name": CELL, "config": "fig12_2tier",
                              "traffic": "websearch80_short", "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "fig12_websearch80" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


@pytest.fixture
def same_cache(monkeypatch):
    """Keep the process's compile cache where it is across a harness run."""
    from repro.netsim.compile_cache import DEFAULT_COMPILE_CACHE

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or DEFAULT_COMPILE_CACHE)


@pytest.mark.parametrize("seed", [2**31 + 5, 77, 2**33 + 1])
def test_program_within_limits_control_beyond(checkout, seed):
    from bench import control

    cell = registry.find_cell(CELL, root=checkout)
    got = control.readings(cell, seed, ("high",), ("stop_quarter",), log=lambda m: None)
    lims = {k[: -len("_limit")]: v for k, v in cell.traffic["check"].items()
            if k.endswith("_limit")}
    assert set(got["program"]) == set(lims), got
    for k, lim in lims.items():
        assert got["program"][k] <= lim, (k, got)
    assert got["high"]["traj_gap"] > lims["traj_gap"], got
    assert got["stop_quarter"]["unfinished"] > lims["unfinished"], got
    assert got["stop_quarter"]["bytes_gap"] > lims["bytes_gap"], got


def _frozen_step(monkeypatch):
    """Every engine step hands back the state it was given (the clock alone
    advances, so the horizon still ends)."""
    from repro.netsim import compact, sweep

    orig = compact.build_compact_sim

    def broken(*a, **k):
        init, step_fn, phases = orig(*a, **k)

        def frozen(st, x):
            new, out = step_fn(st, x)
            return st._replace(step=new.step), out

        return init, frozen, phases

    monkeypatch.setattr(compact, "build_compact_sim", broken)
    sweep.clear_cache()


def _half_batch(monkeypatch):
    """Only the first half of each batch is simulated; the rest take its
    results."""
    from repro.netsim import sweep

    orig = sweep.run_batch

    def broken(topo, cfg, traces, **kw):
        h = max(1, len(traces) // 2)
        res, outs = orig(topo, cfg, traces[:h], **kw)
        k = len(traces) - h
        return res + res[:k], outs + outs[:k]

    monkeypatch.setattr(sweep, "run_batch", broken)


def _altered_answer(monkeypatch):
    """Every fifth flow's completion time is reported one step late."""
    from repro.netsim import sweep

    orig = sweep.run_batch

    def broken(topo, cfg, traces, **kw):
        res, outs = orig(topo, cfg, traces, **kw)
        bad = []
        for r in res:
            f = np.array(r.finish)
            f[::5] += cfg.dt
            bad.append(r._replace(finish=f))
        return bad, outs

    monkeypatch.setattr(sweep, "run_batch", broken)


def _stop_half(monkeypatch):
    """Every sim stops at half its horizon, before its last flows finish."""
    from bench import control
    from repro.netsim import sweep

    monkeypatch.setattr(sweep, "run_batch", control.cut_horizon(sweep.run_batch, 0.5))


@pytest.mark.parametrize("fault", [None, _frozen_step, _half_batch, _altered_answer,
                                   _stop_half])
def test_harness_run_correct_only_when_sound(checkout, same_cache, monkeypatch, capsys,
                                             fault):
    from bench import run
    from repro.netsim import sweep

    if fault is not None:
        fault(monkeypatch)
    try:
        result = run.run(["--workload", CELL, "--seed", str(2**32 + 9), "--seconds", "0",
                          "--trace", "0"], require_tpu=False, root=checkout)
    finally:
        monkeypatch.undo()
        sweep.clear_cache()
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(result))
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(last)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    assert set(last["metrics"]) == {"sim_steps_per_s", "setup_s"}
    assert last["device"]["platform"] == "cpu"
    assert result["correct"] is (fault is None), result["checks"]


def test_run_refuses_without_tpu(checkout, capsys):
    from bench import run

    with pytest.raises(SystemExit) as e:
        run.run(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
                root=checkout)
    assert "no TPU" in str(e.value)
    assert capsys.readouterr().out == ""
