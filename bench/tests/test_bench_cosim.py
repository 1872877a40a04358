"""The co-sim cell on the CPU, at a test-size three-tier fabric: a cell of
``kind`` ``cosim`` added by files alone runs through the harness and comes
out correct, and not correct when the program is broken underneath it; the
readings that set its limits; the reference's ECMP paths against the
program's; and the cell's per-layer readers on a hand-built trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import shutil

import jax
import numpy as np
import pytest

from bench.harness import registry, xtrace

ROOT = registry.ROOT
CELL = "tiny_cosim_killagg"
FABRIC = {"kind": "three_tier", "n_tor": 4, "n_agg": 4, "n_core": 2, "hosts_per_tor": 2,
          "bw_tor_agg": 400e9, "bw_agg_core": 400e9, "host_bw": 100e9, "base_rtt_s": 8e-6}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout whose BENCHMARK.json adds a test-size twin of the co-sim
    cell, made of files alone: 4 ToRs x 4 aggregation x 2 core switches, an
    8-member ring of 4 MB a member (every host), aggregation switch 1 down
    at epoch 2 and back at 4."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.load(open(root / "bench" / "configs" / "fig14_3tier_killagg.json"))
    cfg.update(name="tiny_3tier_killagg", fabric=FABRIC,
               fault=dict(cfg["fault"], switch=1, links=12, down_epoch=2, up_epoch=4))
    (root / "bench" / "configs" / "tiny_3tier_killagg.json").write_text(json.dumps(cfg))
    tr = json.load(open(root / "bench" / "traffic" / "ring20_killagg.json"))
    tr.update(name="ring8_killagg", ring=dict(members=8, size_bytes=4e6, n_chunks=2),
              campaign=dict(tr["campaign"], epochs=6), pool=2,
              check=dict(tr["check"], epochs=2))
    (root / "bench" / "traffic" / "ring8_killagg.json").write_text(json.dumps(tr))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny_3tier_killagg", "source": "test", "reduced": [],
                            "file": "bench/configs/tiny_3tier_killagg.json", "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny_3tier_killagg",
                              "traffic": "ring8_killagg", "chips": 1, "why": "test"})
    for m in spec["per_layer"]:
        if "fig14_cosim_killspine" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


@pytest.fixture
def same_cache(monkeypatch):
    """Keep the process's compile cache where it is across a harness run."""
    from repro.netsim.compile_cache import DEFAULT_COMPILE_CACHE

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       jax.config.jax_compilation_cache_dir or DEFAULT_COMPILE_CACHE)


# ------------------------------------------------------------ planted faults
@contextlib.contextmanager
def kill_ignored():
    """The program runs every epoch on the healthy fabric: its fault state
    never applies the kill, while the reference has it."""
    from repro.dist import cosim

    orig = cosim.capacity_at
    cosim.capacity_at = lambda topo, faults, epoch: np.asarray(topo.capacity, np.float32).copy()
    try:
        yield
    finally:
        cosim.capacity_at = orig


@contextlib.contextmanager
def capacity_baked():
    """Each epoch's capacities are baked into the executable instead of
    riding as a traced operand, so a new fault state builds a new one."""
    from repro.netsim import sweep

    orig = sweep.run_batch

    def broken(topo, cfg, traces, capacity=None, **kw):
        if capacity is not None:
            topo = dataclasses.replace(topo, capacity=jax.numpy.asarray(capacity))
        return orig(topo, cfg, traces, **kw)

    sweep.clear_cache()
    sweep.run_batch = broken
    try:
        yield
    finally:
        sweep.run_batch = orig
        sweep.clear_cache()


@contextlib.contextmanager
def version_stuck():
    """Every plan the planner emits carries version 0, so a newer plan is
    refused and the first stays in force."""
    from repro.dist.elastic import LinkHealth

    orig = LinkHealth.plan
    LinkHealth.plan = lambda self, step, **kw: orig(self, step, **dict(kw, version=0))
    try:
        yield
    finally:
        LinkHealth.plan = orig


def stop_quarter():
    from bench import control

    return control.FAULTS["stop_quarter"]()


FAULTS = {"stop_quarter": stop_quarter, "kill_ignored": kill_ignored,
          "capacity_baked": capacity_baked, "version_stuck": version_stuck}


@pytest.mark.parametrize("fault", [None] + sorted(FAULTS))
def test_harness_run_correct_only_when_sound(checkout, same_cache, capsys, fault):
    from bench import run

    with FAULTS[fault]() if fault else contextlib.nullcontext():
        result = run.run(["--workload", CELL, "--seed", str(2**33 + 3), "--seconds", "0",
                          "--trace", "0"], require_tpu=False, root=checkout)
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(result))
    assert set(last["metrics"]) == {"sim_steps_per_s", "setup_s"}
    assert last["attempted"] == 6
    assert set(last["checks"]) == {"traj_gap", "finish_mismatch", "bytes_gap",
                                   "unfinished_live", "rebuilds_after_epoch0",
                                   "quarantine_mismatch", "convergence_epochs",
                                   "plan_refused"}
    assert out.err.strip().splitlines()[-1].startswith("check ")
    assert result["correct"] is (fault is None), result["checks"]
    if fault is None:
        assert result["failed"] == 0
        assert "compiles_in_window=0 " in out.out


def test_control_readings_program_below_control_and_faults_above(checkout, monkeypatch):
    from bench import control

    monkeypatch.setitem(control.FAULTS, "kill_ignored", kill_ignored)
    cell = registry.find_cell(CELL, root=checkout)
    got = control.readings(cell, 2**31 + 17, ("high",), ("stop_quarter", "kill_ignored"),
                           log=lambda m: None)
    lims = {k[: -len("_limit")]: v for k, v in cell.traffic["check"].items()
            if k.endswith("_limit")}
    assert set(got["program"]) == set(lims), got
    for k, lim in lims.items():
        assert got["program"][k] <= lim, (k, got)
    assert set(got["high"]) == {"traj_gap", "finish_mismatch", "bytes_gap"}
    assert got["high"]["traj_gap"] > lims["traj_gap"], got
    for k in ("unfinished_live", "convergence_epochs", "traj_gap", "bytes_gap"):
        assert got["stop_quarter"][k] > lims[k], (k, got)
    for k in ("quarantine_mismatch", "traj_gap", "bytes_gap"):
        assert got["kill_ignored"][k] > lims[k], (k, got)


def test_schedule_from_reference_link_tables(checkout):
    cell = registry.find_cell(CELL, root=checkout)
    study = cell.driver().Study(cell, 5, lambda m: None)
    study.build_pool()
    from repro.netsim import topology

    # the reference's link layout kills the links the program's kills
    assert sorted(study.dead.tolist()) == sorted(topology.spine_links(study.topo, 1))
    assert study.dead_set == frozenset({2, 3})  # paths (agg 1, core 0 / 1)
    assert [bool(study.expected_quarantine(e)) for e in range(7)] == \
        [False, False, False, True, True, False, False]
    assert study.order in ([0, 1], [1, 0])


def test_steered_flow_ids_same_paths_in_reference_and_program(checkout):
    """The flow ids the ring schedule steers land on their planned paths by
    the engine's own five-tuple (``engine.flow_constants``) and by the
    reference's hash, which the study uses to find flows on dead links."""
    import jax.numpy as jnp

    from bench.reference import fluid
    from repro.core import routing
    from repro.dist import cosim
    from repro.dist.elastic import LinkHealth
    from repro.netsim import engine, topology, workloads

    cell = registry.find_cell(CELL, root=checkout)
    drv = cell.driver()
    fab = fluid.build_fabric(FABRIC)
    topo = topology.three_tier(**{k: v for k, v in FABRIC.items() if k != "kind"})
    P = topo.n_paths
    health = LinkHealth(n_paths=P, phi_steps=2)
    for p in (2, 3):
        health.report_slow(p, step=0)
    plan = health.plan(1, n_chunks=2)
    hosts = cosim.ring_hosts(topo, 4)
    for seed in (0, 1, 2**31 + 9):
        tr = workloads.collective_trace(plan, hosts, 1e6, link_bw=400e9, steer_paths=P,
                                        seed=seed)
        fc = engine.flow_constants(topo, engine.SimConfig(scheme="ecmp"),
                                   jnp.asarray(tr.sizes), jnp.asarray(tr.src),
                                   jnp.asarray(tr.dst), jnp.asarray(tr.flow_id))
        program = np.asarray(routing.ecmp_paths(*fc.f5, P))
        reference = drv.ecmp_paths(fab, tr.src, tr.dst, tr.flow_id)
        np.testing.assert_array_equal(reference, program)
        assert not set(reference.tolist()) & {2, 3}
        links = drv.flow_links(fab, tr.src, tr.dst, reference)
        want = np.asarray(topo.subflow_links(jnp.asarray(tr.src), jnp.asarray(tr.dst),
                                             jnp.asarray(program)))[:, :6]
        np.testing.assert_array_equal(links, want)


# ------------------------------------------------------------ readers
def _trace():
    """Two co-sim epochs (ns), each with its stages, a sim module and a
    short module in its sim span."""
    host = [("bench.batch", 0, 30_000),
            ("repro.cosim.epoch", 1_000, 12_000), ("repro.cosim.trace", 1_000, 1_000),
            ("repro.cosim.sim", 2_000, 8_000), ("repro.cosim.feedback", 10_000, 2_000),
            ("repro.cosim.record", 12_000, 500),
            ("repro.cosim.epoch", 15_000, 10_000), ("repro.cosim.trace", 15_000, 500),
            ("repro.cosim.sim", 15_500, 6_000), ("repro.cosim.feedback", 21_500, 1_500),
            ("repro.cosim.record", 23_000, 1_000)]
    mods = [("jit_fn_one(3)", 3_000, 5_000), ("jit_slice(4)", 8_500, 200),
            ("jit_fn_one(3)", 16_000, 4_000)]
    ops = [("fusion.1", 3_000, 5_000), ("copy.2", 8_500, 200), ("fusion.1", 16_000, 4_000)]
    return xtrace.Summary(xtrace.Trace(device_ops={"/device:TPU:0": ops},
                                       modules={"/device:TPU:0": mods}, host=host))


def _read(name, ctx):
    path = os.path.join(ROOT, "bench", "layer_metrics", f"{name}.py")
    return registry.load_module(path, name).read(ctx)


@pytest.mark.parametrize("name,want", [
    # epochs of 12 us and 10 us, device busy 5.2 us and 4 us inside them
    ("epoch_host_ms", 1e3 * ((12e-6 - 5.2e-6) + (10e-6 - 4e-6)) / 2),
    # trace + feedback + record: 3.5 us and 3 us
    ("epoch_control_ms", 1e-6 * (3_500 + 3_000) / 2),
    # the longest module per sim span over the epochs' exit steps
    ("cosim_step_us", 1e6 * (5e-6 + 4e-6) / (50 + 40)),
])
def test_cosim_readers_on_hand_built_trace(name, want):
    ctx = dict(trace=_trace(), record={"epochs": [{"steps": 50}, {"steps": 40}]}, peaks=None)
    assert _read(name, ctx) == pytest.approx(want)


def test_epoch_host_ms_counts_the_busy_chip_on_a_host_of_four():
    """Three idle chips on the host leave an epoch's device time as it is."""
    tr = _trace()
    four = xtrace.Summary(xtrace.Trace(
        device_ops=dict(tr.trace.device_ops, **{f"/device:TPU:{i}": [] for i in (1, 2, 3)}),
        modules=tr.trace.modules, host=tr.trace.host))
    ctx = dict(trace=four, record={"epochs": [{"steps": 50}, {"steps": 40}]}, peaks=None)
    assert _read("epoch_host_ms", ctx) == pytest.approx(
        1e3 * ((12e-6 - 5.2e-6) + (10e-6 - 4e-6)) / 2)


@pytest.mark.parametrize("name", ["epoch_host_ms", "epoch_control_ms", "cosim_step_us"])
def test_cosim_readers_silent_without_spans(name):
    """A program from before the co-sim spans (the sweep's spans alone)
    gives nothing to read; so does a record whose epochs do not pair up."""
    tr = _trace()
    bare = xtrace.Summary(xtrace.Trace(
        device_ops=tr.trace.device_ops, modules=tr.trace.modules,
        host=[h for h in tr.trace.host if not h[0].startswith("repro.cosim.")]))
    assert _read(name, dict(trace=bare, record={"epochs": [{"steps": 50}]}, peaks=None)) is None
    if name == "cosim_step_us":
        assert _read(name, dict(trace=tr, record={"epochs": [{"steps": 50}]})) is None
