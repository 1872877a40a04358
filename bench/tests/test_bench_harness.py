"""The benchmark's yardstick on the CPU: trace reduction, work counted from
shapes, and the registry that finds a cell's files by name.  No chip, no
TPU description."""
from __future__ import annotations

import json
import os
import shutil

import numpy as np
import pytest

from bench.harness import cost, registry, traffic, xtrace

ROOT = registry.ROOT


# ------------------------------------------------------------ trace reduction
def _trace():
    """Two batches on one device, with a kernel, host spans and gaps (ns)."""
    ops = [("while.7", 1_000, 6_000),  # a loop around the first batch's ops
           ("fusion.1", 1_000, 2_000), ("linkload_cascade_tiered.8", 3_000, 4_000),
           ("fusion.2", 6_000, 1_000),  # nested inside the kernel's interval
           ("fusion.3", 12_000, 3_000), ("linkload_cascade_tiered.8", 15_000, 1_000),
           ("copy.9", 30_000, 500)]  # after the window: left out
    mods = [("jit_core_kw", 1_000, 6_000), ("jit_slice", 8_000, 100), ("jit_core_kw", 12_000, 4_000)]
    host = [("bench.batch", 0, 10_000), ("repro.sweep.dispatch", 500, 7_000),
            ("bench.batch", 11_000, 9_000)]
    return xtrace.Trace(device_ops={"/device:TPU:0": ops}, modules={"/device:TPU:0": mods},
                        host=host)


def test_trace_reduction_busy_idle_kernel():
    s = xtrace.Summary(_trace())
    assert (s.t0, s.t1) == (0, 20_000)
    assert s.window_s == pytest.approx(20e-6)
    # union: [1000, 7000] + [12000, 16000] = 6000 + 4000 ns
    assert s.busy_s == pytest.approx(10e-6)
    assert s.busy_within(0, 10_000) == pytest.approx(6e-6)
    assert s.op_seconds("linkload_cascade_tiered") == pytest.approx(5e-6)
    assert s.op_seconds("linkload_cascade_tiered", 11_000, 20_000) == pytest.approx(1e-6)
    assert [n for n, _, _ in s.modules_within(11_000, 20_000)] == ["jit_core_kw"]
    gaps = s.idle_gaps()
    # [0, 1000], [7000, 12000] and [16000, 20000] ns, longest first
    assert [g for _, g in gaps] == pytest.approx([5e-6, 4e-6, 1e-6])
    assert sum(g for _, g in gaps) == pytest.approx(s.window_s - s.busy_s)
    br = s.breakdown()
    assert br["device_ops"][0] == ["linkload_cascade_tiered.8", pytest.approx(5e-6)]
    assert all(not name.startswith("while") for name, _ in br["device_ops"])
    assert all(name != "copy.9" for name, _ in br["device_ops"])
    assert len(br["idle_gaps"]) <= 10


def test_op_names_are_instruction_names():
    text = ("%linkload_cascade_tiered.8 = (f32[8,1,512]{2,1,0:T(1,128)}) custom-call("
            "s32[8,2,4,1280]{3,2,1,0} %fusion.267), custom_call_target=\"tpu_custom_call\"")
    assert xtrace.op_name(text) == "linkload_cascade_tiered.8"
    assert xtrace.op_name("fusion.3") == "fusion.3"


def test_idle_gap_labels_use_innermost_span():
    s = xtrace.Summary(_trace())
    labels = [n for n, _ in s.idle_gaps()]
    # mid 9500 inside the first batch only; mid 18000 in the second batch;
    # mid 500 inside the program's dispatch span, nested in the batch
    assert labels == ["bench.batch", "bench.batch", "repro.sweep.dispatch"]
    bare = xtrace.Summary(xtrace.Trace(device_ops={"/device:TPU:0": [("f", 0, 10), ("g", 50, 10)]}))
    assert bare.idle_gaps() == [("outside benchmark spans", pytest.approx(40e-9))]


def test_trace_reduction_without_device_reads_nothing():
    s = xtrace.Summary(xtrace.Trace(host=[("bench.batch", 0, 1000)]))
    assert s.busy_s == 0.0 and s.idle_gaps() == []
    ctx = dict(trace=s, record={"batches": []}, peaks=None)
    for m in ("device_idle_pct", "cascade_share_pct", "batch_host_ms"):
        mod = registry.load_module(os.path.join(ROOT, "bench", "layer_metrics", f"{m}.py"), m)
        assert mod.read(ctx) is None


def test_layer_metrics_on_hand_built_trace():
    s = xtrace.Summary(_trace())
    rec = {"batches": [{"exits": [40, 20]}, {"exits": [20, 20]}],
           "units": [{"steps": 40, "dispatches": [(256, 2)]},
                     {"steps": 20, "dispatches": [(256, 2)]}],
           "n_sub": 4, "n_fabric_hops": 2, "n_links": 448}
    peaks = registry.peaks("TPU v5 lite")
    ctx = dict(trace=s, record=rec, peaks=peaks)

    def read(name):
        mod = registry.load_module(os.path.join(ROOT, "bench", "layer_metrics", f"{name}.py"),
                                   name)
        return mod.read(ctx)

    assert read("device_idle_pct") == pytest.approx(50.0)
    assert read("cascade_share_pct") == pytest.approx(50.0)
    # batch spans 10 us and 9 us, device busy 6 us and 4 us inside them
    assert read("batch_host_ms") == pytest.approx(1e3 * ((10e-6 - 6e-6) + (9e-6 - 4e-6)) / 2)
    # largest module per batch: 6 us over 40 steps, 4 us over 20 steps
    assert read("step_us") == pytest.approx(1e6 * 10e-6 / 60)
    # lane-steps: (40 + 20) of 80 and (20 + 20) of 40 used
    assert read("lane_waste_pct") == pytest.approx(100.0 * (1 - 100 / 120))
    per_call = cost.cascade_call_bytes(256, 4, 2, 448, batch=2)
    want = 100.0 * (2 * per_call / peaks["hbm_bytes_per_s"]) / 5e-6
    assert read("cascade_roofline_pct") == pytest.approx(want)


# ------------------------------------------------------------ shapes
@pytest.mark.parametrize("n,N,hf,L,B", [
    (1280, 4, 2, 448, 8),    # fig12_2tier: sim_2tier, W = 1280, eight seeds
    (768, 4, 4, 2080, 8),    # fig14_3tier: three_tier, W = 768, eight seeds
])
def test_cascade_bytes_hand_count(n, N, hf, L, B):
    if hf == 2:
        # fab ids 1280*4*2*4 = 40960; tx, rx 2*1280*4 = 10240; rates
        # 1280*4*4 = 20480; rows 3*448*4 = 5376 in and out; thr 20480
        per_sim = 40960 + 10240 + 20480 + 5376 + 5376 + 20480
    else:
        # fab ids 768*4*4*4 = 49152; tx, rx 6144; rates 12288; rows 3*2080*4
        # = 24960 in and out; thr 12288
        per_sim = 49152 + 6144 + 12288 + 24960 + 24960 + 12288
    assert cost.cascade_call_bytes(n, N, hf, L) == per_sim
    assert cost.cascade_call_bytes(n, N, hf, L, batch=B) == B * per_sim


def test_lane_waste_exact_on_constructed_finish_arrays():
    dt, chunk, horizon = 10e-6, 20, 4000
    finishes = [np.array([1e-5, 395e-5, np.inf]),  # never done: runs the horizon
                np.array([20e-5, 200e-5]),          # last finish step 200 -> exit 200
                np.array([201e-5, 3e-5]),           # step 201 -> exit 220
                np.array([], np.float32)]           # nothing: exits at once
    exits = [cost.exit_steps(f, dt, chunk, horizon) for f in finishes]
    assert exits == [4000, 200, 220, 0]
    assert cost.lane_waste([exits]) == pytest.approx(1 - (4000 + 200 + 220 + 0) / (4 * 4000))
    # over two batches: (4420 + 600) lane-steps used of 16000 + 600
    assert cost.lane_waste([exits, [300, 300]]) == pytest.approx(1 - 5020 / 16600)
    assert cost.lane_waste([[300, 300]]) == 0.0
    assert cost.lane_waste([[0, 0]]) is None


def _sim(sizes, finish, goodput):
    from types import SimpleNamespace as NS

    sizes = np.asarray(sizes, np.float32)
    return (NS(sizes=sizes, valid=np.ones(sizes.size, bool)),
            NS(finish=np.asarray(finish, np.float32), spill_steps=0),
            NS(goodput_total=np.asarray(goodput, np.float32)))


def test_horizon_check_exact_on_constructed_sims():
    from bench.drivers.sweep import horizon_check, sim_horizon

    dt, n = 1e-5, 4
    # 1000 B and 3000 B delivered as 8e8 bps over 1, then 3 steps of 10 us
    sound = _sim([1000, 2000], [2e-5, 4e-5], [8e8, 8e8, 8e8, 0])
    assert sim_horizon(*sound, dt, n) == (0, pytest.approx(0.0))
    short = _sim([1000, 2000], [2e-5, np.inf], [8e8, 8e8])  # stopped at step 2
    assert sim_horizon(*short, dt, n) == (1, pytest.approx(1000 / 3000))
    late = _sim([1000], [5e-5], [8e8, 0, 0, 0, 0])  # finish after the horizon
    assert sim_horizon(*late, dt, n)[0] == 1
    other = _sim([1000, 2000], [2e-5], [8e8, 8e8, 8e8, 0])  # another trace's answer
    assert sim_horizon(*other, dt, n)[0] == 2
    chk = {"unfinished_limit": 0, "bytes_gap_limit": 1e-5}
    numbers, failed = horizon_check([sound, short, sound], dt, n, chk)
    assert numbers == [("unfinished", 1.0, 0), ("bytes_gap", pytest.approx(1 / 3), 1e-5)]
    assert failed == 1


def test_run_order_same_work_in_another_order():
    a, b = traffic.run_order(2**33 + 1, 8, 8), traffic.run_order(7, 8, 8)
    assert a == traffic.run_order(2**33 + 1, 8, 8) and a != b
    for order in (a, b):
        assert sorted(x for batch in order for x in batch) == list(range(64))
        assert [{x // 8 for x in batch} for batch in order] == [{i} for i in range(8)]
    gen = {"workload": "websearch", "load": 0.8, "arrivals_s": 1e-4, "load_base_bps": 9.6e12}
    one, two = (traffic.poisson_flows(gen, 128, 16, 3) for _ in range(2))
    assert all(np.array_equal(one[k], two[k]) for k in one)
    assert np.all(one["src"] // 16 != one["dst"] // 16)


@pytest.mark.parametrize("req,s,n", [(32, 10, 4000), (32, 10, 3200), (32, 1, 4000),
                                     (32, 10, 659), (32, 7, 100)])
def test_chunk_steps_matches_program(req, s, n):
    from repro.netsim import compact, engine

    cfg = engine.SimConfig(chunk_steps=req, uplink_sample_every=s)
    assert cost.chunk_steps(req, s, n) == compact.plan_chunks(cfg, n)[0]


# ------------------------------------------------------------ registry
def test_every_named_file_is_found():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in spec["workloads"]:
        cell = registry.find_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert hasattr(cell.driver(), "Study")
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
    for m in spec["per_layer"]:
        moved = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
        for w in m.get("workloads", [x["name"] for x in spec["workloads"]]):
            assert "workloads" not in moved or w in moved["workloads"]
    with pytest.raises(KeyError):
        registry.peaks("TPU v9 imaginary")


def test_new_cell_from_files_alone(tmp_path):
    """A configuration and a traffic mix added as new files, and the cell
    that names them, run through the harness with no code edited."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(root / "bench" / "configs" / "fig12_2tier.json"))
    cfg.update(name="small_2tier", fabric=dict(cfg["fabric"], n_leaf=2, n_spine=4,
                                               hosts_per_leaf=4))
    (root / "bench" / "configs" / "small_2tier.json").write_text(json.dumps(cfg))
    tr = json.load(open(root / "bench" / "traffic" / "websearch80.json"))
    tr.update(name="short_mix", batch=2, pool_batches=1,
              generator=dict(tr["generator"], arrivals_s=5e-4, load_base_bps=8e11),
              sim=dict(tr["sim"], horizon_s=1e-3), check=dict(tr["check"], sims=2, steps=50))
    (root / "bench" / "traffic" / "short_mix.json").write_text(json.dumps(tr))
    spec["workloads"].append({"name": "small_short", "config": "small_2tier",
                              "traffic": "short_mix", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = registry.find_cell("small_short", root=str(root))
    assert cell.config["fabric"]["n_leaf"] == 2 and cell.traffic["batch"] == 2
    study = cell.driver().Study(cell, 2**31 + 12345, lambda m: None)
    study.build_pool()
    assert len(study.pool) == 2 and study.keys[0][0][3] == 2
    again = cell.driver().Study(cell, 2**31 + 12345, lambda m: None)
    again.build_pool()
    assert all(np.array_equal(a.sizes, b.sizes) and np.array_equal(a.arrivals, b.arrivals)
               for a, b in zip(study.pool, again.pool))
    other = cell.driver().Study(cell, 5, lambda m: None)
    other.build_pool()
    key = lambda t: (t.sizes.tobytes(), t.src.tobytes(), t.flow_id.tobytes())
    assert sorted(map(key, other.pool)) == sorted(map(key, study.pool))
