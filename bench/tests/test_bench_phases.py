"""Per-phase step time and per-batch host spans, read from a hand-built
trace with a stub op -> phase map (``bench.harness.phases``)."""
from __future__ import annotations

import os

import pytest

from bench.harness import phases, registry, xtrace

ROOT = registry.ROOT
FOUR = ("admit", "cascade", "dcqcn", "finish")
NEW = [f"phase_{p}_us" for p in FOUR] + ["batch_prep_ms", "batch_unpack_ms"]

# the program's map: module name -> {instruction: phase}; None where two
# executables share the module name with different maps
MAPS = {
    "jit_core_kw": {"fusion.1": "admit", "linkload_cascade_tiered.8": "cascade",
                    "fusion.2": "dcqcn", "fusion.3": "finish", "copy.4": None,
                    "fusion.5": "outputs", "while.7": None},
    "jit_amb": None,
}


def _trace():
    """Three timed units (ns): two of the sim module ``jit_core_kw`` and one
    of a module whose map is ambiguous."""
    ops = [("while.7", 1_000, 6_000),  # container around the first unit's ops
           ("fusion.1", 1_000, 1_000), ("linkload_cascade_tiered.8", 2_000, 2_000),
           ("fusion.2", 4_000, 500), ("fusion.3", 4_500, 1_000), ("copy.4", 5_500, 300),
           ("fusion.5", 5_800, 200),
           ("fusion.1", 8_000, 100),  # same name, in the short module: not the sim's
           ("fusion.1", 12_000, 500), ("linkload_cascade_tiered.8", 12_500, 2_500),
           ("fusion.3", 15_000, 1_000),
           ("fusion.1", 22_000, 5_000)]
    mods = [("jit_core_kw(11)", 1_000, 6_000), ("jit_slice(12)", 8_000, 100),
            ("jit_core_kw(13)", 12_000, 4_000), ("jit_amb(14)", 22_000, 5_000)]
    host = [("bench.batch", 0, 10_000), ("bench.batch", 11_000, 9_000),
            ("bench.batch", 21_000, 9_000),
            ("repro.sweep.prep", 200, 400), ("repro.sweep.prep", 700, 200),
            ("repro.sweep.dispatch", 900, 100), ("repro.sweep.unpack", 7_500, 300),
            ("repro.sweep.prep", 11_100, 400), ("repro.sweep.unpack", 16_500, 100),
            ("repro.sweep.prep", 21_100, 200)]
    return xtrace.Summary(xtrace.Trace(device_ops={"/device:TPU:0": ops},
                                       modules={"/device:TPU:0": mods}, host=host))


def _ctx(maps=MAPS):
    rec = {"units": [{"steps": 10}, {"steps": 5}, {"steps": 7}]}
    return dict(trace=_trace(), record=rec, peaks=None, op_phases=maps)


def _read(name, ctx):
    path = os.path.join(ROOT, "bench", "layer_metrics", f"{name}.py")
    return registry.load_module(path, name).read(ctx)


@pytest.mark.parametrize("name,want", [
    # the ambiguous third unit is left out: its 7 steps too
    ("phase_admit_us", 1e-3 * (1_000 + 500) / 15),
    ("phase_cascade_us", 1e-3 * (2_000 + 2_500) / 15),
    ("phase_dcqcn_us", 1e-3 * 500 / 15),
    ("phase_finish_us", 1e-3 * (1_000 + 1_000) / 15),
    # prep spans 600, 400 and 200 ns in the three units; unpack 300, 100, 0
    ("batch_prep_ms", 1e-6 * (600 + 400 + 200) / 3),
    ("batch_unpack_ms", 1e-6 * (300 + 100 + 0) / 3),
])
def test_reader_exact_on_hand_built_trace(name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


def test_ambiguous_unit_left_out():
    s = _trace()
    assert phases.unit_phase_ns(s, 21_000, 30_000, MAPS) is None
    assert phases.unit_phase_ns(s, 0, 10_000, {"jit_other": {}}) is None
    # with every unit's map ambiguous, nothing is read
    amb = {"jit_core_kw": None, "jit_amb": None}
    assert all(_read(f"phase_{p}_us", _ctx(amb)) is None for p in FOUR)


def test_phases_and_unscoped_sum_to_module_leaf_time():
    s = _trace()
    got = phases.unit_phase_ns(s, 0, 10_000, MAPS)
    leaf = sum(d for n, _, d in s.ops(None, 1_000, 7_000)
               if not n.startswith(xtrace.CONTAINERS))
    four = sum(got.get(p, 0) for p in FOUR)
    rest = sum(v for k, v in got.items() if k not in FOUR)
    assert four + rest == leaf == 5_000
    assert rest == 300 + 200  # copy.4 unscoped, fusion.5 under ``outputs``


def test_readers_silent_without_the_programs_map_or_spans():
    """A program from before the scopes gives no map and no spans: every
    reader returns None and none raises."""
    ctx = _ctx(maps=None)
    ctx["trace"] = xtrace.Summary(xtrace.Trace(
        device_ops=ctx["trace"].trace.device_ops, modules=ctx["trace"].trace.modules,
        host=[h for h in ctx["trace"].trace.host if h[0] == "bench.batch"]))
    assert all(_read(n, ctx) is None for n in NEW)
