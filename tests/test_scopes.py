"""Named scopes of the engine step, the op -> phase map of a compiled
sweep executable, the sweep's host spans, and the Pallas kernels' names."""
from __future__ import annotations

import contextlib
import functools
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import linkload as ll
from repro.netsim import compact, engine, sweep, topology, workloads
from repro.obs import scopes

FOUR = ("admit", "cascade", "dcqcn", "finish")
SPANS = ("repro.sweep.prep", "repro.sweep.dispatch", "repro.sweep.fetch",
         "repro.sweep.unpack")


def _setup():
    topo = topology.leaf_spine(2, 4, 4, 100e9)
    traces = [workloads.poisson_trace(workloads.TraceConfig(
        workload="alistorage", load=0.5, duration_s=0.5e-3, n_hosts=topo.n_hosts,
        host_bw=100e9, seed=seed, hosts_per_leaf=topo.hosts_per_leaf,
        load_base_bw=2 * 4 * 100e9)) for seed in (0, 1)]
    return topo, engine.SimConfig(scheme="seqbalance", duration_s=1e-3), traces


@pytest.fixture
def vmapped_batch(monkeypatch):
    """One vmapped B = 2 ``run_batch`` (the accelerator's dispatch) from an
    empty executable cache; the compiles it makes are counted."""
    monkeypatch.setenv("REPRO_SWEEP_BATCH", "vmap")
    monkeypatch.setenv("REPRO_SWEEP_DEVICES", "1")
    compiles = []

    def on(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on)
    sweep.clear_cache()
    topo, cfg, traces = _setup()
    try:
        yield topo, cfg, traces, compiles
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
        sweep.clear_cache()


def test_op_phases_map_each_phase_of_a_vmapped_batch(vmapped_batch):
    topo, cfg, traces, compiles = vmapped_batch
    sweep.run_batch(topo, cfg, traces)
    assert sweep.cache_stats()["builds"] == 1
    n = len(compiles)
    maps = sweep.op_phases()
    assert len(compiles) == n, "op_phases compiled again"
    (name, opmap), = maps.items()
    (key, abstract), = sweep._ABSTRACT.items()
    text = sweep._JIT_CACHE[key].lower(*abstract).compile().as_text()
    assert scopes.module_name(text) == name
    assert scopes.op_phases(text) == opmap
    for phase in FOUR:
        assert phase in opmap.values(), phase
    instrs = {ln.split("=", 1)[0].split()[-1].lstrip("%")
              for ln in text.splitlines() if " = " in ln}
    assert set(opmap) <= instrs
    assert set(opmap.values()) <= set(scopes.SCOPES) | {None}


def test_op_phases_fusion_takes_its_roots_scope():
    """Fusions on a hand-written module: the root's scope, else the one
    most of the fusion's instructions carry."""
    text = """HloModule jit_f, entry_computation_layout={()->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(f)/while/body/finish/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> (f32[8], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %neg.2 = f32[8]{0} negate(f32[8]{0} %param_0.1), metadata={op_name="jit(f)/quiesce/cascade/neg"}
  ROOT %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %param_0.1, f32[8]{0} %neg.2)
}

%fused_computation.3 (param_0.2: f32[8], param_1.2: s32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %param_1.2 = s32[8]{0} parameter(1)
  %reshape.10 = f32[8]{0} reshape(f32[8]{0} %param_0.2), metadata={op_name="jit(f)/while/body/finish/reshape"}
  %transpose.11 = f32[8]{0} transpose(f32[8]{0} %reshape.10), dimensions={0}, metadata={op_name="jit(f)/while/body/finish/reshape"}
  %copy.12 = f32[8]{0} copy(f32[8]{0} %reshape.10), metadata={op_name="jit(f)/while/body/admit/copy"}
  ROOT %scatter.13 = f32[8]{0:T(1024)S(1)} scatter(f32[8]{0} %transpose.11, s32[8]{0} %param_1.2, f32[8]{0} %copy.12), update_window_dims={}, to_apply=%region_1
}

%fused_computation.4 (param_0.3: f32[8], param_1.3: s32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  %param_1.3 = s32[8]{0} parameter(1)
  ROOT %fusion.14 = f32[8]{0} fusion(f32[8]{0} %param_0.3, s32[8]{0} %param_1.3), kind=kCustom, calls=%fused_computation.3
}

ENTRY %main.9 () -> f32[8] {
  %constant.4 = f32[8]{0} constant({...})
  %fusion.15 = f32[8]{0} fusion(f32[8]{0} %constant.4, s32[8]{0} %constant.4), kind=kCustom, calls=%fused_computation.4
  %fusion.5 = f32[8]{0:T(1024)} fusion(f32[8]{0} %constant.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/admit/mul"}
  %fusion.6 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %fusion.5), kind=kLoop, calls=%fused_computation.2
  %linkload_cascade_tiered.8 = f32[8]{0} custom-call(f32[8]{0} %fusion.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/cascade/jit(linkload_cascade_tiered)/linkload_cascade_tiered"}
  ROOT %copy.7 = f32[8]{0} copy(f32[8]{0} %fusion.5)
}
"""
    assert scopes.module_name(text) == "jit_f"
    # the fusion's own metadata says admit, its root says finish; the
    # multi-output root takes its scoped operand (quiesce: outermost); a
    # nested fusion's scatter root has no metadata, and most of its
    # instructions say finish
    assert scopes.op_phases(text) == {
        "constant.4": None, "fusion.15": "finish", "fusion.5": "finish",
        "fusion.6": "quiesce", "linkload_cascade_tiered.8": "cascade", "copy.7": None}


def _name_stacks(closed):
    """Every equation's name stack, nested jaxprs included."""
    out = []
    for eqn in closed.jaxpr.eqns:
        out.append(str(eqn.source_info.name_stack))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "jaxpr") and hasattr(sub, "consts"):
                    out += _name_stacks(sub)
    return out


def test_scopes_leave_the_jaxpr_equations_unchanged(monkeypatch):
    """Scopes are metadata: the step's equations, printed, are the same
    with ``jax.named_scope`` made a no-op; only the name stacks differ."""
    topo, cfg, traces = _setup()
    arrays, _, F = compact.sort_trace(traces[0])
    args = (tuple(jnp.asarray(a) for a in arrays), jnp.full((F,), jnp.inf, jnp.float32))

    def trace():  # a new callable each time: no cached trace
        return jax.make_jaxpr(functools.partial(
            compact.run_core, topo, cfg, 256, F, 32, 100))(*args)

    scoped = trace()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = trace()
    assert str(bare) == str(scoped)
    parts = lambda stacks: {p for s in stacks for p in s.split("/")}  # noqa: E731
    assert set(FOUR + ("outputs", "chunk")) <= parts(_name_stacks(scoped))
    assert not parts(_name_stacks(bare)) & set(scopes.SCOPES)


def test_run_batch_host_spans_in_order(vmapped_batch, tmp_path):
    from bench.harness import xtrace

    topo, cfg, traces, _ = vmapped_batch
    sweep.run_batch(topo, cfg, traces)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.batch"):
            res, outs = sweep.run_batch(topo, cfg, traces)
        jax.block_until_ready(outs)
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    host = sorted(xtrace.load(str(tmp_path)).host, key=lambda h: h[1])
    (b0, b1), = [(s, s + d) for n, s, d in host if n == "bench.batch"]
    spans = [(n, s, s + d) for n, s, d in host if n.startswith("repro.sweep.")]
    assert all(b0 <= s and e <= b1 for _, s, e in spans)
    order = [n for i, (n, _, _) in enumerate(spans) if i == 0 or spans[i - 1][0] != n]
    assert order == list(SPANS)
    for name in SPANS:
        same = [(s, e) for n, s, e in spans if n == name]
        assert all(e0 <= s1 for (_, e0), (s1, _) in zip(same, same[1:])), name
    assert len(res) == 2 and np.isfinite(res[0].finish).any()


def _pallas_names(closed):
    """Names of the ``pallas_call`` equations anywhere in a jaxpr."""
    out = []
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") and hasattr(v, "consts"):
                out += _pallas_names(v)
    return out


@pytest.mark.parametrize("kernel", ["linkload", "linkload_cascade_tiered"])
def test_pallas_calls_carry_their_names(kernel):
    n, L = 16, 10
    if kernel == "linkload":
        fn = functools.partial(ll.linkload, n_links=L, interpret=True)
        args = (jnp.zeros((n, 2), jnp.int32), jnp.ones(n), jnp.zeros(L), jnp.ones(L))
    else:
        fn = functools.partial(ll.linkload_cascade_tiered, n_links=L, interpret=True)
        args = (jnp.zeros((n, 4, 2), jnp.int32), jnp.zeros(n, jnp.int32),
                jnp.zeros(n, jnp.int32), jnp.ones((n, 4)), jnp.zeros(L), jnp.ones(L),
                jnp.ones(L))
    assert _pallas_names(jax.make_jaxpr(fn)(*args)) == [kernel]
