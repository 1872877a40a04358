"""Batched (seed, load) sweep runner over the active-window engine.

Paper-style evaluations run the same (scheme, topology) program over many
traces — workloads x loads x seeds (Fig. 12-14), and related work (RDMACell,
predictive LB) needs exactly this cheap batched what-if simulation.  Naively
that costs one XLA compile per trace shape plus one Python-dispatched scan
per sim.  This runner instead:

  * pads every trace to a shape bucket (``F`` to multiples of 2048, the
    active window ``W`` to multiples of 256, shared across the batch) so
    shapes — and therefore compilations — are reused;
  * runs each shape bucket through ONE compiled program — on cpu a B=1
    program executed per sim (own early exit + gated admission; see
    ``batch_mode``), on accelerators one jitted ``vmap`` over the stacked
    batch — with the +inf finish buffer donated (the one state buffer big
    enough to matter; the trace arrays are kept — the retry loop re-reads
    them);
  * memoizes compiled executables in a cache keyed on those statics
    (topology keyed by VALUE — kind/sizes/capacities — so two structurally
    identical Topology instances share one compilation);
  * when more than one local device is present, pads the batch to the
    device count and dispatches it as ONE pmap-of-vmap (one shard of the
    batch per device); the single-device path is untouched and stays
    bit-identical;
  * turns on JAX's persistent compilation cache
    (``compile_cache.enable_compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
    if set, else ``<checkout>/.jax_cache``): sweeps relaunch the same programs every
    process, so from the second process on the several-seconds-per-program
    XLA compiles are disk hits.

``run_batch`` is the workhorse; ``run_one`` is the single-trace
convenience wrapper (the co-sim's entry).  ``run_jobs`` worker count comes
from REPRO_SWEEP_WORKERS (default: capped cpu count).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.netsim import compact
from repro.netsim.compile_cache import enable_compile_cache
from repro.netsim.engine import SimConfig, StepOutputs, line_rate_of
from repro.netsim.topology import Topology
from repro.netsim.workloads import Trace
from repro.obs import scopes

F_BUCKET = 2048
W_BUCKET = 256

_JIT_CACHE: dict = {}
_CACHE_STATS = {"builds": 0, "hits": 0}
_OBS_STATS = {"spill_retries": 0, "job_retries": 0, "job_timeouts": 0,
              "job_failures": 0}
# executable-cache key -> the abstract arguments (``jax.ShapeDtypeStruct``
# pytree) of a single-device executable, stored when it is built
_ABSTRACT: dict = {}


def cache_stats() -> dict:
    """Executable-cache counters: ``builds`` = programs constructed (one XLA
    compile each at first call), ``hits`` = dispatches served by an already
    built program.  The co-sim driver (``dist.cosim``) reads this per epoch
    to prove the compile-reuse-across-capacity-changes contract: with
    ``capacity`` passed as a traced operand, every epoch after the first
    must add zero builds."""
    return dict(_CACHE_STATS)


def obs_stats() -> dict:
    """Flight-log counters (DESIGN.md §16): the compile stats plus the
    sweep runner's resilience events — spill retries (``_run_group``
    window doubling), job retries / timeouts / salvaged failures
    (``run_jobs``).  The co-sim driver snapshots this per epoch into the
    flight log so a slow epoch is attributable (recompile? spill retry?
    crashed cell?) without rerunning anything."""
    out = dict(_CACHE_STATS)
    out.update(_OBS_STATS)
    return out


def clear_cache() -> None:
    """Drop compiled executables, so the next dispatch builds cold."""
    _JIT_CACHE.clear()
    _ABSTRACT.clear()
    _CACHE_STATS["builds"] = 0
    _CACHE_STATS["hits"] = 0
    for k in _OBS_STATS:
        _OBS_STATS[k] = 0


def _topo_key(topo: Topology, traced_cap: bool = False) -> tuple:
    """Value key so structurally identical Topology instances share one
    compilation.  Computed fresh every call — an id()-keyed memo would go
    stale when a collected topology's address is reused by a different one
    (the capacity hash is microseconds next to any simulation).

    ``traced_cap`` marks programs that take link capacity as a TRACED
    operand (co-sim fault schedules): the capacity VALUE then must not key
    the executable — every fault state shares one compilation — so the
    hash slot carries a sentinel instead."""
    cap = "traced" if traced_cap else \
        hashlib.sha1(np.asarray(topo.capacity).tobytes()).hexdigest()[:16]
    return (topo.kind, topo.n_leaf, topo.n_paths, topo.hosts_per_leaf,
            topo.n_links, topo.base_rtt_s, cap)


def _round_up(x: int, m: int) -> int:
    return ((max(x, 1) + m - 1) // m) * m


def _f_bucket(F: int) -> int:
    """Power-of-two flow-count buckets (>= F_BUCKET): per-step cost is O(W),
    not O(F), so generous F padding is nearly free at runtime and maximizes
    compile reuse across traces of similar size."""
    b = F_BUCKET
    while b < F:
        b *= 2
    return b


_OP_NAMES = ("capacity", "loss", "reorder")


def _op_kw(ops_sig: tuple) -> tuple:
    """Traced-operand names selected by the (has_capacity, has_loss,
    has_reorder) signature — positional operands after (trace_arrays,
    finish0) map onto ``run_core`` keywords in this fixed order."""
    return tuple(n for n, has in zip(_OP_NAMES, ops_sig) if has)


def _gated_b1(topo: Topology, cfg: SimConfig, W: int, F_pad: int, A: int,
              n_steps: int, cap_seg_steps: int = 0, record=None,
              ops_sig: tuple = ()):
    """Single-sim callable over [1, ...]-leading inputs: no vmap wrapper,
    and the admission block gated behind a REAL lax.cond branch (vmap
    would lower it to both-branches + select) — once arrivals drain (3/4
    of the horizon on paper traces) the O(W) admission work is skipped
    outright.  Shared by the plain B=1 and the one-sim-per-device pmap
    dispatches.  Traced-operand dispatches pass extra UNBATCHED operands
    (capacity, loss, reorder — flagged by ``ops_sig``); the ``*ops``
    varargs map onto ``run_core`` keywords in that fixed order (same
    callable serves every arity — the executable cache key distinguishes
    them)."""
    core = functools.partial(compact.run_core, topo, cfg, W, F_pad, A,
                             n_steps, cap_seg_steps=cap_seg_steps,
                             gate_admission=True, record=record)
    names = _op_kw(ops_sig)

    def fn_one(trace_arrays, finish0, *ops):
        squeeze = lambda a: jnp.squeeze(a, 0)
        out = core(jax.tree.map(squeeze, trace_arrays),
                   jnp.squeeze(finish0, 0), **dict(zip(names, ops)))
        return jax.tree.map(lambda a: a[None], out)

    return fn_one


def _compiled(topo: Topology, cfg: SimConfig, W: int, F_pad: int, A: int,
              n_steps: int, batch: int, ops_sig: tuple = (),
              cap_seg_steps: int = 0, cap_rows: int = 1, record=None,
              args: tuple = ()):
    """``args`` are the call's arguments: a build stores their abstract
    values for ``op_phases``.  ``ops_sig`` flags the traced operands after
    (trace_arrays, finish0) in the fixed order (capacity, loss, reorder) —
    e.g. (True, False, True) = capacity + reorder.  ``cap_seg_steps`` and ``cap_rows`` (K of a 2-D
    schedule) are static shape/stride facts that must key the executable
    alongside the shapes.  ``record`` (hashable ``obs.RecordSpec`` or None)
    keys the executable too: the ring buffer's shapes are a pure function
    of the spec, so recording costs exactly one extra program per (shape
    bucket, spec) and never a rebuild across epochs — the contract
    tests/test_obs.py asserts."""
    key = (_topo_key(topo, any(ops_sig)), cfg, W, F_pad, A, n_steps, batch,
           ops_sig, cap_seg_steps, cap_rows, record)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        if batch == 1:
            fn = jax.jit(_gated_b1(topo, cfg, W, F_pad, A, n_steps,
                                   cap_seg_steps, record, ops_sig),
                         donate_argnums=(1,))
        else:
            core = functools.partial(compact.run_core, topo, cfg, W, F_pad,
                                     A, n_steps, cap_seg_steps=cap_seg_steps,
                                     record=record)
            names = _op_kw(ops_sig)

            def core_kw(trace_arrays, finish0, *ops):
                return core(trace_arrays, finish0, **dict(zip(names, ops)))

            in_axes = (0, 0) + (None,) * len(names)
            fn = jax.jit(jax.vmap(core_kw, in_axes=in_axes),
                         donate_argnums=(1,))
        _JIT_CACHE[key] = fn
        # no sharding: the call's arguments are uncommitted, and a lowering
        # with one would miss the executable's cache entry
        _ABSTRACT[key] = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        _CACHE_STATS["builds"] += 1
    else:
        _CACHE_STATS["hits"] += 1
    return fn


def op_phases() -> dict:
    """``{HLO module name: {instruction: phase}}`` for the built
    single-device executables (``obs.scopes.op_phases`` of each one's
    optimized HLO), so a profiler trace's operations can be summed per
    phase of the step.  A trace names modules, not executables: a module
    name that two executables share with different maps maps to None.
    Each executable is lowered and compiled again from the abstract
    arguments stored at its build; once it has run, that is a hit in JAX's
    in-memory caches, with no XLA compile."""
    out: dict = {}
    for key, abstract in _ABSTRACT.items():
        text = _JIT_CACHE[key].lower(*abstract).compile().as_text()
        name, phases = scopes.module_name(text), scopes.op_phases(text)
        out[name] = phases if out.get(name, phases) == phases else None
    return out


def sweep_devices() -> int:
    """Local devices the sweep runner will shard batches over.  Override
    with REPRO_SWEEP_DEVICES (e.g. 1 to force the plain vmap path)."""
    env = os.environ.get("REPRO_SWEEP_DEVICES")
    n = int(env) if env else jax.local_device_count()
    return max(1, min(n, jax.local_device_count()))


def _compiled_sharded(topo: Topology, cfg: SimConfig, W: int, F_pad: int,
                      A: int, n_steps: int, per_dev: int, n_dev: int,
                      ops_sig: tuple = (), cap_seg_steps: int = 0,
                      cap_rows: int = 1, record=None):
    """pmap-of-vmap executable: inputs carry a leading [n_dev, per_dev]
    batch, one shard per local device.  Each shard runs the identical
    vmapped compact scan, so per-sim results match the single-device path
    (same program, same shapes — only the dispatch is parallel).  Traced
    operands (capacity [+ loss] [+ reorder]) are broadcast to every device
    (in_axes None)."""
    key = (_topo_key(topo, any(ops_sig)), cfg, W, F_pad, A, n_steps, per_dev,
           n_dev, ops_sig, cap_seg_steps, cap_rows, record, "pmap")
    fn = _JIT_CACHE.get(key)
    if fn is None:
        names = _op_kw(ops_sig)
        if per_dev == 1:
            # one sim per device: same gated, vmap-free core as the plain
            # batch==1 path
            inner = _gated_b1(topo, cfg, W, F_pad, A, n_steps, cap_seg_steps,
                              record, ops_sig)
        else:
            core = functools.partial(
                compact.run_core, topo, cfg, W, F_pad, A, n_steps,
                cap_seg_steps=cap_seg_steps, record=record)

            def core_kw(trace_arrays, finish0, *ops):
                return core(trace_arrays, finish0, **dict(zip(names, ops)))

            inner = jax.vmap(core_kw, in_axes=(0, 0) + (None,) * len(names))
        in_axes = (0, 0) + (None,) * len(names)
        fn = jax.pmap(inner, devices=jax.local_devices()[:n_dev],
                      donate_argnums=(1,), in_axes=in_axes)
        _JIT_CACHE[key] = fn
        _CACHE_STATS["builds"] += 1
    else:
        _CACHE_STATS["hits"] += 1
    return fn


# per-scheme lifetime slack for the concurrency bound: flowlet/hash schemes
# track near-ideal FCTs; SeqBalance holds more sub-flows.  DRILL's
# go-back-N collapse can blow far past any a-priori bound at high load —
# deliberately left at the default so the first (cheap) run doubles as the
# probe whose observed concurrency sizes the retry.
_SCHEME_SLACK = {
    "ecmp": (8.0, 100e-6),
    "letflow": (8.0, 100e-6),
    "conga": (8.0, 100e-6),
    "flowlet_timeout": (8.0, 100e-6),
    "seqbalance": (12.0, 150e-6),
}


def plan_window(topo: Topology, traces: list[Trace], *, scheme: str = "seqbalance",
                window_slots: int | None = None,
                sorted_arrays: list[tuple] | None = None) -> int:
    """Shared active-window size for a batch of traces (max of the per-trace
    concurrency bounds, bucketed)."""
    if window_slots is None:
        slack, extra = _SCHEME_SLACK.get(scheme, (12.0, 150e-6))
        line_rate = float(np.asarray(line_rate_of(topo)))
        if sorted_arrays is None:
            sorted_arrays = [compact.sort_trace(t)[0] for t in traces]
        window_slots = max(
            compact.max_concurrency_bound(
                a[0], a[1], a[5], line_rate, slack_slowdown=slack, slack_s=extra
            )
            for a in sorted_arrays
        )
    return _round_up(window_slots, W_BUCKET)


def _observed_concurrency(prepped, finish, horizon_s: float) -> int:
    """Max in-flight flow count actually seen in a (possibly spilled) run —
    spill delays admission, which only stretches flow lifetimes, so this
    upper-estimates the spill-free concurrency."""
    worst = 1
    for b, (arrays, _, F) in enumerate(prepped):
        valid = arrays[5][:F]
        a = arrays[1][:F][valid]  # sorted arrivals
        f = finish[b, :F][valid]
        f = np.where(np.isfinite(f), f, horizon_s)
        end = np.sort(f)
        started = np.arange(1, a.size + 1)
        ended = np.searchsorted(end, a, side="left")
        if a.size:
            worst = max(worst, int((started - ended).max()))
    return worst


def batch_mode() -> str:
    """How a single-device batch is dispatched: "persim" runs each trace
    through the (shared, cached) B=1 executable — on XLA:CPU that wins
    roughly 2x over one vmap: each sim keeps its own early exit instead of
    running to the batch's slowest, and the admission block is a real
    gated branch.  "vmap" restores the one-program-per-bucket batch (the
    right choice on accelerators with idle lanes).  Default: persim on
    cpu, vmap elsewhere; override with REPRO_SWEEP_BATCH."""
    mode = os.environ.get("REPRO_SWEEP_BATCH", "auto")
    if mode in ("persim", "vmap"):
        return mode
    return "persim" if jax.default_backend() == "cpu" else "vmap"


def _dispatch(topo, cfg, W, F_pad, A, n_steps, stacked, B, capacity=None,
              loss=None, cap_seg_steps=0, record=None, reorder=None):
    """Run a stacked [B, ...] batch, returning (finish, cnp, spill,
    ff_steps, outs) with a leading [B] axis.  >1 local device: pad B up to a multiple of
    the device count (duplicating the last row — padding results are
    sliced off) and run one pmap-of-vmap, one batch shard per device.
    Single device: per-sim B=1 executions (cpu) or one jitted vmap — see
    ``batch_mode``.  ``capacity`` (f32[n_links + 1] or a wall-clock
    schedule f32[K, n_links + 1] with static segment stride
    ``cap_seg_steps``, shared by the whole batch) rides along as a traced
    operand when given — fault-schedule sweeps then reuse one executable
    across capacity changes.  ``loss`` (f32[n_links + 1], requires
    ``capacity``) adds the per-link loss-rate operand for go-back-N
    goodput amplification (faults.LossyLink).  ``record`` (static
    ``obs.RecordSpec``) appends the in-sim ring buffer as a sixth output
    leaf with the same leading [B] axis."""
    assert loss is None or capacity is not None, \
        "loss operand requires an explicit capacity operand"
    assert reorder is None or capacity is not None, \
        "reorder operand requires an explicit capacity operand"
    D = sweep_devices()
    if D == 1 and B > 1 and batch_mode() == "persim":
        # every sim in the bucket shares (W, F_pad, A) -> ONE compiled B=1
        # program serves the whole loop
        parts = [
            _dispatch(topo, cfg, W, F_pad, A, n_steps,
                      tuple(a[i:i + 1] for a in stacked), 1, capacity,
                      loss, cap_seg_steps, record, reorder)
            for i in range(B)
        ]
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
    sharded = D > 1 and B > 1
    with scopes.span("repro.sweep.prep"):
        ops = tuple(jnp.asarray(x, jnp.float32) for x in (capacity, loss, reorder)
                    if x is not None)
        ops_sig = (capacity is not None, loss is not None, reorder is not None)
        cap_rows = ops[0].shape[0] if ops and ops[0].ndim == 2 else 1
        if sharded:
            D = min(D, B)
            Bp = -(-B // D) * D
            if Bp > B:
                stacked = tuple(
                    np.concatenate([a, np.repeat(a[-1:], Bp - B, axis=0)])
                    for a in stacked
                )
            per = Bp // D
            args = (tuple(jnp.asarray(a.reshape((D, per) + a.shape[1:]))
                          for a in stacked),
                    jnp.full((D, per, F_pad), jnp.inf, jnp.float32)) + ops
            fn = _compiled_sharded(topo, cfg, W, F_pad, A, n_steps, per, D,
                                   ops_sig, cap_seg_steps, cap_rows, record)
        else:
            args = (tuple(jnp.asarray(a) for a in stacked),
                    jnp.full((B, F_pad), jnp.inf, jnp.float32)) + ops
            fn = _compiled(topo, cfg, W, F_pad, A, n_steps, B, ops_sig,
                           cap_seg_steps, cap_rows, record, args)
    with scopes.span("repro.sweep.dispatch"):
        out = fn(*args)
    if sharded:
        return jax.tree.map(
            lambda a: jnp.reshape(a, (Bp,) + a.shape[2:])[:B], out
        )
    return out


def _run_group(topo, cfg, prepped, n_steps, window_slots, capacity=None,
               loss=None, cap_seg_steps=0, record=None, reorder=None):
    """One vmapped run over traces sharing an F_pad bucket, with the
    spill-retry loop: the concurrency bound is a heuristic, so any sim that
    reports spill_steps > 0 (an arrived flow found no free slot — its
    admission was delayed, which would diverge from the dense oracle) is
    rerun with a window re-planned from the concurrency it actually
    exhibited.  Spill-free sims keep their first-run results — only the
    offenders pay the retry."""
    with scopes.span("repro.sweep.prep"):
        F_pad = _f_bucket(max(F for (_, _, F) in prepped))
        if window_slots is not None:
            # explicit window: honor it exactly (tests probe the retry path)
            W = max(8, min(int(window_slots), F_pad))
        else:
            W = min(plan_window(topo, [], scheme=cfg.scheme,
                                sorted_arrays=[a for (a, _, _) in prepped]), F_pad)
        A = _round_up(max(compact.max_admits_per_step(a[1], a[5], cfg.dt)
                          for (a, _, _) in prepped), 32)
        A = min(A, F_pad)
        padded = [compact.pad_trace_arrays(a, F_pad) for (a, _, _) in prepped]
    results: list = [None] * len(prepped)
    outs_list: list = [None] * len(prepped)
    pending = list(range(len(prepped)))
    while pending:
        with scopes.span("repro.sweep.prep"):
            stacked = tuple(
                np.stack([padded[i][k] for i in pending])
                for k in range(len(padded[0]))
            )
        out = _dispatch(
            topo, cfg, W, F_pad, A, n_steps, stacked, len(pending), capacity,
            loss, cap_seg_steps, record, reorder)
        finish, cnp, spill, ff, outs = out[:5]
        ring = out[5] if len(out) > 5 else None
        with scopes.span("repro.sweep.fetch"):
            spill = np.asarray(spill)
            finish = np.asarray(finish)
            cnp = np.asarray(cnp)
            ff = np.asarray(ff)
        still, still_rows = [], []
        with scopes.span("repro.sweep.unpack"):
            for b, i in enumerate(pending):
                if spill[b] == 0 or W >= F_pad:
                    _, inv, F = prepped[i]
                    results[i] = compact.CompactResult(
                        finish=finish[b, :F][inv], cnp_pkts=cnp[b],
                        spill_steps=int(spill[b]), window_slots=W,
                        ff_steps=int(ff[b]),
                        ring=None if ring is None
                        else jax.tree.map(lambda a, b=b: a[b], ring),
                    )
                    outs_list[i] = jax.tree.map(lambda a, b=b: a[b], outs)
                else:
                    still.append(i)
                    still_rows.append(b)
        pending = still
        if pending:
            _OBS_STATS["spill_retries"] += 1
            with scopes.span("repro.sweep.prep"):
                seen = _observed_concurrency(
                    [prepped[i] for i in pending], finish[still_rows],
                    n_steps * cfg.dt)
            W = min(max(W * 2, _round_up(int(seen * 1.2) + 64, W_BUCKET)), F_pad)
            A = min(A * 2, F_pad)
    return results, outs_list


def run_batch(
    topo: Topology,
    cfg: SimConfig,
    traces: list[Trace],
    *,
    window_slots: int | None = None,
    capacity: np.ndarray | None = None,
    loss: np.ndarray | None = None,
    cap_seg_steps: int = 0,
    record=None,
    reorder: float | None = None,
) -> tuple[list[compact.CompactResult], list[StepOutputs]]:
    """Run every trace under one (scheme, topology) static pair as vmapped,
    donated, cached-compile computations — one per F_pad shape bucket, so a
    small trace is never padded to a 30x larger sibling's shape.

    ``capacity`` (f32[n_links + 1], sentinel slot included) overrides
    ``topo.capacity`` as a TRACED operand shared by the whole batch: co-sim
    fault schedules change link capacities per planning epoch, and threading
    them as data means every epoch reuses the one compiled program (the
    executable cache keys on a "traced" sentinel instead of the capacity
    hash — see ``cache_stats``).  A 2-D schedule f32[K, n_links + 1] plus a
    static ``cap_seg_steps`` stride extends that to wall-clock fault onsets
    (faults.FaultCampaign).  ``loss`` (f32[n_links + 1]) adds the per-link
    loss-rate operand (lossy-link go-back-N amplification); capacity is
    promoted to ``topo.capacity`` automatically if only loss is given.

    ``record`` (an ``obs.RecordSpec``) turns on the in-sim flight recorder:
    each result's ``ring`` field carries the per-chunk summary ring
    (drain with ``obs.drain``).  ``record=None`` is bit-identical to the
    recorder not existing.

    ``reorder`` (scalar, packets) turns on the flowcell reordering-cost
    model: flows whose trace ``spray`` column exceeds 1 pay a go-back-N
    amplification from inter-path skew beyond the budget
    (``dataplane.reorder_gbn_factor``).  Like loss it is a TRACED operand —
    one compiled program covers every budget value and every split factor —
    and ``reorder=None`` traces the identical pre-flowcell program."""
    assert traces, "empty sweep"
    enable_compile_cache()
    if (loss is not None or reorder is not None) and capacity is None:
        capacity = np.asarray(topo.capacity)
    with scopes.span("repro.sweep.prep"):
        prepped = [compact.sort_trace(t) for t in traces]
        groups: dict[int, list[int]] = {}
        for i, (_, _, F) in enumerate(prepped):
            groups.setdefault(_f_bucket(F), []).append(i)
    n_steps = int(round(cfg.duration_s / cfg.dt))
    results: list = [None] * len(traces)
    outs_list: list = [None] * len(traces)
    for idxs in groups.values():
        res, outs = _run_group(topo, cfg, [prepped[i] for i in idxs], n_steps,
                               window_slots, capacity, loss, cap_seg_steps,
                               record, reorder)
        for i, r, o in zip(idxs, res, outs):
            results[i] = r
            outs_list[i] = o
    return results, outs_list


def run_one(topo: Topology, cfg: SimConfig, trace: Trace, *,
            window_slots: int | None = None,
            capacity: np.ndarray | None = None,
            loss: np.ndarray | None = None,
            cap_seg_steps: int = 0,
            record=None,
            reorder: float | None = None):
    results, outs = run_batch(topo, cfg, [trace], window_slots=window_slots,
                              capacity=capacity, loss=loss,
                              cap_seg_steps=cap_seg_steps, record=record,
                              reorder=reorder)
    return results[0], outs[0]


def default_workers(n_jobs: int) -> int:
    """run_jobs worker count: REPRO_SWEEP_WORKERS if set (>=1), else
    ``os.cpu_count()`` capped at the job count."""
    env = os.environ.get("REPRO_SWEEP_WORKERS")
    if env:
        return max(1, min(int(env), max(n_jobs, 1)))
    return max(1, min(n_jobs, os.cpu_count() or 1))


def _run_job(job):
    """One ``run_jobs`` entry.  Three spellings:

      * ``(topo, cfg, traces)``           — the classic per-scheme sweep;
      * ``(topo, cfg, traces, kwargs)``   — same, with ``run_batch`` keyword
        overrides (``capacity=...`` for fault-schedule grids,
        ``window_slots=...``);
      * any zero-argument callable        — an arbitrary multi-step job,
        e.g. one ``dist.cosim.run_cosim`` epoch loop per (scheme, ring,
        fault, seed) grid point.  The callable runs on the worker thread
        and its sweeps go through the same cached-executable dispatch.
    """
    if callable(job):
        return job()
    topo, cfg, traces, *rest = job
    kw = dict(rest[0]) if rest else {}
    return run_batch(topo, cfg, traces, **kw)


@dataclasses.dataclass(frozen=True)
class JobFailure:
    """Poisoned record a salvaged grid cell returns instead of its result:
    the grid completes, the failure stays visible and attributable.  Check
    ``isinstance(r, sweep.JobFailure)`` (or the ``failed`` marker) before
    consuming grid results from a salvaging run."""

    index: int  # position in the run_jobs list (results stay in job order)
    attempts: int
    error: str  # "ExcType: message" of the last attempt
    elapsed_s: float
    timed_out: bool = False

    @property
    def failed(self) -> bool:
        return True


def retry_sleep_s(index: int, attempt: int, backoff_s: float,
                  jitter_frac: float) -> float:
    """Jittered exponential backoff for retry ``attempt`` of job ``index``:
    base ``backoff_s * 2**(attempt-1)`` (capped at 30 s) stretched by a
    uniform factor in [1, 1 + jitter_frac].  The jitter is DETERMINISTIC —
    seeded on (index, attempt) — so tests replay it exactly, yet
    decorrelated across jobs: a pool of cells that all failed together
    (one flaky dependency hiccup) re-arrives spread out instead of as a
    synchronized retry storm re-hammering whatever just recovered.
    ``backoff_s == 0`` sleeps 0 regardless of jitter (the test fast path)."""
    base = min(backoff_s * (2 ** (attempt - 1)), 30.0)
    if base <= 0.0 or jitter_frac <= 0.0:
        return base
    u = float(np.random.default_rng((index, attempt)).uniform())
    return base * (1.0 + jitter_frac * u)


def _run_job_resilient(job, index: int, *, retries: int, backoff_s: float,
                       salvage: bool, jitter_frac: float = 0.5):
    t0 = time.time()
    for attempt in range(1, retries + 2):
        try:
            return _run_job(job)
        except Exception as e:  # noqa: BLE001 — grid cells fail arbitrarily
            if attempt <= retries:
                _OBS_STATS["job_retries"] += 1
                time.sleep(retry_sleep_s(index, attempt, backoff_s,
                                         jitter_frac))
                continue
            if not salvage:
                raise
            _OBS_STATS["job_failures"] += 1
            return JobFailure(index=index, attempts=attempt,
                              error=f"{type(e).__name__}: {e}",
                              elapsed_s=time.time() - t0)
    raise AssertionError("unreachable")


def run_jobs(
    jobs: list,
    *,
    workers: int | None = None,
    timeout_s: float | None = None,
    retries: int = 0,
    backoff_s: float = 0.5,
    jitter_frac: float = 0.5,
    salvage: bool = False,
) -> list:
    """Run independent sweep jobs (e.g. one per scheme, or one co-sim epoch
    loop per grid point — see ``_run_job`` for the accepted spellings)
    concurrently.

    XLA's CPU executables release the GIL, so a small thread pool overlaps
    independent compiles and scans across cores — the five-scheme Fig. 12
    sweep and the (scheme x ring x fault x seed) co-sim grids are
    embarrassingly parallel at this level.  Results are returned in job
    order, identical to serial execution.

    Crash-proofing (all off by default — the bare call is unchanged):

      * ``retries``   — re-run a raising job up to this many extra times,
        sleeping ``backoff_s * 2**attempt`` (capped at 30 s, stretched by
        the seeded per-(job, attempt) jitter of ``retry_sleep_s`` so
        simultaneous failures don't retry as a synchronized storm;
        ``jitter_frac=0`` disables) between tries; transient failures
        (OOM races, flaky I/O) get a second chance.
      * ``salvage``   — a job that still fails returns a ``JobFailure``
        poisoned record IN PLACE, instead of propagating and killing every
        other cell of the grid; the caller decides what a dead cell costs.
      * ``timeout_s`` — advisory per-job cap, enforced at collection time
        (threads cannot be killed: a stuck job's slot is abandoned — its
        cell salvages as ``timed_out`` — but the worker thread itself only
        dies with the process).  Ignored on the serial (workers == 1)
        path, where there is no second thread to collect from.

    Worker count resolution: explicit ``workers`` argument, else the
    REPRO_SWEEP_WORKERS env var, else a capped ``os.cpu_count()``."""
    import concurrent.futures as cf

    enable_compile_cache()  # once, before worker threads race to compile
    if workers is None:
        workers = default_workers(len(jobs))
    if workers == 1 or len(jobs) == 1:
        return [
            _run_job_resilient(j, i, retries=retries, backoff_s=backoff_s,
                               salvage=salvage, jitter_frac=jitter_frac)
            for i, j in enumerate(jobs)
        ]
    pool = cf.ThreadPoolExecutor(max_workers=workers)
    timed_out = False
    try:
        futs = [
            pool.submit(_run_job_resilient, j, i, retries=retries,
                        backoff_s=backoff_s, salvage=salvage,
                        jitter_frac=jitter_frac)
            for i, j in enumerate(jobs)
        ]
        out = []
        for i, f in enumerate(futs):
            try:
                out.append(f.result(timeout=timeout_s))
            except cf.TimeoutError:
                timed_out = True
                _OBS_STATS["job_timeouts"] += 1
                if not salvage:
                    raise
                out.append(JobFailure(index=i, attempts=1,
                                      error="TimeoutError: job still running",
                                      elapsed_s=float(timeout_s or 0.0),
                                      timed_out=True))
        return out
    finally:
        # a hung job's thread cannot be killed — but shutdown(wait=True)
        # would BLOCK the whole pool behind it, turning one stuck cell back
        # into a wedged sweep.  Abandon the slot; the thread dies with the
        # process (exactly the advisory contract documented above).
        pool.shutdown(wait=not timed_out, cancel_futures=timed_out)
