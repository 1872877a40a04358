"""Per-phase device time of the engine step and host time per span, read
from a traced window.

The program runs each phase of its step under a named scope
(``repro.obs.scopes``) and says, for every built sim executable, which
phase each HLO instruction belongs to: ``repro.netsim.sweep.op_phases()``
gives ``{module name: {instruction: phase}}``, with None for a module name
two executables share with different maps.  A trace names each device
operation by its instruction, so a phase's time in a timed unit is a sum
over the leaf operations of the unit's sim module (the module that ran
longest, as for ``step_us``); control-flow containers are left out, since
they hold the others.  A program without that function (one from before
the scopes) gives no map, and the readers then report nothing.
"""
from __future__ import annotations

import re

from bench.harness.units import unit_spans
from bench.harness.xtrace import CONTAINERS


def program_map(ctx) -> dict | None:
    """The program's op -> phase map, asked once per run (kept in ``ctx``;
    a test puts its own there)."""
    if "op_phases" not in ctx:
        from repro.netsim import sweep

        get = getattr(sweep, "op_phases", None)
        ctx["op_phases"] = None if get is None else get()
    return ctx["op_phases"]


def module_key(event_name: str) -> str:
    """An ``XLA Modules`` event's name without the program id the profiler
    appends on a TPU (``jit_core_kw(6107745517520527096)`` -> ``jit_core_kw``,
    the name in the module's HLO text)."""
    return re.sub(r"\(\d+\)$", "", event_name)


def unit_phase_ns(tr, t0: int, t1: int, maps: dict) -> dict | None:
    """``{phase: ns}`` of the leaf operations of the sim module in [t0, t1]
    (None for operations outside every scope), or None where the unit has
    no module or its module has no single map."""
    per: dict = {}
    mods = tr.modules_within(t0, t1)
    for n, _, d in mods:
        per[n] = per.get(n, 0) + d
    if not per:
        return None
    name = max(per, key=per.get)
    opmap = maps.get(module_key(name))
    if opmap is None:
        return None
    out: dict = {}
    for n, m0, md in mods:
        if n != name:
            continue
        for op, _, d in tr.ops(None, m0, m0 + md):
            if not op.startswith(CONTAINERS):
                ph = opmap.get(op)
                out[ph] = out.get(ph, 0) + d
    return out


def phase_us(ctx, phase: str) -> float | None:
    """Device microseconds per executed step spent in ``phase``, over the
    window's units whose module has a map (steps as for ``step_us``)."""
    tr, units = ctx["trace"], ctx["record"].get("units")
    spans = unit_spans(ctx)
    maps = program_map(ctx) if units and spans else None
    if not maps:
        return None
    ns = steps = 0
    for (s, e), u in zip(spans, units):
        got = unit_phase_ns(tr, s, e, maps)
        if got is not None:
            ns += got.get(phase, 0)
            steps += u["steps"]
    return None if steps <= 0 else 1e-3 * ns / steps


def span_ms(ctx, name: str) -> float | None:
    """Mean over the window's units of the summed durations of the host
    spans called ``name`` inside each unit, in ms; None without any."""
    tr = ctx["trace"]
    units = unit_spans(ctx)
    inner = tr.spans(name) if units else []
    if not inner:
        return None
    per = [sum(b - a for a, b in inner if s <= a and b <= e) for s, e in units]
    return 1e-6 * sum(per) / len(per)
