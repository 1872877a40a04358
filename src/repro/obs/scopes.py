"""Named scopes of the engine step, and the map from a compiled module's
instructions to them.

``netsim/compact.py`` runs each phase of the step under ``jax.named_scope``
(``scope`` below): the name lands in the ``op_name`` metadata of every HLO
instruction the phase lowers to, and survives XLA's fusion as the metadata
of a fusion's root.  A profiler trace names device operations by
instruction only (``fusion.276``), so ``op_phases`` reads the optimized HLO
text of the module that ran (``compiled.as_text()``) and gives each
instruction its phase: per-phase device time is then a sum over the trace.

Scopes:

* ``admit`` / ``cascade`` / ``dcqcn`` / ``finish`` — the four phases of a
  step (``build_compact_sim``);
* ``quiesce`` / ``fast_forward`` — the adaptive-dt predicate and closed-form
  macro-step, which run their own cascade;
* ``outputs`` — the step's ``StepOutputs``;
* ``chunk`` — the loop plumbing of ``run_core`` between scan chunks
  (splicing a chunk's outputs into the horizon, the early-exit test,
  recorder rows).

Host spans (``span`` below), on the profiler's clock with the device
trace:

* ``repro.sweep.prep`` / ``.dispatch`` / ``.fetch`` / ``.unpack`` — a
  ``sweep.run_batch`` call: sorting, bucketing, window and admission-lane
  planning, padding, stacking and the host-to-device transfer; the
  executable call alone; the host waiting for spill / finish / cnp / ff;
  results and per-sim outputs.  The last three once per (shape bucket,
  spill retry);
* ``repro.cosim.epoch`` — one epoch of ``dist.cosim.run_cosim``, holding
  in order ``repro.cosim.trace`` (fault state, stragglers, the ring
  schedule and any in-epoch replan), ``repro.cosim.sim`` (the epoch's
  ``sweep.run_one``), ``repro.cosim.feedback`` (congestion reports or the
  telemetry channel, the next plan and its versioned application) and
  ``repro.cosim.record`` (FCT and imbalance statistics, the epoch record,
  the journal and the flight log).
"""
from __future__ import annotations

import functools
import re
from collections import Counter

import jax

SCOPES = ("admit", "cascade", "dcqcn", "finish", "quiesce", "fast_forward",
          "outputs", "chunk")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``, a
    fresh context per call (``named_scope``'s own object keeps its state on
    itself, so one instance shared by threads tracing at once is unsafe).
    Metadata only: the traced equations are the same without it."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; one of {SCOPES}")

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped

    return deco


def span(name: str):
    """``jax.profiler`` host span named ``name``; near-free when no trace is
    being captured.  Timing only: what runs inside is unchanged."""
    return jax.profiler.TraceAnnotation(name)


def phase_of(op_name: str | None) -> str | None:
    """The outermost scope in an ``op_name`` (``jit(f)/while/body/admit/
    cond/...`` -> ``admit``), or None."""
    for part in (op_name or "").split("/"):
        if part in SCOPES:
            return part
    return None


def module_name(hlo_text: str) -> str:
    """The module's name from its first line (``HloModule jit_core_kw, ...``)."""
    m = re.match(r"\s*HloModule\s+([^\s,]+)", hlo_text)
    if m is None:
        raise ValueError("not HLO module text")
    return m.group(1)


def op_phases(hlo_text: str) -> dict[str, str | None]:
    """``{instruction name: phase}`` for every instruction outside fused
    computations (the operations a profiler trace shows).  A fusion takes
    its root's scope: through a nested fusion, and from a multi-output
    root tuple its first scoped operand.  Where the root carries none (the
    TPU compiler's scatter rewrite drops a scatter's metadata), the fusion
    takes the scope most of its instructions carry.  An instruction with no
    scope in its ``op_name`` maps to None."""
    comps: dict[str, dict] = {}  # computation -> {instrs, root}
    fused: set[str] = set()
    cur = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            m = _COMPUTATION.match(line)
            cur = None if m is None else comps.setdefault(
                m.group(1), {"instrs": {}, "root": None})
            continue
        m = _INSTRUCTION.match(line)
        if cur is None or m is None:
            continue
        root, name, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        opcode = op.group(1) if op else ""
        call = _CALLS.search(rest)
        if opcode == "fusion" and call:
            fused.add(call.group(1))
        meta = _OP_NAME.search(rest)
        cur["instrs"][name] = dict(
            opcode=opcode, phase=phase_of(meta.group(1) if meta else None),
            calls=call.group(1) if call else None, operands=_OPERAND.findall(rest))
        if root:
            cur["root"] = name

    memo: dict[str, str | None] = {}

    def resolve(ins: dict) -> str | None:
        if ins["opcode"] == "fusion" and ins["calls"]:
            return fusion_phase(ins["calls"]) or ins["phase"]
        return ins["phase"]

    def fusion_phase(comp: str) -> str | None:
        if comp not in memo:
            memo[comp] = None  # a cycle cannot recurse forever
            c = comps.get(comp)
            if c is not None and c["root"] is not None:
                instrs = c["instrs"]
                root = instrs[c["root"]]
                phase = resolve(root)
                if phase is None and root["opcode"] == "tuple":
                    phase = next((p for o in root["operands"] if o in instrs
                                  for p in [resolve(instrs[o])] if p), None)
                if phase is None:
                    votes = Counter(p for p in map(resolve, instrs.values()) if p)
                    phase = votes.most_common(1)[0][0] if votes else None
                memo[comp] = phase
        return memo[comp]

    return {name: resolve(ins) for cname, c in comps.items() if cname not in fused
            for name, ins in c["instrs"].items()}
