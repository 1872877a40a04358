"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A TPU's plane is named
``/device:TPU:<i>``; its ``XLA Ops`` line holds one event per device
operation and its ``XLA Modules`` line one per executable run.  Host
threads sit on ``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans
(the benchmark's ``bench.*`` and the program's ``repro.*``) appear by name.
All start times are in nanoseconds on one clock.

The reduction works on plain tuples (``Trace``), so it can be checked on a
hand-built trace without a chip:

* window: from the first to the last of the benchmark's spans around the
  calls it times (``WINDOW_SPANS``), or the whole trace when there is none;
* busy: the union of the intervals in which an operation ran on a device,
  clipped to the window and averaged over the devices seen;
* kernel time: the summed device durations of the operations whose name
  holds a given fragment;
* idle gaps: the stretches of the window with no operation on the device,
  each labelled by the innermost host span open at its midpoint.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPANS = ("bench.batch",)


@dataclass
class Trace:
    """Events as (name, start_ns, duration_ns) tuples."""

    device_ops: dict = field(default_factory=dict)  # device plane -> [events]
    modules: dict = field(default_factory=dict)  # device plane -> [events]
    host: list = field(default_factory=list)  # host spans


def load(trace_dir: str) -> Trace:
    """Read every ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    out = Trace()
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name in (OPS_LINE, MODULES_LINE):
                        dest = out.device_ops if line.name == OPS_LINE else out.modules
                        dest.setdefault(plane.name, []).extend(
                            (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                            for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    out.host.extend((e.name, int(e.start_ns), int(e.duration_ns))
                                    for e in line.events
                                    if e.name.startswith(("bench.", "repro.")))
    return out


def op_name(text: str) -> str:
    """An operation event is named by its whole HLO instruction
    (``%fusion.276 = f32[81920]... fusion(...)``); keep the instruction's
    name (``fusion.276``, ``linkload_cascade_tiered.8``)."""
    return text.split(" = ", 1)[0].lstrip("%")


# control flow: their events span the operations of their bodies
CONTAINERS = ("while", "conditional", "call")


def _union(intervals):
    """Merge [(start, end)] into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _overlap(merged, t0, t1) -> int:
    return sum(max(0, min(e, t1) - max(s, t0)) for s, e in merged)


class Summary:
    """Device numbers of one traced window (seconds unless named _ns)."""

    def __init__(self, trace: Trace, window_names=WINDOW_SPANS):
        self.trace = trace
        spans = [(s, s + d) for n, s, d in trace.host if n in window_names]
        all_ops = [ev for evs in trace.device_ops.values() for ev in evs]
        if spans:
            self.t0, self.t1 = min(s for s, _ in spans), max(e for _, e in spans)
        elif all_ops:
            self.t0 = min(s for _, s, _ in all_ops)
            self.t1 = max(s + d for _, s, d in all_ops)
        else:
            self.t0 = self.t1 = 0
        self.n_devices = max(1, len(trace.device_ops))
        self._merged = {p: _union([(s, s + d) for _, s, d in evs])
                        for p, evs in trace.device_ops.items()}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_within(self.t0, self.t1)

    def busy_within(self, t0: int, t1: int) -> float:
        """Device-busy seconds inside [t0, t1] ns, averaged over devices."""
        if not self._merged:
            return 0.0
        tot = sum(_overlap(m, t0, t1) for m in self._merged.values())
        return tot * 1e-9 / self.n_devices

    def spans(self, name: str) -> list[tuple[int, int]]:
        """(start, end) ns of the host spans called ``name``, in order."""
        return sorted((s, s + d) for n, s, d in self.trace.host if n == name)

    def ops(self, fragment: str | None = None, t0: int | None = None,
            t1: int | None = None) -> list[tuple[str, int, int]]:
        """Device operations in the window (or [t0, t1]) whose name holds
        ``fragment``."""
        lo = self.t0 if t0 is None else t0
        hi = self.t1 if t1 is None else t1
        return [(n, s, d) for evs in self.trace.device_ops.values() for n, s, d in evs
                if s >= lo and s + d <= hi and (fragment is None or fragment in n)]

    def op_seconds(self, fragment: str, t0=None, t1=None) -> float:
        """Device seconds of the matching operations, averaged over devices."""
        return sum(d for _, _, d in self.ops(fragment, t0, t1)) * 1e-9 / self.n_devices

    def modules_within(self, t0: int, t1: int) -> list[tuple[str, int, int]]:
        return [(n, s, d) for evs in self.trace.modules.values() for n, s, d in evs
                if s >= t0 and s + d <= t1]

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Idle stretches of the window on the first device, longest first,
        labelled by the innermost host span open at each one's midpoint."""
        if not self._merged:
            return []
        merged = next(iter(self._merged.values()))
        gaps, cur = [], self.t0
        for s, e in merged:
            if e <= self.t0 or s >= self.t1:
                continue
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        out = []
        for g0, g1 in gaps:
            mid = (g0 + g1) // 2
            open_spans = [(d, n) for n, s, d in self.trace.host if s <= mid <= s + d]
            label = min(open_spans)[1] if open_spans else "outside benchmark spans"
            out.append((label, (g1 - g0) * 1e-9))
        return sorted(out, key=lambda x: -x[1])

    def breakdown(self, k: int = 10) -> dict:
        """The operations that took most device time (control-flow
        containers left out: they hold the others) and the longest idle
        gaps."""
        tot: dict[str, int] = {}
        for n, _, d in self.ops():
            if not n.startswith(CONTAINERS):
                tot[n] = tot.get(n, 0) + d
        top = sorted(tot.items(), key=lambda x: -x[1])[:k]
        return {"device_ops": [[n, d * 1e-9 / self.n_devices] for n, d in top],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps()[:k]]}


def reduce(trace_dir: str) -> Summary:
    return Summary(load(trace_dir))
