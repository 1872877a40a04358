"""The timed units of a window on the trace's clock: one per ``run_batch``
call of a sweep (the benchmark's ``bench.batch`` spans)."""
from __future__ import annotations


def unit_spans(ctx) -> list[tuple[int, int]]:
    """(start, end) ns of each of the record's ``units``, in order."""
    tr = ctx["trace"]
    return [] if tr is None else tr.spans("bench.batch")
