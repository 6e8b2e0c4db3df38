"""Device idle inside the program's own spans: what the per-layer metrics
of single host stages read.

While a trace records, every ``repro.obs`` span also opens a
``jax.profiler.TraceAnnotation`` of its name, so the program's host stages
(``plan.*``, ``container.*``, ``device.encode.*``, ``device.decode.*``,
``store.*``) land on the host line of the calling thread, inside the
benchmark's ``bench.<kind>`` annotation (``Reduction.host``).  Each number
is clipped to the ``bench.<kind>`` annotations and averaged over the cell's
chips, as ``Reduction.busy_seconds`` is.  Where the trace holds none of the
spans asked for (a program that does not record them), a reader gets None.
"""
from __future__ import annotations

import bisect

import devtrace


def intersect(a: list, b: list) -> list:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def region(trace, kind: str, names: tuple) -> list:
    """The union of the host spans whose name starts with one of ``names``,
    clipped to the ``bench.<kind>`` annotations."""
    marks = trace.kind_union.get(kind)
    if not marks:
        return []
    spans = devtrace.union((s, e) for s, e, name in trace.host if name.startswith(names))
    return intersect(spans, marks)


def idle_seconds(trace, kind: str, names: tuple):
    """Device idle time inside ``region(trace, kind, names)``, in seconds per
    chip; None where the region is empty or no chip ran anything."""
    inside = region(trace, kind, names)
    if not inside or not trace.busy:
        return None
    span = devtrace.measure(inside)
    idle = sum(span - devtrace.overlap(b, inside) for b in trace.busy.values())
    return idle / len(trace.busy) / 1e9


def count(trace, kind: str, name: str) -> int:
    """Host spans called ``name`` that start inside a ``bench.<kind>``
    annotation."""
    marks = trace.kind_union.get(kind, [])
    ends = [e for _, e in marks]
    n = 0
    for s, _, ev in trace.host:
        if ev == name:
            i = bisect.bisect_right(ends, s)
            n += i < len(marks) and marks[i][0] <= s
    return n


def per_ms(seconds, n: int):
    """``seconds`` in milliseconds per one of ``n`` (None for none)."""
    return None if seconds is None or not n else seconds * 1e3 / n


def idle_ms_per(ctx, kind: str, names: tuple, per_span: str | None = None):
    """Device idle (ms) inside the ``names`` spans of ``bench.<kind>``, per
    ``per_span`` span of that kind (a frame: ``device.*.get``), or per
    ``bench.<kind>`` call where ``per_span`` is None."""
    if ctx.trace is None:
        return None
    n = ctx.trace.count(kind) if per_span is None else count(ctx.trace, kind, per_span)
    return per_ms(idle_seconds(ctx.trace, kind, names), n)

