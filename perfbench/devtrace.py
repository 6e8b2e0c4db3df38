"""From a profiler trace to device busy time, idle share and a breakdown.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Its device
planes (``/device:TPU:<n>``) carry the operations the chip ran, on the line
``XLA Ops``; the host plane (``/host:CPU``) carries the benchmark's
``bench.<kind>`` annotations on the thread that made the calls, beside the
runtime's own host events.  Busy time is the union of the device operations'
intervals: time in which at least one operation ran.  All times here are in
nanoseconds on the trace's clock, returned as seconds.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION = "bench."


def union(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a: list, b: list) -> float:
    """Measure of the intersection of two disjoint sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def measure(a: list) -> float:
    return sum(e - s for s, e in a)


def gaps(busy: list, lo: float, hi: float) -> list:
    """(start, end) of the stretches of [lo, hi] that ``busy`` leaves idle."""
    out, t = [], lo
    for s, e in busy:
        if e <= lo or s >= hi:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Reduction:
    """A traced window reduced to intervals: device ops per chip, the
    benchmark's annotations by kind, and the host events of their thread."""

    def __init__(self, planes, chips=None):
        """``chips``: the ids of the devices the cell runs on (all, if None)."""
        self.ops: dict = {}                  # chip -> [(start, end, name)]
        self.spans: dict = defaultdict(list)  # kind -> [(start, end)]
        self.host: list = []                 # (start, end, name) on the annotated thread
        for plane in planes:
            chip = plane.name[len(DEVICE_PREFIX):]
            if plane.name.startswith(DEVICE_PREFIX) and chip.isdigit():
                if chips is not None and int(chip) not in chips:
                    continue
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        self.ops[plane.name] = [
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                            for ev in line.events
                        ]
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                              for ev in line.events]
                    marked = [ev for ev in events if ev[2].startswith(ANNOTATION)]
                    if not marked:
                        continue
                    for s, e, name in marked:
                        self.spans[name[len(ANNOTATION):]].append((s, e))
                    self.host.extend(ev for ev in events if not ev[2].startswith(ANNOTATION))
        self.busy = {chip: union((s, e) for s, e, _ in ops) for chip, ops in self.ops.items()}
        self.kind_union = {k: union(v) for k, v in self.spans.items()}
        marked = [iv for v in self.spans.values() for iv in v]
        self.window = (min(s for s, _ in marked), max(e for _, e in marked)) if marked else None

    @classmethod
    def from_dir(cls, log_dir: str, chips=None) -> "Reduction":
        from jax.profiler import ProfileData

        paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls(ProfileData.from_file(paths[-1]).planes, chips)

    # ----------------------------------------------------------- numbers
    def span_seconds(self, kind: str) -> float:
        return measure(self.kind_union.get(kind, [])) / 1e9

    def count(self, kind: str) -> int:
        return len(self.spans.get(kind, []))

    def busy_seconds(self, kind: str | None = None) -> float | None:
        """Device busy time inside the ``kind`` annotations (or the whole
        window), averaged over the chips; None where no chip ran anything."""
        if not any(self.busy.values()):
            return None
        if kind is None:
            if self.window is None:
                return None
            region = [self.window]
        else:
            region = self.kind_union.get(kind)
            if not region:
                return None
        return sum(overlap(b, region) for b in self.busy.values()) / len(self.busy) / 1e9

    def silent(self, kind: str) -> int:
        """``kind`` annotations inside which no operation ran on any chip."""
        busy = [b for b in self.busy.values()]
        return sum(1 for s, e in self.spans.get(kind, [])
                   if not any(overlap(b, [(s, e)]) for b in busy))

    def window_seconds(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9 if self.window else 0.0

    # ---------------------------------------------------------- breakdown
    def top_ops(self, top: int = 10) -> list:
        """The device operations that took most time, summed by name, in
        seconds per chip."""
        total: dict = defaultdict(float)
        for ops in self.ops.values():
            for s, e, name in ops:
                total[name] += (e - s) / 1e9
        n = max(len(self.ops), 1)
        return [[name, t / n] for name, t in
                sorted(total.items(), key=lambda kv: -kv[1])[:top]]

    def idle_by_host(self, top: int = 10) -> list:
        """Device idle time inside the window, summed by what the host was
        doing: each idle stretch is cut at the benchmark's annotations, and
        each piece is named by its annotation and by the innermost host event
        covering its midpoint (``-`` for none)."""
        if self.window is None or not self.busy:
            return []
        lo, hi = self.window
        marks = sorted((a, b, kind) for kind, v in self.kind_union.items() for a, b in v)
        pieces = []                          # (midpoint, length, kind)
        for busy in self.busy.values():
            for s, e in gaps(busy, lo, hi):
                j = max(bisect.bisect_right(marks, (s, float("inf"), "")) - 1, 0)
                t = s
                while t < e:
                    while j < len(marks) and marks[j][1] <= t:
                        j += 1
                    if j < len(marks) and marks[j][0] <= t:
                        end, kind = min(e, marks[j][1]), marks[j][2]
                    else:
                        end, kind = (min(e, marks[j][0]) if j < len(marks) else e), "-"
                    pieces.append((0.5 * (t + end), end - t, kind))
                    t = end
        host = sorted(self.host)
        active: list = []                    # heap of (end, start, name)
        i = 0
        total: dict = defaultdict(float)
        for mid, length, kind in sorted(pieces):
            while i < len(host) and host[i][0] <= mid:
                heapq.heappush(active, (host[i][1], host[i][0], host[i][2]))
                i += 1
            while active and active[0][0] < mid:
                heapq.heappop(active)
            what = min(active, key=lambda ev: ev[0] - ev[1])[2] if active else "-"
            total[f"bench.{kind} / {what}"] += length / 1e9
        n = len(self.busy)
        return [[name, t / n] for name, t in
                sorted(total.items(), key=lambda kv: -kv[1])[:top]]
