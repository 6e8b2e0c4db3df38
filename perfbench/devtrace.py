"""From a profiler trace to device busy time, idle share and a breakdown.

The JAX profiler writes one ``.xplane.pb`` per traced window.  Its device
planes (``/device:TPU:<n>``) carry the operations the chip ran, on the line
``XLA Ops``; the host plane (``/host:CPU``) carries the benchmark's
``bench.<kind>`` annotations on the thread that made the calls, beside the
runtime's own host events.  Busy time is the union of the device operations'
intervals: time in which at least one operation ran.  All times here are in
nanoseconds on the trace's clock, returned as seconds.

An operation's scope is the ``tf_op`` stat of its event metadata: the
``jax.named_scope`` path it was traced under
(``jit(_encode_device_jit)/szx_assemble/compact_mid/...``).  ``ProfileData``
does not show metadata stats, so ``op_scopes`` reads them from the serialized
XSpace itself.  Events are named by their HLO text, which two programs can
share under different scopes: such a name is ambiguous, and its time is
given to no scope.
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
SCOPE_STAT = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int = 0, hi: int | None = None):
    """``(field number, value)`` of the protobuf message in ``buf[lo:hi]``:
    an int for a varint, a ``(start, end)`` slice of ``buf`` for a
    length-delimited field, None for a fixed-width one."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries) -> list:
    """The value slices of protobuf map entries (key 1, value 2)."""
    return [v for lo, hi in entries for n, v in _fields(buf, lo, hi) if n == 2]


def op_scopes(xspace: bytes, chips=None) -> tuple[dict, set]:
    """``({op name: scope}, ambiguous names)`` over the device planes of
    ``chips`` (all, if None), from a serialized XSpace (``xplane.proto``:
    XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5;
    XEventMetadata.name 2, stats 5; XStat.metadata_id 1, str_value 5,
    ref_value 7; XStatMetadata.id 1, name 2).  A name seen under two
    different scopes is ambiguous and gets none."""
    buf = memoryview(xspace)
    seen: dict = defaultdict(set)
    for n, plane in _fields(buf):
        if n != 1:
            continue
        name, events, stats = "", [], []
        for m, v in _fields(buf, *plane):
            if m == 2:
                name = _text(buf, v)
            elif m == 4:
                events.append(v)
            elif m == 5:
                stats.append(v)
        chip = name[len(DEVICE_PREFIX):]
        if not (name.startswith(DEVICE_PREFIX) and chip.isdigit()):
            continue
        if chips is not None and int(chip) not in chips:
            continue
        stat_names = {}
        for lo, hi in _map_values(buf, stats):
            f = dict(_fields(buf, lo, hi))
            stat_names[f.get(1, 0)] = _text(buf, f[2]) if 2 in f else ""
        for lo, hi in _map_values(buf, events):
            op, scope = "", None
            for m, v in _fields(buf, lo, hi):
                if m == 2:
                    op = _text(buf, v)
                elif m == 5:
                    f = dict(_fields(buf, *v))
                    if stat_names.get(f.get(1, 0)) != SCOPE_STAT:
                        continue
                    if 5 in f:
                        scope = _text(buf, f[5])
                    elif 7 in f:
                        scope = stat_names.get(f[7], "")
            if scope is not None:
                seen[op].add(scope)
    scopes = {op: next(iter(s)) for op, s in seen.items() if len(s) == 1}
    return scopes, {op for op, s in seen.items() if len(s) > 1}


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

    def __init__(self, planes, chips=None, scopes=None, ambiguous=()):
        """``chips``: the ids of the devices the cell runs on (all, if None);
        ``scopes``, ``ambiguous``: what ``op_scopes`` read of the ops."""
        self.scopes: dict = dict(scopes or {})   # op name -> tf_op scope
        self.ambiguous: set = set(ambiguous)     # op names of two scopes
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
        with open(paths[-1], "rb") as f:
            xspace = f.read()
        return cls(ProfileData.from_serialized_xspace(xspace).planes, chips,
                   *op_scopes(xspace, chips))

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

    def scoped_seconds(self, kind: str, scope: str) -> float | None:
        """Device time of the ops with ``scope`` among the components of
        their scope path, inside the ``kind`` annotations, averaged over the
        chips; None where the trace names no op's scope or holds no ``kind``
        annotation."""
        region = self.kind_union.get(kind)
        if not region or not self.scopes or not self.ops:
            return None
        inside = {name for name, path in self.scopes.items() if scope in path.split("/")}
        return sum(overlap(union((s, e) for s, e, name in ops if name in inside), region)
                   for ops in self.ops.values()) / len(self.ops) / 1e9

    def ambiguous_seconds(self, kind: str) -> float:
        """Device time of the ops whose name two scopes share (given to no
        scope), inside the ``kind`` annotations, averaged over the chips."""
        region = self.kind_union.get(kind, [])
        return sum(overlap(union((s, e) for s, e, name in ops if name in self.ambiguous),
                           region) for ops in self.ops.values()) / max(len(self.ops), 1) / 1e9

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
