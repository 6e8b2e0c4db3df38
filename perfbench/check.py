"""How ``correct`` is decided: what the timed path produced against the plain
reference (``reference.py``), once the window has closed.

Each number is compared with a limit of its own; ``correct`` holds when
every number is within its limit.

* ``frames_differing``: frames of the window's compress calls whose bytes
  differ from the reference's frame at the same position, or that are
  missing (limit 0: the container format is exact).
* ``values_differing``: values of the sampled restores or reads that differ,
  bit for bit, from the reference's decode of its own frames (limit 0).
* ``max_err_over_bound``: the largest ``|restored - original| / e`` over the
  sampled restores or reads, ``e`` the absolute bound resolved by the
  reference (limit 1, the guarantee the configuration states).
* ``host_path_frames``: frames (chunks, for a read) of the warm-up and the
  window that did not go through the device: the program's transfer
  counters (``repro.obs``) against the frames the harness handed over or
  got back (limit 0: the benchmark times the device route).
* ``calls_without_device_ops`` (traced runs): the window's calls inside
  whose annotation no operation ran on the chip (limit 0).

A traffic kind of its own (``perfbench/kinds/``) may add numbers with limits
of its own (its ``LIMITS``, none of them a name above); its units are held
to the route counters below like the built-in kinds' units.
"""
from __future__ import annotations

import itertools

import numpy as np

import reference

LIMITS = {"frames_differing": 0, "values_differing": 0, "max_err_over_bound": 1.0,
          "host_path_frames": 0, "calls_without_device_ops": 0}

# The program's counter of device transfers, one per frame or chunk, by kind
# of call: (counter, op label).
ROUTE_COUNTERS = {"compress": ("device.get.calls", "encode_stream"),
                  "decompress": ("device.put.calls", "decode_stream"),
                  "roi": ("device.put.calls", "decode_range")}


def host_path_frames(expected: dict) -> int:
    """Frames (by kind of call) that the program's counters do not show
    crossing to or from the device, since ``repro.obs`` was last reset."""
    from repro import obs

    return sum(max(0, n - int(obs.counter(ROUTE_COUNTERS[kind][0],
                                          op=ROUTE_COUNTERS[kind][1]).value))
               for kind, n in expected.items())


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view({2: np.uint16, 4: np.uint32, 8: np.uint64}[a.dtype.itemsize])


def _compare_values(got: np.ndarray, want: np.ndarray, x: np.ndarray, e: float):
    """(values differing, max error over bound) of one output."""
    if got.shape != want.shape:
        return want.size, float("inf")
    if got.dtype != want.dtype:
        differ = want.size
    else:
        differ = int(np.count_nonzero(_bits(got) != _bits(want)))
    err = np.abs(got.astype(np.float64) - x.astype(np.float64))
    worst = float(np.nanmax(err)) if err.size else 0.0
    if np.isnan(err).any():
        worst = float("inf")
    return differ, worst / e


def numbers(values: dict, limits: dict | None = None) -> dict:
    """``{name: {"value", "limit"}}`` for every number compared; a number
    that ``LIMITS`` lacks takes its limit from ``limits`` (a kind's own)."""
    return {k: {"value": v, "limit": LIMITS[k] if k in LIMITS else limits[k]}
            for k, v in values.items()}


def correct(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())


def roundtrip(x: np.ndarray, config: dict, legs: list, outputs: list,
              frame_bytes: int) -> dict:
    """Frames of every compress call, and the sampled restores."""
    fmt = reference.FORMATS[config["dtype"]]
    rel = config["bound"]["value"]
    want = reference.compress_frames(x, rel, fmt, chunk_bytes=frame_bytes)
    frames_differing = sum(
        sum(1 for i, fr in enumerate(frames) if i >= len(want) or fr != want[i])
        + max(0, len(want) - len(frames))
        for frames in legs
    )
    flat = x.reshape(-1)
    e = reference.rel_bound(flat, rel)
    decoded = reference.decompress_frames(want) if outputs else None
    differ, worst = 0, 0.0
    for _label, y in outputs:
        d, w = _compare_values(np.asarray(y).reshape(-1), decoded, flat, e)
        differ += d
        worst = max(worst, w)
    if not outputs:                      # nothing restored: nothing shown correct
        differ, worst = flat.size, float("inf")
    return numbers({"frames_differing": frames_differing,
                    "values_differing": differ,
                    "max_err_over_bound": worst})


class StoreReads:
    """The reference's answer to a store read: each chunk of the grid
    compressed on its own at the field's bound, decoded, and cut to the box."""

    def __init__(self, x: np.ndarray, e: float, fmt: reference.Format, chunk: tuple):
        self.x, self.e, self.fmt, self.chunk = x, e, fmt, chunk
        self.cache: dict = {}

    def _chunk(self, coord):
        if coord not in self.cache:
            box = tuple(slice(c * s, min((c + 1) * s, d))
                        for c, s, d in zip(coord, self.chunk, self.x.shape))
            part = np.ascontiguousarray(self.x[box])
            vals = reference.decode_stream(reference.encode_stream(part, self.e, self.fmt))
            self.cache[coord] = (box, vals.reshape(part.shape))
        return self.cache[coord]

    def read(self, lo: list, hi: list) -> np.ndarray:
        out = np.empty([b - a for a, b in zip(lo, hi)], self.fmt.dtype)
        ranges = [range(a // c, (b - 1) // c + 1) for a, b, c in zip(lo, hi, self.chunk)]
        for coord in itertools.product(*ranges):
            box, vals = self._chunk(coord)
            inter = [(max(a, b.start), min(h, b.stop)) for a, h, b in zip(lo, hi, box)]
            src = tuple(slice(a - b.start, z - b.start) for (a, z), b in zip(inter, box))
            dst = tuple(slice(a - o, z - o) for (a, z), o in zip(inter, lo))
            out[dst] = vals[src]
        return out


def box_bounds(shape: tuple, axis: int, k: int, width: int) -> tuple[list, list]:
    """Corners of the box ``[k:k+width]`` along ``axis``, whole elsewhere."""
    lo = [0] * len(shape)
    hi = list(shape)
    lo[axis], hi[axis] = k, k + width
    return lo, hi


def region_reads(x: np.ndarray, config: dict, chunk: tuple, axis: int, width: int,
                 outputs: list) -> dict:
    """Sampled reads (offset, values) against the reference's decode of the
    store chunks each box touches."""
    fmt = reference.FORMATS[config["dtype"]]
    e = reference.rel_bound(x.reshape(-1), config["bound"]["value"])
    store = StoreReads(x, e, fmt, chunk)
    differ, worst = 0, 0.0
    for k, got in outputs:
        lo, hi = box_bounds(x.shape, axis, k, width)
        want = store.read(lo, hi)
        orig = x[tuple(slice(a, b) for a, b in zip(lo, hi))]
        d, w = _compare_values(np.asarray(got), want, orig, e)
        differ += d
        worst = max(worst, w)
    if not outputs:
        differ, worst = 1, float("inf")
    return numbers({"values_differing": differ, "max_err_over_bound": worst})
