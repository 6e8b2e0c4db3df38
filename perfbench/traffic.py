"""The one traffic generator: drives the system under test from a mix file.

A mix (``perfbench/mixes/<name>.json``) names its ``kind`` and parameters:

* ``roundtrip``: one caller in a closed loop compresses the whole field with
  ``SZxCodec(backend="auto").compress_chunked`` (frames of the
  configuration's ``frame_bytes``), then restores it with
  ``decompress_chunked(frames, n=field.size)``, back to back.
* ``roi``: the field is saved once with ``ArrayStore.save`` (host backend,
  chunks of at most the mix's ``chunk_bytes``); one caller in a closed loop reads boxes
  ``width`` wide along ``axis`` at offsets drawn uniformly from the seed, each
  read being ``ArrayStore.open(path, backend="auto", device=True)[box]``.

A unit of work is one whole call: a ``compress_chunked`` over the field, a
``decompress_chunked`` of its frames, a read.  Its time runs from the call to
its return, so whatever the program does inside (lookahead, overlap of
transfers) is timed in full.  The window is cut between units: a unit (for
``roundtrip``, a compress and the restore of its frames) started before the
deadline finishes and counts.  Every unit runs inside a
``jax.profiler.TraceAnnotation`` named ``bench.<kind>``.

Any other ``kind`` is a module of its own, ``perfbench/kinds/<kind>.py``,
loaded by ``load_kind``; ``perfbench/kinds/README.md`` gives its contract.
"""
from __future__ import annotations

import importlib.util
import os
import tempfile
import time
import traceback
from dataclasses import dataclass, field as dfield
from pathlib import Path

import numpy as np

import check
import reference

KEEP_BYTES = 1 << 30          # restored fields kept for the check, at most
KINDS_DIR = Path(__file__).resolve().parent / "kinds"


@dataclass
class Unit:
    kind: str                 # compress | decompress | roi, or a kind module's own
    t0: float
    t1: float
    raw_bytes: int
    framed_bytes: int = 0
    frames: int = 0           # frames the call produced or restored (chunks, for a read)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class Window:
    """What one measured window did, and what it produced for the check."""

    units: list = dfield(default_factory=list)
    t_start: float = 0.0
    t_end: float = 0.0
    failed: int = 0
    legs: list = dfield(default_factory=list)      # frame lists, one per compress
    outputs: list = dfield(default_factory=list)   # (label, array) kept for the check
    seen: int = 0                                  # outputs offered to the sample

    def keep(self, rng, cap: int, label, value) -> None:
        """Reservoir sample of ``cap`` outputs, drawn from the seed."""
        self.seen += 1
        if len(self.outputs) < cap:
            self.outputs.append((label, value))
        else:
            j = int(rng.integers(0, self.seen))
            if j < cap:
                self.outputs[j] = (label, value)

    def fail(self) -> None:
        """Count a unit that raised; the first traceback goes to stderr."""
        if not self.failed:
            traceback.print_exc()
        self.failed += 1

    @property
    def attempted(self) -> int:
        return len(self.units) + self.failed

    def frames(self, kind: str) -> int:
        return sum(u.frames for u in self.units if u.kind == kind)


def _annotation(kind: str, **stats):
    import jax

    return jax.profiler.TraceAnnotation(f"bench.{kind}", **stats)


class Roundtrip:
    """Compress the field into frames, restore it, again, for the window."""

    def __init__(self, config: dict, mix: dict, x: np.ndarray, seed: int):
        from repro.api import Bound, SZxCodec

        self.x = x
        self.config = config
        self.rel = config["bound"]["value"]
        self.frame_bytes = config["frame_bytes"]
        self.codec = SZxCodec(backend="auto")
        self.bound = Bound.rel(self.rel)
        self.rng = np.random.default_rng([seed, 1])
        self.cap = max(1, KEEP_BYTES // x.nbytes)

    def warm(self) -> dict:
        """Compile (or load) the programs of each frame geometry the field
        makes: its first frame and, where the field does not fill its last
        frame, that tail -- each once, at the field's own absolute bound so
        the restore sees the same body sizes as in the window.  Returns the
        frames compressed and restored."""
        from repro.api import Bound

        flat = self.x.reshape(-1)
        per = reference.chunk_elements(self.x.dtype.itemsize, self.frame_bytes)
        e = Bound.abs(reference.rel_bound(flat, self.rel))
        pieces = [flat[:per]]
        if flat.size > per and flat.size % per:
            pieces.append(flat[flat.size - flat.size % per:])
        n = 0
        for piece in pieces:
            frames = list(self.codec.compress_chunked(piece, e, chunk_bytes=self.frame_bytes))
            self.codec.decompress_chunked(frames, n=piece.size)
            n += len(frames)
        return {"compress": n, "decompress": n}

    def run(self, seconds: float, on_close) -> Window:
        w = Window()
        w.t_start = time.perf_counter()
        deadline = w.t_start + seconds
        while time.perf_counter() < deadline:
            frames = self._compress(w)
            if frames is not None:
                self._decompress(w, frames)
        w.t_end = time.perf_counter()
        on_close()
        return w

    def _compress(self, w: Window):
        t0 = time.perf_counter()
        try:
            with _annotation("compress", call=len(w.legs)):
                frames = list(self.codec.compress_chunked(self.x, self.bound,
                                                          chunk_bytes=self.frame_bytes))
        except Exception:
            w.fail()
            return None
        t1 = time.perf_counter()
        w.legs.append(frames)
        w.units.append(Unit("compress", t0, t1, self.x.nbytes,
                            sum(len(f) for f in frames), len(frames)))
        return frames

    def _decompress(self, w: Window, frames) -> None:
        t0 = time.perf_counter()
        try:
            with _annotation("decompress", call=len(w.legs) - 1):
                y = self.codec.decompress_chunked(frames, n=self.x.size)
        except Exception:
            w.fail()
            return
        t1 = time.perf_counter()
        w.units.append(Unit("decompress", t0, t1, self.x.nbytes,
                            sum(len(f) for f in frames), len(frames)))
        w.keep(self.rng, self.cap, len(w.legs) - 1, y)

    def check(self, w: Window, reduction=None) -> dict:
        return check.roundtrip(self.x, self.config, w.legs, w.outputs, self.frame_bytes)

    def close(self) -> None:
        pass


def chunk_shape(shape, itemsize: int, chunk_bytes: int) -> tuple:
    """Store chunks that keep the trailing axes whole and split the leading
    ones until a chunk holds at most ``chunk_bytes`` (zarr's rule)."""
    rem = max(chunk_bytes // itemsize, 1)
    out = []
    for dim in reversed(shape):
        take = min(dim, rem)
        out.append(take)
        rem = max(rem // dim, 1) if take == dim else 1
    return tuple(reversed(out))


class RegionReads:
    """Read boxes of a saved store, one after another, for the window."""

    def __init__(self, config: dict, mix: dict, x: np.ndarray, seed: int):
        from repro.api import ArrayStore, Bound

        self.x = x
        self.config = config
        self.axis = int(mix["axis"])
        self.width = int(mix["width"])
        self.chunk_shape = chunk_shape(x.shape, x.dtype.itemsize, int(mix["chunk_bytes"]))
        self.rng = np.random.default_rng([seed, 2])
        self.sample_rng = np.random.default_rng([seed, 3])
        self.cap = int(mix["sample_reads"])
        self.tmp = tempfile.TemporaryDirectory(prefix="perfbench-")
        self.path = os.path.join(self.tmp.name, "field.szs")
        ArrayStore.save(self.path, x, Bound.rel(config["bound"]["value"]),
                        chunk_shape=self.chunk_shape)
        self.nbox = x.shape[self.axis] - self.width + 1

    def box(self, k: int) -> tuple:
        sl = [slice(None)] * self.x.ndim
        sl[self.axis] = slice(k, k + self.width)
        return tuple(sl)

    def read(self, k: int) -> np.ndarray:
        from repro.api import ArrayStore

        with ArrayStore.open(self.path, backend="auto", device=True) as arr:
            return arr[self.box(k)]

    def chunks(self, k: int) -> int:
        """Store chunks that the box at ``k`` touches."""
        c = self.chunk_shape[self.axis]
        return (k + self.width - 1) // c - k // c + 1

    def warm(self) -> dict:
        """Every box the window can draw, once: its programs are then loaded.
        Returns the chunks read."""
        for k in range(self.nbox):
            self.read(k)
        return {"roi": sum(self.chunks(k) for k in range(self.nbox))}

    def run(self, seconds: float, on_close) -> Window:
        w = Window()
        w.t_start = t = time.perf_counter()
        deadline = t + seconds
        while t < deadline:
            k = int(self.rng.integers(0, self.nbox))
            try:
                with _annotation("roi", offset=k):
                    out = self.read(k)
            except Exception:
                w.fail()
                t = time.perf_counter()
                continue
            t1 = time.perf_counter()
            w.units.append(Unit("roi", t, t1, out.nbytes, frames=self.chunks(k)))
            w.keep(self.sample_rng, self.cap, k, out)
            t = t1
        w.t_end = t
        on_close()
        return w

    def check(self, w: Window, reduction=None) -> dict:
        return check.region_reads(self.x, self.config, self.chunk_shape, self.axis,
                                  self.width, w.outputs)

    def close(self) -> None:
        self.tmp.cleanup()


KINDS = {"roundtrip": Roundtrip, "roi": RegionReads}


def load_kind(name: str):
    """The module ``KINDS_DIR/<name>.py`` of a kind that is not built in.
    Its ``LIMITS`` may only add numbers: a name that ``check.LIMITS`` has
    already is refused, so a kind can never loosen a limit."""
    path = KINDS_DIR / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown traffic kind {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"perfbench_kind_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    clash = sorted(set(getattr(mod, "LIMITS", {})) & set(check.LIMITS))
    if clash:
        raise ValueError(f"kind {name!r} redefines the limits of {clash}")
    return mod


def make(config: dict, mix: dict, x, seed: int, devices: list, module):
    """The generator of ``mix``'s kind: the built-in one where ``module`` is
    None, else ``module.Kind`` (``load_kind``), which also gets the cell's
    devices."""
    if mix.get("callers", 1) != 1:
        raise ValueError("the generator drives one caller in a closed loop")
    if module is None:
        return KINDS[mix["kind"]](config, mix, x, seed)
    return module.Kind(config, mix, x, seed, list(devices))
