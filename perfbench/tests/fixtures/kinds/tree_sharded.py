"""A traffic kind that exists only as this file: a seeded tree of float32
leaves saved with ``TreeCodec.compress_tree_sharded`` over a mesh of the
cell's devices, then restored with ``decompress_tree``, back to back.

It follows the contract of ``perfbench/kinds/README.md`` and is what
``test_kinds.py`` drives through ``run.run_cell`` on four devices.  The
configuration gives ``leaves`` (name -> shape), ``dtypes`` and ``bound``.

The check is the plain reference's (``reference.py``): each leaf is cut into
one block-aligned shard per device, ``ceil(blocks / devices)`` blocks each,
and each shard encoded at the leaf's absolute bound; the stream's frames are
read by their headers alone.
"""
from __future__ import annotations

import io
import time

import numpy as np

import check
import datagen
import devtrace
import reference
import traffic

LIMITS = {"shards_missing": 0, "chips_idle": 0}


def make_input(config: dict, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {name: datagen.smooth_field(rng, tuple(shape), 3, 0.7, 0.0)
            for name, shape in sorted(config["leaves"].items())}


def shards(n: int, ndev: int) -> list:
    """``(lo, hi)`` of the block-aligned shards of ``n`` values."""
    blocks = -(-n // reference.BLOCK)
    per = -(-blocks // ndev) * reference.BLOCK
    return [(lo, min(lo + per, n)) for lo in range(0, n, per)]


def frame_payloads(buf: bytes) -> list:
    """Payloads of the stream's frames after the raw pack, up to the LAST one."""
    out, off = [], 0
    while True:
        magic, _v, flags, _seq, plen = reference.FRAME_HEADER.unpack_from(buf, off)
        if magic != b"SZXF":
            raise ValueError(f"no frame at byte {off}")
        off += reference.FRAME_HEADER.size
        if not flags & 0x02:                   # not the raw pack
            out.append(buf[off:off + plen])
        off += plen
        if flags & 0x01:
            return out


class Kind:
    def __init__(self, config: dict, mix: dict, x: dict, seed: int, devices: list):
        from jax.sharding import Mesh

        from repro.api import Bound, SZxCodec, TreeCodec

        self.tree = x
        self.rel = config["bound"]["value"]
        self.devices = devices
        self.mesh = Mesh(np.array(devices), ("data",))
        self.codec = TreeCodec(codec=SZxCodec(backend="auto"), bound=Bound.rel(self.rel))
        self.raw = sum(a.nbytes for a in x.values())
        self.nshards = sum(len(shards(a.size, len(devices))) for a in x.values())
        self.rng = np.random.default_rng([seed, 1])

    def _save(self) -> bytes:
        f = io.BytesIO()
        self.codec.compress_tree_sharded(self.tree, f, self.mesh, axis="data")
        return f.getvalue()

    def _restore(self, buf: bytes) -> dict:
        return self.codec.decompress_tree(io.BytesIO(buf), template=self.tree)

    def warm(self) -> dict:
        """Each shard is a frame: one device fetch when it is encoded, one
        device put when it is restored (``check.ROUTE_COUNTERS``)."""
        self._restore(self._save())
        return {"compress": self.nshards, "decompress": self.nshards}

    def run(self, seconds: float, on_close) -> traffic.Window:
        w = traffic.Window()
        w.t_start = time.perf_counter()
        while time.perf_counter() < w.t_start + seconds:
            t0 = time.perf_counter()
            try:
                with traffic._annotation("compress", call=len(w.legs)):
                    buf = self._save()
            except Exception:
                w.fail()
                continue
            t1 = time.perf_counter()
            w.legs.append(buf)
            w.units.append(traffic.Unit("compress", t0, t1, self.raw, len(buf), self.nshards))
            try:
                with traffic._annotation("decompress", call=len(w.legs) - 1):
                    out = self._restore(buf)
            except Exception:
                w.fail()
                continue
            t2 = time.perf_counter()
            w.units.append(traffic.Unit("decompress", t1, t2, self.raw, len(buf), self.nshards))
            w.keep(self.rng, 4, len(w.legs) - 1, out)
        w.t_end = time.perf_counter()
        on_close()
        return w

    def check(self, w: traffic.Window, reduction=None) -> dict:
        fmt = reference.FLOAT32
        want, decoded, bounds = [], {}, {}
        for name, a in self.tree.items():
            flat = a.reshape(-1)
            e = bounds[name] = reference.rel_bound(flat, self.rel)
            parts = [reference.encode_stream(flat[lo:hi], e, fmt)
                     for lo, hi in shards(flat.size, len(self.devices))]
            want += parts
            decoded[name] = np.concatenate([reference.decode_stream(p) for p in parts])
        frames_differing = missing = 0
        for buf in w.legs:
            got = frame_payloads(buf)
            frames_differing += (sum(g != r for g, r in zip(got, want))
                                 + max(0, len(got) - len(want)))
            missing += max(0, len(want) - len(got))
        differ, worst = 0, 0.0
        for _label, out in w.outputs:
            for name, a in self.tree.items():
                d, e = check._compare_values(np.asarray(out[name]).reshape(-1), decoded[name],
                                             a.reshape(-1), bounds[name])
                differ, worst = differ + d, max(worst, e)
        if not w.outputs:
            differ, worst = 1, float("inf")
        values = {"frames_differing": frames_differing, "values_differing": differ,
                  "max_err_over_bound": worst, "shards_missing": missing}
        if reduction is not None:
            region = reduction.kind_union.get("compress", [])
            ran = sum(1 for b in reduction.busy.values() if devtrace.overlap(b, region))
            values["chips_idle"] = len(self.devices) - ran
        return check.numbers(values, LIMITS)

    def close(self) -> None:
        pass
