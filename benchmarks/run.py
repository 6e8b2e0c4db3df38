"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes detailed JSON under
benchmarks/out/.  Datasets are the synthetic scientific fields from
repro.data.scidata (SDRBench is offline-unavailable; DESIGN.md section 8.3).

  table3_compression_ratio   -- Table III: min/overall/max CR per app x REL
                                for szx / zfp-lite / sz-lite / zlib
  table4_compression_speed   -- Table IV: compression MB/s per app
  table5_decompression_speed -- Table V: decompression MB/s per app
  fig2_block_range_cdf       -- Fig 2: CDF of block relative value range
  fig6_shift_overhead        -- Fig 6: Solution-C byte-alignment overhead
  fig8_block_size            -- Fig 8: CR + PSNR vs block size
  fig10_quality              -- Fig 10: PSNR/SSIM at REL 1e-2..1e-4
  fig13_dump_load            -- Fig 13: compress+write / read+decompress wall
                                time vs raw I/O
  beyond_planes_codec        -- szx-planes (in-graph) throughput + wire bytes
                                for gradient/KV compression
  chunked_dump_load          -- monolithic vs chunked vs parallel-chunked
                                (frame-streamed) compression: throughput +
                                peak RSS; writes BENCH_codec.json at the repo
                                root (SZX_BENCH_N / SZX_BENCH_JSON override
                                input size / output path; CI runs this small
                                and gates via benchmarks/check_regression.py)

Run everything: ``PYTHONPATH=src python -m benchmarks.run``
Run a subset:   ``PYTHONPATH=src python -m benchmarks.run chunked_dump_load``
(must run as ``-m`` from the repo root so the ``benchmarks`` package imports)
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks import baselines as B
from repro.core import metrics
from repro.core.codec import SZxCodec
from repro.data import scidata

OUT = os.path.join(os.path.dirname(__file__), "out")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RELS = (1e-2, 1e-3, 1e-4)
_SZX = SZxCodec(backend="numpy")
CODECS = {
    "szx": (_SZX.compress, _SZX.decompress),
    "zfp-lite": (B.zfp_lite_compress, B.zfp_lite_decompress),
    "sz-lite": (B.sz_lite_compress, B.sz_lite_decompress),
}

_rows: list[str] = []


def _emit(name: str, us: float, derived: str):
    row = f"{name},{us:.1f},{derived}"
    _rows.append(row)
    print(row, flush=True)


def _apps():
    for app in scidata.APPLICATIONS:
        yield app, list(scidata.fields(app))


def table3_compression_ratio() -> dict:
    out: dict = {}
    for app, flds in _apps():
        for rel in RELS:
            for cname, (comp, _) in CODECS.items():
                t0 = time.time()
                crs = []
                for _, x in flds:
                    e = rel * float(x.max() - x.min())
                    crs.append(x.nbytes / len(comp(x, e)))
                hmean = len(crs) / sum(1.0 / c for c in crs)
                out[f"{app}|{rel:g}|{cname}"] = dict(
                    min=min(crs), overall=hmean, max=max(crs)
                )
                _emit(
                    f"table3/{app}/{rel:g}/{cname}",
                    (time.time() - t0) * 1e6,
                    f"CR_min={min(crs):.2f};CR={hmean:.2f};CR_max={max(crs):.2f}",
                )
            # lossless reference once per app
        crs = [x.nbytes / len(B.zlib_compress(x)) for _, x in flds]
        out[f"{app}|zlib"] = dict(overall=len(crs) / sum(1 / c for c in crs))
        _emit(f"table3/{app}/zlib", 0.0, f"CR={out[f'{app}|zlib']['overall']:.2f}")
    return out


def _throughput(direction: str) -> dict:
    out: dict = {}
    for app, flds in _apps():
        data = [x for _, x in flds]
        total_bytes = sum(x.nbytes for x in data)
        for cname, (comp, dec) in CODECS.items():
            rel = 1e-3
            bufs = []
            t0 = time.time()
            for x in data:
                e = rel * float(x.max() - x.min())
                bufs.append(comp(x, e))
            t_comp = time.time() - t0
            t0 = time.time()
            for b in bufs:
                dec(b)
            t_dec = time.time() - t0
            t = t_comp if direction == "comp" else t_dec
            mbs = total_bytes / 1e6 / max(t, 1e-9)
            out[f"{app}|{cname}"] = mbs
            _emit(f"table{'4' if direction=='comp' else '5'}/{app}/{cname}",
                  t * 1e6, f"MB/s={mbs:.0f}")
    return out


def table4_compression_speed() -> dict:
    return _throughput("comp")


def table5_decompression_speed() -> dict:
    return _throughput("dec")


def fig2_block_range_cdf() -> dict:
    out = {}
    for app, flds in _apps():
        cdf = np.mean([scidata.block_relative_range_cdf(x) for _, x in flds], axis=0)
        # fraction of size-8 blocks with relative range <= 0.01 (paper quotes
        # 80%+ for Miranda/QMCPack)
        t = np.logspace(-6, 0, 25)
        frac_001 = float(np.interp(0.01, t, cdf))
        out[app] = dict(cdf=cdf.tolist(), frac_le_001=frac_001)
        _emit(f"fig2/{app}", 0.0, f"frac_blocks_relrange<=0.01={frac_001:.2f}")
    return out


def fig6_shift_overhead() -> dict:
    """Solution C (byte-aligned, shift s) vs Solution B (bit-granular)."""
    from repro.core.codec import plan as codec_plan
    from repro.kernels import ops

    out = {}
    for app in ("Miranda", "NYX"):
        for rel in RELS:
            tot_c = tot_b = comp_bytes = 0
            for _, x in scidata.fields(app):
                p, xt = codec_plan.make_plan(
                    x, codec_plan.Bound.rel(rel), block_size=128, backend="numpy"
                )
                e = p.error_bound
                xb = codec_plan.to_blocks(xt, p)
                mu, rad, const, reqlen, shift, nbytes = [
                    np.asarray(a) for a in ops.block_stats(xb, e, backend="numpy")
                ]
                planes, L, mid = [
                    np.asarray(a) for a in ops.pack(xb, mu, shift, nbytes, backend="numpy")
                ]
                nc = ~const
                # Solution C: whole bytes, L' leading bytes elided
                bits_c = int(mid[nc].sum()) * 8
                # Solution B: reqlen bits minus leading bytes of the
                # UNSHIFTED word (bit-granular storage, Formula 6)
                _, L0, _ = [
                    np.asarray(a)
                    for a in ops.pack(xb, mu, np.zeros_like(shift), nbytes, backend="numpy")
                ]
                bits_b = int((reqlen[nc][:, None] - 8 * L0[nc]).clip(min=0).sum())
                tot_c += bits_c
                tot_b += bits_b
                comp_bytes += len(_SZX.compress(x, e))
            ovh = (tot_c - tot_b) / 8.0 / comp_bytes
            out[f"{app}|{rel:g}"] = ovh
            _emit(f"fig6/{app}/{rel:g}", 0.0, f"overhead={ovh*100:.2f}%")
    return out


def fig8_block_size() -> dict:
    out = {}
    flds = list(scidata.fields("Miranda"))
    for rel in (1e-3, 1e-4):
        for bs in (8, 16, 32, 64, 128, 256):
            crs, psnrs = [], []
            codec = SZxCodec(block_size=bs, backend="numpy")
            for _, x in flds:
                e = rel * float(x.max() - x.min())
                buf = codec.compress(x, e)
                y = codec.decompress(buf).reshape(-1)
                crs.append(x.nbytes / len(buf))
                psnrs.append(metrics.psnr(x, y))
            hm = len(crs) / sum(1 / c for c in crs)
            out[f"{rel:g}|{bs}"] = dict(cr=hm, psnr=float(np.mean(psnrs)))
            _emit(f"fig8/bs={bs}/{rel:g}", 0.0,
                  f"CR={hm:.2f};PSNR={np.mean(psnrs):.1f}")
    return out


def fig10_quality() -> dict:
    out = {}
    name, x = next(iter(scidata.fields("Hurricane")))
    for rel in RELS:
        e = rel * float(x.max() - x.min())
        y = _SZX.decompress(_SZX.compress(x, e)).reshape(x.shape)
        out[f"{rel:g}"] = dict(
            psnr=metrics.psnr(x, y), ssim=metrics.ssim(x, y),
            maxerr_over_e=float(np.abs(x - y).max() / e),
        )
        _emit(f"fig10/{rel:g}", 0.0,
              f"PSNR={out[f'{rel:g}']['psnr']:.1f};SSIM={out[f'{rel:g}']['ssim']:.4f}")
    return out


def fig13_dump_load(tmpdir: str = "/tmp/repro_io") -> dict:
    os.makedirs(tmpdir, exist_ok=True)
    data = [x for _, x in scidata.fields("NYX")]
    total = sum(x.nbytes for x in data)
    out = {}
    for rel in (1e-2, 1e-3):
        # dump: compress + write vs raw write
        t0 = time.time()
        paths = []
        for i, x in enumerate(data):
            e = rel * float(x.max() - x.min())
            buf = _SZX.compress(x, e)
            p = os.path.join(tmpdir, f"c{i}.szx")
            with open(p, "wb") as f:
                f.write(buf)
            paths.append(p)
        os.sync()
        t_comp_dump = time.time() - t0
        t0 = time.time()
        for i, x in enumerate(data):
            with open(os.path.join(tmpdir, f"r{i}.raw"), "wb") as f:
                f.write(x.tobytes())
        os.sync()
        t_raw_dump = time.time() - t0
        # load: read + decompress vs raw read
        t0 = time.time()
        for p in paths:
            with open(p, "rb") as f:
                _SZX.decompress(f.read())
        t_comp_load = time.time() - t0
        t0 = time.time()
        for i in range(len(data)):
            with open(os.path.join(tmpdir, f"r{i}.raw"), "rb") as f:
                np.frombuffer(f.read(), np.float32)
        t_raw_load = time.time() - t0
        comp_total = sum(os.path.getsize(p) for p in paths)
        # Modeled contended-PFS regime (the paper's Fig 13 runs 64-1024 MPI
        # ranks against one parallel FS; per-rank effective bandwidth is
        # ~100-250 MB/s).  This container's tmpfs is faster than its single
        # 20 MB/s core, inverting the paper's regime, so we report both the
        # raw local measurement and the modeled-PFS speedup with measured
        # compression times and ratios.
        cr = total / comp_total
        t_cpu_dump = t_comp_dump            # measured compress+write time
        t_cpu_load = t_comp_load
        modeled = {}
        # 25 MB/s == 1024 ranks contending a ~25 GB/s PFS (paper Fig 13 scale)
        for bw in (25e6, 100e6, 250e6):
            dump = (total / bw) / (t_cpu_dump + comp_total / bw)
            load = (total / bw) / (t_cpu_load + comp_total / bw)
            modeled[f"{bw/1e6:.0f}MBps"] = dict(dump=dump, load=load)
        out[f"{rel:g}"] = dict(
            dump_speedup_local=t_raw_dump / t_comp_dump,
            load_speedup_local=t_raw_load / t_comp_load,
            cr=cr,
            modeled=modeled,
            mb=total / 1e6,
        )
        m250 = modeled["250MBps"]
        _emit(f"fig13/{rel:g}", t_comp_dump * 1e6,
              f"local_dump={t_raw_dump/t_comp_dump:.2f};"
              f"pfs250_dump={m250['dump']:.2f};pfs250_load={m250['load']:.2f};CR={cr:.1f}")
    return out


def beyond_planes_codec() -> dict:
    """szx-planes in-graph codec: throughput + wire bytes (grad/KV use)."""
    import jax
    import jax.numpy as jnp

    from repro.core import planes as cp

    out = {}
    x = np.cumsum(
        np.random.default_rng(0).standard_normal(1 << 22), 0
    ).astype(np.float32)
    xj = jnp.asarray(x)
    for p in (1, 2):
        enc_fn = jax.jit(lambda v, p=p: cp.encode(v, num_planes=p))
        # n/block_size are static fields; close over them so jit only traces
        # the array leaves
        dec_fn = jax.jit(
            lambda mu, sexp, planes: cp.decode(
                cp.PlanesEncoded(mu, sexp, planes, x.size, cp.DEFAULT_BLOCK_SIZE),
                shape=x.shape,
            )
        )
        dec_call = lambda e: dec_fn(e.mu, e.sexp, e.planes)  # noqa: E731
        enc = enc_fn(xj)
        jax.block_until_ready(enc.planes)
        t0 = time.time()
        for _ in range(5):
            enc = enc_fn(xj)
            jax.block_until_ready(enc.planes)
        t_enc = (time.time() - t0) / 5
        y = dec_call(enc)
        jax.block_until_ready(y)
        t0 = time.time()
        for _ in range(5):
            jax.block_until_ready(dec_call(enc))
        t_dec = (time.time() - t0) / 5
        wire = cp.wire_bytes(enc)
        err = float(jnp.abs(xj - dec_call(enc)).max())
        out[f"P{p}"] = dict(
            enc_mbs=x.nbytes / 1e6 / t_enc,
            dec_mbs=x.nbytes / 1e6 / t_dec,
            wire_ratio=x.nbytes / wire,
            max_err=err,
        )
        _emit(f"beyond/planes/P{p}", t_enc * 1e6,
              f"enc_MB/s={x.nbytes/1e6/t_enc:.0f};dec_MB/s={x.nbytes/1e6/t_dec:.0f};"
              f"wire_ratio={x.nbytes/wire:.2f}")
    return out


_CHUNKED_CHILD = r"""
import json, os, resource, sys, time
import numpy as np
import ml_dtypes
from repro.core.codec import Bound, SZxCodec

mode, path = sys.argv[1], sys.argv[2]
kind, phase = mode.rsplit("_", 1)
n = int(os.environ.get("SZX_BENCH_N", 1 << 26))   # f32-equivalent elem count
# the dtype legs keep the BYTE volume constant (n * 4) so throughputs are
# comparable across rows: n_elems = n * 4 / itemsize
if kind.endswith("-f64"):
    dtype = np.dtype(np.float64)
elif kind.endswith("-bf16"):
    dtype = np.dtype(ml_dtypes.bfloat16)
else:
    dtype = np.dtype(np.float32)
n_elems = n * 4 // dtype.itemsize
workers = (os.cpu_count() or 1) if kind == "chunked-par" else 1
# chunked-dev-decode: the SAME frame pipeline on the device backend --
# encode_to_stream on dump, decode_stream on load (one transfer per chunk,
# on-device container parse + fused unpack+compose)
backend = "jax" if kind == "chunked-dev-decode" else "numpy"
# stage-* kinds: the negotiated lossless second stage over the mid bytes
second_stage = None
if kind.startswith("stage-"):
    second_stage = {"off": None, "rle": "bitshuffle-rle",
                    "deflate": "deflate", "zstd": "bitshuffle-zstd",
                    }[kind.split("-", 1)[1]]
codec = SZxCodec(backend=backend, workers=workers, stage=second_stage)
rel = 1e-3


def make_tree(x):
    # checkpoint-shaped pytree over the same bytes: 4 big float leaves plus
    # small integer leaves that ride in the shared raw pack frame
    q = x.size // 4
    return {
        "layers": {f"w{i}": x[i * q : (i + 1) * q] for i in range(4)},
        "step": np.int64(7),
        "opt": {"count": np.arange(64, dtype=np.int32)},
    }


if kind == "tree_checkpoint":
    from repro.core.codec import TreeCodec

    tree_codec = TreeCodec(
        codec=codec, bound=Bound.rel(rel), chunk_bytes=8 << 20
    )


class CountingFile:
    # byte-counting reader: measures the store ROI read's bytes-read ratio
    def __init__(self, raw):
        self.raw = raw
        self.n = 0

    def seek(self, *a):
        return self.raw.seek(*a)

    def tell(self):
        return self.raw.tell()

    def read(self, k=-1):
        data = self.raw.read(k)
        self.n += len(data)
        return data

    def close(self):
        self.raw.close()

reps = int(os.environ.get("SZX_BENCH_REPS", 3))   # best-of-N vs host noise
# device legs pay jit compile on the first call: run one extra untimed rep
# so the best-of-N measures steady-state throughput, not compile time
warmup = 1 if backend != "numpy" else 0
if kind == "pipeline_compressed_a2a":
    # gpipe dryrun: compressed vs raw activation shift on an 8-device host
    # mesh (parent sets XLA_FLAGS).  dump = compressed schedule, load = raw;
    # wire bytes are analytic (wire_bytes_per_value), so the parent's cr is
    # the deterministic compressed-vs-raw bytes-moved ratio.
    import jax, jax.numpy as jnp
    from repro.core import grad_compress as gc
    from repro.launch.mesh import make_mesh
    from repro.pipeline_par import pipeline_apply

    n_stages, n_micro, d = 4, 8, 512
    mb = max(n // (n_micro * d * 8), 1)           # scale batch with SZX_BENCH_N
    mesh = make_mesh((n_stages,), ("stage",))
    rng = np.random.default_rng(0)
    ws = jnp.asarray((rng.standard_normal((n_stages, d, d)) * 0.1),
                     jnp.float32)
    xs = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)
    stage = lambda p, x: jnp.tanh(x @ p)
    planes = 1
    fn = pipeline_apply(
        stage, mesh, compress_activations=phase == "dump", num_planes=planes
    )
    jax.block_until_ready(fn(ws, xs))             # compile outside the timing
    dt = float("inf")
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(fn(ws, xs))
        dt = min(dt, time.time() - t0)
    ticks = n_micro + n_stages - 1
    wire_raw = ticks * n_stages * mb * d * 4      # per-tick per-stage shift
    wire_comp = wire_raw / 4.0 * gc.wire_bytes_per_value(planes, 64)
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({"t": dt, "rss_mb": rss_mb, "stored": int(wire_comp),
                      "n": n, "dtype": "float32", "workers": 1,
                      "wire_raw_mb": wire_raw / 1e6,
                      "wire_comp_mb": wire_comp / 1e6}))
    sys.exit(0)
if kind == "ingest_windowed" and phase == "load":
    # streaming training ingest over the store: a serial shuffled-ROI-window
    # epoch through a byte-counting reader pins bytes-read ∝ windows (not
    # the store); a pipelined epoch (worker pool + bounded lookahead)
    # measures the overlap win as samples/sec vs the serial loader
    from repro.data.store_loader import StoreLoader
    from repro.store import ArrayStore

    file_bytes = os.path.getsize(path)
    win = (16, 4096)
    win_elems = win[0] * win[1]
    # epoch sized to touch <=10% of the store (~8% nominal coverage)
    windows = max(int(0.08 * n_elems / win_elems), 4)
    batch = min(8, windows)
    steps = max(windows // batch, 1)
    windows = steps * batch
    serial_t = float("inf")
    for _ in range(reps):
        counting = CountingFile(open(path, "rb"))
        with ArrayStore.open(counting) as ca:
            ld = StoreLoader(ca, win, batch, seed=5, workers=0)
            t0 = time.time()
            for s in range(steps):
                y = ld.batch_at(s)
            serial_t = min(serial_t, time.time() - t0)
            ld.close()
        read_ratio = counting.n / file_bytes
        counting.close()
    assert y.shape == (batch,) + win and y.dtype == dtype
    cpus = os.cpu_count() or 1
    pool = min(4, max(cpus, 2))
    dt = float("inf")
    for _ in range(reps):
        with StoreLoader(path, win, batch, seed=5, workers=pool,
                         lookahead=2) as ld:
            t0 = time.time()
            for _b in ld.batches(steps=steps):
                pass
            dt = min(dt, time.time() - t0)
    roi_bytes = windows * win_elems * dtype.itemsize
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({"t": dt, "rss_mb": rss_mb, "stored": file_bytes,
                      "n": n, "dtype": dtype.name, "workers": pool,
                      "roi_bytes": roi_bytes, "read_ratio": read_ratio,
                      "serial_t": serial_t, "samples": windows,
                      "cpus": cpus}))
    sys.exit(0)
if kind == "store_roi" and phase == "load":
    # lazy ROI read of the leading ~1% of rows: report ROI MB/s and the
    # bytes-read ratio (the "bytes read scale with the ROI" guarantee)
    from repro.store import ArrayStore

    file_bytes = os.path.getsize(path)
    dt = float("inf")
    for _ in range(reps):
        counting = CountingFile(open(path, "rb"))
        with ArrayStore.open(counting) as ca:
            rows = max(ca.shape[0] // 100, 1)
            t0 = time.time()
            y = ca[:rows]
            dt = min(dt, time.time() - t0)
            read_ratio = counting.n / file_bytes
            roi_bytes = y.nbytes
        counting.close()
    assert y.shape[0] == rows and y.dtype == dtype
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    print(json.dumps({"t": dt, "rss_mb": rss_mb, "stored": file_bytes,
                      "n": n, "dtype": dtype.name, "workers": workers,
                      "roi_bytes": roi_bytes, "read_ratio": read_ratio}))
    sys.exit(0)
if phase == "dump":
    rng = np.random.default_rng(0)
    x = np.cumsum(rng.standard_normal(n_elems, dtype=np.float32) * 0.01)
    x = x.astype(dtype)
    e = rel * float(x.astype(np.float32).max() - x.astype(np.float32).min())
    if kind.startswith("stage-"):
        # pinned ABS bound (= rel 1e-3 of the full 1<<26 walk): the frontier
        # rows compare stages in the SAME quantization regime at any
        # SZX_BENCH_N, so CR gains are size-independent
        e = 0.07230465698242187
    dt = float("inf")
    for r in range(reps + warmup):
        t0 = time.time()
        if kind == "store_roi":
            from repro.store import ArrayStore

            x3 = x.reshape(-1, 256, 256)
            ArrayStore.save(path, x3, e, workers=workers)
            stored = os.path.getsize(path)
        elif kind == "ingest_windowed":
            from repro.store import ArrayStore

            # leading-axis-slab grid: window reads stay block-tight
            x2 = x.reshape(-1, 4096)
            ArrayStore.save(path, x2, e, chunk_shape=(32, 4096),
                            workers=workers)
            stored = os.path.getsize(path)
        elif kind == "mono":
            buf = codec.compress(x, e)
            with open(path, "wb") as f:
                f.write(buf)
            stored = len(buf)
        elif kind == "tree_checkpoint":
            tree = make_tree(x)
            with open(path, "wb") as f:
                tree_codec.compress_tree(tree, f)
            stored = os.path.getsize(path)
        else:
            with open(path, "wb") as f:
                stored = codec.dump_chunked(x, f, e, chunk_bytes=8 << 20)
        if warmup and r == 0:
            continue
        dt = min(dt, time.time() - t0)
else:
    dt = float("inf")
    for r in range(reps + warmup):
        t0 = time.time()
        if kind == "mono":
            with open(path, "rb") as f:
                y = codec.decompress(f.read())
        elif kind == "tree_checkpoint":
            with open(path, "rb") as f:
                out = tree_codec.decompress_tree(f)
            y = np.concatenate([out[f"layers/w{i}"] for i in range(4)])
        else:
            with open(path, "rb") as f:
                y = codec.load_chunked(f)
        if warmup and r == 0:
            continue
        dt = min(dt, time.time() - t0)
    stored = os.path.getsize(path)
    if kind == "tree_checkpoint":
        assert y.size == 4 * (n_elems // 4) and y.dtype == dtype
    else:
        assert y.size == n_elems and y.dtype == dtype

rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
print(json.dumps({"t": dt, "rss_mb": rss_mb, "stored": stored, "n": n,
                  "dtype": dtype.name, "workers": workers}))
"""


def _telemetry_overhead(n: int) -> dict:
    """Telemetry-on cost of the chunked compress/decompress paths.

    The gated overheads are computed as (measured obs work per frame) /
    (measured codec wall time per frame).  The numerator microbenchmarks
    exactly the code telemetry adds to each path -- the span enter/exit
    plus every ``record_*`` call a frame triggers (including the per-frame
    L-code histogram) -- over thousands of reps, so it is stable to well
    under 0.1%.  The denominator is the best-of-reps per-frame wall time
    the row reports anyway.  An end-to-end on/off wall-clock ratio was
    tried first and swings +-3% run to run on shared hosts (bursty sibling
    load defeats even paired, locally-drift-normalized medians), which
    would flake the <3% absolute gate in benchmarks/check_regression.py;
    the quotient of two tight measurements gates the same regression class
    (obs hot-path code getting expensive) without the flake.  Telemetry
    cannot change the bytes themselves -- tests pin byte-identical output
    with obs on.  The workload size is pinned (independent of
    SZX_BENCH_N): overheads are ratios, not throughputs."""
    import io

    from repro import obs
    from repro.core.codec import container
    from repro.obs import stream_stats

    del n                                       # pinned size; see docstring
    reps = max(int(os.environ.get("SZX_BENCH_REPS", 3)), 5)
    n_elems = 1 << 23
    chunk_bytes = 4 << 20                       # 1 Mi elements -> many frames
    rng = np.random.default_rng(0)
    x = np.cumsum(rng.standard_normal(n_elems, dtype=np.float32) * 0.01)
    e = 1e-3 * float(x.max() - x.min())
    codec = SZxCodec(backend="numpy")
    was = obs.enabled()
    best = {"off": [float("inf")] * 2, "on": [float("inf")] * 2}
    nframes = 0

    def _one(mode):
        (obs.enable if mode == "on" else obs.disable)()
        obs.reset()
        bio = io.BytesIO()
        t0 = time.perf_counter()
        codec.dump_chunked(x, bio, e, chunk_bytes=chunk_bytes)
        tc = time.perf_counter() - t0
        bio.seek(0)
        t0 = time.perf_counter()
        y = codec.load_chunked(bio)
        td = time.perf_counter() - t0
        assert y.size == n_elems
        if mode == "on":
            nonlocal nframes
            nframes = len(obs.REGISTRY.frames())
            assert nframes > 0, "telemetry on but no frames logged"
        best[mode][0] = min(best[mode][0], tc)
        best[mode][1] = min(best[mode][1], td)

    def _per_call(fn, reps=2000):
        fn()                                    # warm-up
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps

    try:
        _one("off")                             # warm-up, not scored
        for _ in range(reps):
            _one("off")
            _one("on")
        # microbenchmark the per-frame obs work on a representative payload
        obs.enable()
        obs.reset()
        payload = codec.compress(x[: chunk_bytes // 4], e)
        frame = container.build_frame(payload, 0, True)
        nbytes = len(x[: chunk_bytes // 4].tobytes())

        def comp_obs():
            with obs.span("codec.compress", n=chunk_bytes // 4,
                          dtype="float32"):
                pass
            stream_stats.record_compress(payload, 0.01)
            stream_stats.record_frame_built(payload, len(frame), 0, 0)

        def decomp_obs():
            with obs.span("codec.decompress"):
                pass
            stream_stats.record_decompress(nbytes, 0.01)

        t_comp_obs = _per_call(comp_obs)
        t_decomp_obs = _per_call(decomp_obs)
    finally:
        (obs.enable if was else obs.disable)()
        obs.reset()
    mb = x.nbytes / 1e6
    per_frame = {"comp": best["off"][0] / nframes,
                 "decomp": best["off"][1] / nframes}
    return dict(
        comp_mbs=mb / best["off"][0],
        decomp_mbs=mb / best["off"][1],
        comp_mbs_obs=mb / best["on"][0],
        decomp_mbs_obs=mb / best["on"][1],
        comp_overhead=t_comp_obs / per_frame["comp"],
        decomp_overhead=t_decomp_obs / per_frame["decomp"],
        obs_us_per_frame_comp=t_comp_obs * 1e6,
        obs_us_per_frame_decomp=t_decomp_obs * 1e6,
        frames=nframes,
        dtype="float32",
        workers=1,
    )


def _store_service_load(tmpdir: str, n: int) -> dict:
    """Load-generate against a live store service: cold vs warm-cache ROI
    latency (p50/p99), hit rate and request throughput.

    The latency probes use narrow-column ROIs (the read path still decodes
    ~the whole chunk's flat span cold, but the warm path answers from the
    decoded-chunk cache), so the warm/cold ratio isolates the cache win.
    Asserts the warm p50 is >=5x below the cold p50 at byte-identical
    responses; the throughput probes re-read whole chunks for a stable
    decomp_mbs.
    """
    import threading
    import urllib.request

    from repro.api import ArrayStore, Bound
    from repro.serve.store_service import make_server

    cols = 4096
    rows = max(n // cols, 64)
    rng = np.random.default_rng(12)
    base = np.cumsum(rng.standard_normal(rows)).astype(np.float32)
    x = base[:, None] + rng.standard_normal((rows, cols)).astype(np.float32) * 0.01
    path = os.path.join(tmpdir, "service.szs")
    t0 = time.perf_counter()
    idx = ArrayStore.save(path, x, Bound.rel(1e-3))
    save_t = time.perf_counter() - t0
    stored = sum(f[1] for f in idx["frames"])
    chunk_rows = idx["chunk_shape"][0]
    nchunks = len(idx["frames"])

    srv = make_server(path, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    host, port = srv.server_address
    url = f"http://{host}:{port}/v1/stores/default/read?roi="

    def fetch(roi: str) -> tuple[float, bytes]:
        t = time.perf_counter()
        with urllib.request.urlopen(url + roi, timeout=120) as r:
            body = r.read()
        return time.perf_counter() - t, body

    def pct(xs: list[float], q: float) -> float:
        xs = sorted(xs)
        return xs[min(int(q * (len(xs) - 1) + 0.5), len(xs) - 1)]

    try:
        probes = [
            f"{cid * chunk_rows}:{min((cid + 1) * chunk_rows, rows)},0:64"
            for cid in range(min(nchunks, 16))
        ]
        cold, bodies = [], {}
        for roi in probes:                      # first touch: decode path
            dt, body = fetch(roi)
            cold.append(dt)
            bodies[roi] = body
        warm = []
        warm_t0 = time.perf_counter()
        for _ in range(5):                      # repeats: cache path
            for roi in probes:
                dt, body = fetch(roi)
                warm.append(dt)
                assert body == bodies[roi], f"warm bytes diverged for {roi}"
        warm_wall = time.perf_counter() - warm_t0
        cold_p50, warm_p50 = pct(cold, 0.50), pct(warm, 0.50)
        assert warm_p50 * 5 <= cold_p50, (
            f"warm-cache p50 {warm_p50 * 1e3:.2f} ms not >=5x below cold "
            f"{cold_p50 * 1e3:.2f} ms"
        )
        # throughput probes: whole-chunk re-reads from the warm cache
        full = f"0:{min(chunk_rows, rows)},0:{cols}"
        fetch(full)                             # prime
        tput_bytes = 0
        tput_t0 = time.perf_counter()
        for _ in range(8):
            _dt, body = fetch(full)
            tput_bytes += len(body)
        tput_wall = time.perf_counter() - tput_t0
        cache = srv.service.cache.stats()
        assert cache["hits"] > 0
    finally:
        srv.shutdown()
        srv.server_close()
    return dict(
        comp_mbs=x.nbytes / 1e6 / save_t,       # store save (ingest) MB/s
        decomp_mbs=tput_bytes / 1e6 / tput_wall,  # warm whole-chunk read MB/s
        cr=x.nbytes / stored,
        cold_p50_ms=cold_p50 * 1e3, cold_p99_ms=pct(cold, 0.99) * 1e3,
        warm_p50_ms=warm_p50 * 1e3, warm_p99_ms=pct(warm, 0.99) * 1e3,
        warm_speedup=cold_p50 / warm_p50,
        hit_rate=cache["hit_rate"],
        req_s=len(warm) / warm_wall,
        dtype="float32",
        workers=1,
    )


def chunked_dump_load(tmpdir: str = "/tmp/repro_chunked") -> dict:
    """Monolithic vs chunked vs parallel-chunked codec: throughput + peak RSS.

    Each phase runs in a fresh subprocess so ru_maxrss isolates that phase's
    peak memory.  'chunked-par' runs the frame pipeline with one worker
    thread per core (byte output identical to 'chunked').  The
    'chunked-f64' / 'chunked-bf16' legs run the SAME byte volume
    (SZX_BENCH_N * 4 bytes) through the width-generic kernel layer in those
    dtypes, gating the per-dtype fast paths.  'tree_checkpoint' pushes the
    same bytes through the pytree front-end (TreeCodec: multi-leaf
    container-v3 stream with index footer), gating the checkpoint path.
    'store_roi_read' saves the same bytes as an N-d repro.store chunk grid
    and lazily reads a ~1% leading-rows ROI: comp_mbs is the store save
    throughput, decomp_mbs the ROI read MB/s, and roi_bytes_read_ratio pins
    that bytes read scale with the ROI, not the array.  'ingest_windowed'
    runs the streaming training-ingest loader over the same store: a
    shuffled-ROI-window epoch touching <=10% of the array, reporting
    samples/sec (pipelined vs serial) and the bytes-read ratio (must stay
    ≪ 1).  'chunked-dev-decode' runs the chunked pipeline on the device
    backend (one transfer per chunk both ways; the decode tentpole's
    symmetric path); device legs run one untimed warmup rep so jit compile
    stays out of the best-of-N.
    'pipeline_compressed_a2a' dry-runs the gpipe activation shift on an
    8-device host mesh: comp_mbs/decomp_mbs are the compressed/raw schedule
    wire-throughputs and cr is the analytic compressed-vs-raw bytes-moved
    ratio.  'store_service_load' load-generates against a live HTTP store
    service: comp_mbs is store-save (ingest) MB/s, decomp_mbs the warm
    whole-chunk read MB/s, plus cold/warm ROI p50/p99 latency, cache hit
    rate and req/s (asserts warm p50 >=5x below cold at byte-identical
    responses).  'telemetry_overhead' reports chunked f32 round-trip
    throughput with repro.obs off vs on plus the fractional cost of the
    per-frame telemetry work (microbenchmarked against the per-frame wall
    time; see _telemetry_overhead); check_regression.py gates that
    overhead below 3% absolutely.  Results
    also land in
    BENCH_codec.json at the repo root (override the path with
    SZX_BENCH_JSON, the f32-equivalent element count with SZX_BENCH_N) to
    anchor the codec perf trajectory; benchmarks/check_regression.py gates
    CI on them.
    """
    os.makedirs(tmpdir, exist_ok=True)
    n = int(os.environ.get("SZX_BENCH_N", 1 << 26))
    out: dict = {"n": n}
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO_ROOT, "src")}
    for kind in ("mono", "chunked", "chunked-par", "chunked-f64", "chunked-bf16",
                 "chunked-dev-decode", "tree_checkpoint", "store_roi_read",
                 "ingest_windowed", "pipeline_compressed_a2a"):
        child_kind = "store_roi" if kind == "store_roi_read" else kind
        child_env = env
        if kind == "pipeline_compressed_a2a":
            child_env = {
                **env, "XLA_FLAGS": "--xla_force_host_platform_device_count=8"
            }
        path = os.path.join(tmpdir, f"{kind}.szx")
        res = {}
        for phase in ("dump", "load"):
            r = subprocess.run(
                [sys.executable, "-c", _CHUNKED_CHILD, f"{child_kind}_{phase}", path],
                capture_output=True, text=True, timeout=1800, env=child_env,
            )
            assert r.returncode == 0, r.stderr[-2000:]
            res[phase] = json.loads(r.stdout.strip().splitlines()[-1])
        if kind == "pipeline_compressed_a2a":
            wire_raw_mb = res["dump"]["wire_raw_mb"]
            out[kind] = dict(
                comp_mbs=wire_raw_mb / res["dump"]["t"],    # compressed sched
                decomp_mbs=wire_raw_mb / res["load"]["t"],  # raw schedule
                cr=wire_raw_mb / res["dump"]["wire_comp_mb"],
                wire_raw_mb=wire_raw_mb,
                wire_comp_mb=res["dump"]["wire_comp_mb"],
                dtype="float32",
                workers=1,
            )
            _emit(
                f"beyond/chunked_dump_load/{kind}", res["dump"]["t"] * 1e6,
                f"comp_MB/s={out[kind]['comp_mbs']:.0f};"
                f"decomp_MB/s={out[kind]['decomp_mbs']:.0f};"
                f"wire_raw_MB={wire_raw_mb:.1f};"
                f"wire_comp_MB={out[kind]['wire_comp_mb']:.1f};"
                f"bytes_moved_ratio={out[kind]['cr']:.2f}",
            )
            continue
        raw_mb = n * 4 / 1e6
        # store_roi_read's load phase reads a ~1% ROI lazily: decomp_mbs is
        # ROI MB/s (the serving metric), and read_ratio pins bytes-read ∝ ROI
        load_mb = res["load"].get("roi_bytes", n * 4) / 1e6
        out[kind] = dict(
            comp_mbs=raw_mb / res["dump"]["t"],
            decomp_mbs=load_mb / res["load"]["t"],
            dump_peak_rss_mb=res["dump"]["rss_mb"],
            load_peak_rss_mb=res["load"]["rss_mb"],
            stored_mb=res["dump"]["stored"] / 1e6,
            cr=n * 4 / res["dump"]["stored"],
            dtype=res["dump"]["dtype"],
            workers=res["dump"]["workers"],
        )
        extra = ""
        if "read_ratio" in res["load"]:
            out[kind]["roi_bytes_read_ratio"] = res["load"]["read_ratio"]
            extra = f";roi_read_ratio={res['load']['read_ratio']:.4f}"
        if kind == "ingest_windowed":
            # decomp_mbs above is the pipelined loader's decoded-window MB/s;
            # the ingest metrics proper are samples/sec and the serial-vs-
            # pipelined speedup (gated in CI when the host has >=2 cpus)
            ld = res["load"]
            out[kind].update(
                samples_s=ld["samples"] / ld["t"],
                serial_samples_s=ld["samples"] / ld["serial_t"],
                pipeline_speedup=ld["serial_t"] / ld["t"],
                bytes_read_ratio=ld["read_ratio"],
                ingest_workers=ld["workers"],
                cpus=ld["cpus"],
            )
            extra += (
                f";samples_s={out[kind]['samples_s']:.0f}"
                f";serial_samples_s={out[kind]['serial_samples_s']:.0f}"
                f";speedup={out[kind]['pipeline_speedup']:.2f}"
                f";workers={ld['workers']};cpus={ld['cpus']}"
            )
        _emit(
            f"beyond/chunked_dump_load/{kind}", res["dump"]["t"] * 1e6,
            f"comp_MB/s={out[kind]['comp_mbs']:.0f};"
            f"decomp_MB/s={out[kind]['decomp_mbs']:.0f};"
            f"dump_RSS_MB={out[kind]['dump_peak_rss_mb']:.0f};"
            f"load_RSS_MB={out[kind]['load_peak_rss_mb']:.0f};"
            f"CR={out[kind]['cr']:.2f}" + extra,
        )
    # --- second-stage speed/ratio frontier: stage-off vs each lossless
    # second stage over the SAME bytes at a pinned abs bound (the child
    # overrides e so the quantization regime is size-independent).  Gated
    # absolutely in check_regression.py: at least one stage must buy
    # >=1.5x CR at <30% comp+decomp throughput cost.
    from repro.core.codec import stage as stage_mod

    stage_kinds = ["stage-off", "stage-rle", "stage-deflate"]
    if stage_mod._zstd() is not None:
        stage_kinds.append("stage-zstd")
    frontier: dict = {}
    for kind in stage_kinds:
        path = os.path.join(tmpdir, f"{kind}.szx")
        res = {}
        for phase in ("dump", "load"):
            r = subprocess.run(
                [sys.executable, "-c", _CHUNKED_CHILD, f"{kind}_{phase}", path],
                capture_output=True, text=True, timeout=1800, env=env,
            )
            assert r.returncode == 0, r.stderr[-2000:]
            res[phase] = json.loads(r.stdout.strip().splitlines()[-1])
        raw_mb = n * 4 / 1e6
        frontier[kind] = dict(
            comp_mbs=raw_mb / res["dump"]["t"],
            decomp_mbs=raw_mb / res["load"]["t"],
            stored_mb=res["dump"]["stored"] / 1e6,
            cr=n * 4 / res["dump"]["stored"],
        )
    off_row = frontier["stage-off"]
    for kind in stage_kinds:
        f_row = frontier[kind]
        f_row["cr_gain"] = f_row["cr"] / off_row["cr"]
        f_row["comp_rel"] = f_row["comp_mbs"] / off_row["comp_mbs"]
        f_row["decomp_rel"] = f_row["decomp_mbs"] / off_row["decomp_mbs"]
        _emit(
            f"beyond/chunked_dump_load/{kind}", 0.0,
            f"comp_MB/s={f_row['comp_mbs']:.0f};"
            f"decomp_MB/s={f_row['decomp_mbs']:.0f};"
            f"CR={f_row['cr']:.2f};"
            f"CR_gain={f_row['cr_gain']:.2f}x;"
            f"comp_rel={f_row['comp_rel']:.2f};"
            f"decomp_rel={f_row['decomp_rel']:.2f}",
        )
    out["second_stage_frontier"] = frontier

    row = out["telemetry_overhead"] = _telemetry_overhead(n)
    _emit(
        "beyond/chunked_dump_load/telemetry_overhead", 0.0,
        f"comp_MB/s={row['comp_mbs']:.0f};"
        f"comp_obs_MB/s={row['comp_mbs_obs']:.0f};"
        f"decomp_MB/s={row['decomp_mbs']:.0f};"
        f"decomp_obs_MB/s={row['decomp_mbs_obs']:.0f};"
        f"comp_ovh={row['comp_overhead'] * 100:.2f}%;"
        f"decomp_ovh={row['decomp_overhead'] * 100:.2f}%;"
        f"frames={row['frames']}",
    )

    row = out["store_service_load"] = _store_service_load(tmpdir, n)
    _emit(
        "beyond/chunked_dump_load/store_service_load",
        row["warm_p50_ms"] * 1e3,
        f"comp_MB/s={row['comp_mbs']:.0f};"
        f"decomp_MB/s={row['decomp_mbs']:.0f};"
        f"cold_p50_ms={row['cold_p50_ms']:.2f};"
        f"warm_p50_ms={row['warm_p50_ms']:.2f};"
        f"hit_rate={row['hit_rate']:.2f};"
        f"req_s={row['req_s']:.0f};"
        f"CR={row['cr']:.2f}",
    )
    bench_json = os.environ.get(
        "SZX_BENCH_JSON", os.path.join(REPO_ROOT, "BENCH_codec.json")
    )
    with open(bench_json, "w") as f:
        json.dump({"chunked_dump_load": out}, f, indent=1, default=float)
    return out


ALL = [
    table3_compression_ratio,
    table4_compression_speed,
    table5_decompression_speed,
    fig2_block_range_cdf,
    fig6_shift_overhead,
    fig8_block_size,
    fig10_quality,
    fig13_dump_load,
    beyond_planes_codec,
    chunked_dump_load,
]


# the only benchmarks that put arrays on a JAX device in THIS process.  A
# chip belongs to one process: they run after every benchmark that starts
# child processes (chunked_dump_load), so no child finds the chip taken.
ON_DEVICE = (beyond_planes_codec,)


def main(names: list[str] | None = None) -> None:
    from repro import compile_cache

    compile_cache.enable()
    os.makedirs(OUT, exist_ok=True)
    by_name = {fn.__name__: fn for fn in ALL}
    if names:
        unknown = [n for n in names if n not in by_name]
        if unknown:
            raise SystemExit(f"unknown benchmarks {unknown}; have {sorted(by_name)}")
        todo = [by_name[n] for n in names]
    else:
        todo = ALL
    todo = sorted(todo, key=lambda fn: fn in ON_DEVICE)
    results = {}
    print("name,us_per_call,derived")
    for fn in todo:
        results[fn.__name__] = fn()
    with open(os.path.join(OUT, "benchmarks.json"), "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"# wrote {os.path.join(OUT, 'benchmarks.json')}")


if __name__ == "__main__":
    main(sys.argv[1:])
