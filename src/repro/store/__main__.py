"""File CLI for the array store.

    python -m repro.store create  IN.bin OUT.szs --shape 256,256,256 \
        --dtype float32 --bound rel:1e-3
    python -m repro.store info    STORE.szs [--json]
    python -m repro.store read    STORE.szs OUT.bin --roi "0:16,:,3"
    python -m repro.store query   STORE.szs [--roi ...] [--header-only] [--json]
    python -m repro.store serve   STORE.szs [--port 8117]

``create`` writes a chunk-grid store from a raw binary array; ``read``
decodes only the requested ROI; ``query`` runs the compressed-domain stats
scan; ``serve`` starts the HTTP slice/query service
(:mod:`repro.serve.store_service`).  Exit code is non-zero on any error.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


from repro.store.grid import parse_roi  # noqa: F401  (compat re-export)


def _shape(text: str) -> tuple[int, ...]:
    return tuple(int(v) for v in text.split(",") if v.strip())


def _cmd_create(args) -> int:
    from repro.core.codec.__main__ import resolve_cli_bound
    from repro.core.codec.tree import np_dtype_for
    from repro.store import ArrayStore

    dtype = np_dtype_for(args.dtype)
    data = np.fromfile(args.input, dtype=dtype)
    shape = _shape(args.shape)
    data = data.reshape(shape)
    kw = dict(
        chunk_shape=_shape(args.chunk_shape) if args.chunk_shape else None,
        block_size=args.block_size, backend=args.backend, workers=args.workers,
        stage=args.stage,
    )
    if args.shards:
        man = ArrayStore.save_sharded(
            args.output, data, resolve_cli_bound(args), nshards=args.shards,
            **kw,
        )
        frames = [fr for sh in man["shards"] for fr in sh["frames"]]
        chunk_shape, e = man["chunk_shape"], man["e"]
        where = f"{len(man['shards'])} shard files + manifest"
    else:
        idx = ArrayStore.save(args.output, data, resolve_cli_bound(args), **kw)
        frames, chunk_shape, e = idx["frames"], idx["chunk_shape"], idx["e"]
        where = "1 file"
    stored = sum(f[1] for f in frames)
    print(
        f"{args.input}: {data.nbytes} -> {stored} bytes in "
        f"{len(frames)} chunks of {tuple(chunk_shape)} ({where}, "
        f"CR {data.nbytes / max(stored, 1):.2f}, e={e:g})"
    )
    return 0


def _cmd_info(args) -> int:
    from repro.store import ArrayStore

    with ArrayStore.open(args.input) as ca:
        info = {
            "kind": "szx-store",
            "shape": list(ca.shape),
            "chunk_shape": list(ca.chunk_shape),
            "dtype": ca.dtype.name,
            "e": ca.error_bound,
            "nchunks": ca.nchunks,
            "raw_bytes": ca.nbytes,
            "stored_bytes": ca.stored_bytes,
            "cr": ca.nbytes / max(ca.stored_bytes, 1),
            "attrs": ca.attrs,
            "stage": ca.stage,
        }
    if args.json:
        print(json.dumps(info, indent=1))
    else:
        print(
            f"store {tuple(info['shape'])} {info['dtype']} in "
            f"{info['nchunks']} chunks of {tuple(info['chunk_shape'])}, "
            f"e={info['e']:g}, CR={info['cr']:.2f}"
        )
    return 0


def _cmd_read(args) -> int:
    from repro.store import ArrayStore

    with ArrayStore.open(args.input, backend=args.backend) as ca:
        roi = parse_roi(args.roi)
        out = ca[roi]
    out.tofile(args.output)
    print(f"{args.input}[{args.roi or '...'}]: {out.shape} {out.dtype} "
          f"({out.nbytes} bytes) -> {args.output}")
    return 0


def _cmd_query(args) -> int:
    from repro.store import ArrayStore

    with ArrayStore.open(args.input, backend=args.backend) as ca:
        if args.roi:
            # ROI queries decode the (small) region and answer in numpy
            sub = ca[parse_roi(args.roi)].astype(np.float64)
            stats = {
                "count": int(sub.size), "exact": True,
                "sum": [float(sub.sum())] * 2, "mean": [float(sub.mean())] * 2,
                "min": [float(sub.min())] * 2, "max": [float(sub.max())] * 2,
            }
        else:
            stats = ca.stats(header_only=args.header_only).to_dict()
    if args.json:
        print(json.dumps(stats, indent=1))
    elif stats["exact"]:
        print(
            f"count={stats['count']} mean={stats['mean'][0]:.8g} "
            f"min={stats['min'][0]:.8g} max={stats['max'][0]:.8g} "
            f"sum={stats['sum'][0]:.8g}"
        )
    else:
        print(
            f"count={stats['count']} "
            f"mean=[{stats['mean'][0]:.8g}, {stats['mean'][1]:.8g}] "
            f"min=[{stats['min'][0]:.8g}, {stats['min'][1]:.8g}] "
            f"max=[{stats['max'][0]:.8g}, {stats['max'][1]:.8g}]"
        )
    return 0


def _cmd_serve(args) -> int:
    from repro.serve.store_service import serve_store

    serve_store(args.input, host=args.host, port=args.port,
                backend=args.backend)
    return 0


def main(argv: list[str] | None = None) -> int:
    from repro import compile_cache

    compile_cache.enable()
    ap = argparse.ArgumentParser(
        prog="python -m repro.store", description=__doc__.splitlines()[0]
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("create", help="raw binary -> chunk-grid store")
    c.add_argument("input")
    c.add_argument("output")
    c.add_argument("--shape", required=True, help="comma-separated dims")
    c.add_argument("--bound", default=None, metavar="SPEC",
                   help="error bound: '1e-3' (abs), 'abs:1e-3', 'rel:1e-4'")
    c.add_argument("--error-bound", type=float, default=None,
                   help="legacy: ABS bound, or REL factor with --mode rel")
    c.add_argument("--mode", choices=("abs", "rel"), default=None)
    c.add_argument("--dtype", default="float32")
    c.add_argument("--chunk-shape", default=None, help="comma-separated dims")
    c.add_argument("--shards", type=int, default=0,
                   help="write N shard files + a JSON manifest (OUTPUT is "
                        "the manifest path) instead of one store file")
    c.add_argument("--block-size", type=int, default=128)
    c.add_argument("--workers", type=int, default=1)
    c.add_argument("--backend", default="numpy")
    c.add_argument("--stage", default=None,
                   choices=("bitshuffle-rle", "bitshuffle-zstd", "deflate"),
                   help="negotiated lossless second stage over the mid-byte "
                        "section (per-chunk; skipped when it would not shrink)")
    c.set_defaults(fn=_cmd_create)

    i = sub.add_parser("info", help="print store geometry")
    i.add_argument("input")
    i.add_argument("--json", action="store_true")
    i.set_defaults(fn=_cmd_info)

    r = sub.add_parser("read", help="ROI -> raw binary")
    r.add_argument("input")
    r.add_argument("output")
    r.add_argument("--roi", default=None, help='e.g. "0:16,:,3"')
    r.add_argument("--backend", default="numpy")
    r.set_defaults(fn=_cmd_read)

    q = sub.add_parser("query", help="compressed-domain stats")
    q.add_argument("input")
    q.add_argument("--roi", default=None)
    q.add_argument("--header-only", action="store_true",
                   help="interval stats, never reading plane bytes")
    q.add_argument("--json", action="store_true")
    q.add_argument("--backend", default="numpy")
    q.set_defaults(fn=_cmd_query)

    s = sub.add_parser("serve", help="HTTP slice/query service")
    s.add_argument("input")
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8117)
    s.add_argument("--backend", default="numpy")
    s.set_defaults(fn=_cmd_serve)

    args = ap.parse_args(argv)
    import struct

    try:
        return args.fn(args)
    except (OSError, ValueError, TypeError, KeyError, IndexError,
            struct.error) as err:
        print(f"error: {err}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    raise SystemExit(main())
