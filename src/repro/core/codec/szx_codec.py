"""Byte-stream front-end: SZxCodec (monolithic + chunked streaming).

This is the host-facing API over the plan -> transform -> container pipeline.
``compress``/``decompress`` handle whole arrays; ``compress_chunked`` /
``decompress_chunked`` process arbitrarily large arrays in bounded-memory
chunks, each chunk an independent, self-delimiting frame (the paper's
Fig. 13 checkpoint dump/load use case at scale).  Chunk payloads are
bit-identical to compressing the same slice monolithically, so the chunked
path inherits every error-bound guarantee of the monolithic one.
"""
from __future__ import annotations

import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Callable, Iterable, Iterator

import numpy as np

from repro import obs
from repro.core.codec import container, plan as plan_mod, transform
from repro.core.codec.plan import DEFAULT_BLOCK_SIZE, Bound, Plan

DEFAULT_CHUNK_BYTES = 64 << 20     # 64 MB of input per frame


def _imap_ordered(fn: Callable, items: Iterable, workers: int) -> Iterator:
    """Ordered, bounded-lookahead parallel map over a thread pool.

    Results are yielded strictly in input order; at most ``2 * workers`` items
    are in flight, so peak memory stays O(workers * item) no matter how slowly
    the consumer drains.  Frame bodies are numpy-heavy and numpy releases the
    GIL, so threads give real parallelism without pickling the input.
    """
    lookahead = 2 * workers
    with ThreadPoolExecutor(max_workers=workers) as pool:
        pending: deque = deque()
        try:
            for item in items:
                pending.append(pool.submit(fn, item))
                if obs.enabled():
                    obs.gauge("codec.pipeline.queue_depth").set(len(pending))
                if len(pending) >= lookahead:
                    yield pending.popleft().result()
            while pending:
                if obs.enabled():
                    obs.gauge("codec.pipeline.queue_depth").set(len(pending))
                yield pending.popleft().result()
        finally:
            while pending:
                pending.popleft().cancel()


def _validate_select(select) -> list[int]:
    """Normalize a frame selection: integers, strictly increasing, non-empty.

    Out-of-range, duplicate, and unsorted selections all raise a clear
    ValueError here (or in the caller, for the upper range check) instead of
    leaking numpy/IndexError from the read path.
    """
    out = []
    for i in select:
        if isinstance(i, bool) or not isinstance(i, (int, np.integer)):
            raise ValueError(
                f"select= expects integer frame indices, got {i!r}"
            )
        i = int(i)
        if i < 0:
            raise ValueError(f"frame index {i} out of range (negative)")
        if out and i <= out[-1]:
            raise ValueError(
                f"select= must be strictly increasing (got {i} after "
                f"{out[-1]}: duplicates/unsorted selections are ambiguous)"
            )
        out.append(i)
    if not out:
        raise ValueError("empty SZx frame selection")
    return out


@dataclass(frozen=True)
class CompressionStats:
    n: int
    raw_bytes: int
    compressed_bytes: int
    ratio: float
    constant_block_fraction: float
    mean_bytes_per_value: float
    error_bound: float


@dataclass(frozen=True)
class SZxCodec:
    """Configured byte-stream codec; instances are cheap and immutable.

    ``backend`` picks the width-generic kernel implementation for EVERY
    stream dtype (f32/f64/f16/bf16): 'jax' jitted oracle, 'kernel' Pallas,
    'numpy' mirror, or 'auto'; all are bit-identical per dtype.  Each frame
    body stages ONE fused encode program (stats + pack, a single
    host<->device round trip) -- including under ``workers > 1``, where the
    chunked paths' frame bodies run on a thread pool (frames are independent
    and order-tagged); the byte output is identical to the serial path and
    memory stays O(workers * chunk).
    """

    block_size: int = DEFAULT_BLOCK_SIZE
    backend: str = "auto"          # kernels.ops backend (all dtypes)
    workers: int = 1               # threads for compress_chunked/decompress_chunked
    stage: str | int | None = None  # negotiated second stage for chunked frames
                                    # (None | 'bitshuffle-rle' | 'bitshuffle-zstd'
                                    # | 'deflate'; see repro.core.codec.stage)

    def __post_init__(self):
        if self.stage is not None:
            from repro.core.codec import stage as stage_mod

            stage_mod.resolve(self.stage)   # unknown/unavailable -> raises now

    # ------------------------------------------------------------- monolithic
    def compress(self, x, bound: Bound | float | None = None, *,
                 mode: str | None = None, dtype=None,
                 error_bound: float | None = None) -> bytes:
        """Compress an array (f32/f64/f16/bf16) into one v2 stream.

        bound: a :class:`repro.api.Bound` (``Bound.abs(1e-3)`` /
               ``Bound.rel(1e-4)``) or a bare float meaning ``Bound.abs``.
        dtype: optionally force the codec dtype (input is cast first).
        The legacy ``(error_bound, mode=)`` kwargs still work but emit a
        ``DeprecationWarning``.
        """
        b = plan_mod.as_bound(bound, mode, error_bound=error_bound,
                              owner="SZxCodec.compress")
        p, xt = plan_mod.make_plan(
            x, b, block_size=self.block_size, backend=self.backend, dtype=dtype,
        )
        if not obs.enabled():
            return self._compress_planned(xt, p)
        t0 = time.perf_counter()
        with obs.span("codec.compress", n=int(p.n), dtype=p.dtype.name):
            buf = self._compress_planned(xt, p)
        obs.stream_stats.record_compress(buf, time.perf_counter() - t0)
        return buf

    def _compress_planned(self, xt: np.ndarray, p: Plan) -> bytes:
        from repro.kernels import ops

        with obs.span("plan.to_blocks"):
            xb = plan_mod.to_blocks(xt, p)
        if ops._resolve(p.backend, p.dtype) == "numpy":
            # host mirror: encode + serialize entirely in numpy (byte-identical
            # to the device path; this is also the benchmark hot path)
            enc = transform.encode_blocks(xb, p)
            return container.build_stream(p, enc)
        # device backends: fused stats+pack AND layout derivation stay on
        # device; the frame reaches the host as ONE device_get (device.py)
        from repro.core.codec import device

        return device.encode_to_stream(xb, p)

    def decompress(self, buf: bytes, *, out: np.ndarray | None = None) -> np.ndarray:
        """Decompress one v2 stream -> flat array in the stream's dtype.

        On the device backends ('jax'/'kernel', or 'auto' resolving to them)
        the whole decode is device-resident -- ONE ``jax.device_put`` of the
        raw body bytes, on-device section parsing + the fused unpack+compose
        program, one readback (``device.decode_stream``); the numpy backend
        keeps the host mirror.  With ``out`` (a flat (n,) array in the
        stream's dtype) the result is written in place and ``out`` returned.
        """
        if not obs.enabled():
            return self._decompress_impl(buf, out=out)
        t0 = time.perf_counter()
        with obs.span("codec.decompress"):
            res = self._decompress_impl(buf, out=out)
        obs.stream_stats.record_decompress(
            res.nbytes, time.perf_counter() - t0
        )
        return res

    def _decompress_impl(self, buf: bytes, *,
                         out: np.ndarray | None = None) -> np.ndarray:
        from repro.kernels import ops

        if ops._resolve(self.backend) != "numpy":
            from repro.core.codec import device

            res = device.decode_stream(buf, backend=self.backend, out=out)
            if res is not None:
                return res
        p, enc = container.parse_stream(buf, backend=self.backend)
        if out is not None and p.n == p.nblocks * p.block_size:
            transform.decode_blocks(
                enc, p, out=out.reshape(p.nblocks, p.block_size)
            )
            return out
        xb = transform.decode_blocks(enc, p)
        flat = np.asarray(xb).reshape(-1)[: p.n]
        if out is not None:
            np.copyto(out, flat)
            return out
        return flat

    def decompress_range(self, buf: bytes, lo_block: int, hi_block: int) -> np.ndarray:
        """Partial decode of one v2 stream: blocks [lo_block, hi_block) only.

        Returns the flat values covered by those blocks (the trailing padded
        values of the stream's final block are clipped), i.e. elements
        ``[lo_block * bs, min(hi_block * bs, n))`` of ``decompress(buf)`` --
        at O(range) decode cost.  Parsing is still O(stream); callers that
        also want byte reads proportional to the range use the
        section-level API (``repro.store``).  Device backends decode the
        range with the same one-put fused program as :meth:`decompress`.
        """
        if not obs.enabled():
            return self._decompress_range_impl(buf, lo_block, hi_block)
        t0 = time.perf_counter()
        with obs.span("codec.decompress_range", lo=lo_block, hi=hi_block):
            res = self._decompress_range_impl(buf, lo_block, hi_block)
        obs.stream_stats.record_decompress(
            res.nbytes, time.perf_counter() - t0, kind="range"
        )
        return res

    def _decompress_range_impl(self, buf: bytes, lo_block: int,
                               hi_block: int) -> np.ndarray:
        from repro.kernels import ops

        if ops._resolve(self.backend) != "numpy":
            from repro.core.codec import device

            res = device.decode_stream(
                buf, backend=self.backend, block_range=(lo_block, hi_block)
            )
            if res is not None:
                return res
        p, enc = container.parse_stream(buf, backend=self.backend)
        xb = transform.decode_block_range(enc, p, lo_block, hi_block)
        flat = np.asarray(xb).reshape(-1)
        return flat[: min(hi_block * p.block_size, p.n) - lo_block * p.block_size]

    def compress_with_stats(self, x, bound: Bound | float | None = None,
                            **kw) -> tuple[bytes, CompressionStats]:
        buf = self.compress(x, bound, **kw)
        _, _, _, _, n, e, nb, nnc, _ = container.HEADER.unpack_from(buf, 0)
        itemsize = plan_mod.spec_for_code(buf[5]).itemsize
        return buf, CompressionStats(
            n=int(n),
            raw_bytes=itemsize * int(n),
            compressed_bytes=len(buf),
            ratio=itemsize * int(n) / len(buf),
            constant_block_fraction=1.0 - nnc / max(nb, 1),
            mean_bytes_per_value=len(buf) / max(int(n), 1),
            error_bound=float(e),
        )

    # ---------------------------------------------------------------- chunked
    def iter_chunk_payloads(
        self,
        x,
        bound: Bound | float | None = None,
        *,
        mode: str | None = None,
        chunk_bytes: int = DEFAULT_CHUNK_BYTES,
        dtype=None,
        error_bound: float | None = None,
    ) -> Iterator[tuple[bytes, bool]]:
        """Yield ``(payload, is_last)`` covering ``x`` in chunk order.

        The frame-less core of :meth:`compress_chunked` -- and the ONE place
        the chunk count is derived, so every wrapper agrees on which payload
        closes the sequence.  The bound (:class:`Bound` or bare-float abs)
        is resolved over the FULL array first (so ``Bound.rel`` matches the
        monolithic stream -- every chunk carries the same absolute ``e``),
        then each block-aligned chunk is compressed independently; each
        payload is bit-identical to ``compress(chunk, e_abs)``.  With
        ``workers > 1`` the chunk bodies run concurrently but payloads are
        yielded strictly in order.  Callers that interleave several arrays
        into one stream (``TreeCodec``) wrap these in their own frames.
        """
        b = plan_mod.as_bound(bound, mode, error_bound=error_bound,
                              owner="SZxCodec.iter_chunk_payloads")
        x = np.asarray(x)
        if dtype is not None:
            x = x.astype(np.dtype(dtype), copy=False)
        spec = plan_mod.spec_for(x.dtype)
        with obs.span("plan.resolve_error_bound"):
            e = plan_mod.resolve_error_bound(x, b, spec=spec)
        flat = x.reshape(-1)
        per_chunk = plan_mod.chunk_elements(self.block_size, chunk_bytes, spec.itemsize)
        nchunks = max((flat.size + per_chunk - 1) // per_chunk, 1)

        def payload(i: int) -> bytes:
            return self.compress(flat[i * per_chunk : (i + 1) * per_chunk], e)

        if self.workers > 1 and nchunks > 1:
            payloads = _imap_ordered(payload, range(nchunks), self.workers)
        else:
            payloads = map(payload, range(nchunks))
        for i, pl in enumerate(payloads):
            yield pl, i == nchunks - 1

    def compress_chunked(
        self,
        x,
        bound: Bound | float | None = None,
        *,
        mode: str | None = None,
        chunk_bytes: int = DEFAULT_CHUNK_BYTES,
        dtype=None,
        error_bound: float | None = None,
    ) -> Iterator[bytes]:
        """Yield self-delimiting frames covering ``x`` in order.

        Frames wrap :meth:`iter_chunk_payloads` payloads: peak memory is
        O(workers * chunk), each payload bit-identical to the monolithic
        compression of its slice, the byte stream identical for any worker
        count.
        """
        b = plan_mod.as_bound(bound, mode, error_bound=error_bound,
                              owner="SZxCodec.compress_chunked")
        for i, (payload, last) in enumerate(
            self.iter_chunk_payloads(x, b, chunk_bytes=chunk_bytes, dtype=dtype)
        ):
            yield container.build_frame(payload, i, last=last, stage=self.stage)

    def decompress_chunked(self, frames, *, n: int | None = None) -> np.ndarray:
        """Decompress a frame sequence -> flat array.

        ``frames`` may be concatenated bytes, a binary file object, or an
        iterable of frame byte strings (e.g. from :meth:`compress_chunked`).
        Pass ``n`` (the total element count, e.g. from a manifest) to
        preallocate the output and keep peak memory at O(n + workers * chunk):
        each frame's element count is peeked from its header and the frame
        decodes straight into its slice of the output (``out=``), with no
        per-frame result copy -- including under ``workers > 1``.  Without
        ``n`` the decoded chunks are buffered and concatenated, peaking at
        ~2x the output size.  With ``workers > 1`` frame payloads decode
        concurrently; results are consumed strictly in frame order.
        """
        out = None

        def jobs() -> Iterator[tuple[bytes, int, int]]:
            nonlocal out
            spec_code = None
            off = 0
            for payload in container.iter_frames(frames):
                if len(payload) <= 5:
                    raise ValueError("truncated SZx stream (shorter than header)")
                if spec_code is None:
                    spec_code = payload[5]
                    if n is not None:
                        out = np.empty(
                            n, plan_mod.spec_for_code(spec_code).np_dtype
                        )
                elif payload[5] != spec_code:
                    raise ValueError("SZx frame sequence mixes dtypes")
                with obs.span("container.parse_header"):
                    _code, fn, _e = container.peek_stream_meta(payload)
                if out is not None and off + fn > n:
                    raise ValueError(
                        f"SZx frame sequence longer than expected ({n} elements)"
                    )
                yield payload, off, int(fn)
                off += int(fn)

        def decode(job: tuple[bytes, int, int]) -> np.ndarray:
            payload, off, fn = job
            if out is not None:
                return self.decompress(payload, out=out[off : off + fn])
            return self.decompress(payload)

        if self.workers > 1:
            decoded = _imap_ordered(decode, jobs(), self.workers)
        else:
            decoded = map(decode, jobs())

        parts: list[np.ndarray] = []
        filled = 0
        seen = False
        for part in decoded:
            seen = True
            if out is None:
                parts.append(part)
            filled += part.size
        if not seen:
            raise ValueError("empty SZx frame sequence")
        if out is not None:
            if filled != n:
                raise ValueError(
                    f"SZx frame sequence has {filled} elements, expected {n}"
                )
            return out
        return np.concatenate(parts) if len(parts) > 1 else parts[0]

    def dump_chunked(self, x, fileobj, bound: Bound | float | None = None, *,
                     index: bool = True, **kw) -> int:
        """Stream ``compress_chunked`` frames straight to a file; returns
        bytes written.  Peak memory stays O(workers * chunk).

        With ``index=True`` (the default) a container-v3 footer is appended
        after the LAST frame: per-frame ``[offset, length, elements]`` plus
        the stream totals, enabling random access (``load_chunked`` with
        ``select=``).  ``index=False`` reproduces the footer-less v2 stream.
        """
        x_arr = np.asarray(x)
        written = 0
        frames_idx: list[list[int]] = []
        dtype_code = None
        for frame in self.compress_chunked(x_arr, bound, **kw):
            if index:
                dtype_code, payload_n, _e = container.peek_stream_meta(
                    memoryview(frame)[container.FRAME_HEADER.size:]
                )
                frames_idx.append([written, len(frame), int(payload_n)])
            fileobj.write(frame)
            written += len(frame)
        if index:
            footer = container.build_index_footer(
                {
                    "v": container.INDEX_VERSION,
                    "kind": "szx-chunked",
                    "n": int(x_arr.size),
                    "dtype": dtype_code,
                    "frames": frames_idx,
                }
            )
            fileobj.write(footer)
            written += len(footer)
        return written

    def load_chunked(self, fileobj, *, n: int | None = None,
                     select=None) -> np.ndarray:
        """Read + decompress a frame sequence from a file object.  Pass ``n``
        (total element count) to preallocate: peak memory
        O(n + workers * chunk).

        ``select``: a strictly increasing iterable of in-range frame indices
        -- decode ONLY those frames (concatenated), reading only their byte
        ranges via the container-v3 index footer (requires a seekable stream
        written with ``index=True``; raises ValueError on v2 streams).  A
        present-but-corrupt footer falls back to a sequential decode of the
        whole stream (with a RuntimeWarning), still returning only the
        selected frames' elements.
        """
        if select is None:
            return self.decompress_chunked(fileobj, n=n)
        select = _validate_select(select)
        idx = container.read_index_footer_safe(fileobj)
        if idx is not None and idx.get("kind") != "szx-chunked":
            raise ValueError(
                f"not a single-array chunked stream (footer kind "
                f"{idx.get('kind')!r}); tree streams restore via "
                "TreeCodec.decompress_tree"
            )
        if idx is None:
            # distinguish "no footer was ever written" (v2: select= is a
            # caller error) from "footer present but unreadable" (corrupt:
            # fall back to the sequential decode select= still works on).
            # A valid trailer starts with the SZXI magic in the last 20
            # bytes; a corrupt-but-present footer usually still does.
            end = fileobj.seek(0, 2)
            fileobj.seek(max(end - container.INDEX_TRAILER.size, 0))
            trailer = fileobj.read(container.INDEX_TRAILER.size)
            fileobj.seek(0)
            if container.INDEX_MAGIC not in trailer:
                raise ValueError(
                    "select= needs a container-v3 index footer; this stream "
                    "has none (rewrite it with dump_chunked(..., index=True))"
                )
            wanted = set(select)
            parts = []
            for i, payload in enumerate(container.iter_frames(fileobj)):
                if i in wanted:
                    parts.append(self.decompress(payload))
            if select[-1] >= i + 1:
                raise ValueError(
                    f"frame index {select[-1]} out of range [0, {i + 1})"
                )
            return np.concatenate(parts) if len(parts) > 1 else parts[0]
        frames = idx["frames"]
        parts = []
        for i in select:
            if i >= len(frames):
                raise ValueError(f"frame index {i} out of range [0, {len(frames)})")
            off, length, _elems = frames[i]
            payload, _flags = container.read_frame_at(fileobj, off, length, i)
            parts.append(self.decompress(payload))
        return np.concatenate(parts) if len(parts) > 1 else parts[0]


# functional API (compat shim repro.core.szx re-exports these)
def compress(x, bound: Bound | float | None = None, *, mode: str | None = None,
             block_size: int = DEFAULT_BLOCK_SIZE, backend: str = "auto",
             dtype=None, error_bound: float | None = None) -> bytes:
    b = plan_mod.as_bound(bound, mode, error_bound=error_bound,
                          owner="szx_codec.compress")
    return SZxCodec(block_size, backend).compress(x, b, dtype=dtype)


def decompress(buf: bytes, *, backend: str = "auto") -> np.ndarray:
    return SZxCodec(backend=backend).decompress(buf)


def compress_with_stats(x, bound: Bound | float | None = None, *,
                        mode: str | None = None,
                        block_size: int = DEFAULT_BLOCK_SIZE, backend: str = "auto",
                        dtype=None, error_bound: float | None = None,
                        ) -> tuple[bytes, CompressionStats]:
    b = plan_mod.as_bound(bound, mode, error_bound=error_bound,
                          owner="szx_codec.compress_with_stats")
    return SZxCodec(block_size, backend).compress_with_stats(x, b, dtype=dtype)
