"""Container layer: versioned stream serialization + self-delimiting frames.

Stage three of the pipeline: the only place that knows the byte layout.  The
v2 stream layout is pinned by a golden-bytes test (tests/test_codec.py) and
specified in docs/FORMAT.md:

  header  '<4sBBHQdIIQ': magic 'SZXJ' | version u8 | dtype u8 |
          block_size u16 | n u64 | e f64 | nblocks u32 | n_nonconst u32 |
          nmid u64
  const bitmap  ceil(nb/8) bytes (np.packbits order)
  mu            itemsize * nb bytes (input dtype, one per block)
  reqlen        u8 * n_nonconst
  L codes       2-bit * (n_nonconst * block_size), little-endian packed
  mid stream    nmid bytes in (block, value, byteplane) order

Chunked streams are sequences of frames, each framing one independent v2
stream:

  frame header '<4sBBIQ': magic 'SZXF' | version u8 | flags u8 (bit0 = last,
               bit1 = raw, bits 2-4 = second-stage code, see stage.py)
               | seq u32 | payload_len u64
"""
from __future__ import annotations

import functools
import struct
from dataclasses import dataclass
from typing import Iterator

import numpy as np

from repro import obs
from repro.core.codec import plan as plan_mod
from repro.core.codec.plan import Plan
from repro.core.codec.transform import BlockEncoding, derive_layout

MAGIC = b"SZXJ"
VERSION = 2
HEADER = struct.Struct("<4sBBHQdIIQ")

FRAME_MAGIC = b"SZXF"
FRAME_VERSION = 1
FRAME_HEADER = struct.Struct("<4sBBIQ")
FLAG_LAST = 0x01
FLAG_RAW = 0x02        # payload is raw bytes, not a v2 SZx stream (v3 packs)
# bits 2-4: negotiated lossless second-stage code over the mid-byte section
# (0 = none; see repro.core.codec.stage).  Readers that meet a non-zero code
# they cannot destage MUST fail loudly, never hand out garbage bytes.
FLAG_STAGE_SHIFT = 2
FLAG_STAGE_MASK = 0x7 << FLAG_STAGE_SHIFT


def stage_of_flags(flags: int) -> int:
    """Second-stage code recorded in a frame's flag bits (0 = stage-off)."""
    return (flags & FLAG_STAGE_MASK) >> FLAG_STAGE_SHIFT

# container v3: a frame sequence MAY be followed by a seekable index footer
# (JSON index payload + fixed trailer at the very end of the stream), which
# gives per-frame/per-leaf random access.  v2 streams (no footer) still
# decode; v2 readers predating the footer reject v3 files on the trailing
# bytes, which is the intended forward-compat failure mode.
INDEX_MAGIC = b"SZXI"
INDEX_VERSION = 1
INDEX_TRAILER = struct.Struct("<4sBBHQI")   # magic|ver|flags|reserved|len|crc32


# ---------------------------------------------------------------------------
# 2-bit code packing
# ---------------------------------------------------------------------------

def pack_2bit(codes: np.ndarray) -> np.ndarray:
    """codes: (m,) uint8 in [0,3] -> ceil(m/4) bytes."""
    pad = (-codes.size) % 4
    if pad:
        codes = np.concatenate([codes, np.zeros(pad, np.uint8)])
    c = codes.reshape(-1, 4)
    return (c[:, 0] | (c[:, 1] << 2) | (c[:, 2] << 4) | (c[:, 3] << 6)).astype(np.uint8)


def unpack_2bit(raw: np.ndarray, m: int) -> np.ndarray:
    b = raw.astype(np.uint8)
    out = np.empty((b.size, 4), np.uint8)
    out[:, 0] = b & 3
    out[:, 1] = (b >> 2) & 3
    out[:, 2] = (b >> 4) & 3
    out[:, 3] = (b >> 6) & 3
    return out.reshape(-1)[:m]


# ---------------------------------------------------------------------------
# monolithic v2 stream
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _value_base_cached(nb: int, bs: int, itemsize: int, wide: bool) -> np.ndarray:
    dt = np.int64 if wide else np.int32
    return (
        np.arange(nb, dtype=dt)[:, None] * (itemsize * bs)
        + np.arange(bs, dtype=dt)
    ).reshape(-1)


def _value_base(nb: int, bs: int, itemsize: int, wide: bool) -> np.ndarray:
    """Flat plane-0 index of each value in a C-contiguous (nb, itemsize, bs)
    plane array.  Small shapes are cached (read-only) because the chunked
    paths reuse one shape for every frame; larger ones are rebuilt per call
    so the cache pins at most 4 x 16 MB for the process lifetime."""
    if nb * bs <= 1 << 22:
        return _value_base_cached(nb, bs, itemsize, wide)
    return _value_base_cached.__wrapped__(nb, bs, itemsize, wide)


def _mid_plan(L: np.ndarray, nbytes: np.ndarray, itemsize: int):
    """Exact mid-stream layout from per-value counts (``nbytes - L``).

    Returns ``(counts, start, nmid, wide)``: flat per-value byte counts, the
    exclusive prefix sum (each value's offset into the mid stream), the total
    mid-byte count, and whether flat plane indices overflow int32.  Replaces
    the O(nblocks*block_size*itemsize) boolean mask of the v1 implementation.
    """
    nb, bs = L.shape
    wide = nb * bs * itemsize > np.iinfo(np.int32).max - bs
    counts = np.maximum(nbytes[:, None] - L, 0).reshape(-1)
    ends = np.cumsum(counts, dtype=np.int64 if wide else np.int32)
    nmid = int(ends[-1]) if counts.size else 0
    return counts, ends - counts, nmid, wide


def _copy_mid(L, nbytes, itemsize, counts, start, wide, src, dst, *, gather):
    """Move mid bytes between a flat (nb, itemsize, bs) plane array and the
    packed mid stream, in (block, value, byteplane) order.

    One fancy-index copy per byte slot k (<= itemsize passes, each over only
    the values with ``counts > k``): value v's k-th stored byte is plane
    ``L[v] + k`` and lands at mid offset ``start[v] + k``.  Indices are unique,
    so plain fancy assignment suffices -- no ``np.add.at``.
    """
    nb, bs = L.shape
    lb = L.reshape(-1)
    if wide:
        lb = lb.astype(np.int64)
    src0 = _value_base(nb, bs, itemsize, wide) + lb * bs
    for k in range(itemsize):
        sel = np.flatnonzero(counts > k)
        if sel.size == 0:
            break
        plane_idx = src0[sel] + k * bs
        mid_idx = start[sel] + k
        if gather:
            dst[mid_idx] = src[plane_idx]
        else:
            dst[plane_idx] = src[mid_idx]


@dataclass(frozen=True)
class StreamSections:
    """Parsed metadata sections of one v2 stream -- everything EXCEPT the
    mid-byte stream.

    This is the partial-decode contract: the metadata prefix (header, const
    bitmap, mu, reqlen, L codes) is tiny relative to the mid stream, and
    ``block_mid_start`` locates every block's mid bytes, so a reader can
    fetch the prefix, pick a block range, and then read ONLY that range's
    mid bytes (``repro.store`` ROI reads do exactly this).
    """

    plan: Plan
    const: np.ndarray            # (nb,) bool
    mu: np.ndarray               # (nb,) stream dtype
    reqlen: np.ndarray           # (nb,) int32 (0 for const blocks)
    shift: np.ndarray            # (nb,) int32
    nbytes: np.ndarray           # (nb,) int32
    L: np.ndarray                # (nb, bs) int32
    nmid: int                    # total mid-stream length (header field)
    mid_offset: int              # byte offset of the mid stream in the stream
    block_mid_start: np.ndarray  # (nb,) int64 exclusive cumsum of block mid bytes

    def mid_range(self, lo: int, hi: int) -> tuple[int, int]:
        """[start, stop) byte offsets WITHIN the mid stream holding the mid
        bytes of blocks [lo, hi)."""
        nb = self.plan.nblocks
        start = int(self.block_mid_start[lo]) if lo < nb else self.nmid
        stop = int(self.block_mid_start[hi]) if hi < nb else self.nmid
        return start, stop


def stream_prefix_length(header: bytes) -> int:
    """Byte length of the metadata prefix (header through L codes) of a v2
    stream, computed from its 40-byte header alone."""
    if len(header) < HEADER.size:
        raise ValueError("truncated SZx stream (shorter than header)")
    _m, _v, dtype_code, bs, _n, _e, nb, nnc, _nmid = HEADER.unpack_from(header, 0)
    spec = plan_mod.spec_for_code(dtype_code)
    nbm = (nb + 7) // 8
    nl = (nnc * bs + 3) // 4
    return HEADER.size + nbm + spec.itemsize * nb + nnc + nl


def parse_stream_sections(prefix, *, backend: str = "auto") -> StreamSections:
    """Validate + deserialize the metadata prefix of a v2 stream.

    ``prefix`` must cover at least the metadata sections (header, const
    bitmap, mu, reqlen, L codes); the mid-byte stream may be absent -- its
    layout is returned as ``block_mid_start`` so callers can read just the
    ranges they need (see :func:`extract_block_range`).
    """
    buf = bytes(prefix) if not isinstance(prefix, (bytes, bytearray)) else prefix
    if len(buf) < HEADER.size:
        raise ValueError("truncated SZx stream (shorter than header)")
    magic, version, dtype_code, bs, n, e, nb, nnc, nmid = HEADER.unpack_from(buf, 0)
    if magic != MAGIC:
        raise ValueError("bad SZx stream header (magic mismatch)")
    if version != VERSION:
        raise ValueError(f"unsupported SZx stream version {version}")
    spec = plan_mod.spec_for_code(dtype_code)           # raises on unknown code
    if nnc > nb:
        raise ValueError("corrupt SZx stream (n_nonconst > nblocks)")
    if bs == 0 or nb != (n + bs - 1) // bs:
        raise ValueError("corrupt SZx stream (block count mismatch)")
    p = plan_mod.plan_for_stream(dtype_code, bs, n, e, backend)

    nbm = (nb + 7) // 8
    nl = (nnc * bs + 3) // 4
    prefix_len = HEADER.size + nbm + spec.itemsize * nb + nnc + nl
    if len(buf) < prefix_len:
        raise ValueError(
            f"truncated SZx stream ({len(buf)} bytes, metadata sections "
            f"need {prefix_len})"
        )
    off = HEADER.size
    const = np.unpackbits(np.frombuffer(buf, np.uint8, nbm, off))[:nb].astype(bool)
    off += nbm
    mu = np.frombuffer(buf, spec.np_dtype, nb, off).copy()
    off += spec.itemsize * nb
    reqlen_nc = np.frombuffer(buf, np.uint8, nnc, off).astype(np.int32)
    off += nnc
    L_nc = unpack_2bit(np.frombuffer(buf, np.uint8, nl, off), nnc * bs)
    off += nl

    nc = ~const
    if int(nc.sum()) != nnc:
        raise ValueError("corrupt SZx stream (const bitmap / n_nonconst mismatch)")
    reqlen = np.zeros(nb, np.int32)
    reqlen[nc] = reqlen_nc
    shift, nbytes = derive_layout(reqlen, const, spec)
    if nbytes.max(initial=0) > spec.itemsize:
        raise ValueError("corrupt SZx stream (reqlen exceeds dtype width)")
    L = np.zeros((nb, bs), np.int32)
    L[nc] = L_nc.reshape(nnc, bs)

    # sum_v max(nbytes - L_v, 0) == bs*nbytes - sum_v min(L_v, nbytes);
    # computed on the non-const rows only (L is all-zero elsewhere)
    block_counts = nbytes.astype(np.int64) * bs
    if nnc:
        block_counts[nc] -= np.minimum(
            L_nc.reshape(nnc, bs), nbytes[nc, None]
        ).sum(axis=1, dtype=np.int64)
    ends = np.cumsum(block_counts)
    total = int(ends[-1]) if nb else 0
    if total != nmid:
        raise ValueError("corrupt SZx stream (mid-stream length mismatch)")
    return StreamSections(
        p, const, mu, reqlen, shift, nbytes, L, int(nmid), off,
        ends - block_counts,
    )


def extract_block_range(sec: StreamSections, mid, lo: int, hi: int) -> BlockEncoding:
    """Materialize the block encoding of blocks [lo, hi) of a parsed stream.

    ``mid`` holds EXACTLY those blocks' mid bytes (the ``sec.mid_range(lo,
    hi)`` slice of the mid stream).  The returned encoding is self-contained
    (block axis rebased to start at ``lo``) and decodes with the ordinary
    :func:`repro.core.codec.transform.decode_blocks` on any backend --
    partial decode costs O(hi - lo), not O(nblocks).
    """
    nb = sec.plan.nblocks
    if not 0 <= lo < hi <= nb:
        raise ValueError(f"block range [{lo}, {hi}) out of [0, {nb})")
    spec = sec.plan.dtype
    itemsize = spec.itemsize
    bs = sec.plan.block_size
    L_r = np.ascontiguousarray(sec.L[lo:hi])
    nbytes_r = np.ascontiguousarray(sec.nbytes[lo:hi])
    counts, start, nmid_r, wide = _mid_plan(L_r, nbytes_r, itemsize)
    mid_u8 = np.frombuffer(mid, np.uint8) if not isinstance(mid, np.ndarray) else mid
    if mid_u8.size != nmid_r:
        raise ValueError(
            f"mid-byte range for blocks [{lo}, {hi}) has {mid_u8.size} bytes, "
            f"expected {nmid_r}"
        )
    planes = np.zeros((hi - lo, itemsize, bs), np.uint8)
    if nmid_r:
        _copy_mid(
            L_r, nbytes_r, itemsize, counts, start, wide,
            mid_u8, planes.reshape(-1), gather=False,
        )
    return BlockEncoding(
        sec.mu[lo:hi], sec.const[lo:hi], sec.reqlen[lo:hi],
        sec.shift[lo:hi], nbytes_r, planes, L_r,
    )


def build_stream(p: Plan, enc: BlockEncoding) -> bytes:
    """Serialize one plan + block encoding into a self-contained v2 stream."""
    nc = ~enc.const
    nnc = int(nc.sum())
    itemsize = p.dtype.itemsize
    nb = p.nblocks
    bs = p.block_size
    counts, start, nmid, wide = _mid_plan(enc.L, enc.nbytes, itemsize)
    nbm = (nb + 7) // 8
    nl = (nnc * bs + 3) // 4
    # one preallocated buffer, every section written in place (no join copies)
    out = bytearray(HEADER.size + nbm + itemsize * nb + nnc + nl + nmid)
    HEADER.pack_into(
        out, 0, MAGIC, VERSION, p.dtype.code, p.block_size, p.n,
        p.error_bound, p.nblocks, nnc, nmid,
    )
    u8 = np.frombuffer(out, np.uint8)
    off = HEADER.size
    u8[off : off + nbm] = np.packbits(enc.const.astype(np.uint8))
    off += nbm
    u8[off : off + itemsize * nb] = np.ascontiguousarray(enc.mu).view(np.uint8)
    off += itemsize * nb
    u8[off : off + nnc] = enc.reqlen[nc].astype(np.uint8)
    off += nnc
    u8[off : off + nl] = pack_2bit(enc.L[nc].reshape(-1).astype(np.uint8))
    off += nl
    if nmid:
        _copy_mid(
            enc.L, enc.nbytes, itemsize, counts, start, wide,
            np.ascontiguousarray(enc.planes).reshape(-1),
            u8[off : off + nmid], gather=True,
        )
    return bytes(out)


def parse_stream(buf: bytes, *, backend: str = "auto") -> tuple[Plan, BlockEncoding]:
    """Validate + deserialize a v2 stream into (plan, block encoding)."""
    sec = parse_stream_sections(buf, backend=backend)
    expected = sec.mid_offset + sec.nmid
    if len(buf) < expected:
        raise ValueError(
            f"truncated SZx stream ({len(buf)} bytes, expected {expected})"
        )
    nb = sec.plan.nblocks
    if nb == 0:
        spec = sec.plan.dtype
        planes = np.zeros((0, spec.itemsize, sec.plan.block_size), np.uint8)
        return sec.plan, BlockEncoding(
            sec.mu, sec.const, sec.reqlen, sec.shift, sec.nbytes, planes, sec.L
        )
    mid_stream = np.frombuffer(buf, np.uint8, sec.nmid, sec.mid_offset)
    return sec.plan, extract_block_range(sec, mid_stream, 0, nb)


# ---------------------------------------------------------------------------
# self-delimiting frames (chunked streaming)
# ---------------------------------------------------------------------------

def build_frame(payload: bytes, seq: int, last: bool, *, raw: bool = False,
                stage=None) -> bytes:
    """Wrap one payload (v2 stream, or raw bytes with ``raw=True``) as a
    self-delimiting frame.

    ``stage`` (a ``repro.core.codec.stage`` name or code) requests the
    negotiated lossless second stage over the payload's mid-byte section:
    the frame is staged only when that actually shrinks it (and never for
    ``raw`` payloads), so a frame with stage bits set is always smaller than
    its stage-off form and ``stage=...`` can never lose.  Stage-off frames
    are byte-identical to frames built before the stage existed.
    """
    with obs.span("container.build_frame", seq=seq):
        flags = (FLAG_LAST if last else 0) | (FLAG_RAW if raw else 0)
        staged_code = 0
        orig_payload = payload
        if stage is not None and not raw:
            from repro.core.codec import stage as stage_mod

            code = stage_mod.resolve(stage)
            if code:
                staged = stage_mod.stage_payload(payload, code)
                if staged is not None:
                    payload = staged
                    flags |= code << FLAG_STAGE_SHIFT
                    staged_code = code
        frame = FRAME_HEADER.pack(
            FRAME_MAGIC, FRAME_VERSION, flags, seq, len(payload)
        ) + payload
        if not raw and obs.enabled():
            obs.stream_stats.record_frame_built(
                orig_payload, len(frame), staged_code
            )
    return frame


def destage_frame_payload(payload: bytes, flags: int) -> tuple[bytes, int]:
    """Undo a frame's second stage: ``(raw v2 payload, flags sans stage bits)``.

    Stage-off frames pass through untouched.  Frames whose stage this reader
    cannot run (unknown code, missing optional dependency) raise the
    fail-loudly ``stream requires second stage ...`` ValueError; raw frames
    with stage bits set are rejected as corrupt (writers never emit them).
    """
    code = stage_of_flags(flags)
    if not code:
        return payload, flags
    if flags & FLAG_RAW:
        raise ValueError(
            "corrupt SZx frame (raw frame carries second-stage flag bits)"
        )
    from repro.core.codec import stage as stage_mod

    return stage_mod.destage_payload(payload, code), flags & ~FLAG_STAGE_MASK


# ---------------------------------------------------------------------------
# container v3: seekable index footer
# ---------------------------------------------------------------------------

def build_index_footer(index: dict) -> bytes:
    """Serialize an index dict as the v3 footer: JSON payload + trailer.

    Appended AFTER the LAST-flagged frame; the trailer sits at the very end
    of the stream so a reader can find the index with two seeks.
    """
    import json
    import zlib

    payload = json.dumps(index, separators=(",", ":"), default=float).encode()
    # leading sentinel magic: lets a sequential frame reader recognize "the
    # rest of this stream is the index footer" from the first 4 bytes
    return INDEX_MAGIC + payload + INDEX_TRAILER.pack(
        INDEX_MAGIC, INDEX_VERSION, 0, 0, len(payload), zlib.crc32(payload)
    )


def read_index_footer(f) -> dict | None:
    """Read the v3 index footer of a seekable stream; None if absent (v2).

    Corrupt footers (bad CRC, truncated index, unsupported version) raise --
    a stream that CLAIMS to have an index must have a valid one.  The file
    position is left at the start of the index payload's JSON on success.
    """
    import json
    import zlib

    end = f.seek(0, 2)
    if end < INDEX_TRAILER.size:
        return None
    f.seek(end - INDEX_TRAILER.size)
    magic, version, _flags, _res, ilen, crc = INDEX_TRAILER.unpack(
        _read_exact(f, INDEX_TRAILER.size)
    )
    if magic != INDEX_MAGIC:
        return None
    if version != INDEX_VERSION:
        raise ValueError(f"unsupported SZx index footer version {version}")
    if ilen > end - INDEX_TRAILER.size:
        raise ValueError("corrupt SZx index footer (index longer than stream)")
    f.seek(end - INDEX_TRAILER.size - ilen)
    payload = _read_exact(f, ilen)
    if zlib.crc32(payload) != crc:
        raise ValueError("corrupt SZx index footer (CRC mismatch)")
    return json.loads(payload)


def read_index_footer_safe(f) -> dict | None:
    """Corruption-tolerant :func:`read_index_footer`: a bit-flipped or
    truncated footer returns ``None`` after a ``RuntimeWarning`` instead of
    raising, so callers can fall back to a sequential v2 decode.  A stream
    with no footer at all returns ``None`` silently, exactly like
    :func:`read_index_footer`."""
    import json
    import warnings

    try:
        return read_index_footer(f)
    except (ValueError, json.JSONDecodeError, struct.error) as err:
        warnings.warn(
            f"corrupt container-v3 index footer ({err}); treating the stream "
            "as a sequential (v2) frame sequence",
            RuntimeWarning,
            stacklevel=2,
        )
        return None


def read_frame_at(f, offset: int, length: int, seq: int) -> tuple[bytes, int]:
    """Random-access read of one frame via its index entry.

    Seeks to ``offset``, reads exactly ``length`` bytes, validates the frame
    header against the expected ``seq``, and returns ``(payload, flags)``.
    """
    f.seek(offset)
    frame = _read_exact(f, length)
    if len(frame) < FRAME_HEADER.size:
        raise ValueError("truncated SZx frame (shorter than frame header)")
    magic, version, flags, fseq, plen = FRAME_HEADER.unpack_from(frame, 0)
    if magic != FRAME_MAGIC:
        raise ValueError("bad SZx frame (magic mismatch)")
    if version != FRAME_VERSION:
        raise ValueError(f"unsupported SZx frame version {version}")
    if fseq != seq:
        raise ValueError(f"SZx index/frame seq mismatch (frame {fseq}, index {seq})")
    if len(frame) != FRAME_HEADER.size + plen:
        raise ValueError("truncated SZx frame (payload length mismatch)")
    return destage_frame_payload(frame[FRAME_HEADER.size:], flags)


def read_frame_stream_header_at(f, offset: int, seq: int) -> tuple[int, int, bytes]:
    """Random-access 58-byte peek at a frame's headers: seek to ``offset``,
    validate the frame header against ``seq`` and the payload's v2 stream
    header, and return ``(flags, payload_len, stream_header)``.

    The shared entry for every partial reader (store ROI reads, query
    scans, checkpoint sliced restore) -- none of them should interpret
    index-supplied offsets without these checks.  The file position is left
    right after the stream header.  Raw frames (no v2 payload) are the
    caller's job to route around via the index.
    """
    f.seek(offset)
    head = _read_exact(f, FRAME_HEADER.size + HEADER.size)
    magic, version, flags, fseq, plen = FRAME_HEADER.unpack_from(head, 0)
    if magic != FRAME_MAGIC:
        raise ValueError("bad SZx frame (magic mismatch)")
    if version != FRAME_VERSION:
        raise ValueError(f"unsupported SZx frame version {version}")
    if fseq != seq:
        raise ValueError(f"SZx index/frame seq mismatch (frame {fseq}, index {seq})")
    if plen < HEADER.size:
        raise ValueError("truncated SZx stream (shorter than header)")
    sheader = head[FRAME_HEADER.size:]
    if sheader[:4] != MAGIC:
        raise ValueError("bad SZx stream header (magic mismatch)")
    if sheader[4] != VERSION:
        raise ValueError(f"unsupported SZx stream version {sheader[4]}")
    return flags, plen, sheader


def _read_exact(f, size: int) -> bytes:
    data = f.read(size)
    if len(data) != size:
        raise ValueError(
            f"truncated SZx frame sequence (wanted {size} bytes, got {len(data)})"
        )
    return data


def peek_stream_meta(payload: bytes) -> tuple[int, int, float]:
    """(dtype code, element count, absolute bound) of one v2 payload's
    header -- the layout-aware peek for index builders and `info` tools."""
    if len(payload) < HEADER.size:
        raise ValueError("truncated SZx stream (shorter than header)")
    _m, _v, dtype_code, _bs, n, e, _nb, _nnc, _nmid = HEADER.unpack_from(payload, 0)
    return dtype_code, n, e


def iter_frames(source, *, with_flags: bool = False) -> Iterator:
    """Yield frame payloads from bytes, a binary file object, or an iterable
    of frame byte strings.  Validates magic, version, sequence numbers, and
    that the sequence terminates with a LAST-flagged frame.  With
    ``with_flags=True`` yields ``(payload, flags)`` pairs instead."""
    if isinstance(source, (bytes, bytearray, memoryview)):
        import io

        source = io.BytesIO(source)
    if hasattr(source, "read"):
        it = _iter_frames_file(source)
    else:
        it = _iter_frames_iterable(source)
    for payload, flags in it:
        yield (payload, flags) if with_flags else payload


def _iter_frames_iterable(source) -> Iterator[tuple[bytes, int]]:
    # iterable of per-frame byte strings (e.g. straight from compress_chunked)
    seq_expected = 0
    saw_last = False
    for frame in source:
        if saw_last:
            raise ValueError("SZx frame after the LAST-flagged frame")
        with obs.span("container.parse_frame", seq=seq_expected):
            payload, flags = _parse_one_frame(frame, seq_expected)
        saw_last = bool(flags & FLAG_LAST)
        seq_expected += 1
        yield payload, flags
    if seq_expected == 0:
        raise ValueError("empty SZx frame sequence")
    if not saw_last:
        raise ValueError("SZx frame sequence ended without a LAST frame")


def _parse_one_frame(frame: bytes, seq_expected: int) -> tuple[bytes, int]:
    if len(frame) < FRAME_HEADER.size:
        raise ValueError("truncated SZx frame (shorter than frame header)")
    magic, version, flags, seq, plen = FRAME_HEADER.unpack_from(frame, 0)
    if magic != FRAME_MAGIC:
        raise ValueError("bad SZx frame (magic mismatch)")
    if version != FRAME_VERSION:
        raise ValueError(f"unsupported SZx frame version {version}")
    if seq != seq_expected:
        raise ValueError(f"SZx frame out of order (seq {seq}, expected {seq_expected})")
    if len(frame) != FRAME_HEADER.size + plen:
        raise ValueError("truncated SZx frame (payload length mismatch)")
    return destage_frame_payload(frame[FRAME_HEADER.size:], flags)


def _iter_frames_file(f) -> Iterator[tuple[bytes, int]]:
    seq_expected = 0
    while True:
        if seq_expected == 0:
            hdr = f.read(FRAME_HEADER.size)
            if not hdr:
                raise ValueError("empty SZx frame sequence")
            if len(hdr) != FRAME_HEADER.size:
                raise ValueError(
                    f"truncated SZx frame sequence (wanted {FRAME_HEADER.size} "
                    f"bytes, got {len(hdr)})"
                )
        else:
            hdr = _read_exact(f, FRAME_HEADER.size)
        magic, version, flags, seq, plen = FRAME_HEADER.unpack(hdr)
        if magic != FRAME_MAGIC:
            raise ValueError("bad SZx frame (magic mismatch)")
        if version != FRAME_VERSION:
            raise ValueError(f"unsupported SZx frame version {version}")
        if seq != seq_expected:
            raise ValueError(
                f"SZx frame out of order (seq {seq}, expected {seq_expected})"
            )
        yield destage_frame_payload(_read_exact(f, plen), flags)
        seq_expected += 1
        if flags & FLAG_LAST:
            # v3 streams carry an index footer after the LAST frame.  A
            # further frame (FRAME_MAGIC) is always an error; any OTHER
            # trailing bytes are most plausibly a corrupted footer, and the
            # frames themselves are intact, so tolerate them with a warning
            # (sequential decode is the corrupt-footer fallback path).
            tail = f.read(len(INDEX_MAGIC))
            if tail and tail != INDEX_MAGIC:
                if tail.startswith(FRAME_MAGIC[: len(tail)]):
                    raise ValueError("SZx frame after the LAST-flagged frame")
                import warnings

                warnings.warn(
                    "ignoring unrecognized trailing bytes after the LAST "
                    "SZx frame (corrupt index footer?)",
                    RuntimeWarning,
                    stacklevel=3,
                )
            return
