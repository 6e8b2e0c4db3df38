"""Plain reference SZx codec: container-v2 streams in chunk frames, in numpy.

Written from the published format (SZx, arXiv 2201.13020, Algorithm 1 and
Formulas 4-5; the stream layout of the repository's docs/FORMAT.md) and
imports nothing of the program under test, so the benchmark's comparison has
a second reading that no later change to ``src/`` can move.

Per block of ``bs`` values, with stats in float32:

* ``mu = (min + max) / 2`` rounded to the storage dtype; radius ``r`` against
  that rounded ``mu``; the block is constant iff ``r <= e`` (for the 16-bit
  storage formats the next float32 above ``r`` is tested, so that the bound
  stays strict);
* otherwise ``reqlen = 1 + exp_bits + clip(p(r) - p(e) + 1, 0, mant_bits)``
  leading bits of each residual ``x - mu`` are kept, right-shifted by
  ``(8 - reqlen % 8) % 8`` so they fill whole bytes; a block whose residual
  would need more bits than the mantissa has is kept verbatim (``mu = 0``);
* each value's kept bytes are XOR-compared with its predecessor's and the
  count ``L`` of equal leading bytes (at most ``min(3, itemsize)``) is elided.

Only float32 and bfloat16 are implemented: float32 is what the benchmark's
configurations state, bfloat16 is the control one precision below it.
"""
from __future__ import annotations

import math
import struct
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

STREAM_HEADER = struct.Struct("<4sBBHQdIIQ")
FRAME_HEADER = struct.Struct("<4sBBIQ")
CHUNK_BYTES = 64 << 20
BLOCK = 128


class Format:
    """Storage geometry of one float format (stats always run in float32)."""

    def __init__(self, code, dtype, uint, exp_bits, mant_bits):
        self.code = code
        self.dtype = np.dtype(dtype)
        self.uint = np.dtype(uint)
        self.width = self.dtype.itemsize
        self.exp_bits = exp_bits
        self.mant_bits = mant_bits
        self.lead_cap = min(3, self.width)
        self.guard = self.width < 4


FLOAT32 = Format(0, np.float32, np.uint32, 8, 23)
BFLOAT16 = Format(3, ml_dtypes.bfloat16, np.uint16, 8, 7)
FORMATS = {"float32": FLOAT32, "bfloat16": BFLOAT16}


def rel_bound(x: np.ndarray, rel: float) -> float:
    """Absolute bound of a value-range-relative one, over the whole field
    (the range is taken in the field's own dtype, as a user's numpy would)."""
    rng = float(x.max() - x.min())
    e = rel * rng
    if e == 0.0:
        e = float(np.finfo(np.float32).tiny)
    return e


def _exponent(a32: np.ndarray) -> np.ndarray:
    """floor(log2 |a|) of float32 normals; -127 for zero and subnormals."""
    bits = a32.view(np.uint32)
    return ((bits >> 23) & 0xFF).astype(np.int32) - 127


def encode_stream(x: np.ndarray, e: float, fmt: Format) -> bytes:
    """One array (any length > 0) -> one container-v2 stream."""
    n = x.size
    nb = -(-n // BLOCK)
    flat = np.asarray(x, fmt.dtype).reshape(-1)
    if nb * BLOCK != n:
        flat = np.concatenate([flat, np.full(nb * BLOCK - n, flat[-1], fmt.dtype)])
    x32 = flat.astype(np.float32).reshape(nb, BLOCK)
    e32 = np.float32(e)
    lo = x32.min(axis=1)
    hi = x32.max(axis=1)
    mu = (0.5 * (lo + hi)).astype(fmt.dtype)
    mu32 = mu.astype(np.float32)
    radius = np.maximum(hi - mu32, mu32 - lo)
    tested = radius
    if fmt.guard:
        tested = (radius.view(np.uint32) + np.uint32(1)).view(np.float32)
    const = tested <= e32
    p_e = math.frexp(e)[1] - 1
    need = _exponent(radius) - p_e + 1
    verbatim = need > fmt.mant_bits
    mu = np.where(verbatim, np.zeros_like(mu), mu)
    mu32 = mu.astype(np.float32)
    reqlen = 1 + fmt.exp_bits + np.clip(need, 0, fmt.mant_bits)
    shift = (8 - reqlen % 8) % 8
    nbytes = np.where(const, 0, (reqlen + shift) // 8).astype(np.int32)
    reqlen = np.where(const, 0, reqlen)
    shift = np.where(const, 0, shift)

    live = ~const
    nnc = int(live.sum())
    xl = x32[live]
    resid = (xl - mu32[live][:, None]).astype(fmt.dtype)
    words = resid.view(fmt.uint) >> shift[live][:, None].astype(fmt.uint)
    prev = np.zeros_like(words)
    prev[:, 1:] = words[:, :-1]
    diff = words ^ prev
    lead = np.zeros(words.shape, np.int32)
    run = np.ones(words.shape, bool)
    top = 8 * (fmt.width - 1)
    for j in range(fmt.lead_cap):
        run &= (diff >> fmt.uint.type(top - 8 * j)) == 0
        lead += run
    keep = nbytes[live][:, None]
    lead = np.minimum(lead, keep)

    # mid bytes, (block, value, byte) order: bytes lead..keep-1, MSB first
    count = (keep - lead).reshape(-1).astype(np.int64)
    start = np.cumsum(count) - count
    nmid = int(count.sum())
    mid = np.empty(nmid, np.uint8)
    wflat = words.reshape(-1)
    lflat = lead.reshape(-1)
    for k in range(fmt.width):
        sel = np.flatnonzero(count > k)
        if sel.size == 0:
            break
        byte_pos = lflat[sel] + k
        shifts = (top - 8 * byte_pos).astype(fmt.uint)
        mid[start[sel] + k] = ((wflat[sel] >> shifts) & 0xFF).astype(np.uint8)

    codes = lead.reshape(-1).astype(np.uint8)
    codes = np.concatenate([codes, np.zeros((-codes.size) % 4, np.uint8)]).reshape(-1, 4)
    lpacked = codes[:, 0] | (codes[:, 1] << 2) | (codes[:, 2] << 4) | (codes[:, 3] << 6)
    header = STREAM_HEADER.pack(b"SZXJ", 2, fmt.code, BLOCK, n, float(e), nb, nnc, nmid)
    return b"".join([
        header,
        np.packbits(const.astype(np.uint8)).tobytes(),
        np.ascontiguousarray(mu).view(np.uint8).tobytes(),
        reqlen[live].astype(np.uint8).tobytes(),
        lpacked.astype(np.uint8).tobytes(),
        mid.tobytes(),
    ])


def decode_stream(buf: bytes) -> np.ndarray:
    """One container-v2 stream -> its flat values (float32 or bfloat16)."""
    magic, version, code, bs, n, e, nb, nnc, nmid = STREAM_HEADER.unpack_from(buf, 0)
    if magic != b"SZXJ" or version != 2 or bs != BLOCK:
        raise ValueError("not a container-v2 stream of 128-value blocks")
    fmt = next(f for f in FORMATS.values() if f.code == code)
    off = STREAM_HEADER.size
    nbm = -(-nb // 8)
    const = np.unpackbits(np.frombuffer(buf, np.uint8, nbm, off))[:nb].astype(bool)
    off += nbm
    mu = np.frombuffer(buf, fmt.dtype, nb, off)
    off += fmt.width * nb
    reqlen = np.frombuffer(buf, np.uint8, nnc, off).astype(np.int32)
    off += nnc
    nl = -(-nnc * bs // 4)
    packed = np.frombuffer(buf, np.uint8, nl, off)
    off += nl
    mid = np.frombuffer(buf, np.uint8, nmid, off)
    lead = np.stack([(packed >> s) & 3 for s in (0, 2, 4, 6)], axis=1)
    lead = lead.reshape(-1)[: nnc * bs].astype(np.int32).reshape(nnc, bs)
    shift = (8 - reqlen % 8) % 8
    keep = ((reqlen + shift) // 8)[:, None]

    # value v keeps bytes lead[v]..keep-1 at mid offsets base[v] + byte; a
    # byte it elides is the same byte of the nearest earlier value in its
    # block that stored it (zero where none did)
    count = np.maximum(keep - lead, 0).reshape(-1).astype(np.int64)
    base = np.cumsum(count) - count - lead.reshape(-1)
    flat_idx = np.arange(nnc * bs, dtype=np.int64).reshape(nnc, bs)
    words = np.zeros((nnc, bs), fmt.uint)
    for j in range(fmt.width if nmid else 0):
        stored = (lead <= j) & (j < keep)
        src = np.where(stored, flat_idx, -1)
        if j < fmt.lead_cap:
            # running max within each row: rows hold disjoint index ranges,
            # so -1 restarts each block
            src = np.maximum.accumulate(src, axis=1)
        ok = src >= 0
        pos = np.clip(base[np.maximum(src, 0)] + j, 0, nmid - 1)
        byte = np.where(ok, mid[pos], 0)
        words |= byte.astype(fmt.uint) << fmt.uint.type(8 * (fmt.width - 1 - j))
    words <<= shift[:, None].astype(fmt.uint)
    resid = words.view(fmt.dtype).astype(np.float32)
    out = np.repeat(mu[:, None], bs, axis=1)
    live = ~const
    out[live] = (resid + mu[live].astype(np.float32)[:, None]).astype(fmt.dtype)
    return out.reshape(-1)[:n]


def chunk_elements(width: int, chunk_bytes: int = CHUNK_BYTES) -> int:
    """Values per frame: ``chunk_bytes`` of input, whole blocks."""
    return max(chunk_bytes // width // BLOCK, 1) * BLOCK


def compress_frames(x: np.ndarray, rel: float, fmt: Format = FLOAT32, *,
                    chunk_bytes: int = CHUNK_BYTES, workers: int = 8) -> list[bytes]:
    """A whole field -> its frame sequence (frame header + v2 stream each);
    the bound is resolved once over the field."""
    xs = np.asarray(x, fmt.dtype).reshape(-1)
    e = rel_bound(xs, rel)
    per = chunk_elements(fmt.width, chunk_bytes)
    parts = [xs[i:i + per] for i in range(0, xs.size, per)]
    with ThreadPoolExecutor(max(1, min(workers, len(parts)))) as pool:
        streams = list(pool.map(lambda p: encode_stream(p, e, fmt), parts))
    return [
        FRAME_HEADER.pack(b"SZXF", 1, int(i == len(streams) - 1), i, len(s)) + s
        for i, s in enumerate(streams)
    ]


def decompress_frames(frames: list[bytes], *, workers: int = 8) -> np.ndarray:
    """A frame sequence -> the flat field."""
    def one(fr):
        magic, _v, _flags, _seq, plen = FRAME_HEADER.unpack_from(fr, 0)
        if magic != b"SZXF" or len(fr) != FRAME_HEADER.size + plen:
            raise ValueError("bad frame")
        return decode_stream(fr[FRAME_HEADER.size:])

    with ThreadPoolExecutor(max(1, min(workers, len(frames)))) as pool:
        return np.concatenate(list(pool.map(one, frames)))
