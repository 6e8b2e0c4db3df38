"""Shared tiling and word helpers for the row-tiled SZx Pallas kernels.

Every transform kernel tiles an ``(nb, bs)`` block matrix into
``(TILE_BLOCKS, bs)`` row tiles: one SZx block per sublane row, its values
along the lanes.  Per-block scalars (mu, shift, nbytes, ...) live in two
layouts:

* inside a kernel, as ``(tb, 1)`` columns that broadcast along a block's
  values;
* in HBM, as rows of one lane-dense ``(8, nb)`` "meta" array (row k holds
  field k of every block), tiled ``(8, TILE_BLOCKS)``.

:func:`cols_to_rows` and :func:`rows_to_cols` convert between the two with one
32-bit 2-D transpose, which Mosaic supports; Mosaic has no rank-1 blocks of
fewer than 128 lanes and no sublane<->lane reshape.

Mosaic on v5e has no float16 vector type, so kernels never hold a 16-bit
float.  They load the storage BITS (``spec.uint_dtype``) widened to a 32-bit
word and convert with integer arithmetic (:func:`to_compute`,
:func:`to_storage_bits`): exact widening, round-to-nearest-even narrowing,
bit-identical to numpy's and XLA's conversions for every finite value.
float64 (64-bit words) exists only off-TPU, in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.specs import DtypeSpec

TILE_BLOCKS = 512        # SZx blocks per grid step (rows of a tile)
META_ROWS = 8            # sublane height of the per-block meta array
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def interpret_default(interpret: bool | None) -> bool:
    """Kernels compile natively on TPU and run interpreted elsewhere."""
    return jax.default_backend() != "tpu" if interpret is None else interpret


def word_dtype(spec: DtypeSpec):
    """In-kernel word: 64-bit for float64, 32-bit for every narrower format."""
    return np.dtype(np.uint64) if spec.itemsize == 8 else np.dtype(np.uint32)


def meta_dtype(spec: DtypeSpec):
    """Signed dtype of the per-block meta rows (holds mu's storage bits)."""
    return np.dtype(np.int64) if spec.itemsize == 8 else np.dtype(np.int32)


def storage_bits(x, spec: DtypeSpec):
    """(XLA side) a storage-dtype array as its unsigned bit pattern."""
    return jax.lax.bitcast_convert_type(jnp.asarray(x, spec.np_dtype), spec.uint_dtype)


def from_storage_bits(bits, spec: DtypeSpec):
    """(XLA side) inverse of :func:`storage_bits` for any integer carrier."""
    return jax.lax.bitcast_convert_type(_as_int(bits, spec.uint_dtype), spec.np_dtype)


def _as_int(a, dtype):
    """Integer/bool array -> ``dtype``: a bitcast at equal width (so no sign
    conversion touches the bits), a zero/sign extension or truncation else."""
    a = jnp.asarray(a)
    if a.dtype != bool and a.dtype.itemsize == np.dtype(dtype).itemsize:
        return jax.lax.bitcast_convert_type(a, dtype)
    return a.astype(dtype)


def row_tiles(nb: int) -> tuple[int, int]:
    """-> (rows, tile): arrays of ``rows`` blocks are cut into ``tile``-row
    tiles.  Frames of at least one tile keep their size (Pallas masks the
    ragged last tile); smaller ones are padded to a multiple of 128 blocks
    and run as one tile, so the meta transposes stay (8, 128)-aligned."""
    if nb >= TILE_BLOCKS:
        return nb, TILE_BLOCKS
    rows = -(-nb // 128) * 128
    return rows, rows


def pad_rows(a, rows: int, axis: int = 0):
    extra = rows - a.shape[axis]
    if extra == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, extra)
    return jnp.pad(a, widths)


def row_spec(tile: int, *trailing: int) -> pl.BlockSpec:
    """Block of ``tile`` rows (leading axis) with full trailing axes."""
    return pl.BlockSpec((tile,) + trailing, lambda i: (i,) + (0,) * len(trailing))


def meta_spec(tile: int) -> pl.BlockSpec:
    return pl.BlockSpec((META_ROWS, tile), lambda i: (0, i))


def meta_rows(fields, rows: int, dtype):
    """(XLA side) stack per-block vectors into an (8, rows) meta array."""
    out = jnp.zeros((META_ROWS, rows), dtype)
    for k, f in enumerate(fields):
        out = out.at[k, : f.shape[0]].set(_as_int(f, dtype))
    return out


# ---------------------------------------------------------------------------
# in-kernel helpers (trace-time; also run as plain XLA in interpret mode)
# ---------------------------------------------------------------------------

def cols_to_rows(cols, dtype):
    """(tb, 1) columns -> (8, tb) meta rows: place column k in lane k of a
    (tb, 128) tile, then one transpose turns lanes into rows."""
    tb = cols[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, 128), 1)
    acc = jnp.zeros((tb, 128), dtype)
    for k, c in enumerate(cols):
        acc = jnp.where(lane == k, c.astype(dtype), acc)
    return acc.T[:META_ROWS, :]


def rows_to_cols(meta, n: int):
    """(8, tb) meta rows -> the first ``n`` rows as (tb, 1) columns."""
    tb = meta.shape[1]
    full = jnp.concatenate(
        [meta, jnp.zeros((128 - META_ROWS, tb), meta.dtype)], axis=0
    ).T
    return [full[:, k:k + 1] for k in range(n)]


def shift_lanes(a, step: int, fill):
    """out[:, i] = a[:, i - step] for i >= step, ``fill`` before."""
    return jnp.pad(a, ((0, 0), (step, 0)), constant_values=fill)[:, : a.shape[1]]


def prefix_max_lanes(key):
    """Inclusive running max along lanes in log2(bs) shift+max steps (the
    paper's interleaved index propagation, Fig. 9)."""
    step = 1
    while step < key.shape[1]:
        key = jnp.maximum(key, shift_lanes(key, step, -1))
        step *= 2
    return key


def _bitcast(x, dtype):
    return jax.lax.bitcast_convert_type(x, dtype)


def _u32(v):
    return jnp.asarray(v, jnp.uint32)


def _f16_to_f32(h):
    """Exact float16 -> float32 from the 16 low bits of a uint32 word."""
    sign = (h >> 15 & 1) << 31
    e = h >> 10 & 0x1F
    m = h & 0x3FF
    normal = ((e + 112) << 23) | (m << 13)
    infnan = _u32(0x7F800000) | (m << 13)
    sub = _bitcast(
        _bitcast(m, jnp.int32).astype(jnp.float32) * jnp.float32(2.0 ** -24),
        jnp.uint32,
    )
    bits = jnp.where(e == 0, sub, jnp.where(e == 31, infnan, normal))
    return _bitcast(bits | sign, jnp.float32)


def _f32_to_f16(x):
    """float32 -> float16 bits (round to nearest even, overflow to inf)."""
    b = _bitcast(x, jnp.uint32)
    sign = (b >> 16) & 0x8000
    a = b & 0x7FFFFFFF
    # normal results: rebias the exponent, round off 13 mantissa bits; a
    # carry into exponent 31 or anything larger saturates to inf
    t = a - _u32(0x38000000)
    normal = (t + 0xFFF + ((t >> 13) & 1)) >> 13
    normal = jnp.where(normal > 0x7C00, _u32(0x7C00), normal)
    # subnormal results: mant * 2**(e - 150) in units of 2**-24, rounded
    e = _bitcast(a >> 23, jnp.int32)
    mant = (a & 0x7FFFFF) | 0x800000
    sh = _bitcast(jnp.clip(126 - e, 1, 25), jnp.uint32)
    q = mant >> sh
    rem = mant & ((_u32(1) << sh) - 1)
    half = _u32(1) << (sh - 1)
    up = (rem > half) | ((rem == half) & ((q & 1) == 1))
    sub = q + up.astype(jnp.uint32)
    h = jnp.where(a >= 0x38800000, normal, sub)
    h = jnp.where(a > 0x7F800000, 0x7E00 | (a >> 13 & 0x3FF), h)   # quiet NaN
    return sign | h


def _f32_to_bf16(x):
    """float32 -> bfloat16 bits (round to nearest even)."""
    b = _bitcast(x, jnp.uint32)
    r = (b + 0x7FFF + ((b >> 16) & 1)) >> 16
    nan = (b & 0x7FFFFFFF) > 0x7F800000
    return jnp.where(nan, (b >> 16) | 0x40, r)


def to_compute(spec: DtypeSpec, w):
    """Storage bits held in a :func:`word_dtype` word -> compute-dtype value."""
    if spec.itemsize == 2:
        w = w & 0xFFFF
        if spec.exp_bits == 8:                       # bfloat16
            return _bitcast(w << 16, jnp.float32)
        return _f16_to_f32(w)
    return _bitcast(w, spec.compute_np_dtype)


def to_storage_bits(spec: DtypeSpec, x):
    """Compute-dtype value -> storage bits (rounded) in a word_dtype word."""
    if spec.itemsize == 2:
        return _f32_to_bf16(x) if spec.exp_bits == 8 else _f32_to_f16(x)
    return _bitcast(x, word_dtype(spec))
