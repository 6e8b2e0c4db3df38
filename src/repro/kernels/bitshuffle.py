"""Pallas TPU kernel: bitplane shuffle (bit transpose) over byte tiles.

The lossless second stage (``repro.core.codec.stage``) groups a block range's
mid bytes into byteplane-major order and then bit-transposes fixed-size
tiles so that bit k of every byte in a tile lands contiguously -- turning the
per-value "top magnitude bits rarely set / Solution-C shift pad bits always
zero" structure into long zero runs an RLE can consume (FZ-GPU's
bitshuffle+sparsification, PAPERS.md).

Geometry: a tile is ``T = TILE_VALUES * spec.itemsize`` bytes.  Forward, out
bit-row k (``T/8`` bytes) holds bit k of every input byte, packed
little-endian: out byte ``(k, m)`` bit b is bit k of in byte ``8m + b``.
Viewing a tile as the (8, T/8) matrix ``A[b, m] = in[8m + b]`` makes both
directions the same per-column 8x8 bit transpose (:func:`transpose8`):
forward reads ``A`` and writes the bit-rows, inverse reads the bit-rows and
writes ``A``.  The XLA wrapper moves bytes between the tile and that matrix
layout, so the kernel is pure elementwise row arithmetic.  Tiles never mix,
which keeps the stage addressable per ROI block range.  The jnp oracle in
``ref.py`` and the numpy mirror in ``ops.py`` are bit-identical to this
kernel (pinned by tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import specs, tiles
from repro.kernels.specs import DtypeSpec

TILE_VALUES = 1024          # values per tile; tile bytes = TILE_VALUES * itemsize
LANES = 4096                # matrix columns per grid step


def tile_bytes(spec: DtypeSpec) -> int:
    """Bytes per shuffle tile for this dtype geometry (multiple of 8)."""
    return TILE_VALUES * spec.itemsize


def transpose8(rows):
    """8x8 bit transpose per column: ``rows`` is 8 equal-shape int32 arrays
    of byte values; output row i bit j = input row j bit i."""
    return [
        sum(((rows[j] >> i) & 1) << j for j in range(8)) for i in range(8)
    ]


def to_matrix(tiles_u8, inverse: bool):
    """(nt, T) tiles -> (8, nt*T/8) matrix the bit transpose reads."""
    nt, T = tiles_u8.shape
    if inverse:                     # bit-rows are already contiguous
        return tiles_u8.reshape(nt, 8, T // 8).transpose(1, 0, 2).reshape(8, -1)
    return tiles_u8.reshape(nt, T // 8, 8).transpose(2, 0, 1).reshape(8, -1)


def from_matrix(mat, nt: int, inverse: bool):
    """Inverse layout move of :func:`to_matrix` for the OTHER direction's
    output: forward output is bit-rows, inverse output is ``A``."""
    T = mat.shape[1] // nt * 8
    if inverse:
        return mat.reshape(8, nt, T // 8).transpose(1, 2, 0).reshape(nt, T)
    return mat.reshape(8, nt, T // 8).transpose(1, 0, 2).reshape(nt, T)


def _kernel(a_ref, out_ref):
    a = a_ref[...].astype(jnp.int32)
    rows = transpose8([a[j:j + 1, :] for j in range(8)])
    out_ref[...] = jnp.concatenate(rows, axis=0).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("spec", "inverse", "interpret"))
def bitshuffle(tiles_u8, *, spec: DtypeSpec = specs.F32, inverse: bool = False,
               interpret: bool | None = None):
    """Bit-transpose ``(nt, tile_bytes(spec))`` uint8 tiles (Pallas route)."""
    nt, T = tiles_u8.shape
    if T != tile_bytes(spec):
        raise ValueError(
            f"bitshuffle tile width {T} != tile_bytes({spec.name}) = "
            f"{tile_bytes(spec)}"
        )
    if nt == 0:
        return jnp.zeros((0, T), jnp.uint8)
    mat = to_matrix(tiles_u8, inverse)
    cols = mat.shape[1]                       # nt * T/8, a multiple of 128
    lanes = min(LANES, cols)
    spec_ = pl.BlockSpec((8, lanes), lambda i: (0, i))
    out = pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(cols, lanes),),
        in_specs=[spec_],
        out_specs=spec_,
        out_shape=jax.ShapeDtypeStruct((8, cols), jnp.uint8),
        interpret=tiles.interpret_default(interpret),
        name="szx_bitshuffle",
    )(mat)
    return from_matrix(out, nt, inverse)
