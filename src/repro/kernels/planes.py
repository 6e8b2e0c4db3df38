"""Pallas TPU kernels: szx-planes fixed-plane encode/decode.

The in-graph (static-shape) SZx variant used for gradient/KV compression:
per-block mu + radius-exponent-derived scale + P uint8 quantization planes
(oracle: ``ref.planes_encode_ref`` / ``ref.planes_decode_ref``, bit-identical).

Shapes: leading dims are flattened to (nb, bs) before the call and restored
after, so the kernels only ever see ``(TILE_BLOCKS, bs)`` row tiles of
float32 (szx-planes is an f32-only mode); mu and sexp travel as meta rows
(see :mod:`repro.kernels.tiles`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiles


def _make_encode_kernel(num_planes: int):
    nbits = 8 * num_planes
    lim = float(2.0 ** (nbits - 1))   # python literal: closure-safe in pallas

    def _kernel(x_ref, planes_ref, meta_ref):
        x = x_ref[...]                                   # (TB, bs) f32
        mn = jnp.min(x, axis=1, keepdims=True)
        mx = jnp.max(x, axis=1, keepdims=True)
        mu = 0.5 * (mn + mx)
        radius = jnp.maximum(mx - mu, mu - mn)
        E = (
            (jax.lax.bitcast_convert_type(radius, jnp.uint32) >> 23)
            & jnp.uint32(0xFF)
        ).astype(jnp.int32) - 127
        sexp = (nbits - 2) - E
        scale = jnp.exp2(sexp.astype(jnp.float32))
        q = jnp.clip(jnp.round((x - mu) * scale), -lim, lim - 1).astype(jnp.int32)
        for p in range(num_planes):
            planes_ref[p, :, :] = ((q >> (8 * p)) & 0xFF).astype(jnp.uint8)
        meta_ref[...] = tiles.cols_to_rows(
            [jax.lax.bitcast_convert_type(mu, jnp.int32), sexp], jnp.int32
        )

    return _kernel


def _make_decode_kernel(num_planes: int):
    nbits = 8 * num_planes

    def _kernel(meta_ref, planes_ref, out_ref):
        mu_m, sexp = tiles.rows_to_cols(meta_ref[...], 2)
        mu = jax.lax.bitcast_convert_type(mu_m, jnp.float32)
        uq = planes_ref[0].astype(jnp.int32)
        for p in range(1, num_planes):
            uq = uq | (planes_ref[p].astype(jnp.int32) << (8 * p))
        # sign-extend a width-`nbits` two's-complement integer (fits in int32)
        q = jnp.where(uq >= (1 << (nbits - 1)), uq - (1 << nbits), uq).astype(
            jnp.float32
        )
        out_ref[...] = q * jnp.exp2(-sexp.astype(jnp.float32)) + mu

    return _kernel


def _planes_spec(num_planes: int, tb: int, bs: int) -> pl.BlockSpec:
    return pl.BlockSpec((num_planes, tb, bs), lambda i: (0, i, 0))


@functools.partial(jax.jit, static_argnames=("num_planes", "interpret"))
def planes_encode(xb, num_planes: int, *, interpret: bool | None = None):
    """Same contract as ref.planes_encode_ref; xb may have leading dims."""
    assert 1 <= num_planes <= 3, "szx-planes supports 1..3 byte planes"
    xb = jnp.asarray(xb, jnp.float32)
    lead = xb.shape[:-1]
    bs = xb.shape[-1]
    x2 = xb.reshape(-1, bs)
    nb = x2.shape[0]
    if nb == 0:
        return (jnp.zeros(lead, jnp.float32), jnp.zeros(lead, jnp.int32),
                jnp.zeros((num_planes,) + lead + (bs,), jnp.uint8))
    rows, tb = tiles.row_tiles(nb)
    planes, meta = pl.pallas_call(
        _make_encode_kernel(num_planes),
        grid=(pl.cdiv(rows, tb),),
        in_specs=[tiles.row_spec(tb, bs)],
        out_specs=(_planes_spec(num_planes, tb, bs), tiles.meta_spec(tb)),
        out_shape=(
            jax.ShapeDtypeStruct((num_planes, rows, bs), jnp.uint8),
            jax.ShapeDtypeStruct((tiles.META_ROWS, rows), jnp.int32),
        ),
        interpret=tiles.interpret_default(interpret),
        name="szx_planes_encode",
    )(tiles.pad_rows(x2, rows))
    mu = jax.lax.bitcast_convert_type(meta[0, :nb], jnp.float32)
    return (mu.reshape(lead), meta[1, :nb].reshape(lead),
            planes[:, :nb].reshape((num_planes,) + lead + (bs,)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def planes_decode(mu, sexp, planes, *, interpret: bool | None = None):
    """Same contract as ref.planes_decode_ref -> (..., bs) f32."""
    num_planes = planes.shape[0]
    assert num_planes <= 3, "szx-planes supports 1..3 byte planes"
    lead = planes.shape[1:-1]
    bs = planes.shape[-1]
    p2 = planes.reshape(num_planes, -1, bs)
    nb = p2.shape[1]
    if nb == 0:
        return jnp.zeros(lead + (bs,), jnp.float32)
    rows, tb = tiles.row_tiles(nb)
    meta = tiles.meta_rows(
        [jax.lax.bitcast_convert_type(jnp.asarray(mu, jnp.float32).reshape(-1),
                                      jnp.int32),
         jnp.asarray(sexp, jnp.int32).reshape(-1)],
        rows, jnp.int32,
    )
    out = pl.pallas_call(
        _make_decode_kernel(num_planes),
        grid=(pl.cdiv(rows, tb),),
        in_specs=[tiles.meta_spec(tb), _planes_spec(num_planes, tb, bs)],
        out_specs=tiles.row_spec(tb, bs),
        out_shape=jax.ShapeDtypeStruct((rows, bs), jnp.float32),
        interpret=tiles.interpret_default(interpret),
        name="szx_planes_decode",
    )(meta, tiles.pad_rows(p2, rows, axis=1))
    return out[:nb].reshape(lead + (bs,))
