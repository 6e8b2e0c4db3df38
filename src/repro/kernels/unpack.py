"""Pallas TPU kernel: SZx decompression (leading-byte retrieval + reassembly).

The single decode kernel.  Its input is one storage word per value holding
that value's STORED bytes (bytes a value elides are ignored), the L codes, and
the per-block meta rows (mu bits, shift, nbytes; see
:mod:`repro.kernels.tiles`).  Both decode routes build those words in XLA:
:func:`unpack` ORs the byte planes together, and the stream decode
(``ops.decode_staged``) gathers the stored bytes out of the container body
(``ref.gather_words_ref``) -- Mosaic has no 1-D dynamic gather.

Width-generic: the loop runs over ``itemsize`` MSB-first bytes and
reassembles the spec's word.  The paper's GPU "index propagation" (Fig. 9:
O(log n) interleaved-addressing max propagation) maps 1:1 onto a log2(bs)
sequence of lane shifts + maxima over a fused key ``idx*256 + byte`` -- idx
dominates the max, so the surviving key carries the byte of the nearest
preceding stored position; ``key & 0xFF`` recovers it.  Bytes past the lead
cap (the 2-bit L code tops out at 3) are always stored for live blocks, so
they skip the propagation entirely -- which is also what makes the ``dense``
variant (all-``L==0`` frames, no L input) a plain masked byte composition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import specs, tiles
from repro.kernels.specs import DtypeSpec


def _make_kernel(spec: DtypeSpec, dense: bool):
    wdt = tiles.word_dtype(spec)
    W = spec.itemsize

    def _kernel(*refs):
        if dense:
            meta_ref, w_ref, out_ref = refs
        else:
            meta_ref, w_ref, L_ref, out_ref = refs
            L = L_ref[...].astype(jnp.int32)
        mu_m, shift, nbytes = tiles.rows_to_cols(meta_ref[...], 3)
        mu_bits = jax.lax.bitcast_convert_type(mu_m, wdt)
        w = w_ref[...].astype(wdt)
        idx = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        ws = jnp.zeros(w.shape, wdt)
        for j in range(W):
            sh = 8 * (W - 1 - j)
            byte = ((w >> sh) & 0xFF).astype(jnp.int32)
            live = j < nbytes
            if dense or j >= spec.lead_cap:
                # L <= lead_cap <= j: every live value stored this byte
                b = jnp.where(live, byte, 0)
            else:
                key = jnp.where((L <= j) & live, idx * 256 + byte, -1)
                key = tiles.prefix_max_lanes(key)           # index propagation
                b = jnp.where(key >= 0, key & 0xFF, 0)
            ws = ws | (b.astype(wdt) << sh)
        # Solution-C shift back, re-add mu in the compute dtype, round to
        # storage; constant blocks decode to mu
        v = tiles.to_compute(spec, ws << shift.astype(wdt))
        bits = tiles.to_storage_bits(spec, v + tiles.to_compute(spec, mu_bits))
        out_ref[...] = jnp.where(nbytes == 0, mu_bits, bits).astype(spec.uint_dtype)

    return _kernel


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def unpack_words(words, mu, shift, nbytes, L=None, *, spec: DtypeSpec = specs.F32,
                 interpret: bool | None = None):
    """(nb, bs) stored-byte words (``spec.uint_dtype``) + (nb,) mu/shift/
    nbytes (+ (nb, bs) L codes; None means all zero) -> (nb, bs) values."""
    nb, bs = words.shape
    if nb == 0:
        return jnp.zeros((0, bs), spec.np_dtype)
    rows, tb = tiles.row_tiles(nb)
    tile = tiles.row_spec(tb, bs)
    meta = tiles.meta_rows(
        [tiles.storage_bits(mu, spec), shift, nbytes], rows, tiles.meta_dtype(spec)
    )
    args = [meta, tiles.pad_rows(words.astype(spec.uint_dtype), rows)]
    if L is not None:
        args.append(tiles.pad_rows(L.astype(jnp.uint8), rows))
    out = pl.pallas_call(
        _make_kernel(spec, dense=L is None),
        grid=(pl.cdiv(rows, tb),),
        in_specs=[tiles.meta_spec(tb)] + [tile] * (len(args) - 1),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows, bs), spec.uint_dtype),
        interpret=tiles.interpret_default(interpret),
        name=f"szx_decode_{spec.name}",
    )(*args)
    return tiles.from_storage_bits(out[:nb], spec)


def planes_to_words(planes, spec: DtypeSpec):
    """(nb, itemsize, bs) MSB-first byte planes -> (nb, bs) storage words."""
    W = spec.itemsize
    w = jnp.zeros((planes.shape[0], planes.shape[2]), spec.uint_dtype)
    for j in range(W):
        w = w | (planes[:, j, :].astype(spec.uint_dtype) << (8 * (W - 1 - j)))
    return w


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def unpack(planes, mu, shift, nbytes, L, *, spec: DtypeSpec = specs.F32,
           interpret: bool | None = None):
    """Same contract as ref.unpack_ref -> (nb, bs) in the spec's dtype."""
    return unpack_words(planes_to_words(planes, spec), mu, shift, nbytes, L,
                        spec=spec, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def unpack_dense(planes, mu, shift, nbytes, *, spec: DtypeSpec = specs.F32,
                 interpret: bool | None = None):
    """All-``L==0`` fast path; bit-identical to ``unpack(..., L=0)``."""
    return unpack_words(planes_to_words(planes, spec), mu, shift, nbytes,
                        spec=spec, interpret=interpret)
