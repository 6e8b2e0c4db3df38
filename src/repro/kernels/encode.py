"""Pallas TPU kernel: fused SZx encode (block stats + pack in ONE kernel).

Each ``(TILE_BLOCKS, bs)`` row tile is loaded once: the per-block statistics
(paper Algorithm 1 lines 3-7: min/max/mu/radius/reqlen/shift/nbytes) are
lane reductions over each block's row, and the SAME resident tile is then
normalized, shifted (Solution C), XOR-lead counted (lines 8-9).  The kernel
emits the shifted storage words, the L codes and one lane-dense meta array of
per-block fields (see :mod:`repro.kernels.tiles`); the byte-plane split is an
elementwise XLA op on the words.  Width-generic via
:class:`repro.kernels.specs.DtypeSpec`.

:func:`block_stats` and :func:`pack` are the same kernel: the stats half
alone, and the pack half with mu/shift/nbytes given instead of computed.
All three are bit-identical to the jnp oracles in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import specs, tiles
from repro.kernels.specs import DtypeSpec


def _stats(spec: DtypeSpec, x, e, p_e):
    """(tb, bs) compute-dtype values -> (tb, 1) columns (mu_bits, radius,
    const, reqlen, shift, nbytes); reqlen/shift/nbytes are 0 for constant
    blocks and mu is 0 for verbatim blocks, exactly as ``ref.block_stats_ref``."""
    cdt = spec.compute_np_dtype
    cudt = spec.compute_uint_dtype
    mn = jnp.min(x, axis=1, keepdims=True)
    mx = jnp.max(x, axis=1, keepdims=True)
    mu_bits = tiles.to_storage_bits(spec, 0.5 * (mn + mx))  # storage-rounded
    mu_w = tiles.to_compute(spec, mu_bits)
    r = jnp.maximum(mx - mu_w, mu_w - mn)
    rbits = jax.lax.bitcast_convert_type(r, cudt)
    r_test = r
    if spec.stats_rounding_guard:
        # 16-bit formats: next-up radius keeps the constant-block bound
        # strict against the f32 subtraction rounding (see DtypeSpec)
        r_test = jax.lax.bitcast_convert_type(rbits + 1, cdt)
    const = r_test <= e
    rexp = (
        (rbits >> spec.compute_mant_bits) & ((1 << spec.compute_exp_bits) - 1)
    ).astype(jnp.int32) - spec.compute_exp_bias
    req_m_raw = rexp - p_e + 1
    req_m = jnp.clip(req_m_raw, 0, spec.mant_bits)
    mu_bits = jnp.where(req_m_raw > spec.mant_bits, jnp.zeros_like(mu_bits), mu_bits)
    reqlen = 1 + spec.exp_bits + req_m
    shift = (8 - (reqlen & 7)) & 7                 # Formula (5), Solution C
    nbytes = (reqlen + shift) >> 3
    zero = jnp.zeros_like(reqlen)
    return (mu_bits, rbits, const, jnp.where(const, zero, reqlen),
            jnp.where(const, zero, shift), jnp.where(const, zero, nbytes))


def _pack(spec: DtypeSpec, x, mu_bits, shift, nbytes):
    """Normalize, Solution-C shift and XOR-lead count -> (ws, L)."""
    wdt = tiles.word_dtype(spec)
    W = spec.itemsize
    mu_w = tiles.to_compute(spec, mu_bits)
    ws = tiles.to_storage_bits(spec, x - mu_w) >> shift.astype(wdt)
    xw = ws ^ tiles.shift_lanes(ws, 1, 0)           # vs. predecessor (zero word)
    L = jnp.zeros(ws.shape, jnp.int32)
    run = jnp.ones(ws.shape, bool)
    for j in range(spec.lead_cap):
        run = run & ((xw >> (8 * (W - 1 - j))) == 0)
        L = L + run.astype(jnp.int32)
    return ws, jnp.minimum(L, nbytes)


def _make_kernel(spec: DtypeSpec, given: bool):
    wdt = tiles.word_dtype(spec)
    mdt = tiles.meta_dtype(spec)

    def _kernel(*refs):
        if given:
            meta_in_ref, w_ref, ws_ref, L_ref = refs
            mu_m, shift, nbytes = tiles.rows_to_cols(meta_in_ref[...], 3)
            mu_bits = jax.lax.bitcast_convert_type(mu_m, wdt)
        else:
            e_ref, pe_ref, w_ref, ws_ref, L_ref, meta_ref = refs
        x = tiles.to_compute(spec, w_ref[...].astype(wdt))
        if not given:
            mu_bits, rbits, const, reqlen, shift, nbytes = _stats(
                spec, x, e_ref[0], pe_ref[0]
            )
            meta_ref[...] = tiles.cols_to_rows(
                [jax.lax.bitcast_convert_type(mu_bits, mdt),
                 jax.lax.bitcast_convert_type(rbits, mdt),
                 const, reqlen, shift, nbytes],
                mdt,
            )
        ws, L = _pack(spec, x, mu_bits, shift, nbytes)
        ws_ref[...] = ws.astype(spec.uint_dtype)
        L_ref[...] = L.astype(jnp.uint8)

    return _kernel


def _call(spec: DtypeSpec, xb, *, e=None, p_e=None, meta_in=None,
          interpret: bool | None):
    """One pallas_call over row tiles -> (ws, L[, meta]) for ``rows`` blocks."""
    nb, bs = xb.shape
    rows, tb = tiles.row_tiles(nb)
    words = tiles.pad_rows(tiles.storage_bits(xb, spec), rows)
    tile = tiles.row_spec(tb, bs)
    given = meta_in is not None
    if given:
        in_specs = [tiles.meta_spec(tb), tile]
        args = (meta_in, words)
    else:
        in_specs = [tiles.SMEM, tiles.SMEM, tile]
        args = (jnp.reshape(e.astype(spec.compute_np_dtype), (1,)),
                jnp.reshape(p_e.astype(jnp.int32), (1,)), words)
    out_specs = [tile, tile]
    out_shape = [jax.ShapeDtypeStruct((rows, bs), spec.uint_dtype),
                 jax.ShapeDtypeStruct((rows, bs), jnp.uint8)]
    if not given:
        out_specs.append(tiles.meta_spec(tb))
        out_shape.append(
            jax.ShapeDtypeStruct((tiles.META_ROWS, rows), tiles.meta_dtype(spec))
        )
    return pl.pallas_call(
        _make_kernel(spec, given),
        grid=(pl.cdiv(rows, tb),),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=tiles.interpret_default(interpret),
        name=f"szx_encode_{spec.name}",
    )(*args)


def _planes(spec: DtypeSpec, ws):
    """(nb, bs) shifted words -> (nb, itemsize, bs) MSB-first byte planes."""
    W = spec.itemsize
    return jnp.stack(
        [((ws >> (8 * (W - 1 - j))) & 0xFF).astype(jnp.uint8) for j in range(W)],
        axis=1,
    )


def _fields(spec: DtypeSpec, meta, nb: int):
    """Meta rows -> (mu, radius, const, reqlen, shift, nbytes) vectors."""
    m = meta[:, :nb]
    return (
        tiles.from_storage_bits(m[0], spec),
        jax.lax.bitcast_convert_type(m[1], spec.compute_np_dtype),
        m[2] != 0,
        m[3].astype(jnp.int32),
        m[4].astype(jnp.int32),
        m[5].astype(jnp.int32),
    )


def _empty_stats(spec: DtypeSpec):
    z = jnp.zeros((0,), jnp.int32)
    return (jnp.zeros((0,), spec.np_dtype), jnp.zeros((0,), spec.compute_np_dtype),
            jnp.zeros((0,), bool), z, z, z)


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def encode(xb: jax.Array, e: jax.Array, p_e: jax.Array, *,
           spec: DtypeSpec = specs.F32, interpret: bool | None = None):
    """Fused stats+pack -> (mu, const, reqlen, shift, nbytes, planes, L).

    Bit-identical to ``block_stats`` followed by ``pack`` (oracle:
    ``ref.encode_ref``); one kernel launch, one read of the input tile.
    """
    nb, bs = xb.shape
    if nb == 0:
        mu, _r, const, reqlen, shift, nbytes = _empty_stats(spec)
        return (mu, const, reqlen, shift, nbytes,
                jnp.zeros((0, spec.itemsize, bs), jnp.uint8),
                jnp.zeros((0, bs), jnp.int32))
    ws, L, meta = _call(spec, xb, e=e, p_e=p_e, interpret=interpret)
    mu, _r, const, reqlen, shift, nbytes = _fields(spec, meta, nb)
    return (mu, const, reqlen, shift, nbytes, _planes(spec, ws[:nb]),
            L[:nb].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def block_stats(xb: jax.Array, e: jax.Array, p_e: jax.Array, *,
                spec: DtypeSpec = specs.F32, interpret: bool | None = None):
    """xb: (nb, bs) spec dtype, e: scalar compute dtype, p_e: scalar int32
    (exact floor(log2 e)) -> same tuple as ``ref.block_stats_ref``."""
    if xb.shape[0] == 0:
        return _empty_stats(spec)
    _ws, _L, meta = _call(spec, xb, e=e, p_e=p_e, interpret=interpret)
    return _fields(spec, meta, xb.shape[0])


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def pack(xb, mu, shift, nbytes, *, spec: DtypeSpec = specs.F32,
         interpret: bool | None = None):
    """Same contract as ``ref.pack_ref`` -> (planes, L, mid)."""
    nb, bs = xb.shape
    if nb == 0:
        return (jnp.zeros((0, spec.itemsize, bs), jnp.uint8),
                jnp.zeros((0, bs), jnp.int32), jnp.zeros((0, bs), jnp.int32))
    rows, _tb = tiles.row_tiles(nb)
    meta_in = tiles.meta_rows(
        [tiles.storage_bits(mu, spec), shift, nbytes], rows, tiles.meta_dtype(spec)
    )
    ws, L = _call(spec, xb, meta_in=meta_in, interpret=interpret)
    L = L[:nb].astype(jnp.int32)
    return _planes(spec, ws[:nb]), L, nbytes[:, None] - L
