"""jit'd wrappers + backend dispatch for the width-generic SZx kernels.

Backends:
  'jax'    -- jnp oracle from ``ref.py`` under ``jax.jit`` (CPU default)
  'kernel' -- Pallas TPU kernels (``interpret=True`` automatically off-TPU)
  'numpy'  -- pure-numpy mirror (no jit/dispatch overhead; host-side use)
  'auto'   -- 'kernel' on TPU, 'jax' elsewhere (override with the
              ``SZX_OPS_BACKEND`` env var, e.g. to force the Pallas
              interpret path on CPU CI runners)

Every transform op takes a ``spec`` (:class:`repro.kernels.specs.DtypeSpec`,
default float32) and all three backends are bit-identical per spec.  float64
needs 64-bit words, which jax disables by default, so the jax/kernel routes
run those calls under ``jax.enable_x64(True)``.  TPUs have no 64-bit words:
there 'auto' resolves float64 to 'numpy' (the host mirror), and an explicit
'jax' or 'kernel' for float64 raises.

``encode`` is the fused stats+pack op: one traced program and a single
host<->device round trip instead of two, which is what the chunked codec hot
path stages per frame.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref, specs
from repro.kernels.specs import DtypeSpec


def _resolve(backend: str, spec: DtypeSpec | None = None) -> str:
    """Concrete backend for ``backend`` (and, when given, the dtype ``spec``).

    On TPU, float64 resolves 'auto' to 'numpy' and rejects 'jax'/'kernel':
    the chip has no 64-bit words, so no device program exists for it.
    'numpy' never queries (and so never initializes) a JAX backend.
    """
    if backend == "numpy":
        return backend
    on_tpu = jax.default_backend() == "tpu"
    no_device = spec is not None and spec.needs_x64 and on_tpu
    if backend == "auto":
        if no_device:
            return "numpy"
        backend = os.environ.get("SZX_OPS_BACKEND") or (
            "kernel" if on_tpu else "jax"
        )
    if backend not in ("jax", "kernel", "numpy"):
        raise ValueError(
            f"unknown SZx ops backend {backend!r}; "
            "expected 'jax', 'kernel', 'numpy', or 'auto'"
        )
    if no_device and backend != "numpy":
        raise ValueError(
            f"SZx {spec.name} has no {backend!r} route on TPU (no 64-bit "
            "words); use backend='numpy' or 'auto'"
        )
    return backend


def _x64_scope(spec: DtypeSpec):
    """Context enabling 64-bit words for specs that need them (float64)."""
    return jax.enable_x64(True) if spec.needs_x64 else contextlib.nullcontext()


# --------------------------------------------------------------------------
# jit'd oracle paths (spec is static: one program per dtype geometry)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("spec",))
def _block_stats_jax(xb, e, p_e, spec):
    return ref.block_stats_ref(xb, e, spec, p_e)


@functools.partial(jax.jit, static_argnames=("spec",))
def _pack_jax(xb, mu, shift, nbytes, spec):
    return ref.pack_ref(xb, mu, shift, nbytes, spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def _encode_jax(xb, e, p_e, spec):
    return ref.encode_ref(xb, e, spec, p_e)


@functools.partial(jax.jit, static_argnames=("spec",))
def _unpack_jax(planes, mu, shift, nbytes, L, spec):
    return ref.unpack_ref(planes, mu, shift, nbytes, L, spec)


@functools.partial(jax.jit, static_argnames=("spec",))
def _unpack_dense_jax(planes, mu, shift, nbytes, spec):
    return ref.unpack_dense_ref(planes, mu, shift, nbytes, spec)


# --------------------------------------------------------------------------
# numpy mirrors (bit-identical to ref.py, width-generic)
# --------------------------------------------------------------------------

def _np_exponent(x, spec: DtypeSpec = specs.F32):
    """Exponent field of |x| in the spec's COMPUTE dtype, bias removed."""
    cdt = spec.compute_np_dtype
    bits = np.asarray(x, cdt).view(spec.compute_uint_dtype)
    field = (bits >> np.asarray(spec.compute_mant_bits, spec.compute_uint_dtype)) & (
        (1 << spec.compute_exp_bits) - 1
    )
    return field.astype(np.int32) - spec.compute_exp_bias


def _to_compute(xb, spec: DtypeSpec):
    """Input -> storage-rounded -> compute dtype (no-copy when already there)."""
    return (
        np.asarray(xb)
        .astype(spec.np_dtype, copy=False)
        .astype(spec.compute_np_dtype, copy=False)
    )


def _block_stats_np(xb, e, spec: DtypeSpec, p_e: int | None = None):
    return _block_stats_np_c(_to_compute(xb, spec), e, spec, p_e)


def _block_stats_np_c(x, e, spec: DtypeSpec, p_e: int | None = None):
    cdt = spec.compute_np_dtype
    mn = x.min(axis=1)
    mx = x.max(axis=1)
    mu = (cdt.type(0.5) * (mn + mx)).astype(spec.np_dtype)
    mu_w = mu.astype(cdt)
    radius = np.maximum(mx - mu_w, mu_w - mn)
    r_test = radius
    if spec.stats_rounding_guard:
        # 16-bit formats: next-up radius keeps the constant-block bound
        # strict against the f32 subtraction rounding (see DtypeSpec)
        r_test = (
            radius.view(spec.compute_uint_dtype) + spec.compute_uint_dtype.type(1)
        ).view(cdt)
    const = r_test <= cdt.type(e)
    if p_e is None:
        p_e = specs.exact_exponent_of(float(e))
    req_m_raw = _np_exponent(radius, spec) - np.int32(p_e) + 1
    req_m = np.clip(req_m_raw, 0, spec.mant_bits)
    mu = np.where(req_m_raw > spec.mant_bits, np.zeros_like(mu), mu)  # verbatim
    reqlen = 1 + spec.exp_bits + req_m
    shift = (8 - reqlen % 8) % 8
    nbytes = (reqlen + shift) // 8
    z = np.zeros_like(reqlen)
    return (
        mu,
        radius,
        const,
        np.where(const, z, reqlen),
        np.where(const, z, shift),
        np.where(const, z, nbytes),
    )


def _pack_np(xb, mu, shift, nbytes, spec: DtypeSpec):
    return _pack_np_c(_to_compute(xb, spec), mu, shift, nbytes, spec)


def _pack_np_c(x, mu, shift, nbytes, spec: DtypeSpec):
    """Bit-identical to ``ref.pack_ref`` but allocation-lean: the shift runs
    in place on the normalized words and the XOR-lead run length is computed
    by byte-view equality against the predecessor (no xor word, no shifts)."""
    cdt = spec.compute_np_dtype
    udt = spec.uint_dtype
    itemsize = spec.itemsize
    nb, bs = x.shape
    mu_w = np.asarray(mu).astype(cdt, copy=False)
    v = x - mu_w[:, None]                  # fresh, contiguous
    if v.dtype != spec.np_dtype:
        v = v.astype(spec.np_dtype)        # storage-rounded residual
    ws = v.view(udt)
    np.right_shift(ws, shift[:, None].astype(udt), out=ws)
    # little-endian byte view: plane j (MSB-first) is byte itemsize-1-j -- no
    # shifts.  L counts how many leading bytes equal the predecessor's (the
    # first value compares against the zero word), capped at lead_cap.
    wsb = ws.view(np.uint8).reshape(nb, bs, itemsize)
    L = np.zeros((nb, bs), np.int32)
    run = np.empty((nb, bs), bool)
    eq = np.empty((nb, bs), bool)
    for j in range(spec.lead_cap):
        pj = wsb[:, :, itemsize - 1 - j]
        eq[:, 0] = pj[:, 0] == 0
        np.equal(pj[:, 1:], pj[:, :-1], out=eq[:, 1:])
        if j == 0:
            run[:] = eq
        else:
            run &= eq
        L += run
    np.minimum(L, nbytes[:, None], out=L)
    planes = np.ascontiguousarray(wsb[:, :, ::-1].transpose(0, 2, 1))
    mid = nbytes[:, None] - L
    return planes, L, mid


def _encode_np(xb, e, spec: DtypeSpec, p_e: int | None = None):
    """Fused mirror: the storage->compute upcast runs ONCE and feeds both
    stats and pack (the 16-bit dtypes otherwise pay the widening twice)."""
    x = _to_compute(xb, spec)
    mu, _radius, const, reqlen, shift, nbytes = _block_stats_np_c(x, e, spec, p_e)
    planes, L, _mid = _pack_np_c(x, mu, shift, nbytes, spec)
    return mu, const, reqlen, shift, nbytes, planes, L


def _finish_unpack_np(ws, mu, shift, nbytes, spec: DtypeSpec, out=None):
    """Shared tail of the numpy unpack mirrors: Solution-C shift back,
    bitcast, mu add, constant-block fill.  ``ws`` is consumed (shifted in
    place).  With ``out`` the reconstruction lands in the caller's buffer --
    for f32/f64 the mu add itself writes there, dropping the frame-sized
    temporary entirely."""
    udt = spec.uint_dtype
    np.left_shift(ws, shift[:, None].astype(udt), out=ws)
    v = ws.view(spec.np_dtype)
    cdt = spec.compute_np_dtype
    mu_w = np.asarray(mu).astype(cdt, copy=False)
    if out is not None and np.dtype(cdt) == np.dtype(spec.np_dtype):
        x = np.add(v, mu_w[:, None], out=out)
    else:
        x = (v.astype(cdt, copy=False) + mu_w[:, None]).astype(
            spec.np_dtype, copy=False
        )
    constm = nbytes == 0
    if out is None:
        return np.where(constm[:, None], np.asarray(mu)[:, None], x)
    if x is not out:
        np.copyto(out, x)
    if constm.any():
        out[constm] = np.asarray(mu)[constm, None]
    return out


def _unpack16_np(planes, mu, shift, nbytes, L, spec: DtypeSpec, out=None):
    """2-plane (float16/bfloat16) specialization of ``_unpack_np``.

    The generic loop pays per-plane index compression (``flatnonzero`` +
    fancy gathers) and strided byte-view scatters that dominate 16-bit decode
    time.  With exactly two planes the word composes arithmetically:
    propagate each plane only when some value actually elides it, then
    ``msb << 8 | lsb`` -- full-width masked ops, no index arrays, one
    contiguous word write.  Bit-identical to the generic path."""
    nb, _, bs = planes.shape
    msb = planes[:, 0, :]
    lsb = planes[:, 1, :]
    live0 = (nbytes > 0)[:, None]
    live1 = (nbytes > 1)[:, None]
    idxs256 = (np.arange(bs, dtype=np.int32) << 8)[None, :]
    if (L > 0).any():
        key = np.where((L <= 0) & live0, idxs256 | msb, np.int32(-1))
        np.maximum.accumulate(key, axis=1, out=key)
        b0 = (key & 0xFF).astype(np.uint16)
        b0[key < 0] = 0
    else:
        b0 = np.where(live0, msb, 0).astype(np.uint16)
    if (L > 1).any():
        key = np.where((L <= 1) & live1, idxs256 | lsb, np.int32(-1))
        np.maximum.accumulate(key, axis=1, out=key)
        b1 = (key & 0xFF).astype(np.uint16)
        b1[key < 0] = 0
    else:
        b1 = np.where(live1, lsb, 0).astype(np.uint16)
    ws = (b0 << np.uint16(8)) | b1
    return _finish_unpack_np(ws, mu, shift, nbytes, spec, out)


def _unpack_np(planes, mu, shift, nbytes, L, spec: DtypeSpec, out=None):
    """Bit-identical to ``ref.unpack_ref`` but byte-oriented: planes are written
    straight into a little-endian word byte view, index propagation runs only
    on planes that actually need it (some value has ``L > j``) and only over
    blocks where the plane is live (``nbytes > j``).  The propagation itself
    is the fused-key trick of the Pallas kernel: one cumulative max over
    ``idx*256 + byte`` (idx dominates, so the surviving key carries the byte
    of the nearest preceding stored position) -- no gather pass."""
    if spec.itemsize == 2:
        return _unpack16_np(planes, mu, shift, nbytes, L, spec, out)
    udt = spec.uint_dtype
    itemsize = spec.itemsize
    nb, _, bs = planes.shape
    ws = np.zeros((nb, bs), udt)
    wsb = ws.view(np.uint8).reshape(nb, bs, itemsize)  # little-endian host:
    idxs256 = (np.arange(bs, dtype=np.int32) << 8)[None, :]  # plane j is byte
    for j in range(min(itemsize, int(nbytes.max(initial=0)))):   # W-1-j
        live = nbytes > j
        act = slice(None) if live.all() else np.flatnonzero(live)
        pj = planes[act, j, :]
        Lj = L[act]
        # L <= lead_cap, so planes past it (and any plane with no L > j value)
        # are stored verbatim for every live value -- no propagation pass
        if j >= spec.lead_cap or not (Lj > j).any():
            wsb[act, :, itemsize - 1 - j] = pj
            continue
        key = np.where(Lj <= j, idxs256 | pj, np.int32(-1))
        np.maximum.accumulate(key, axis=1, out=key)    # index propagation
        byte = (key & 0xFF).astype(np.uint8)
        byte[key < 0] = 0
        wsb[act, :, itemsize - 1 - j] = byte
    return _finish_unpack_np(ws, mu, shift, nbytes, spec, out)


def _unpack_dense_np(planes, mu, shift, nbytes, spec: DtypeSpec, out=None):
    """All-``L==0`` fast path.  ``_unpack_np`` already degenerates to verbatim
    byte composition on every plane when no value has ``L > j``, so delegate
    with a broadcastable all-zero L instead of duplicating the loop (the real
    dense-path win is the jitted oracle, which drops the propagation scan)."""
    return _unpack_np(
        planes, mu, shift, nbytes, np.zeros((planes.shape[0], 1), np.int32),
        spec, out,
    )


# --------------------------------------------------------------------------
# szx-planes numpy mirrors (bit-identical to ref.py)
# --------------------------------------------------------------------------

def _planes_encode_np(xb, num_planes):
    assert 1 <= num_planes <= 3, "szx-planes supports 1..3 byte planes"
    xb = np.asarray(xb, np.float32)
    mn = xb.min(axis=-1)
    mx = xb.max(axis=-1)
    mu = np.float32(0.5) * (mn + mx)
    radius = np.maximum(mx - mu, mu - mn)
    E = _np_exponent(radius)
    nbits = 8 * num_planes
    sexp = (nbits - 2) - E
    v = xb - mu[..., None]
    scale = np.exp2(sexp.astype(np.float32))[..., None]
    lim = np.float32(2.0 ** (nbits - 1))
    q = np.clip(np.rint(v * scale), -lim, lim - 1).astype(np.int32)
    uq = q.astype(np.uint32)
    planes = np.stack(
        [((uq >> np.uint32(8 * p)) & np.uint32(0xFF)).astype(np.uint8) for p in range(num_planes)],
        axis=0,
    )
    return mu, sexp, planes


def _planes_decode_np(mu, sexp, planes):
    num_planes = planes.shape[0]
    assert num_planes <= 3, "szx-planes supports 1..3 byte planes"
    nbits = 8 * num_planes
    uq = np.zeros(planes.shape[1:], np.int32)
    for p in range(num_planes):
        uq = uq | (planes[p].astype(np.int32) << (8 * p))
    q = np.where(uq >= (1 << (nbits - 1)), uq - (1 << nbits), uq).astype(np.float32)
    v = q * np.exp2(-np.asarray(sexp, np.int32).astype(np.float32))[..., None]
    return v + np.asarray(mu, np.float32)[..., None]


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

def _as_words(xb, spec: DtypeSpec):
    return jnp.asarray(xb, spec.np_dtype)


def block_stats(xb, e, *, spec: DtypeSpec = specs.F32, backend: str = "auto"):
    backend = _resolve(backend, spec)
    if backend == "numpy":
        return _block_stats_np(xb, e, spec)
    p_e = specs.exact_exponent_of(float(e))
    with _x64_scope(spec):
        if backend == "kernel":
            from repro.kernels import encode as k

            return k.block_stats(
                _as_words(xb, spec),
                jnp.asarray(float(e), spec.compute_np_dtype),
                jnp.int32(p_e),
                spec=spec,
            )
        return _block_stats_jax(
            _as_words(xb, spec),
            jnp.asarray(float(e), spec.compute_np_dtype),
            jnp.int32(p_e),
            spec,
        )


def pack(xb, mu, shift, nbytes, *, spec: DtypeSpec = specs.F32, backend: str = "auto"):
    backend = _resolve(backend, spec)
    if backend == "numpy":
        return _pack_np(
            np.asarray(xb), np.asarray(mu), np.asarray(shift), np.asarray(nbytes),
            spec,
        )
    with _x64_scope(spec):
        args = (
            _as_words(xb, spec),
            _as_words(mu, spec),
            jnp.asarray(shift, jnp.int32),
            jnp.asarray(nbytes, jnp.int32),
        )
        if backend == "kernel":
            from repro.kernels import encode as k

            return k.pack(*args, spec=spec)
        return _pack_jax(*args, spec)


def encode_staged(xb, e, p_e, *, spec: DtypeSpec = specs.F32, backend: str = "jax"):
    """Trace-composable fused encode: dispatch WITHOUT host syncs.

    For callers that stage the encode into a larger jitted program (the
    device-resident stream assembly in ``repro.core.codec.device``): the
    error-bound exponent ``p_e`` is passed in as a traced value instead of
    being derived via ``float(e)``, and ``backend`` must already be resolved
    to 'jax' or 'kernel'.  Same outputs as :func:`encode`.
    """
    if backend not in ("jax", "kernel"):
        raise ValueError(
            f"encode_staged needs a resolved device backend, got {backend!r}"
        )
    if backend == "kernel":
        from repro.kernels import encode as k

        return k.encode(xb, e, p_e, spec=spec)
    return ref.encode_ref(xb, e, spec, p_e)


def decode_staged(body, nnc, lo=0, *, spec: DtypeSpec = specs.F32, nb: int,
                  bs: int, rb: int | None = None, rebase: bool = False,
                  backend: str = "jax"):
    """Trace-composable fused stream decode: dispatch WITHOUT host syncs.

    The decode mirror of :func:`encode_staged`, for callers that stage the
    decode into a larger jitted program (the device-resident container parse
    in ``repro.core.codec.device``).  ``body`` is the raw stream body (40-byte
    header stripped, zero-padded to a static capacity); ``nnc`` the header's
    n_nonconst field and ``lo`` the first decoded block, both traced; ``nb``/
    ``bs``/``rb`` static.  Parses the metadata sections on device
    (``ref.parse_body_ref``), gathers each value's stored bytes out of the
    body (``ref.gather_words_ref``, XLA: Mosaic has no 1-D dynamic gather)
    and composes the values -- the Pallas decode kernel on the 'kernel'
    route, the jnp oracle on 'jax'.  Returns (vals (rb, bs), measured (3,)
    int32): the bitmap's nonconst count, the max per-block nbytes, and the
    L-implied mid-stream total -- checked against the header fields on the
    host after its single readback (the device-side half of
    ``container.parse_stream`` validation).
    """
    if backend not in ("jax", "kernel"):
        raise ValueError(
            f"decode_staged needs a resolved device backend, got {backend!r}"
        )
    if rb is None:
        rb = nb
    _const, mu, shift, nbytes, rank, nnc_seen = ref.parse_body_ref(
        body, nnc, spec, nb
    )
    with jax.named_scope("szx_gather"):      # names its ops in the profiler
        words, L, mid_total = ref.gather_words_ref(
            body, nnc, lo, nbytes, rank, spec, bs=bs, rb=rb, rebase=rebase
        )
    args = [jax.lax.dynamic_slice_in_dim(a, lo, rb, axis=0)
            for a in (mu, shift, nbytes)]
    if backend == "kernel":
        from repro.kernels import unpack as k

        vals = k.unpack_words(words, *args, L, spec=spec)
    else:
        vals = ref.unpack_words_ref(words, *args, L, spec)
    measured = jnp.stack(
        [nnc_seen, jnp.max(nbytes).astype(jnp.int32), mid_total]
    )
    return vals, measured


def encode(xb, e, *, spec: DtypeSpec = specs.F32, backend: str = "auto"):
    """Fused block_stats + pack: (mu, const, reqlen, shift, nbytes, planes, L).

    One dispatched program (and for the jax/kernel routes a single
    host<->device round trip) instead of the two-call stats-then-pack
    sequence; bit-identical to calling :func:`block_stats` + :func:`pack`.
    """
    backend = _resolve(backend, spec)
    if backend == "numpy":
        return _encode_np(xb, e, spec)
    p_e = specs.exact_exponent_of(float(e))
    with _x64_scope(spec):
        args = (
            _as_words(xb, spec),
            jnp.asarray(float(e), spec.compute_np_dtype),
            jnp.int32(p_e),
        )
        if backend == "kernel":
            from repro.kernels import encode as k

            return k.encode(*args, spec=spec)
        return _encode_jax(*args, spec)


def unpack(planes, mu, shift, nbytes, L, *, spec: DtypeSpec = specs.F32,
           backend: str = "auto", out=None):
    """Inverse of :func:`pack`.  With ``out`` (a (nb, bs) array in the spec's
    dtype) the reconstruction is written into the caller's buffer and ``out``
    is returned -- allocation-free on the numpy route, one copy elsewhere."""
    backend = _resolve(backend, spec)
    if backend == "numpy":
        return _unpack_np(
            np.asarray(planes),
            np.asarray(mu),
            np.asarray(shift),
            np.asarray(nbytes),
            np.asarray(L),
            spec,
            out,
        )
    with _x64_scope(spec):
        args = (
            jnp.asarray(np.asarray(planes), jnp.uint8),
            _as_words(mu, spec),
            jnp.asarray(shift, jnp.int32),
            jnp.asarray(nbytes, jnp.int32),
            jnp.asarray(L, jnp.int32),
        )
        if backend == "kernel":
            from repro.kernels import unpack as k

            res = k.unpack(*args, spec=spec)
        else:
            res = _unpack_jax(*args, spec)
    if out is not None:
        np.copyto(out, np.asarray(res))
        return out
    return res


def unpack_dense(planes, mu, shift, nbytes, *, spec: DtypeSpec = specs.F32,
                 backend: str = "auto", out=None):
    """Batched fast path for frames whose L codes are all zero: every stored
    byte sits at its own value, so decode skips the per-byte index-propagation
    scan entirely.  Bit-identical to ``unpack(..., L=0)``.
    """
    backend = _resolve(backend, spec)
    if backend == "numpy":
        return _unpack_dense_np(
            np.asarray(planes), np.asarray(mu), np.asarray(shift),
            np.asarray(nbytes), spec, out,
        )
    with _x64_scope(spec):
        args = (
            jnp.asarray(np.asarray(planes), jnp.uint8),
            _as_words(mu, spec),
            jnp.asarray(shift, jnp.int32),
            jnp.asarray(nbytes, jnp.int32),
        )
        if backend == "kernel":
            from repro.kernels import unpack as k

            res = k.unpack_dense(*args, spec=spec)
        else:
            res = _unpack_dense_jax(*args, spec)
    if out is not None:
        np.copyto(out, np.asarray(res))
        return out
    return res


def unpack_range(planes, mu, shift, nbytes, L, lo: int, hi: int, *,
                 spec: DtypeSpec = specs.F32, backend: str = "auto"):
    """Partial decode of blocks [lo, hi): the ROI read primitive.

    Slices every per-block operand to the range, then dispatches the same
    width-generic ``unpack``/``unpack_dense`` pair -- so the partial decode
    is bit-identical to ``unpack(...)[lo:hi]`` on every backend (jax /
    kernel / numpy) at O(hi - lo) cost, and ranges with no XOR-lead elision
    take the dense fast path like full frames do.
    """
    nb = np.asarray(mu).shape[0]
    if not 0 <= lo < hi <= nb:
        raise ValueError(f"block range [{lo}, {hi}) out of [0, {nb})")
    L_r = L[lo:hi]
    args = (planes[lo:hi], mu[lo:hi], shift[lo:hi], nbytes[lo:hi])
    if not np.asarray(L_r).any():
        return unpack_dense(*args, spec=spec, backend=backend)
    return unpack(*args, L_r, spec=spec, backend=backend)


def planes_encode(xb, num_planes: int, *, backend: str = "auto"):
    """szx-planes fixed-plane encode (see kernels.ref.planes_encode_ref).

    The jax path calls the oracle untraced -- in-graph callers (jit /
    shard_map / scan bodies) stage it into their own program.  'kernel'
    dispatches the Pallas kernel (``repro.kernels.planes``).
    """
    backend = _resolve(backend)
    if backend == "numpy":
        return _planes_encode_np(xb, num_planes)
    if backend == "kernel":
        from repro.kernels import planes as k

        return k.planes_encode(jnp.asarray(xb, jnp.float32), num_planes)
    return ref.planes_encode_ref(jnp.asarray(xb, jnp.float32), num_planes)


def planes_decode(mu, sexp, planes, *, backend: str = "auto"):
    """Inverse of :func:`planes_encode`."""
    backend = _resolve(backend)
    if backend == "numpy":
        return _planes_decode_np(mu, sexp, planes)
    if backend == "kernel":
        from repro.kernels import planes as k

        return k.planes_decode(
            jnp.asarray(mu, jnp.float32), jnp.asarray(sexp, jnp.int32),
            jnp.asarray(planes, jnp.uint8),
        )
    return ref.planes_decode_ref(
        jnp.asarray(mu, jnp.float32), jnp.asarray(sexp, jnp.int32),
        jnp.asarray(planes, jnp.uint8),
    )


# --------------------------------------------------------------------------
# bitplane shuffle (second-stage transform)
# --------------------------------------------------------------------------

def _bitshuffle_np(tiles, inverse):
    """numpy mirror of ``ref.bitshuffle_ref`` (independent ground truth:
    built on np.unpackbits/np.packbits instead of the shared jnp body)."""
    tiles = np.ascontiguousarray(tiles, np.uint8)
    nt, T = tiles.shape
    if T % 8:
        raise ValueError(f"bitshuffle tile width {T} is not a multiple of 8")
    if nt == 0:
        return tiles.copy()
    bits = np.unpackbits(tiles, axis=1, bitorder="little").reshape(nt, T, 8)
    if inverse:
        bits = bits.reshape(nt, 8, T // 8, 8).transpose(0, 2, 3, 1)
    else:
        bits = bits.transpose(0, 2, 1)
    return np.packbits(bits.reshape(nt, T * 8), axis=1, bitorder="little")


@functools.partial(jax.jit, static_argnames=("inverse",))
def _bitshuffle_jax(tiles, inverse):
    return ref.bitshuffle_ref(tiles, inverse=inverse)


def bitshuffle(tiles, *, spec: DtypeSpec = specs.F32, inverse: bool = False,
               backend: str = "auto"):
    """Bit-transpose uint8 tiles of ``bitshuffle.tile_bytes(spec)`` bytes.

    ``tiles``: (nt, tile_bytes) uint8.  Forward groups bit k of every tile
    byte contiguously; ``inverse=True`` is the exact inverse.  All three
    backends are bit-identical (the second-stage container bytes must not
    depend on the backend).
    """
    backend = _resolve(backend)
    if backend == "numpy":
        return _bitshuffle_np(np.asarray(tiles), inverse)
    if backend == "kernel":
        from repro.kernels import bitshuffle as k

        return k.bitshuffle(
            jnp.asarray(tiles, jnp.uint8), spec=spec, inverse=inverse
        )
    return _bitshuffle_jax(jnp.asarray(tiles, jnp.uint8), inverse)
