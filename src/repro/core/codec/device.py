"""Device layer: device-resident stream assembly + the shared encoding record.

The FZ-GPU / cuSZ lesson (PAPERS.md) is that an ultra-fast compressor must
keep the variable-length block compaction ON the accelerator and read back a
single contiguous payload; anything else turns the PCIe/ICI link into the
bottleneck.  Before this layer, ``transform.encode_blocks`` pulled seven
fixed-shape arrays (mu/const/reqlen/shift/nbytes/planes/L) to the host and
derived the byte layout there -- up to ``itemsize + 1`` times the compressed
size crossing the link, plus a host-side gather per frame.

:func:`encode_device` stages the whole encode as ONE jitted program: the
fused stats+pack kernel (``ops.encode_staged``) AND the layout derivation --
the const bitmap and mu words at fixed offsets, then the compacted reqlen,
2-bit L codes and mid-byte stream, each front-packed by an order-preserving
compaction of log2(length) static shifts (:func:`_compact`, no scatter) and
placed at its prefix-sum offset in one contiguous ``uint8`` body buffer.
A chunk therefore reaches the host as ONE ``jax.device_get`` of final
container bytes plus a tiny header struct (:func:`to_stream` -- the
transfer-spy test in ``tests/test_device_encoding.py`` pins the single-get
contract).  The byte layout is bit-identical to the host serializer
``container.build_stream`` for every dtype/backend (golden f32 bytes
unchanged); the numpy mirror is kept for the host backend.

:class:`DeviceEncoding` is the shared device-resident representation: a
registered pytree of named arrays plus static metadata.  The byte-stream
codec uses kind ``"szx-v2"`` (body/total/nnc/nmid); the fixed-shape
in-graph codec (``PlanesCodec`` -- gradient and KV-cache compression) uses
kind ``"szx-planes"`` (mu/sexp/planes), so checkpointing, grad compression,
and serving all speak one encoding record.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.codec import container, transform
from repro.core.codec.plan import Plan
from repro.kernels.specs import DtypeSpec

_INT32_SAFE = np.iinfo(np.int32).max - 16


@jax.tree_util.register_pytree_node_class
@dataclass(frozen=True, eq=False)
class DeviceEncoding:
    """A named bundle of (device or host) arrays plus static metadata.

    Registered as a jax pytree -- instances flow through ``jit`` /
    ``shard_map`` / ``lax.scan`` / collectives like ``all_gather`` exactly
    like a dict of arrays, while carrying the encoding ``kind`` and any
    static metadata (e.g. the resolved :class:`Plan`) out of band.
    """

    kind: str
    arrays: dict[str, Any]
    meta: tuple = ()               # sorted (key, value) pairs; values hashable

    @classmethod
    def make(cls, kind: str, arrays: Mapping[str, Any], **meta) -> "DeviceEncoding":
        return cls(kind, dict(arrays), tuple(sorted(meta.items())))

    @property
    def info(self) -> dict:
        return dict(self.meta)

    def __getitem__(self, key: str):
        return self.arrays[key]

    def replace(self, **arrays) -> "DeviceEncoding":
        """New encoding with some arrays swapped (kind/meta preserved)."""
        unknown = set(arrays) - set(self.arrays)
        if unknown:
            raise KeyError(f"unknown encoding arrays {sorted(unknown)}")
        return DeviceEncoding(self.kind, {**self.arrays, **arrays}, self.meta)

    # ------------------------------------------------------------- pytree
    def tree_flatten(self):
        names = tuple(sorted(self.arrays))
        return tuple(self.arrays[n] for n in names), (self.kind, names, self.meta)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, names, meta = aux
        return cls(kind, dict(zip(names, children)), meta)


# ---------------------------------------------------------------------------
# on-device layout derivation (the container byte layout, by compaction)
# ---------------------------------------------------------------------------

def _left(a, d: int):
    """``a`` moved left by ``d`` places in row-major order, zeros after: a
    move of ``d // lanes`` rows and ``d % lanes`` lanes."""
    q, r = divmod(d, a.shape[1])
    a = jnp.pad(a[q:], ((0, q), (0, 0)))
    if r:
        below = jnp.pad(a[1:], ((0, 1), (0, 0)))
        a = jnp.concatenate([a[:, r:], below[:, :r]], axis=1)
    return a


def _move(arrays, go, d: int):
    """Move the elements marked ``go`` left by ``d``; the places they leave
    and nothing enters become zero."""
    come = _left(go, d)
    return tuple(jnp.where(come, _left(a, d), jnp.where(go, 0, a)) for a in arrays)


def _compact(payload, shift, valid):
    """Front-pack the kept bytes of a 2-D ``payload``, in row-major order.

    ``payload`` (uint8), ``shift`` and ``valid`` share one shape; element
    ``i`` of the row-major order, if ``valid``, moves to ``i - shift``.  The
    shifts are those of a boolean-mask compaction: they do not decrease along
    the kept elements, and no two kept elements share a destination.  Step
    ``s`` moves the elements whose shift has bit ``s`` set left by a static
    ``2**s``, with slices, concatenations and selects: no scatter.  No step
    makes two kept elements collide, since the gap in their shifts, taken
    modulo ``2**s``, stays below their distance.  Returns the kept bytes
    front-packed, zeros after.  Two dimensions keep the TPU's lanes dense.
    """
    steps = (payload.size - 1).bit_length()
    # a byte rides in the low 8 bits of one uint32 word with the part of its
    # shift still to go, once that part fits in the other 24; the first steps
    # of an array longer than 2**24 carry the two apart
    apart = max(0, steps - 24)
    x = jnp.where(valid, payload, 0).astype(jnp.uint32)
    sh = jnp.where(valid, shift, 0).astype(jnp.uint32)  # empty: zero; it stays
    for s in range(apart):
        x, sh = _move((x, sh), (sh & 1) == 1, 1 << s)
        sh = sh >> 1
    w = sh << 8 | x
    for s in range(apart, steps):
        (w,) = _move((w,), (w & 256) != 0, 1 << s)
        w = w >> 9 << 8 | w & 255
    return (w & 255).astype(payload.dtype)


def _assemble_body(spec: DtypeSpec, mu, const, reqlen, nbytes, planes, L):
    """Lay out every v2 section in one contiguous uint8 body buffer.

    Pure jnp -- runs inside the fused encode program.  The buffer is sized to
    the static worst case (``cap``); ``total`` is the actual body length.
    Section layouts mirror ``container.build_stream`` byte for byte.  The
    compacted sections (reqlen, L codes, mid bytes) are each one
    :func:`_compact` of their candidates, placed at their data-dependent
    offset by ``dynamic_update_slice``; the program holds no scatter.  The
    caller runs it under the name scope ``szx_assemble``, each section under
    its own (``bitmap``, ``mu``, ``compact_reqlen``, ``compact_lcodes``,
    ``compact_mid``), so the profiler's trace names the device ops.
    """
    nb, bs = L.shape
    W = spec.itemsize
    nbm = (nb + 7) // 8
    req_off = nbm + W * nb
    cap = _capacity(nb, bs, W)
    idt = jnp.int32        # caller guarantees cap fits (host fallback otherwise)

    with jax.named_scope("bitmap"):
        # const bitmap (np.packbits order: MSB-first within each byte)
        cpad = jnp.pad(const.astype(jnp.int32), (0, nbm * 8 - nb))
        bitmap = (cpad.reshape(nbm, 8) << jnp.arange(7, -1, -1)).sum(axis=1)
    with jax.named_scope("mu"):
        # mu words, little-endian bytes (same order as the host .view(np.uint8))
        mu_bytes = jax.lax.bitcast_convert_type(mu, jnp.uint8).reshape(-1)
    body = jnp.concatenate([
        bitmap.astype(jnp.uint8), mu_bytes, jnp.zeros((cap - req_off,), jnp.uint8)
    ])
    # a non-constant block moves left by the number of constant blocks before it
    nonconst = ~const
    skip = jnp.cumsum(const.astype(idt)) - const.astype(idt)
    nnc = nb - jnp.sum(const.astype(idt))
    with jax.named_scope("compact_reqlen"):
        req = _compact(*(a[None, :] for a in (reqlen.astype(jnp.uint8), skip, nonconst)))
        body = jax.lax.dynamic_update_slice(body, req[0], (req_off,))
    with jax.named_scope("compact_lcodes"):
        # 2-bit L codes of the non-constant blocks, 4 per byte little-endian:
        # byte = c0|c1<<2|c2<<4|c3<<6 (a block's codes may share a byte with
        # the next block's when bs % 4 != 0)
        l_off = req_off + nnc
        codes = _compact(
            L.astype(jnp.uint8),
            jnp.broadcast_to(skip[:, None] * bs, L.shape),
            jnp.broadcast_to(nonconst[:, None], L.shape),
        ).reshape(-1)
        nl_max = (codes.size + 3) // 4
        c = jnp.pad(codes, (0, -codes.size % 512)).reshape(-1, 512)
        # strided lanes of 512-wide rows: a stride-4 slice of the flat array
        # is a gather on the TPU
        packed = c[:, 0::4] | c[:, 1::4] << 2 | c[:, 2::4] << 4 | c[:, 3::4] << 6
        body = jax.lax.dynamic_update_slice(
            body, packed.reshape(-1)[:nl_max], (l_off,))
        nl = (nnc * bs + 3) // 4
    with jax.named_scope("compact_mid"):
        # mid stream in (block, value, byteplane) order: value v stores bytes
        # L[v] .. nbytes[v]-1 at offset start[v] (the exclusive prefix sum of
        # the per-value counts).  Candidate (v, k) sits at v*W + k, so each
        # kept byte of v moves left by v*W + L[v] - start[v].
        mid_off = l_off + nl
        counts = jnp.maximum(nbytes[:, None] - L, 0).reshape(-1).astype(idt)
        ends = jnp.cumsum(counts)
        start = ends - counts
        nmid = ends[-1]
        # (nb, bs*W) per block: the lane-dense layout in which the TPU
        # interleaves planes without padding a minor axis of W
        def by_value(a):
            return jnp.swapaxes(a, 1, 2).reshape(nb, bs * W)

        k = jnp.arange(W, dtype=idt)[None, :, None]
        keep = (L[:, None, :] <= k) & (k < nbytes[:, None, None])
        v = jnp.arange(nb * bs, dtype=idt).reshape(nb, 1, bs)
        shift = v * W + L[:, None, :] - start.reshape(nb, 1, bs)
        mid = _compact(by_value(planes), by_value(jnp.broadcast_to(shift, planes.shape)),
                       by_value(keep)).reshape(-1)
        # mid_off + nb*bs*W <= cap, so the offset is never clamped
        body = jax.lax.dynamic_update_slice(body, mid, (mid_off,))
    return body, mid_off + nmid, nnc, nmid


@functools.partial(jax.jit, static_argnames=("spec", "backend"))
def _encode_device_jit(xb, e, p_e, *, spec: DtypeSpec, backend: str):
    from repro.kernels import ops

    mu, const, reqlen, _shift, nbytes, planes, L = ops.encode_staged(
        xb, e, p_e, spec=spec, backend=backend
    )
    with jax.named_scope("szx_assemble"):
        return _assemble_body(spec, mu, const, reqlen, nbytes, planes, L)


def _capacity(nb: int, bs: int, itemsize: int) -> int:
    """Worst-case body length of ``nb`` blocks of ``bs`` values: bitmap, mu
    words, reqlen and L codes of every block, every value's bytes in full."""
    return (nb + 7) // 8 + itemsize * nb + nb + (nb * bs + 3) // 4 + nb * bs * itemsize


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def encode_device(xb, p: Plan) -> DeviceEncoding:
    """(nb, bs) blocks -> kind ``"szx-v2"`` encoding, resident where it ran.

    On the 'jax'/'kernel' backends everything up to (and including) the final
    byte layout stays on device; the host backend ('numpy', or any input too
    large for int32 offsets) produces the byte-identical numpy
    mirror via the host serializer.  Arrays: ``body`` (worst-case-cap uint8
    buffer), ``total`` (actual body length), ``nnc``, ``nmid``.
    """
    from repro.kernels import ops

    backend = ops._resolve(p.backend, p.dtype)
    cap = _capacity(p.nblocks, p.block_size, p.dtype.itemsize)
    if backend == "numpy" or p.nblocks == 0 or cap > _INT32_SAFE:
        return _encode_host(xb, p)
    spec = p.dtype
    from repro.kernels import specs

    p_e = specs.exact_exponent_of(float(p.error_bound))
    with ops._x64_scope(spec), obs.span("device.encode.dispatch"):
        # f64 words need x64 for asarray AND trace; jnp.asarray handles
        # numpy AND already-device inputs -- no host bounce
        body, total, nnc, nmid = _encode_device_jit(
            jnp.asarray(xb, spec.np_dtype),
            jnp.asarray(p.error_bound, spec.compute_np_dtype),
            jnp.int32(p_e),
            spec=spec,
            backend=backend,
        )
    return DeviceEncoding.make(
        "szx-v2", {"body": body, "total": total, "nnc": nnc, "nmid": nmid}, plan=p
    )


def _encode_host(xb, p: Plan) -> DeviceEncoding:
    """Numpy mirror: same record, bytes from the host serializer."""
    enc = transform.encode_blocks(xb, p)
    stream = container.build_stream(p, enc)
    (_m, _v, _d, _bs, _n, _e, _nb, nnc, nmid) = container.HEADER.unpack_from(stream, 0)
    body = np.frombuffer(stream, np.uint8, offset=container.HEADER.size)
    return DeviceEncoding.make(
        "szx-v2",
        {"body": body, "total": np.int64(body.size), "nnc": np.int64(nnc),
         "nmid": np.int64(nmid)},
        plan=p,
    )


def to_stream(enc: DeviceEncoding) -> bytes:
    """Materialize a ``"szx-v2"`` encoding as one self-contained v2 stream.

    Exactly ONE ``jax.device_get`` (body buffer + the tiny header scalars in
    a single transfer); the 40-byte header is packed on the host from the
    plan plus those scalars.  Host-mirror encodings pass through device_get
    untouched (numpy in, numpy out -- no transfer).
    """
    if enc.kind != "szx-v2":
        raise ValueError(f"cannot serialize encoding kind {enc.kind!r}")
    p: Plan = enc.info["plan"]
    with obs.span("device.encode.get"):
        body, total, nnc, nmid = jax.device_get(
            (enc["body"], enc["total"], enc["nnc"], enc["nmid"])
        )
    if obs.enabled():
        obs.counter("device.get.calls", op="encode_stream").inc()
        obs.counter("device.get.bytes", op="encode_stream").inc(
            int(np.asarray(body).nbytes)
        )
    with obs.span("container.serialize"):
        header = container.HEADER.pack(
            container.MAGIC, container.VERSION, p.dtype.code, p.block_size,
            p.n, p.error_bound, p.nblocks, int(nnc), int(nmid),
        )
        return header + body[: int(total)].tobytes()


def encode_to_stream(xb, p: Plan) -> bytes:
    """One-transfer encode: blocks -> final container bytes."""
    return to_stream(encode_device(xb, p))


# ---------------------------------------------------------------------------
# device-resident decode (the mirror: ONE device_put of raw frame bytes)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("spec", "backend", "nb", "bs", "rb", "rebase")
)
def _decode_device_jit(body, nnc, lo, *, spec: DtypeSpec, backend: str,
                       nb: int, bs: int, rb: int, rebase: bool):
    from repro.kernels import ops

    return ops.decode_staged(
        body, nnc, lo, spec=spec, nb=nb, bs=bs, rb=rb, rebase=rebase,
        backend=backend,
    )


def _bucket_body(parts, cap: int) -> np.ndarray:
    """The body bytes ``parts`` laid end to end, zero-padded to the next
    power-of-2 bucket (bounded by the worst-case capacity) so each chunk
    geometry compiles a handful of decode programs instead of one per
    payload length.  Gathers in the decode program are index-clipped, so the
    padding is never observable."""
    n = sum(int(p.size) for p in parts)
    size = min(cap, 1 << (max(n, 1) - 1).bit_length())
    padded = np.zeros(size, np.uint8)
    off = 0
    for p in parts:
        padded[off: off + p.size] = p
        off += p.size
    return padded


def _checked_stream_header(buf):
    """Host-side header-only validation (mirrors ``container.parse_stream``'s
    messages); returns the unpacked fields + spec + section geometry."""
    from repro.core.codec import plan as plan_mod

    if len(buf) < container.HEADER.size:
        raise ValueError("truncated SZx stream (shorter than header)")
    magic, version, dtype_code, bs, n, e, nb, nnc, nmid = (
        container.HEADER.unpack_from(buf, 0)
    )
    if magic != container.MAGIC:
        raise ValueError("bad SZx stream header (magic mismatch)")
    if version != container.VERSION:
        raise ValueError(f"unsupported SZx stream version {version}")
    spec = plan_mod.spec_for_code(dtype_code)           # raises on unknown code
    if nnc > nb:
        raise ValueError("corrupt SZx stream (n_nonconst > nblocks)")
    if bs == 0 or nb != (n + bs - 1) // bs:
        raise ValueError("corrupt SZx stream (block count mismatch)")
    prefix_len = (
        container.HEADER.size + (nb + 7) // 8 + spec.itemsize * nb + nnc
        + (nnc * bs + 3) // 4
    )
    if len(buf) < prefix_len:
        raise ValueError(
            f"truncated SZx stream ({len(buf)} bytes, metadata sections "
            f"need {prefix_len})"
        )
    return spec, bs, n, nb, nnc, nmid, prefix_len


def _check_measured(meas, nnc: int, nmid: int, spec: DtypeSpec) -> None:
    """Raise the canonical ``container`` corrupt-stream errors from the
    data-dependent checks the device program measured (fetched alongside the
    decoded values in its single readback)."""
    if int(meas[0]) != nnc:
        raise ValueError("corrupt SZx stream (const bitmap / n_nonconst mismatch)")
    if int(meas[1]) > spec.itemsize:
        raise ValueError("corrupt SZx stream (reqlen exceeds dtype width)")
    if int(meas[2]) != nmid:
        raise ValueError("corrupt SZx stream (mid-stream length mismatch)")


def decode_stream(buf, *, backend: str = "auto", out=None, block_range=None):
    """Device-resident decompress of ONE v2 stream -> flat (n,) numpy array.

    The decode mirror of :func:`encode_to_stream`: the 40-byte header is
    unpacked on the host (pure struct math, no numpy section parsing), the
    raw body bytes cross the link as exactly ONE ``jax.device_put``, and the
    section offsets, metadata parse, and fused unpack+compose all run inside
    one jitted program (``ops.decode_staged``).  The decoded values return
    with the validation scalars in a single ``jax.device_get``.

    Returns None when the device route does not apply (numpy backend, empty
    stream, int32-unsafe capacity, or a body longer than the worst case) --
    callers then take the host path.  With ``out`` (flat (n,) array in the
    stream dtype) the result is written in place.  ``block_range=(lo, hi)``
    decodes only those blocks of the same device-put body (mid offsets stay
    absolute) and returns their clipped flat values.
    """
    from repro.kernels import ops

    with obs.span("container.parse_header"):
        spec, bs, n, nb, nnc, nmid, prefix_len = _checked_stream_header(buf)
    backend = ops._resolve(backend, spec)
    if backend == "numpy":
        return None
    expected = prefix_len + nmid
    if len(buf) < expected:
        raise ValueError(
            f"truncated SZx stream ({len(buf)} bytes, expected {expected})"
        )
    cap = _capacity(nb, bs, spec.itemsize)
    blen = expected - container.HEADER.size
    if nb == 0 or cap > _INT32_SAFE or blen > cap:
        return None
    lo, hi = (0, nb) if block_range is None else block_range
    if not 0 <= lo < hi <= nb:
        return None                      # host path raises the canonical error
    raw = np.frombuffer(buf, np.uint8, blen, container.HEADER.size)
    vals = _decode_body(
        [raw], cap, spec=spec, backend=backend, nb=nb, bs=bs, nnc=nnc,
        nmid=nmid, lo=lo, hi=hi, rebase=False, op="decode_stream",
    )
    flat = vals[: min(hi * bs, n) - lo * bs]
    if out is not None:
        with obs.span("device.decode.copy_out"):
            np.copyto(out, flat)
        return out
    return flat


def decode_range(prefix: bytes, mid: bytes, lo: int, hi: int, *,
                 backend: str = "auto"):
    """Device decode of blocks [lo, hi) from a metadata prefix + exactly that
    range's mid bytes (the store ROI read layout) -> flat (hi-lo)*bs values.

    The combined ``prefix[40:] + mid`` buffer has the SAME section offsets as
    a full body (the mid section simply starts at block ``lo``'s first mid
    byte), so this shares the full-decode program with ``rebase=True``: the
    kernel re-derives block ``lo``'s absolute mid offset from the L-code
    cumsum and subtracts it.  One ``device_put``, one jitted program, one
    readback.  Returns None when the device route does not apply.
    """
    from repro.kernels import ops

    with obs.span("container.parse_header"):
        spec, bs, n, nb, nnc, nmid, prefix_len = _checked_stream_header(prefix)
    backend = ops._resolve(backend, spec)
    if backend == "numpy":
        return None
    cap = _capacity(nb, bs, spec.itemsize)
    if nb == 0 or cap > _INT32_SAFE or not 0 <= lo < hi <= nb:
        return None
    parts = [
        np.frombuffer(prefix, np.uint8, prefix_len - container.HEADER.size,
                      container.HEADER.size),
        np.frombuffer(mid, np.uint8),
    ]
    if parts[0].size + parts[1].size > cap:
        return None
    return _decode_body(
        parts, cap, spec=spec, backend=backend, nb=nb, bs=bs, nnc=nnc,
        nmid=nmid, lo=lo, hi=hi, rebase=True, op="decode_range",
    )


def _decode_body(parts, cap: int, *, spec: DtypeSpec, backend: str, nb: int,
                 bs: int, nnc: int, nmid: int, lo: int, hi: int, rebase: bool,
                 op: str) -> np.ndarray:
    """Blocks [lo, hi) of one stream body on the device -> flat values.

    Stages the body bytes ``parts`` into one bucketed buffer, puts it on the
    device, runs the decode program and fetches the values with the
    validation scalars: one put, one get, counted under ``op``."""
    from repro.kernels import ops

    with obs.span("device.decode.stage"):
        body = _bucket_body(parts, cap)
    with ops._x64_scope(spec):
        with obs.span("device.decode.put"):
            dev_body = jax.device_put(body)
        with obs.span("device.decode.dispatch", lo=lo, hi=hi):
            vals, meas = _decode_device_jit(
                dev_body, np.int32(nnc), np.int32(lo),
                spec=spec, backend=backend, nb=nb, bs=bs, rb=hi - lo,
                rebase=rebase,
            )
        with obs.span("device.decode.get"):
            vals, meas = jax.device_get((vals, meas))
    if obs.enabled():
        obs.counter("device.put.calls", op=op).inc()
        obs.counter("device.put.bytes", op=op).inc(int(dev_body.nbytes))
        obs.counter("device.get.calls", op=op).inc()
        obs.counter("device.get.bytes", op=op).inc(
            int(vals.nbytes) + int(np.asarray(meas).nbytes)
        )
    _check_measured(meas, nnc, nmid, spec)
    return vals.reshape(-1)
