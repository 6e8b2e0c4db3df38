"""Device-resident stream assembly (repro.core.codec.device).

Pins the tentpole contracts: the encode path performs exactly ONE host
transfer per chunk (transfer spy over jax.device_get), the device-assembled
bytes are bit-identical to the host serializer for every dtype and device
backend (f32 golden bytes are pinned separately in test_codec.py), and
DeviceEncoding behaves as a pytree shared by the planes consumers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.codec import DeviceEncoding, PlanesCodec, SZxCodec, device, plan

try:
    import ml_dtypes

    BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    BF16 = None

_DTYPES = [np.float32, np.float64, np.float16] + ([BF16] if BF16 is not None else [])


def _walk(n, seed=0, dtype=np.float32, scale=0.01):
    rng = np.random.default_rng(seed)
    return (np.cumsum(rng.standard_normal(n)) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# transfer spy: ONE device_get per chunk
# ---------------------------------------------------------------------------

def test_encode_device_is_one_host_transfer(monkeypatch):
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda v: calls.append(v) or real_get(v))
    x = _walk(100_000, seed=1)
    buf = SZxCodec(backend="jax").compress(x, 1e-3)
    assert len(calls) == 1, "encode path must read back exactly once per chunk"
    # ... and that single get carries the body plus the tiny header scalars
    assert isinstance(calls[0], tuple) and len(calls[0]) == 4
    assert buf == SZxCodec(backend="numpy").compress(x, 1e-3)


def test_chunked_encode_is_one_transfer_per_frame(monkeypatch):
    calls = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda v: calls.append(v) or real_get(v))
    x = _walk(300_000, seed=2)
    frames = list(SZxCodec(backend="jax").compress_chunked(x, 1e-3, chunk_bytes=1 << 19))
    per = plan.chunk_elements(128, 1 << 19, 4)
    nchunks = -(-x.size // per)
    assert len(frames) == nchunks
    assert len(calls) == nchunks, "one device_get per chunk, no more"


# ---------------------------------------------------------------------------
# the compaction primitive: equal to numpy boolean-mask compaction
# ---------------------------------------------------------------------------

def _mask(name, n, rng):
    if name == "all":
        return np.ones(n, bool)
    if name == "none":
        return np.zeros(n, bool)
    if name == "alternating":
        return np.arange(n) % 2 == 0
    if name == "tail":
        return (np.arange(n) >= n - 1000) & (rng.random(n) < 0.5)
    return rng.random(n) < float(name)


def _layouts(n):
    """One row, one lane, and a grid of both where ``n`` factors."""
    out = [(1, n), (n, 1)]
    d = max(d for d in range(1, int(n**0.5) + 1) if n % d == 0)
    if d > 1:
        out.append((d, n // d))
    return out


@pytest.mark.parametrize("n,mask", [
    (n, mask)
    for n in (1, 2, 255, 256, 257, 5000)
    for mask in ("all", "none", "alternating", "0.05", "0.5", "0.95")
] + [(2**24 + 1024, "tail")])     # shifts past 2**24: byte and shift ride apart
def test_compact_matches_boolean_mask(n, mask):
    rng = np.random.default_rng(n)
    payload = rng.integers(0, 256, n, dtype=np.uint8)
    keep = _mask(mask, n, rng)
    # a kept element moves left by the number of dropped ones before it; the
    # shift of a dropped element is noise the primitive must ignore
    shift = np.where(keep, np.arange(n) - np.cumsum(keep) + 1,
                     rng.integers(0, n + 1, n)).astype(np.int32)
    want = np.zeros(n, np.uint8)
    want[: keep.sum()] = payload[keep]
    for shape in _layouts(n) if n < 5001 else [(n // 1024, 1024)]:
        got = device._compact(*(jnp.asarray(a.reshape(shape)) for a in (payload, shift, keep)))
        np.testing.assert_array_equal(np.asarray(got).reshape(-1), want, err_msg=str(shape))


# ---------------------------------------------------------------------------
# byte identity: device assembly == host serializer, every dtype x backend
# ---------------------------------------------------------------------------

def _mixed(dtype, bs):
    """Constant, verbatim and mid-heavy blocks in one frame, last block ragged."""
    rng = np.random.default_rng(bs)
    return np.concatenate([
        np.full(3 * bs, 2.5),
        _walk(2 * bs, seed=4, scale=0.01),
        rng.uniform(-1e4, 1e4, 2 * bs),
        np.full(bs + 3, -1.0),
        _walk(3 * bs - 5, seed=6, scale=1.0),
    ]).astype(dtype)


@pytest.mark.parametrize("case", ["walks", "bs7", "bs33", "mixed"])
@pytest.mark.parametrize("dtype", _DTYPES, ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("backend", ["jax", "kernel"])
def test_device_stream_bit_identical_to_host(dtype, backend, case):
    ref = SZxCodec(backend="numpy")
    dev = SZxCodec(backend=backend)
    if case == "walks":
        for n, bs, e in ((9999, 128, 1e-3), (257, 32, 1e-2), (1000, 128, 1.0)):
            x = _walk(n, seed=n, dtype=dtype)
            assert (
                SZxCodec(block_size=bs, backend=backend).compress(x, e)
                == SZxCodec(block_size=bs, backend="numpy").compress(x, e)
            ), (np.dtype(dtype).name, backend, n, bs, e)
        # constant + verbatim extremes
        c = np.full(1500, 2.5).astype(dtype)
        assert dev.compress(c, 1e-3) == ref.compress(c, 1e-3)
        tiny = float(plan.finfo(np.dtype(dtype)).tiny)
        v = _walk(2000, seed=3, dtype=dtype, scale=1.0)
        assert dev.compress(v, tiny) == ref.compress(v, tiny)
        return
    # odd block sizes: a block's 2-bit L codes share a byte with the next's
    bs = {"bs7": 7, "bs33": 33, "mixed": 128}[case]
    x = _mixed(dtype, bs) if case == "mixed" else _walk(20 * bs + 3, seed=bs, dtype=dtype)
    for e in (1e-3, 1e-1):
        assert (
            SZxCodec(block_size=bs, backend=backend).compress(x, e)
            == SZxCodec(block_size=bs, backend="numpy").compress(x, e)
        ), (np.dtype(dtype).name, backend, case, e)


def test_encode_device_host_mirror_matches_device_record():
    """encode_device on the numpy backend produces the same body bytes and
    scalars as the device route (the kept numpy mirror)."""
    x = _walk(20_000, seed=5)
    p, xt = plan.make_plan(x, 1e-3, backend="numpy")
    host = device.encode_device(plan.to_blocks(xt, p), p)
    pj, xtj = plan.make_plan(x, 1e-3, backend="jax")
    dev = device.encode_device(plan.to_blocks(xtj, pj), pj)
    h = jax.device_get((host["body"], host["total"], host["nnc"], host["nmid"]))
    d = jax.device_get((dev["body"], dev["total"], dev["nnc"], dev["nmid"]))
    assert int(h[1]) == int(d[1]) and int(h[2]) == int(d[2]) and int(h[3]) == int(d[3])
    np.testing.assert_array_equal(h[0][: int(h[1])], d[0][: int(d[1])])
    assert device.to_stream(host) == device.to_stream(dev)


# ---------------------------------------------------------------------------
# DeviceEncoding: the shared record
# ---------------------------------------------------------------------------

def test_device_encoding_is_a_pytree():
    enc = DeviceEncoding.make(
        "szx-planes",
        {"mu": jnp.ones((4,)), "sexp": jnp.zeros((4,), jnp.int32),
         "planes": jnp.zeros((1, 4, 8), jnp.uint8)},
        num_planes=1,
    )
    leaves, treedef = jax.tree_util.tree_flatten(enc)
    assert len(leaves) == 3
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.kind == "szx-planes" and rebuilt.info == {"num_planes": 1}
    # tree.map preserves the record; replace() swaps arrays only
    doubled = jax.tree.map(lambda a: a * 2, enc)
    np.testing.assert_array_equal(np.asarray(doubled["mu"]), 2 * np.ones(4))
    swapped = enc.replace(mu=jnp.zeros((4,)))
    assert swapped.kind == enc.kind
    with pytest.raises(KeyError):
        enc.replace(nope=jnp.zeros(1))


def test_planes_codec_device_encoding_roundtrip():
    xb = np.random.default_rng(11).standard_normal((6, 64)).astype(np.float32)
    for p in (1, 2):
        codec = PlanesCodec(p)
        enc = codec.encode_blocks_device(jnp.asarray(xb))
        assert enc.kind == "szx-planes" and enc.info["num_planes"] == p
        mu, sexp, planes = codec.encode_blocks(jnp.asarray(xb))
        np.testing.assert_array_equal(np.asarray(enc["planes"]), np.asarray(planes))
        dec = np.asarray(codec.decode_encoding(enc))
        np.testing.assert_array_equal(
            dec, np.asarray(codec.decode_blocks(mu, sexp, planes))
        )
    with pytest.raises(ValueError):
        PlanesCodec(3).decode_encoding(enc)          # plane-count mismatch
    with pytest.raises(ValueError):
        PlanesCodec(1).decode_encoding(
            DeviceEncoding.make("szx-v2", {"mu": jnp.zeros(1)})
        )


def test_to_stream_rejects_non_stream_kinds():
    enc = DeviceEncoding.make("szx-planes", {"mu": jnp.zeros(1)})
    with pytest.raises(ValueError):
        device.to_stream(enc)
