"""Per-kernel validation: Pallas (interpret=True) vs. pure-jnp oracle vs. numpy
mirror, swept over shapes; plus bit-level invariants of the transform."""
import numpy as np
import pytest

from repro.core import planes as cplanes
from repro.kernels import ops

SHAPES = [(1, 128), (3, 64), (17, 128), (8, 256), (5, 32), (64, 128), (2, 8)]
BACKENDS = ["jax", "numpy", "kernel"]


def _mk(nb, bs, seed=0, scale=1.0, const_rows=()):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((nb, bs)) * scale).astype(np.float32)
    for r in const_rows:
        x[r % nb] = np.float32(1.2345)
    return x


@pytest.mark.parametrize("nb,bs", SHAPES)
def test_block_stats_backends_agree(nb, bs):
    x = _mk(nb, bs, const_rows=(1,))
    e = 1e-3 * float(x.max() - x.min())
    outs = {b: [np.asarray(a) for a in ops.block_stats(x, e, backend=b)] for b in BACKENDS}
    for b in BACKENDS[1:]:
        for a_ref, a_b in zip(outs["jax"], outs[b]):
            np.testing.assert_array_equal(a_ref, a_b, err_msg=f"backend={b}")


@pytest.mark.parametrize("nb,bs", SHAPES)
def test_pack_unpack_backends_agree_and_bounded(nb, bs):
    x = _mk(nb, bs, seed=nb * 1000 + bs, const_rows=(0,))
    e = 1e-4 * float(np.abs(x).max() + 1.0)
    mu, rad, const, reqlen, shift, nbytes = [
        np.asarray(a) for a in ops.block_stats(x, e, backend="jax")
    ]
    packs = {b: [np.asarray(a) for a in ops.pack(x, mu, shift, nbytes, backend=b)] for b in BACKENDS}
    for b in BACKENDS[1:]:
        for a_ref, a_b in zip(packs["jax"], packs[b]):
            np.testing.assert_array_equal(a_ref, a_b, err_msg=f"pack backend={b}")
    planes, L, mid = packs["jax"]
    ups = {
        b: np.asarray(ops.unpack(planes, mu, shift, nbytes, L, backend=b))
        for b in BACKENDS
    }
    for b in BACKENDS[1:]:
        np.testing.assert_array_equal(ups["jax"], ups[b], err_msg=f"unpack backend={b}")
    assert np.abs(ups["jax"] - x).max() <= e


def test_bitlevel_invariants():
    """Solution C: stored window is byte-aligned; L is capped; mid >= 0."""
    x = _mk(32, 128, seed=7)
    e = 1e-3
    mu, rad, const, reqlen, shift, nbytes = [
        np.asarray(a) for a in ops.block_stats(x, e, backend="jax")
    ]
    nc = ~const
    assert np.all((reqlen[nc] + shift[nc]) % 8 == 0)          # Formula (5)
    assert np.all((nbytes[nc] >= 2) & (nbytes[nc] <= 4))
    assert np.all(reqlen[nc] >= 9)
    planes, L, mid = [np.asarray(a) for a in ops.pack(x, mu, shift, nbytes, backend="jax")]
    assert L.min() >= 0 and L.max() <= 3
    assert np.all(mid >= 0)
    assert np.all(L <= nbytes[:, None])


def test_constant_block_roundtrip_is_mu():
    x = np.full((4, 128), 42.5, np.float32)
    e = 1e-6
    mu, rad, const, reqlen, shift, nbytes = ops.block_stats(x, e, backend="jax")
    assert np.asarray(const).all()
    planes, L, mid = ops.pack(x, np.asarray(mu), np.asarray(shift), np.asarray(nbytes), backend="jax")
    y = np.asarray(ops.unpack(planes, mu, shift, nbytes, L, backend="jax"))
    np.testing.assert_array_equal(y, np.asarray(mu)[:, None] * np.ones((1, 128), np.float32))


@pytest.mark.parametrize("num_planes", [1, 2, 3])
@pytest.mark.parametrize("n", [16, 128, 1000, 4096])
def test_planes_mode_bound(num_planes, n):
    rng = np.random.default_rng(num_planes * 100 + n)
    x = rng.standard_normal(n).astype(np.float32) * 3.0
    enc = cplanes.encode(x, num_planes=num_planes)
    y = np.asarray(cplanes.decode(enc, shape=(n,)))
    bound = float(np.asarray(cplanes.max_block_error_bound(enc)).max())
    assert np.abs(x - y).max() <= bound
    # wire accounting: padded-block planes + 8B/block of mu+sexp
    nb = (n + 127) // 128
    assert cplanes.wire_bytes(enc) == nb * 128 * num_planes + 8 * nb


@pytest.mark.parametrize("special", ["negzero", "tiny", "mixed_sign", "large"])
def test_special_values(special):
    if special == "negzero":
        x = np.zeros((2, 128), np.float32)
        x[0, ::2] = -0.0
    elif special == "tiny":
        x = (np.random.default_rng(0).standard_normal((2, 128)) * 1e-30).astype(np.float32)
    elif special == "mixed_sign":
        x = np.linspace(-1, 1, 256, dtype=np.float32).reshape(2, 128)
    else:
        x = (np.random.default_rng(1).standard_normal((2, 128)) * 1e30).astype(np.float32)
    e = max(1e-9, 1e-4 * float(np.abs(x).max() + 1e-30))
    mu, rad, const, reqlen, shift, nbytes = [
        np.asarray(a) for a in ops.block_stats(x, e, backend="jax")
    ]
    planes, L, mid = ops.pack(x, mu, shift, nbytes, backend="jax")
    y = np.asarray(ops.unpack(planes, mu, shift, nbytes, L, backend="jax"))
    assert np.abs(y - x).max() <= e


@pytest.mark.parametrize("name", ["float16", "bfloat16"])
def test_tile_16bit_conversions_match_numpy(name):
    """The kernels hold 16-bit floats as integer bits (Mosaic has no f16
    vectors): widening every bit pattern and rounding f32 values around
    every 16-bit rounding boundary must match numpy bit for bit."""
    import jax.numpy as jnp

    from repro.kernels import specs, tiles

    spec = specs.BF16 if name == "bfloat16" else specs.F16
    bits = np.arange(1 << 16, dtype=np.uint32)
    vals = bits.astype(np.uint16).view(spec.np_dtype)
    finite = np.isfinite(vals.astype(np.float32))
    wide = np.asarray(tiles.to_compute(spec, jnp.asarray(bits)))
    np.testing.assert_array_equal(
        wide[finite].view(np.uint32), vals[finite].astype(np.float32).view(np.uint32)
    )
    # f32 inputs: every finite 16-bit value, its f32 neighbours, the exact
    # midpoints to the next value (ties to even), plus random f32 bits
    v32 = vals[finite].astype(np.float32)
    with np.errstate(over="ignore"):     # the largest value steps to inf
        nxt = np.nextafter(vals[finite], np.asarray(np.inf, spec.np_dtype))
    nxt = nxt.astype(np.float32)
    mid = ((v32.astype(np.float64) + nxt.astype(np.float64)) / 2).astype(np.float32)
    rnd = np.random.default_rng(0).integers(0, 1 << 32, 1 << 16, dtype=np.uint64)
    x = np.concatenate([
        v32, np.nextafter(v32, np.float32(np.inf)), np.nextafter(v32, np.float32(-np.inf)),
        mid, rnd.astype(np.uint32).view(np.float32),
    ])
    x = x[np.isfinite(x)]
    got = np.asarray(tiles.to_storage_bits(spec, jnp.asarray(x))).astype(np.uint16)
    with np.errstate(over="ignore"):     # out-of-range values round to inf
        want = x.astype(spec.np_dtype).view(np.uint16)
    np.testing.assert_array_equal(got, want)
