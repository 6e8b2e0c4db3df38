"""Compile the codec's Pallas kernels natively for a TPU v5e -- no chip needed.

The TPU compiler ships with jaxlib's TPU support, so a described (not
attached) ``v5e:2x2`` topology lets these tests lower and compile every
main-path kernel at the full default-frame geometry with ``interpret=False``:
what Mosaic refuses here (unaligned blocks, unsupported casts or reshapes)
would be refused on the chip.  Each compiled program must contain the kernel
(``tpu_custom_call``), i.e. the 'kernel' route runs a real Pallas call.

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture (never at import), which skips where it cannot
be described.  Only the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.codec import device
from repro.kernels import bitshuffle as kbit
from repro.kernels import encode as kenc
from repro.kernels import ops, specs, tiles
from repro.kernels import planes as kplanes
from repro.kernels import unpack as kun

FULL_F32 = 131_072        # blocks of 128 in one 64 MiB float32 frame
FULL_16 = 262_144         # blocks of 128 in one 32 Mi-element 16-bit frame
TAIL_F32 = 64_241         # the Hurricane field's second float32 frame
F16_FRAME = 195_313       # the Hurricane field as one float16 frame


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_native(one_chip):
    """-> compile(fn, *(shape, dtype)) for the described chip, with the
    kernels forced native and the persistent compilation cache off (its
    entries could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    # code below asks jax.default_backend(), which is the CPU here
    mp.setattr(tiles, "interpret_default",
               lambda interpret: False if interpret is None else interpret)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()          # no trace made for the CPU may be reused

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "no Pallas kernel in the program"
        return text

    yield compile_
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    mp.undo()


SCALARS = ((), jnp.float32), ((), jnp.int32)


@pytest.mark.parametrize("spec,nb", [
    (specs.F32, FULL_F32), (specs.F32, TAIL_F32),
    (specs.BF16, FULL_16), (specs.F16, F16_FRAME),
], ids=["f32-full", "f32-tail", "bf16-full", "f16-frame"])
def test_encode_compiles(compile_native, spec, nb):
    compile_native(
        lambda x, e, pe: kenc.encode(x, e, pe, spec=spec),
        ((nb, 128), spec.np_dtype), *SCALARS,
    )


@pytest.mark.parametrize("spec,nb", [
    (specs.F32, FULL_F32), (specs.F32, TAIL_F32), (specs.BF16, FULL_16),
], ids=["f32-full", "f32-tail", "bf16-full"])
def test_encode_program_compiles_without_scatter(compile_native, spec, nb):
    """The whole encode program (kernel and body assembly) at frame size: the
    compacted sections are laid out by shifts and selects, never a scatter."""
    text = compile_native(
        lambda x, e, pe: device._encode_device_jit(x, e, pe, spec=spec, backend="kernel"),
        ((nb, 128), spec.np_dtype), ((), spec.compute_np_dtype), ((), jnp.int32),
    )
    assert "scatter(" not in text


def _body_bytes(spec, nb, bs=128):
    cap = (nb + 7) // 8 + spec.itemsize * nb + nb + (nb * bs + 3) // 4 \
        + nb * bs * spec.itemsize
    return min(cap, 1 << (cap // 2 - 1).bit_length())


@pytest.mark.parametrize("rb,rebase", [(FULL_F32, False), (1954, True)],
                         ids=["full-frame", "store-roi"])
def test_stream_decode_compiles(compile_native, rb, rebase):
    spec = specs.F32
    compile_native(
        lambda body, nnc, lo: ops.decode_staged(
            body, nnc, lo, spec=spec, nb=FULL_F32, bs=128, rb=rb,
            rebase=rebase, backend="kernel",
        ),
        ((_body_bytes(spec, FULL_F32),), jnp.uint8), ((), jnp.int32), ((), jnp.int32),
    )


@pytest.mark.parametrize("dense", [False, True], ids=["sparse", "dense"])
def test_unpack_compiles(compile_native, dense):
    spec, nb = specs.F32, FULL_F32
    meta = [((nb, 4, 128), jnp.uint8), ((nb,), jnp.float32), ((nb,), jnp.int32),
            ((nb,), jnp.int32)]
    if dense:
        compile_native(lambda *a: kun.unpack_dense(*a, spec=spec), *meta)
    else:
        compile_native(lambda *a: kun.unpack(*a, spec=spec), *meta,
                       ((nb, 128), jnp.int32))


@pytest.mark.parametrize("num_planes", [1, 2])
def test_planes_compile(compile_native, num_planes):
    nb = 2048 * 8192 // 64                 # a d_model x d_ff leaf, block 64
    compile_native(lambda x: kplanes.planes_encode(x, num_planes),
                   ((nb, 64), jnp.float32))
    compile_native(kplanes.planes_decode, ((nb,), jnp.float32), ((nb,), jnp.int32),
                   ((num_planes, nb, 64), jnp.uint8))


@pytest.mark.parametrize("inverse", [False, True], ids=["forward", "inverse"])
def test_bitshuffle_compiles(compile_native, inverse):
    spec = specs.F32
    compile_native(
        lambda t: kbit.bitshuffle(t, spec=spec, inverse=inverse),
        ((4099, kbit.tile_bytes(spec)), jnp.uint8),
    )
