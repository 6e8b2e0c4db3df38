#!/usr/bin/env python3
"""Chip smoke test: the SZx codec's device path, end to end, on a TPU.

    python chip_smoke.py                # one chip: codec + store + f64 rule
    python chip_smoke.py --four-chips   # four chips: gradient exchange and
                                        # sharded checkpoint, nothing else

One chip: an SDRBench Hurricane field at its published shape (100x500x500
float32, sdrbench.github.io) is generated from ``--seed`` and round-tripped
through ``SZxCodec(backend="auto").compress_chunked`` / ``decompress_chunked``
at ``Bound.rel(1e-3)`` in float32, bfloat16 and float16.  Each dtype must keep
every value within the resolved absolute bound, produce frames byte-identical
to ``SZxCodec(backend="numpy")``, and show one device transfer per frame in
each direction (a silent host fallback fails the run).  A ~1% ROI read from
``ArrayStore.open(device=True)`` must equal the host decode, and float64 must
follow its documented dispatch rule (host mirror under 'auto', a clear error
for an explicit device backend).

Four chips: ``grad_compress.compressed_psum_mean`` over one Llama-3.2-1B
layer's gradient shapes on a ("data",) mesh against ``lax.pmean``, and
``TreeCodec.compress_tree_sharded`` of that tree against ``compress_tree``.

The run fails (nonzero exit, no result line) without a TPU, and any failed
check fails it.  Lines marked ``smoke-timing`` include host<->device transfer
and compilation effects; they are information, not benchmark numbers.  The
last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro import compile_cache, obs  # noqa: E402

HURRICANE_SHAPE = (100, 500, 500)      # SDRBench Hurricane ISABEL, published
REL = 1e-3


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(*parts) -> None:
    print(*parts, flush=True)


def device_check(count: int) -> dict:
    """Fail unless JAX sees ``count`` TPU devices; returns the device record."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu", f"no TPU: JAX found {dev['platform']!r} devices")
    check(dev["count"] >= count, f"need {count} TPU devices, found {dev['count']}")
    say(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    return dev


def hurricane(seed: int) -> np.ndarray:
    """Hurricane field at the published shape from the repo's generator."""
    from repro.data import scidata

    app = scidata.APPLICATIONS["Hurricane"]
    rng = np.random.default_rng(seed)
    return scidata._smooth_field(
        rng, HURRICANE_SHAPE, app["octaves"], app["rough"], app["spikes"]
    )


def _counter(name: str, op: str) -> int:
    return int(obs.counter(name, op=op).value)


def roundtrip(x: np.ndarray, name: str) -> None:
    """compress_chunked / decompress_chunked on 'auto' vs the numpy mirror."""
    from repro.api import Bound
    from repro.core.codec import SZxCodec, plan as plan_mod

    spec = plan_mod.spec_for(x.dtype)
    bound = Bound.rel(REL)
    e = plan_mod.resolve_error_bound(x, bound, spec=spec)
    dev, host = SZxCodec(backend="auto"), SZxCodec(backend="numpy")

    def run():
        obs.reset()
        t0 = time.perf_counter()
        frames = list(dev.compress_chunked(x, bound))
        t1 = time.perf_counter()
        y = dev.decompress_chunked(frames, n=x.size)
        t2 = time.perf_counter()
        return frames, y, t1 - t0, t2 - t1

    frames, y, c_cold, d_cold = run()
    frames, y, c_warm, d_warm = run()       # counters cover this run only
    nframes = len(frames)
    gets = _counter("device.get.calls", "encode_stream")
    puts = _counter("device.put.calls", "decode_stream")
    ref = list(host.compress_chunked(x, bound))
    stored = sum(len(f) for f in frames)
    err = float(np.abs(x.reshape(-1).astype(np.float64) - y.astype(np.float64)).max())
    say(f"roundtrip {name}: frames={nframes} CR={x.nbytes / stored:.4f} "
        f"max_err={err!r} bound={e!r}")
    check(y.dtype == x.dtype and y.shape == (x.size,), f"{name}: bad output")
    check(err <= e, f"{name}: max error {err} exceeds bound {e}")
    check(frames == ref, f"{name}: device frames differ from the numpy mirror")
    say(f"bytes {name}: {stored} framed bytes identical to backend=numpy")
    check(gets == nframes and puts == nframes,
          f"{name}: {gets} encode gets / {puts} decode puts for {nframes} frames "
          "(a frame took the host path)")
    say(f"transfers {name}: encode_stream gets={gets} decode_stream puts={puts} "
        f"frames={nframes}")
    gb = x.nbytes / 1e9
    say(f"smoke-timing {name} (includes host<->device transfer; not a "
        f"benchmark): compile_s={c_cold + d_cold - c_warm - d_warm:.2f} "
        f"compress_GBps={gb / c_warm:.3f} decompress_GBps={gb / d_warm:.3f}")


def abs_bound(x: np.ndarray) -> float:
    from repro.api import Bound
    from repro.core.codec import plan as plan_mod

    return plan_mod.resolve_error_bound(x, Bound.rel(REL))


def store_roi(x: np.ndarray) -> None:
    """Device ROI read (decode_range, rebase=True) vs the host decode."""
    from repro.api import ArrayStore, Bound

    with tempfile.TemporaryDirectory() as tmp:
        path = str(Path(tmp) / "hurricane.szs")
        ArrayStore.save(path, x, Bound.rel(REL))
        k = x.shape[0] // 2
        roi = (slice(k, k + 1), slice(None), slice(None))     # 1% of the field
        obs.reset()
        got = ArrayStore.open(path, backend="auto", device=True)[roi]
        puts = _counter("device.put.calls", "decode_range")
        want = ArrayStore.open(path, backend="numpy")[roi]
    e = abs_bound(x)
    err = float(np.abs(got.astype(np.float64) - x[roi]).max())
    check(puts > 0, "store ROI read never reached the device decode")
    check(np.array_equal(got, want), "device ROI read differs from host decode")
    check(err <= e, f"store ROI error {err} exceeds bound {e}")
    say(f"store: roi [{k}:{k + 1},:,:] {got.size} values equal host decode "
        f"(decode_range puts={puts}, max_err={err!r} bound={e!r})")


def f64_rule(x: np.ndarray) -> None:
    """float64: 'auto' is the host mirror on TPU; device backends refuse."""
    from repro.api import Bound
    from repro.core.codec import SZxCodec
    from repro.kernels import ops, specs

    backend = ops._resolve("auto", specs.F64)
    check(backend == "numpy", f"f64 'auto' resolved to {backend!r}")
    x64 = x[:10].astype(np.float64)
    codec = SZxCodec(backend="auto")
    y = codec.decompress(codec.compress(x64, Bound.rel(REL)))
    e = abs_bound(x64)
    check(float(np.abs(y - x64.reshape(-1)).max()) <= e, "f64 bound violated")
    for b in ("jax", "kernel"):
        try:
            SZxCodec(backend=b).compress(x64, Bound.rel(REL))
        except ValueError as exc:
            check("no" in str(exc) and "TPU" in str(exc), f"unclear error: {exc}")
        else:
            raise SmokeFailure(f"f64 backend={b} did not raise on TPU")
    say(f"f64 backend=numpy (host); explicit jax/kernel raise ValueError; "
        f"{x64.size} values within bound")


def one_chip(seed: int) -> None:
    from repro.kernels import ops, specs

    resolved = {s.name: ops._resolve("auto", s) for s in specs.SPECS}
    say("backend: " + " ".join(f"{k}={v}" for k, v in resolved.items()))
    for name in ("float32", "bfloat16", "float16"):
        check(resolved[name] == "kernel",
              f"{name} resolves to {resolved[name]!r}, not 'kernel' "
              "(is SZX_OPS_BACKEND set?)")
    t0 = time.perf_counter()
    x = hurricane(seed)
    say(f"data: Hurricane {'x'.join(map(str, x.shape))} float32 "
        f"({x.nbytes / 2**20:.1f} MiB) seed={seed} "
        f"generated in {time.perf_counter() - t0:.1f}s")
    import ml_dtypes

    for dtype in (np.float32, ml_dtypes.bfloat16, np.float16):
        roundtrip(x.astype(dtype), np.dtype(dtype).name)
    store_roi(x)
    f64_rule(x)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def llama_layer_shapes() -> dict:
    """One Llama-3.2-1B layer's parameter (= gradient) shapes, full width."""
    import dataclasses

    import jax

    from repro import configs
    from repro.models import transformer as T

    cfg = dataclasses.replace(configs.get("llama3.2-1b"), n_layers=1)
    shapes = jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0))
    return jax.tree.map(lambda s: s.shape[1:], shapes["layers"])


def _planes_bound(g: np.ndarray, num_planes: int, block: int) -> np.ndarray:
    """Per-block error bound of szx-planes: ``core.planes.max_block_error_bound``
    doubled, to cover the positive-side clamp that bound excludes."""
    from repro.kernels import ops

    _mu, sexp, _pl = ops.planes_encode(g.reshape(-1, block), num_planes,
                                       backend="numpy")
    E = (8 * num_planes - 2) - sexp.astype(np.float64)
    slack = 1.0 + 2.0 ** (8 * num_planes - 23)
    return 2.0 * slack * np.exp2(E + 1 - 8 * num_planes)


def four_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import Bound
    from repro.core import grad_compress
    from repro.core.codec import SZxCodec
    from repro.core.codec.tree import TreeCodec
    from repro.launch.mesh import make_mesh

    n = 4
    mesh = make_mesh((n,), ("data",), devices=jax.devices()[:n])
    shapes = llama_layer_shapes()
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    spread = NamedSharding(mesh, P("data"))
    grads = jax.tree.unflatten(treedef, [
        jax.jit(lambda k, s=s: 1e-2 * jax.random.normal(k, (n,) + s, jnp.float32),
                out_shardings=spread)(k)
        for k, s in zip(keys, leaves)
    ])
    say(f"grads: one llama3.2-1b layer, {len(leaves)} leaves, "
        f"{sum(int(np.prod(s)) for s in leaves)} values per member, {n} members")

    def devices_of(a) -> set:
        return {s.device for s in a.addressable_shards}

    for a in jax.tree.leaves(grads):
        check(len(devices_of(a)) == n, "an input leaf is not spread over 4 devices")
    host = jax.tree.map(np.asarray, grads)
    block = grad_compress.DEFAULT_BLOCK
    for planes in (1, 2):
        def body(g, planes=planes):
            g = jax.tree.map(lambda a: a[0], g)
            mean, _resid = grad_compress.compressed_psum_mean(
                g, "data", num_planes=planes)
            exact = jax.tree.map(lambda a: jax.lax.pmean(a, "data"), g)
            return jax.tree.map(lambda a: a[None], (mean, exact))

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data"), check_vma=False))
        mean, exact = fn(grads)
        worst = 0.0
        for name, m, x, h in zip(
            [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(
                mean)],
            jax.tree.leaves(mean), jax.tree.leaves(exact), jax.tree.leaves(host),
        ):
            check(len(devices_of(m)) == n and len(devices_of(x)) == n,
                  f"{name}: output shards not on {n} distinct devices")
            m, x = np.asarray(m), np.asarray(x)
            check(all(np.array_equal(m[0], m[i]) for i in range(1, n)),
                  f"{name}: members disagree on the compressed mean")
            bound = np.mean(
                [_planes_bound(h[i], planes, block) for i in range(n)], axis=0
            ).max() + 4 * np.spacing(np.float32(np.abs(h).max()))
            err = float(np.abs(m[0] - x[0]).max())
            check(err <= bound, f"{name} P={planes}: |mean - pmean| {err} > {bound}")
            worst = max(worst, err / bound)
        say(f"grad exchange P={planes}: compressed_psum_mean vs pmean within the "
            f"planes bound on every leaf (worst err/bound={worst:.4f}); "
            f"inputs and outputs on {n} distinct devices")

    tree = jax.tree.map(lambda a: a[0], host)
    tc = TreeCodec(codec=SZxCodec(backend="auto"), bound=Bound.rel(REL))
    plain, sharded = io.BytesIO(), io.BytesIO()
    tc.compress_tree(tree, plain)
    obs.reset()
    tc.compress_tree_sharded(tree, sharded, mesh, axis="data")
    gets = _counter("device.get.calls", "encode_stream")
    a = tc.decompress_tree(io.BytesIO(plain.getvalue()))
    b = tc.decompress_tree(io.BytesIO(sharded.getvalue()))
    for (name, la), lb, lt in zip(jax.tree_util.tree_leaves_with_path(a),
                                  jax.tree.leaves(b), jax.tree.leaves(tree)):
        name = jax.tree_util.keystr(name)
        check(np.array_equal(la, lb), f"{name}: sharded checkpoint != compress_tree")
        e = abs_bound(lt)
        check(float(np.abs(lb - lt).max()) <= e, f"{name}: checkpoint bound")
    say(f"sharded checkpoint: {len(jax.tree.leaves(tree))} leaves decode equal to "
        f"compress_tree and within bound; {gets} device shard encodes, "
        f"{len(sharded.getvalue())} bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip gradient exchange and "
                         "sharded checkpoint")
    args = ap.parse_args(argv)
    cache_dir = compile_cache.enable()
    dev = device_check(4 if args.four_chips else 1)
    obs.enable()
    if args.four_chips:
        four_chip(args.seed)
    else:
        one_chip(args.seed)
    st = compile_cache.stats()
    say(f"compile cache: dir={cache_dir} hits={st['hits']} misses={st['misses']} "
        f"hit={'yes' if st['hits'] else 'no'}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
