"""SZx gradient compression for the slow (cross-pod) data-parallel axis.

The paper's pitch -- compression throughput above link bandwidth -- pays off
exactly where links are slowest: the DCN/inter-pod reduction.  We therefore
compress ONLY the 'pod'-axis all-reduce: within a pod, gradients reduce in
full precision via GSPMD; across pods we run a manual shard_map collective
(auto-GSPMD inside) that

  1. adds the error-feedback accumulator,
  2. szx-planes-encodes the sum (per-block mu + sexp + P uint8 planes),
  3. all_gathers the (~4x smaller at P=1) encoded payload over 'pod',
  4. decodes + means, and
  5. stores the local residual back into the accumulator.

Error feedback makes the scheme convergence-safe (the compression error is
re-applied next step instead of being lost).  Everything is fixed-shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.codec import PlanesCodec

DEFAULT_BLOCK = 64


def _record_wire(op: str, x, enc, members: int = 1) -> None:
    """Trace-time wire accounting: these collectives run inside jit/shard_map
    tracing, so this executes ONCE per compiled program -- counters record
    bytes-per-call of the traced shapes, not per executed step."""
    if not obs.enabled():
        return
    raw = int(x.size) * jnp.dtype(x.dtype).itemsize
    wire = sum(
        int(enc[k].size) * jnp.dtype(enc[k].dtype).itemsize
        for k in ("mu", "sexp", "planes")
    )
    obs.counter("collective.calls", op=op).inc()
    obs.counter("collective.raw_bytes", op=op).inc(raw * members)
    obs.counter("collective.wire_bytes", op=op).inc(wire * members)


def _encode_leaf(g, num_planes, block, backend="jax"):
    """Blocks run along the LAST axis, leading dims untouched.

    Flattening the leaf would destroy its TP/FSDP sharding and make GSPMD
    all-gather the full-precision gradient before encoding (measured +11 GB
    of intra-pod collectives per step on llama -- EXPERIMENTS section Perf);
    keeping the leaf shape keeps every encode op local to its shard.  The
    default 'jax' backend stages the whole encode into the caller's
    shard_map program (one fused program per leaf); 'kernel' dispatches the
    Pallas planes kernels instead.

    Returns the shared device-resident record (``DeviceEncoding``, kind
    'szx-planes') -- a registered pytree, so it flows through ``all_gather``
    and ``tree.map`` like the plain dict it replaced."""
    enc = PlanesCodec(num_planes, backend=backend).encode_last_axis_device(g, block)
    return enc.replace(sexp=enc["sexp"].astype(jnp.int16))  # wire: halve sexp bytes


def _decode_leaf(enc, shape, dtype, block, backend="jax"):
    return PlanesCodec(
        enc["planes"].shape[0], backend=backend
    ).decode_last_axis_encoding(enc, shape, dtype)


def compressed_psum_mean(grads, axis_name: str, *, num_planes: int = 1,
                         block: int = DEFAULT_BLOCK, backend: str = "jax"):
    """Inside shard_map: compressed all-reduce-mean over `axis_name`.

    Returns the mean of the decoded per-member gradients plus this member's
    compression residual (for error feedback)."""
    n = jax.lax.axis_size(axis_name)

    def leaf(g):
        enc = _encode_leaf(g, num_planes, block, backend)
        _record_wire("psum_mean", g, enc, members=n)
        dec_local = _decode_leaf(enc, g.shape, jnp.float32, block, backend)
        residual = g.astype(jnp.float32) - dec_local
        gathered = jax.lax.all_gather(enc, axis_name)     # leading axis n
        total = jnp.zeros(g.shape, jnp.float32)
        for i in range(n):                                # n == 2 pods: unrolled
            member = jax.tree.map(lambda a: a[i], gathered)
            total = total + _decode_leaf(member, g.shape, jnp.float32, block, backend)
        return (total / n).astype(g.dtype), residual

    pairs = jax.tree.map(leaf, grads)
    mean = jax.tree.map(lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    resid = jax.tree.map(lambda pr: pr[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return mean, resid


def compressed_ppermute(x, axis_name: str, perm, *, num_planes: int = 1,
                        block: int = DEFAULT_BLOCK, backend: str = "jax"):
    """Inside shard_map: szx-planes-compressed ``jax.lax.ppermute``.

    Encodes ``x`` along its last axis, permutes the (~4x smaller at P=1)
    encoding arrays over ``axis_name``, and decodes on the receiving member.
    The point-to-point activation shift of pipeline parallelism
    (``pipeline_par.gpipe``) is the intended caller: the wire moves
    ``wire_bytes_per_value`` bytes/value instead of 4.0.
    """
    enc = _encode_leaf(x, num_planes, block, backend)
    _record_wire("ppermute", x, enc)
    moved = jax.tree.map(
        lambda a: jax.lax.ppermute(a, axis_name, perm), enc
    )
    return _decode_leaf(moved, x.shape, x.dtype, block, backend)


def compressed_all_to_all(x, axis_name: str, split_axis: int, concat_axis: int,
                          *, num_planes: int = 1, block: int = DEFAULT_BLOCK,
                          backend: str = "jax"):
    """Inside shard_map: szx-planes-compressed ``jax.lax.all_to_all``.

    Encodes along the LAST axis (which becomes the block grid and must not
    be the split/concat axis), moves each encoding array with a tiled
    ``all_to_all`` -- the ``planes`` array's leading plane axis shifts the
    operand axes by one -- and decodes to the post-exchange shape.
    """
    if x.ndim < 2:
        raise ValueError("compressed_all_to_all needs >= 2 dims (last = blocks)")
    split_axis, concat_axis = split_axis % x.ndim, concat_axis % x.ndim
    if x.ndim - 1 in (split_axis, concat_axis):
        raise ValueError(
            "compressed_all_to_all cannot split/concat the blocked last axis"
        )
    n = jax.lax.axis_size(axis_name)
    enc = _encode_leaf(x, num_planes, block, backend)
    _record_wire("all_to_all", x, enc)

    def move(a, lead):
        return jax.lax.all_to_all(
            a, axis_name, split_axis + lead, concat_axis + lead, tiled=True
        )

    moved = enc.replace(
        mu=move(enc["mu"], 0),
        sexp=move(enc["sexp"], 0),
        planes=move(enc["planes"], 1),
    )
    shape = list(x.shape)
    shape[split_axis] //= n
    shape[concat_axis] *= n
    return _decode_leaf(moved, tuple(shape), x.dtype, block, backend)


def wire_bytes_per_value(num_planes: int, block: int = DEFAULT_BLOCK) -> float:
    """Bytes/gradient-value moved over the pod axis (vs 4.0 uncompressed)."""
    return PlanesCodec(num_planes).wire_bytes_per_value(block)
