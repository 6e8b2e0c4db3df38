"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

shard_map manual over 'stage'; microbatches stream through stages via
``jax.lax.ppermute``.  The schedule runs (n_micro + n_stages - 1) ticks; each
tick every stage processes one microbatch (bubble at the edges, the classic
GPipe cost).  Stage-local layer stacks are plain scans, so this composes with
the TP/DP shardings of the stage-interior (auto axes).

This is the optional PP axis (DESIGN.md section 6): the production dry-run
grid uses DP x TP x EP x FSDP x SP, and PP is validated separately by
tests/test_pipeline.py on an 8-device host mesh.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs


def pipeline_apply(
    stage_fn: Callable,        # (stage_params, x) -> y   (per-stage compute)
    mesh,
    stage_axis: str = "stage",
    *,
    compress_activations: bool = False,
    num_planes: int = 1,
    compress_block: int = 64,
    compress_backend: str = "jax",
):
    """Returns fn(stacked_stage_params, microbatches) -> outputs.

    stacked_stage_params: pytree with leading [n_stages] dim (stage-sharded).
    microbatches: (n_micro, mb, ...) input microbatches.
    Output: (n_micro, mb, ...) as produced by the LAST stage.

    ``compress_activations=True`` routes the per-tick activation shift
    through ``grad_compress.compressed_ppermute``: each stage szx-planes
    encodes its output, permutes the encoding arrays (~4x fewer wire bytes
    at P=1), and the next stage decodes -- the paper's
    faster-than-the-link compression applied to pipeline traffic.  Lossy
    (bounded by the planes budget); leave off for exact schedules.
    """
    n_stages = mesh.shape[stage_axis]

    def run(params, xs):
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        if obs.enabled():
            # trace-time accounting (scan body runs once per trace): bytes a
            # stage shifts per tick, raw vs on-the-wire when compressed
            mb = xs[0]
            raw = int(mb.size) * jnp.dtype(mb.dtype).itemsize
            wire = raw
            if compress_activations:
                from repro.core import grad_compress

                wire = int(
                    mb.size * grad_compress.wire_bytes_per_value(
                        num_planes, compress_block
                    )
                )
            obs.counter("pipeline.programs").inc()
            obs.gauge("pipeline.ticks").set(ticks)
            obs.gauge("pipeline.tick_raw_bytes").set(raw)
            obs.gauge("pipeline.tick_wire_bytes").set(wire)

        def body(carry, t):
            buf, outs = carry          # buf: (1, mb, ...) current stage input
            stage = jax.lax.axis_index(stage_axis)
            # stage 0 injects microbatch t (or zeros past the end)
            inject = jnp.where(
                t < n_micro,
                jax.lax.dynamic_index_in_dim(
                    xs, jnp.minimum(t, n_micro - 1), 0, keepdims=False
                ),
                jnp.zeros_like(buf[0]),
            )
            x = jnp.where(stage == 0, inject, buf[0])
            y = stage_fn(jax.tree.map(lambda p: p[0], params), x)
            # last stage emits its result for microbatch (t - n_stages + 1)
            emit_idx = t - (n_stages - 1)
            outs = jax.lax.cond(
                (stage == n_stages - 1) & (emit_idx >= 0),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(emit_idx, 0), 0
                ),
                lambda o: o,
                outs,
            )
            # shift activations to the next stage
            ring = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            if compress_activations:
                from repro.core import grad_compress

                nxt = grad_compress.compressed_ppermute(
                    y, stage_axis, ring, num_planes=num_planes,
                    block=compress_block, backend=compress_backend,
                )
            else:
                nxt = jax.lax.ppermute(y, stage_axis, ring)
            return (nxt[None], outs), None

        buf0 = jnp.zeros_like(xs[:1])
        outs0 = jnp.zeros_like(xs)
        (buf, outs), _ = jax.lax.scan(body, (buf0, outs0), jnp.arange(ticks))
        # only the last stage holds real outputs (zeros elsewhere): psum
        # broadcasts them so the P() out_spec is truthful
        return jax.lax.psum(outs, stage_axis)

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        axis_names={stage_axis},
        check_vma=False,
    )
