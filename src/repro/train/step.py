"""Training step factory: plain GSPMD step, or hierarchical step with SZx
gradient compression on the cross-pod reduction.

Plain: one jit; DP/TP/EP/FSDP all via GSPMD from the param/batch shardings.

Compressed: ``jax.shard_map`` manual over 'pod' (auto over 'data'/'model'),
per-pod grads + error feedback -> szx-planes encode -> all_gather('pod') of
the ~4x-smaller payload -> decode+mean -> optimizer.  See
repro.core.grad_compress and DESIGN.md section 3.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import grad_compress
from repro.models import transformer as T
from repro.optim.adamw import AdamW


def init_state(cfg: ArchConfig, opt: AdamW, key, *, ef_planes: int = 0) -> dict:
    params = T.init_params(cfg, key)
    state = {"params": params, "opt": opt.init(params)}
    if ef_planes:
        state["ef"] = jax.tree.map(
            lambda p: jnp.zeros((2,) + p.shape, jnp.bfloat16), params
        )
    return state


def state_specs(cfg: ArchConfig, state_tree, mesh):
    """PartitionSpec pytree for a train state (params/opt share param specs)."""
    from repro.launch.mesh import param_specs_tree

    pspecs = param_specs_tree(cfg, state_tree["params"], mesh)
    out = {
        "params": pspecs,
        "opt": type(state_tree["opt"])(
            step=P(),
            m=param_specs_tree(cfg, state_tree["opt"].m, mesh),
            v=param_specs_tree(cfg, state_tree["opt"].v, mesh),
        ),
    }
    if "ef" in state_tree:
        out["ef"] = jax.tree.map(lambda s: P("pod", *s), pspecs)
    return out


def make_train_step(cfg: ArchConfig, opt: AdamW, *, mesh=None, compress_planes: int = 0):
    loss_of = lambda p, b: T.loss_fn(p, cfg, b)  # noqa: E731

    if not compress_planes:

        def train_step(state, batch):
            loss, grads = jax.value_and_grad(loss_of)(state["params"], batch)
            params, opt_state, metrics = opt.update(grads, state["opt"], state["params"])
            return {"params": params, "opt": opt_state}, {"loss": loss, **metrics}

        return train_step

    assert mesh is not None and "pod" in mesh.axis_names
    # only 'pod' is manual; 'data'/'model' stay automatic so GSPMD keeps the
    # intra-pod DP/TP shardings

    def per_pod(params, ef, batch):
        ef = jax.tree.map(lambda e: e[0], ef)            # strip sharded pod dim
        loss, grads = jax.value_and_grad(loss_of)(params, batch)
        g_eff = jax.tree.map(
            lambda g, e: g.astype(jnp.float32) + e.astype(jnp.float32), grads, ef
        )
        mean, resid = grad_compress.compressed_psum_mean(
            g_eff, "pod", num_planes=compress_planes
        )
        loss = jax.lax.pmean(loss, "pod")
        resid = jax.tree.map(lambda r: r.astype(jnp.bfloat16)[None], resid)
        return loss, mean, resid

    inner = jax.shard_map(
        per_pod,
        mesh=mesh,
        axis_names={"pod"},
        in_specs=(P(), P("pod"), P("pod")),
        out_specs=(P(), P(), P("pod")),
        check_vma=False,
    )

    def train_step(state, batch):
        loss, grads, ef = inner(state["params"], state["ef"], batch)
        params, opt_state, metrics = opt.update(grads, state["opt"], state["params"])
        return (
            {"params": params, "opt": opt_state, "ef": ef},
            {"loss": loss, **metrics},
        )

    return train_step
