"""Production mesh + partition-spec rules for parameters, batches and caches.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16, 16) = 256 chips ('data', 'model').
Multi-pod: (2, 16, 16) = 512 chips ('pod', 'data', 'model') -- the 'pod' axis
is the slow (DCN / inter-pod ICI) dimension and is where SZx gradient
compression applies (DESIGN.md section 3)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``, so GSPMD propagates
    shardings and ``with_sharding_constraint`` may name any axis (it accepts
    only ``Auto`` axes, and ``jax.make_mesh`` defaults to ``Explicit``)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# parameter partition specs (Megatron TP + optional FSDP over 'data')
# ---------------------------------------------------------------------------

def _param_rule(path: tuple[str, ...], ndim: int, cfg: ArchConfig):
    name = path[-1]
    stacked = "layers" in path            # leading L axis from the layer stack
    fsdp = "data" if cfg.fsdp else None
    lead = (None,) if stacked else ()

    if name in ("ln1", "ln2", "ln_cross", "final_ln", "norm", "dt_bias", "A_log", "D"):
        return P(*lead, *((None,) * (ndim - len(lead))))
    if name == "embed":
        return P("model", fsdp)                           # vocab x d_model
    if name == "lm_head":
        return P(fsdp, "model")                           # d_model x vocab
    if name == "frontend_proj":
        return P(fsdp, "model")
    if name in ("wq", "wk", "wv", "wi", "in", "router", "shared_wi"):
        return P(*lead, fsdp, "model")                    # column parallel
    if name in ("wo", "out", "shared_wo"):
        return P(*lead, "model", fsdp)                    # row parallel
    if name == "conv":
        return P(*lead, None, "model")                    # depthwise channels
    raise ValueError(f"no partition rule for param {'/'.join(path)}")


def _moe_rule(path, ndim, cfg):
    name = path[-1]
    fsdp = "data" if cfg.fsdp else None
    if name == "wi":
        return P(None, "model", fsdp, None)               # (L, E, D, 2F): EP
    if name == "wo":
        return P(None, "model", None, fsdp)               # (L, E, F, D): EP
    return None


def _tree_paths(tree):
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: (tuple(getattr(k, "key", str(k)) for k in kp), x), tree
    )


def _sanitize(spec: P, shape, mesh) -> P:
    """Drop mesh axes whose size doesn't divide the dim (e.g. hymba's SSM
    in-proj Z = 2*di + 2*N + H = 6482 on a 16-way 'model' axis); jit input
    shardings must divide evenly."""
    if mesh is None:
        return spec
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        total = 1
        for a in axes:
            total *= sizes.get(a, 1)
        out.append(ax if dim % total == 0 else None)
    return P(*out)


def replicated_specs_tree(params_tree):
    """All-replicated specs (pure-DP profile for small models)."""
    return jax.tree.map(lambda leaf: P(*((None,) * leaf.ndim)), params_tree)


def serve_param_specs_tree(cfg: ArchConfig, params_tree, mesh=None):
    """Decode-oriented weight layout (section Perf hillclimb H1).

    FSDP weight-gathers are catastrophic at decode (one all-gather of the
    full layer weights per token), so: no fsdp on dense/attention weights
    (they are small), and MoE experts sharded over BOTH axes -- E over
    'data', per-expert F over 'model' -- so the big expert tensors stay fully
    sharded without any per-step weight collective (dispatch moves MB-scale
    activations instead)."""
    import dataclasses as _dc

    cfg_noshard = _dc.replace(cfg, fsdp=False)

    def rule(kp, leaf):
        path = tuple(getattr(k, "key", str(k)) for k in kp)
        if "moe" in path and path[-1] == "wi":
            return _sanitize(P(None, "data", None, "model"), leaf.shape, mesh)
        if "moe" in path and path[-1] == "wo":
            return _sanitize(P(None, "data", "model", None), leaf.shape, mesh)
        return _sanitize(_param_rule(path, leaf.ndim, cfg_noshard), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def param_specs_tree(cfg: ArchConfig, params_tree, mesh=None):
    """PartitionSpec pytree matching `params_tree` (params or eval_shape)."""

    def rule(kp, leaf):
        path = tuple(getattr(k, "key", str(k)) for k in kp)
        if "moe" in path and path[-1] in ("wi", "wo"):
            spec = _moe_rule(path, leaf.ndim, cfg)
            if spec is not None:
                return _sanitize(spec, leaf.shape, mesh)
        return _sanitize(_param_rule(path, leaf.ndim, cfg), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def param_shardings(cfg, mesh, params_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs_tree(cfg, params_tree, mesh)
    )


# ---------------------------------------------------------------------------
# batch / cache partition specs
# ---------------------------------------------------------------------------

def batch_specs_tree(cfg: ArchConfig, mesh, batch_tree, *, long_context: bool = False):
    """tokens/labels: (B, S); frames/image_embeds: (B, T, D)."""
    dp = dp_axes(mesh)
    bspec = None if long_context else dp

    def rule(kp, leaf):
        return _sanitize(P(bspec, *((None,) * (leaf.ndim - 1))), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def cache_specs_tree(cfg: ArchConfig, mesh, cache_tree, *, long_context: bool = False):
    """Decode-cache sharding.

    Dense KV slabs (L, B, W, Hkv, hd): batch over DP, head_dim over 'model'
    (hd is 16-divisible for every assigned arch, so no padding waste even for
    kv-head counts like 2 or 4).  Long-context (B=1): batch replicated,
    window/seq dim over 'data' (sequence parallelism).
    """
    dp = dp_axes(mesh)
    b_ax = None if long_context else dp
    w_ax = "data" if long_context else None

    def rule(kp, leaf):
        path = tuple(getattr(k, "key", str(k)) for k in kp)
        name = path[-1]
        if name in ("pos", "slot_pos"):
            return P(*((None,) * leaf.ndim))
        if name in ("k", "v"):                     # (L,B,W,Hkv,hd) [cross: no W ring]
            return P(None, b_ax, w_ax, None, "model")
        if name.endswith("mu") or name.endswith("sexp"):   # (L,B,W,Hkv)
            return P(None, b_ax, w_ax, None)
        if name.endswith("pl"):                    # (L,P,B,W,Hkv,hd)
            return P(None, None, b_ax, w_ax, None, "model")
        if name == "state":                        # (L,B,H,N,hp)
            return P(None, b_ax, "model", None, None)
        if name == "conv":                         # (L,B,W-1,CC)
            return P(None, b_ax, None, "model")
        raise ValueError(f"no cache rule for {'/'.join(path)}")

    def rule_sane(kp, leaf):
        return _sanitize(rule(kp, leaf), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(rule_sane, cache_tree)
