"""Logical-axis activation sharding hints.

Model code calls ``shard_activation(x, logical_axes)`` with *logical* names;
the launcher installs a rule table mapping logical -> mesh axes for the
current mesh/cell via ``use_rules``.  Outside any rule context the calls are
no-ops, so the model runs unchanged on a single CPU device in tests.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


_state = threading.local()


DEFAULT_RULES: dict[str, object] = {
    # activation batch over all data-parallel axes
    "act_batch": ("pod", "data"),
    "act_heads": "model",
    "act_hd": "model",        # decode: head_dim-sharded q/KV (kv-head agnostic)
    "act_ff": "model",
    "act_expert": "model",
    "act_moe_batch": ("pod", "data"),   # batch dim of MoE dispatch buffers
    "act_seq": None,
    "act_embed": None,
}

# long-context decode (batch=1): batch replicated, sequence sharded over data
LONG_CONTEXT_RULES = dict(DEFAULT_RULES, act_batch=None, act_seq="data")

# pure data parallelism: for small models (<~1B) on a big mesh, TP collectives
# on (B,S,D) activations dwarf the compute; replicate params and shard the
# batch over EVERY mesh axis instead (section Perf hillclimb H2)
PURE_DP_RULES = dict(
    DEFAULT_RULES,
    act_batch=("pod", "data", "model"),
    act_heads=None, act_hd=None, act_ff=None, act_expert=None,
    act_moe_batch=("pod", "data", "model"),
)

# serve-layout MoE (H1): experts live on 'data' x 'model'; dispatch buffers
# follow the weights' E-sharding (activations are tiny at decode, weights are
# not -- replicate the token dim, shard E over 'data')
SERVE_MOE_RULES = dict(act_expert="data", act_moe_batch=None)


@contextlib.contextmanager
def use_rules(mesh, rules: dict | None = None):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, dict(DEFAULT_RULES, **(rules or {})))
    try:
        yield
    finally:
        _state.ctx = prev


def rules_active() -> bool:
    return getattr(_state, "ctx", None) is not None


def shard_activation(x, logical_axes):
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    axes = []
    for name in logical_axes:
        axis = rules.get(name) if name else None
        if isinstance(axis, tuple):
            axis = tuple(a for a in axis if a in mesh.axis_names) or None
        elif axis is not None and axis not in mesh.axis_names:
            axis = None
        axes.append(axis)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))
