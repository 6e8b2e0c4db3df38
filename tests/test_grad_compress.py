"""Cross-pod SZx gradient compression: encoded all-reduce correctness and
convergence-safe compressed-DP training (error feedback).

Runs in a subprocess with an 8-device host platform and a (2,2,2)
pod/data/model mesh so the main process keeps 1 device."""
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

from repro.core import grad_compress as gc


def test_wire_bytes_accounting():
    # block 64 (shard-local): 1 + 6/64 = 1.094 B/val -> ~3.7x vs fp32
    assert gc.wire_bytes_per_value(1) < 4.0 / 3.6
    assert gc.wire_bytes_per_value(2) < 4.0 / 1.9


def test_encode_decode_leaf_roundtrip():
    g = jnp.asarray(np.random.default_rng(0).standard_normal((333,)), jnp.float32)
    enc = gc._encode_leaf(g, 1, 256)
    dec = gc._decode_leaf(enc, g.shape, jnp.float32, 256)
    # P=1 block quantization: error bounded by per-block 2^(E-6)-ish; check
    # the residual is small relative to the gradient scale
    assert float(jnp.abs(g - dec).max()) < 0.05 * float(jnp.abs(g).max())


CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, numpy as np
import jax, jax.numpy as jnp
from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import sharding as shard_rules
from repro.optim import AdamW
from repro.train import step as step_mod

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = dataclasses.replace(configs.get("llama3.2-1b").reduced(), n_layers=2)
opt = AdamW(lr=1e-2)

def batches(step):
    rng = np.random.default_rng(step)
    t = rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)
    return {"tokens": jnp.asarray(t),
            "labels": jnp.asarray(np.roll(t, -1, 1))}

losses = {}
for planes in (0, 1):
    state = step_mod.init_state(cfg, opt, jax.random.key(0), ef_planes=planes)
    rules = dict(shard_rules.DEFAULT_RULES, act_batch=("data",))
    with shard_rules.use_rules(mesh, rules):
        fn = jax.jit(step_mod.make_train_step(
            cfg, opt, mesh=mesh, compress_planes=planes))
        ls = []
        for i in range(12):
            state, m = fn(state, batches(i))
            ls.append(float(m["loss"]))
    losses[planes] = ls

l0, l1 = losses[0], losses[1]
assert l0[-1] < l0[0], "uncompressed did not train"
assert l1[-1] < l1[0], "compressed did not train"
# compressed-DP with error feedback tracks the uncompressed loss closely
diff = abs(l0[-1] - l1[-1]) / abs(l0[-1])
assert diff < 0.08, (l0[-1], l1[-1])
print("GRADCOMP-OK", round(l0[-1], 4), round(l1[-1], 4))
"""


def test_compressed_dp_training_matches():
    r = subprocess.run(
        [sys.executable, "-c", CODE],
        capture_output=True, text=True, timeout=900,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert "GRADCOMP-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


COLLECTIVES_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import grad_compress as gc

mesh = jax.make_mesh((4,), ("x",))
rng = np.random.default_rng(1)
perm = [(i, (i + 1) % 4) for i in range(4)]

def run(fn, x):
    return np.asarray(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
        axis_names={"x"}, check_vma=False,
    )(x))

# compressed ppermute tracks the raw permute within the planes budget
x = rng.normal(size=(4, 8, 64)).astype(np.float32)
a = run(lambda xs: gc.compressed_ppermute(xs[0], "x", perm, num_planes=3)[None], x)
b = run(lambda xs: jax.lax.ppermute(xs[0], "x", perm)[None], x)
assert a.shape == b.shape and np.abs(a - b).max() < 0.05, np.abs(a - b).max()

# compressed all_to_all matches the raw exchange's shape and values
x2 = rng.normal(size=(4, 8, 12, 64)).astype(np.float32)
a2 = run(lambda xs: gc.compressed_all_to_all(xs[0], "x", 0, 1, num_planes=3)[None], x2)
b2 = run(lambda xs: jax.lax.all_to_all(xs[0], "x", 0, 1, tiled=True)[None], x2)
assert a2.shape == b2.shape and np.abs(a2 - b2).max() < 0.05, np.abs(a2 - b2).max()

# blocked-last-axis misuse is rejected
import traceback
try:
    run(lambda xs: gc.compressed_all_to_all(xs[0], "x", 0, 2, num_planes=1)[None], x2)
except ValueError as e:
    assert "blocked last axis" in str(e)
else:
    raise AssertionError("expected ValueError for last-axis exchange")

# gpipe compressed activation shift tracks the exact schedule
from repro.pipeline_par import pipeline_apply
smesh = jax.make_mesh((4,), ("stage",))
ws = (rng.normal(size=(4, 64, 64)) * 0.1).astype(np.float32)
xs = rng.normal(size=(8, 2, 64)).astype(np.float32)
stage = lambda p, x: jnp.tanh(x @ p)
raw = np.asarray(pipeline_apply(stage, smesh)(jnp.asarray(ws), jnp.asarray(xs)))
comp = np.asarray(pipeline_apply(
    stage, smesh, compress_activations=True, num_planes=3,
)(jnp.asarray(ws), jnp.asarray(xs)))
assert np.abs(raw - comp).max() < 0.05, np.abs(raw - comp).max()
print("COLLECTIVES-OK")
"""


def test_compressed_collectives_track_raw():
    r = subprocess.run(
        [sys.executable, "-c", COLLECTIVES_CODE],
        capture_output=True, text=True, timeout=600,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert "COLLECTIVES-OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
