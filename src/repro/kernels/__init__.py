"""Pallas TPU kernels for the SZx hot loops + pure-jnp oracles.

All transform kernels are width-generic: parameterized by a
:class:`repro.kernels.specs.DtypeSpec` so one implementation covers
float32/float64/float16/bfloat16 (f64 needs 64-bit words; the dispatch layer
runs it under ``jax.enable_x64(True)``, and only off-TPU: TPUs have no
64-bit words).

Modules:
  specs.py       -- DtypeSpec: storage + compute IEEE-754 geometry
  ref.py         -- pure-jnp oracles (ground truth)
  tiles.py       -- row tiling, meta rows, integer 16-bit float conversion
  encode.py      -- FUSED stats+pack (one kernel, one round trip per chunk)
  unpack.py      -- the decode kernel: log-time index propagation (Fig. 9)
                    + the all-L==0 dense variant
  planes.py      -- szx-planes fixed-plane encode/decode (in-graph mode)
  bitshuffle.py  -- bit transpose for the lossless second stage
  ops.py         -- jit'd wrappers + backend dispatch
"""
