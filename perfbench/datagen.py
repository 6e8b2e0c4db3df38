"""Synthetic scientific fields at their published shapes, made from a seed.

A copy of the repository's multi-octave smoothness generator
(``src/repro/data/scidata.py``, ``_smooth_field``), kept here so that later
changes to the program cannot move the benchmark's inputs.  Each octave is a
coarse grid of standard normals, repeated up to the field's shape and box
smoothed along every axis; octave ``o`` is weighted ``rough**o``, and a
``spikes`` share of values is multiplied by 50.
"""
from __future__ import annotations

import numpy as np


def smooth_field(rng, shape, octaves, roughness, spike_frac=0.0):
    out = np.zeros(shape, np.float32)
    for o in range(octaves):
        amp = roughness**o
        coarse = [max(2, s // (2 ** (octaves - o))) for s in shape]
        small = rng.standard_normal(coarse).astype(np.float32)
        for ax, (cs, fs) in enumerate(zip(coarse, shape)):
            reps = int(np.ceil(fs / cs))
            small = np.repeat(small, reps, axis=ax)
            sl = [slice(None)] * len(shape)
            sl[ax] = slice(0, fs)
            small = small[tuple(sl)]
            k = max(1, fs // cs // 2)
            if k > 1:
                c = np.cumsum(small, axis=ax)
                sl_a = [slice(None)] * len(shape)
                sl_b = [slice(None)] * len(shape)
                sl_a[ax] = slice(k, None)
                sl_b[ax] = slice(0, -k)
                body = (c[tuple(sl_a)] - c[tuple(sl_b)]) / k
                pad = [(0, 0)] * len(shape)
                pad[ax] = (0, small.shape[ax] - body.shape[ax])
                small = np.pad(body, pad, mode="edge")
        out += amp * small
    if spike_frac:
        n = int(out.size * spike_frac)
        idx = rng.integers(0, out.size, n)
        out.reshape(-1)[idx] *= 50.0
    return out


def make_field(config: dict, seed: int) -> np.ndarray:
    """The configuration's field (its ``shape``, ``dtype`` and
    ``generator`` parameters) for ``seed``: the same seed, the same field."""
    g = config["generator"]
    x = smooth_field(np.random.default_rng(seed), tuple(config["shape"]),
                     g["octaves"], g["rough"], g["spikes"])
    return x.astype(config["dtype"], copy=False)
