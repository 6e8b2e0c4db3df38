"""The control -- the plain reference one precision lower (bfloat16) in the
program's place -- has to come out not correct; the reference itself, in the
configuration's float32, has to come out correct."""
import json
from pathlib import Path

import numpy as np
import pytest

import check
import control
import datagen
import reference
import traffic

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SMALL = {"hurricane-isabel": {"shape": [10, 50, 50], "frame_bytes": 64 << 10},
         "nyx": {"shape": [32, 32, 32], "frame_bytes": 32 << 10}}


def small(workload):
    cell = next(w for w in BENCH["workloads"] if w["name"] == workload)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    config = dict(json.loads((ROOT / entry["file"]).read_text()), **SMALL[cell["config"]])
    mix = json.loads((ROOT / "perfbench" / "mixes" / f"{cell['traffic']}.json").read_text())
    if mix["kind"] == "roi":
        mix["chunk_bytes"] = 20_000
    return config, mix


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload", ["hurricane-isabel.roundtrip", "nyx.roundtrip",
                                      "hurricane-isabel.roi"])
def test_control_is_not_correct(workload, seed):
    config, mix = small(workload)
    checked = control.run(BENCH, workload, seed, config=config, mix=mix)
    assert not check.correct(checked), checked


@pytest.mark.parametrize("workload", ["hurricane-isabel.roundtrip", "hurricane-isabel.roi"])
def test_reference_in_place_is_correct(workload):
    config, mix = small(workload)
    x = datagen.make_field(config, 5)
    if mix["kind"] == "roundtrip":
        frames = reference.compress_frames(x, config["bound"]["value"],
                                           chunk_bytes=config["frame_bytes"])
        y = reference.decompress_frames(frames)
        checked = check.roundtrip(x, config, [frames], [(0, y)], config["frame_bytes"])
    else:
        chunk = traffic.chunk_shape(x.shape, 4, mix["chunk_bytes"])
        e = reference.rel_bound(x.reshape(-1), config["bound"]["value"])
        part = np.ascontiguousarray(x[0:chunk[0]])
        vals = reference.decode_stream(reference.encode_stream(part, e, reference.FLOAT32))
        checked = check.region_reads(x, config, chunk, 0, 1,
                                     [(1, vals.reshape(part.shape)[1:2])])
    assert check.correct(checked), checked


def test_reference_matches_the_program_mirror():
    """The reference and the program's host mirror agree byte for byte, and
    on constant and verbatim blocks too (the program's own tests pin that
    mirror to its device paths)."""
    from repro.api import Bound, SZxCodec

    rng = np.random.default_rng(7)
    x = datagen.smooth_field(rng, (9, 40, 37), 4, 0.65, 0.0005)
    x.reshape(-1)[:600] = 1.5                       # constant blocks
    x.reshape(-1)[700:800] = 1e-30                  # beyond the mantissa: verbatim
    codec = SZxCodec(backend="numpy")
    for fmt in (reference.FLOAT32, reference.BFLOAT16):
        xs = x.astype(fmt.dtype)
        for rel in (1e-3, 1e-6):
            got = list(codec.compress_chunked(xs, Bound.rel(rel), chunk_bytes=16 << 10))
            want = reference.compress_frames(xs, rel, fmt, chunk_bytes=16 << 10)
            assert got == want
            y = reference.decompress_frames(want)
            np.testing.assert_array_equal(y, codec.decompress_chunked(got, n=xs.size))
