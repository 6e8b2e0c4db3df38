#!/usr/bin/env python3
"""The control of the benchmark's comparison: the plain reference put in the
program's place one precision lower (bfloat16 for the configurations'
float32), at the cell's own size, fed through the same checks as a run.
Every seed has to come out not correct.

    python3 perfbench/control.py --workload hurricane-isabel.roundtrip --seeds 1,2,3

Prints one JSON line per seed: the numbers compared, each with its limit.
It needs no chip: the control runs on the host.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import datagen  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402

LOWER = reference.BFLOAT16


def roundtrip(x: np.ndarray, config: dict, mix: dict, seed: int) -> dict:
    frames = reference.compress_frames(x, config["bound"]["value"], LOWER,
                                       chunk_bytes=config["frame_bytes"])
    y = reference.decompress_frames(frames).astype(x.dtype)
    return check.roundtrip(x, config, [frames], [(0, y)], config["frame_bytes"])


def roi(x: np.ndarray, config: dict, mix: dict, seed: int) -> dict:
    axis, width = int(mix["axis"]), int(mix["width"])
    chunk = traffic.chunk_shape(x.shape, x.dtype.itemsize, int(mix["chunk_bytes"]))
    offsets = np.random.default_rng([seed, 2]).integers(
        0, x.shape[axis] - width + 1, int(mix["sample_reads"]))
    low = x.astype(LOWER.dtype)
    store = check.StoreReads(low, reference.rel_bound(low.reshape(-1), config["bound"]["value"]),
                             LOWER, chunk)
    outputs = [(int(k), store.read(*check.box_bounds(x.shape, axis, int(k), width))
                .astype(x.dtype)) for k in offsets]
    return check.region_reads(x, config, chunk, axis, width, outputs)


KINDS = {"roundtrip": roundtrip, "roi": roi}


def run(bench: dict, workload: str, seed: int, *, config=None, mix=None) -> dict:
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    if config is None:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        config = json.loads((HERE.parent / entry["file"]).read_text())
    if mix is None:
        mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text())
    x = datagen.make_field(config, seed)
    return KINDS[mix["kind"]](x, config, mix, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    for seed in (int(s) for s in args.seeds.split(",")):
        checked = run(bench, args.workload, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": check.correct(checked),
                          "checks": {k: {n: repr(v) if isinstance(v, float) else v
                                         for n, v in c.items()}
                                     for k, c in checked.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
