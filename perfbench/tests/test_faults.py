"""A whole run at a small size on the CPU, without the look for a chip: a
sound run is correct, and each fault planted in the timed path is caught."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import run
from repro.core.codec import device
from repro.core.codec.szx_codec import SZxCodec
from repro.kernels import ops
from repro.store.array import CompressedArray

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
SMALL = {"hurricane-isabel": {"shape": [10, 50, 50], "frame_bytes": 64 << 10},
         "nyx": {"shape": [32, 32, 32], "frame_bytes": 32 << 10}}


def small_run(workload: str, seed: int = 2**31 + 11, **mix_keys) -> dict:
    cell = next(w for w in BENCH["workloads"] if w["name"] == workload)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    config = dict(json.loads((ROOT / entry["file"]).read_text()), **SMALL[cell["config"]])
    mix = json.loads((ROOT / "perfbench" / "mixes" / f"{cell['traffic']}.json").read_text())
    if mix["kind"] == "roi":
        mix["chunk_bytes"] = 20_000          # several chunks of 2 levels
    mix.update(mix_keys)
    return run.run_cell(BENCH, workload, seed, 0.5, False, config=config, mix=mix,
                        devices=jax.devices()[:1], peak={"hbm_bytes_per_s": 819e9})


def flip_first_frame(real):
    def compress_chunked(self, *a, **k):
        for i, frame in enumerate(real(self, *a, **k)):
            yield frame if i else frame[:-1] + bytes([frame[-1] ^ 1])
    return compress_chunked


def drop_last_frame(real):
    def compress_chunked(self, *a, **k):
        frames = list(real(self, *a, **k))
        yield from frames[:-1] if len(frames) > 1 else frames
    return compress_chunked


def alter_restore(real, how):
    def decompress_chunked(self, *a, **k):
        y = real(self, *a, **k).copy()
        how(y)
        return y
    return decompress_chunked


def bump_first(y):
    y[0] += 1


def drop_half(y):
    y[y.size // 2:] = 0


ROUNDTRIP_FAULTS = {
    "frame altered where produced": ("compress_chunked", flip_first_frame),
    "last frame left out": ("compress_chunked", drop_last_frame),
    "restored value altered": ("decompress_chunked", lambda r: alter_restore(r, bump_first)),
    "half the restore left out": ("decompress_chunked", lambda r: alter_restore(r, drop_half)),
}


def alter_read(real, how):
    def getitem(self, key):
        out = np.array(real(self, key))
        how(out.reshape(-1))
        return out
    return getitem


ROI_FAULTS = {
    "read value altered": lambda r: alter_read(r, bump_first),
    "half the read left out": lambda r: alter_read(r, drop_half),
}


@pytest.mark.parametrize("workload", ["hurricane-isabel.roundtrip", "nyx.roundtrip",
                                      "hurricane-isabel.roi"])
def test_sound_run_is_correct(workload):
    r = small_run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    names = {m["name"] for m in BENCH["end_to_end"]
             if "workloads" not in m or workload in m["workloads"]}
    assert set(r["metrics"]) == names
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(ROUNDTRIP_FAULTS))
def test_roundtrip_fault_is_caught(monkeypatch, fault):
    attr, make = ROUNDTRIP_FAULTS[fault]
    monkeypatch.setattr(SZxCodec, attr, make(getattr(SZxCodec, attr)))
    r = small_run("hurricane-isabel.roundtrip")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", sorted(ROI_FAULTS))
def test_roi_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(CompressedArray, "__getitem__",
                        ROI_FAULTS[fault](CompressedArray.__getitem__))
    r = small_run("hurricane-isabel.roi")
    assert not r["correct"], r["checks"]


def test_host_route_is_caught(monkeypatch):
    """Frames compressed and restored by the host mirror are byte-identical,
    so only the transfer counters show them: the run is not correct."""
    monkeypatch.setattr(ops, "_resolve", lambda backend, spec=None: "numpy")
    r = small_run("hurricane-isabel.roundtrip")
    assert r["checks"]["frames_differing"]["value"] == 0
    assert r["checks"]["host_path_frames"]["value"] > 0 and not r["correct"]


@pytest.mark.parametrize("entry", ["decode_stream", "decode_range"])
def test_host_decode_is_caught(monkeypatch, entry):
    monkeypatch.setattr(device, entry, lambda *a, **k: None)
    workload = "hurricane-isabel.roi" if entry == "decode_range" else "nyx.roundtrip"
    r = small_run(workload)
    assert r["checks"]["host_path_frames"]["value"] > 0 and not r["correct"]
