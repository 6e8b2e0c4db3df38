"""A traffic kind can come as a file of its own (``perfbench/kinds/``), and
the built-in kinds are untouched by that: the fixture kind
``fixtures/kinds/tree_sharded.py`` runs through ``run.run_cell`` on four
virtual CPU devices, in a subprocess so that this process keeps its one
device; the three cells of ``BENCHMARK.json`` give the same units and check
numbers as before kinds could be files."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import check
import run
import traffic
from test_faults import BENCH, small_run

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
FIXTURE = HERE / "fixtures" / "kinds" / "tree_sharded.py"

CELL = {"name": "tiny.sharded", "config": "tiny", "traffic": "tree_sharded", "chips": 4,
        "why": "a seeded two-leaf tree saved sharded over four devices and restored"}
READ = ("compress_GBps", "decompress_GBps", "compression_ratio")


def with_cell(bench: dict, cell: dict, metrics) -> dict:
    """``bench`` with ``cell`` added and its name appended to the
    ``workloads`` list of each end-to-end metric in ``metrics``: additions
    only, as a later cell comes (``perfbench/kinds/README.md``)."""
    out = json.loads(json.dumps(bench))
    out["workloads"].append(cell)
    for m in out["end_to_end"]:
        if m["name"] in metrics:
            m["workloads"].append(cell["name"])
    return out


CODE = r"""
import json, sys
from pathlib import Path
sys.path[:0] = [sys.argv[2], "src"]
import jax
import run, traffic
from repro.api import TreeCodec
from repro.kernels import ops

traffic.KINDS_DIR = Path(sys.argv[1])
bench = json.loads(Path(sys.argv[3]).read_text())
config = {"leaves": {"w1": [256, 130], "w2": [3000]}, "dtypes": ["float32"],
          "bound": {"mode": "rel", "value": 1e-3}}


def one():
    return run.run_cell(bench, "tiny.sharded", 2**31 + 17, 0.5, False, config=config,
                        mix={"kind": "tree_sharded"}, devices=jax.devices()[:4],
                        peak={"hbm_bytes_per_s": 819e9})


out = {"sound": one()}
real_resolve = ops._resolve
ops._resolve = lambda backend, spec=None: "numpy"      # every shard on the host mirror
out["host"] = one()
ops._resolve = real_resolve
real = TreeCodec._sharded_leaf_payloads


def flipped(self, arr, devices):
    for i, (payload, last) in enumerate(real(self, arr, devices)):
        yield (payload[:-1] + bytes([payload[-1] ^ 1]) if i == 1 else payload), last


TreeCodec._sharded_leaf_payloads = flipped
out["fault"] = one()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def file_kind(tmp_path_factory):
    """The fixture kind as the only file of a ``kinds`` directory, run on the
    real ``BENCHMARK.json`` with a four-device cell added."""
    kinds = tmp_path_factory.mktemp("kinds")
    (kinds / "tree_sharded.py").write_text(FIXTURE.read_text())
    bench = kinds / "BENCHMARK.json"
    bench.write_text(json.dumps(with_cell(BENCH, CELL, READ)))
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu", PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", CODE, str(kinds), str(ROOT / "perfbench"),
                        str(bench)],
                       capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_added_cell_is_an_addition():
    """Every entry of ``BENCHMARK.json`` is there as it was, but for the new
    cell's name at the end of three ``workloads`` lists."""
    bench = BENCH
    added = with_cell(bench, CELL, READ)
    assert added["workloads"] == bench["workloads"] + [CELL]
    assert added["per_layer"] == bench["per_layer"]
    for old, new in zip(bench["end_to_end"], added["end_to_end"], strict=True):
        extra = [CELL["name"]] if old["name"] in READ else []
        assert new == (dict(old, workloads=old["workloads"] + extra)
                       if "workloads" in old else old)


def test_file_kind_runs_on_four_devices(file_kind):
    r = file_kind["sound"]
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["count"] == 4 and r["device"]["platform"] == "cpu"


def test_file_kind_reports_what_its_lists_name(file_kind):
    """The cell's end-to-end metrics are those whose ``workloads`` list
    names it, and ``setup_s``, which has none; ``roi_read_p95_ms`` is not."""
    assert set(file_kind["sound"]["metrics"]) == {*READ, "setup_s"}


@pytest.mark.parametrize("metric", READ)
def test_file_kind_units_are_read(file_kind, metric):
    assert file_kind["sound"]["metrics"][metric]["value"] > 0


def test_file_kind_ratio_is_a_compression(file_kind):
    assert file_kind["sound"]["metrics"]["compression_ratio"]["value"] > 1


@pytest.mark.parametrize("number", ["frames_differing", "values_differing",
                                    "max_err_over_bound", "host_path_frames",
                                    "shards_missing"])
def test_file_kind_numbers_are_checked(file_kind, number):
    c = file_kind["sound"]["checks"][number]
    assert c["value"] <= c["limit"]
    assert "chips_idle" not in file_kind["sound"]["checks"]   # untraced run


def test_file_kind_limits_are_its_own(file_kind):
    assert file_kind["sound"]["checks"]["shards_missing"] == {"value": 0, "limit": 0}


def test_file_kind_host_route_is_caught(file_kind):
    """Shards encoded and restored by the host mirror are byte-identical, so
    only ``check.ROUTE_COUNTERS`` shows them: the run is not correct."""
    r = file_kind["host"]
    assert r["checks"]["frames_differing"]["value"] == 0
    assert r["checks"]["host_path_frames"]["value"] > 0 and not r["correct"]


def test_file_kind_flipped_shard_byte_is_caught(file_kind):
    r = file_kind["fault"]
    assert not r["correct"], r["checks"]
    assert r["checks"]["frames_differing"]["value"] > 0


@pytest.mark.parametrize("name", sorted(check.LIMITS))
def test_kind_cannot_redefine_a_limit(tmp_path, monkeypatch, name):
    (tmp_path / "loose.py").write_text(f"LIMITS = {{{name!r}: 1e9}}\n")
    monkeypatch.setattr(traffic, "KINDS_DIR", tmp_path)
    with pytest.raises(ValueError, match="redefines"):
        traffic.load_kind("loose")


def test_unknown_kind_is_refused(tmp_path, monkeypatch):
    monkeypatch.setattr(traffic, "KINDS_DIR", tmp_path)
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic.load_kind("nope")


class FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_chip_checks_every_dtype(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(jax, "devices", lambda: [FakeTPU()] * 4)
    monkeypatch.setattr(ops, "_resolve", lambda backend, spec=None:
                        "kernel" if spec.name != "bfloat16" else "numpy")
    assert len(run.chip(4, "float32", "float16")) == 4
    with pytest.raises(run.NoChip, match="bfloat16"):
        run.chip(4, "float32", "bfloat16")


# Read on the parent of kind files, with this file's ``units_and_checks``
# (seed 2**31 + 11): every unit's (kind, frames, raw bytes, framed bytes),
# the warm-up's frames and the check numbers.
BEFORE = {
    "hurricane-isabel.roundtrip": (
        {("compress", 2, 100000, 32673), ("decompress", 2, 100000, 32673)},
        {"compress": 2, "decompress": 2},
        {"frames_differing": 0, "values_differing": 0,
         "max_err_over_bound": 0.2843801893292288, "host_path_frames": 0}),
    "nyx.roundtrip": (
        {("compress", 4, 131072, 44737), ("decompress", 4, 131072, 44737)},
        {"compress": 1, "decompress": 1},
        {"frames_differing": 0, "values_differing": 0,
         "max_err_over_bound": 0.29458251568793054, "host_path_frames": 0}),
    "hurricane-isabel.roi": (
        {("roi", 1, 10000, 0)},
        {"roi": 10},
        {"values_differing": 0, "max_err_over_bound": 0.2843801893292288,
         "host_path_frames": 0}),
}


def units_and_checks(monkeypatch, workload):
    """A small run of ``workload`` whose generator is watched; every ROI
    read is kept for the check (so its largest error is the field's)."""
    seen = {}
    real_make = traffic.make

    def make(*a, **k):
        gen = real_make(*a, **k)
        warm, run_window = gen.warm, gen.run

        def watched_warm():
            seen["warm"] = warm()
            return seen["warm"]

        def watched_run(*a, **k):
            w = run_window(*a, **k)
            seen["units"] = {(u.kind, u.frames, u.raw_bytes, u.framed_bytes) for u in w.units}
            return w

        gen.warm, gen.run = watched_warm, watched_run
        return gen

    monkeypatch.setattr(traffic, "make", make)
    extra = {"sample_reads": 1 << 20} if workload.endswith(".roi") else {}
    r = small_run(workload, **extra)
    return r, seen


@pytest.mark.parametrize("workload", sorted(BEFORE))
def test_builtin_cells_unchanged(monkeypatch, workload):
    def no_files(name):
        raise AssertionError(f"a built-in kind loaded the file kind {name!r}")

    monkeypatch.setattr(traffic, "load_kind", no_files)
    r, seen = units_and_checks(monkeypatch, workload)
    units, warm, numbers = BEFORE[workload]
    assert seen["units"] == units and seen["warm"] == warm
    assert {k: c["value"] for k, c in r["checks"].items()} == numbers
    assert r["correct"]
