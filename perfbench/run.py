#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python3 perfbench/run.py --workload hurricane-isabel.roundtrip \\
        --seed 7 --seconds 30 --trace 0

The cell names a configuration (``perfbench/configs/<name>.json``: the
field's shape, dtype, bound and generator) and a traffic mix
(``perfbench/mixes/<traffic>.json``) whose ``kind`` is built in
(``traffic.py``) or a module of its own (``perfbench/kinds/<kind>.py``, see
``perfbench/kinds/README.md``); each metric is read by
``perfbench/metrics/<metric>.py``.  A run makes the field from ``--seed``,
warms every program the window will use, measures for ``--seconds``, and then
checks what the timed path produced against the plain reference
(``reference.py``, ``check.py``).  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` profiles the window and reports its
per-layer metrics, device busy time and a breakdown.

The last line of standard output is the JSON result.  Without a TPU, or with
fewer chips than the cell asks for, or on a chip the peaks table does not
know, the run prints no result and exits with 2.
"""
from __future__ import annotations

import time

_T_IMPORT = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import check  # noqa: E402
import datagen  # noqa: E402
import devtrace  # noqa: E402
import traffic  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """The machine lacks what the cell needs: no result may be printed."""


def process_age() -> float:
    """Seconds since this process started, from /proc where it exists."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.monotonic() - _T_IMPORT


class Compiles:
    """Counts programs lowered and compiled while ``on`` (the window)."""

    def __init__(self):
        import jax

        self.on = False
        self.lowered = self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


class Context:
    """What a metric's reader may read: the window's units of work, the
    reduced trace (traced runs), the program's counters and the chip's peak."""

    def __init__(self, setup_s, window, trace, peak):
        self.setup_s = setup_s
        self.window = window
        self.trace = trace
        self.peak = peak

    def units(self, kind: str) -> list:
        return [u for u in self.window.units if u.kind == kind]

    def rate(self, kind: str):
        """Raw GB (1e9 bytes) per second over the window's ``kind`` units."""
        units = self.units(kind)
        secs = sum(u.seconds for u in units)
        return sum(u.raw_bytes for u in units) / secs / 1e9 if units and secs > 0 else None

    @staticmethod
    def counter(name: str, **labels):
        from repro import obs

        return obs.counter(name, **labels).value


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def chip(chips: int, *dtypes: str) -> list:
    """The devices a cell runs on; NoChip where they are not TPUs enough, or
    where the codec's ``auto`` route for any of ``dtypes`` is not the kernels."""
    import jax

    from repro.kernels import ops, specs

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    for dtype in dtypes:
        route = ops._resolve("auto", next(s for s in specs.SPECS if s.name == dtype))
        if route != "kernel":
            raise NoChip(f"backend 'auto' resolves {dtype} to {route!r}, not 'kernel' "
                         "(is SZX_OPS_BACKEND set?)")
    return devs[:chips]


def peak_of(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in perfbench/peaks.json")
    return peaks[kind]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks, default=0))


def run_cell(bench: dict, workload: str, seed: int, seconds: float, trace: bool, *,
             config: dict | None = None, mix: dict | None = None,
             devices=None, peak: dict | None = None) -> dict:
    """One run of one cell; returns the result object (the numbers compared,
    ``checks``, come last).  ``config``, ``mix``,
    ``devices`` and ``peak`` replace what the files and the chip would give
    (tests drive a run at a small size without a chip)."""
    import jax

    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    if config is None:
        entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
        config = json.loads((ROOT / entry["file"]).read_text())
    if mix is None:
        mix = json.loads((HERE / "mixes" / f"{cell['traffic']}.json").read_text())
    dtypes = config["dtypes"] if "dtypes" in config else [config["dtype"]]
    devs = devices if devices is not None else chip(cell["chips"], *dtypes)
    peak = peak if peak is not None else peak_of(devs[0].device_kind)
    compiles = Compiles()
    from repro import obs

    obs_was_on = obs.enabled()
    obs.enable()                         # the transfer counters show the route taken
    obs.reset()
    kind = None if mix["kind"] in traffic.KINDS else traffic.load_kind(mix["kind"])
    x = getattr(kind, "make_input", datagen.make_field)(config, seed)
    gen = traffic.make(config, mix, x, seed, devs, kind)
    log_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if trace else None
    try:
        off_route = check.host_path_frames(gen.warm())
        obs.reset()
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(log_dir, profiler_options=opts)

        def on_close():
            compiles.on = False
            if trace:
                jax.profiler.stop_trace()

        setup_s = process_age()
        compiles.on = True
        window = gen.run(seconds, on_close)
        kinds = {u.kind for u in window.units}
        off_route += check.host_path_frames({k: window.frames(k) for k in kinds})
        mem = memory_peak(devs)
        reduction = None
        if trace:
            reduction = devtrace.Reduction.from_dir(log_dir, {d.id for d in devs})
            if not reduction.busy or not any(reduction.busy.values()):
                raise RuntimeError("the trace holds no operation on the cell's devices "
                                   f"({sorted(d.id for d in devs)}): nothing to reduce")
        checked = gen.check(window, reduction)
        checked.update(check.numbers({"host_path_frames": off_route}))
        if trace:
            checked.update(check.numbers({"calls_without_device_ops":
                                          sum(reduction.silent(k) for k in kinds)}))
    finally:
        gen.close()
        if not obs_was_on:
            obs.disable()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    ctx = Context(setup_s, window, reduction, peak)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench[section]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    result = {
        "correct": check.correct(checked) and window.failed == 0,
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": metrics,
        "device": device,
    }
    if reduction is not None:
        device["busy_s"] = reduction.busy_seconds()
        device["window_s"] = reduction.window_seconds()
        result["breakdown"] = {"device_ops": reduction.top_ops(10),
                               "idle_gaps": reduction.idle_by_host(10)}
    result["checks"] = {k: {n: v if isinstance(v, int) or math.isfinite(v) else repr(v)
                            for n, v in c.items()} for k, c in checked.items()}
    print(f"window: {window.t_end - window.t_start:.3f} s, programs lowered in it: "
          f"{compiles.lowered}, compiled in it: {compiles.compiled}", file=sys.stderr)
    if reduction is not None:
        print(f"trace: {len(reduction.scopes)} op names with a scope, "
              f"{len(reduction.ambiguous)} of two scopes "
              f"({sum(map(reduction.ambiguous_seconds, kinds)):.6f} s a chip "
              "inside the calls, given to no scope)",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)   # no eviction between runs
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    try:
        result = run_cell(bench, args.workload, args.seed % 2**64, args.seconds,
                          bool(args.trace))
    except NoChip as exc:
        print(f"perfbench: {exc}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
