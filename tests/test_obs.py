"""Unified telemetry layer (repro.obs) tests.

Covers registry thread-safety under concurrent observers, span nesting in
the Chrome trace export, Prometheus text exposition validity (every line
parsed), disabled-mode no-op guarantees (spy asserts ZERO registry calls
from the codec/store hot paths), per-frame stream stats against container
ground truth across dtypes x stage on/off, byte-identity of compressed
output with telemetry on vs off, the serve tier's ``/v1/metrics`` content
negotiation, and the ``Metrics._pct`` ceil-rank percentile pins.
"""
from __future__ import annotations

import io
import json
import re
import threading

import numpy as np
import pytest

from repro import obs
from repro.core.codec import container
from repro.core.codec.plan import Bound
from repro.core.codec.szx_codec import SZxCodec
from repro.obs.registry import Registry
from repro.serve.service.metrics import Metrics
from repro.serve.store_service import make_service
from repro.store import ArrayStore

try:
    import ml_dtypes

    BF16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover - ml_dtypes ships with jax
    BF16 = None


@pytest.fixture
def obs_on():
    """Telemetry enabled on a clean global registry; restored afterwards."""
    was = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs.REGISTRY
    if not was:
        obs.disable()
    obs.reset()


@pytest.fixture
def obs_off():
    """Telemetry force-disabled; restored afterwards."""
    was = obs.enabled()
    obs.disable()
    yield
    if was:
        obs.enable()


def _walk(n, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    x = (np.cumsum(rng.standard_normal(n)) * 0.01).astype(dtype)
    x[: n // 4] = x.flat[0]                       # some constant blocks
    return x


# ---------------------------------------------------------------------------
# registry: thread safety
# ---------------------------------------------------------------------------
def test_registry_concurrent_counters_exact():
    reg = Registry()
    nthreads, nincs = 8, 2000

    def work():
        c = reg.counter("t.hits")
        h = reg.histogram("t.lat")
        for i in range(nincs):
            c.inc()
            reg.counter("t.bytes", route=f"/r{i % 3}").inc(2)
            h.observe(1e-3)

    threads = [threading.Thread(target=work) for _ in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("t.hits").value == nthreads * nincs
    total = sum(
        reg.counter("t.bytes", route=f"/r{i}").value for i in range(3)
    )
    assert total == nthreads * nincs * 2
    _counts, s, count = reg.histogram("t.lat").value
    assert count == nthreads * nincs
    assert s == pytest.approx(1e-3 * count)


def test_registry_concurrent_span_recording():
    reg = Registry()
    nthreads, nspans = 6, 300

    def work(tid):
        for i in range(nspans):
            reg.record_span("s", i, 10, tid, 1, None)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(nthreads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    count, total = reg.span_aggregates()["s"]
    assert count == nthreads * nspans
    assert total == 10 * count


def test_registry_kind_conflict_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_span_log_bound_keeps_aggregates():
    reg = Registry(max_spans=4)
    for i in range(10):
        reg.record_span("s", i, 5, 0, 1, None)
    assert len(reg.spans()) == 4                  # log bounded
    assert reg.span_aggregates()["s"] == (10, 50)  # totals survive


# ---------------------------------------------------------------------------
# spans: nesting order in the Chrome trace
# ---------------------------------------------------------------------------
def test_span_nesting_chrome_trace(obs_on):
    with obs.span("outer", step=1):
        with obs.span("inner_a"):
            pass
        with obs.span("inner_b"):
            pass
    doc = obs.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert set(ev) == {"outer", "inner_a", "inner_b"}
    outer, a, b = ev["outer"], ev["inner_a"], ev["inner_b"]
    assert outer["ph"] == "X" and outer["args"]["step"] == 1
    assert a["tid"] == b["tid"] == outer["tid"]
    # timestamp containment: children inside the parent, a before b
    for child in (a, b):
        assert outer["ts"] <= child["ts"]
        assert child["ts"] + child["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert a["ts"] <= b["ts"]
    assert a["args"]["depth"] == b["args"]["depth"] == outer["args"]["depth"] + 1
    # events are sorted by start time
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


def test_write_chrome_trace_valid_json(obs_on, tmp_path):
    with obs.span("alpha"):
        pass
    p = tmp_path / "trace.json"
    obs.write_chrome_trace(str(p))
    doc = json.loads(p.read_text())
    assert [e["name"] for e in doc["traceEvents"]] == ["alpha"]


def test_traced_decorator_responds_to_late_enable():
    obs.reset()
    obs.disable()

    @obs.traced("deco.fn")
    def fn():
        return 7

    try:
        assert fn() == 7
        assert obs.REGISTRY.span_aggregates() == {}
        obs.enable()
        assert fn() == 7
        assert obs.REGISTRY.span_aggregates()["deco.fn"][0] == 1
    finally:
        obs.disable()
        obs.reset()


# ---------------------------------------------------------------------------
# spans on the jax.profiler clock
# ---------------------------------------------------------------------------
def _host_lines(log_dir, body):
    """Run ``body`` inside a ``bench.x`` annotation under a profiler trace;
    return the host plane's lines as {name: [(event, start, end)]}."""
    import glob

    import jax
    from jax.profiler import ProfileData, TraceAnnotation

    jax.profiler.start_trace(str(log_dir))
    try:
        with TraceAnnotation("bench.x"):
            body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    return {line.name: [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
            for line in host.lines}


def test_span_lands_in_profiler_trace(obs_on, tmp_path):
    @obs.traced("unit.traced")
    def leaf():
        pass

    def body():
        with obs.span("unit.outer", seq=3):
            with obs.span("unit.inner"):
                leaf()

    lines = _host_lines(tmp_path, body)
    line, = [evs for evs in lines.values() if any(n == "bench.x" for n, _, _ in evs)]
    ev = {n: (s, e) for n, s, e in line}
    assert {"unit.outer", "unit.inner", "unit.traced"} <= set(ev)
    (b0, b1), (o0, o1) = ev["bench.x"], ev["unit.outer"]
    (i0, i1), (t0, t1) = ev["unit.inner"], ev["unit.traced"]
    assert b0 <= o0 <= i0 <= t0 <= t1 <= i1 <= o1 <= b1
    # the in-memory span log keeps recording beside the profiler
    assert [sp[0] for sp in obs.REGISTRY.spans()] == [
        "unit.traced", "unit.inner", "unit.outer"]


def test_span_off_writes_no_profiler_event(obs_off, tmp_path):
    assert obs.span("unit.off") is obs._NULL

    def body():
        with obs.span("unit.off", seq=1):
            pass

    lines = _host_lines(tmp_path, body)
    names = {n for evs in lines.values() for n, _, _ in evs}
    assert "bench.x" in names and "unit.off" not in names
    assert obs.REGISTRY.spans() == []


def test_obs_imports_without_jax():
    import os
    import subprocess
    import sys

    import repro

    code = ("import sys; from repro import obs; obs.enable()\n"
            "with obs.span('a'): pass\n"
            "assert 'jax' not in sys.modules, 'obs imported jax'")
    src = os.path.dirname(os.path.dirname(repro.__file__))
    env = {**os.environ, "PYTHONPATH": src}
    subprocess.run([sys.executable, "-c", code], check=True, env=env, timeout=60)


# ---------------------------------------------------------------------------
# Prometheus text exposition: parse every line
# ---------------------------------------------------------------------------
_PROM_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                 # metric name
    r'(\{[a-zA-Z0-9_]+="[^"]*"(,[a-zA-Z0-9_]+="[^"]*")*\})?'
    r" -?[0-9.eE+\-]+(\+Inf)?$"
)
_PROM_TYPE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]*"
    r" (counter|gauge|histogram)$"
)


def test_prometheus_text_every_line_valid(obs_on):
    obs.counter("codec.compress.calls").inc(3)
    obs.counter("serve.requests", route="/v1/read").inc()
    obs.gauge("ingest.lookahead").set(2)
    h = obs.histogram("codec.compress.seconds")
    for v in (5e-5, 2e-3, 0.3, 50.0):
        h.observe(v)
    with obs.span("unit.span"):
        pass
    text = obs.prometheus_text()
    assert text.endswith("\n")
    lines = text.strip().split("\n")
    assert lines, "empty exposition"
    for line in lines:
        if line.startswith("#"):
            assert _PROM_TYPE.match(line), line
        else:
            assert _PROM_SAMPLE.match(line), line
    # histogram: cumulative buckets monotonic, +Inf equals _count
    buckets = [
        float(line.rsplit(" ", 1)[1])
        for line in lines
        if line.startswith("szx_codec_compress_seconds_bucket")
    ]
    assert buckets == sorted(buckets)
    count = [
        float(line.rsplit(" ", 1)[1])
        for line in lines
        if line.startswith("szx_codec_compress_seconds_count")
    ][0]
    assert buckets[-1] == count == 4
    # span aggregates exported as counters
    assert any(line.startswith('szx_span_count{name="unit.span"} ')
               for line in lines)
    # dotted names mapped, labels kept
    assert 'szx_serve_requests{route="/v1/read"} 1' in lines


def test_summary_renders(obs_on):
    assert obs.summary() == "(no telemetry recorded)\n"
    obs.counter("a.b").inc()
    with obs.span("s"):
        pass
    out = obs.summary()
    assert "a.b" in out and "s" in out and "spans" in out


# ---------------------------------------------------------------------------
# disabled mode: hot paths never touch the registry
# ---------------------------------------------------------------------------
def test_disabled_mode_is_noop(obs_off, monkeypatch, tmp_path):
    calls = []
    for name in ("_get", "record_span"):
        orig = getattr(Registry, name)

        def spy(self, *a, _orig=orig, _n=name, **kw):
            calls.append(_n)
            return _orig(self, *a, **kw)

        monkeypatch.setattr(Registry, name, spy)

    x = _walk(4096)
    codec = SZxCodec(backend="numpy", stage="deflate")
    buf = codec.compress(x, Bound.abs(1e-3))
    codec.decompress(buf)
    codec.decompress_range(buf, 0, 4)
    bio = io.BytesIO()
    codec.dump_chunked(x, bio, Bound.abs(1e-3), chunk_bytes=4096)
    bio.seek(0)
    codec.load_chunked(bio)
    szs = tmp_path / "a.szs"
    ArrayStore.save(str(szs), x.reshape(64, 64), Bound.abs(1e-3),
                    chunk_shape=(16, 64))
    with ArrayStore.open(str(szs)) as ca:
        ca[0:20, 0:32]
    assert calls == []
    # span() does not even allocate: the shared null singleton comes back
    assert obs.span("a") is obs.span("b")


def test_enabled_output_byte_identical(tmp_path):
    """SZX_OBS only observes: compressed bytes identical on vs off."""
    x = _walk(8192)
    obs.disable()
    obs.reset()
    try:
        bio_off = io.BytesIO()
        SZxCodec(backend="numpy", stage="deflate").dump_chunked(
            x, bio_off, Bound.abs(1e-3), chunk_bytes=8192)
        obs.enable()
        bio_on = io.BytesIO()
        SZxCodec(backend="numpy", stage="deflate").dump_chunked(
            x, bio_on, Bound.abs(1e-3), chunk_bytes=8192)
    finally:
        obs.disable()
        obs.reset()
    assert bio_off.getvalue() == bio_on.getvalue()


# ---------------------------------------------------------------------------
# per-frame stream stats vs container ground truth
# ---------------------------------------------------------------------------
def _dtypes():
    out = [np.dtype(np.float32), np.dtype(np.float64)]
    if BF16 is not None:
        out.append(BF16)
    return out


@pytest.mark.parametrize("dtype", _dtypes(), ids=lambda d: d.name)
@pytest.mark.parametrize("stage_name", [None, "deflate"])
def test_frame_stats_ground_truth(dtype, stage_name, obs_on):
    from repro.obs import stream_stats

    x = _walk(6000, dtype=dtype)
    codec = SZxCodec(backend="numpy", stage=stage_name)
    bio = io.BytesIO()
    codec.dump_chunked(x, bio, Bound.abs(1e-3), chunk_bytes=8192, index=False)
    data = bio.getvalue()

    # ground truth straight from the container layer
    frames = []
    off = 0
    while off < len(data):
        _m, _v, flags, seq, ln = container.FRAME_HEADER.unpack_from(data, off)
        frame = data[off:off + container.FRAME_HEADER.size + ln]
        frames.append((frame, flags, seq))
        off += container.FRAME_HEADER.size + ln
        if flags & container.FLAG_LAST:
            break

    recs = [stream_stats.frame_stats(f) for f, _fl, _s in frames]
    total_elems = sum(r["elements"] for r in recs)
    assert total_elems == x.size
    for rec, (frame, flags, seq) in zip(recs, frames):
        assert rec["seq"] == seq
        assert rec["frame_bytes"] == len(frame)
        assert rec["dtype"] == dtype.name
        # stage code in the record matches the frame's flag bits
        assert rec["stage"] == container.stage_of_flags(flags)
        if stage_name is None:
            assert rec["stage"] == 0
            assert rec["staged_mid_bytes"] == rec["raw_mid_bytes"]
        # CR against raw bytes of this frame's elements
        assert rec["ratio"] == pytest.approx(
            rec["elements"] * dtype.itemsize / rec["frame_bytes"])
        # const fraction + L histogram against a decoded-payload ground truth
        payload, _ = container.destage_frame_payload(
            frame[container.FRAME_HEADER.size:], flags)
        h = container.HEADER.unpack_from(payload, 0)
        _magic, _ver, _dc, bs, n, _e, nb, nnc, _nmid = h
        assert rec["nblocks"] == nb
        assert rec["const_blocks"] == nb - nnc
        assert rec["const_fraction"] == pytest.approx(
            (nb - nnc) / nb if nb else 0.0)
        assert sum(rec["l_hist"]) == nnc * bs

    # the codec's own frame counters (fed by container.build_frame) agree
    stages = {}
    for rec in recs:
        stages[rec["stage"]] = stages.get(rec["stage"], 0) + 1
    for code, count in stages.items():
        assert obs.counter("codec.frames.built", stage=code).value == count
    assert obs.counter("codec.frames.raw_bytes").value == sum(
        r["raw_bytes"] for r in recs)
    assert obs.counter("codec.frames.frame_bytes").value == sum(
        r["frame_bytes"] for r in recs)


def test_l_hist_matches_direct_bincount(obs_on):
    """L-code histogram via the byte-level table == per-element bincount."""
    from repro.obs import stream_stats

    x = _walk(5000)
    codec = SZxCodec(backend="numpy")
    buf = codec.compress(x, Bound.abs(1e-3))
    st = stream_stats.payload_stats(buf)
    sec = container.parse_stream_sections(buf, backend="numpy")
    L = np.asarray(sec.L)
    nonconst = ~np.asarray(sec.const)
    want = np.bincount(L[nonconst].ravel(), minlength=4)
    assert st["l_hist"] == [int(v) for v in want]


def test_l2bit_hist_matches_table_all_shapes():
    """Popcount-path 2-bit counting == byte-table bincount for every
    word-alignment: odd lengths (unaligned tail) and odd data-pointer
    offsets (unaligned uint64 view)."""
    from repro.obs import stream_stats

    rng = np.random.default_rng(42)
    base = rng.integers(0, 256, 1024 + 16, dtype=np.uint8)
    for off in (0, 1, 3, 7):
        for ln in (0, 1, 7, 8, 9, 64, 1021):
            lb = base[off:off + ln]
            want = stream_stats._l2bit_table().T @ np.bincount(
                lb, minlength=256
            )
            got = stream_stats._l2bit_hist(lb)
            assert np.array_equal(got, want), (off, ln, got, want)


# ---------------------------------------------------------------------------
# serve tier: _pct pins + /v1/metrics negotiation
# ---------------------------------------------------------------------------
def test_pct_ceil_rank_pins():
    samples = [float(v) for v in range(1, 101)]
    assert Metrics._pct(samples, 0.50) == 50.0
    assert Metrics._pct(samples, 0.99) == 99.0
    assert Metrics._pct([10.0, 20.0, 30.0, 40.0], 0.50) == 20.0
    assert Metrics._pct([10.0, 20.0, 30.0, 40.0], 0.99) == 40.0
    assert Metrics._pct([7.0], 0.50) == 7.0
    assert Metrics._pct([7.0], 0.99) == 7.0
    assert Metrics._pct([1.0, 2.0], 0.50) == 1.0
    assert Metrics._pct([1.0, 2.0], 0.99) == 2.0
    assert Metrics._pct([], 0.50) == 0.0


def test_metrics_endpoint_negotiation(tmp_path, obs_on):
    x = _walk(40 * 64).reshape(40, 64)
    szs = tmp_path / "m.szs"
    ArrayStore.save(str(szs), x, Bound.abs(1e-3), chunk_shape=(8, 64))
    service = make_service(str(szs))
    try:
        r = service.handle("GET", "/v1/stores/default/read?roi=0:8,:", {})
        assert r.status == 200
        # JSON default: legacy schema + additive obs key
        r = service.handle("GET", "/v1/metrics", {})
        assert r.content_type == "application/json"
        snap = json.loads(r.body)
        for k in ("requests", "errors", "bytes_sent", "by_route",
                  "by_status", "by_tenant", "latency", "cache"):
            assert k in snap
        assert snap["by_route"]["/v1/stores/default/read"] == 1
        assert "obs" in snap
        assert "serve.requests" in snap["obs"]["metrics"]
        assert "serve.request" in snap["obs"]["spans"]
        # Prometheus on Accept: text/plain
        r = service.handle("GET", "/v1/metrics", {"accept": "text/plain"})
        assert r.status == 200
        assert r.content_type.startswith("text/plain; version=0.0.4")
        text = r.body.decode()
        for line in text.strip().split("\n"):
            if line.startswith("#"):
                assert _PROM_TYPE.match(line), line
            else:
                assert _PROM_SAMPLE.match(line), line
        assert "szx_serve_requests" in text
        assert "szx_store_roi_reads" in text       # store counters flow in
    finally:
        service.close()


def test_metrics_endpoint_json_unchanged_when_disabled(tmp_path, obs_off):
    x = _walk(16 * 64).reshape(16, 64)
    szs = tmp_path / "m2.szs"
    ArrayStore.save(str(szs), x, Bound.abs(1e-3), chunk_shape=(8, 64))
    service = make_service(str(szs))
    try:
        service.handle("GET", "/info", {})
        r = service.handle("GET", "/v1/metrics", {})
        snap = json.loads(r.body)
        assert "obs" not in snap                   # additive key only when on
        assert snap["requests"] == 1
    finally:
        service.close()
