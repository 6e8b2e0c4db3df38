"""The trace reduction and the readers built on it, on a hand-built trace."""

import pytest
from jax.profiler import ProfileData

import devtrace
import run
import traffic

MS = 1_000_000 * 1000   # picoseconds in a millisecond

# device ops (ms): [0,5] and [3,8] overlap, then [10,12]; host annotations:
# compress [0,9], decompress [9,20]; a host event device_get [8,10]
TRACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {5 * MS} }}
    events {{ metadata_id: 2 offset_ps: {3 * MS} duration_ps: {5 * MS} }}
    events {{ metadata_id: 2 offset_ps: {10 * MS} duration_ps: {2 * MS} }}
  }}
  lines {{
    id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: {20 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "szx_encode_float32" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "scatter.7" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit__encode_device_jit" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 3 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {9 * MS} }}
    events {{ metadata_id: 2 offset_ps: {9 * MS} duration_ps: {11 * MS} }}
    events {{ metadata_id: 3 offset_ps: {8 * MS} duration_ps: {2 * MS} }}
  }}
  lines {{
    id: 4 name: "other thread" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: {20 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.compress" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.decompress" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "device_get" }} }}
}}
"""


@pytest.fixture(scope="module")
def red():
    xspace = ProfileData.text_proto_to_serialized_xspace(TRACE)
    return devtrace.Reduction(ProfileData.from_serialized_xspace(xspace).planes)


def test_interval_arithmetic():
    assert devtrace.union([(3, 8), (0, 5), (10, 12), (11, 11)]) == [(0, 8), (10, 12)]
    assert devtrace.overlap([(0, 8), (10, 12)], [(0, 9)]) == 8
    assert devtrace.overlap([(0, 8), (10, 12)], [(9, 20)]) == 2
    assert devtrace.gaps([(0, 8), (10, 12)], 0, 20) == [(8, 10), (12, 20)]


def test_busy_and_spans(red):
    assert red.span_seconds("compress") == pytest.approx(0.009)
    assert red.busy_seconds("compress") == pytest.approx(0.008)
    assert red.busy_seconds("decompress") == pytest.approx(0.002)
    assert red.busy_seconds() == pytest.approx(0.010)
    assert red.window_seconds() == pytest.approx(0.020)
    assert red.count("compress") == 1 and red.busy_seconds("roi") is None


def test_breakdown(red):
    ops = dict(red.top_ops())
    assert ops == pytest.approx({"scatter.7": 0.007, "szx_encode_float32": 0.005})
    idle = dict(red.idle_by_host())
    assert idle == pytest.approx({"bench.compress / device_get": 0.001,
                                  "bench.decompress / device_get": 0.001,
                                  "bench.decompress / -": 0.008})


def ctx(red, units):
    window = traffic.Window(units=units)
    return run.Context(1.0, window, red, {"hbm_bytes_per_s": 1e9})


def test_idle_and_rooflines(red):
    units = [traffic.Unit("compress", 0, 0.009, raw_bytes=6_000, framed_bytes=2_000),
             traffic.Unit("decompress", 0.009, 0.02, raw_bytes=6_000, framed_bytes=2_000)]
    c = ctx(red, units)
    assert run.load_reader("device_idle.compress").read(c) == pytest.approx(100 / 9)
    assert run.load_reader("device_idle.decompress").read(c) == pytest.approx(100 * 9 / 11)
    assert run.load_reader("device_idle.roi").read(c) is None
    enc = run.load_reader("encode_program_roofline")
    dec = run.load_reader("decode_program_roofline")
    assert enc.least_bytes(6_000, 2_000) == 8_000 == dec.least_bytes(2_000, 6_000)
    # 8000 B at 1e9 B/s is 8 us, against 8 ms and 2 ms of device busy time
    assert enc.read(c) == pytest.approx(0.1)
    assert dec.read(c) == pytest.approx(0.4)


def test_rooflines_silent_without_trace():
    c = run.Context(1.0, traffic.Window(units=[]), None, {"hbm_bytes_per_s": 1e9})
    for name in ("encode_program_roofline", "decode_program_roofline",
                 "device_idle.compress", "roi.device_ms_per_read"):
        assert run.load_reader(name).read(c) is None


def test_rates_and_latencies():
    units = [traffic.Unit("compress", 0, 0.5, 1_000_000_000, 250_000_000),
             traffic.Unit("compress", 0.5, 1.0, 1_000_000_000, 250_000_000),
             traffic.Unit("decompress", 1.0, 1.5, 1_000_000_000, 250_000_000)]
    units += [traffic.Unit("roi", i, i + (i + 1) / 1000, 1_000_000) for i in range(100)]
    c = run.Context(1.0, traffic.Window(units=units), None, {})
    assert run.load_reader("compress_GBps").read(c) == pytest.approx(2.0)
    assert run.load_reader("decompress_GBps").read(c) == pytest.approx(2.0)
    assert run.load_reader("compression_ratio").read(c) == pytest.approx(4.0)
    assert run.load_reader("roi_read_p95_ms").read(c) == pytest.approx(95.05)
    assert run.load_reader("roi.p50_ms").read(c) == pytest.approx(50.5)
    assert run.load_reader("setup_s").read(c) == 1.0


def test_unknown_device_kind_raises():
    with pytest.raises(run.NoChip):
        run.peak_of("cpu")
    assert run.peak_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_no_chip_raises():
    with pytest.raises(run.NoChip):
        run.chip(1, "float32")


def reduce(text, chips=None):
    xspace = ProfileData.text_proto_to_serialized_xspace(text)
    return devtrace.Reduction(ProfileData.from_serialized_xspace(xspace).planes, chips)


def test_silent_annotations_and_chip_filter(red):
    assert red.silent("compress") == 0 and red.silent("decompress") == 0
    last_op = f"events {{ metadata_id: 2 offset_ps: {10 * MS} duration_ps: {2 * MS} }}"
    assert last_op in TRACE
    dropped = reduce(TRACE.replace(last_op, ""))
    assert dropped.silent("compress") == 0 and dropped.silent("decompress") == 1
    assert reduce(TRACE, {0}).busy_seconds("compress") == pytest.approx(0.008)
    other = reduce(TRACE, {1})
    assert other.busy == {} and other.busy_seconds() is None
