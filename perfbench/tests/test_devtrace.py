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


# The same trace with each device op's scope (the ``tf_op`` stat of its event
# metadata, once as a string and once by reference), a decode gather op in
# the restore [13, 16] ms, and a second chip whose ``scatter.7`` has another
# scope.
SCOPED_OPS = """
    events { metadata_id: 4 offset_ps: %d duration_ps: %d }""" % (13 * MS, 3 * MS)
SCOPED_META = """
  event_metadata { key: 1 value { id: 1 name: "szx_encode_float32"
    stats { metadata_id: 10 str_value: "jit(_encode_device_jit)/szx_encode" } } }
  event_metadata { key: 2 value { id: 2 name: "scatter.7"
    stats { metadata_id: 10 ref_value: 11 } } }
  event_metadata { key: 4 value { id: 4 name: "gather.3"
    stats { metadata_id: 10 str_value: "jit(_decode_device_jit)/szx_gather/gather" } } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "jit(_encode_device_jit)/szx_assemble/compact_mid/scatter" } }
"""
OTHER_CHIP = f"""
planes {{
  id: 3 name: "/device:TPU:1"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 2 offset_ps: {2 * MS} duration_ps: {2 * MS} }}
  }}
  event_metadata {{ key: 2 value {{ id: 2 name: "scatter.7"
    stats {{ metadata_id: 10 str_value: "jit(other)/elsewhere" }} }} }}
  stat_metadata {{ key: 10 value {{ id: 10 name: "tf_op" }} }}
}}
"""


def scoped_trace(chips_text: str = "") -> str:
    last_op = f"events {{ metadata_id: 2 offset_ps: {10 * MS} duration_ps: {2 * MS} }}"
    meta = 'event_metadata { key: 3 value { id: 3 name: "jit__encode_device_jit" } }'
    assert last_op in TRACE and meta in TRACE
    return (TRACE.replace(last_op, last_op + SCOPED_OPS).replace(meta, meta + SCOPED_META)
            + chips_text)


def from_dir(tmp_path, text, chips=None):
    (tmp_path / "run").mkdir(exist_ok=True)
    (tmp_path / "run" / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return devtrace.Reduction.from_dir(str(tmp_path), chips)


def test_op_scopes_from_serialized_xspace():
    xspace = ProfileData.text_proto_to_serialized_xspace(scoped_trace())
    scopes, ambiguous = devtrace.op_scopes(xspace)
    assert scopes == {"szx_encode_float32": "jit(_encode_device_jit)/szx_encode",
                      "scatter.7": "jit(_encode_device_jit)/szx_assemble/compact_mid/scatter",
                      "gather.3": "jit(_decode_device_jit)/szx_gather/gather"}
    assert ambiguous == set()
    assert devtrace.op_scopes(ProfileData.text_proto_to_serialized_xspace(TRACE)) == ({}, set())


def test_a_name_of_two_scopes_is_ambiguous(tmp_path):
    both = from_dir(tmp_path, scoped_trace(OTHER_CHIP))
    assert both.ambiguous == {"scatter.7"} and "scatter.7" not in both.scopes
    # chip 0 ran scatter.7 [3, 8] and chip 1 [2, 4] inside the compress: 7 ms over 2 chips
    assert both.ambiguous_seconds("compress") == pytest.approx(0.0035)
    assert both.scoped_seconds("compress", "szx_assemble") == 0
    one = from_dir(tmp_path, scoped_trace(OTHER_CHIP), chips={0})
    assert one.ambiguous == set() and one.ambiguous_seconds("compress") == 0
    assert one.scoped_seconds("compress", "szx_assemble") == pytest.approx(0.005)


SCOPED_UNITS = [traffic.Unit("compress", 0, 0.009, 6_000, 2_000, frames=2),
                traffic.Unit("decompress", 0.009, 0.02, 6_000, 2_000, frames=2)]


def test_program_section_readers(tmp_path):
    c = ctx(from_dir(tmp_path, scoped_trace()), SCOPED_UNITS)
    # szx_assemble: scatter.7 [3, 8] inside the compress [0, 9], not its
    # [10, 12] in the restore; szx_gather: gather.3 [13, 16]; two frames each
    assert run.load_reader("encode_program.assemble_ms_per_frame").read(c) == pytest.approx(2.5)
    assert run.load_reader("decode_program.gather_ms_per_frame").read(c) == pytest.approx(1.5)
    assert c.trace.scoped_seconds("compress", "compact_mid") == pytest.approx(0.005)
    assert c.trace.scoped_seconds("compress", "compact") == 0      # a whole component only
    assert c.trace.scoped_seconds("roi", "szx_gather") is None


@pytest.mark.parametrize("name", ["encode_program.assemble_ms_per_frame",
                                  "decode_program.gather_ms_per_frame"])
def test_program_section_readers_silent(tmp_path, red, name):
    """Without a trace, without scopes (a trace that names none), or with
    the scope's ops all of ambiguous name, a reader returns nothing."""
    reader = run.load_reader(name)
    assert reader.read(ctx(None, SCOPED_UNITS)) is None
    assert reader.read(ctx(red, SCOPED_UNITS)) is None
    both = from_dir(tmp_path, scoped_trace(OTHER_CHIP))
    if name.startswith("encode"):
        assert reader.read(ctx(both, SCOPED_UNITS)) is None
