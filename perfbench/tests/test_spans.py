"""The readers of program spans, on the hand-built trace of
``test_devtrace.py`` extended with them."""

import pytest
from jax.profiler import ProfileData

import devtrace
import run
import spans
import traffic
from test_devtrace import MS, TRACE

US = MS // 1000    # picoseconds in a microsecond

# program spans on the annotated host line, (name, start us, end us):
# compress [0, 9] ms, device busy [0, 8]; decompress [9, 20] ms, busy [10, 12]
PROGRAM_SPANS = [
    ("plan.resolve_error_bound", 0, 500),
    ("device.encode.dispatch", 500, 1000),
    ("device.encode.get", 1000, 8200),           # idle 0.2 ms
    ("plan.to_blocks", 8200, 8300),              # idle 0.1 ms
    ("container.serialize", 8300, 8600),         # idle 0.3 ms
    ("container.build_frame", 8600, 8800),       # idle 0.2 ms
    ("container.parse_frame", 9000, 9100),       # idle 0.1 ms
    ("container.parse_header", 9100, 9200),      # idle 0.1 ms
    ("codec.decompress", 9200, 19000),           # whole call: no layer reads it
    ("container.parse_header", 9200, 9300),      # idle 0.1 ms
    ("device.decode.stage", 9300, 9600),         # idle 0.3 ms
    ("device.decode.put", 9600, 9800),           # idle 0.2 ms
    ("device.decode.dispatch", 9800, 10000),     # idle 0.2 ms
    ("device.decode.get", 10000, 13000),         # idle 1.0 ms
    ("device.decode.copy_out", 13000, 14000),    # idle 1.0 ms
    ("device.decode.get", 15000, 16000),         # idle 1.0 ms, a second frame
]


def with_program(trace: str, host_spans) -> str:
    """``trace`` with ``host_spans`` added to its ``python`` line."""
    names = sorted({n for n, _, _ in host_spans})
    ids = {n: 100 + i for i, n in enumerate(names)}
    events = "".join(f"    events {{ metadata_id: {ids[n]} offset_ps: {s * US} "
                     f"duration_ps: {(e - s) * US} }}\n" for n, s, e in host_spans)
    meta = "".join(f'  event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} name: "{n}" }} }}\n'
                   for n in names)
    line = 'id: 3 name: "python" timestamp_ns: 0\n'
    plane = 'id: 2 name: "/host:CPU"\n'
    assert line in trace and plane in trace
    return trace.replace(line, line + events).replace(plane, plane + meta)


def reduce(text):
    xspace = ProfileData.text_proto_to_serialized_xspace(text)
    return devtrace.Reduction(ProfileData.from_serialized_xspace(xspace).planes)


@pytest.fixture(scope="module")
def plain():
    return reduce(TRACE)


@pytest.fixture(scope="module")
def red():
    return reduce(with_program(TRACE, PROGRAM_SPANS))


def ctx(trace, units=()):
    return run.Context(1.0, traffic.Window(units=list(units)), trace, {"hbm_bytes_per_s": 1e9})


UNITS = [traffic.Unit("compress", 0, 0.009, raw_bytes=6_000, framed_bytes=2_000),
         traffic.Unit("decompress", 0.009, 0.02, raw_bytes=6_000, framed_bytes=2_000)]

NEW_ROUNDTRIP = {
    "plan.idle_ms_per_call.compress": 0.1,
    "framing.idle_ms_per_frame.compress": 0.5,
    "staging.idle_ms_per_frame.compress": 0.2,
    "framing.idle_ms_per_frame.decompress": 0.3 / 2,
    "staging.idle_ms_per_frame.decompress": 3.7 / 2,
}


def test_reduction_keeps_spans(red):
    host = {name for _, _, name in red.host}
    assert {n for n, _, _ in PROGRAM_SPANS} <= host
    assert spans.count(red, "compress", "device.encode.get") == 1
    assert spans.count(red, "decompress", "device.decode.get") == 2
    assert spans.count(red, "compress", "device.decode.get") == 0


def test_interval_helpers():
    assert spans.intersect([(0, 4), (6, 9)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert spans.intersect([(0, 1)], [(2, 3)]) == []


@pytest.mark.parametrize("name", sorted(NEW_ROUNDTRIP))
def test_new_reader_values(red, name):
    assert run.load_reader(name).read(ctx(red, UNITS)) == pytest.approx(NEW_ROUNDTRIP[name])


@pytest.mark.parametrize("name", sorted(NEW_ROUNDTRIP) + ["roi.store_idle_ms_per_read"])
def test_new_readers_silent_without_program_spans(plain, name):
    """A program that records no spans (the parent of this instrumentation)
    reads None, and no reader raises."""
    assert run.load_reader(name).read(ctx(plain, UNITS)) is None
    assert run.load_reader(name).read(ctx(None, UNITS)) is None


@pytest.mark.parametrize("name", ["device_idle.compress", "device_idle.decompress",
                                  "device_idle.roi", "encode_program_roofline",
                                  "decode_program_roofline", "roi.device_ms_per_read"])
def test_existing_readers_unchanged(plain, red, name):
    reader = run.load_reader(name)
    assert reader.read(ctx(red, UNITS)) == reader.read(ctx(plain, UNITS))


def test_layers_add_up_to_the_idle(red):
    """Plan, framing and staging name all the idle inside the calls that the
    hand-built spans cover: 1.0 of compress's 1.0 ms but the 0.2 ms after
    the last span, 4.0 of decompress's 9.0 ms."""
    named = sum(spans.idle_seconds(red, "compress", names)
                for names in (("plan.",), ("container.",), ("device.encode.",)))
    assert named == pytest.approx(0.0008)
    assert red.span_seconds("compress") - red.busy_seconds("compress") == pytest.approx(0.001)
    named = sum(spans.idle_seconds(red, "decompress", names)
                for names in (("container.",), ("device.decode.",)))
    assert named == pytest.approx(0.004)


def test_breakdown_names_program_spans(red):
    """The breakdown names each idle stretch by the innermost host event at
    its midpoint: now a program span, where it was ``-`` or ``device_get``."""
    assert dict(red.idle_by_host()) == pytest.approx({
        "bench.compress / container.serialize": 0.001,
        "bench.decompress / device.decode.stage": 0.001,
        "bench.decompress / device.decode.get": 0.008})


# two ROI reads of 10 ms; device busy [2, 4] and [12, 14] ms
ROI_TRACE = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {2 * MS} duration_ps: {2 * MS} }}
    events {{ metadata_id: 1 offset_ps: {12 * MS} duration_ps: {2 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{
    id: 3 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {10 * MS} }}
    events {{ metadata_id: 1 offset_ps: {10 * MS} duration_ps: {10 * MS} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.roi" }} }}
}}
"""

ROI_SPANS = []
for t in (0, 10_000):
    ROI_SPANS += [
        ("store.open", t, t + 1000),                        # idle 1.0 ms
        ("store.read", t + 1000, t + 9000),                 # whole call: not read
        ("store.chunk.read_prefix", t + 1000, t + 1500),    # idle 0.5 ms
        ("store.chunk.read_mid", t + 1500, t + 1700),       # idle 0.2 ms
        ("device.decode.get", t + 1700, t + 5000),          # idle 1.3 ms
        ("store.assemble", t + 5000, t + 6000),             # idle 1.0 ms
    ]


def test_roi_store_idle():
    red = reduce(with_program(ROI_TRACE, ROI_SPANS))
    units = [traffic.Unit("roi", 0, 0.01, 1_000_000), traffic.Unit("roi", 0.01, 0.02, 1_000_000)]
    c = ctx(red, units)
    assert run.load_reader("roi.store_idle_ms_per_read").read(c) == pytest.approx(2.7)
    assert spans.idle_ms_per(c, "roi", ("device.decode.",)) == pytest.approx(1.3)
    assert run.load_reader("roi.device_ms_per_read").read(c) == pytest.approx(2.0)
    assert run.load_reader("device_idle.roi").read(c) == pytest.approx(80.0)
