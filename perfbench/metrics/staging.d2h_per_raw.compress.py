"""staging.d2h_per_raw.compress: bytes the program fetched from the device
while compressing (``repro.obs`` counter ``device.get.bytes`` with
``op=encode_stream``) per raw byte it compressed."""


def read(ctx):
    got = ctx.counter("device.get.bytes", op="encode_stream")
    raw = sum(u.raw_bytes for u in ctx.units("compress"))
    return got / raw if got and raw else None
