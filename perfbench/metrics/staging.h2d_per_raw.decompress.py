"""staging.h2d_per_raw.decompress: bytes the program put on the device
while restoring (``repro.obs`` counter ``device.put.bytes`` with
``op=decode_stream``) per raw byte it restored."""


def read(ctx):
    put = ctx.counter("device.put.bytes", op="decode_stream")
    raw = sum(u.raw_bytes for u in ctx.units("decompress"))
    return put / raw if put and raw else None
