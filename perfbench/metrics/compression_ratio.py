"""compression_ratio: raw bytes over framed bytes, over every frame that
the window compressed."""


def read(ctx):
    units = ctx.units("compress")
    framed = sum(u.framed_bytes for u in units)
    return sum(u.raw_bytes for u in units) / framed if framed else None
