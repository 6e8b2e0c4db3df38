"""compress_GBps: raw bytes (1e9) of the field turned into frames, over
the seconds spent in ``compress_chunked``, for every frame of the window."""


def read(ctx):
    return ctx.rate("compress")
