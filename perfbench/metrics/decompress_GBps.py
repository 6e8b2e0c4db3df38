"""decompress_GBps: raw bytes (1e9) restored by ``decompress_chunked``,
over the seconds spent restoring them, for every frame of the window."""


def read(ctx):
    return ctx.rate("decompress")
