"""decode_program.gather_ms_per_frame: device time (ms) of the decode
program's byte gather, the ops under the ``szx_gather`` scope, inside the
``bench.decompress`` annotations, per frame the window restored."""


def read(ctx):
    if ctx.trace is None:
        return None
    frames = ctx.window.frames("decompress")
    busy = ctx.trace.scoped_seconds("decompress", "szx_gather")
    return busy * 1e3 / frames if busy and frames else None
