"""encode_program.assemble_ms_per_frame: device time (ms) of the encode
program's body layout, the ops under the ``szx_assemble`` scope (its
sections ``bitmap``, ``mu``, ``compact_reqlen``, ``compact_lcodes``,
``compact_mid``), inside the ``bench.compress`` annotations, per frame the
window compressed (a field's full frames and its tail alike)."""


def read(ctx):
    if ctx.trace is None:
        return None
    frames = ctx.window.frames("compress")
    busy = ctx.trace.scoped_seconds("compress", "szx_assemble")
    return busy * 1e3 / frames if busy and frames else None
