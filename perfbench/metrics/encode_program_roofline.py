"""encode_program_roofline: the least time the chip could take to encode
the window's frames, over the device busy time inside the ``bench.compress``
annotations (%).  The least time counts what the algorithm must move: the
raw field read once and the framed bytes written once, at the HBM peak of the
chip (``peaks.json``); it is bound by memory, the encode doing a few integer
operations per byte."""


def least_bytes(raw_bytes: int, framed_bytes: int) -> int:
    return raw_bytes + framed_bytes


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy_seconds("compress")
    units = ctx.units("compress")
    if not busy or not units:
        return None
    moved = least_bytes(sum(u.raw_bytes for u in units), sum(u.framed_bytes for u in units))
    return 100.0 * moved / (ctx.peak["hbm_bytes_per_s"] * busy)
