"""decode_program_roofline: the least time the chip could take to decode
the window's frames, over the device busy time inside the
``bench.decompress`` annotations (%).  The least time counts the framed bytes
read once and the raw field written once, at the HBM peak of the chip
(``peaks.json``)."""


def least_bytes(framed_bytes: int, raw_bytes: int) -> int:
    return framed_bytes + raw_bytes


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy_seconds("decompress")
    units = ctx.units("decompress")
    if not busy or not units:
        return None
    moved = least_bytes(sum(u.framed_bytes for u in units), sum(u.raw_bytes for u in units))
    return 100.0 * moved / (ctx.peak["hbm_bytes_per_s"] * busy)
