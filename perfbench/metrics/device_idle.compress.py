"""device_idle.compress: share (%) of the time inside the ``bench.compress``
annotations in which no operation ran on the device."""


def read(ctx):
    if ctx.trace is None:
        return None
    span = ctx.trace.span_seconds("compress")
    busy = ctx.trace.busy_seconds("compress")
    return None if busy is None or not span else 100.0 * (1.0 - busy / span)
