"""roi.device_ms_per_read: device busy time inside the ``bench.roi``
annotations of the trace, in milliseconds, per read."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.count("roi"):
        return None
    busy = ctx.trace.busy_seconds("roi")
    return None if busy is None else busy * 1e3 / ctx.trace.count("roi")
