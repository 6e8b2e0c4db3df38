"""staging.idle_ms_per_frame.compress: device idle time (ms) inside the
program's ``device.encode.*`` spans (the raw frame's put with the program's
dispatch, and the body's fetch) within the ``bench.compress`` annotations,
per frame (``device.encode.get`` span)."""

import spans


def read(ctx):
    return spans.idle_ms_per(ctx, "compress", ("device.encode.",), "device.encode.get")
