"""staging.idle_ms_per_frame.decompress: device idle time (ms) inside the
program's ``device.decode.*`` spans (stage, put, dispatch, get, copy out)
within the ``bench.decompress`` annotations, per frame
(``device.decode.get`` span)."""

import spans


def read(ctx):
    return spans.idle_ms_per(ctx, "decompress", ("device.decode.",), "device.decode.get")
