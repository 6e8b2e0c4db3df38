"""framing.idle_ms_per_frame.compress: device idle time (ms) inside the
program's ``container.*`` spans (``container.serialize``,
``container.build_frame``) within the ``bench.compress`` annotations, per
frame (``device.encode.get`` span)."""

import spans


def read(ctx):
    return spans.idle_ms_per(ctx, "compress", ("container.",), "device.encode.get")
