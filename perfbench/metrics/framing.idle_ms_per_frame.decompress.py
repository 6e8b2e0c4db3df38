"""framing.idle_ms_per_frame.decompress: device idle time (ms) inside the
program's ``container.*`` spans (``container.parse_frame``,
``container.parse_header``) within the ``bench.decompress`` annotations, per
frame (``device.decode.get`` span)."""

import spans


def read(ctx):
    return spans.idle_ms_per(ctx, "decompress", ("container.",), "device.decode.get")
