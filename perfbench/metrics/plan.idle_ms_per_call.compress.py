"""plan.idle_ms_per_call.compress: device idle time (ms) inside the
program's ``plan.*`` spans (``plan.resolve_error_bound``, ``plan.to_blocks``)
within the ``bench.compress`` annotations, per compress call."""

import spans


def read(ctx):
    return spans.idle_ms_per(ctx, "compress", ("plan.",))
