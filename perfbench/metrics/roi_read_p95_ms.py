"""roi_read_p95_ms: 95th percentile, in milliseconds, of every ROI read
of the window (open, read, close)."""

import numpy as np


def read(ctx):
    ms = [u.seconds * 1e3 for u in ctx.units("roi")]
    return float(np.percentile(ms, 95)) if ms else None
