"""roi.p50_ms: median, in milliseconds, of the window's ROI reads."""

import numpy as np


def read(ctx):
    ms = [u.seconds * 1e3 for u in ctx.units("roi")]
    return float(np.median(ms)) if ms else None
