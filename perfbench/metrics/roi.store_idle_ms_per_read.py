"""roi.store_idle_ms_per_read: device idle time (ms) inside the store's
spans (``store.open``, ``store.chunk.read_prefix``,
``store.chunk.read_mid``, ``store.assemble``; not the whole-call
``store.read``) within the ``bench.roi`` annotations, per read."""

import spans

STORE_STAGES = ("store.open", "store.chunk.", "store.assemble")


def read(ctx):
    return spans.idle_ms_per(ctx, "roi", STORE_STAGES)
