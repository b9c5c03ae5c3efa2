"""Engine queue: 95th percentile of the time a query request waited
from its due time to the start of the flush that served it."""
import numpy as np


def read(ctx):
    s, reqs = ctx["served"], ctx["reqs"]
    q = np.array([r.kind == "query" for r in reqs], bool) \
        & ~np.isnan(s["start"])
    if not q.any():
        return None
    return float(np.percentile((s["start"] - s["due"])[q], 95) * 1e3)
