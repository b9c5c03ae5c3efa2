"""95th percentile, over every query request due in the window, of the
time from its due time to the return of the flush that answered it; a
request never answered counts until the loop gave up on it."""
import numpy as np


def read(ctx):
    s, reqs = ctx["served"], ctx["reqs"]
    q = np.array([r.kind == "query" for r in reqs], bool)
    if not q.any():
        return None
    done = np.where(np.isnan(s["done"]), s["end"], s["done"])
    return float(np.percentile((done - s["due"])[q], 95) * 1e3)
