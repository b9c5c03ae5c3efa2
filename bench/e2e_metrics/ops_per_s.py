"""Vector operations of the window's whole schedule over the window and
the drain past its close: from the window's opening to the return of
the flush that answered its last request, or to the close if that came
first.  Every round and every maintenance epoch that the schedule
causes counts.  A query counts its query vectors, an insert, delete or
update the vectors it writes or removes."""
import numpy as np


def read(ctx):
    s, reqs = ctx["served"], ctx["reqs"]
    done = ~np.isnan(s["done"])
    if not done.any():
        return None
    ops = np.array([r.n_ops for r in reqs], float)
    return float(ops[done].sum()
                 / max(np.max(s["done"][done]), ctx["seconds"]))
