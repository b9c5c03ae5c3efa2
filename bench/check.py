"""The comparison that decides ``correct``.

Each answer is a query vector, the flush that answered it, and the ids
and distances the index returned.  Against the plain reference
(``reference.py``) over the versions live at that flush, the numbers
compared, each with its limit from the configuration's ``limits``:

* ``missing``: requests due in the window never answered (limit 0);
* ``dead_returned``: returned ids with no live version at that flush:
  deleted, not yet written, or unknown (limit 0, exact);
* ``dist_err_max``: the widest gap between a returned distance and the
  reference's distance to that id's live version (float64);
* ``self_miss``: exact vectors of acknowledged writes and of random
  live ids whose own id is not returned first within ``self_dist_max``
  (limit 0, exact).

Recall is not among them: the index is approximate by design, and the
bfloat16 control (``control.py``) reads a recall as high as sound runs
do, so no recall limit separates the two.  Recall is an end-to-end
metric with a bound (:func:`recall`).
"""
from __future__ import annotations

import numpy as np

import reference


def recall(a: dict, versions, cfg: dict) -> float:
    """Mean recall@k of one group of answers against the reference."""
    if not len(a["q"]):
        return float("nan")
    want, _ = reference.topk(a["q"], a["flush"], versions, cfg["k"],
                             cfg["metric"])
    return float(np.mean([len(set(g[g >= 0].tolist())
                              & set(w[w >= 0].tolist())) / cfg["k"]
                          for g, w in zip(a["ids"], want)]))


def compare(answers: dict, versions, cfg: dict, missing: int) -> dict:
    """``answers``: group name -> dict of ``q`` (n, d), ``flush`` (n,),
    ``ids`` (n, k), ``dists`` (n, k) and, for ``self``, ``expect``.
    Groups: ``window`` (a seeded sample of window answers), ``recall``,
    ``self``, ``gone``.  Returns {name: (value, "<=", limit)}."""
    metric, k = cfg["metric"], cfg["k"]
    lim = cfg["limits"]
    dead = 0
    err = 0.0
    out = {}
    for name, a in answers.items():
        if not len(a["q"]):
            continue
        ids, dists = np.asarray(a["ids"]), np.asarray(a["dists"])
        flush = np.repeat(a["flush"], k)
        flat = ids.reshape(-1)
        got = flat >= 0
        ver = reference.version_at(versions, flat[got], flush[got])
        dead += int(np.sum(ver < 0))
        ok = ver >= 0
        if ok.any():
            qrow = np.repeat(np.arange(len(ids)), k)[got][ok]
            dref = reference.distance(a["q"][qrow],
                                      versions.all_vec[ver[ok]], metric)
            err = max(err, float(np.max(np.abs(
                dists.reshape(-1)[got][ok].astype(np.float64) - dref))))
        if name == "self":
            hit = (ids[:, 0] == a["expect"]) & \
                (dists[:, 0] <= lim["self_dist_max"])
            out["self_miss"] = (int(np.sum(~hit)), "<=", 0)
    out["missing"] = (int(missing), "<=", 0)
    out["dead_returned"] = (dead, "<=", 0)
    out["dist_err_max"] = (err, "<=", lim["dist_err_max"])
    return out


def holds(value, rule: str, limit) -> bool:
    """Every number compared is held from above (``rule`` is "<=")."""
    return value <= limit


def verdict(numbers: dict) -> bool:
    return all(holds(*v) for v in numbers.values())
