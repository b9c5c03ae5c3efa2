"""The plain reference: exact k nearest neighbours by brute force.

Numpy only, independent of ``src/``.  A query answered by flush ``f``
is compared against every version live at ``f``
(``loadgen.Versions``): the index's window ordering lets a flush apply
all its writes before any of its queries, so that is exactly the set
the answer may draw from.  Distances are the index's: squared L2 for
``l2``, ``1 - cos`` for ``angular``.
"""
from __future__ import annotations

import numpy as np

CHUNK = 1 << 18           # versions per block
QBLOCK = 128              # queries per block


def distance(q: np.ndarray, x: np.ndarray, metric: str) -> np.ndarray:
    """Row-wise distance in float64: (n, d), (n, d) -> (n,)."""
    q = q.astype(np.float64)
    x = x.astype(np.float64)
    if metric == "angular":
        qn = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        xn = x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
        return 1.0 - np.sum(qn * xn, axis=-1)
    return np.sum((q - x) ** 2, axis=-1)


def topk(qvecs: np.ndarray, flushes: np.ndarray, versions, k: int,
         metric: str) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k ids (and distances) of each query over the versions
    live at its flush.  (Q, d), (Q,) -> (Q, k) ids, (Q, k) float32."""
    allv, allid = versions.all_vec, versions.all_ids
    nq = len(qvecs)
    if metric == "angular":
        xnorm = np.linalg.norm(allv, axis=1)
    xs = np.einsum("nd,nd->n", allv, allv)
    special = versions.special()
    best_d = np.full((nq, k), np.inf, np.float32)
    best_i = np.zeros((nq, k), np.int64)
    for q0 in range(0, nq, QBLOCK):
        q = qvecs[q0:q0 + QBLOCK].astype(np.float32)
        f = flushes[q0:q0 + QBLOCK]
        if metric == "angular":
            q = q / np.maximum(np.linalg.norm(q, axis=1, keepdims=True),
                               1e-12)
        bd, bi = best_d[q0:q0 + QBLOCK], best_i[q0:q0 + QBLOCK]
        for s in range(0, len(allv), CHUNK):
            x = allv[s:s + CHUNK]
            dots = q @ x.T
            if metric == "angular":
                d = 1.0 - dots / np.maximum(xnorm[s:s + CHUNK], 1e-12)[None]
            else:
                d = xs[s:s + CHUNK][None] - 2.0 * dots \
                    + np.einsum("qd,qd->q", q, q)[:, None]
            sp = special[(special >= s) & (special < s + len(x))]
            if len(sp):
                live = versions.live_at(sp[None], f[:, None])
                d[:, sp - s] = np.where(live, d[:, sp - s], np.inf)
            part = np.argpartition(d, k, axis=1)[:, :k]
            cd = np.concatenate([bd, np.take_along_axis(d, part, 1)], 1)
            ci = np.concatenate([bi, part + s], 1)
            sel = np.argsort(cd, axis=1, kind="stable")[:, :k]
            bd[:] = np.take_along_axis(cd, sel, 1)
            bi[:] = np.take_along_axis(ci, sel, 1)
    ids = np.where(np.isfinite(best_d), allid[best_i], -1)
    return ids, best_d


def version_at(versions, ids: np.ndarray, flushes: np.ndarray) -> np.ndarray:
    """Index of the version of each id live at its flush, -1 if none."""
    out = np.full(len(ids), -1, np.int64)
    chains: dict[int, list[int]] = {}
    allid = versions.all_ids
    for v in versions.special():
        chains.setdefault(int(allid[v]), []).append(int(v))
    for j, (i, f) in enumerate(zip(ids.tolist(), flushes.tolist())):
        cand = chains.get(i)
        if cand is None:
            # an id never touched during the run: its base version
            if 0 <= i < versions.n_base:
                out[j] = i
            continue
        for v in cand:
            if versions.t_from[v] <= f < versions.t_to[v]:
                out[j] = v
    return out
