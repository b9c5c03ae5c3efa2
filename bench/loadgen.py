"""Data and traffic from ``--seed``: the one general generator.

Data are planted clusters, as ``chip_smoke.make_base`` and
``repro.data.VectorStream`` make them: unit centres, each vector a
centre plus per-dimension Gaussian noise, normalised to unit length, so
a vector's nearest neighbours lie in its cluster.  Vectors come in
groups of ``group_size`` consecutive ids: a SIFT point is a group of
one, a ColBERT document a group of 64 token vectors.

A traffic mix (``bench/traffic/<name>.json``) is data read here:

* ``rate``: requests per second, offered on an open loop;
* ``mix``: the share of each kind (``query``, ``insert``, ``delete``,
  ``update``).  Counts are exact: a run of ``T`` seconds holds
  ``round(rate * T)`` requests, split by largest remainder, in one
  order drawn from the seed, so every seed does the same work;
* arrivals: a Poisson process conditioned on the count of arrivals (the
  sorted uniform order statistics over the window);
* ``delete``: ``"oldest"`` (a retention window) or ``"uniform"``.

A query asks for the ``k`` nearest neighbours of ``query_size`` vectors
that share one due time, each a live vector of one random live group
plus Gaussian noise; an insert adds a new group; a delete removes a
live group; an update gives every vector of a random live group a new
vector.  The :class:`Workload` keeps the live set as the schedule
leaves it and every version of every id (:class:`Versions`); the
harness stamps each version with the flush that applied it, which is
all the reference needs.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("query", "insert", "delete", "update")
NEVER = np.iinfo(np.int64).max


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator per (seed, stream): any whole seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 63), *stream]))


class Planted:
    """Planted-cluster unit vectors (``repro.data.VectorStream``)."""

    def __init__(self, cfg: dict, seed: int):
        self.dim, self.noise = cfg["dim"], cfg["noise"]
        c = rng(seed, 0).standard_normal((cfg["n_clusters"], self.dim),
                                         dtype=np.float32)
        self.centers = c / np.linalg.norm(c, axis=1, keepdims=True)
        self.seed = seed

    def draw(self, stream: int, n: int) -> np.ndarray:
        r = rng(self.seed, 1, stream)
        v = self.centers[r.integers(0, len(self.centers), n)]
        v += r.standard_normal((n, self.dim), dtype=np.float32) * self.noise
        v /= np.linalg.norm(v, axis=1, keepdims=True)
        return v


class Versions:
    """Every version of every id: vector, id, and the flushes in which
    it is live, ``t_from <= f < t_to``.  The base set is live from
    flush -1 (set-up); a written version is live from the flush the
    harness stamps on it."""

    def __init__(self, base: np.ndarray):
        n = len(base)
        self.vec = [base]
        self.ids = [np.arange(n, dtype=np.int64)]
        self.t_from = np.full(n, -1, np.int64)
        self.t_to = np.full(n, NEVER, np.int64)
        self.n = self.n_base = n
        self.cur = {}               # id -> version index, for written ids

    def current(self, ids: np.ndarray) -> np.ndarray:
        return np.array([self.cur.get(int(i), int(i)) for i in ids],
                        np.int64)

    def add(self, ids: np.ndarray, vecs: np.ndarray) -> np.ndarray:
        """New versions of ``ids`` (live from when stamped)."""
        idx = np.arange(self.n, self.n + len(ids), dtype=np.int64)
        self.vec.append(vecs)
        self.ids.append(ids.astype(np.int64))
        self.t_from = np.concatenate([self.t_from,
                                      np.full(len(ids), NEVER, np.int64)])
        self.t_to = np.concatenate([self.t_to,
                                    np.full(len(ids), NEVER, np.int64)])
        self.n += len(ids)
        for i, v in zip(ids, idx):
            self.cur[int(i)] = int(v)
        return idx

    def freeze(self) -> None:
        self.vec = [np.concatenate(self.vec)]
        self.ids = [np.concatenate(self.ids)]

    @property
    def all_vec(self) -> np.ndarray:
        return self.vec[0] if len(self.vec) == 1 else np.concatenate(self.vec)

    @property
    def all_ids(self) -> np.ndarray:
        return self.ids[0] if len(self.ids) == 1 else np.concatenate(self.ids)

    def live_at(self, idx: np.ndarray, f: np.ndarray) -> np.ndarray:
        return (self.t_from[idx] <= f) & (f < self.t_to[idx])

    def special(self) -> np.ndarray:
        """Versions whose liveness changes during the run; every other
        version is a base vector live at every flush."""
        return np.flatnonzero((self.t_from >= 0) | (self.t_to != NEVER))


@dataclasses.dataclass
class Request:
    kind: str
    due: float                    # seconds after the window opens
    ids: np.ndarray               # ids written or deleted (empty: query)
    vecs: np.ndarray | None       # query vectors or written vectors
    opens: np.ndarray             # version indices this request makes live
    closes: np.ndarray            # version indices this request ends

    @property
    def n_ops(self) -> int:
        return len(self.vecs) if self.kind == "query" else len(self.ids)


class Workload:
    """The live set of groups as the schedule leaves it (module doc)."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.g = cfg.get("group_size", 1)
        self.q = cfg.get("query_size", 1)
        self.data = Planted(cfg, seed)
        n_groups = cfg["n_base"] // self.g
        self.base = self.data.draw(0, n_groups * self.g)
        self.versions = Versions(self.base)
        cap = n_groups + (1 << 16)
        self.live = np.zeros(cap, np.int64)       # live group list
        self.pos = np.full(cap, -1, np.int64)     # group -> index in live
        self.live[:n_groups] = np.arange(n_groups)
        self.pos[:n_groups] = np.arange(n_groups)
        self.n_live = n_groups
        self.n_groups = n_groups
        self.oldest = 0
        self.written: set[int] = set()            # ids written by traffic
        self.dead: set[int] = set()               # ids deleted, not back
        self._streams = 1

    def _stream(self) -> int:
        self._streams += 1
        return self._streams

    # -- live-group bookkeeping ----------------------------------------
    def _group_ids(self, g: int) -> np.ndarray:
        return np.arange(g * self.g, (g + 1) * self.g, dtype=np.int64)

    def _remove(self, grp: int) -> None:
        i = self.pos[grp]
        last = self.live[self.n_live - 1]
        self.live[i], self.pos[last] = last, i
        self.pos[grp] = -1
        self.n_live -= 1

    def _add_group(self) -> int:
        grp = self.n_groups
        self.n_groups += 1
        if grp >= len(self.pos):
            self.pos = np.concatenate([self.pos, np.full(len(self.pos), -1)])
            self.live = np.concatenate([self.live, np.zeros_like(self.live)])
        self.live[self.n_live], self.pos[grp] = grp, self.n_live
        self.n_live += 1
        return grp

    def random_live_group(self, r) -> int:
        return int(self.live[r.integers(0, self.n_live)])

    def vectors_of(self, ids: np.ndarray) -> np.ndarray:
        v = self.versions
        idx = v.current(ids)
        out = np.empty((len(ids), self.cfg["dim"]), np.float32)
        out[:] = v.all_vec[idx]
        return out

    # -- requests ------------------------------------------------------
    def query_vectors(self, r, n_queries: int) -> np.ndarray:
        """``n_queries`` queries of ``query_size`` vectors each."""
        qs = []
        for _ in range(n_queries):
            ids = self._group_ids(self.random_live_group(r))
            pick = r.choice(len(ids), self.q, replace=self.q > len(ids))
            qs.append(self.vectors_of(ids[pick]))
        qv = np.concatenate(qs) if qs else np.zeros((0, self.cfg["dim"]),
                                                    np.float32)
        qv = qv + r.standard_normal(qv.shape, dtype=np.float32) \
            * self.cfg["query_noise"]
        return qv.reshape(n_queries, self.q, -1)

    def segment(self, traffic: dict, seconds: float,
                stream: int | None = None) -> list[Request]:
        """The requests due in a window of ``seconds`` (module doc)."""
        stream = self._stream() if stream is None else stream
        r = rng(self.seed, 2, stream)
        n = max(1, int(round(traffic["rate"] * seconds)))
        mix = traffic["mix"]
        kinds = [k for k in KINDS if mix.get(k, 0) > 0]
        share = np.array([mix[k] for k in kinds], float)
        share /= share.sum()
        counts = np.floor(share * n).astype(int)
        rest = np.argsort(-(share * n - counts))[:n - counts.sum()]
        counts[rest] += 1
        order = r.permutation(np.repeat(np.arange(len(kinds)), counts))
        gaps = r.exponential(1.0, n + 1)
        due = np.cumsum(gaps)[:n] / gaps.sum() * seconds
        n_write = int(sum(c for k, c in zip(kinds, counts)
                          if k in ("insert", "update"))) * self.g
        fresh = self.data.draw(1 + stream, n_write)
        fresh_at = 0
        qvecs = self.query_vectors(r, int(counts[kinds.index("query")])
                                   if "query" in kinds else 0)
        q_at = 0
        out = []
        empty = np.zeros(0, np.int64)
        for i, kk in enumerate(order):
            kind = kinds[kk]
            if kind == "query":
                out.append(Request(kind, float(due[i]), empty, qvecs[q_at],
                                   empty, empty))
                q_at += 1
                continue
            if kind == "insert":
                grp = self._add_group()
            elif kind == "delete" and traffic.get("delete") == "oldest":
                while self.pos[self.oldest] < 0:
                    self.oldest += 1
                grp = self.oldest
            else:
                grp = self.random_live_group(r)
            ids = self._group_ids(grp)
            closes = empty if kind == "insert" else \
                self.versions.current(ids)
            if kind == "delete":
                self._remove(grp)
                self.dead.update(ids.tolist())
                self.written.difference_update(ids.tolist())
                out.append(Request(kind, float(due[i]), ids, None, empty,
                                   closes))
                continue
            vecs = fresh[fresh_at:fresh_at + len(ids)]
            fresh_at += len(ids)
            opens = self.versions.add(ids, vecs)
            self.written.update(ids.tolist())
            out.append(Request(kind, float(due[i]), ids, vecs, opens,
                               closes))
        self.versions.freeze()
        return out

    def retire_oldest(self, n_groups: int) -> np.ndarray:
        """Delete the ``n_groups`` oldest live groups before the window
        (set-up, flush -1): their ids, in order."""
        out = []
        for _ in range(n_groups):
            while self.pos[self.oldest] < 0:
                self.oldest += 1
            ids = self._group_ids(self.oldest)
            self.versions.t_to[self.versions.current(ids)] = -1
            self._remove(self.oldest)
            self.dead.update(ids.tolist())
            out.append(ids)
        return np.concatenate(out) if out else np.zeros(0, np.int64)

    # -- post-window probes (the fixed, seeded probe set) ----------------
    def probes(self, n_recall: int, n_self: int, n_gone: int) -> dict:
        """Queries issued after the window, from the live set the
        schedule leaves: ``recall`` (perturbed live vectors), ``self``
        (exact vectors of written and of random live ids: each must
        come back first) and ``gone`` (last vectors of deleted ids:
        none may come back)."""
        r = rng(self.seed, 3)
        out = {}
        qv = []
        for _ in range(n_recall):
            ids = self._group_ids(self.random_live_group(r))
            qv.append(self.vectors_of(ids[r.integers(0, len(ids), 1)]))
        qv = np.concatenate(qv)
        out["recall"] = (qv + r.standard_normal(qv.shape, dtype=np.float32)
                         * self.cfg["query_noise"], None)
        written = np.array(sorted(self.written), np.int64)
        w = r.choice(written, min(n_self // 2, len(written)),
                     replace=False) if len(written) else written
        rand = []
        while len(rand) < n_self - len(w):
            ids = self._group_ids(self.random_live_group(r))
            rand.append(int(ids[r.integers(0, len(ids))]))
        self_ids = np.concatenate([w, np.array(rand, np.int64)])
        out["self"] = (self.vectors_of(self_ids), self_ids)
        dead = np.array(sorted(self.dead), np.int64)
        gone = r.choice(dead, min(n_gone, len(dead)), replace=False) \
            if len(dead) else dead
        out["gone"] = (self.vectors_of(gone), gone)
        return out
