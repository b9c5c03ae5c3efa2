"""Chip smoke: the served PFO path once, on a TPU, at SIFT-1M scale.

Deployment: the shape of ANN-benchmarks SIFT-128-euclidean (Aumueller
et al., arXiv 1807.05614) — 1,000,000 base vectors, d=128, f32, L2 —
with every vector, the LSH forests, the sealed ring and the MainTable
resident on one chip (no cold tier).  Data is generated from
``--seed``: clustered unit vectors (``repro.data.VectorStream``) with
planted neighbourhoods, so each point's top-10 lies in its cluster.

One process, phases in order (no phase's exception is caught):

1. compile the served insert and query steps and assert the Pallas
   kernels are in them (``tpu_custom_call``);
2. bulk-load the 1M vectors through ``PFOIndex.insert`` in fixed-size
   batches;
3. ``StreamEngine.warmup()``;
4. serve an interleaved 50/25/12.5/12.5 query/insert/delete/update
   stream through ``StreamEngine`` at k=10, with one seal epoch forced
   midway through the engine's API; the delete/update churn fills the
   tombstone buffer and fires a merge epoch;
5. check: exact self-queries come back first at distance ~0, deleted
   ids never come back, recall@10 against a numpy brute force over the
   live set is >= 0.90.

``--chips 4`` runs only the sharded path and what it is compared
with: the single-chip ``StreamEngine`` and ``DistStreamEngine`` on a
(1, 4) mesh in the same process, each fed the same 1M load and the
same (shorter, merge-free) trace through the engine API; it asserts
the same checks plus equal result ids between the two engines, and
prints per-device peak bytes.

Times printed here are smoke timings of one run (compile included
where marked), not benchmark results.  The last stdout line is one
JSON object naming the device.  Exits non-zero, with no result line,
when JAX finds no TPU or any check fails.

    python3 chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_BASE = 1_000_000
DIM = 128
LOAD_BATCH = 15_625              # 64 equal bulk-load batches
N_CLUSTERS = 16_384              # ~61 points per planted neighbourhood
NOISE = 0.01                     # per-dim cluster spread (unit centers)
QUERY_NOISE = 0.005              # per-dim perturbation of recall queries
N_REQUESTS = 6_000
# the four-chip trace stays under the tombstone-merge watermark, so that
# call compiles no merge program (each takes minutes for v5e)
N_REQUESTS_4 = 2_000
LOAD_BUCKET_4 = 1_024
MIX = (0.5, 0.25, 0.125, 0.125)  # query / insert / delete / update
K = 10
FLUSH_EVERY = 256
BUCKET = 64                      # the engine's one micro-batch size
N_CHECK = 256                    # queries per final check
RECALL_FLOOR = 0.90


def smoke_config():
    """PFOConfig holding all 1M vectors on one chip, cold tier off.

    A sealed LSH probe reads at most ``snap_budget_per_probe`` entries
    of its bucket prefix (the MainTable ring is searched by full key and
    does not depend on it).  ``snap_prefix_bits=20`` keeps an LSH
    bucket at its planted neighbours sharing the prefix (~20) plus a
    stranger or two; a budget of 64 reads such a bucket whole, also on
    a shard of the (1, 4) mesh, whose segments mix the tables it owns —
    so the sharded and the single-chip engine rank the same candidates.
    """
    from repro.core import PFOConfig
    return PFOConfig(dim=DIM, metric="l2", L=10, C=4, m=4,
                     max_leaves_per_tree=4096, max_nodes_per_tree=512,
                     main_max_leaves_per_tree=16384,
                     main_max_nodes_per_tree=1024,
                     store_capacity=1 << 21, snap_prefix_bits=20,
                     snap_budget_per_probe=64)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok, msg="") -> None:
    """A failed check fails the smoke (and survives ``python -O``)."""
    if not ok:
        raise AssertionError(msg)


# ----------------------------------------------------------------------
# data + request trace (host numpy, from the seed)
# ----------------------------------------------------------------------
def make_base(seed: int, n: int, dim: int):
    from repro.data import VectorStream
    vs = VectorStream(dim=dim, n_clusters=N_CLUSTERS, seed=seed, noise=NOISE)
    _, vecs = vs.batch(0, n)
    return vs, vecs


def make_trace(vs, base: np.ndarray, n_requests: int, seed: int):
    """Interleaved (kind, *args) requests over the loaded base set."""
    rng = np.random.default_rng(seed + 1)
    n_base, dim = base.shape
    kinds = rng.choice(4, size=n_requests, p=MIX)
    _, fresh = vs.batch(1, n_requests)         # insert / update payloads
    reqs, next_id = [], n_base
    for i, kd in enumerate(kinds):
        if kd == 0:
            src = base[int(rng.integers(0, n_base))]
            q = src + rng.normal(size=dim).astype(np.float32) * QUERY_NOISE
            reqs.append(("query", q.astype(np.float32), K))
        elif kd == 1:
            reqs.append(("insert", next_id, fresh[i]))
            next_id += 1
        elif kd == 2:
            reqs.append(("delete", int(rng.integers(0, next_id))))
        else:
            reqs.append(("update", int(rng.integers(0, n_base)), fresh[i]))
    return reqs, next_id


class LiveSet:
    """Host model of the index contents: id -> current vector, live."""

    def __init__(self, base: np.ndarray, capacity: int):
        self.vec = np.zeros((capacity, base.shape[1]), np.float32)
        self.vec[:len(base)] = base
        self.live = np.zeros(capacity, bool)
        self.live[:len(base)] = True
        self.ever = self.live.copy()               # inserted at some point
        self.touched = np.zeros(capacity, bool)    # written by the stream

    def apply(self, req) -> None:
        kind = req[0]
        if kind in ("insert", "update"):
            self.vec[req[1]] = req[2]
            self.live[req[1]] = True
            self.ever[req[1]] = True
            self.touched[req[1]] = True
        elif kind == "delete":
            self.live[req[1]] = False


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def assert_kernels_compiled(index, engine) -> None:
    """Compile the served insert/query programs; the Pallas kernels
    must be in them (not interpret mode, not the ref.py swap)."""
    import jax
    import jax.numpy as jnp
    from repro.core.index import insert_step, query_step

    cfg, be = index.cfg, engine.backend
    b = engine.scfg.max_batch
    mcap, lcap = be.capacities(b)
    fm, fl = be._flags_caps
    programs = {
        "insert": insert_step.lower(
            index.state, jnp.zeros((b,), jnp.int32),
            jnp.zeros((b, cfg.dim), jnp.float32),
            jnp.full((b,), -2, jnp.int32), jnp.zeros((b,), bool),
            jnp.zeros((b * cfg.L,), bool), cfg, mcap, lcap, fm, fl),
        "query": query_step.lower(index.state,
                                  jnp.zeros((b, cfg.dim), jnp.float32),
                                  cfg, K),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        n_kern = compiled.as_text().count("tpu_custom_call")
        mem = compiled.memory_analysis()
        log(f"compiled {name}_step bucket={b}: tpu_custom_call x{n_kern}; "
            f"argument_bytes={mem.argument_size_in_bytes} "
            f"output_bytes={mem.output_size_in_bytes} "
            f"temp_bytes={mem.temp_size_in_bytes}")
        check(n_kern > 0, f"{name}_step has no Pallas kernel")


def bulk_load(index, base: np.ndarray) -> None:
    n = len(base)
    t0 = time.perf_counter()
    for s in range(0, n, LOAD_BATCH):
        ids = np.arange(s, min(s + LOAD_BATCH, n), dtype=np.int32)
        index.insert(ids, base[s:s + LOAD_BATCH])
        done = s + len(ids)
        if done % (8 * LOAD_BATCH) == 0 or done == n:
            log(f"loaded {done} rows, {time.perf_counter() - t0:.3f} s, "
                f"maintenance={_counts(index.maintenance_log)}")


def serve(engine, reqs, live: LiveSet, seal_at: int):
    """Drive the trace through the engine API; returns query results
    as (request index, ids, dists)."""
    tickets = {}
    out = []

    def flush():
        res = engine.flush()
        for t, i in list(tickets.items()):
            if t in res:
                out.append((i, *res[t]))
                del tickets[t]

    for i, req in enumerate(reqs):
        if i == seal_at:
            flush()
            engine.seal()
        kind = req[0]
        if kind == "query":
            tickets[engine.query(req[1], req[2])] = i
        else:
            getattr(engine, kind)(*req[1:])
        live.apply(req)
        if (i + 1) % FLUSH_EVERY == 0:
            flush()
    flush()
    return out


def query_all(engine, qvecs: np.ndarray):
    tickets = [engine.query(q, K) for q in qvecs]
    res = engine.flush()
    ids = np.stack([np.asarray(res[t][0]) for t in tickets])
    dists = np.stack([np.asarray(res[t][1]) for t in tickets])
    return ids, dists


def brute_force(live: LiveSet, qvecs: np.ndarray, k: int) -> np.ndarray:
    """Exact L2 top-k ids over the live set (numpy, chunked)."""
    lid = np.flatnonzero(live.live)
    x = live.vec[lid]
    xs = np.einsum("nd,nd->n", x, x)
    best_d = np.full((len(qvecs), k), np.inf)
    best_i = np.zeros((len(qvecs), k), np.int64)
    for s in range(0, len(x), 1 << 18):
        xc = x[s:s + (1 << 18)]
        d = xs[s:s + (1 << 18)][None] - 2.0 * (qvecs @ xc.T)
        part = np.argpartition(d, k, axis=1)[:, :k]
        cand_d = np.concatenate([best_d, np.take_along_axis(d, part, 1)], 1)
        cand_i = np.concatenate([best_i, part + s], 1)
        sel = np.argsort(cand_d, axis=1)[:, :k]
        best_d = np.take_along_axis(cand_d, sel, 1)
        best_i = np.take_along_axis(cand_i, sel, 1)
    return lid[best_i]


def check_results(engine, live: LiveSet, rng, label: str) -> dict:
    """Self-query, deleted-id and recall checks against the host model;
    every result is logged before any check asserts.  Returns the
    (ids, dists) of each check's queries."""
    lid = np.flatnonzero(live.live)
    dead = np.flatnonzero(live.ever & ~live.live)
    # self-queries: random live ids plus ids the stream (re)wrote
    written = np.flatnonzero(live.live & live.touched)
    n_written = min(64, len(written))
    pick = np.concatenate([
        rng.choice(lid, N_CHECK - n_written, replace=False),
        rng.choice(written, n_written, replace=False)])
    out = {"self": query_all(engine, live.vec[pick])}
    # deleted ids: query with their last vectors; they must not return
    gone = rng.choice(dead, min(N_CHECK, len(dead)), replace=False)
    out["gone"] = query_all(engine, live.vec[gone])
    # recall@10 of perturbed live vectors vs the exact live-set oracle
    src = rng.choice(lid, N_CHECK, replace=False)
    qv = (live.vec[src] + rng.normal(size=(N_CHECK, live.vec.shape[1]))
          .astype(np.float32) * QUERY_NOISE).astype(np.float32)
    out["recall"] = query_all(engine, qv)
    oracle = brute_force(live, qv, K)
    rids = out["recall"][0]
    recall = float(np.mean([len(set(rids[i]) & set(oracle[i])) / K
                            for i in range(N_CHECK)]))
    ids, dists = out["self"]
    top1 = float(np.mean(ids[:, 0] == pick))
    seen = np.concatenate([o[0].ravel() for o in out.values()])
    resurfaced = np.intersect1d(seen[seen >= 0], dead)
    log(f"{label}: self-query top-1 exact {top1:.4f} "
        f"(max self distance {float(dists[:, 0].max())}); "
        f"deleted ids queried {len(gone)}, resurfaced {len(resurfaced)}; "
        f"recall@{K} {recall:.4f} over {len(lid)} live vectors")
    check(top1 == 1.0, f"{label}: self-query top-1 exact for {top1:.4f}")
    check(np.all(dists[:, 0] <= 1e-4), f"{label}: self distance")
    check(len(resurfaced) == 0, f"{label}: deleted ids came back")
    check(recall >= RECALL_FLOOR, f"{label}: recall@{K} {recall:.4f}")
    return out


def _counts(events) -> dict:
    out: dict = {}
    for e in events:
        out[e] = out.get(e, 0) + 1
    return out


def _peak_bytes() -> list:
    import jax
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in jax.local_devices()]


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------
def run_one_chip(seed: int, n_base: int = N_BASE,
                 n_requests: int = N_REQUESTS, cfg=None) -> None:
    from repro.core import PFOIndex
    from repro.serving import StreamConfig, StreamEngine

    cfg = cfg or smoke_config()
    t = time.perf_counter()
    vs, base = make_base(seed, n_base, cfg.dim)
    reqs, next_id = make_trace(vs, base, n_requests, seed)
    live = LiveSet(base, next_id)
    log(f"data: {n_base} x {cfg.dim} f32 base + {n_requests} requests, "
        f"{time.perf_counter() - t:.3f} s (host, set-up)")

    t = time.perf_counter()
    index = PFOIndex(cfg, seed=seed)
    # one size bucket: warmup compiles one program per op kind (a
    # full-size query program takes minutes to compile for v5e, and
    # each extra bucket would add one)
    engine = StreamEngine(index, StreamConfig(max_batch=BUCKET,
                                              min_batch=BUCKET,
                                              default_k=K))
    log(f"init: {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    assert_kernels_compiled(index, engine)
    log(f"kernel check compile: {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    bulk_load(index, base)
    log(f"load: {n_base} rows in {time.perf_counter() - t:.3f} s "
        "(includes the load step's compile)")

    t = time.perf_counter()
    engine.warmup()
    log(f"warmup compile: {time.perf_counter() - t:.3f} s")

    t = time.perf_counter()
    results = serve(engine, reqs, live, seal_at=n_requests // 2)
    dt = time.perf_counter() - t
    st = engine.stats()
    log(f"serve: {n_requests} requests in {dt:.3f} s (smoke timing, "
        f"not a benchmark); queries answered {len(results)}")
    log("engine stats: " + json.dumps(
        {k: st[k] for k in ("requests", "flushes", "batches", "rounds",
                            "rounds_by_kind", "readbacks",
                            "readbacks_per_round", "seals", "merges")}))
    log(f"index maintenance (load + serve): "
        f"{_counts(index.maintenance_log)}; stats {index.stats()}")
    check(len(results) == sum(1 for r in reqs if r[0] == "query"))
    for _, ids, dists in results:
        check(ids.shape == (K,) and np.all(np.isfinite(dists[ids >= 0])))
    check(st["seals"] >= 1 and st["merges"] >= 1, st)
    check(index.stats()["overflow_events"] == 0)

    t = time.perf_counter()
    check_results(engine, live, np.random.default_rng(seed + 2), "one-chip")
    log(f"checks: {time.perf_counter() - t:.3f} s")
    log(f"peak_bytes_in_use per device: {_peak_bytes()}")


# ----------------------------------------------------------------------
# four chips: sharded engine vs the single-chip engine, same process
# ----------------------------------------------------------------------
def run_four_chips(seed: int, n_base: int = N_BASE,
                   n_requests: int = N_REQUESTS_4, cfg=None,
                   n_model: int = 4) -> None:
    from repro.core import DistConfig, PFOIndex
    from repro.serving import DistStreamEngine, StreamConfig, StreamEngine
    from repro.sharding.policy import stream_mesh

    cfg = cfg or smoke_config()
    vs, base = make_base(seed, n_base, cfg.dim)
    reqs, next_id = make_trace(vs, base, n_requests, seed)
    # load rounds of 1,024 rows scan the same 8-deep tree mailboxes as
    # rounds of 64 (1M rows in ~1k rounds, not ~16k); queries stay at
    # one 64-row bucket (no warmup: each program compiles on first use)
    scfg = StreamConfig(max_batch=LOAD_BUCKET_4, min_batch=BUCKET,
                        query_max_batch=BUCKET, default_k=K)
    engines = {
        "single": lambda: StreamEngine(PFOIndex(cfg, seed=seed), scfg),
        "sharded": lambda: DistStreamEngine(
            DistConfig(pfo=cfg, batch_axes=("data",), n_model=n_model),
            stream_mesh(n_model), scfg, seed=seed),
    }
    seen = {}
    for name, make in engines.items():
        eng = make()
        t = time.perf_counter()
        for i in range(n_base):
            eng.insert(i, base[i])
        eng.flush()
        log(f"{name}: loaded {n_base} rows through the engine in "
            f"{time.perf_counter() - t:.3f} s (compiles included)")
        t = time.perf_counter()
        model = LiveSet(base, next_id)
        results = serve(eng, reqs, model, seal_at=n_requests // 2)
        st = eng.stats()
        log(f"{name}: serve {n_requests} requests in "
            f"{time.perf_counter() - t:.3f} s (smoke timing); stats "
            + json.dumps({k: st[k] for k in ("rounds", "readbacks_per_round",
                                             "seals", "merges")}))
        check(st["seals"] >= 1, st)
        seen[name] = (results, check_results(
            eng, model, np.random.default_rng(seed + 2), name))
        log(f"{name}: peak_bytes_in_use per device: {_peak_bytes()}")
    (r_sh, c_sh), (r_1, c_1) = seen["sharded"], seen["single"]
    check([r[0] for r in r_sh] == [r[0] for r in r_1])
    pairs = [(a[1], a[2], b[1], b[2]) for a, b in zip(r_sh, r_1)]
    for key in c_sh:
        pairs += list(zip(*c_sh[key], *c_1[key]))
    n_diff = sum(not same_neighbours(*p) for p in pairs)
    log(f"sharded vs single-chip: {n_diff} of {len(pairs)} queries "
        "with differing ids")
    check(n_diff == 0, "sharded engine ids differ from the single chip")


def same_neighbours(ids_a, d_a, ids_b, d_b, atol: float = 1e-5) -> bool:
    """Equal top-k ids up to distance ties: the sharded engine ranks
    with its own float formula, so neighbours whose distances agree
    within ``atol`` may trade places, also across the k-th slot."""
    if np.array_equal(ids_a, ids_b):
        return True
    if not np.allclose(d_a, d_b, atol=atol):
        return False
    kth = max(d_a[-1], d_b[-1])
    dist = {**dict(zip(ids_a.tolist(), d_a)), **dict(zip(ids_b.tolist(),
                                                         d_b))}
    return all(dist[i] >= kth - atol
               for i in set(ids_a.tolist()) ^ set(ids_b.tolist()))


# ----------------------------------------------------------------------
def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if os.environ.get("REPRO_PALLAS", "on") == "off":
        raise SystemExit("chip_smoke.py refuses REPRO_PALLAS=off: the "
                         "served path must run the Pallas kernels")

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found no "
                         f"accelerator (platform {devs[0].platform!r})")
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} TPU "
                         f"devices; JAX found {len(devs)}")
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} ({warm} entries at start)")
    log(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}")

    t = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args.seed)
    else:
        run_four_chips(args.seed)
    log(f"total: {time.perf_counter() - t:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
