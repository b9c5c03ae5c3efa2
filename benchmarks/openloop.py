"""Open-loop latency-at-offered-load benchmark (ANN-benchmarks style
frontier, not closed-loop throughput).

A closed-loop driver (``benchmarks/streaming.py``) submits the next
request only after the previous flush returns, so its latency numbers
hide queueing entirely — the engine never sees a backlog.  This
benchmark is the serving-front-end view the ROADMAP asks for: requests
arrive on a **Poisson process at a configurable offered load** whether
or not the engine is keeping up, and per-request latency is read from
the engine's request-grain accounting (``req.e2e_ms{kind=}`` decomposed
into ``req.queue_wait_ms`` / ``req.batch_wait_ms`` / ``req.service_ms``
— see ``obs/README.md``).

Each offered-load point runs on a fresh engine + fresh metrics registry
(jit caches are shared module-level, so only the first point pays
compilation).  The submitting client carries ``deadline_ms``, so every
point also reports the SLO view (``slo.violation_rate`` /
``slo.burn_rate``) at that load.

The curve to read: ``queue_wait`` stays near zero while the offered
load is below capacity, then explodes at saturation while ``service``
stays flat and ``achieved_rps`` clamps — that knee is the serving
capacity, and ``peak_achieved_rps`` is the trajectory metric
``benchmarks/regress.py`` gates on.

    PYTHONPATH=src python benchmarks/openloop.py [--smoke]
        [--loads 100,200,400] [--deadline-ms 50]

Without ``--loads`` the benchmark calibrates: a closed-loop prefix
measures capacity, then sweeps 0.25x / 0.5x / 1.0x of it (>= 3 points,
the last one deliberately saturating).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from common import add_chip_flag, bench_cfg, bench_setup, emit_bench
from repro.core import PFOIndex
from repro.obs import Obs
from repro.serving import StreamConfig, StreamEngine
from streaming import make_workload


def submit(client, req, t_arrival: float | None = None) -> int:
    """One ``(kind, *args)`` workload tuple -> client submission,
    stamped with its Poisson arrival time (so ``req.queue_wait_ms``
    covers the backlog a request sat in while a flush ran, not just the
    buffer time after the driver got around to submitting it)."""
    kind, args = req[0], req[1:]
    if kind == "query":
        return client.query(args[0], t_arrival=t_arrival)
    if kind == "insert":
        return client.insert(args[0], args[1], t_arrival=t_arrival)
    if kind == "delete":
        return client.delete(args[0], t_arrival=t_arrival)
    return client.update(args[0], args[1], t_arrival=t_arrival)


def run_open_loop(engine: StreamEngine, client, reqs: list,
                  arrivals: np.ndarray) -> float:
    """Replay ``reqs`` at their Poisson ``arrivals`` (seconds from
    start); flush whenever a backlog exists.  Returns elapsed seconds.

    This is the open-loop contract: submission time is dictated by the
    arrival clock, never by the engine — when a flush runs long, every
    request that arrived meanwhile lands in the next (bigger) batch and
    its wait shows up in ``req.queue_wait_ms``.
    """
    n = len(reqs)
    i = 0
    t0 = time.perf_counter()
    while i < n:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            submit(client, reqs[i], t_arrival=t0 + arrivals[i])
            i += 1
        if engine.pending():
            engine.flush()
        elif i < n:
            time.sleep(min(max(arrivals[i] - now, 0.0), 2e-3))
    if engine.pending():
        engine.flush()
    return time.perf_counter() - t0


def _pt(hists, name, q):
    h = hists.get(name)
    return round(h[q], 3) if h and h.get("count") else None


def run_load_point(cfg, scfg, reqs, seed_ids, seed_vecs, offered_rps: float,
                   deadline_ms: float, seed: int) -> dict:
    """One offered-load point on a fresh engine + registry."""
    obs = Obs(metrics=True, trace=False)
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs), scfg)
    eng.index.insert(seed_ids, seed_vecs)
    eng.warmup()
    client = eng.client(deadline_ms=deadline_ms)

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, len(reqs)))
    elapsed = run_open_loop(eng, client, reqs, arrivals)

    snap = obs.snapshot()
    hists, gauges = snap["histograms"], snap["gauges"]
    st = eng.stats()
    # the one-readback-per-round invariant survives open-loop serving
    assert st["readbacks"] <= st["rounds"] + 2 * st["batches"] + 16, st
    dl = float(deadline_ms)
    return {
        "offered_rps": round(offered_rps, 1),
        "achieved_rps": round(len(reqs) / elapsed, 1),
        "duration_s": round(elapsed, 3),
        "e2e_p50_ms": _pt(hists, "req.e2e_ms{kind=query}", "p50"),
        "e2e_p99_ms": _pt(hists, "req.e2e_ms{kind=query}", "p99"),
        "queue_wait_p50_ms": _pt(hists, "req.queue_wait_ms", "p50"),
        "queue_wait_p99_ms": _pt(hists, "req.queue_wait_ms", "p99"),
        "batch_wait_p50_ms": _pt(hists, "req.batch_wait_ms", "p50"),
        "service_p50_ms": _pt(hists, "req.service_ms", "p50"),
        "service_p99_ms": _pt(hists, "req.service_ms", "p99"),
        "violation_rate": gauges.get(
            f"slo.violation_rate{{deadline_ms={dl}}}"),
        "burn_rate": gauges.get(f"slo.burn_rate{{deadline_ms={dl}}}"),
        "flushes": st["flushes"],
        "mean_batch": round(len(reqs) / max(st["batches"], 1), 1),
    }


def calibrate_rps(cfg, scfg, reqs, seed_ids, seed_vecs,
                  flush_every: int) -> float:
    """Closed-loop capacity estimate used to place the sweep points."""
    from repro.serving.stream import drive
    eng = StreamEngine(PFOIndex(cfg, seed=0), scfg)
    eng.index.insert(seed_ids, seed_vecs)
    eng.warmup()
    drive(eng, reqs, flush_every=flush_every)          # warm/compile
    _, elapsed, _ = drive(eng, reqs, flush_every=flush_every)
    return len(reqs) / elapsed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=2000,
                    help="requests per offered-load point")
    ap.add_argument("--seed-vecs", type=int, default=2000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--flush-every", type=int, default=64,
                    help="calibration closed-loop flush cadence")
    ap.add_argument("--loads", default=None,
                    help="comma-separated offered loads (rps); default "
                         "calibrates capacity and sweeps 0.25/0.5/1.0x")
    ap.add_argument("--deadline-ms", type=float, default=50.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + assertions only (CI)")
    ap.add_argument("--json", default=None)
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_openloop.json lands")
    add_chip_flag(ap)
    args = ap.parse_args()
    bench_setup(args.chip)
    if args.smoke:
        args.requests, args.seed_vecs = 400, 500
        args.max_batch = 64

    cfg = bench_cfg(dim=args.dim)
    scfg = StreamConfig(max_batch=args.max_batch, min_batch=8,
                        default_k=args.k)
    reqs, seed_ids, seed_vecs = make_workload(
        args.requests, args.dim, n_seed_vecs=args.seed_vecs)

    if args.loads:
        loads = [float(x) for x in args.loads.split(",")]
    else:
        cap = calibrate_rps(cfg, scfg, reqs, seed_ids, seed_vecs,
                            args.flush_every)
        loads = [cap * f for f in (0.25, 0.5, 1.0)]
        print(f"[bench] calibrated closed-loop capacity ~{cap:.0f} rps")

    points = []
    for j, rps in enumerate(loads):
        pt = run_load_point(cfg, scfg, reqs, seed_ids, seed_vecs, rps,
                            args.deadline_ms, seed=17 + j)
        print(f"[bench] offered {pt['offered_rps']:>8} rps -> achieved "
              f"{pt['achieved_rps']:>8} rps  e2e p50/p99 "
              f"{pt['e2e_p50_ms']}/{pt['e2e_p99_ms']} ms  queue_wait p99 "
              f"{pt['queue_wait_p99_ms']} ms")
        points.append(pt)

    rec = {
        "loads": points,
        "peak_achieved_rps": max(p["achieved_rps"] for p in points),
        "deadline_ms": args.deadline_ms,
    }
    os.makedirs(args.out_dir, exist_ok=True)
    emit_bench("openloop", config={
        "requests": args.requests, "seed_vecs": args.seed_vecs,
        "dim": args.dim, "k": args.k, "max_batch": args.max_batch,
        "smoke": args.smoke, "loads": [round(x, 1) for x in loads],
    }, results=rec, out_dir=args.out_dir)

    print(json.dumps(rec, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f)

    if args.smoke:
        assert len(points) >= 3, points
        for pt in points:
            # latency decomposition present at every load point
            for key in ("e2e_p50_ms", "e2e_p99_ms", "queue_wait_p50_ms",
                        "queue_wait_p99_ms", "service_p50_ms",
                        "service_p99_ms", "violation_rate"):
                assert pt[key] is not None, (key, pt)
            assert pt["e2e_p99_ms"] >= pt["e2e_p50_ms"], pt
        # the sub-capacity points must actually sustain their offered
        # load (generous factor: CI boxes timeshare)
        assert points[0]["achieved_rps"] >= 0.5 * points[0]["offered_rps"], \
            points[0]
        print("SMOKE OK")


if __name__ == "__main__":
    main()
