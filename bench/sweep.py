"""Find a cell's knee: the highest offered rate its mix sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> \\
        --rates 20,40,80:30 --seconds 10

One set-up as ``run.py`` makes it, then one open-loop window per rate,
in the order given, each with the cell's mix at that rate, for
``--seconds`` or for the seconds given after a rate's colon.  For each it
prints a JSON line: offered and completed requests per second, vector
operations per second, query latency percentiles, the backlog left at
the close, and requests per flush.  A rate is sustained when the
requests completed keep up with those offered: inside the window with
no backlog left at the close, or, where maintenance epochs come in
lumps, over the window and its drain (``*_drained``: every request of
the window over the window and the time past its close until the last
is answered).  A benchmark run does not run this; the rate a
cell offers is written into its traffic file from one sweep on the
chip (``PERF.md``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

import numpy as np  # noqa: E402


def point(reqs: list, served: dict, rate: float, seconds: float) -> dict:
    due, start, done = served["due"], served["start"], served["done"]
    inside = ~np.isnan(done) & (done <= seconds)
    q = np.array([r.kind == "query" for r in reqs])
    ops = np.array([r.n_ops for r in reqs], float)
    span = max(served["end"], seconds)
    lat = (np.where(np.isnan(done), served["end"], done) - due)[q] * 1e3
    return {
        "offered_rps": rate,
        "completed_rps": float(inside.sum() / seconds),
        "ops_per_s": float(ops[inside].sum() / seconds),
        "query_p50_ms": float(np.percentile(lat, 50)) if q.any() else None,
        "query_p95_ms": float(np.percentile(lat, 95)) if q.any() else None,
        "backlog_at_close": int(np.sum(np.isnan(start) | (start > seconds))),
        "requests_per_flush": float(np.sum(~np.isnan(start)) / max(
            1, served["next_flush"] - served["flush0"])),
        "drain_s": float(served["end"] - seconds),
        "completed_rps_drained": float(np.sum(~np.isnan(done)) / span),
        "ops_per_s_drained": float(ops[~np.isnan(done)].sum() / span),
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import benchspec
    import harness
    import loadgen
    from run import accelerator_or_exit
    cell = benchspec.cell(args.workload)
    accelerator_or_exit(cell["chips"])
    harness.enable_compile_cache()
    points = [(float(r.split(":")[0]),
               float(r.split(":")[1]) if ":" in r else args.seconds)
              for r in args.rates.split(",")]
    print(json.dumps(sweep(cell, args.seed, points, loadgen)), flush=True)


def sweep(cell: dict, seed: int, points: list, loadgen) -> dict:
    """``points``: (offered requests per second, window seconds)."""
    import harness
    cfg, traffic = cell["config"], dict(cell["traffic"])
    work = loadgen.Workload(cfg, seed)
    pre = traffic.get("preload", {})
    retired = work.retire_oldest(pre.get("delete_groups", 0))
    index, engine = harness.build(cfg, seed)
    harness.bulk_load(index, work.base, cfg["load_batch"])
    harness.preload(engine, work, pre, retired)
    client = engine.client()
    out = {"workload": cell["name"],
           "setup_s": time.perf_counter() - T_START, "points": []}
    harness.log(f"set-up {out['setup_s']:.3f} s")
    flush = 0
    for rate, seconds in points:
        traffic["rate"] = rate
        reqs = work.segment(traffic, seconds)
        served = harness.serve(engine, client, reqs, work.versions,
                               traffic["max_flush_requests"], cfg["k"],
                               flush0=flush)
        flush = served["next_flush"]
        p = point(reqs, served, rate, seconds)
        p["seconds"] = seconds
        harness.log(json.dumps(p))
        out["points"].append(p)
    out["stats"] = {k: v for k, v in engine.stats().items()
                    if k in ("rounds_by_kind", "seals", "merges")}
    return out


if __name__ == "__main__":
    main()
