"""Query batch-scaling benchmark: per-row query cost vs batch size.

The lockstep penalty this measures: with the legacy ``"loop"``
traversal, vmapped ``lax.while_loop`` chain walks lock every query row
in a batch to the slowest walk, so per-row cost *grows* with Q (the
reason ``serving/stream.py`` historically capped query buckets at 16).
The fixed-trip ``"masked"`` traversal runs every row over identical
static trip counts, so large batches amortize the fixed dispatch cost
and per-row cost falls.

Both modes are timed over the *same* index state (only the jit-static
``traversal`` flag differs), sweeping Q = 1..128:

    PYTHONPATH=src:benchmarks python benchmarks/query_scaling.py [--smoke]

``--smoke`` shrinks sizes and asserts the acceptance gate: masked
per-row cost at Q=64 must be <= 1.5x the Q=1 cost.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import jax
import numpy as np

from common import (add_chip_flag, bench_cfg, bench_setup,
                    clustered_dataset, timeit)
from repro.core import PFOIndex
from repro.core.index import query_step


def sweep(index: PFOIndex, vecs: np.ndarray, qs: list[int], k: int,
          traversal: str, seed: int = 9) -> dict[int, float]:
    """Per-row query latency (ms) for each batch size in ``qs``."""
    cfg = dataclasses.replace(index.cfg, traversal=traversal)
    rng = np.random.default_rng(seed)
    out = {}
    for q in qs:
        base = vecs[rng.integers(0, vecs.shape[0], q)]
        qv = (base + rng.normal(size=base.shape).astype(np.float32) * 0.05
              ).astype(np.float32)
        t = timeit(lambda: query_step(index.state, jax.numpy.asarray(qv),
                                      cfg, k))
        out[q] = 1e3 * t / q
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--qs", default="1,2,4,8,16,32,64,128")
    ap.add_argument("--modes", default="masked,loop")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + the Q=64 <= 1.5x Q=1 gate (CI)")
    ap.add_argument("--json", default=None)
    add_chip_flag(ap)
    args = ap.parse_args()
    bench_setup(args.chip)
    qs = [int(x) for x in args.qs.split(",")]
    if args.smoke:
        args.n, qs = 1000, [1, 16, 64]

    cfg = bench_cfg(dim=args.dim)
    ids, vecs, _ = clustered_dataset(args.n, args.dim, seed=0)
    vecs = np.asarray(vecs)
    index = PFOIndex(cfg, seed=0)
    step = 500
    for s in range(0, args.n, step):
        index.insert(np.asarray(ids)[s:s + step], vecs[s:s + step])

    rec: dict = {"n": args.n, "dim": args.dim, "k": args.k, "per_row_ms": {}}
    for mode in args.modes.split(","):
        per_row = sweep(index, vecs, qs, args.k, mode)
        rec["per_row_ms"][mode] = {str(q): round(v, 3)
                                   for q, v in per_row.items()}
    if "masked" in rec["per_row_ms"]:
        m = rec["per_row_ms"]["masked"]
        rec["masked_q_ratio"] = round(
            m[str(qs[-1])] / m[str(qs[0])], 3)

    print(json.dumps(rec, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rec, f)
    if args.smoke:
        m = rec["per_row_ms"]["masked"]
        ratio = m["64"] / m["1"]
        assert ratio <= 1.5, \
            f"masked per-row cost at Q=64 is {ratio:.2f}x Q=1 (> 1.5x)"
        print("SMOKE OK")


if __name__ == "__main__":
    main()
