"""The control: the reference in the program's place, one precision down.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

The configurations state float32 vectors and exact float32 distances.
The control answers each cell's queries by brute force with vectors,
queries and distances in bfloat16, the step below float32 that would
tempt a faster path, and feeds its answers to the same comparison
(``check.compare``) that judges a run.  It must come out not correct:
``dist_err_max`` reads its widest distance gap, which sets the upper
reading of that limit (``PERF.md``).

The control serves each request alone, in due order (flush ``i`` is
request ``i``), so each query is answered against the live set its due
time sees; the window sample and the probe set are drawn as a run draws
them.  A benchmark run does not run this.
"""
from __future__ import annotations

import argparse
import json

import numpy as np

import check
import harness
import loadgen

BLOCK = 128


def control_topk(qvecs: np.ndarray, flushes: np.ndarray, versions, k: int,
                 metric: str):
    """Brute-force top-k in bfloat16 on the default JAX device."""
    import jax
    import jax.numpy as jnp
    x = jnp.asarray(versions.all_vec, jnp.bfloat16)
    if metric == "angular":
        x = x / jnp.linalg.norm(x.astype(jnp.float32), axis=1,
                                keepdims=True).astype(jnp.bfloat16)
    xs = jnp.sum(x * x, axis=1)
    top = np.iinfo(np.int32).max
    t_from = jnp.asarray(np.minimum(versions.t_from, top).astype(np.int32))
    t_to = jnp.asarray(np.minimum(versions.t_to, top).astype(np.int32))

    @jax.jit
    def block(q, f):
        q = q.astype(jnp.bfloat16)
        if metric == "angular":
            q = q / jnp.linalg.norm(q.astype(jnp.float32), axis=1,
                                    keepdims=True).astype(jnp.bfloat16)
            d = 1 - q @ x.T
        else:
            d = jnp.sum(q * q, axis=1)[:, None] + xs[None] - 2 * (q @ x.T)
        live = (t_from[None] <= f[:, None]) & (f[:, None] < t_to[None])
        neg, idx = jax.lax.top_k(jnp.where(live, -d, -jnp.inf), k)
        return idx, -neg.astype(jnp.float32)

    ids, dists = [], []
    allid = versions.all_ids
    for s in range(0, len(qvecs), BLOCK):
        q = np.zeros((BLOCK, qvecs.shape[1]), np.float32)
        f = np.zeros(BLOCK, np.int32)
        n = min(BLOCK, len(qvecs) - s)
        q[:n], f[:n] = qvecs[s:s + n], flushes[s:s + n]
        idx, d = jax.device_get(block(jnp.asarray(q), jnp.asarray(f)))
        ids.append(allid[idx[:n]])
        dists.append(d[:n])
    if not ids:
        return np.zeros((0, k), np.int64), np.zeros((0, k), np.float32)
    return np.concatenate(ids), np.concatenate(dists)


def control_run(cell: dict, seed: int, seconds: float) -> dict:
    """The numbers ``check.compare`` gives the control's answers."""
    cfg, k = cell["config"], cell["config"]["k"]
    work = loadgen.Workload(cfg, seed)
    reqs = work.segment(cell["traffic"], seconds)
    probes = work.probes(harness.N_RECALL, harness.N_SELF, harness.N_GONE)
    v = work.versions
    flush_of = np.arange(len(reqs), dtype=np.int64)
    for i, r in enumerate(reqs):
        v.t_from[r.opens] = i
        v.t_to[r.closes] = i
    answers = {}
    for name, (q, expect) in probes.items():
        fl = np.full(len(q), len(reqs), np.int64)
        ids, dists = control_topk(q, fl, v, k, cfg["metric"])
        answers[name] = {"q": q, "flush": fl, "ids": ids, "dists": dists}
        if expect is not None:
            answers[name]["expect"] = expect
    qi = [i for i, r in enumerate(reqs) if r.kind == "query"]
    served = {"flush": flush_of, "answers": {}}
    if qi:
        qv = np.concatenate([reqs[i].vecs for i in qi])
        fl = np.repeat(flush_of[qi], [len(reqs[i].vecs) for i in qi])
        ids, dists = control_topk(qv, fl, v, k, cfg["metric"])
        at = 0
        for i in qi:
            n = len(reqs[i].vecs)
            served["answers"][i] = (ids[at:at + n], dists[at:at + n])
            at += n
    answers["window"] = harness.window_sample(reqs, served, seed, k,
                                              cfg["dim"])
    return check.compare(answers, v, cfg, missing=0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import benchspec
    cell = benchspec.cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_run(cell, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": check.verdict(numbers),
                          "checks": numbers}), flush=True)


if __name__ == "__main__":
    main()
