"""One run of one cell: set-up, the measured window, the check.

Set-up (``setup_s``, from process start to the window's opening):
generate the data and the schedule from the seed, build ``PFOIndex``
behind ``StreamEngine`` as the configuration states, bulk-load the base
set through ``PFOIndex.insert``, then bring the index to the state the
traffic file's ``preload`` names, through the engine's API: a seal
epoch (the base set moves to the sealed ring), a merge epoch, and the
deletes of the oldest groups a retention window has buffered since its
last merge.  Last, one warm flush through the client API.  The epochs
and the flushes run every program the window runs, at the window's one
bucket, so the window compiles nothing; no other shape is warmed.

Window: an open loop.  Requests are due on the schedule's Poisson
clock whether or not the engine keeps up.  The client submits every
request that is due through ``StreamEngine.client()`` with
``t_arrival`` = its due time, at most ``max_flush_requests`` requests
to a flush (the front end's admission batch), and calls ``flush()``;
a request's latency runs from its due time to the return of the flush
that answered it.  After the window closes the loop drains what is
still due, then issues the post-window probe set.

Check: the peak device memory is read, the program's state is freed,
and ``check.compare`` holds the answers against the plain reference.
"""
from __future__ import annotations

import contextlib
import gc
import os
import sys
import tempfile
import time

import numpy as np

import benchspec
import check
import loadgen

sys.path.insert(0, os.path.join(benchspec.ROOT, "src"))

N_WINDOW_SAMPLE = 512      # window answers compared with the reference
N_RECALL, N_SELF, N_GONE = 1024, 256, 128
DRAIN_S = 120.0            # how long past the close answers are awaited


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> str:
    """The program's persistent cache placement (``JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), with every program kept, however
    small or quick to compile, so that a second run compiles nothing."""
    import jax
    from repro.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ----------------------------------------------------------------------
# set-up
# ----------------------------------------------------------------------
def build(cfg: dict, seed: int, obs=None):
    """``PFOIndex`` behind ``StreamEngine`` as ``cfg`` states."""
    from repro.core import PFOConfig, PFOIndex
    from repro.serving import StreamConfig, StreamEngine
    pcfg = PFOConfig(dim=cfg["dim"], metric=cfg["metric"], **cfg["index"])
    index = PFOIndex(pcfg, seed=seed % (1 << 31), obs=obs)
    engine = StreamEngine(index, StreamConfig(default_k=cfg["k"],
                                              **cfg["engine"]))
    return index, engine


def bulk_load(index, base: np.ndarray, batch: int) -> int:
    """Insert the base set in batches; returns the dispatch rounds."""
    rounds = 0
    for s in range(0, len(base), batch):
        ids = np.arange(s, min(s + batch, len(base)), dtype=np.int32)
        rounds += index.insert(ids, base[s:s + batch])
    return rounds


def preload(engine, work: loadgen.Workload, pre: dict,
            retired: np.ndarray) -> dict:
    """Bring the loaded index to the traffic's starting state (module
    doc): ``retired`` are the ids ``Workload.retire_oldest`` took out
    of the live set.  Returns the seconds of each step."""
    took = {}
    t = time.perf_counter()
    if pre.get("seal"):
        engine.seal()
        _sync(engine)
    took["seal"] = time.perf_counter() - t
    t = time.perf_counter()
    if pre.get("merge"):
        engine.merge()
        _sync(engine)
    took["merge"] = time.perf_counter() - t
    t = time.perf_counter()
    client = engine.client()
    for i in retired:
        client.delete(int(i))
    # one update (a vector written back to itself) and one query: the
    # engine's write and query rounds and its small host-side programs
    # run here, not in the window
    i = int(work.live[0]) * work.g
    vec = work.vectors_of(np.array([i]))[0]
    client.update(i, vec)
    client.query(vec, work.cfg["k"])
    engine.flush()
    _sync(engine)
    took["deletes+warm"] = time.perf_counter() - t
    return took


def _sync(engine) -> None:
    import jax
    jax.block_until_ready(engine.backend.index.state)


# ----------------------------------------------------------------------
# the window
# ----------------------------------------------------------------------
def serve(engine, client, reqs: list, versions, max_flush: int, k: int,
          flush0: int = 0, annotate=contextlib.nullcontext) -> dict:
    """Run the open loop over ``reqs`` (module doc).  Returns per-request
    times (seconds after the window opened) and query answers."""
    n = len(reqs)
    due = np.array([r.due for r in reqs])
    start = np.full(n, np.nan)
    done = np.full(n, np.nan)
    flush_of = np.full(n, -1, np.int64)
    answers = {}
    i = j = 0
    f = flush0
    t0 = time.perf_counter()
    stop = (due[-1] if n else 0.0) + DRAIN_S
    while j < n:
        now = time.perf_counter() - t0
        if now > stop:
            break
        while i < n and due[i] <= now:
            i += 1
        if j == i:
            time.sleep(max(0.0, due[i] - now))
            continue
        take = range(j, min(i, j + max_flush))
        tickets = {}
        with annotate("bench_submit"):
            for r_i in take:
                r = reqs[r_i]
                t_arr = t0 + r.due
                if r.kind == "query":
                    tickets[r_i] = [client.query(v, k, t_arrival=t_arr)
                                    for v in r.vecs]
                elif r.kind == "delete":
                    tickets[r_i] = [client.delete(int(x), t_arrival=t_arr)
                                    for x in r.ids]
                else:
                    op = getattr(client, r.kind)
                    tickets[r_i] = [op(int(x), v, t_arrival=t_arr)
                                    for x, v in zip(r.ids, r.vecs)]
                versions.t_from[r.opens] = f
                versions.t_to[r.closes] = f
                flush_of[r_i] = f
        ts = time.perf_counter()
        out = engine.flush()
        te = time.perf_counter()
        for r_i, tk in tickets.items():
            if all(t in out for t in tk):
                start[r_i], done[r_i] = ts - t0, te - t0
                if reqs[r_i].kind == "query":
                    answers[r_i] = (np.stack([out[t][0] for t in tk]),
                                    np.stack([out[t][1] for t in tk]))
        j = take.stop
        f += 1
    return {"due": due, "start": start, "done": done, "flush": flush_of,
            "answers": answers, "flush0": flush0, "next_flush": f,
            "end": time.perf_counter() - t0}


def probe(engine, client, probes: dict, k: int, flush: int) -> dict:
    """Issue the post-window probe set in one flush."""
    tickets = {name: [client.query(v, k) for v in q]
               for name, (q, _) in probes.items()}
    out = engine.flush()
    res = {}
    for name, (q, expect) in probes.items():
        tk = tickets[name]
        res[name] = {
            "q": q, "flush": np.full(len(q), flush, np.int64),
            "ids": np.stack([out[t][0] for t in tk]) if tk else
            np.zeros((0, k), np.int64),
            "dists": np.stack([out[t][1] for t in tk]) if tk else
            np.zeros((0, k), np.float32)}
        if expect is not None:
            res[name]["expect"] = expect
    return res


def window_sample(reqs: list, served: dict, seed: int, k: int,
                  dim: int) -> dict:
    """A sample, drawn from the seed, of the query vectors answered for
    requests due in the window."""
    rows = [(r_i, v) for r_i in sorted(served["answers"])
            for v in range(len(reqs[r_i].vecs))]
    r = loadgen.rng(seed, 4)
    pick = r.choice(len(rows), min(N_WINDOW_SAMPLE, len(rows)),
                    replace=False) if rows else []
    q, fl, ids, dists = [], [], [], []
    for p in sorted(pick):
        r_i, v = rows[p]
        q.append(reqs[r_i].vecs[v])
        fl.append(served["flush"][r_i])
        ids.append(served["answers"][r_i][0][v])
        dists.append(served["answers"][r_i][1][v])
    if not q:
        return {"q": np.zeros((0, dim), np.float32),
                "flush": np.zeros(0, np.int64),
                "ids": np.zeros((0, k), np.int64),
                "dists": np.zeros((0, k), np.float32)}
    return {"q": np.stack(q), "flush": np.array(fl, np.int64),
            "ids": np.stack(ids), "dists": np.stack(dists)}


def peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


# ----------------------------------------------------------------------
# one run
# ----------------------------------------------------------------------
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, root: str = benchspec.ROOT) -> dict:
    import jax
    from repro.obs import Obs

    cfg, traffic = cell["config"], cell["traffic"]
    k = cfg["k"]
    phases = {}
    t = time.perf_counter()
    work = loadgen.Workload(cfg, seed)
    pre = traffic.get("preload", {})
    retired = work.retire_oldest(pre.get("delete_groups", 0))
    reqs = work.segment(traffic, seconds)
    probes = work.probes(N_RECALL, N_SELF, N_GONE)
    phases["data"] = time.perf_counter() - t

    t = time.perf_counter()
    obs = Obs(trace=True, jax_annotations=True, trace_capacity=1 << 20) \
        if trace else None
    index, engine = build(cfg, seed, obs)
    phases["init"] = time.perf_counter() - t
    t = time.perf_counter()
    load_rounds = bulk_load(index, work.base, cfg["load_batch"])
    jax.block_until_ready(index.state)
    phases["load"] = time.perf_counter() - t
    log(f"bulk load: {load_rounds} insert rounds, maintenance "
        f"{index.maintenance_log}")
    phases.update(preload(engine, work, pre, retired))

    annotate = contextlib.nullcontext
    trace_dir = None
    if trace:
        annotate = jax.profiler.TraceAnnotation
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
    client = engine.client()
    stats0 = engine.stats()
    setup_s = time.perf_counter() - t_start
    log("set-up phases (s): " + " ".join(
        f"{p}={v:.3f}" for p, v in phases.items())
        + f" total={setup_s:.3f}")

    with annotate("bench_window"):
        served = serve(engine, client, reqs, work.versions,
                       traffic["max_flush_requests"], k, annotate=annotate)
    stats1 = engine.stats()
    summary = None
    if trace:
        import trace_reduce
        jax.profiler.stop_trace()
        summary = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    n_done = int(np.sum(~np.isnan(served["done"])))
    log(f"window: {len(reqs)} requests due in {seconds} s, {n_done} "
        f"answered, {served['next_flush']} flushes; engine "
        f"rounds_by_kind={stats1['rounds_by_kind']} seals={stats1['seals']}"
        f" merges={stats1['merges']}")

    answers = probe(engine, client, probes, k, served["next_flush"])
    answers["window"] = window_sample(reqs, served, seed, k, cfg["dim"])
    memory_peak = peak_bytes()
    missing = len(reqs) - n_done
    del engine, index, client
    gc.collect()

    t = time.perf_counter()
    numbers = check.compare(answers, work.versions, cfg, missing)
    recall = check.recall(answers["recall"], work.versions, cfg)
    log(f"check against the reference: {time.perf_counter() - t:.3f} s; "
        f"recall@{k} of the probe set {recall:.4f}, of the window sample "
        f"{check.recall(answers['window'], work.versions, cfg):.4f}")

    ctx = {"seconds": seconds, "setup_s": setup_s, "reqs": reqs,
           "served": served, "stats0": stats0, "stats1": stats1,
           "bucket": cfg["engine"]["max_batch"], "recall": recall,
           "trace": summary}
    metrics = {}
    for m in (cell["per_layer"] if trace else cell["end_to_end"]):
        kind = "layer_metrics" if trace else "e2e_metrics"
        v = benchspec.reader(m["name"], root, kind)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": check.verdict(numbers), "attempted": len(reqs),
              "failed": missing, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = {name: {"value": v, "rule": rule, "limit": lim}
                        for name, (v, rule, lim) in numbers.items()}
    return result
