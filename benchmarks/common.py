"""Shared benchmark utilities: datasets, metrics, timing."""
from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import PFOConfig
from repro.data import VectorStream
from repro.kernels import ops


def clustered_dataset(n: int, dim: int, seed: int = 0,
                      n_clusters: int = 32):
    """Stand-in for MNIST/COLOR (offline container): clustered unit
    vectors with planted neighbor structure."""
    vs = VectorStream(dim=dim, n_clusters=n_clusters, seed=seed)
    ids, vecs = vs.batch(0, n)
    return ids, vecs, vs


def error_ratio(query_d: np.ndarray, oracle_d: np.ndarray,
                k: int) -> float:
    """Paper Eq. 1 with the paper's penalty: a missing neighbor counts
    as similarity 0 (angular distance 1.0)."""
    qd = np.where(np.isfinite(query_d[:, :k]), query_d[:, :k], 1.0)
    od = np.maximum(oracle_d[:, :k], 1e-6)
    return float(np.mean(qd / od))


def oracle(qvecs, vecs, k):
    ids, d = ops.brute_force_topk(jnp.asarray(qvecs), jnp.asarray(vecs),
                                  k, "angular")
    return np.asarray(ids), np.asarray(d)


def timeit(fn, *args, warmup: int = 1, iters: int = 3):
    """Median wall time (s) after warmup; blocks on jax results."""
    for _ in range(warmup):
        r = fn(*args)
        jax.block_until_ready(r) if r is not None else None
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn(*args)
        if r is not None:
            jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def load_hlo(path: str) -> str:
    """Read a dry-run HLO artifact, zstd (.zst) or raw (no-zstd fallback
    writers emit plain '.hlo' — see launch/dryrun.py)."""
    blob = open(path, "rb").read()
    if path.endswith(".zst"):
        import zstandard
        return zstandard.ZstdDecompressor().decompress(blob).decode()
    return blob.decode()


def bench_cfg(**kw) -> PFOConfig:
    base = dict(dim=64, L=4, C=2, m=2, l=32, t=4,
                max_nodes_per_tree=128, max_leaves_per_tree=512,
                main_m=4, main_max_nodes_per_tree=256,
                main_max_leaves_per_tree=2048, store_capacity=32768,
                max_candidates_per_probe=24, max_candidates_total=256,
                max_snapshots=6, bloom_bits=1 << 14, snap_prefix_bits=10,
                snap_budget_per_probe=24)
    base.update(kw)
    return PFOConfig(**base)


# ----------------------------------------------------------------------
# machine-readable telemetry (BENCH_<name>.json, uploaded by CI)
# ----------------------------------------------------------------------
def bench_env() -> dict:
    """Environment fingerprint stamped into every benchmark artifact."""
    import platform
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "backend": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "python": platform.python_version(),
    }


def add_chip_flag(ap) -> None:
    ap.add_argument("--chip", action="store_true",
                    help="measure on the chip: fail unless JAX finds a TPU")


def bench_setup(chip: bool) -> dict:
    """First call of a benchmark's main, before anything compiles:
    places JAX's compile cache and names the device the run is on.  A
    run asked onto the chip (``--chip``) that finds none fails; a CPU
    run says so, since its times are not device metrics."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    env = bench_env()
    if chip and env["backend"] != "tpu":
        raise SystemExit(f"--chip: JAX found no TPU (platform "
                         f"{env['backend']!r})")
    print(f"[bench] device: {env['backend']} {env['device_kind']} "
          f"x{env['n_devices']}" + ("" if env["backend"] == "tpu" else
                                    " -- not a chip run; times are not "
                                    "device metrics"), file=sys.stderr)
    return env


def emit_bench(name: str, config: dict, results: dict, obs=None,
               out_dir: str = ".") -> str:
    """Write ``BENCH_<name>.json``: config + headline results + (when an
    observability handle is passed) the full metrics snapshot with
    per-histogram p50/p99.  Returns the path written."""
    import json
    import os
    doc = {
        "name": name,
        "created_unix": int(time.time()),
        "env": bench_env(),
        "config": config,
        "results": results,
    }
    if obs is not None:
        doc["metrics"] = obs.snapshot()
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        # stable key order -> clean diffs against committed baselines
        json.dump(doc, f, indent=1, sort_keys=True, default=str)
    print(f"[bench] wrote {path}")
    return path
