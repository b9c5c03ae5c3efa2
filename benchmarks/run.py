"""Benchmark harness: one function per paper table/figure.

``python -m benchmarks.run [--only fig5,fig7]`` prints
``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list, e.g. fig5,fig10")
    from .common import add_chip_flag, bench_setup
    add_chip_flag(ap)
    args = ap.parse_args()
    if not args.chip:
        # Interpret-mode Pallas is a correctness tool (Python-executed
        # kernel bodies); benchmarking it would measure the
        # interpreter.  The jnp ref path is the same math the TPU
        # kernels fuse.  A --chip run times the compiled kernels.
        os.environ.setdefault("REPRO_PALLAS", "off")
    bench_setup(args.chip)

    from . import paper_figs

    print("name,us_per_call,derived")
    t0 = time.time()
    for fn in paper_figs.ALL:
        tag = fn.__name__.split("_")[0]
        if args.only and tag not in args.only.split(","):
            continue
        print(f"# --- {fn.__name__}: {fn.__doc__.splitlines()[0]}",
              file=sys.stderr)
        fn()
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
