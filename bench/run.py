"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``); the run
builds the index from the seed, loads and warms it, serves the mix on
an open loop for ``--seconds``, checks the answers against the plain
reference (``bench/reference.py``), and prints one JSON line: the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
from a profiler trace of the window.  Progress and the numbers compared
with their limits go to standard error.

The run refuses, with a non-zero exit and no result line, when JAX
finds no accelerator, fewer chips than the cell asks for, or
``REPRO_PALLAS=off`` (the served path must run its Pallas kernels).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def accelerator_or_exit(chips: int) -> None:
    if os.environ.get("REPRO_PALLAS", "on") == "off":
        raise SystemExit("bench/run.py refuses REPRO_PALLAS=off: the "
                         "served path must run the Pallas kernels")
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("bench/run.py needs an accelerator; JAX found "
                         f"none (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import benchspec
    import harness
    cell = benchspec.cell(args.workload)
    accelerator_or_exit(cell["chips"])
    harness.log(f"compile cache: {harness.enable_compile_cache()}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for name, c in result["checks"].items():
        ok = "ok" if harness.check.holds(c["value"], c["rule"],
                                         c["limit"]) else "FAILS"
        harness.log(f"check {name} {c['value']!r} {c['rule']} "
                    f"{c['limit']!r} {ok}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
