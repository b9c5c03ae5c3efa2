"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of
its own, and each per-layer metric is a small reader of its own:

* configuration: the ``file`` that ``BENCHMARK.json`` gives it;
* traffic mix:   ``bench/traffic/<traffic>.json``;
* metric reader: ``bench/layer_metrics/<metric name>.py`` (per-layer)
  or ``bench/e2e_metrics/<metric name>.py`` (end-to-end), a module with
  ``read(ctx) -> float | None``; ``None`` leaves the metric out.

Adding a cell, a mix or a metric therefore adds files and entries and
edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str, spec: dict) -> bool:
    """Whether a metric is reported in a cell.  A metric with a
    ``workloads`` list is reported in those cells; an end-to-end metric
    without one in every cell; a per-layer metric without one in every
    cell that reports the end-to-end metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" not in metric:
        return True
    moved = [m for m in spec["end_to_end"] if m["name"] == metric["moves"]]
    return bool(moved) and applies(moved[0], cell, spec)


def cell(name: str, spec: dict | None = None, root: str = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, resolved by name."""
    spec = spec or load(root)
    work = [w for w in spec["workloads"] if w["name"] == name]
    if not work:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    work = work[0]
    conf = [c for c in spec["configs"] if c["name"] == work["config"]][0]
    return {
        "name": name,
        "chips": work["chips"],
        "config": _read_json(os.path.join(root, conf["file"])),
        "traffic": _read_json(os.path.join(root, "bench", "traffic",
                                           work["traffic"] + ".json")),
        "end_to_end": [m for m in spec["end_to_end"]
                       if applies(m, name, spec)],
        "per_layer": [m for m in spec["per_layer"]
                      if applies(m, name, spec)],
    }


def reader(metric: str, root: str = ROOT, kind: str = "layer_metrics"):
    """``read`` of ``bench/<kind>/<metric>.py``: ``layer_metrics`` for
    per-layer metrics, ``e2e_metrics`` for end-to-end ones."""
    path = os.path.join(root, "bench", kind, metric + ".py")
    mod_name = "bench_metric_" + metric.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
