"""CPU tests of the benchmark at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The repository's own suite (``testpaths = tests``) does not collect
these; they run the harness end to end without the chip check, with
the cells' files cut to a size the CPU holds.
"""
import copy
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

#: what a CPU run can hold; every other key is the cell's own
TINY_INDEX = {"L": 4, "C": 2, "m": 2, "max_leaves_per_tree": 256,
              "max_nodes_per_tree": 64, "main_m": 4,
              "main_max_leaves_per_tree": 512,
              "main_max_nodes_per_tree": 128, "store_capacity": 8192,
              "snap_prefix_bits": 10, "snapshot_capacity": 4096,
              "max_candidates_total": 256, "max_tombstones": 64}
TINY = {"n_base": 2048, "n_clusters": 64, "load_batch": 512,
        "engine": {"max_batch": 16, "min_batch": 16, "ordering": "window"}}


def tiny(cell: dict, rate: float | None = None) -> dict:
    cell = copy.deepcopy(cell)
    cell["config"].update(copy.deepcopy(TINY))
    cell["config"]["index"].update(TINY_INDEX)
    if rate is not None:
        cell["traffic"]["rate"] = rate
    pre = cell["traffic"].get("preload", {})
    if pre.get("delete_groups"):
        pre["delete_groups"] = 1      # of the 32 tiny documents
    return cell


@pytest.fixture(scope="session")
def spec():
    import benchspec
    return benchspec.load()
