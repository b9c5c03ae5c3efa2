"""The benchmark's harness at a tiny size on the CPU: names resolve, each
traffic kind runs end to end and matches the reference, the result line
keeps its schema, the control and planted faults come out not correct,
and the trace reduction reads a small synthetic trace."""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import BENCH, tiny

import benchspec
import check
import control
import harness
import loadgen
import reference
import trace_reduce

ROOT = os.path.dirname(BENCH)
CELLS = {"sift250k-query": 20.0, "colbert250k-ingest": 4.0}
SEED = 2 ** 31 + 12345          # a seed beyond 32 signed bits


def _run(name, seconds=3.0):
    cell = tiny(benchspec.cell(name), CELLS[name])
    return cell, harness.run_cell(cell, SEED, seconds, False, 0.0)


# -- names ---------------------------------------------------------------
def test_every_name_resolves(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for w in spec["workloads"]:
        cell = benchspec.cell(w["name"], spec)
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"]
        for m in cell["end_to_end"]:
            assert callable(benchspec.reader(m["name"], kind="e2e_metrics"))
        for m in cell["per_layer"]:
            assert callable(benchspec.reader(m["name"]))
            assert m["moves"] in e2e
        assert cell["traffic"]["name"] == w["traffic"]
        assert cell["config"]["name"] == w["config"]


def test_config_files_hold_their_source(spec):
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert conf["dim"] == 128 and conf["k"] == 10


# -- generator and reference ----------------------------------------------
def test_schedule_is_fixed_by_the_seed():
    cfg = tiny(benchspec.cell("colbert250k-ingest"))["config"]
    traffic = dict(benchspec.cell("colbert250k-ingest")["traffic"], rate=4.0)
    a = loadgen.Workload(cfg, SEED).segment(traffic, 10.0)
    b = loadgen.Workload(cfg, SEED).segment(traffic, 10.0)
    c = loadgen.Workload(cfg, SEED + 1).segment(traffic, 10.0)
    assert [(r.kind, r.due) for r in a] == [(r.kind, r.due) for r in b]
    assert all(np.array_equal(x.vecs, y.vecs) for x, y in zip(a, b)
               if x.vecs is not None)
    count = lambda reqs: sorted(r.kind for r in reqs)      # noqa: E731
    assert count(a) == count(c)          # the same work, another order
    assert len(a) == round(traffic["rate"] * 10.0)
    assert all(0 <= r.due < 10.0 for r in a)
    assert all(x.due < y.due for x, y in zip(a, a[1:]))


def test_reference_matches_a_plain_loop():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((300, 8)).astype(np.float32)
    v = loadgen.Versions(base)
    new = v.add(np.array([5, 301]), rng.standard_normal((2, 8))
                .astype(np.float32))
    v.freeze()
    v.t_to[5] = 2                        # id 5 rewritten at flush 2
    v.t_from[new[0]] = 2
    v.t_from[new[1]] = 4                 # id 301 inserted at flush 4
    v.t_to[7] = 3                        # id 7 deleted at flush 3
    q = rng.standard_normal((6, 8)).astype(np.float32)
    fl = np.array([0, 1, 2, 3, 4, 5])
    for metric in ("l2", "angular"):
        ids, d = reference.topk(q, fl, v, 5, metric)
        for i in range(len(q)):
            live = [j for j in range(v.n) if v.t_from[j] <= fl[i] < v.t_to[j]]
            dist = reference.distance(np.repeat(q[i:i + 1], len(live), 0),
                                      v.all_vec[live], metric)
            want = v.all_ids[np.array(live)[np.argsort(dist)[:5]]]
            assert list(ids[i]) == list(want)
            np.testing.assert_allclose(d[i], np.sort(dist)[:5], atol=1e-5)
    got = reference.version_at(v, np.array([5, 5, 7, 7, 301, 301, 9]),
                               np.array([1, 2, 2, 3, 3, 4, 0]))
    assert list(got) == [5, new[0], 7, -1, -1, new[1], 9]


# -- end to end -----------------------------------------------------------
@pytest.mark.parametrize("name", sorted(CELLS))
def test_tiny_run_is_correct_and_keeps_the_schema(name):
    cell, res = _run(name)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    for name_, m in res["metrics"].items():
        assert m["value"] > 0 and isinstance(m["unit"], str), name_
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert set(res["checks"]) == {"missing", "dead_returned",
                                  "dist_err_max", "self_miss"}
    assert res["metrics"]["recall_at_10"]["value"] >= 0.9
    json.loads(json.dumps(res))


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(name):
    cell = tiny(benchspec.cell(name), CELLS[name])
    numbers = control.control_run(cell, SEED, 3.0)
    assert not check.verdict(numbers)
    assert not check.holds(*numbers["dist_err_max"])


def _stale_writes(monkeypatch):
    """A write step that hands back its state unchanged."""
    from repro.serving.stream import LocalBackend
    orig = LocalBackend.insert_round

    def frozen(self, *a, **kw):
        state = self.index.state
        out = orig(self, *a, **kw)
        self.index.state = state
        return out
    monkeypatch.setattr(LocalBackend, "insert_round", frozen)


def _half_batch(monkeypatch):
    """Half of each query batch left out, answered with the first row's
    answer."""
    from repro.serving.stream import StreamEngine
    orig = StreamEngine._query_batch

    def half(self, packed, chunk, bucket, out, overlap=None):
        keep = chunk[:(len(chunk) + 1) // 2]
        orig(self, packed, keep, bucket, out, overlap)
        for t, *_ in chunk[len(keep):]:
            out[t] = out[keep[0][0]]
    monkeypatch.setattr(StreamEngine, "_query_batch", half)


def _altered_answer(monkeypatch):
    """Every returned id altered where the query step produces it."""
    from repro.serving.stream import LocalBackend
    orig = LocalBackend.query_rows

    def altered(self, qvecs, k, overlap=None):
        ids, dists = orig(self, qvecs, k, overlap=overlap)
        return jnp.where(ids >= 0, ids + 1, ids), dists
    monkeypatch.setattr(LocalBackend, "query_rows", altered)


@pytest.mark.parametrize("fault", [_stale_writes, _half_batch,
                                   _altered_answer],
                         ids=["stale_writes", "half_batch", "altered_answer"])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    _, res = _run(name)
    assert res["correct"] is False, res["checks"]


def test_ingest_rate_counts_every_round(monkeypatch):
    """``ops_per_s`` runs to the last answer, the drain included: delete
    rounds made slower lower it, wherever in the schedule they fall."""
    import time as _time
    from repro.serving.stream import LocalBackend
    cell = tiny(benchspec.cell("colbert250k-ingest"), 16.0)
    fast = harness.run_cell(cell, SEED, 1.0, False, 0.0)
    orig = LocalBackend.delete_round

    def slow(self, *a, **kw):
        _time.sleep(0.25)
        return orig(self, *a, **kw)
    monkeypatch.setattr(LocalBackend, "delete_round", slow)
    slowed = harness.run_cell(cell, SEED, 1.0, False, 0.0)
    assert fast["correct"] and slowed["correct"]
    f, s_ = (r["metrics"]["ops_per_s"]["value"] for r in (fast, slowed))
    assert s_ < 0.9 * f, (f, s_)


# -- refusals -------------------------------------------------------------
def _bench_run(cwd, **env):
    full = dict(os.environ, **{"JAX_PLATFORMS": "cpu", **env})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift250k-query",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=full, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env", [{}, {"REPRO_PALLAS": "off"}],
                         ids=["no_accelerator", "pallas_off"])
def test_run_refuses_without_a_chip(env):
    r = _bench_run(ROOT, **env)
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_run_fails_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench_run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


# -- trace reduction and readers ------------------------------------------
def _event(meta, t0_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {int(t0_us * 1e6)} "
            f"duration_ps: {int(dur_us * 1e6)} }}")


def synthetic_trace():
    """One chip, 100 us window: a query step 10-40 us (ops 10-20 and
    25-40), a merge 60-70 us, an insert step 80-85 us; host spans: a
    flush 12-50 us with a flag readback 20-26 us."""
    dev = "\n".join([
        'planes { id: 1 name: "/device:TPU:0"',
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0',
        _event(1, 10, 30), _event(2, 60, 10), _event(6, 80, 5), '}',
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0',
        _event(3, 10, 10), _event(4, 25, 15), _event(5, 60, 10),
        _event(3, 80, 5), '}',
        'event_metadata { key: 1 value { id: 1 name: "jit_query_step(7)" } }',
        'event_metadata { key: 2 value { id: 2 name: "jit_merge_step(9)" } }',
        'event_metadata { key: 3 value { id: 3 name: "fusion.1" } }',
        'event_metadata { key: 4 value { id: 4 name: "sort.2" } }',
        'event_metadata { key: 5 value { id: 5 name: "sort.3" } }',
        'event_metadata { key: 6 value { id: 6 name: "jit_insert_step(4)" } }',
        '}'])
    host = "\n".join([
        'planes { id: 2 name: "/host:CPU"',
        'lines { id: 1 name: "python" timestamp_ns: 0',
        _event(1, 0, 100), _event(2, 12, 38), _event(3, 20, 6), '}',
        'event_metadata { key: 1 value { id: 1 name: "bench_window" } }',
        'event_metadata { key: 2 value { id: 2 name: "flush" } }',
        'event_metadata { key: 3 value { id: 3 name: "flag_readback" } }',
        '}'])
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(dev + "\n" + host)


def test_trace_reduce_on_a_synthetic_trace():
    s = trace_reduce.reduce(synthetic_trace())
    assert s.window_s == pytest.approx(100e-6)
    assert s.busy_s == pytest.approx(40e-6)
    assert s.module("jit_query_step") == (pytest.approx(30e-6), 1)
    assert s.module("jit_merge_step") == (pytest.approx(10e-6), 1)
    ops = dict((k, v) for k, v in s.device_ops)
    assert ops["jit_query_step:sort.2"] == pytest.approx(15e-6)
    gaps = dict((k, v) for k, v in s.idle_gaps)
    # 0-10 client_wait, 20-25 flag_readback (inside the flush),
    # 40-60 the flush (midpoint 50 is its end), 70-80 and 85-100
    # client_wait
    assert sum(gaps.values()) == pytest.approx(60e-6)
    assert gaps == {"client_wait": pytest.approx(35e-6),
                    "flag_readback": pytest.approx(5e-6),
                    "flush": pytest.approx(20e-6)}


def test_layer_readers_on_a_synthetic_run(spec):
    s = trace_reduce.reduce(synthetic_trace())
    cfg = tiny(benchspec.cell("sift250k-query"))["config"]
    reqs = loadgen.Workload(cfg, 1).segment(
        {"rate": 10.0, "mix": {"query": 0.5, "insert": 0.25,
                               "delete": 0.25}}, 4.0)
    n = len(reqs)
    ctx = {"seconds": 4.0, "setup_s": 1.0, "reqs": reqs, "trace": s,
           "served": {"due": np.array([r.due for r in reqs]),
                      "start": np.array([r.due + 0.1 for r in reqs]),
                      "done": np.array([r.due + 0.2 for r in reqs]),
                      "end": 5.0},
           "stats0": {"rounds_by_kind": {"query": 0, "insert": 0,
                                         "delete": 0, "update": 0}},
           "stats1": {"rounds_by_kind": {"query": n, "insert": n,
                                         "delete": n, "update": 0}},
           "bucket": 16, "recall": 0.97}
    for m in spec["per_layer"]:
        v = benchspec.reader(m["name"])(ctx)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100, m["name"]
    assert benchspec.reader("device_idle_share.query")(ctx) == \
        pytest.approx(60.0)
    assert benchspec.reader("queue_wait_p95_ms.query")(ctx) == \
        pytest.approx(100.0)
    for m in spec["end_to_end"]:
        assert benchspec.reader(m["name"], kind="e2e_metrics")(ctx) > 0
