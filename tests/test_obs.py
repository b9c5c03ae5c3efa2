"""Observability layer tests: histogram accuracy vs numpy, registry
semantics, span ring buffer + Perfetto export schema, disabled-path
no-ops, derived-metric consistency with ``StreamEngine.stats()``,
request-grain accounting (``req.*`` decomposition), deadline/SLO
classes (``obs/slo.py``), and the hard invariant — tracing AND
per-request accounting add ZERO device readbacks to a steady-state
round (checked under the JAX transfer guard)."""
import glob
import json
import os
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import small_pfo_config
from repro.core import PFOIndex
from repro.core import index as pfo
from repro.obs import (NULL_METRIC, NULL_SPAN, Obs, Tracer, phases, report)
from repro.obs.metrics import Histogram, MetricsRegistry, render_name
from repro.serving import StreamConfig, StreamEngine


def _vecs(n, dim, seed=0):
    rng = np.random.default_rng(seed)
    v = rng.normal(size=(n, dim)).astype(np.float32)
    return v / np.linalg.norm(v, axis=1, keepdims=True)


# -- metrics ------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=1.0, sigma=1.5, size=20_000)
    h = Histogram()
    for s in samples:
        h.observe(float(s))
    for q in (50.0, 90.0, 99.0):
        got = h.percentile(q)
        want = float(np.percentile(samples, q))
        # log-bucketed (32 sub-buckets/octave): rel error ~ 1/32 worst
        assert abs(got - want) / want < 0.06, (q, got, want)
    s = h.summary()
    assert s["count"] == len(samples)
    assert abs(s["mean"] - samples.mean()) / samples.mean() < 0.06
    assert s["min"] <= s["p50"] <= s["p90"] <= s["p99"] <= s["max"]


def test_histogram_clamps_out_of_range():
    h = Histogram(lo=1e-3, hi=1e3)
    h.observe(0.0)          # below lo -> bottom bucket, min tracked
    h.observe(1e9)          # above hi -> top bucket, max tracked
    s = h.summary()
    assert s["count"] == 2 and s["min"] == 0.0 and s["max"] == 1e9


def test_registry_interning_labels_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("stream.flag_fired", flag="need_seal")
    assert reg.counter("stream.flag_fired", flag="need_seal") is c
    assert reg.counter("stream.flag_fired", flag="pending") is not c
    c.inc(); c.inc(3)
    reg.gauge("stream.queue_depth").set(17)
    reg.histogram("stream.round_ms", kind="q").observe(2.0)
    snap = reg.snapshot()
    assert snap["enabled"] is True
    assert snap["counters"]["stream.flag_fired{flag=need_seal}"] == 4
    assert snap["gauges"]["stream.queue_depth"] == 17
    assert snap["histograms"]["stream.round_ms{kind=q}"]["count"] == 1
    # same rendered key with a different kind is a bug -> loud failure
    with pytest.raises(AssertionError):
        reg.counter("stream.queue_depth")      # registered as a gauge


def test_render_name():
    assert render_name("x", None) == "x"
    assert render_name("x", {"b": 1, "a": "y"}) == "x{a=y,b=1}"


def test_disabled_registry_returns_shared_null_metric():
    reg = MetricsRegistry(enabled=False)
    assert reg.counter("a") is NULL_METRIC
    assert reg.gauge("b") is NULL_METRIC
    assert reg.histogram("c") is NULL_METRIC
    NULL_METRIC.inc(); NULL_METRIC.set(3); NULL_METRIC.observe(1.0)
    assert reg.snapshot()["enabled"] is False


def test_on_snapshot_keyed_rebind():
    reg = MetricsRegistry()
    calls = []
    reg.on_snapshot("k", lambda: calls.append("old"))
    reg.on_snapshot("k", lambda: calls.append("new"))   # replaces
    reg.snapshot()
    assert calls == ["new"]


# -- tracing ------------------------------------------------------------

def test_span_nesting_and_ring_wraparound():
    tr = Tracer(capacity=8)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    ev = tr.events()
    # spans record on __exit__, so inner lands before outer
    assert [e[0] for e in ev] == ["inner", "outer"]
    assert ev[0][2] >= 1                       # dur_us floored at 1

    for i in range(18):                        # 20 spans total through cap 8
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 12
    names = [e[0] for e in tr.events()]
    assert names == [f"s{i}" for i in range(10, 18)]   # last 8, in order


def test_perfetto_export_schema_roundtrip(tmp_path):
    obs = Obs(metrics=True, trace=True, trace_capacity=64)
    with obs.span("flush", depth=3):
        with obs.span("dispatch", kind="i", bucket=64):
            pass
    path = tmp_path / "trace.json"
    obs.save_trace(str(path))
    doc = json.loads(path.read_text())         # round-trips as JSON
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert meta and meta[0]["name"] == "thread_name"
    assert {e["name"] for e in spans} == {"flush", "dispatch"}
    for e in spans:
        assert e["cat"] == "pfo" and e["pid"] == 0
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["dur"] >= 1 and isinstance(e["tid"], int)
    d = next(e for e in spans if e["name"] == "dispatch")
    assert d["args"] == {"kind": "i", "bucket": 64}


def test_disabled_span_is_shared_noop():
    obs = Obs(metrics=True, trace=False)
    s1 = obs.span("x", a=1)
    s2 = obs.span("y")
    assert s1 is s2 is NULL_SPAN               # one branch, no alloc
    with s1:
        pass
    assert obs.tracer.events() == []
    # NullTracer still writes a valid (empty) trace file
    assert obs.tracer.export() == {"traceEvents": []}


def test_disabled_span_overhead_is_small():
    obs = Obs(metrics=False, trace=False)
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        obs.span("x")
    dt = time.perf_counter() - t0
    # one branch + attribute loads: generous CI bound of 10us/call
    assert dt / n < 10e-6, dt


# -- report / derived ---------------------------------------------------

def test_per_round_zero_rounds_guard():
    assert report.per_round(0, 0) == 0.0
    assert report.per_round(7, 0) == 0.0
    assert report.per_round(6, 4) == 1.5


def test_format_table_smoke():
    obs = Obs()
    obs.counter("a.b").inc(2)
    obs.gauge("c.d", shard=0).set(1.5)
    obs.histogram("e.f").observe(3.0)
    txt = obs.format(title="t")
    assert "a.b" in txt and "c.d{shard=0}" in txt and "e.f" in txt


# -- engine integration -------------------------------------------------

def test_traced_steady_state_round_zero_extra_readbacks():
    """With metrics, tracing, per-request accounting AND a deadline
    class all live, a warm steady-state round still does exactly one
    explicit scalar sync (the flag word) and zero implicit device->host
    transfers."""
    cfg = small_pfo_config()
    v = _vecs(256, cfg.dim, seed=3)
    obs = Obs(metrics=True, trace=True, trace_capacity=4096)
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs),
                       StreamConfig(max_batch=64, min_batch=64,
                                    query_max_batch=64))
    client = eng.client(deadline_ms=100.0)    # SLO path live too
    for lo in (0, 64):                        # warm both rounds + flags
        for i in range(lo, lo + 64):
            client.insert(i, v[i])
        eng.flush()

    for i in range(128, 192):
        client.insert(i, v[i])
    before_sync = eng.index.sync_count
    before_rounds = eng.n_rounds
    n_ev = len(obs.tracer.events())
    with jax.transfer_guard_device_to_host("disallow"):
        eng.flush()
    rounds = eng.n_rounds - before_rounds
    assert rounds >= 1
    assert eng.index.sync_count - before_sync == rounds
    names = {e[0] for e in obs.tracer.events()[n_ev:]}
    assert {"flush", "pack", "dispatch", "flag_readback", "req_queue",
            "req_batch", "req_service", "req_hold"} <= names
    # the accounting observed every request of the guarded flush
    snap = obs.snapshot()
    h = snap["histograms"]["req.e2e_ms{kind=insert}"]
    assert h["count"] == 192
    assert snap["counters"]["slo.requests{deadline_ms=100.0}"] == 192


# -- request-grain accounting + SLO -------------------------------------

def test_request_accounting_decomposition():
    """e2e = queue_wait + batch_wait + service, exactly, per request —
    checked on the histogram totals (same sample count, same sum)."""
    cfg = small_pfo_config()
    v = _vecs(128, cfg.dim, seed=7)
    obs = Obs()
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs),
                       StreamConfig(max_batch=32, min_batch=8))
    for i in range(64):
        eng.insert(i, v[i])
    eng.flush()
    for i in range(16):
        eng.query(v[i], k=4)
    eng.flush()
    hs = obs.snapshot()["histograms"]
    n = sum(hs[k]["count"] for k in hs if k.startswith("req.e2e_ms"))
    assert n == 80
    for part in ("queue_wait", "batch_wait", "service"):
        assert hs[f"req.{part}_ms"]["count"] == n
    e2e_sum = sum(hs[k]["mean"] * hs[k]["count"] for k in hs
                  if k.startswith("req.e2e_ms") and hs[k]["count"])
    part_sum = sum(hs[f"req.{p}_ms"]["mean"] * n
                   for p in ("queue_wait", "batch_wait", "service"))
    assert abs(e2e_sum - part_sum) / e2e_sum < 1e-6


def test_t_arrival_backdates_queue_wait():
    """An upstream front-end can stamp arrival time (socket receive /
    Poisson clock); queue_wait then covers that upstream backlog."""
    cfg = small_pfo_config()
    v = _vecs(8, cfg.dim, seed=8)
    obs = Obs()
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs),
                       StreamConfig(max_batch=8, min_batch=8))
    c = eng.client()
    c.insert(0, v[0], t_arrival=time.perf_counter() - 1.0)
    eng.flush()
    hs = obs.snapshot()["histograms"]
    assert hs["req.queue_wait_ms"]["max"] >= 1000.0
    assert hs["req.e2e_ms{kind=insert}"]["max"] >= 1000.0


def test_deadline_violations_fire_under_injected_slow_flush():
    """Satellite: a flush slowed past the deadline violates every
    in-flight request of the tight class — deterministically — while a
    loose class in the same flush stays clean."""
    cfg = small_pfo_config()
    v = _vecs(32, cfg.dim, seed=9)
    obs = Obs()
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs),
                       StreamConfig(max_batch=16, min_batch=8))
    tight = eng.client(deadline_ms=5.0)
    loose = eng.client(deadline_ms=1e6)
    real_pack = eng._pack

    def slow_pack(kind, chunk, bucket):      # inject >deadline stall
        time.sleep(0.02)
        return real_pack(kind, chunk, bucket)

    eng._pack = slow_pack
    for i in range(8):
        tight.insert(i, v[i])
        loose.insert(100 + i, v[16 + i])
    eng.flush()
    cs = obs.snapshot()["counters"]
    assert cs["slo.requests{deadline_ms=5.0}"] == 8
    assert cs["slo.violations{deadline_ms=5.0}"] == 8
    assert cs["slo.requests{deadline_ms=1000000.0}"] == 8
    assert cs["slo.violations{deadline_ms=1000000.0}"] == 0
    gs = obs.snapshot()["gauges"]
    assert gs["slo.violation_rate{deadline_ms=5.0}"] == 1.0
    assert gs["slo.burn_rate{deadline_ms=5.0}"] == 100.0   # 0.99 target
    assert gs["slo.burn_rate{deadline_ms=1000000.0}"] == 0.0


def test_edf_order_prioritizes_tight_deadline_queries():
    from repro.obs.slo import edf_order
    from repro.core.dispatch import client_ticket
    deadlines = {1: 10.0, 2: 1000.0}
    t0 = 100.0
    queue = [
        (client_ticket(2, 0), "query", "a", t0),        # slack 1.0s
        (client_ticket(3, 0), "query", "b", t0),        # no deadline
        (client_ticket(1, 0), "query", "c", t0 + 0.5),  # abs 100.51
        (client_ticket(1, 1), "query", "d", t0),        # abs 100.01
    ]
    got = [r[2] for r in edf_order(queue, deadlines)]
    assert got == ["d", "c", "a", "b"]
    # no deadline classes registered -> identity (not even a sort)
    assert edf_order(queue, {}) is queue


def test_engine_client_rejects_bad_deadline():
    cfg = small_pfo_config()
    eng = StreamEngine(PFOIndex(cfg, seed=0),
                       StreamConfig(max_batch=8, min_batch=8))
    with pytest.raises(AssertionError):
        eng.client(deadline_ms=0)
    c = eng.client(deadline_ms=25.0)
    assert c.deadline_ms == 25.0
    assert eng.stats()["deadline_clients"] == 1


def test_trace_dropped_gauge_and_save_warning(tmp_path):
    """Ring wraparound is never silent: the gauge mirrors
    ``Tracer.dropped`` and ``save_trace`` warns."""
    obs = Obs(metrics=True, trace=True, trace_capacity=4)
    for i in range(10):
        with obs.span(f"s{i}"):
            pass
    assert obs.snapshot()["gauges"]["obs.trace_dropped"] == 6
    with pytest.warns(RuntimeWarning, match="overwrote 6 span"):
        obs.save_trace(str(tmp_path / "t.json"))
    # no wraparound, no warning; NullTracer reports dropped == 0
    import warnings as _w
    clean = Obs(metrics=True, trace=True, trace_capacity=64)
    with clean.span("x"):
        pass
    with _w.catch_warnings():
        _w.simplefilter("error")
        clean.save_trace(str(tmp_path / "t2.json"))
    off = Obs(metrics=True, trace=False)
    assert off.tracer.dropped == 0
    with _w.catch_warnings():
        _w.simplefilter("error")
        off.save_trace(str(tmp_path / "t3.json"))


def test_stats_and_snapshot_derive_identically():
    """Satellite (a): readbacks_per_round comes from ONE implementation
    — engine stats() and the obs snapshot cannot drift."""
    cfg = small_pfo_config()
    v = _vecs(96, cfg.dim, seed=5)
    eng = StreamEngine(PFOIndex(cfg, seed=0),
                       StreamConfig(max_batch=32, min_batch=8))
    # zero-rounds guard first: fresh engine reports 0.0, not a crash
    assert eng.stats()["readbacks_per_round"] == 0.0
    for i in range(96):
        eng.insert(i, v[i])
    eng.flush()
    st = eng.stats()
    snap = eng.obs.snapshot()
    assert snap["derived"]["readbacks_per_round"] == \
        st["readbacks_per_round"]
    assert snap["gauges"]["index.readbacks"] == eng.index.sync_count
    assert snap["gauges"]["stream.rounds"] == eng.n_rounds
    # flag counters only ever fire on documented flag names
    from repro.core.dispatch import FLAG_NAMES
    for key in snap["counters"]:
        if key.startswith("stream.flag_fired"):
            assert key.split("flag=")[1][:-1] in FLAG_NAMES.values()


def test_metrics_off_engine_still_serves():
    cfg = small_pfo_config()
    v = _vecs(64, cfg.dim, seed=6)
    obs = Obs(metrics=False, trace=False)
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs),
                       StreamConfig(max_batch=32, min_batch=8))
    for i in range(64):
        eng.insert(i, v[i])
    eng.flush()
    t = eng.query(v[10], k=3)
    ids, d = eng.result(t)
    assert ids[0] == 10 and d[0] < 1e-5
    snap = eng.obs.snapshot()
    assert snap["enabled"] is False and snap["counters"] == {}


# -- benchmark telemetry ------------------------------------------------

def test_emit_bench_writes_schema(tmp_path):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                           / "benchmarks"))
    try:
        from common import emit_bench
    finally:
        sys.path.pop(0)
    obs = Obs()
    obs.counter("stream.rounds_total").inc(3)
    obs.histogram("stream.round_ms").observe(1.25)
    path = emit_bench("unittest", config={"dim": 16, "smoke": True},
                      results={"rps": 123.4}, obs=obs,
                      out_dir=str(tmp_path))
    assert Path(path).name == "BENCH_unittest.json"
    doc = json.loads(Path(path).read_text())
    assert doc["name"] == "unittest"
    assert doc["config"]["dim"] == 16
    assert doc["results"]["rps"] == 123.4
    assert "jax" in doc["env"] and "backend" in doc["env"]
    h = doc["metrics"]["histograms"]["stream.round_ms"]
    assert h["count"] == 1 and "p50" in h and "p99" in h


# -- request spans, the profiler clock, compiles ---------------------------

def _traced_engine(**obs_kw):
    cfg = small_pfo_config()
    obs = Obs(metrics=True, trace=True, trace_capacity=1 << 14, **obs_kw)
    eng = StreamEngine(PFOIndex(cfg, seed=0, obs=obs),
                       StreamConfig(max_batch=16, min_batch=16))
    v = _vecs(96, cfg.dim, seed=11)
    client = eng.client()
    for i in range(48):
        client.insert(i, v[i])
    eng.flush()                                  # compiles out of the way
    return eng, obs, client, v


def test_request_spans_tile_each_request():
    """Four spans per ticket: they share the ticket as id and the round's
    first dispatch span as parent, and their durations sum to the
    flush's return minus the ticket's arrival."""
    eng, obs, client, v = _traced_engine()
    n0 = len(obs.tracer.events())
    t_arr = time.perf_counter() - 0.005
    tickets = ([client.query(v[i], 4, t_arrival=t_arr) for i in range(20)]
               + [client.delete(i, t_arrival=t_arr) for i in range(3)]
               + [client.insert(60 + i, v[60 + i], t_arrival=t_arr)
                  for i in range(2)]
               + [client.update(7, v[90], t_arrival=t_arr)])
    eng.flush()
    t_ret = time.perf_counter()
    ev = obs.tracer.events()[n0:]
    dispatch = {e[5]: e for e in ev if e[0] == "dispatch"}
    assert all(e[4]["rows"] <= e[4]["bucket"] for e in dispatch.values())
    spans: dict = {}
    for e in ev:
        if e[0].startswith("req_"):
            spans.setdefault(e[5], []).append(e)
    assert set(spans) == set(tickets)
    for t, sp in spans.items():
        assert sorted(e[0] for e in sp) == ["req_batch", "req_hold",
                                            "req_queue", "req_service"]
        assert len({e[6] for e in sp}) == 1 and sp[0][6] in dispatch
        total_ms = sum(e[2] for e in sp) / 1e3
        assert total_ms == pytest.approx((t_ret - t_arr) * 1e3, abs=1.0)
        queue = next(e for e in sp if e[0] == "req_queue")
        # the queue span starts at the arrival, on the profiler clock
        arr_us = (int(t_arr * 1e9) + obs.tracer._anchor) // 1000
        assert abs(queue[1] - arr_us) <= 1


def test_tracer_spans_lie_on_the_profiler_clock(tmp_path):
    """Each tracer ``flush`` span starts within 100 us of its
    ``TraceAnnotation`` twin on the capture's host plane; the bridge
    also keys the compilation cache on op metadata, so a profile never
    shows a cached executable's stale phase names."""
    from jax.profiler import ProfileData
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    try:
        eng, obs, client, v = _traced_engine(jax_annotations=True)
        assert getattr(jax.config, key) is True
    finally:
        jax.config.update(key, before)
    n0 = len(obs.tracer.events())
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            client.query(v[i], 4)
            eng.flush()
    finally:
        jax.profiler.stop_trace()
    mine = [e[1] * 1000 for e in obs.tracer.events()[n0:]
            if e[0] == "flush"]
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    start = dict(next(p for p in pd.planes
                      if p.name == "Task Environment").stats)
    twins = [ev.start_ns + start["profile_start_time"]
             for p in pd.planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events if ev.name == "flush"]
    assert len(mine) == len(twins) == 3
    for t in mine:
        assert min(abs(t - w) for w in twins) < 100_000


def test_compile_counter_counts_each_program_once():
    obs = Obs(metrics=True, trace=True)
    x = jnp.arange(7.0)
    f = jax.jit(lambda a: a * 3.0 + 1.0)
    c = obs.counter("jit.compiles")
    n0, e0 = c.value, len(obs.tracer.events())
    f(x).block_until_ready()
    assert c.value - n0 == 1
    f(x).block_until_ready()
    assert c.value - n0 == 1
    assert [e[0] for e in obs.tracer.events()[e0:]] == ["compile"]
    assert "jit.compiles" not in Obs(metrics=False).snapshot()["counters"]


# -- named device phases ----------------------------------------------------

def _step_paths(step: str) -> set:
    cfg = small_pfo_config()
    st = jax.eval_shape(lambda k: pfo.init_state(cfg, k),
                        jax.random.PRNGKey(0))
    s = jax.ShapeDtypeStruct
    b = 16
    ids, act = s((b,), jnp.int32), s((b,), jnp.bool_)
    vecs = s((b, cfg.dim), jnp.float32)
    lowered = {
        "query_step": lambda: pfo.query_step.lower(st, vecs, cfg, 4),
        "insert_step": lambda: pfo.insert_step.lower(
            st, ids, vecs, ids, act, s((b * cfg.L,), jnp.bool_), cfg,
            b, b),
        "delete_step": lambda: pfo.delete_step.lower(st, ids, act, cfg,
                                                     b, b),
        "merge_step": lambda: pfo.merge_step.lower(st, cfg),
        "seal_step": lambda: pfo.seal_step.lower(st, cfg),
    }[step]()
    return set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))


@pytest.mark.parametrize("step", sorted(phases.STEPS))
def test_step_phases_are_named_in_op_metadata(step):
    found = {phases.phase_of(p) for p in _step_paths(step)}
    assert set(phases.STEPS[step]) <= found, found


def test_phase_of_reads_through_transforms():
    assert phases.phase_of(
        "jit(query_step)/vmap(vmap(main_lookup))/vmap(jit(searchsorted))"
        "/while/body/gather") == "main_lookup"
    assert phases.phase_of(
        "jit(query_step)/main_lookup/ring_lookup/while/body"
        "/jit(searchsorted)/while/body/gather") == "ring_lookup"
    assert phases.phase_of(
        "jit(query_step)/rank/jit(gather_rank_pallas)/gather_rank"
        "/pallas_call") == "gather_rank"
    assert phases.phase_of("jit(merge_step)/vmap(reseal)/sort") == "reseal"
    assert phases.phase_of("jit(query_step)/concatenate") == \
        phases.UNSCOPED


def _xspace():
    """One chip: a query_step run 0-30 us (ops: a while loop 0-10 whose
    body runs a main_lookup op 2-8, rank's gather_rank kernel 10-25, an
    unnamed op 25-30) and a merge_step run 40-60 us (merge_filter 40-55,
    reseal 55-60)."""
    def ev(meta, t0_us, dur_us):
        return (f"events {{ metadata_id: {meta} offset_ps: "
                f"{int(t0_us * 1e6)} duration_ps: {int(dur_us * 1e6)} }}")

    def meta(key, name, path=None):
        stat = (f' stats {{ metadata_id: 9 str_value: "{path}:" }}'
                if path else "")
        return (f'event_metadata {{ key: {key} value {{ id: {key} '
                f'name: "{name}"{stat} }} }}')
    text = "\n".join([
        'planes { id: 1 name: "/device:TPU:0"',
        'lines { id: 1 name: "XLA Modules" timestamp_ns: 0',
        ev(1, 0, 30), ev(2, 40, 20), "}",
        'lines { id: 2 name: "XLA Ops" timestamp_ns: 0',
        ev(8, 0, 10), ev(3, 2, 6), ev(4, 10, 15), ev(5, 25, 5),
        ev(6, 40, 15),
        ev(7, 55, 5), "}",
        meta(1, "jit_query_step(11)"), meta(2, "jit_merge_step(12)"),
        meta(3, "%fusion.22 = s32[8]", "jit(query_step)/vmap(vmap("
             "main_lookup))/gather"),
        meta(4, "%gather_rank.1 = f32[8]", "jit(query_step)/rank/"
             "gather_rank/pallas_call"),
        meta(5, "%copy.3 = f32[8]", "jit(query_step)/copy"),
        meta(6, "%while.59 = s32[8]", "jit(merge_step)/vmap(merge_filter)"
             "/while"),
        meta(7, "%sort.2 = s32[8]", "jit(merge_step)/vmap(reseal)/sort"),
        meta(8, "%while.85 = s32[8]"),
        'stat_metadata { key: 9 value { id: 9 name: "tf_op" } }',
        "}"])
    from jax.profiler import ProfileData
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_device_time_by_phase_on_a_synthetic_capture():
    xs = _xspace()
    assert phases.op_paths(xs)["%while.59 = s32[8]"] == \
        "jit(merge_step)/vmap(merge_filter)/while"
    got = phases.device_time(xs)
    # the loop's own time is what its body leaves: 10 - 6 us
    want = {("jit_query_step", "main_lookup"): 6e-6,
            ("jit_query_step", "gather_rank"): 15e-6,
            ("jit_query_step", phases.UNSCOPED): 4e-6 + 5e-6,
            ("jit_merge_step", "merge_filter"): 15e-6,
            ("jit_merge_step", "reseal"): 5e-6}
    assert set(got) == set(want)
    for k, secs in want.items():
        assert got[k] == [pytest.approx(secs), 1]
    # a window clips op time and drops runs outside it
    got = phases.device_time(xs, window=(20_000, 50_000))
    assert got[("jit_query_step", "gather_rank")] == \
        [pytest.approx(5e-6), 1]
    assert got[("jit_merge_step", "merge_filter")] == \
        [pytest.approx(10e-6), 1]
