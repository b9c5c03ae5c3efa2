"""The sealed MainTable ring's exact-key lookup (``snapshots.lookup_exact``).

Checked against the prefix-span lookup it replaced, kept here as the
plain reference, and against a newest-wins dict over the live
segments; then ``index._main_lookup`` against a dict model of id ->
store slot through inserts, seals, updates, deletes and merges.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import small_pfo_config
from repro.core import index as pfo
from repro.core import snapshots as snap_mod
from repro.core.config import PFOConfig
from repro.core.lsh import main_table_keys

S, CAP, UNIVERSE = 4, 512, 4096


def _cfg(prefix_bits: int = 16) -> PFOConfig:
    return PFOConfig(dim=8, L=2, C=1, m=2, snapshot_capacity=CAP,
                     max_snapshots=S, bloom_bits=1 << 12,
                     snap_prefix_bits=prefix_bits, snap_budget_per_probe=8,
                     snap_probes=1)


def _keys(ids) -> jax.Array:
    return main_table_keys(jnp.asarray(ids, jnp.int32), _cfg())[0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def span_lookup(snaps, h, vid, cfg):
    """The lookup before the exact-key search: the LSH tier's
    prefix-bucket probe over every segment, then the first id match in
    newest-first order."""
    def one(h1, vid1):
        cids, cvals = snap_mod.probe(snaps, h1[None], cfg)
        match = (cids[0] >= 0) & (cids[0] == vid1)
        found = jnp.any(match)
        return jnp.where(found, cvals[0, jnp.argmax(match)], -1), found
    return jax.vmap(one)(h, vid)


def _ring(n_live: int, cfg: PFOConfig, seed: int = 0):
    """All S segments sealed, each from a random id subset of the
    universe with segment-specific vals and some masked-out rows; then
    ``n_snaps`` cut to ``n_live``, so segments past it hold stale data.
    Returns (snaps, per-segment {id: val})."""
    rng = np.random.default_rng(seed)
    snaps = snap_mod.init_snapshots(cfg)
    segs = []
    for s in range(S):
        ids = rng.choice(UNIVERSE // 2, CAP - 16, replace=False)
        vals = 1000 * (s + 1) + rng.integers(0, 1000, ids.size)
        mask = rng.random(ids.size) > 0.1
        snaps = snap_mod.seal(snaps, _keys(ids), jnp.asarray(ids, jnp.int32),
                              jnp.asarray(vals, jnp.int32),
                              jnp.asarray(mask), jnp.int32(s + 1), cfg)
        segs.append(dict(zip(ids[mask].tolist(), vals[mask].tolist())))
    return snaps._replace(n_snaps=jnp.int32(n_live)), segs


def _newest_wins(segs, n_live):
    out = {}
    for seg in segs[:n_live]:
        out.update(seg)
    return out


def _query_ids(case: str, segs, n_live: int, rng) -> np.ndarray:
    live = set().union(*segs[:n_live])
    stale = set().union(*segs[n_live:]) - live
    absent = np.arange(UNIVERSE // 2, UNIVERSE)
    pick = {
        "present": lambda: np.array(sorted(live)),
        "absent": lambda: absent,
        "stale": lambda: np.array(sorted(stale)),
        "negative": lambda: np.full(64, -1),
    }
    if case == "mixed":
        return rng.permutation(np.concatenate(
            [pick[c]()[:200] for c in pick]))
    return pick[case]()


CASES = [(c, n) for c in ("present", "absent", "negative", "mixed")
         for n in range(1, S + 1)] + [("stale", n) for n in range(1, S)]


@pytest.mark.parametrize("case,n_live", CASES)
def test_lookup_exact_matches_prefix_span_lookup(case, n_live):
    cfg = _cfg()
    snaps, segs = _ring(n_live, cfg, seed=n_live)
    ids = _query_ids(case, segs, n_live, np.random.default_rng(7))
    assert ids.size
    h, vid = _keys(ids), jnp.asarray(ids, jnp.int32)
    val, found = jax.device_get(snap_mod.lookup_exact(snaps, h, vid))
    ref_val, ref_found = jax.device_get(span_lookup(snaps, h, vid, cfg))
    np.testing.assert_array_equal(found, ref_found)
    np.testing.assert_array_equal(val, ref_val)
    model = _newest_wins(segs, n_live)
    want = np.array([model.get(i, -1) for i in ids.tolist()])
    np.testing.assert_array_equal(val, want)
    np.testing.assert_array_equal(found, want >= 0)
    if case == "present":
        assert found.all()
    elif case != "mixed":
        assert not found.any()


def test_lookup_exact_scalar():
    cfg = _cfg()
    snaps, segs = _ring(2, cfg)
    vid = sorted(segs[1])[0]
    val, found = snap_mod.lookup_exact(snaps, _keys(vid), jnp.int32(vid))
    assert val.shape == () and bool(found)
    assert int(val) == segs[1][vid]


def test_lookup_exact_finds_ids_past_the_probe_budget():
    """16 prefix buckets of ~30 entries against an 8-entry window: the
    prefix-span lookup misses most ids of a segment, the exact-key
    search none, and the two agree wherever the span lookup finds one."""
    cfg = _cfg(prefix_bits=4)
    snaps, segs = _ring(1, cfg)
    model = segs[0]
    ids = np.array(sorted(model))
    h, vid = _keys(ids), jnp.asarray(ids, jnp.int32)
    val, found = jax.device_get(snap_mod.lookup_exact(snaps, h, vid))
    ref_val, ref_found = jax.device_get(span_lookup(snaps, h, vid, cfg))
    assert found.all()
    np.testing.assert_array_equal(val, [model[i] for i in ids.tolist()])
    assert 0 < ref_found.sum() < ids.size // 2
    np.testing.assert_array_equal(val[ref_found], ref_val[ref_found])


# ----------------------------------------------------- _main_lookup model
_lookup = jax.jit(pfo._main_lookup, static_argnames=("cfg",))


def _slots_of(state, vecs: np.ndarray) -> list[int]:
    """The live store row holding each vector (each exactly one)."""
    data = np.asarray(state.store.data)
    live = np.asarray(state.store.live)
    out = []
    for v in vecs:
        rows = np.flatnonzero(live & (data == v).all(axis=1))
        assert rows.size == 1, rows
        out.append(int(rows[0]))
    return out


def _check(index, model: dict, deleted: set, merged: bool) -> None:
    """Live ids resolve to their model slot; a deleted id is not found,
    except through a sealed copy its tombstone still covers (the
    tombstone filters it on every read until a merge drops it)."""
    st = index.state
    ids = np.array(sorted(model) + sorted(deleted), np.int32)
    slot, found = jax.device_get(_lookup(st, jnp.asarray(ids), index.cfg))
    n = len(model)
    assert found[:n].all()
    np.testing.assert_array_equal(slot[:n], [model[i] for i in ids[:n]])
    tombs = set(np.asarray(st.tombstones).tolist())
    for i, f in zip(ids[n:].tolist(), found[n:]):
        assert not f or (not merged and i in tombs), i


def test_main_lookup_follows_a_dict_model():
    cfg = small_pfo_config()
    index = pfo.PFOIndex(cfg, seed=3)
    rng = np.random.default_rng(5)
    model, deleted = {}, set()
    vec = {}

    def write(op, ids):
        vs = rng.normal(size=(len(ids), cfg.dim)).astype(np.float32)
        op(np.asarray(ids, np.int32), vs)
        vec.update(zip(ids, vs))
        model.update(zip(ids, _slots_of(index.state, vs)))
        deleted.difference_update(ids)

    def delete(ids):
        index.delete(np.asarray(ids, np.int32))
        for i in ids:
            model.pop(i)
        deleted.update(ids)

    def step(fn):
        index.state = fn(index.state, cfg)
        index._flags = None          # the carried flag word is stale

    write(index.insert, list(range(0, 300)))
    _check(index, model, deleted, merged=False)
    step(pfo.seal_step)
    _check(index, model, deleted, merged=False)
    write(index.insert, list(range(300, 500)))
    write(index.update, list(range(0, 40)) + list(range(300, 320)))
    _check(index, model, deleted, merged=False)
    delete(list(range(40, 80)) + list(range(320, 360)))
    _check(index, model, deleted, merged=False)
    step(pfo.seal_step)
    assert int(index.state.main_snaps.n_snaps) == 2
    _check(index, model, deleted, merged=False)
    delete(list(range(80, 100)) + list(range(360, 380)))
    step(pfo.merge_step)
    assert int(index.state.main_snaps.n_snaps) == 1
    _check(index, model, deleted, merged=True)
    write(index.insert, list(range(500, 560)) + list(range(40, 60)))
    step(pfo.seal_step)
    _check(index, model, deleted, merged=False)

    live = np.array(sorted(model), np.int32)
    got, _ = index.query(np.stack([vec[i] for i in live[:64]]), k=4)
    assert not set(got.ravel().tolist()) & deleted
    assert (got[:, 0] == live[:64]).mean() > 0.9
    _check(index, model, deleted, merged=False)
