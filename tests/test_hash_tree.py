"""Adaptive hash tree unit + property tests (paper §5.1 semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # optional dep: deterministic fallback
    from _prop import given, settings, strategies as st

from repro.core.hash_tree import (TreeConfig, init_tree, tree_delete,
                                  tree_insert, tree_lookup, tree_query)

CFG = TreeConfig(skip_bits=2, log2_l=4, l=16, t=3, max_depth=7,
                 max_nodes=128, max_leaves=512, max_candidates=64)


def _insert_all(pairs, cfg=CFG):
    stt = init_tree(cfg)
    for h, vid in pairs:
        stt = tree_insert(stt, jnp.uint32(h), jnp.int32(vid),
                          jnp.int32(vid), cfg)
    return stt


def test_insert_then_query_returns_chain():
    stt = _insert_all([(0x80000000, 1), (0x80000001, 2)])
    ids, vals, n = tree_query(stt, jnp.uint32(0x80000000), CFG)
    got = set(np.asarray(ids)[np.asarray(ids) >= 0].tolist())
    assert 1 in got          # same bucket prefix keeps both reachable
    assert int(stt.n_items) == 2


def test_bucket_spread_after_t_exceeded():
    # 5 keys sharing the root slot but differing at the next level
    keys = [0x10000000 | (i << 20) for i in range(5)]
    stt = _insert_all([(k, i) for i, k in enumerate(keys)])
    # root slot must now point at a directory node (split happened)
    assert int(stt.node_cnt) >= 2
    for i, k in enumerate(keys):
        val, found = tree_lookup(stt, jnp.uint32(k), jnp.int32(i), CFG)
        assert bool(found) and int(val) == i


def test_delete_unlinks_and_reclaims():
    stt = _insert_all([(0xA0000000, 1), (0xA0000000, 2), (0xA0000000, 3)])
    stt, found = tree_delete(stt, jnp.uint32(0xA0000000), jnp.int32(2), CFG)
    assert bool(found)
    assert int(stt.n_items) == 2
    assert int(stt.free_head) > 0          # leaf on the free list
    _, f2 = tree_lookup(stt, jnp.uint32(0xA0000000), jnp.int32(2), CFG)
    assert not bool(f2)
    # free slot is reused by the next insert
    before = int(stt.leaf_cnt)
    stt = tree_insert(stt, jnp.uint32(0xA0000000), jnp.int32(9),
                      jnp.int32(9), CFG)
    assert int(stt.leaf_cnt) == before     # bump cursor untouched


def test_update_newest_version_wins():
    stt = _insert_all([(0xB0000000, 7)])
    stt = tree_insert(stt, jnp.uint32(0xB0000000), jnp.int32(7),
                      jnp.int32(123), CFG)
    val, found = tree_lookup(stt, jnp.uint32(0xB0000000), jnp.int32(7), CFG)
    assert bool(found) and int(val) == 123  # prepend => newest first


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=60,
                unique=True))
def test_property_every_inserted_key_is_findable(keys):
    pairs = [(k, i) for i, k in enumerate(keys)]
    stt = _insert_all(pairs)
    assert int(stt.overflow) == 0
    for k, i in pairs:
        val, found = tree_lookup(stt, jnp.uint32(k), jnp.int32(i), CFG)
        assert bool(found) and int(val) == i


@settings(max_examples=10, deadline=None)
@given(st.lists(st.integers(0, 2**32 - 1), min_size=2, max_size=40,
                unique=True),
       st.data())
def test_property_delete_removes_only_target(keys, data):
    pairs = [(k, i) for i, k in enumerate(keys)]
    stt = _insert_all(pairs)
    victim = data.draw(st.integers(0, len(keys) - 1))
    stt, found = tree_delete(stt, jnp.uint32(keys[victim]),
                             jnp.int32(victim), CFG)
    assert bool(found)
    for k, i in pairs:
        val, f = tree_lookup(stt, jnp.uint32(k), jnp.int32(i), CFG)
        if i == victim:
            assert not bool(f)
        else:
            assert bool(f) and int(val) == i


def test_chain_capped_query_still_terminates():
    # adversarial: many identical keys (chain growth at max depth)
    stt = _insert_all([(0xFFFFFFFF, i) for i in range(40)])
    ids, vals, n = tree_query(stt, jnp.uint32(0xFFFFFFFF), CFG)
    assert int(n) <= CFG.max_candidates
    assert int(stt.n_items) == 40


# ----------------------------------------------------------------------
# forest_insert_dispatched == a vmap over trees of the per-tree insert
# ----------------------------------------------------------------------
def _vmapped_tree_insert(forest, hs, ids, vals, cfg):
    """The per-tree reference: each tree scans its mailbox through
    :func:`tree_insert`, trees vmapped."""
    def per_tree(stt, hs, vids, vals):
        def step(stt, x):
            h, vid, val = x
            stt = jax.lax.cond(vid >= 0,
                               lambda s: tree_insert(s, h, vid, val, cfg),
                               lambda s: s, stt)
            return stt, ()
        return jax.lax.scan(step, stt, (hs, vids, vals))[0]
    return jax.vmap(per_tree)(forest, hs, ids, vals)


def _mailboxes(rng, kind, n_trees, k, base_id):
    if kind == "random":
        keys = rng.integers(0, 2**32, (n_trees, k), dtype=np.uint64)
    elif kind == "clustered":      # shared prefixes: deep spreads
        keys = (rng.integers(0, 4, (n_trees, k), dtype=np.uint64) << 28) \
            | rng.integers(0, 64, (n_trees, k), dtype=np.uint64)
    else:                          # identical keys: chains at max depth
        keys = np.full((n_trees, k), 0x5A5A5A5A, np.uint64)
    ids = base_id + np.arange(n_trees * k, dtype=np.int32).reshape(n_trees, k)
    ids = np.where(rng.random((n_trees, k)) < 0.15, -1, ids)  # padding
    return (jnp.asarray(keys.astype(np.uint32)), jnp.asarray(ids),
            jnp.asarray(ids * 3))


@pytest.mark.parametrize("kind", ["random", "clustered", "identical"])
@pytest.mark.parametrize("max_nodes", [64, 3])
def test_forest_insert_matches_per_tree_insert(kind, max_nodes):
    """Flat batched forest insert is field-for-field the vmapped
    per-tree insert — spreads, chains past ``t`` at max depth, node
    exhaustion (``max_nodes=3``), padding, and free-list reuse after
    deletes."""
    from repro.core.hash_tree import (forest_delete_dispatched,
                                      forest_insert_dispatched,
                                      init_forest)
    cfg = TreeConfig(skip_bits=2, log2_l=4, l=16, t=3, max_depth=4,
                     max_nodes=max_nodes, max_leaves=256,
                     max_candidates=16)
    rng = np.random.default_rng(len(kind) * 7 + max_nodes)
    n_trees, k = 5, 48
    got = want = init_forest(cfg, n_trees)
    for rnd in range(2):
        hs, ids, vals = _mailboxes(rng, kind, n_trees, k, rnd * 1000)
        got = forest_insert_dispatched(got, hs, ids, vals, cfg)
        want = _vmapped_tree_insert(want, hs, ids, vals, cfg)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # delete a third of the round: the next round pops the free list
        dels = jnp.where(jnp.asarray(rng.random(ids.shape) < 0.33), ids, -1)
        got = forest_delete_dispatched(got, hs, dels, cfg)
        want = forest_delete_dispatched(want, hs, dels, cfg)


def test_forest_insert_leaf_exhaustion_drops_and_counts():
    """A full leaf arena drops the record and counts it in ``overflow``;
    everything that did land stays findable."""
    from repro.core.hash_tree import (forest_insert_dispatched,
                                      forest_lookup, init_forest)
    cfg = TreeConfig(skip_bits=2, log2_l=4, l=16, t=3, max_depth=4,
                     max_nodes=64, max_leaves=40, max_candidates=64)
    rng = np.random.default_rng(11)
    hs, ids, vals = _mailboxes(rng, "clustered", 3, 64, 0)
    f = forest_insert_dispatched(init_forest(cfg, 3), hs, ids, vals, cfg)
    n_valid = np.sum(np.asarray(ids) >= 0, axis=1)
    np.testing.assert_array_equal(np.asarray(f.n_items),
                                  np.minimum(n_valid, 40))
    np.testing.assert_array_equal(np.asarray(f.overflow),
                                  np.maximum(n_valid - 40, 0))
    landed = np.asarray(f.leaf_id)                      # (T, 40)
    tids = np.repeat(np.arange(3), 40)
    keys = np.asarray(f.leaf_key).reshape(-1)
    _, found = forest_lookup(f, jnp.asarray(tids), jnp.asarray(keys),
                             jnp.asarray(landed.reshape(-1)), cfg)
    assert bool(jnp.all(found))
