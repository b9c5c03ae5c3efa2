"""Dispatch (actor mailboxes), dense/sparse stores, bloom, snapshots —
unit + hypothesis property tests on the core invariants."""
import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:            # optional dep: deterministic fallback
    from _prop import given, settings, strategies as st

from repro.core import bloom
from repro.core.config import PFOConfig
from repro.core.dispatch import dispatch_to_trees, gather_mailbox, mailbox_ids
from repro.core.store import (dense_alloc, dense_free, dense_init,
                              dense_read, sparse_free, sparse_init,
                              sparse_read, sparse_to_dense, sparse_write)
from repro.core import snapshots as snap_mod


# ----------------------------------------------------------- dispatch
@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(-1, 7), min_size=1, max_size=64),
       st.integers(1, 8))
def test_dispatch_partition_properties(tree_ids, cap):
    """Every valid request lands exactly once (mailbox or overflow);
    no mailbox slot holds a request for the wrong tree."""
    t = jnp.asarray(tree_ids, jnp.int32)
    mbox, ovf = dispatch_to_trees(t, 8, cap)
    mbox, ovf = np.asarray(mbox), np.asarray(ovf)
    placed = mbox[mbox >= 0]
    assert len(placed) == len(set(placed.tolist()))        # no dupes
    for tree in range(8):
        for slot in mbox[tree][mbox[tree] >= 0]:
            assert tree_ids[slot] == tree                  # right mailbox
    for i, tid in enumerate(tree_ids):
        if tid >= 0:
            assert (i in placed.tolist()) != bool(ovf[i])  # exactly once
        else:
            assert i not in placed.tolist() and not ovf[i]


def test_dispatch_order_within_tree_is_stable():
    t = jnp.asarray([2, 2, 2, 1, 2], jnp.int32)
    mbox, _ = dispatch_to_trees(t, 4, 8)
    row = np.asarray(mbox)[2]
    assert row[:4].tolist() == [0, 1, 2, 4]


def test_gather_mailbox_and_ids():
    t = jnp.asarray([1, 1, 0], jnp.int32)
    ids = jnp.asarray([10, 11, 12], jnp.int32)
    payload = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
    mbox, _ = dispatch_to_trees(t, 2, 2)
    (g,) = gather_mailbox(mbox, payload)
    mi = mailbox_ids(mbox, ids)
    assert np.asarray(mi)[0, 0] == 12
    assert set(np.asarray(mi)[1].tolist()) >= {10, 11}
    assert g.shape == (2, 2, 2)


# ----------------------------------------------------------- dense store
@settings(max_examples=15, deadline=None)
@given(st.integers(1, 30), st.integers(0, 29))
def test_dense_store_alloc_free_no_leak(n_alloc, n_free):
    n_free = min(n_free, n_alloc)
    stt = dense_init(32, 4)
    vecs = jnp.arange(n_alloc * 4, dtype=jnp.float32).reshape(n_alloc, 4)
    stt, slots, ok = dense_alloc(stt, vecs, jnp.ones(n_alloc, bool))
    assert bool(ok.all())
    got = dense_read(stt, slots)
    np.testing.assert_allclose(np.asarray(got), np.asarray(vecs))
    free_before = int(stt.free_top)
    stt = dense_free(stt, slots[:n_free], jnp.ones(n_free, bool))
    assert int(stt.free_top) == free_before + n_free
    # double free is a no-op
    stt2 = dense_free(stt, slots[:n_free], jnp.ones(n_free, bool))
    assert int(stt2.free_top) == int(stt.free_top)


def test_dense_store_duplicate_free_in_one_batch_frees_once():
    """Two rows freeing the same slot in ONE batch must reclaim it once;
    a double push would later hand the slot to two different ids."""
    stt = dense_init(16, 2)
    vecs = jnp.ones((3, 2), jnp.float32)
    stt, slots, _ = dense_alloc(stt, vecs, jnp.ones(3, bool))
    free_before = int(stt.free_top)
    dup = jnp.asarray([int(slots[0]), int(slots[0]), int(slots[1])],
                      jnp.int32)
    stt = dense_free(stt, dup, jnp.ones(3, bool))
    assert int(stt.free_top) == free_before + 2     # not +3
    # the two re-allocations must get distinct slots
    stt, news, ok = dense_alloc(stt, vecs[:2], jnp.ones(2, bool))
    assert bool(ok.all()) and int(news[0]) != int(news[1])


def test_dense_store_full_returns_not_ok():
    stt = dense_init(4, 2)
    vecs = jnp.ones((6, 2), jnp.float32)
    stt, slots, ok = dense_alloc(stt, vecs, jnp.ones(6, bool))
    assert int(ok.sum()) == 4
    assert (np.asarray(slots)[~np.asarray(ok)] == -1).all()


# ----------------------------------------------------------- sparse store
def test_sparse_store_roundtrip_and_chaining():
    stt = sparse_init(n_blocks=16, granule=4)
    idxs = jnp.asarray([0, 3, 9, 11, 15, -1, -1, -1], jnp.int32)
    vals = jnp.asarray([1., 2., 3., 4., 5., 0, 0, 0], jnp.float32)
    stt, head, ok = sparse_write(stt, idxs, vals)
    assert bool(ok)
    ri, rv = sparse_read(stt, head, 8)
    dense = sparse_to_dense(ri, rv, 16)
    assert float(dense[3]) == 2.0 and float(dense[15]) == 5.0
    free_before = int(stt.n_free)
    stt = sparse_free(stt, head, max_chain=4)
    assert int(stt.n_free) == free_before + 2   # 5 nnz / granule 4 -> 2


def test_sparse_store_size_class_reuse():
    stt = sparse_init(n_blocks=8, granule=4)
    idxs = jnp.asarray([1, 2, -1, -1], jnp.int32)
    vals = jnp.asarray([1., 1., 0., 0.], jnp.float32)
    stt, h1, _ = sparse_write(stt, idxs, vals)
    stt = sparse_free(stt, h1, max_chain=2)
    stt, h2, _ = sparse_write(stt, idxs, vals)
    assert int(h2) == int(h1)                   # freed block reused


# ----------------------------------------------------------- bloom
@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2**32 - 1), min_size=1, max_size=100,
                unique=True))
def test_bloom_no_false_negatives(keys):
    arr = jnp.asarray(keys, jnp.uint32)
    filt = bloom.build(arr, n_hashes=4, bloom_bits=1 << 12)
    assert bool(bloom.contains(filt, arr, 4).all())


def test_bloom_false_positive_rate_reasonable():
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(0, 2**31, 500), jnp.uint32)
    filt = bloom.build(keys, n_hashes=4, bloom_bits=1 << 14)
    probe = jnp.asarray(rng.integers(2**31, 2**32 - 1, 2000), jnp.uint32)
    fp = float(bloom.contains(filt, probe, 4).mean())
    assert fp < 0.05


# ----------------------------------------------------------- snapshots
def test_snapshot_seal_probe_merge():
    cfg = PFOConfig(dim=8, L=2, C=1, m=2, snapshot_capacity=64,
                    max_snapshots=4, bloom_bits=1 << 10,
                    snap_prefix_bits=4, snap_budget_per_probe=8)
    snaps = snap_mod.init_snapshots(cfg)
    keys = jnp.asarray([0x10000000, 0x10000001, 0xF0000000], jnp.uint32)
    ids = jnp.asarray([1, 2, 3], jnp.int32)
    vals = jnp.asarray([10, 20, 30], jnp.int32)
    snaps = snap_mod.seal(snaps, keys, ids, vals,
                          jnp.ones(3, bool), jnp.int32(1), cfg)
    cids, cvals = snap_mod.probe(snaps, jnp.asarray([0x10000002],
                                                    jnp.uint32), cfg)
    got = set(np.asarray(cids)[0][np.asarray(cids)[0] >= 0].tolist())
    assert got == {1, 2}
    # newest version wins after merge
    snaps = snap_mod.seal(snaps, keys[:1], ids[:1],
                          jnp.asarray([99], jnp.int32),
                          jnp.ones(1, bool), jnp.int32(2), cfg)
    merged = snap_mod.merge(snaps, cfg)
    val, found = snap_mod.lookup_exact(merged, jnp.uint32(0x10000000),
                                       jnp.int32(1))
    assert bool(found) and int(val) == 99
    assert int(merged.n_snaps) == 1
