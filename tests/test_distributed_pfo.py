"""Distributed PFO (shard_map) on a 1-device mesh: semantics must match
the single-host index (routing degenerates, logic identical)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import small_pfo_config
from repro.core import DistConfig, dist_init_state, make_dist_insert, \
    make_dist_query
from repro.kernels import ops


@pytest.fixture(scope="module")
def dist_setup():
    cfg = small_pfo_config(dim=16, L=2, C=1, m=2, main_m=2,
                           max_leaves_per_tree=512,
                           main_max_leaves_per_tree=2048,
                           store_capacity=4096, max_candidates_total=128)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    dcfg = DistConfig(pfo=cfg, batch_axes=("data",), n_model=1)
    state = dist_init_state(dcfg, jax.random.PRNGKey(0), mesh)
    rng = np.random.default_rng(0)
    n = 600
    vecs = rng.normal(size=(n, 16)).astype(np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    ins = make_dist_insert(dcfg, mesh, capacity=2048)
    state, pending = ins(state, jnp.arange(n, dtype=jnp.int32),
                         jnp.asarray(vecs), jnp.ones(n, bool))
    assert int(pending.sum()) == 0
    qry = make_dist_query(dcfg, mesh, k=10)
    return state, qry, vecs


def test_dist_query_self_hit(dist_setup):
    state, qry, vecs = dist_setup
    ids, dists = qry(state, jnp.asarray(vecs[:16]))
    assert (np.asarray(ids)[:, 0] == np.arange(16)).all()
    np.testing.assert_allclose(np.asarray(dists)[:, 0], 0, atol=1e-5)


def test_dist_query_no_duplicate_ids(dist_setup):
    state, qry, vecs = dist_setup
    ids, _ = qry(state, jnp.asarray(vecs[:8]))
    for row in np.asarray(ids):
        live = row[row >= 0]
        assert len(live) == len(set(live.tolist()))


def test_dist_recall_beats_random(dist_setup):
    state, qry, vecs = dist_setup
    rng = np.random.default_rng(2)
    q = vecs[:16] + rng.normal(size=(16, 16)).astype(np.float32) * 0.05
    ids, _ = qry(state, jnp.asarray(q))
    oid, _ = ops.brute_force_topk(jnp.asarray(q), jnp.asarray(vecs), 10,
                                  "angular")
    oid = np.asarray(oid)
    rec = np.mean([len(set(np.asarray(ids)[i]) & set(oid[i])) / 10
                   for i in range(16)])
    assert rec > 0.1


def test_dedup_topk_keeps_k_distinct_ids_across_shard_copies():
    """Each of the S shards that found a candidate ranks its own copy;
    with every id present S times the merge still returns k distinct
    ids (keeping only the 2k nearest entries returned k/2)."""
    from repro.core.distributed import _dedup_topk
    S, k = 4, 10
    ids = np.repeat(np.arange(3 * k, dtype=np.int32), S)
    d = np.repeat(np.arange(3 * k, dtype=np.float32), S)
    out_ids, out_d = _dedup_topk(jnp.asarray(ids), jnp.asarray(d), k, S)
    np.testing.assert_array_equal(np.asarray(out_ids), np.arange(k))
    np.testing.assert_array_equal(np.asarray(out_d), np.arange(k))
