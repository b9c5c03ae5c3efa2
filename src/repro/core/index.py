"""PFOIndex — the public API assembling the paper's full system.

Layout (paper §3, Fig. 1): one MainTable (id -> vector, murmur-hashed)
plus ``L`` LSHTables (compound key -> id).  Every table is a Partitioned
Hash Forest (§4.1) living in pre-allocated device arrays (the off-heap
tier); overflowing tables *seal* into read-only snapshot segments with
Bloom summaries (§3.2.2, the flash tier); queries union hot + sealed
candidates from all L tables, dedupe, fetch vectors from the MainTable
store, and exact-rank (§3.1).

Concurrency (§4.2): request batches are dispatched into per-tree
mailboxes (``dispatch.py``) and applied with tree-level parallelism —
the actor model's single-writer guarantee, SPMD-style.  The host loop
re-submits mailbox overflow in rounds and handles seal/merge epochs
(the paper's maintenance routines).  Arena exhaustion is prevented by
construction: a round adds at most ``capacity`` leaves and nodes per
tree, and the host seals whenever the headroom falls below that bound
(the in-tree overflow counter stays zero; it is asserted in tests).

All L LSH tables are stacked into one forest with global tree ids
``table * 2^(C+m) + region`` so a single dispatch covers every table.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Obs

from . import coldtier
from . import snapshots as snap_mod
from .config import PFOConfig
from .dispatch import (FLAG_ANY_PENDING, FLAG_COLD_FULL, FLAG_COLD_MISS,
                       FLAG_COLD_SPILL, FLAG_NEED_SEAL, FLAG_SNAPS_FULL,
                       FLAG_STORE_FULL, FLAG_TOMBS_FULL, dispatch_to_trees,
                       gather_mailbox, mailbox_ids, pack_round_flags)
from .hash_tree import (TreeConfig, TreeState, forest_delete_dispatched,
                        forest_headroom, forest_insert_dispatched,
                        forest_lookup, forest_query, init_forest)
from .lsh import main_table_keys, make_projections, region_ids
from .membership import member_sorted
from .store import (DenseStore, dense_alloc, dense_free, dense_init,
                    dense_read, dense_read_tiered)

INT_MAX = jnp.int32(2**31 - 1)
MAX_TOMBSTONES = 1024        # default for PFOConfig.max_tombstones


def lsh_tree_config(cfg: PFOConfig) -> TreeConfig:
    return TreeConfig(
        skip_bits=cfg.m, log2_l=cfg.log2_l, l=cfg.l, t=cfg.t,
        max_depth=cfg.max_depth, max_nodes=cfg.max_nodes_per_tree,
        max_leaves=cfg.max_leaves_per_tree,
        max_candidates=cfg.max_candidates_per_probe,
        sibling_probe=cfg.sibling_probe,
        traversal=cfg.traversal, max_chain=cfg.max_chain)


def main_tree_config(cfg: PFOConfig) -> TreeConfig:
    return TreeConfig(
        skip_bits=cfg.main_m, log2_l=cfg.log2_l, l=cfg.l, t=cfg.t,
        max_depth=cfg.main_max_depth, max_nodes=cfg.main_max_nodes_per_tree,
        max_leaves=cfg.main_max_leaves_per_tree,
        max_candidates=cfg.max_candidates_per_probe,
        traversal=cfg.traversal, max_chain=cfg.max_chain)


class PFOState(NamedTuple):
    lsh_forest: TreeState        # leading axis L * 2^(C+m)
    main_forest: TreeState       # leading axis 2^main_m
    store: DenseStore
    lsh_snaps: snap_mod.SnapshotSet   # leading axis L
    main_snaps: snap_mod.SnapshotSet
    tombstones: jax.Array        # i32 (MAX_TOMBSTONES,) -1 pad
    n_tombstones: jax.Array      # i32 ()
    stamp: jax.Array             # i32 () seal epoch counter
    proj: dict                   # LSH projection params
    # cold-tier routing table + device segment cache; None when the
    # cold tier is disabled (the pytree then has no cold leaves, so
    # every pre-cold jitted program and sharding spec is unchanged)
    cold: coldtier.ColdState | None = None


def _snap_cfg_lsh(cfg: PFOConfig) -> PFOConfig:
    cap = cfg.n_trees * cfg.max_leaves_per_tree
    return PFOConfig(**{**cfg.__dict__, "snapshot_capacity": cap})


def _snap_cfg_main(cfg: PFOConfig) -> PFOConfig:
    cap = cfg.main_n_trees * cfg.main_max_leaves_per_tree
    return PFOConfig(**{**cfg.__dict__, "snapshot_capacity": cap})


def init_state(cfg: PFOConfig, key: jax.Array) -> PFOState:
    lsh_cfg, main_cfg = lsh_tree_config(cfg), main_tree_config(cfg)
    lsh_snaps = jax.vmap(lambda _: snap_mod.init_snapshots(_snap_cfg_lsh(cfg)))(
        jnp.arange(cfg.L))
    return PFOState(
        lsh_forest=init_forest(lsh_cfg, cfg.L * cfg.n_trees),
        main_forest=init_forest(main_cfg, cfg.main_n_trees),
        store=dense_init(cfg.store_capacity, cfg.dim),
        lsh_snaps=lsh_snaps,
        main_snaps=snap_mod.init_snapshots(_snap_cfg_main(cfg)),
        tombstones=jnp.full((cfg.max_tombstones,), -1, jnp.int32),
        n_tombstones=jnp.int32(0),
        stamp=jnp.int32(0),
        proj=make_projections(key, cfg),
        cold=coldtier.init_cold(cfg, _snap_cfg_lsh(cfg),
                                _snap_cfg_main(cfg)),
    )


# ======================================================================
# jitted pipelines
# ======================================================================
def compute_keys(state: PFOState, vecs: jax.Array, cfg: PFOConfig):
    """(N,d) -> compound keys (N,L) and global tree ids (N,L)."""
    from repro.kernels import ops as kops
    h = kops.lsh_hash(vecs, state.proj["table_proj"], cfg.M)     # (N, L)
    region = region_ids(h, state.proj["part_proj"], cfg)         # (N, L)
    table_off = jnp.arange(cfg.L, dtype=jnp.int32)[None] * cfg.n_trees
    return h, region + table_off


def _tombs_threshold(cfg: PFOConfig) -> int:
    """Proactive-merge watermark: leave one round of delete headroom."""
    return cfg.max_tombstones - max(1, min(64, cfg.max_tombstones // 4))


def _cold_full_threshold(cfg: PFOConfig) -> int:
    """Routing-table watermark that kicks the background compaction —
    enough headroom left for the spills that land while it runs."""
    return cfg.cold_segments - max(1, cfg.cold_segments // 4)


@jax.named_scope("flags")
def _round_flags(state: PFOState, cfg: PFOConfig, main_capacity: int,
                 lsh_capacity: int, any_pending: jax.Array,
                 cold_miss: jax.Array | None = None) -> jax.Array:
    """Device-side maintenance decision for the *next* round, packed.

    A round adds at most ``capacity`` leaves and nodes per tree (module
    doc), so comparing the worst-tree cursors against the arena sizes
    decides seal; snapshot-set and tombstone occupancy decide merge.
    With a cold tier, a full ring spills (COLD_SPILL) instead of
    merging, and routing-table occupancy arms the background
    compaction (COLD_FULL).  All of it stays on device — the host
    reads back one i32.
    """
    leaf_head, node_head = forest_headroom(state.lsh_forest)
    mleaf, mnode = forest_headroom(state.main_forest)
    need_seal = (
        (leaf_head + lsh_capacity > cfg.max_leaves_per_tree)
        | (node_head + lsh_capacity > cfg.max_nodes_per_tree)
        | (mleaf + main_capacity > cfg.main_max_leaves_per_tree)
        | (mnode + main_capacity > cfg.main_max_nodes_per_tree)
        | (leaf_head >= jnp.int32(
            int(cfg.seal_threshold * cfg.max_leaves_per_tree))))
    ring_full = (jnp.max(state.lsh_snaps.n_snaps)
                 >= cfg.max_snapshots - 1)
    tombs_full = state.n_tombstones >= _tombs_threshold(cfg)
    if cfg.cold_enabled:
        # capacity relief is a spill, never a merge — SNAPS_FULL stays 0
        cold_spill = ring_full
        store_full = None
        if cfg.store_low_watermark:
            # tiered store pressure: free slots under the watermark.
            # Relief is spilling ring payloads off-device; with an empty
            # ring the hot forest must seal first so there is something
            # to spill.  (Python-gated: watermark-off programs keep the
            # exact pre-tiered flag trace.)
            store_low = state.store.free_top < cfg.store_low_watermark
            ring_nonempty = state.main_snaps.n_snaps > 0
            hot_nonempty = jnp.sum(state.main_forest.n_items) > 0
            cold_spill = cold_spill | (store_low & ring_nonempty)
            need_seal = need_seal | (store_low & ~ring_nonempty
                                     & hot_nonempty)
            store_full = store_low
        return pack_round_flags(
            jnp.asarray(any_pending), need_seal, jnp.bool_(False),
            tombs_full, cold_spill=cold_spill,
            cold_full=state.cold.n_cold >= _cold_full_threshold(cfg),
            cold_miss=cold_miss, store_full=store_full)
    return pack_round_flags(jnp.asarray(any_pending), need_seal,
                            ring_full, tombs_full)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "main_capacity", "lsh_capacity"))
def round_flags(state: PFOState, cfg: PFOConfig, main_capacity: int,
                lsh_capacity: int) -> jax.Array:
    """Standalone flag computation (cold start / capacity change only —
    steady-state rounds get their flags from the step itself)."""
    return _round_flags(state, cfg, main_capacity, lsh_capacity,
                        jnp.bool_(False))


@functools.partial(jax.jit,
                   static_argnames=("cfg", "main_capacity", "lsh_capacity",
                                    "flags_main_capacity",
                                    "flags_lsh_capacity"))
def insert_step(state: PFOState, ids: jax.Array, vecs: jax.Array,
                slots_in: jax.Array, main_active: jax.Array,
                lsh_active: jax.Array, cfg: PFOConfig, main_capacity: int,
                lsh_capacity: int, flags_main_capacity: int | None = None,
                flags_lsh_capacity: int | None = None):
    """One dispatch round of batched insert.

    ids/vecs: (N,), (N,d).  ``slots_in``: -2 == store slot not yet
    allocated.  ``main_active`` (N,) / ``lsh_active`` (N*L,) mark
    requests still pending — tracked per *request* so a retry round
    never double-inserts entries that already landed.
    Returns (state, slots, main_pending, lsh_pending, flags) where
    ``flags`` is the packed maintenance word for the next round.
    ``flags_*_capacity`` override the capacity the flag headroom is
    computed against (the stream engine passes its worst-case bucket so
    one carried flag word stays valid across bucket sizes).
    """
    # --- store allocation (at most once per row) ---------------------
    with jax.named_scope("store_alloc"):
        need_alloc = (slots_in == -2) & main_active
        store, new_slots, alloc_ok = dense_alloc(state.store, vecs,
                                                 need_alloc)
        slots = jnp.where(need_alloc & alloc_ok, new_slots, slots_in)
        state = state._replace(store=store)
        have_slot = slots >= 0

    # --- MainTable insert --------------------------------------------
    with jax.named_scope("main_insert"):
        # re-inserting a previously-deleted id revokes its tombstone
        # (the fresh hot MainTable entry shadows any stale sealed copies)
        revived = member_sorted(state.tombstones,
                                jnp.where(main_active, ids, -1))
        state = state._replace(
            tombstones=jnp.where(revived, -1, state.tombstones))
        mh, mtree = main_table_keys(ids, cfg)
        m_req = jnp.where(main_active & have_slot, mtree, -1)
        mbox, m_ovf = dispatch_to_trees(m_req, cfg.main_n_trees,
                                        main_capacity)
        (mh_g,) = gather_mailbox(mbox, mh)
        mid_g = mailbox_ids(mbox, ids)
        (mval_g,) = gather_mailbox(mbox, slots)
        main_forest = forest_insert_dispatched(
            state.main_forest, mh_g, mid_g, mval_g, main_tree_config(cfg))

    # --- LSHTables insert ---------------------------------------------
    with jax.named_scope("lsh_insert"):
        h, gtrees = compute_keys(state, vecs, cfg)               # (N, L)
        flat_h = h.reshape(-1)
        flat_id = jnp.repeat(ids, cfg.L)
        l_req = jnp.where(lsh_active & jnp.repeat(have_slot, cfg.L),
                          gtrees.reshape(-1), -1)
        lbox, l_ovf = dispatch_to_trees(l_req, cfg.L * cfg.n_trees,
                                        lsh_capacity)
        (lh_g,) = gather_mailbox(lbox, flat_h)
        lid_g = mailbox_ids(lbox, flat_id)
        lsh_forest = forest_insert_dispatched(
            state.lsh_forest, lh_g, lid_g, lid_g, lsh_tree_config(cfg))

    state = state._replace(main_forest=main_forest, lsh_forest=lsh_forest)

    main_pending = main_active & (m_ovf | ~have_slot)
    lsh_pending = lsh_active & (l_ovf | ~jnp.repeat(have_slot, cfg.L))
    flags = _round_flags(state, cfg,
                         flags_main_capacity or main_capacity,
                         flags_lsh_capacity or lsh_capacity,
                         jnp.any(main_pending) | jnp.any(lsh_pending))
    return state, slots, main_pending, lsh_pending, flags


@functools.partial(jax.jit, static_argnames=("cfg",))
def seal_step(state: PFOState, cfg: PFOConfig) -> PFOState:
    """Seal every LSH table + the MainTable into snapshot segments and
    reset the hot forests (paper §3.2.2)."""
    stamp = state.stamp + 1

    lf = state.lsh_forest
    L, T, ML = cfg.L, cfg.n_trees, cfg.max_leaves_per_tree
    keys = lf.leaf_key.reshape(L, T * ML)
    ids = lf.leaf_id.reshape(L, T * ML)
    vals = lf.leaf_val.reshape(L, T * ML)
    lsh_snaps = jax.vmap(
        lambda s, k, i, v: snap_mod.seal(s, k, i, v, i >= 0, stamp,
                                         _snap_cfg_lsh(cfg)))(
        state.lsh_snaps, keys, ids, vals)

    mf = state.main_forest
    main_snaps = snap_mod.seal(state.main_snaps, mf.leaf_key.reshape(-1),
                               mf.leaf_id.reshape(-1),
                               mf.leaf_val.reshape(-1),
                               mf.leaf_id.reshape(-1) >= 0, stamp,
                               _snap_cfg_main(cfg))

    return state._replace(
        lsh_forest=init_forest(lsh_tree_config(cfg), L * T),
        main_forest=init_forest(main_tree_config(cfg), cfg.main_n_trees),
        lsh_snaps=lsh_snaps, main_snaps=main_snaps, stamp=stamp)


@functools.partial(jax.jit, static_argnames=("cfg",))
def merge_step(state: PFOState, cfg: PFOConfig) -> PFOState:
    tombs = state.tombstones
    lsh_snaps = jax.vmap(
        lambda s: snap_mod.merge(s, _snap_cfg_lsh(cfg), tombs))(
        state.lsh_snaps)
    main_snaps = snap_mod.merge(state.main_snaps, _snap_cfg_main(cfg), tombs)
    return state._replace(
        lsh_snaps=lsh_snaps, main_snaps=main_snaps,
        tombstones=jnp.full_like(state.tombstones, -1),
        n_tombstones=jnp.int32(0))


@jax.named_scope("main_lookup")
def _main_lookup(state: PFOState, ids: jax.Array, cfg: PFOConfig):
    """(N,) id -> (slot, found), searching hot forest then sealed tier."""
    mh, mtree = main_table_keys(ids, cfg)
    val, found = forest_lookup(state.main_forest, mtree, mh, ids,
                               main_tree_config(cfg))
    sval, sfound = snap_mod.lookup_exact(state.main_snaps, mh, ids)
    slot = jnp.where(found, val, jnp.where(sfound, sval, -1))
    return slot, found | sfound


def _hot_sealed_candidates(state: PFOState, qvecs: jax.Array,
                           cfg: PFOConfig):
    """Shared head of the read path: hash, probe hot trees fully
    parallel, probe the sealed ring Bloom-first (newest segments
    first).  Returns (h (Q, L), cand (Q, L*mc + L*S*P*B))."""
    q = qvecs.shape[0]
    with jax.named_scope("hash"):
        h, gtrees = compute_keys(state, qvecs, cfg)              # (Q, L)
    with jax.named_scope("hot_descent"):
        flat_ids, _, _ = forest_query(state.lsh_forest,
                                      gtrees.reshape(-1), h.reshape(-1),
                                      lsh_tree_config(cfg))
        hot = flat_ids.reshape(q, -1)                            # (Q, L*mc)

    def per_table(snaps_l, h_l):
        cids, _ = snap_mod.probe(snaps_l, h_l, _snap_cfg_lsh(cfg))
        return cids                                              # (Q, S*P*B)

    with jax.named_scope("sealed_probe"):
        sealed = jax.vmap(per_table, in_axes=(0, 1), out_axes=1)(
            state.lsh_snaps, h)                                  # (Q, L, ·)
        return h, jnp.concatenate([hot, sealed.reshape(q, -1)], axis=1)


@jax.named_scope("dedupe")
def _dedupe_candidates(cand: jax.Array, tombstones: jax.Array,
                       cfg: PFOConfig) -> jax.Array:
    """Tombstone filter + dedupe + truncate to the ranking budget:
    (Q, C_any) -> (Q, max_candidates_total), -1 pad."""
    q = cand.shape[0]
    dead = member_sorted(cand, tombstones) & (cand >= 0)
    skey = jnp.where((cand >= 0) & ~dead, cand, INT_MAX)
    skey = jnp.sort(skey, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((q, 1), bool), skey[:, 1:] == skey[:, :-1]], axis=1)
    uniq = jnp.sort(jnp.where(dup, INT_MAX, skey), axis=1)
    uniq = uniq[:, :cfg.max_candidates_total]                    # (Q, Ct)
    return jnp.where(uniq == INT_MAX, -1, uniq)


@jax.named_scope("rank")
def _rank_candidates(state: PFOState, qvecs: jax.Array, cids: jax.Array,
                     slot: jax.Array, found: jax.Array, cfg: PFOConfig,
                     k: int, staging: jax.Array | None = None):
    """Exact re-rank: the fused gather+rank+top-k kernel path reads
    candidate vectors straight out of the store by slot id — no
    (Q, Ct, d) candidate block is ever materialized.  ``staging`` is
    the cold tier's flattened device payload arena; slots
    ``>= store_capacity`` gather from it (``staging=None`` keeps the
    exact pre-tiered kernel program)."""
    from repro.kernels import ops as kops
    valid = (cids >= 0) & found & (slot >= 0)
    idx, top_d = kops.gather_rank_topk(qvecs, state.store.data,
                                       jnp.where(valid, slot, 0), valid,
                                       k, cfg.metric, staging=staging)
    top_ids = jnp.take_along_axis(cids, idx, axis=1)
    return jnp.where(jnp.isfinite(top_d), top_ids, -1), top_d


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def query_step(state: PFOState, qvecs: jax.Array, cfg: PFOConfig, k: int):
    """Batched kNN query: (Q,d) -> (ids (Q,k), dists (Q,k)).

    Paper §3.1 read path: hash into every LSHTable, union A(q) from hot
    trees + sealed segments, dedupe ids, gather vectors via MainTable,
    exact-rank, top-k.
    """
    q = qvecs.shape[0]
    _, cand = _hot_sealed_candidates(state, qvecs, cfg)
    cids = _dedupe_candidates(cand, state.tombstones, cfg)
    slot, found = _main_lookup(state, cids.reshape(-1), cfg)
    slot, found = slot.reshape(q, -1), found.reshape(q, -1)
    return _rank_candidates(state, qvecs, cids, slot, found, cfg, k)


# ======================================================================
# cold-tier variants (cfg.cold_enabled): same pipelines plus the cold
# Bloom route / cache probe and the wanted/missing fetch protocol
# ======================================================================
def _staging_arena(state: PFOState, cfg: PFOConfig) -> jax.Array | None:
    """The cold MainTable cache's payload pages flattened to one
    (cold_cache_slots * seg_cap, d) device arena; staging slot
    ``store_capacity + e*seg_cap + r`` addresses row r of cache entry
    e.  None when the cache carries no payloads (pre-tiered state)."""
    vecs = state.cold.main_cache.vecs
    if vecs is None:
        return None
    return vecs.reshape(-1, vecs.shape[-1])


@jax.named_scope("main_lookup")
def _main_lookup_cold(state: PFOState, ids: jax.Array, cfg: PFOConfig,
                      active: jax.Array | None = None):
    """(N,) id -> (slot, found, unresolved, wanted, missing, probed, fp).

    Hot forest, then the device ring, then the cold cache — structural
    newest-first precedence (every ring segment is younger than every
    cold segment; spill always takes the oldest).  Rows already
    resolved by a hotter tier are masked out of the cold route, so a
    stale cold copy of a live id never triggers a fetch.
    ``unresolved`` marks rows whose Bloom route hit a non-resident
    cold segment: the caller must fetch (``missing``) and retry them.
    """
    mh, mtree = main_table_keys(ids, cfg)
    val, found = forest_lookup(state.main_forest, mtree, mh, ids,
                               main_tree_config(cfg))
    sval, sfound = snap_mod.lookup_exact(state.main_snaps, mh, ids)
    cold_ids = jnp.where(found | sfound, -1, ids)
    if active is not None:
        cold_ids = jnp.where(active, cold_ids, -1)
    cval, cfound, row_missing, wanted, missing, probed, fp = \
        coldtier.cold_lookup_main(state.cold, mh, cold_ids,
                                  _snap_cfg_main(cfg))
    # a non-resident matched segment may hold a NEWER copy of the id
    # than any resident one — never resolve a row through the cold
    # cache while part of its route is missing (a stale val could,
    # e.g., free a store slot that was reused by another id); the row
    # stays unresolved and retries after the fetch
    cfound = cfound & ~row_missing
    slot = jnp.where(found, val,
                     jnp.where(sfound, sval, jnp.where(cfound, cval, -1)))
    found_any = found | sfound | cfound
    unresolved = ~found_any & row_missing
    return slot, found_any, unresolved, wanted, missing, probed, fp


@functools.partial(jax.jit, static_argnames=("cfg", "k"))
def query_step_cold(state: PFOState, qvecs: jax.Array, cfg: PFOConfig,
                    k: int):
    """Batched kNN query over hot + ring + cold tiers.

    Identical to :func:`query_step` plus the cold Bloom route: cold
    candidates come from whatever matched segments are resident in the
    device cache, and the (wanted, missing) masks for both tiers ride
    back with the results in the round's single pickup — the host
    fetches missing segments and re-probes only on a miss.  Candidates
    that resolve to a *staging* slot (a spilled store row cached in the
    cold payload arena) rank straight out of that arena — the spilled
    vector never re-enters the dense store.
    Returns (ids, dists, wanted_l, missing_l, wanted_m, missing_m,
    info) with info the (10,) cold accounting vector.
    """
    q = qvecs.shape[0]
    h, cand = _hot_sealed_candidates(state, qvecs, cfg)
    ccand, wanted_l, missing_l, lsh_probed, lsh_fp = \
        coldtier.cold_probe_lsh(state.cold, h, _snap_cfg_lsh(cfg))
    cids = _dedupe_candidates(jnp.concatenate([cand, ccand], axis=1),
                              state.tombstones, cfg)

    slot, found, _, wanted_m, missing_m, m_probed, m_fp = \
        _main_lookup_cold(state, cids.reshape(-1), cfg)
    slot, found = slot.reshape(q, -1), found.reshape(q, -1)
    staging = _staging_arena(state, cfg)
    top_ids, top_d = _rank_candidates(state, qvecs, cids, slot, found,
                                      cfg, k, staging=staging)
    valid = (cids >= 0) & found & (slot >= 0)
    staged_ranked = jnp.sum(
        (valid & (slot >= cfg.store_capacity)).astype(jnp.int32))
    ranked_total = jnp.sum(valid.astype(jnp.int32))
    info = coldtier.pack_cold_info(wanted_l, missing_l, lsh_probed,
                                   lsh_fp, wanted_m, missing_m,
                                   m_probed, m_fp, staged_ranked,
                                   ranked_total)
    return top_ids, top_d, wanted_l, missing_l, wanted_m, missing_m, info


def _delete_apply(state: PFOState, ids: jax.Array, slot: jax.Array,
                  ok: jax.Array, cfg: PFOConfig, main_capacity: int,
                  lsh_capacity: int, staging: jax.Array | None = None):
    """The delete pipeline after the lookup, shared by both delete
    steps: unlink hot entries, free store slots, append tombstones.
    Returns (state, pending) where pending covers mailbox and
    tombstone-buffer overflow rows.

    ``staging`` enables the tiered path: a row resolved to a staging
    slot re-derives its LSH keys from the cold payload arena, and its
    store slot is NOT freed (the spill already freed it — freeing the
    out-of-range encoded slot would push garbage on the free stack).
    """
    with jax.named_scope("lsh_unlink"):
        # re-derive LSH keys from the stored vector
        vecs = dense_read_tiered(state.store, staging,
                                 jnp.where(ok, slot, 0))
        h, gtrees = compute_keys(state, vecs, cfg)
        flat_tree = jnp.where(jnp.repeat(ok, cfg.L), gtrees.reshape(-1),
                              -1)
        flat_id = jnp.repeat(ids, cfg.L)
        lbox, l_ovf = dispatch_to_trees(flat_tree, cfg.L * cfg.n_trees,
                                        lsh_capacity)
        (lh_g,) = gather_mailbox(lbox, h.reshape(-1))
        lid_g = mailbox_ids(lbox, flat_id)
        lsh_forest = forest_delete_dispatched(state.lsh_forest, lh_g,
                                              lid_g, lsh_tree_config(cfg))

    with jax.named_scope("main_unlink"):
        mh, mtree = main_table_keys(ids, cfg)
        mbox, m_ovf = dispatch_to_trees(jnp.where(ok, mtree, -1),
                                        cfg.main_n_trees, main_capacity)
        (mh_g,) = gather_mailbox(mbox, mh)
        mid_g = mailbox_ids(mbox, ids)
        main_forest = forest_delete_dispatched(state.main_forest, mh_g,
                                               mid_g, main_tree_config(cfg))

    with jax.named_scope("store_free"):
        if staging is None:
            store = dense_free(state.store, slot, ok)
        else:
            hot_ok = ok & (slot < cfg.store_capacity)
            store = dense_free(state.store, jnp.where(hot_ok, slot, 0),
                               hot_ok)

    # tombstones cover sealed copies; overflow rows stay pending.
    # Overflow writes park out of bounds (dropped by XLA) — clamping
    # them to the last slot would clobber the tombstone legitimately
    # written there in the same scatter.
    with jax.named_scope("tombstone"):
        want = ok.astype(jnp.int32)
        rank = jnp.cumsum(want) - want
        pos = state.n_tombstones + rank
        fits = ok & (pos < cfg.max_tombstones)
        safe = jnp.where(fits, pos, cfg.max_tombstones)
        tombs = state.tombstones.at[safe].set(ids, mode="drop")
        n_t = jnp.minimum(
            state.n_tombstones + jnp.sum(fits.astype(jnp.int32)),
            cfg.max_tombstones)

    state = state._replace(lsh_forest=lsh_forest, main_forest=main_forest,
                           store=store, tombstones=tombs, n_tombstones=n_t)
    l_row = jnp.any(l_ovf.reshape(-1, cfg.L), axis=1)
    tomb_ovf = ok & ~fits
    return state, (ok & (l_row | m_ovf)) | tomb_ovf


@functools.partial(jax.jit,
                   static_argnames=("cfg", "main_capacity", "lsh_capacity",
                                    "flags_main_capacity",
                                    "flags_lsh_capacity"))
def delete_step(state: PFOState, ids: jax.Array, active: jax.Array,
                cfg: PFOConfig, main_capacity: int, lsh_capacity: int,
                flags_main_capacity: int | None = None,
                flags_lsh_capacity: int | None = None):
    """Batched delete: unlink hot entries, free store slots, tombstone
    sealed copies.  Idempotent per round, so per-row retry is safe.
    Returns (state, pending, flags).

    Tombstone-buffer overflow marks the row *pending* (it is NOT safe to
    drop: a sealed copy could resurface on query).  The host sees
    TOMBS_FULL in ``flags``, merges — which drains the buffer and
    physically drops tombstoned sealed entries — and retries the row;
    the retry re-finds any surviving sealed copy via the MainTable
    sealed tier and tombstones it then.  Rows whose hot/store cleanup
    already ran are no-ops on retry (unlink misses, dense_free checks
    ``live``)."""
    slot, found = _main_lookup(state, ids, cfg)
    ok = active & found & (slot >= 0)
    state, pending = _delete_apply(state, ids, slot, ok, cfg,
                                   main_capacity, lsh_capacity)
    flags = _round_flags(state, cfg,
                         flags_main_capacity or main_capacity,
                         flags_lsh_capacity or lsh_capacity,
                         jnp.any(pending))
    return state, pending, flags


@functools.partial(jax.jit,
                   static_argnames=("cfg", "main_capacity", "lsh_capacity",
                                    "flags_main_capacity",
                                    "flags_lsh_capacity"))
def delete_step_cold(state: PFOState, ids: jax.Array, active: jax.Array,
                     cfg: PFOConfig, main_capacity: int, lsh_capacity: int,
                     flags_main_capacity: int | None = None,
                     flags_lsh_capacity: int | None = None):
    """Cold-tier batched delete: :func:`delete_step` with the MainTable
    lookup extended through the cold cache.

    A row whose id resolves only through a *non-resident* cold segment
    cannot complete this round: it stays pending, the packed flag word
    carries COLD_MISS, and the host fetches the (returned) missing
    segments before the retry round — the steady-state case (no cold
    hit) still reads back exactly the one flag word.
    Returns (state, pending, flags, wanted_m, missing_m).
    """
    slot, found, unresolved, wanted_m, missing_m, _, _ = \
        _main_lookup_cold(state, ids, cfg, active=active)
    ok = active & found & (slot >= 0)
    state, pending = _delete_apply(state, ids, slot, ok, cfg,
                                   main_capacity, lsh_capacity,
                                   staging=_staging_arena(state, cfg))
    pending = pending | (active & unresolved)
    flags = _round_flags(state, cfg,
                         flags_main_capacity or main_capacity,
                         flags_lsh_capacity or lsh_capacity,
                         jnp.any(pending), cold_miss=jnp.any(missing_m))
    return state, pending, flags, wanted_m, missing_m


# ======================================================================
# host orchestrator
# ======================================================================
class PFOIndex:
    """Host-side driver: owns the device state, runs dispatch rounds and
    seal/merge epochs (the paper's maintenance routines).

    Steady-state rounds are device-resident: every jitted step returns a
    packed i32 flag word (pending / seal / merge signals — see
    ``dispatch.pack_round_flags``) and the host performs exactly ONE
    explicit scalar readback per round (:meth:`_read_flags`, counted in
    ``sync_count``).  The flag word is carried across calls, so the cold
    ``round_flags`` probe only runs on the first round after init or
    when a call's dispatch capacity grows beyond what the carried word
    was computed for.
    """

    MAX_ROUNDS = 64

    def __init__(self, cfg: PFOConfig, seed: int = 0,
                 cold_dir: str | None = None, obs: Obs | None = None):
        self.cfg = cfg
        self.state = init_state(cfg, jax.random.PRNGKey(seed))
        self.n_inserted = 0
        self.rounds_log: list[int] = []
        self.sync_count = 0          # explicit host<->device scalar syncs
        self.maintenance_log: list[str] = []    # "seal"/"merge"/"spill"...
        self._flags: int | None = None
        self._flags_caps = (0, 0)    # (main_cap, lsh_cap) flags were computed for
        # cold tier: host segment store + routing/cache bookkeeping.
        # ``cold_dir`` selects file backing (mmap'd flash segments);
        # None keeps segments in host RAM.
        self.cold: coldtier.ColdManager | None = None
        self._delete_miss = None     # device masks stashed by delete rounds
        if cfg.cold_enabled:
            self.cold = coldtier.ColdManager(
                cfg, _snap_cfg_lsh(cfg), _snap_cfg_main(cfg),
                main_tree_config(cfg), root=cold_dir,
                on_sync=self._count_sync)
        # metrics on / tracing off by default; everything recorded is
        # host-side, so instrumentation never adds a device readback
        self.set_obs(obs if obs is not None else Obs())

    def _count_sync(self) -> None:
        self.sync_count += 1

    # -- observability --------------------------------------------------
    def set_obs(self, obs: Obs) -> None:
        """Bind an observability handle; the readback count mirrors
        into a gauge lazily at snapshot time (``repro.obs``), and the
        cold manager inherits the same handle."""
        self.obs = obs
        obs.on_snapshot("index", self._mirror_obs)
        if self.cold is not None:
            self.cold.set_obs(obs)

    def _mirror_obs(self) -> None:
        self.obs.gauge("index.readbacks").set(self.sync_count)

    def _epoch(self, name: str, fn, *args):
        """Run one maintenance epoch under a span."""
        with self.obs.span(name):
            return fn(*args)

    # -- capacity heuristics -------------------------------------------
    def _lsh_capacity(self, n: int) -> int:
        total = self.cfg.L * self.cfg.n_trees
        per = (n * self.cfg.L + total - 1) // total
        return int(max(8, 2 * per))

    def _main_capacity(self, n: int) -> int:
        per = (n + self.cfg.main_n_trees - 1) // self.cfg.main_n_trees
        return int(max(8, 2 * per))

    # -- device-resident maintenance -----------------------------------
    def _read_flags(self, flags: jax.Array, caps: tuple[int, int]) -> int:
        """THE host<->device sync of a round: one explicit i32 readback."""
        self.sync_count += 1
        f = int(jax.device_get(flags))
        self._flags, self._flags_caps = f, caps
        return f

    def _ensure_flags(self, mcap: int, lcap: int) -> int:
        """Flags valid for a round at (mcap, lcap), reusing the carried
        word when it was computed for capacities at least this large."""
        if (self._flags is not None
                and self._flags_caps[0] >= mcap
                and self._flags_caps[1] >= lcap):
            return self._flags
        return self._read_flags(
            round_flags(self.state, self.cfg, mcap, lcap), (mcap, lcap))

    def _maintain(self, flags: int) -> None:
        """Run the seal/merge/spill epochs the flag word asks for."""
        if self.cold is not None:
            before = self.cold.counters["compactions"]
            self.state = self.cold.compact_maybe_install(self.state)
            if self.cold.counters["compactions"] != before:
                self.maintenance_log.append("cold_compact")
                self._flags = None
        if flags & FLAG_NEED_SEAL:
            if flags & FLAG_COLD_SPILL:
                # capacity relief with a cold tier: spill, never merge
                if self.cold.n_cold >= self.cfg.cold_segments:
                    self.state = self._epoch("cold_compact",
                                             self.cold.compact, self.state)
                    self.maintenance_log.append("cold_compact")
                self.state = self._epoch("spill", self.cold.spill,
                                         self.state)
                self.maintenance_log.append("spill")
            elif flags & FLAG_SNAPS_FULL:
                self.state = self._epoch("merge", merge_step, self.state,
                                         self.cfg)
                self.maintenance_log.append("merge")
            self.state = self._epoch("seal", seal_step, self.state,
                                     self.cfg)
            self.maintenance_log.append("seal")
        elif (flags & FLAG_STORE_FULL) and (flags & FLAG_COLD_SPILL):
            # tiered store pressure without arena pressure: spill the
            # oldest ring segment so its payload rows leave the dense
            # store (slots free at spill) — no seal needed, the hot
            # forest still has headroom
            if self.cold.n_cold >= self.cfg.cold_segments:
                self.state = self._epoch("cold_compact",
                                         self.cold.compact, self.state)
                self.maintenance_log.append("cold_compact")
            self.state = self._epoch("spill", self.cold.spill, self.state)
            self.maintenance_log.append("spill")
        if flags & FLAG_TOMBS_FULL:
            if self.cold is not None:
                self._epoch("merge", self._merge_with_cold)
            else:
                self.state = self._epoch("merge", merge_step, self.state,
                                         self.cfg)
            self.maintenance_log.append("merge")
        if self.cold is not None and flags & FLAG_COLD_FULL:
            self.cold.compact_start_async()
        if flags & (FLAG_NEED_SEAL | FLAG_TOMBS_FULL | FLAG_STORE_FULL):
            self._flags = None       # state changed; carried word is stale

    def _merge_with_cold(self) -> None:
        """Cold-enabled merge epoch: the tombstones drain into a host
        fold over ring + cold segments (dead ids physically dropped from
        every sealed copy), the ring resets, and the device buffer
        clears in the same epoch."""
        self._count_sync()
        tombs = jax.device_get(self.state.tombstones)
        self.state = self.cold.merge_cold(self.state, tombs)
        self.state = self.state._replace(
            tombstones=jnp.full_like(self.state.tombstones, -1),
            n_tombstones=jnp.int32(0))

    # -- public API ----------------------------------------------------
    def insert(self, ids, vecs) -> int:
        """Insert a batch; returns the number of dispatch rounds used."""
        ids = jnp.asarray(ids, jnp.int32)
        vecs = jnp.asarray(vecs, jnp.float32)
        n = int(ids.shape[0])
        slots = jnp.full((n,), -2, jnp.int32)
        main_active = jnp.ones((n,), bool)
        lsh_active = jnp.ones((n * self.cfg.L,), bool)
        lcap, mcap = self._lsh_capacity(n), self._main_capacity(n)
        with self.obs.span("insert", n=n):
            flags = self._ensure_flags(mcap, lcap)
            rounds = 0
            for _ in range(self.MAX_ROUNDS):
                self._maintain(flags)
                self.state, slots, main_active, lsh_active, fw = insert_step(
                    self.state, ids, vecs, slots, main_active, lsh_active,
                    self.cfg, mcap, lcap)
                rounds += 1
                flags = self._read_flags(fw, (mcap, lcap))
                if not flags & FLAG_ANY_PENDING:
                    break
        self.n_inserted += n
        self.rounds_log.append(rounds)
        return rounds

    def query(self, qvecs, k: int = 10):
        qvecs = jnp.asarray(qvecs, jnp.float32)
        with self.obs.span("query", n=int(qvecs.shape[0]), k=k):
            if self.cold is None:
                ids, dists = query_step(self.state, qvecs, self.cfg, k)
                ids, dists = jax.device_get((ids, dists))
            else:
                ids, dists = self._query_cold(qvecs, k)
        return np.asarray(ids), np.asarray(dists)

    def _query_cold(self, qvecs, k: int, overlap=None):
        """Cold-tier query loop: probe; on a cold-cache miss fetch the
        Bloom-matched segments (transfers issued together, overlapping
        the next probe's hot-tier work) and re-probe.  A round that
        hits no non-resident cold segment does exactly ONE device->host
        pickup — results and masks travel together.  ``overlap`` (the
        stream engine's double-buffer hook) fires right after the first
        dispatch, before its blocking pickup, so host batch packing
        still hides under device execution.  Returns host
        (ids, dists)."""
        for attempt in range(self.cfg.cold_fetch_rounds + 1):
            out = query_step_cold(self.state, qvecs, self.cfg, k)
            if attempt == 0 and overlap is not None:
                overlap()            # first dispatch is in flight
            ids, dists, wl, ml, wm, mm, info = jax.device_get(out)
            self.cold.record_query_round(info)
            if not (ml.any() or mm.any()):
                break
            if attempt == self.cfg.cold_fetch_rounds:
                # fetch budget exhausted with matches still missing:
                # results lack those segments' candidates — counted, so
                # capacity tests/dashboards can assert it never happens
                self.cold.counters["incomplete_query_rounds"] += 1
                break
            before = self.cold.counters["fetches"]
            with self.obs.span("cold_fetch", attempt=attempt):
                self.state = self.cold.fetch(self.state, wl, ml, wm, mm)
            if self.cold.counters["fetches"] == before:
                # every cache slot is wanted by this round: the missing
                # set can never drain (cache undersized for the query
                # batch's Bloom fan-out) — degrade observably
                self.cold.counters["incomplete_query_rounds"] += 1
                break
        return ids, dists

    def delete(self, ids) -> int:
        ids = jnp.asarray(ids, jnp.int32)
        active = jnp.ones(ids.shape, bool)
        n = int(ids.shape[0])
        lcap, mcap = self._lsh_capacity(n), self._main_capacity(n)
        with self.obs.span("delete", n=n):
            flags = self._ensure_flags(mcap, lcap)
            rounds = 0
            for _ in range(self.MAX_ROUNDS):
                self._maintain(flags)
                if self.cold is None:
                    self.state, pending, fw = delete_step(
                        self.state, ids, active, self.cfg, mcap, lcap)
                else:
                    self.state, pending, fw, wm, mm = delete_step_cold(
                        self.state, ids, active, self.cfg, mcap, lcap)
                    self._delete_miss = (wm, mm)
                rounds += 1
                flags = self._read_flags(fw, (mcap, lcap))
                self.fetch_delete_miss(flags)
                if not flags & FLAG_ANY_PENDING:
                    break
                active = pending
        return rounds

    def fetch_delete_miss(self, flags: int) -> None:
        """COLD_MISS service: a delete round's MainTable probe matched a
        non-resident cold segment — read the stashed masks (the only
        extra readback, and only on miss rounds) and fetch before the
        retry round.

        A miss round where the cache can install nothing (every slot is
        wanted by this very round) can never make progress — the retry
        would see the identical missing set forever and the delete
        would silently ack with the id still live — so it raises
        instead: the cache is undersized for the workload's per-row
        Bloom fan-out."""
        if self.cold is None or not flags & FLAG_COLD_MISS \
                or self._delete_miss is None:
            return
        self._count_sync()
        wm, mm = jax.device_get(self._delete_miss)
        self._delete_miss = None
        C, L = self.cfg.cold_segments, self.cfg.L
        zeros = np.zeros((L, C), bool)
        before = self.cold.counters["fetches"]
        with self.obs.span("cold_fetch", path="delete"):
            self.state = self.cold.fetch(self.state, zeros, zeros, wm, mm)
        if np.any(mm) and self.cold.counters["fetches"] == before:
            raise RuntimeError(
                f"delete cannot resolve: its Bloom route spans "
                f"{int(np.sum(wm))} cold segments but cold_cache_slots="
                f"{self.cfg.cold_cache_slots} cannot hold them at once; "
                "raise PFOConfig.cold_cache_slots")

    def update(self, ids, vecs) -> None:
        """Online update (paper §5): new version written, old reclaimed."""
        self.delete(ids)
        self.insert(ids, vecs)

    def stats(self) -> dict:
        st = self.state
        out = {
            "items_hot": int(np.asarray(st.main_forest.n_items).sum()),
            "lsh_leaves": int(np.asarray(st.lsh_forest.n_items).sum()),
            "snapshots": int(st.main_snaps.n_snaps),
            "tombstones": int(st.n_tombstones),
            "store_free": int(st.store.free_top),
            "overflow_events": int(np.asarray(st.lsh_forest.overflow).sum()),
            "stamp": int(st.stamp),
        }
        if self.cold is not None:
            out["cold"] = self.cold.stats()
        return out
