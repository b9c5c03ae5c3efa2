"""Distributed PFO — the paper's parallel design on a TPU mesh.

Placement (mesh axes ``(pod, data, model)`` or ``(data, model)``):

* **hash trees** (all L tables) shard over ``model`` — contiguous
  blocks of global tree ids per chip, the actor-pool-per-core of §4.2
  scaled to chips;
* the **MainTable** (id -> slot, vectors) shards over ``model`` by
  murmur owner — every id has exactly one home chip (single-copy
  invariant of §3.1);
* **queries** shard over ``(pod, data)`` — the online read stream —
  while the state is replicated over the batch axes, so **updates**
  enter replicated over ``(pod, data)`` and every data shard applies
  the identical round (state replicas can never diverge).

Query protocol (collectives over ``model`` only):
  1. each chip hashes its contiguous block of query rows once; the
     full key table reassembles with one integer ``all_gather``;
  2. (row, table) probe requests route by one ``all_to_all`` to the
     tree-owner chip, which descends only the trees it owns and probes
     its local sealed snapshots and cold routing table (ownership ==
     the actor single-writer guarantee);
  3. candidate ids route by one ``all_to_all`` to their murmur owner,
     which looks up the vector (hot store or cold staging arena) and
     exact-ranks against the query;
  4. (id, dist) partials ``all_gather`` over ``model``; every chip
     keeps the deduped global top-k.

Update protocol (the stream-round steps): senders partition the batch
rows into contiguous per-chip blocks (so the per-tree apply order is
exactly the batch order — the property the differential stream tests
assert), route (h, id) to tree-owner chips and (id, vec) to murmur
owners with one ``all_to_all`` each, and receivers re-dispatch into
per-tree mailboxes at single-chip capacity.  Overflow at either hop is
*acked back* to the sending chip (one reverse ``all_to_all`` of bools)
and re-submitted by the host next round — the same bounded-inbox retry
protocol as the single-chip path, with zero extra readbacks: every
round step returns ONE packed i32 flag word
(``core.dispatch.pack_round_flags``) whose headroom terms are combined
across chips with ``pmax`` on device.  Seal and merge run as
shard-local epochs (each chip seals its own tree block into its own
snapshot segment set), so cross-chip synchronization stays
*structurally* absent: every tree and every id has one writer per
round.

The same routing substrate carries MoE expert dispatch in
``repro.models.moe`` — see DESIGN.md §3.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import coldtier
from . import snapshots as snap_mod
from .config import PFOConfig
from .dispatch import dispatch_to_trees, gather_mailbox, mailbox_ids, \
    pack_round_flags
from .hash_tree import (forest_delete_dispatched, forest_headroom,
                        forest_insert_dispatched, forest_lookup,
                        forest_query, init_forest)
from .index import (PFOState, _cold_full_threshold, _tombs_threshold,
                    lsh_tree_config, main_tree_config)
from .lsh import main_table_keys, make_projections, region_ids
from .membership import member_sorted
from .store import (dense_alloc, dense_free, dense_init, dense_read,
                    dense_read_tiered)
from repro import compat
from repro.kernels import ops as kops

INT_MAX = jnp.int32(2**31 - 1)


class DistConfig(NamedTuple):
    pfo: PFOConfig
    model_axis: str = "model"
    batch_axes: tuple = ("data",)      # ("pod", "data") on multi-pod
    n_model: int = 16

    @property
    def trees_per_shard(self) -> int:
        total = self.pfo.L * self.pfo.n_trees
        assert total % self.n_model == 0
        return total // self.n_model

    @property
    def main_trees_per_shard(self) -> int:
        assert self.pfo.main_n_trees % self.n_model == 0
        return self.pfo.main_n_trees // self.n_model


def shard_snap_cfg(dcfg: DistConfig) -> PFOConfig:
    cap = dcfg.trees_per_shard * dcfg.pfo.max_leaves_per_tree
    return PFOConfig(**{**dcfg.pfo.__dict__, "snapshot_capacity": cap})


def shard_main_snap_cfg(dcfg: DistConfig) -> PFOConfig:
    cap = dcfg.main_trees_per_shard * dcfg.pfo.main_max_leaves_per_tree
    # store_capacity shrinks to the shard's dense-store rows so the
    # cold staging-slot encoding (store_capacity + arena row) starts
    # exactly at the per-shard tiered-read boundary
    return PFOConfig(**{**dcfg.pfo.__dict__, "snapshot_capacity": cap,
                        "store_capacity":
                            dcfg.pfo.store_capacity // dcfg.n_model,
                        "store_low_watermark": 0})


def shard_cold_cfg(dcfg: DistConfig) -> PFOConfig:
    """Per-shard cold-tier driver config: a shard's cold chain is one
    *mixed-table* segment sequence (it mirrors the shard's mixed sealed
    ring, table id in ``vals``), so the shared coldtier machinery runs
    with ``L == 1``."""
    return PFOConfig(**{**dcfg.pfo.__dict__, "L": 1})


def _dist_cold_init(dcfg: DistConfig):
    """Stacked (n_model, ...) empty per-shard cold states, or None."""
    cfg = dcfg.pfo
    if not cfg.cold_enabled:
        return None
    # the tiered-store low watermark needs per-shard free-list flag
    # plumbing that does not exist yet; refuse rather than mis-spill
    assert cfg.store_low_watermark == 0, \
        "store_low_watermark is not supported on the distributed backend"
    ccfg = shard_cold_cfg(dcfg)
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    return jax.vmap(lambda _: coldtier.init_cold(ccfg, snap_cfg,
                                                 msnap_cfg))(
        jnp.arange(dcfg.n_model))


def _abstract_state(dcfg: DistConfig) -> PFOState:
    """Shape skeleton of the distributed state (no allocation)."""
    cfg = dcfg.pfo
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    return jax.eval_shape(
        lambda k: PFOState(
            lsh_forest=init_forest(lsh_tree_config(cfg),
                                   cfg.L * cfg.n_trees),
            main_forest=init_forest(main_tree_config(cfg), cfg.main_n_trees),
            store=jax.vmap(
                lambda _: dense_init(cfg.store_capacity // dcfg.n_model,
                                     cfg.dim))(jnp.arange(dcfg.n_model)),
            lsh_snaps=jax.vmap(
                lambda _: snap_mod.init_snapshots(snap_cfg))(
                jnp.arange(dcfg.n_model)),
            main_snaps=jax.vmap(
                lambda _: snap_mod.init_snapshots(msnap_cfg))(
                jnp.arange(dcfg.n_model)),
            tombstones=jnp.full((cfg.max_tombstones,), -1, jnp.int32),
            n_tombstones=jnp.int32(0),
            stamp=jnp.int32(0),
            proj=make_projections(k, cfg),
            cold=_dist_cold_init(dcfg),
        ), jax.random.PRNGKey(0))


def state_pspecs(dcfg: DistConfig) -> PFOState:
    mdl = dcfg.model_axis
    ex = _abstract_state(dcfg)

    def s0(_):
        return P(mdl)

    return PFOState(
        lsh_forest=jax.tree.map(s0, ex.lsh_forest),
        main_forest=jax.tree.map(s0, ex.main_forest),
        store=jax.tree.map(s0, ex.store),
        lsh_snaps=jax.tree.map(s0, ex.lsh_snaps),
        main_snaps=jax.tree.map(s0, ex.main_snaps),
        tombstones=P(), n_tombstones=P(), stamp=P(),
        proj=jax.tree.map(lambda _: P(), ex.proj),
        cold=jax.tree.map(s0, ex.cold),
    )


def dist_init_state(dcfg: DistConfig, key: jax.Array, mesh: Mesh) -> PFOState:
    """Materialize the distributed state with its NamedShardings."""
    cfg = dcfg.pfo
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    st = PFOState(
        lsh_forest=init_forest(lsh_tree_config(cfg), cfg.L * cfg.n_trees),
        main_forest=init_forest(main_tree_config(cfg), cfg.main_n_trees),
        store=jax.vmap(
            lambda _: dense_init(cfg.store_capacity // dcfg.n_model,
                                 cfg.dim))(jnp.arange(dcfg.n_model)),
        lsh_snaps=jax.vmap(lambda _: snap_mod.init_snapshots(snap_cfg))(
            jnp.arange(dcfg.n_model)),
        main_snaps=jax.vmap(lambda _: snap_mod.init_snapshots(msnap_cfg))(
            jnp.arange(dcfg.n_model)),
        tombstones=jnp.full((cfg.max_tombstones,), -1, jnp.int32),
        n_tombstones=jnp.int32(0),
        stamp=jnp.int32(0),
        proj=make_projections(key, cfg),
        cold=_dist_cold_init(dcfg),
    )
    specs = state_pspecs(dcfg)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), st, specs)


def _batch_spec(dcfg: DistConfig) -> P:
    axes = dcfg.batch_axes
    return P(axes if len(axes) > 1 else axes[0])


def _dedup_topk(pid: jax.Array, pd: jax.Array, k: int, copies: int):
    """Top-k by distance with id dedupe (flat (N,) id/dist arrays), where
    an id occurs at most ``copies`` times (once per shard that found
    it): the ``copies * k`` nearest entries hold k distinct ids."""
    neg, idx = jax.lax.top_k(-pd, min(copies * k, pd.shape[0]))
    ii = pid[idx]
    same = ii[:, None] == ii[None, :]
    dup = jnp.tril(same, -1).any(axis=1) & (ii >= 0)
    dd = jnp.where(dup, jnp.inf, -neg)
    neg2, idx2 = jax.lax.top_k(-dd, k)
    out_ids = jnp.where(jnp.isfinite(-neg2), ii[idx2], -1)
    return out_ids, -neg2


# ======================================================================
# routing primitives (inside shard_map, over the model axis)
# ======================================================================
def _psum_bool(x: jax.Array, axis: str) -> jax.Array:
    """OR-combine per-shard boolean contributions (disjoint owners)."""
    return jax.lax.psum(x.astype(jnp.int32), axis) > 0


def _block_mine(n: int, n_shards: int, me: jax.Array) -> jax.Array:
    """Contiguous-block row partition: rows [me*per, (me+1)*per).

    Block (not strided) so the receive-side apply order — sender-major,
    then slot order — equals global batch order: stable per-tree
    semantics match the single-chip dispatch exactly.
    """
    per = -(-n // n_shards)
    return (jnp.arange(n, dtype=jnp.int32) // per) == me


def _route_acked(payload: jax.Array, dest: jax.Array, n_shards: int,
                 capacity: int, axis: str, marker_col: int = 0):
    """Route payload rows to destination shards with a reverse-ack
    channel, ONE ``all_to_all`` each way.

    dest: (N,) i32 destination shard, -1 inactive.  The payload's
    ``marker_col`` must be an id-like column: it is rewritten to -1 in
    empty mailbox slots before the exchange, so receivers identify
    padding from the payload itself — no separate validity collective.
    Returns (recv (S*K, C) sender-major, send_ovf, ack) where
    ``ack(fail)`` maps a receiver-side (S*K,) failure mask back onto
    the sender's (N,) rows with one reverse ``all_to_all`` — two-hop
    overflow surfaces as ordinary send-side pending instead of
    silently dropping routed requests.
    """
    mbox, send_ovf = dispatch_to_trees(dest, n_shards, capacity)
    (buf,) = gather_mailbox(mbox, payload)
    mark = jnp.where(mbox >= 0, buf[..., marker_col],
                     jnp.asarray(-1, buf.dtype))
    buf = buf.at[..., marker_col].set(mark)
    recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=True).reshape(n_shards * capacity,
                                                  payload.shape[1])

    n = dest.shape[0]

    def ack(fail: jax.Array) -> jax.Array:
        back = jax.lax.all_to_all(fail.reshape(n_shards, capacity), axis,
                                  split_axis=0, concat_axis=0, tiled=True)
        flat = mbox.reshape(-1)
        safe = jnp.where(flat >= 0, flat, n)
        return jnp.zeros((n,), bool).at[safe].set(
            jnp.where(flat >= 0, back.reshape(-1), False), mode="drop")

    return recv, send_ovf, ack


def _dist_round_flags(state: PFOState, dcfg: DistConfig, fm: int, fl: int,
                      any_pending: jax.Array, mdl: str,
                      cold_miss: jax.Array | None = None) -> jax.Array:
    """Packed maintenance word over the shard-local state (inside
    shard_map): worst-tree headroom combines with ``pmax`` so the word
    is replicated and the host reads ONE scalar — and the thresholds
    mirror ``index._round_flags`` exactly, so a distributed engine
    seals/merges at the same rounds as a single-chip one fed the same
    trace (the differential tests rely on this).  With a cold tier the
    per-shard ring/routing occupancy folds into the same word
    (``pmax``-combined COLD_SPILL / COLD_FULL / COLD_MISS bits), so
    steady-state rounds still read back exactly one scalar.
    """
    cfg = dcfg.pfo
    leaf_head, node_head = forest_headroom(state.lsh_forest)
    mleaf, mnode = forest_headroom(state.main_forest)
    leaf_head = jax.lax.pmax(leaf_head, mdl)
    node_head = jax.lax.pmax(node_head, mdl)
    mleaf = jax.lax.pmax(mleaf, mdl)
    mnode = jax.lax.pmax(mnode, mdl)
    need_seal = (
        (leaf_head + fl > cfg.max_leaves_per_tree)
        | (node_head + fl > cfg.max_nodes_per_tree)
        | (mleaf + fm > cfg.main_max_leaves_per_tree)
        | (mnode + fm > cfg.main_max_nodes_per_tree)
        | (leaf_head >= jnp.int32(
            int(cfg.seal_threshold * cfg.max_leaves_per_tree))))
    snaps_full = jax.lax.pmax(state.lsh_snaps.n_snaps[0], mdl) \
        >= cfg.max_snapshots - 1
    tombs_full = state.n_tombstones >= _tombs_threshold(cfg)
    if cfg.cold_enabled:
        # capacity relief is a spill, never a merge — SNAPS_FULL stays
        # 0 and the full ring arms COLD_SPILL instead; every shard
        # spills in the same epoch (lockstep rings, pmax-combined bit)
        cold_full = jax.lax.pmax(state.cold.n_cold[0], mdl) \
            >= _cold_full_threshold(cfg)
        return pack_round_flags(
            jnp.asarray(any_pending), need_seal, jnp.bool_(False),
            tombs_full, cold_spill=snaps_full, cold_full=cold_full,
            cold_miss=cold_miss)
    return pack_round_flags(jnp.asarray(any_pending), need_seal,
                            snaps_full, tombs_full)


# ======================================================================
# query
# ======================================================================
def make_dist_query(dcfg: DistConfig, mesh: Mesh, k: int,
                    with_drop_count: bool = False):
    """Jitted distributed query: (Q_global, d) -> ids/dists (Q_global, k).

    Each chip hashes only its contiguous block of query rows (the full
    key table reassembles with one integer ``all_gather`` — bit-exact —
    for the sealed-segment probe), then (row, table) probe requests
    route to the tree-owner shard with the same ``all_to_all`` + ack
    machinery as the write paths: every chip descends only the trees it
    owns, so per-chip probe work drops ~``n_model``-fold instead of
    being replicated.  Candidates route to their murmur owner for the
    vector lookup + exact rank, and the (id, dist) partials
    ``all_gather`` so each chip keeps the deduped global top-k.
    Tombstoned ids are filtered exactly like the single-chip read path
    (sealed copies of deleted ids must not resurface).

    ``with_drop_count`` adds a third output: a replicated i32 scalar
    counting candidates dropped by owner-mailbox skew overflow (queries
    have no retry round) — the stream backend accumulates it on device
    and surfaces it through ``stats()``.

    With a cold tier (``cfg.cold_enabled``) each shard also probes its
    *own* mixed-table cold routing table/cache against the full key
    table (shard-local Bloom route — no cross-shard traffic), murmur
    owners extend the exact lookup through their cold MainTable cache,
    and candidates resolved to a staging slot rank straight out of the
    shard's staging arena.  Four per-shard (1, C) wanted/missing masks
    and the psum'd (10,) cold-info vector append to the outputs, riding
    the round's single result pickup exactly like the single-chip path.
    """
    cfg = dcfg.pfo
    mdl = dcfg.model_axis
    tcfg = lsh_tree_config(cfg)
    mcfg = main_tree_config(cfg)
    tps = dcfg.trees_per_shard
    mtps = dcfg.main_trees_per_shard
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    S = dcfg.n_model

    def local_fn(state: PFOState, qvecs: jax.Array):
        me = jax.lax.axis_index(mdl)
        ql = qvecs.shape[0]
        # --- hash once: each chip hashes only its block of rows -------
        # The full (ql, L) key table reassembles with one integer
        # all_gather (bit-exact transport) for the sealed probe below.
        per = -(-ql // S)
        qpad = jnp.pad(qvecs, ((0, S * per - ql), (0, 0)))
        qblk = jax.lax.dynamic_slice_in_dim(qpad, me * per, per, axis=0)
        hb = kops.lsh_hash(qblk, state.proj["table_proj"], cfg.M)  # (per, L)
        regb = region_ids(hb, state.proj["part_proj"], cfg)
        off = jnp.arange(cfg.L, dtype=jnp.int32)[None] * cfg.n_trees
        h = jax.lax.all_gather(hb, mdl, tiled=True)[:ql]           # (ql, L)

        # --- route (row, table) probes to the tree-owner shard -------
        # Every row has exactly one owner per table, so the global
        # probe multiset a chip receives equals the rows the old
        # replicated descent kept under its ownership mask — routing
        # changes who computes, not what is computed.
        gtb = (regb + off).reshape(-1)
        rowb = me * per + jnp.arange(per, dtype=jnp.int32)
        qrow = jnp.repeat(rowb, cfg.L)
        psend = jnp.repeat(rowb < ql, cfg.L)
        pdest = jnp.where(psend, gtb // tps, -1)
        ppay = jnp.stack([hb.reshape(-1).astype(jnp.int32), qrow,
                          gtb % tps], axis=1)
        # per-owner capacity: 2x the even spread + per-table slack,
        # capped at the sender total (skew beyond it DROPS probes —
        # counted below, asserted zero by the differential tests)
        Kp = min(per * cfg.L, 2 * ((per * cfg.L) // S) + 2 * cfg.L)
        precv, p_ovf, _ = _route_acked(ppay, pdest, S, Kp, mdl,
                                       marker_col=1)
        rq_p = precv[:, 1]
        pvalid = rq_p >= 0
        rh_p = precv[:, 0].astype(jnp.uint32)
        rt_p = jnp.where(pvalid, precv[:, 2], 0)
        ids_p, _, _ = forest_query(state.lsh_forest, rt_p, rh_p, tcfg)

        # regroup the descents by query row (capacity L is exact: a row
        # sends one probe per table, so this hop can never overflow)
        rbox_p, _ = dispatch_to_trees(jnp.where(pvalid, rq_p, -1), ql,
                                      cfg.L)
        (hot_g,) = gather_mailbox(rbox_p,
                                  jnp.where(pvalid[:, None], ids_p, -1))
        hot = jnp.where((rbox_p >= 0)[:, :, None], hot_g,
                        -1).reshape(ql, -1)

        # --- probe local sealed segments ---------------------------
        # a chip's segments mix entries from every LSH table (one set
        # per chip, not per table); the seal stores the table id in
        # ``vals`` so cross-table bucket-prefix collisions filter out —
        # the candidate set stays identical to the single-chip tier
        snaps = jax.tree.map(lambda a: a[0], state.lsh_snaps)
        scands = []
        for tl in range(cfg.L):
            s, sv = snap_mod.probe(snaps, h[:, tl], snap_cfg)
            scands.append(jnp.where(sv == tl, s, -1))
        sealed = jnp.concatenate(scands, axis=1)
        cand = jnp.concatenate([hot, sealed], axis=1)

        # --- probe the shard's cold routing table / segment cache ----
        # (same mixed-table layout as the ring: one chain per shard,
        # table id in vals — the Bloom route stays shard-local)
        if cfg.cold_enabled:
            cold_l = jax.tree.map(lambda a: a[0], state.cold)
            ccand, wl, ml, lsh_probed, lsh_fp = \
                coldtier.cold_probe_lsh_mixed(cold_l, h, snap_cfg)
            cand = jnp.concatenate([cand, ccand], axis=1)

        # --- tombstone filter, dedupe, truncate to per-shard budget --
        dead = member_sorted(cand, state.tombstones) & (cand >= 0)
        skey = jnp.where((cand >= 0) & ~dead, cand, INT_MAX)
        skey = jnp.sort(skey, axis=1)
        dup = jnp.concatenate([jnp.zeros((ql, 1), bool),
                               skey[:, 1:] == skey[:, :-1]], axis=1)
        uniq = jnp.sort(jnp.where(dup, INT_MAX, skey), axis=1)
        budget = min(max(cfg.max_candidates_total // S, k), uniq.shape[1])
        cids = jnp.where(uniq[:, :budget] == INT_MAX, -1, uniq[:, :budget])

        # --- route candidates to murmur owners ----------------------
        flat_c = cids.reshape(-1)
        _, mtree = main_table_keys(flat_c, cfg)
        owner = jnp.where(flat_c >= 0, mtree // mtps, -1)
        qidx = jnp.repeat(jnp.arange(ql, dtype=jnp.int32), budget)
        payload = jnp.stack([flat_c, qidx], axis=1)
        # per-owner send capacity: 2x the even spread + slack.  A query
        # has no retry round, so skew beyond this DROPS candidates —
        # counted into the returned scalar (surfaced via engine stats;
        # the differential tests assert it stays zero) rather than
        # silently degrading recall.
        K = 2 * (flat_c.shape[0] // S) + budget
        recv, send_ovf, _ = _route_acked(payload, owner, S, K, mdl)
        dropped = jax.lax.psum(jnp.sum(send_ovf.astype(jnp.int32))
                               + jnp.sum(p_ovf.astype(jnp.int32)), mdl)
        rid = recv[:, 0]
        rq = jnp.clip(recv[:, 1], 0, ql - 1)

        # --- owner-side lookup + rank --------------------------------
        rh, rtree = main_table_keys(rid, cfg)
        rlocal = jnp.clip(rtree - me * mtps, 0, mtps - 1)
        slot, found = forest_lookup(state.main_forest, rlocal, rh, rid, mcfg)
        msnaps = jax.tree.map(lambda a: a[0], state.main_snaps)
        sval, sfound = snap_mod.lookup_exact(msnaps, rh, rid)
        slot = jnp.where(found, slot, jnp.where(sfound, sval, -1))
        store_l = jax.tree.map(lambda a: a[0], state.store)
        if cfg.cold_enabled:
            # extend the exact lookup through the shard's cold cache
            # (hot forest, then ring, then cold — newest-first);  a
            # staging-slot hit ranks out of the shard's payload arena
            cold_ids = jnp.where(found | sfound, -1, rid)
            cval, cfound, row_missing, wm, mm, m_probed, m_fp = \
                coldtier.cold_lookup_main(cold_l, rh, cold_ids,
                                          msnap_cfg)
            cfound = cfound & ~row_missing
            slot = jnp.where(slot >= 0, slot,
                             jnp.where(cfound, cval, -1))
            ok = (rid >= 0) & (slot >= 0)
            arena = cold_l.main_cache.vecs
            vecs = dense_read_tiered(store_l,
                                     arena.reshape(-1, arena.shape[-1]),
                                     jnp.where(ok, slot, 0))
            staged = jnp.sum(
                (ok & (slot >= msnap_cfg.store_capacity))
                .astype(jnp.int32))
            info = jax.lax.psum(coldtier.pack_cold_info(
                wl, ml, lsh_probed, lsh_fp, wm, mm, m_probed, m_fp,
                staged, jnp.sum(ok.astype(jnp.int32))), mdl)
        else:
            ok = (rid >= 0) & (slot >= 0)
            vecs = dense_read(store_l, jnp.where(ok, slot, 0))
        # exact rank inline: each routed row pairs ONE candidate with
        # its query — the fused rank kernels want wide per-query
        # candidate blocks and pad a C=1 row out to a full block
        # (measured ~1000x slower here); same formula as kernels.ref
        qv = qvecs[rq]
        if cfg.metric == "angular":
            qn = qv / jnp.maximum(
                jnp.linalg.norm(qv, axis=-1, keepdims=True), 1e-9)
            xn = vecs / jnp.maximum(
                jnp.linalg.norm(vecs, axis=-1, keepdims=True), 1e-9)
            d = 1.0 - jnp.sum(qn * xn, axis=-1)
        else:
            d = jnp.maximum(jnp.sum((qv - vecs) ** 2, axis=-1), 0.0)
        d = jnp.where(ok, d, jnp.inf)

        # --- gather partials row-wide, keep the global top-k ---------
        # ids ride the f32 partial rows BITCAST (a value cast rounds
        # ids above 2^24; -1 padding survives the round trip exactly)
        part = jnp.stack([jax.lax.bitcast_convert_type(rid, jnp.float32),
                          rq.astype(jnp.float32), d], axis=1)
        allp = jax.lax.all_gather(part, mdl, tiled=True)
        pid = jax.lax.bitcast_convert_type(allp[:, 0], jnp.int32)
        pq = allp[:, 1].astype(jnp.int32)
        pd = jnp.where(jnp.isfinite(allp[:, 2]) & (pid >= 0),
                       allp[:, 2], jnp.inf)

        # group partials by query row first (dispatch primitive with
        # row == tree): every (row, shard) pair contributes at most
        # ``budget`` partials, so a (ql, S*budget) dense table is exact
        # and the per-row top-k runs over S*budget entries instead of
        # the whole flattened partial set
        rbox, _ = dispatch_to_trees(
            jnp.where(jnp.isfinite(pd), pq, -1), ql, S * budget)
        pid_r = mailbox_ids(rbox, pid)
        (pd_g,) = gather_mailbox(rbox, pd)
        pd_r = jnp.where(rbox >= 0, pd_g, jnp.inf)
        out_ids, out_d = jax.vmap(
            lambda ii, dd: _dedup_topk(ii, dd, k, S))(pid_r, pd_r)
        out = (out_ids, out_d)
        if with_drop_count:
            out = out + (dropped,)
        if cfg.cold_enabled:
            # per-shard (1, C) masks stack to (S, C) host-side — the
            # backend drives each shard's ColdManager fetch from its row
            out = out + (wl[None], ml[None], wm[None], mm[None], info)
        return out

    bspec = _batch_spec(dcfg)
    mdl_p = P(mdl)
    out_specs = (bspec, bspec) + ((P(),) if with_drop_count else ())
    if cfg.cold_enabled:
        out_specs = out_specs + (mdl_p, mdl_p, mdl_p, mdl_p, P())
    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg), bspec),
                          out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


# ======================================================================
# insert (stream round)
# ======================================================================
def make_dist_insert_round(dcfg: DistConfig, mesh: Mesh, *,
                           route_main: int, tree_main: int,
                           route_lsh: int, tree_lsh: int,
                           flags_main: int, flags_lsh: int):
    """Jitted distributed insert round returning the packed flag word.

    fn(state, ids, vecs, main_active, lsh_active) ->
        (state, main_pending, lsh_pending, flags)

    ids/vecs enter replicated over the batch axes (every data shard
    applies the identical round, keeping the state replicas
    consistent); sender-side rows partition into contiguous per-chip
    blocks over ``model``.  ``route_*`` size the per-destination-shard
    send mailboxes, ``tree_*`` the receive-side per-tree mailboxes
    (single-chip capacities — the per-tree scan stays short);
    ``flags_*`` are the capacities the next-round headroom check is
    computed against (the stream engine passes its worst-case bucket).
    Pending tracks main rows and LSH entries separately so retry rounds
    never double-insert what already landed.
    """
    cfg = dcfg.pfo
    mdl = dcfg.model_axis
    tcfg = lsh_tree_config(cfg)
    mcfg = main_tree_config(cfg)
    tps = dcfg.trees_per_shard
    mtps = dcfg.main_trees_per_shard
    S = dcfg.n_model

    def local_fn(state: PFOState, ids: jax.Array, vecs: jax.Array,
                 main_active: jax.Array, lsh_active: jax.Array):
        n = ids.shape[0]
        me = jax.lax.axis_index(mdl)
        mine_row = _block_mine(n, S, me)

        # re-inserting a previously-deleted id revokes its tombstone
        # (computed identically on every shard: batch is replicated)
        revived = member_sorted(state.tombstones,
                                jnp.where(main_active, ids, -1))
        state = state._replace(
            tombstones=jnp.where(revived, -1, state.tombstones))

        h = kops.lsh_hash(vecs, state.proj["table_proj"], cfg.M)
        region = region_ids(h, state.proj["part_proj"], cfg)
        off = jnp.arange(cfg.L, dtype=jnp.int32)[None] * cfg.n_trees
        gtree = region + off

        # --- MainTable rows -> murmur owners --------------------------
        mh, mtree = main_table_keys(ids, cfg)
        msend = main_active & mine_row
        mdest = jnp.where(msend, mtree // mtps, -1)
        # ids ride the f32 vec payload BITCAST, not value-cast: a value
        # cast silently rounds ids above 2^24.  The route's -1 padding
        # marker (f32 -1.0) bitcasts back to a negative i32, so the
        # rids >= 0 validity checks still hold.
        idbits = jax.lax.bitcast_convert_type(ids, jnp.float32)
        mpay = jnp.concatenate([idbits[:, None], vecs], axis=1)
        mrecv, m_send_ovf, mack = _route_acked(mpay, mdest, S, route_main,
                                               mdl)
        rids = jax.lax.bitcast_convert_type(mrecv[:, 0], jnp.int32)
        rvecs = mrecv[:, 1:]
        store_l = jax.tree.map(lambda a: a[0], state.store)
        store_l, slots, alloc_ok = dense_alloc(store_l, rvecs, rids >= 0)
        rh2, rtree2 = main_table_keys(rids, cfg)
        rlocal = jnp.where((rids >= 0) & alloc_ok, rtree2 % mtps, -1)
        mbox_l, m_recv_ovf = dispatch_to_trees(rlocal, mtps, tree_main)
        (mh_g,) = gather_mailbox(mbox_l, rh2)
        mid_g = mailbox_ids(mbox_l, rids)
        (mval_g,) = gather_mailbox(mbox_l, slots)
        main_forest = forest_insert_dispatched(state.main_forest, mh_g,
                                               mid_g, mval_g, mcfg)
        # rows whose local dispatch overflowed never stored a reference
        # to their slot — reclaim it so the retry cannot leak the store
        store_l = dense_free(store_l, slots,
                             (rids >= 0) & alloc_ok & m_recv_ovf)
        store = jax.tree.map(lambda a: a[None, ...], store_l)
        m_fail = mack((rids >= 0) & (~alloc_ok | m_recv_ovf))
        main_pending = _psum_bool(msend & (m_send_ovf | m_fail), mdl)
        main_pending = main_pending & main_active

        # --- LSH entries -> tree owners ------------------------------
        ent_mine = jnp.repeat(mine_row, cfg.L)
        lsend = lsh_active & ent_mine
        gflat = gtree.reshape(-1)
        ldest = jnp.where(lsend, gflat // tps, -1)
        lpay = jnp.stack([h.reshape(-1).astype(jnp.int32),
                          jnp.repeat(ids, cfg.L),
                          gflat % tps], axis=1)
        lrecv, l_send_ovf, lack = _route_acked(lpay, ldest, S, route_lsh,
                                               mdl, marker_col=1)
        rh = lrecv[:, 0].astype(jnp.uint32)
        rid = lrecv[:, 1]
        rlt = lrecv[:, 2]
        lbox, l_recv_ovf = dispatch_to_trees(
            jnp.where(rid >= 0, rlt, -1), tps, tree_lsh)
        (lh_g,) = gather_mailbox(lbox, rh)
        lid_g = mailbox_ids(lbox, rid)
        lsh_forest = forest_insert_dispatched(state.lsh_forest, lh_g,
                                              lid_g, lid_g, tcfg)
        l_fail = lack((rid >= 0) & l_recv_ovf)
        lsh_pending = _psum_bool(lsend & (l_send_ovf | l_fail), mdl)
        lsh_pending = lsh_pending & lsh_active

        state = state._replace(lsh_forest=lsh_forest,
                               main_forest=main_forest, store=store)
        any_pending = jnp.any(main_pending) | jnp.any(lsh_pending)
        flags = _dist_round_flags(state, dcfg, flags_main, flags_lsh,
                                  any_pending, mdl)
        return state, main_pending, lsh_pending, flags

    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg), P(), P(), P(), P()),
                          out_specs=(state_pspecs(dcfg), P(), P(), P()),
                          check_vma=False)
    return jax.jit(fn)


def make_dist_insert(dcfg: DistConfig, mesh: Mesh, capacity: int):
    """Legacy batch-insert entry point: (state, ids, vecs, active) ->
    (state, pending).  A jitted (``.lower()``-able — launch/dryrun
    relies on it) wrapper over the stream round step with every mailbox
    sized to ``capacity``."""
    cfg = dcfg.pfo
    step = make_dist_insert_round(
        dcfg, mesh, route_main=capacity, tree_main=capacity,
        route_lsh=capacity, tree_lsh=capacity,
        flags_main=capacity, flags_lsh=capacity)

    def run(state, ids, vecs, active):
        state, mp, lp, _ = step(state, ids, vecs, active,
                                jnp.repeat(active, cfg.L))
        pending = mp | jnp.any(lp.reshape(-1, cfg.L), axis=1)
        return state, pending

    return jax.jit(run)


# ======================================================================
# delete (stream round)
# ======================================================================
def make_dist_delete_round(dcfg: DistConfig, mesh: Mesh, *,
                           tree_main: int, route_lsh: int, tree_lsh: int,
                           flags_main: int, flags_lsh: int):
    """Jitted distributed delete round returning the packed flag word.

    fn(state, ids, active) -> (state, pending, flags)

    Every murmur owner unlinks the hot MainTable entry for the ids it
    owns, frees the store slot, re-derives the LSH keys from the stored
    vector and routes the (h, id) unlink requests to tree owners.
    Tombstones stay replicated: the global per-row success mask is
    psum-combined so every shard appends the identical id sequence
    (same order, same overflow behaviour as the single-chip
    ``delete_step``, including the retry-after-merge protocol for
    tombstone-buffer overflow).

    With a cold tier the owner's lookup extends through its cold cache
    (fn returns two extra (S, C) wanted/missing mask outputs): a row
    resolving only through a *non-resident* cold segment stays pending,
    the flag word carries the pmax-combined COLD_MISS bit, and the host
    fetches the missing segments into the owning shard's cache before
    the retry round — steady-state rounds still read one scalar.
    """
    cfg = dcfg.pfo
    mdl = dcfg.model_axis
    tcfg = lsh_tree_config(cfg)
    mcfg = main_tree_config(cfg)
    tps = dcfg.trees_per_shard
    mtps = dcfg.main_trees_per_shard
    snap_cfg = shard_main_snap_cfg(dcfg)
    S = dcfg.n_model

    def local_fn(state: PFOState, ids: jax.Array, active: jax.Array):
        me = jax.lax.axis_index(mdl)
        mh, mtree = main_table_keys(ids, cfg)
        own = active & (mtree // mtps == me)
        ltree = jnp.where(own, mtree % mtps, 0)
        slot, found = forest_lookup(state.main_forest, ltree, mh, ids, mcfg)
        msnaps = jax.tree.map(lambda a: a[0], state.main_snaps)
        sval, sfound = snap_mod.lookup_exact(msnaps, mh, ids)
        slot = jnp.where(found, slot, jnp.where(sfound, sval, -1))
        store_l = jax.tree.map(lambda a: a[0], state.store)
        if cfg.cold_enabled:
            cold_l = jax.tree.map(lambda a: a[0], state.cold)
            cold_ids = jnp.where(own & ~(found | sfound), ids, -1)
            cval, cfound, row_missing, wm, mm, _, _ = \
                coldtier.cold_lookup_main(cold_l, mh, cold_ids, snap_cfg)
            cfound = cfound & ~row_missing
            slot = jnp.where(slot >= 0, slot,
                             jnp.where(cfound, cval, -1))
            ok = own & (found | sfound | cfound) & (slot >= 0)
            unresolved = own & ~(found | sfound | cfound) & row_missing
            arena = cold_l.main_cache.vecs
            vecs = dense_read_tiered(store_l,
                                     arena.reshape(-1, arena.shape[-1]),
                                     jnp.where(ok, slot, 0))
        else:
            ok = own & (found | sfound) & (slot >= 0)
            vecs = dense_read(store_l, jnp.where(ok, slot, 0))
        ok_all = _psum_bool(ok, mdl)

        # re-derive LSH keys from the stored vector (owner-side)
        h = kops.lsh_hash(vecs, state.proj["table_proj"], cfg.M)
        region = region_ids(h, state.proj["part_proj"], cfg)
        off = jnp.arange(cfg.L, dtype=jnp.int32)[None] * cfg.n_trees
        gflat = (region + off).reshape(-1)
        lsend = jnp.repeat(ok, cfg.L)
        ldest = jnp.where(lsend, gflat // tps, -1)
        lpay = jnp.stack([h.reshape(-1).astype(jnp.int32),
                          jnp.repeat(ids, cfg.L),
                          gflat % tps], axis=1)
        lrecv, l_send_ovf, lack = _route_acked(lpay, ldest, S, route_lsh,
                                               mdl, marker_col=1)
        rh = lrecv[:, 0].astype(jnp.uint32)
        rid = lrecv[:, 1]
        rlt = lrecv[:, 2]
        lbox, l_recv_ovf = dispatch_to_trees(
            jnp.where(rid >= 0, rlt, -1), tps, tree_lsh)
        (lh_g,) = gather_mailbox(lbox, rh)
        lid_g = mailbox_ids(lbox, rid)
        lsh_forest = forest_delete_dispatched(state.lsh_forest, lh_g,
                                              lid_g, tcfg)
        l_fail = lack((rid >= 0) & l_recv_ovf)
        l_ent = lsend & (l_send_ovf | l_fail)
        l_row = _psum_bool(jnp.any(l_ent.reshape(-1, cfg.L), axis=1), mdl)

        # hot MainTable unlink + store reclaim, owner-local
        mbox, m_ovf = dispatch_to_trees(jnp.where(ok, ltree, -1), mtps,
                                        tree_main)
        (mh_g,) = gather_mailbox(mbox, mh)
        mid_g = mailbox_ids(mbox, ids)
        main_forest = forest_delete_dispatched(state.main_forest, mh_g,
                                               mid_g, mcfg)
        m_row = _psum_bool(ok & m_ovf, mdl)
        if cfg.cold_enabled:
            # staging-slot rows were freed when their segment spilled —
            # freeing the out-of-range encoded slot would push garbage
            # onto the free stack
            hot_ok = ok & (slot < snap_cfg.store_capacity)
            store_l = dense_free(store_l, jnp.where(hot_ok, slot, 0),
                                 hot_ok)
        else:
            store_l = dense_free(store_l, slot, ok)
        store = jax.tree.map(lambda a: a[None, ...], store_l)

        # tombstones (replicated; identical append on every shard —
        # overflow parks out of bounds, exactly like the single-chip
        # scatter, and the row stays pending until a merge drains it)
        want = ok_all.astype(jnp.int32)
        rank = jnp.cumsum(want) - want
        pos = state.n_tombstones + rank
        fits = ok_all & (pos < cfg.max_tombstones)
        safe = jnp.where(fits, pos, cfg.max_tombstones)
        tombs = state.tombstones.at[safe].set(ids, mode="drop")
        n_t = jnp.minimum(
            state.n_tombstones + jnp.sum(fits.astype(jnp.int32)),
            cfg.max_tombstones)

        state = state._replace(lsh_forest=lsh_forest,
                               main_forest=main_forest, store=store,
                               tombstones=tombs, n_tombstones=n_t)
        tomb_ovf = ok_all & ~fits
        pending = (ok_all & (l_row | m_row)) | tomb_ovf
        if cfg.cold_enabled:
            pending = pending | _psum_bool(unresolved, mdl)
            cold_miss = jax.lax.psum(jnp.any(mm).astype(jnp.int32),
                                     mdl) > 0
            flags = _dist_round_flags(state, dcfg, flags_main,
                                      flags_lsh, jnp.any(pending), mdl,
                                      cold_miss=cold_miss)
            return state, pending, flags, wm[None], mm[None]
        flags = _dist_round_flags(state, dcfg, flags_main, flags_lsh,
                                  jnp.any(pending), mdl)
        return state, pending, flags

    out_specs = (state_pspecs(dcfg), P(), P())
    if cfg.cold_enabled:
        out_specs = out_specs + (P(mdl), P(mdl))
    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg), P(), P()),
                          out_specs=out_specs, check_vma=False)
    return jax.jit(fn)


# ======================================================================
# maintenance epochs + cold-start flags (shard-local, no collectives
# beyond the pmax folded into the flag word)
# ======================================================================
def make_dist_seal(dcfg: DistConfig, mesh: Mesh):
    """Jitted distributed seal: every chip seals its own tree block into
    its own snapshot segment set and resets its hot forests."""
    cfg = dcfg.pfo
    tcfg = lsh_tree_config(cfg)
    mcfg = main_tree_config(cfg)
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    tps = dcfg.trees_per_shard
    mtps = dcfg.main_trees_per_shard

    mdl = dcfg.model_axis

    def local_fn(state: PFOState):
        stamp = state.stamp + 1
        me = jax.lax.axis_index(mdl)
        lf = state.lsh_forest
        # LSH leaf vals are redundant (val == id); store the table id
        # instead so mixed-table segments probe and merge per table
        table = (me * tps + jnp.arange(tps, dtype=jnp.int32)) \
            // cfg.n_trees
        ltag = jnp.broadcast_to(table[:, None],
                                lf.leaf_id.shape).reshape(-1)
        lsnap = snap_mod.seal(
            jax.tree.map(lambda a: a[0], state.lsh_snaps),
            lf.leaf_key.reshape(-1), lf.leaf_id.reshape(-1),
            ltag, lf.leaf_id.reshape(-1) >= 0,
            stamp, snap_cfg)
        mf = state.main_forest
        msnap = snap_mod.seal(
            jax.tree.map(lambda a: a[0], state.main_snaps),
            mf.leaf_key.reshape(-1), mf.leaf_id.reshape(-1),
            mf.leaf_val.reshape(-1), mf.leaf_id.reshape(-1) >= 0,
            stamp, msnap_cfg)
        return state._replace(
            lsh_forest=init_forest(tcfg, tps),
            main_forest=init_forest(mcfg, mtps),
            lsh_snaps=jax.tree.map(lambda a: a[None, ...], lsnap),
            main_snaps=jax.tree.map(lambda a: a[None, ...], msnap),
            stamp=stamp)

    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg),),
                          out_specs=state_pspecs(dcfg), check_vma=False)
    return jax.jit(fn)


def make_dist_merge(dcfg: DistConfig, mesh: Mesh):
    """Jitted distributed merge: shard-local snapshot compaction with
    the replicated tombstone buffer, then drain the buffer."""
    cfg = dcfg.pfo
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)

    def local_fn(state: PFOState):
        tombs = state.tombstones
        lsnap = snap_mod.merge(
            jax.tree.map(lambda a: a[0], state.lsh_snaps), snap_cfg, tombs,
            group_by_val=True)
        msnap = snap_mod.merge(
            jax.tree.map(lambda a: a[0], state.main_snaps), msnap_cfg,
            tombs)
        return state._replace(
            lsh_snaps=jax.tree.map(lambda a: a[None, ...], lsnap),
            main_snaps=jax.tree.map(lambda a: a[None, ...], msnap),
            tombstones=jnp.full_like(state.tombstones, -1),
            n_tombstones=jnp.int32(0))

    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg),),
                          out_specs=state_pspecs(dcfg), check_vma=False)
    return jax.jit(fn)


def make_dist_spill(dcfg: DistConfig, mesh: Mesh):
    """Jitted distributed spill epoch: every shard pops the oldest
    segment of its mixed LSH ring and of its MainTable ring, folds the
    popped metadata into its own cold routing table, gathers the popped
    MainTable payloads out of its dense store and frees the spilled
    slots — entirely shard-local (lockstep rings mean every shard
    spills in the same epoch; no cross-shard synchronization).

    Returns ``(state', popped_lsh, popped_main)`` with the popped
    arrays stacked (S, ...) — the host reads them back once and
    persists each shard's segments through that shard's
    ``ColdManager.adopt_spill``.
    """
    cfg = dcfg.pfo
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    mcfg = main_tree_config(cfg)
    mtps = dcfg.main_trees_per_shard
    mdl = dcfg.model_axis

    def local_fn(state: PFOState):
        lsh2, main2, cold2, store2, pl, pm = coldtier.spill_device(
            state.lsh_snaps,
            jax.tree.map(lambda a: a[0], state.main_snaps),
            jax.tree.map(lambda a: a[0], state.cold),
            jax.tree.map(lambda a: a[0], state.store),
            state.main_forest, state.tombstones,
            snap_cfg, msnap_cfg, mcfg, tree_mod=mtps)
        state = state._replace(
            lsh_snaps=lsh2,
            main_snaps=jax.tree.map(lambda a: a[None, ...], main2),
            cold=jax.tree.map(lambda a: a[None, ...], cold2),
            store=jax.tree.map(lambda a: a[None, ...], store2))
        return state, pl, jax.tree.map(lambda a: a[None, ...], pm)

    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg),),
                          out_specs=(state_pspecs(dcfg), P(mdl), P(mdl)),
                          check_vma=False)
    return jax.jit(fn)


def make_dist_ring_drain(dcfg: DistConfig, mesh: Mesh):
    """Jitted device half of the distributed cold merge: every shard
    gathers the vector payloads of the ring entries it holds the
    current version of and frees those store slots (the entries leave
    the device for the shard's host fold).  Returns
    ``(state', payloads (S, R, cap, d), cur (S, R, cap))``."""
    cfg = dcfg.pfo
    msnap_cfg = shard_main_snap_cfg(dcfg)
    mcfg = main_tree_config(cfg)
    mtps = dcfg.main_trees_per_shard
    mdl = dcfg.model_axis

    def local_fn(state: PFOState):
        payload, cur, store2 = coldtier.ring_payload_drain(
            jax.tree.map(lambda a: a[0], state.main_snaps),
            jax.tree.map(lambda a: a[0], state.store),
            state.main_forest, state.tombstones, msnap_cfg, mcfg,
            tree_mod=mtps)
        state = state._replace(
            store=jax.tree.map(lambda a: a[None, ...], store2))
        return state, payload[None], cur[None]

    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg),),
                          out_specs=(state_pspecs(dcfg), P(mdl), P(mdl)),
                          check_vma=False)
    return jax.jit(fn)


def dist_put_cold(dcfg: DistConfig, mesh: Mesh, cold_states):
    """Stack per-shard :class:`coldtier.ColdState` values (one per
    shard, in shard order) into the distributed state's (S, ...) cold
    leaves with their NamedShardings — the install half of a
    distributed cold merge/compaction."""
    mdl = dcfg.model_axis
    cold = jax.tree.map(lambda *xs: jnp.stack(xs), *cold_states)
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, P(mdl))), cold)


def dist_fresh_rings(dcfg: DistConfig, mesh: Mesh):
    """Fresh (empty) per-shard snapshot rings with their shardings —
    the ring reset of a distributed cold merge."""
    mdl = dcfg.model_axis
    snap_cfg = shard_snap_cfg(dcfg)
    msnap_cfg = shard_main_snap_cfg(dcfg)
    mk = jax.jit(lambda: (
        jax.vmap(lambda _: snap_mod.init_snapshots(snap_cfg))(
            jnp.arange(dcfg.n_model)),
        jax.vmap(lambda _: snap_mod.init_snapshots(msnap_cfg))(
            jnp.arange(dcfg.n_model))))
    lsnaps, msnaps = mk()
    put = functools.partial(jax.tree.map, lambda x: jax.device_put(
        x, NamedSharding(mesh, P(mdl))))
    return put(lsnaps), put(msnaps)


def make_dist_round_flags(dcfg: DistConfig, mesh: Mesh, flags_main: int,
                          flags_lsh: int):
    """Cold-start flag probe (capacity change / first round only —
    steady-state rounds get their flags from the step itself)."""
    mdl = dcfg.model_axis

    def local_fn(state: PFOState):
        return _dist_round_flags(state, dcfg, flags_main, flags_lsh,
                                 jnp.bool_(False), mdl)

    fn = compat.shard_map(local_fn, mesh=mesh,
                          in_specs=(state_pspecs(dcfg),),
                          out_specs=P(), check_vma=False)
    return jax.jit(fn)
