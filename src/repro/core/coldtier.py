"""Cold tier — host/flash-resident sealed segments with device-side
Bloom routing and an on-device LRU segment cache (paper §3.2.2's
"scale the system capacity by using flash memory").

The hierarchy this module completes:

  hot forests (HBM)  →  sealed snapshot ring (HBM, ``snapshots.py``)
                     →  **cold segment store (host RAM / flash files)**

When the device snapshot ring fills past ``max_snapshots - 1`` (or the
dense store's free list falls below ``store_low_watermark``) the
*oldest* sealed segment of every LSH table (and of the MainTable)
spills verbatim to a host :class:`repro.storage.SegmentStore` — the
write-once, bucket-major Index+Data layout seals already produce is
exactly the sequential-flash format the paper wants.  A spilled
MainTable segment carries its **vector payloads** with it (one f32 row
per entry, gathered out of the dense store) and frees the store slots
of every entry it takes sole custody of — the dense arena only ever
holds the hot + ring working set, and cold candidates are ranked from
the payload pages of cache-resident segments (the device **staging
arena**, ``ColdCache.vecs``).  What stays on device is a compact
**routing table** per tier: the spilled segments' Bloom filters, seal
stamps and entry counts.  The query path probes
*all* filters (device ring + cold routing) in the same vectorized shot
it always did; only segments whose filter matched and that are not
already resident in the small device-side **segment cache** trigger a
fetch.  Fetches are asynchronous at the transfer level (the host
issues every missing segment's ``device_put`` before dispatching the
re-probe, so the copies overlap each other and the round's hot-tier
descent) and the cache is updated functionally — the previous round's
buffers stay valid while the next round's fill is in flight (double
buffering by construction).

Steady-state discipline: a query round whose Bloom pass hits no
non-resident cold segment performs ZERO extra host<->device traffic —
the wanted/missing masks ride in the round's one result pickup.  Only
miss rounds fetch and re-probe.  Spills, cold merges and compactions
are maintenance epochs driven by the round flag word
(``dispatch.FLAG_COLD_*``), exactly like seal/merge.

Background compaction: superseded-duplicate folding of cold segments
(the host half of the paper's merge routine) is semantics-preserving
without tombstones, so it runs on a worker thread against the
immutable segment files and the result is installed between rounds —
rounds never stall on it.  Tombstone application (deletes) is the
exception: it must be atomic with the device-side tombstone drain, so
it runs synchronously inside the merge epoch (:meth:`ColdManager.
merge_cold`).
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bloom as bloom_mod
from . import snapshots as snap_mod
from .config import PFOConfig
from .hash_tree import TreeConfig, forest_lookup
from .lsh import main_table_keys
from .membership import member_sorted as _member_sorted
from .store import DenseStore, dense_free
from repro.storage import SegmentStore

_PAD_KEY = np.uint32(0xFFFFFFFF)


# ======================================================================
# device-resident structures
# ======================================================================
class ColdRouting(NamedTuple):
    """What stays hot for spilled segments: Bloom + metadata only."""
    blooms: jax.Array   # u32 (..., C, W) packed filters
    stamps: jax.Array   # i32 (..., C) seal stamps
    counts: jax.Array   # i32 (..., C) live entries


class ColdCache(NamedTuple):
    """Device-side LRU segment cache (fetched cold segment payloads)."""
    keys: jax.Array     # u32 (E, cap) sorted per segment
    ids: jax.Array      # i32 (E, cap)
    vals: jax.Array     # i32 (E, cap)
    stamps: jax.Array   # i32 (E,)
    tables: jax.Array   # i32 (E,) owning LSH table (0 for main); -1 empty
    segs: jax.Array     # i32 (E,) cold segment index; -1 empty
    # vector payload pages (MainTable cache only): f32 (E, cap, d) with
    # row r holding segment entry r's vector — the device **staging
    # arena** cold candidates are ranked from (flattened to (E*cap, d)
    # and addressed as slot = store_capacity + e*cap + r).  None for
    # the LSH cache, whose vals are ids, not vectors.
    vecs: jax.Array | None = None


class ColdState(NamedTuple):
    lsh_route: ColdRouting    # stacked (L, C, ...)
    main_route: ColdRouting   # (C, ...)
    lsh_cache: ColdCache
    main_cache: ColdCache
    n_cold: jax.Array         # i32 () cold segments per tier instance


def _empty_cache(cfg: PFOConfig, cap: int, dim: int | None = None
                 ) -> ColdCache:
    E = cfg.cold_cache_slots
    return ColdCache(
        keys=jnp.full((E, cap), jnp.uint32(_PAD_KEY)),
        ids=jnp.full((E, cap), -1, jnp.int32),
        vals=jnp.zeros((E, cap), jnp.int32),
        stamps=jnp.zeros((E,), jnp.int32),
        tables=jnp.full((E,), -1, jnp.int32),
        segs=jnp.full((E,), -1, jnp.int32),
        vecs=None if dim is None
        else jnp.zeros((E, cap, dim), jnp.float32),
    )


def init_cold(cfg: PFOConfig, lsh_cfg: PFOConfig,
              main_cfg: PFOConfig) -> ColdState | None:
    """Empty cold tier (None when disabled — the state pytree then has
    no cold leaves and every cold code path is statically skipped)."""
    if not cfg.cold_enabled:
        return None
    C, L = cfg.cold_segments, cfg.L
    Wl = lsh_cfg.bloom_bits_eff // 32
    Wm = main_cfg.bloom_bits_eff // 32
    return ColdState(
        lsh_route=ColdRouting(blooms=jnp.zeros((L, C, Wl), jnp.uint32),
                              stamps=jnp.zeros((L, C), jnp.int32),
                              counts=jnp.zeros((L, C), jnp.int32)),
        main_route=ColdRouting(blooms=jnp.zeros((C, Wm), jnp.uint32),
                               stamps=jnp.zeros((C,), jnp.int32),
                               counts=jnp.zeros((C,), jnp.int32)),
        lsh_cache=_empty_cache(cfg, lsh_cfg.snapshot_capacity),
        main_cache=_empty_cache(cfg, main_cfg.snapshot_capacity,
                                dim=cfg.dim),
        n_cold=jnp.int32(0),
    )


# ======================================================================
# device-side probes (called inside the jitted query/delete steps)
# ======================================================================
def _residency(cache: ColdCache, table, C: int):
    """(slot_ok, slot_seg, resident): which cold segments sit in cache."""
    slot_ok = (cache.tables == table) & (cache.segs >= 0)
    slot_seg = jnp.where(slot_ok, cache.segs, C)
    resident = jnp.zeros((C + 1,), bool).at[slot_seg].set(True)[:C]
    return slot_ok, slot_seg, resident


def cold_probe_lsh(cold: ColdState, hs: jax.Array, lsh_cfg: PFOConfig):
    """Cold-tier LSH candidates for a query batch.

    hs: (Q, L) compound keys.  Probes every cold segment's Bloom filter
    (multi-probe prefixes included) and gathers bucket spans from the
    segments resident in the cache.  Returns
    (cand (Q, L*E*P*B), wanted (L, C), missing (L, C), probed, fp)
    where probed/fp are i32 scalars for Bloom-accounting.
    """
    Q = hs.shape[0]
    C = cold.lsh_route.stamps.shape[1]
    cache = cold.lsh_cache

    def per_table(route_l, l, h_l):
        pfx = snap_mod.probe_prefixes(h_l, lsh_cfg).reshape(-1)   # (Q*P,)
        hit = bloom_mod.contains_multi(route_l.blooms, pfx,
                                       lsh_cfg.bloom_hashes_eff)  # (C, Q*P)
        act = (jnp.arange(C)[:, None] < cold.n_cold) & hit
        wanted = jnp.any(act, axis=1)                             # (C,)
        slot_ok, slot_seg, resident = _residency(cache, l, C)
        missing = wanted & ~resident
        act_slot = slot_ok[:, None] & act[jnp.clip(cache.segs, 0, C - 1)]
        cids, _, _, matched = jax.vmap(
            lambda k, i, v, a: snap_mod.span_gather(k, i, v, a, pfx,
                                                    lsh_cfg))(
            cache.keys, cache.ids, cache.vals, act_slot)   # (E, Q*P, B)
        probed = wanted & resident
        seg_any = jnp.zeros((C + 1,), bool).at[slot_seg].set(
            jnp.any(matched, axis=1))[:C]
        fp = probed & ~seg_any
        cand = jnp.transpose(cids, (1, 0, 2)).reshape(Q, -1)
        return (cand, wanted, missing,
                jnp.sum(probed.astype(jnp.int32)),
                jnp.sum(fp.astype(jnp.int32)))

    L = hs.shape[1]
    cand, wanted, missing, probed, fp = jax.vmap(
        per_table, in_axes=(0, 0, 1))(
        cold.lsh_route, jnp.arange(L, dtype=jnp.int32), hs)
    cand = jnp.transpose(cand, (1, 0, 2)).reshape(Q, -1)
    return cand, wanted, missing, jnp.sum(probed), jnp.sum(fp)


def cold_probe_lsh_mixed(cold: ColdState, hs: jax.Array,
                         lsh_cfg: PFOConfig):
    """Cold-tier LSH candidates against a *mixed-table* segment set —
    the distributed per-shard tier, where one segment chain holds
    entries from every LSH table a shard owns (table id in ``vals``,
    the same encoding the shard's sealed ring uses).

    ``cold.lsh_route`` is stacked (1, C, W) (one mixed chain); every
    table's probe prefixes test the same C filters, spans gather from
    the same cache slots (``tables`` tag 0), and cross-table
    bucket-prefix collisions filter out by ``val == table`` — the
    candidate multiset matches the per-table tier.  Returns
    (cand (Q, L*E*P*B), wanted (C,), missing (C,), probed, fp).
    """
    Q, L = hs.shape
    C = cold.lsh_route.stamps.shape[1]
    cache = cold.lsh_cache
    route = jax.tree.map(lambda a: a[0], cold.lsh_route)
    slot_ok, slot_seg, resident = _residency(cache, 0, C)
    cands = []
    wanted = jnp.zeros((C,), bool)
    seg_any = jnp.zeros((C,), bool)
    for tl in range(L):
        pfx = snap_mod.probe_prefixes(hs[:, tl], lsh_cfg).reshape(-1)
        hit = bloom_mod.contains_multi(route.blooms, pfx,
                                       lsh_cfg.bloom_hashes_eff)  # (C, Q*P)
        act = (jnp.arange(C)[:, None] < cold.n_cold) & hit
        wanted = wanted | jnp.any(act, axis=1)
        act_slot = slot_ok[:, None] & act[jnp.clip(cache.segs, 0, C - 1)]
        cids, cvals, _, matched = jax.vmap(
            lambda k, i, v, a: snap_mod.span_gather(k, i, v, a, pfx,
                                                    lsh_cfg))(
            cache.keys, cache.ids, cache.vals, act_slot)   # (E, Q*P, B)
        cids = jnp.where(cvals == tl, cids, -1)
        seg_any = seg_any | jnp.zeros((C + 1,), bool).at[slot_seg].set(
            jnp.any(matched, axis=1))[:C]
        cands.append(jnp.transpose(cids, (1, 0, 2)).reshape(Q, -1))
    missing = wanted & ~resident
    probed = wanted & resident
    fp = probed & ~seg_any
    return (jnp.concatenate(cands, axis=1), wanted, missing,
            jnp.sum(probed.astype(jnp.int32)),
            jnp.sum(fp.astype(jnp.int32)))


def cold_lookup_main(cold: ColdState, mh: jax.Array, vids: jax.Array,
                     main_cfg: PFOConfig):
    """Exact (key, id) lookup in the cold MainTable cache.

    mh/vids: (N,) murmur keys and ids (-1 == padding).  Returns
    (slot, found, row_missing, wanted (C,), missing (C,), probed, fp):
    ``slot`` is a **staging-arena slot** — the resolving entry's row in
    the flattened (E*cap, d) payload arena, offset by
    ``store_capacity`` so the tiered gather can route by range (the
    entry's dense-store slot was freed when its segment spilled).
    ``row_missing`` marks rows whose Bloom route hit a *non-resident*
    segment — the row cannot be resolved this round and must retry
    after a fetch.
    """
    C = cold.main_route.stamps.shape[0]
    cache = cold.main_cache
    n = mh.shape[0]
    cap = main_cfg.snapshot_capacity
    pfx = snap_mod._prefix(mh, main_cfg.snap_prefix_bits)         # (N,)
    hit = bloom_mod.contains_multi(cold.main_route.blooms, pfx,
                                   main_cfg.bloom_hashes_eff)     # (C, N)
    act = ((jnp.arange(C)[:, None] < cold.n_cold) & hit
           & (vids >= 0)[None, :])
    wanted = jnp.any(act, axis=1)
    slot_ok, slot_seg, resident = _residency(cache, 0, C)
    missing = wanted & ~resident
    act_slot = slot_ok[:, None] & act[jnp.clip(cache.segs, 0, C - 1)]
    cids, _, cpos, matched = jax.vmap(
        lambda k, i, v, a: snap_mod.span_gather(k, i, v, a, pfx,
                                                main_cfg))(
        cache.keys, cache.ids, cache.vals, act_slot)       # (E, N, B)

    is_vid = (cids >= 0) & (cids == vids[None, :, None])
    stamp_sc = jnp.where(is_vid, cache.stamps[:, None, None], -1)
    srow = (jnp.arange(cache.keys.shape[0], dtype=jnp.int32)[:, None, None]
            * cap + jnp.maximum(cpos, 0))                  # (E, N, B)
    flat_s = jnp.transpose(stamp_sc, (1, 0, 2)).reshape(n, -1)
    flat_r = jnp.transpose(srow, (1, 0, 2)).reshape(n, -1)
    best = jnp.argmax(flat_s, axis=1)                  # newest stamp wins
    found = jnp.max(flat_s, axis=1, initial=-1) >= 0
    val = jnp.where(
        found,
        main_cfg.store_capacity
        + jnp.take_along_axis(flat_r, best[:, None], 1)[:, 0], -1)
    row_missing = jnp.any(act & missing[:, None], axis=0)

    probed = wanted & resident
    seg_any = jnp.zeros((C + 1,), bool).at[slot_seg].set(
        jnp.any(matched, axis=1))[:C]
    fp = probed & ~seg_any
    return (val, found, row_missing, wanted, missing,
            jnp.sum(probed.astype(jnp.int32)),
            jnp.sum(fp.astype(jnp.int32)))


def pack_cold_info(lsh_wanted, lsh_missing, lsh_probed, lsh_fp,
                   main_wanted, main_missing, main_probed, main_fp,
                   staged_ranked, ranked_total):
    """Round accounting vector (i32 (10,)): rides in the result pickup.
    ``staged_ranked``/``ranked_total`` count candidates ranked out of
    the staging arena vs. all ranked candidates — the host derives the
    staging share and read amplification from them without any extra
    readback."""
    def c(x):
        return jnp.sum(x.astype(jnp.int32)) \
            if jnp.issubdtype(x.dtype, jnp.bool_) else x.astype(jnp.int32)
    return jnp.stack([c(lsh_wanted), c(lsh_missing), c(lsh_probed),
                      c(lsh_fp), c(main_wanted), c(main_missing),
                      c(main_probed), c(main_fp), c(staged_ranked),
                      c(ranked_total)])


# ======================================================================
# jitted maintenance helpers (host-called, epoch-time)
# ======================================================================
@functools.partial(jax.jit,
                   static_argnames=("lsh_cfg", "main_cfg", "main_tcfg",
                                    "tree_mod"))
def spill_device(lsh_snaps, main_snaps, cold: ColdState,
                 store: DenseStore, main_forest, tombs,
                 lsh_cfg: PFOConfig, main_cfg: PFOConfig,
                 main_tcfg: TreeConfig, tree_mod: int | None = None):
    """Pop the oldest ring segment of every tier; route metadata into
    the cold routing table; gather the popped MainTable segment's
    vector payloads out of the dense store and free the store slots of
    every entry the segment takes sole custody of.  Returns
    (lsh', main', cold', store', popped_lsh, popped_main) — the popped
    arrays (now including ``popped_main["payload"]``) are read back by
    the host once and persisted in the SegmentStore.

    "Sole custody" (the ``cur`` mask): the entry's id has no newer
    copy in the hot MainTable forest or the remaining ring, no pending
    tombstone, and its slot is still live.  Only those entries get a
    real payload row and a freed slot; stale entries keep a zero
    payload — they are never ranked (hot/ring precedence,
    newest-stamp-wins resolution and the tombstone filter all shadow
    them) and their slots were already freed (or re-owned) by the
    delete/update that superseded them.

    ``tree_mod``: the distributed per-shard variant — the shard's hot
    MainTable forest holds only its ``tree_mod`` local trees, so the
    global murmur tree id reduces modulo it (the shard's ring only ever
    holds ids the shard owns)."""
    lsh2, pl = jax.vmap(
        lambda s: snap_mod.pop_oldest(s, lsh_cfg))(lsh_snaps)
    main2, pm = snap_mod.pop_oldest(main_snaps, main_cfg)
    ids, vals = pm["ids"], pm["vals"]
    n_store = store.data.shape[0]
    mh, mtree = main_table_keys(ids, main_cfg)
    if tree_mod is not None:
        mtree = mtree % tree_mod
    _, hot_found = forest_lookup(main_forest, mtree, mh, ids, main_tcfg)
    in_ring = _member_sorted(ids, main2.ids)
    dead = _member_sorted(ids, tombs)
    safe = jnp.clip(vals, 0, n_store - 1)
    live = store.live[safe] & (vals >= 0)
    cur = (ids >= 0) & ~hot_found & ~in_ring & ~dead & live
    pm = dict(pm)
    pm["payload"] = jnp.where(cur[:, None], store.data[safe],
                              jnp.float32(0.0))
    pm["cur"] = cur
    store2 = dense_free(store, vals, cur)
    nc = cold.n_cold
    lr, mr = cold.lsh_route, cold.main_route
    cold2 = cold._replace(
        lsh_route=ColdRouting(
            blooms=lr.blooms.at[:, nc].set(pl["bloom"]),
            stamps=lr.stamps.at[:, nc].set(pl["stamp"]),
            counts=lr.counts.at[:, nc].set(pl["count"])),
        main_route=ColdRouting(
            blooms=mr.blooms.at[nc].set(pm["bloom"]),
            stamps=mr.stamps.at[nc].set(pm["stamp"]),
            counts=mr.counts.at[nc].set(pm["count"])),
        n_cold=nc + 1)
    return lsh2, main2, cold2, store2, pl, pm


@jax.jit
def cache_install(cache: ColdCache, slot, keys, ids, vals, stamp,
                  table, seg, vecs=None) -> ColdCache:
    """Load one fetched segment into a cache slot (functional update —
    the previous cache buffers stay live for any in-flight round).
    ``vecs`` (cap, d) loads the segment's vector payload page into the
    staging arena (MainTable cache only)."""
    return ColdCache(
        keys=cache.keys.at[slot].set(keys),
        ids=cache.ids.at[slot].set(ids),
        vals=cache.vals.at[slot].set(vals),
        stamps=cache.stamps.at[slot].set(stamp),
        tables=cache.tables.at[slot].set(table),
        segs=cache.segs.at[slot].set(seg),
        vecs=cache.vecs if vecs is None else cache.vecs.at[slot].set(vecs),
    )


@functools.partial(jax.jit, static_argnames=("main_cfg", "main_tcfg",
                                             "tree_mod"))
def ring_payload_drain(main_snaps, store: DenseStore, main_forest,
                       tombs, main_cfg: PFOConfig, main_tcfg: TreeConfig,
                       tree_mod: int | None = None):
    """Device half of the cold merge's ring drain: gather the vector
    payload of every ring entry the ring holds the current version of,
    and free those store slots (the entries leave the device for the
    cold fold).  Returns (payloads (S, cap, d), cur (S, cap), store').

    ``cur`` mirrors :func:`spill_device`'s sole-custody mask, with one
    extra clause: only the *newest ring copy* of an id qualifies —
    an updated id can have several ring copies, and the stale ones'
    slots were already freed (and possibly re-owned by another id) at
    delete time, so freeing by their ``val`` would corrupt the store.
    The newest-per-id choice is made by (stamp-desc, id) lexsort, the
    same discipline the fold itself applies."""
    S, cap = main_snaps.ids.shape
    ids = main_snaps.ids.reshape(-1)
    vals = main_snaps.vals.reshape(-1)
    stamps = jnp.broadcast_to(main_snaps.stamps[:, None],
                              (S, cap)).reshape(-1)
    valid = ids >= 0               # pads (and slots >= n_snaps) are -1
    imax = jnp.int32(2**31 - 1)
    ikey = jnp.where(valid, ids, imax)
    order = jnp.lexsort((-stamps, ikey))
    sid = ikey[order]
    first = jnp.concatenate([jnp.array([True]), sid[1:] != sid[:-1]])
    newest = jnp.zeros_like(valid).at[order].set(first & (sid < imax))
    mh, mtree = main_table_keys(ids, main_cfg)
    if tree_mod is not None:                   # distributed: local trees
        mtree = mtree % tree_mod
    _, hot_found = forest_lookup(main_forest, mtree, mh, ids, main_tcfg)
    dead = _member_sorted(ids, tombs)
    n_store = store.data.shape[0]
    safe = jnp.clip(vals, 0, n_store - 1)
    live = store.live[safe] & (vals >= 0)
    cur = valid & newest & ~hot_found & ~dead & live
    payload = jnp.where(cur[:, None], store.data[safe], jnp.float32(0.0))
    store2 = dense_free(store, vals, cur)
    return (payload.reshape(S, cap, -1), cur.reshape(S, cap), store2)


# ======================================================================
# host-side Bloom build (numpy mirror of core.bloom — parity-tested)
# ======================================================================
_GOLDEN = 0x9E3779B9
_M32 = 0xFFFFFFFF


def _np_fmix32(x: np.ndarray, seed: int) -> np.ndarray:
    h = (x ^ ((seed * _GOLDEN) & _M32)) & _M32
    h = h ^ (h >> 16)
    h = (h * 0x85EBCA6B) & _M32
    h = h ^ (h >> 13)
    h = (h * 0xC2B2AE35) & _M32
    h = h ^ (h >> 16)
    return h


def np_bloom_build(keys: np.ndarray, n_hashes: int, bloom_bits: int,
                   mask: np.ndarray | None = None) -> np.ndarray:
    """Pure-numpy twin of ``bloom.build`` — bit-identical filters, so
    the background compaction thread never touches the JAX runtime."""
    seeds = np.arange(1, n_hashes + 1, dtype=np.uint64)
    x = (keys.astype(np.uint64)[..., None] + seeds * _GOLDEN) & _M32
    pos = (_np_fmix32(x, seed=7) % bloom_bits).astype(np.int64)
    if mask is not None:
        pos = pos[mask]
    bits = np.zeros((bloom_bits,), bool)
    bits[pos.reshape(-1)] = True
    words = bits.reshape(-1, 32).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(32, dtype=np.uint32))
    return (words * weights).sum(axis=1, dtype=np.uint32)


def _np_prefix(keys: np.ndarray, bits: int) -> np.ndarray:
    return (keys.astype(np.uint32) >> np.uint32(32 - bits))


# ======================================================================
# host orchestration
# ======================================================================
class _FoldResult(NamedTuple):
    """Output of a (possibly background) cold compaction fold."""
    gen: int                       # cold-store generation it was computed at
    lsh_segments: list             # per table: list of segment dicts
    main_segments: list


def _fold_entries(keys, ids, vals, stamps, dead: np.ndarray, cap: int,
                  prefix_bits: int, bloom_hashes: int, bloom_bits: int,
                  payloads=None, group_by_val: bool = False):
    """Fold concatenated segment entries: drop dead/padding, keep the
    newest stamp per id, re-sort bucket-major, chunk into cap-sized
    write-once segments with fresh Bloom filters.  Pure numpy.
    ``payloads`` (n, d) rows travel with their entries (MainTable
    tier), so tombstoned/superseded vectors are physically dropped in
    the same pass that drops their index entries.  ``group_by_val``
    dedupes per (id, val) instead of per id — mixed-table segments
    (``val`` == owning LSH table, the distributed per-shard tier) keep
    one entry per table legitimately, mirroring
    ``snapshots.merge(group_by_val=True)``."""
    live = ids >= 0
    if dead.size:
        live &= ~np.isin(ids, dead)
    k = np.asarray(keys, np.uint32)[live]
    i = np.asarray(ids, np.int32)[live]
    v = np.asarray(vals, np.int32)[live]
    s = np.asarray(stamps, np.int32)[live]
    p = None if payloads is None \
        else np.asarray(payloads, np.float32)[live]
    if i.size:
        if group_by_val:
            order = np.lexsort((-s, v, i))     # (id, val) asc, stamp desc
            same = (i[order][1:] == i[order][:-1]) \
                & (v[order][1:] == v[order][:-1])
            first = np.concatenate([[True], ~same])
        else:
            order = np.lexsort((-s, i))        # id asc, stamp desc
            first = np.concatenate([[True], i[order][1:] != i[order][:-1]])
        keep = np.sort(order[first])
        k, i, v, s = k[keep], i[keep], v[keep], s[keep]
        ko = np.argsort(k, kind="stable")
        k, i, v, s = k[ko], i[ko], v[ko], s[ko]
        if p is not None:
            p = p[keep][ko]
    out = []
    for lo in range(0, len(i), cap):
        ck, ci, cv, cs = (a[lo:lo + cap] for a in (k, i, v, s))
        n = len(ci)
        pk = np.full((cap,), _PAD_KEY, np.uint32)
        pi = np.full((cap,), -1, np.int32)
        pv = np.zeros((cap,), np.int32)
        pk[:n], pi[:n], pv[:n] = ck, ci, cv
        bloom = np_bloom_build(_np_prefix(pk, prefix_bits), bloom_hashes,
                               bloom_bits, mask=pi >= 0)
        seg = {"keys": pk, "ids": pi, "vals": pv, "count": n,
               "stamp": int(cs.max()) if n else 0, "bloom": bloom}
        if p is not None:
            pp = np.zeros((cap, p.shape[1]), np.float32)
            pp[:n] = p[lo:lo + cap]
            seg["payload"] = pp
        out.append(seg)
    return out


class ColdManager:
    """Host half of the cold tier, owned by :class:`PFOIndex`.

    Tracks the segment-store layout (cold index -> gid per tier), the
    cache LRU bookkeeping mirroring the device tags, and the cold
    counters surfaced by ``stats()``.  All state mutations happen
    between device rounds on the driver thread; the background
    compaction worker only *computes* fold results from immutable
    segment files, and the driver installs them at a safe point.
    """

    def __init__(self, cfg: PFOConfig, lsh_cfg: PFOConfig,
                 main_cfg: PFOConfig, main_tcfg: TreeConfig,
                 root: str | None = None, on_sync=None,
                 mixed_lsh: bool = False):
        """``mixed_lsh``: the LSH tier is one mixed-table segment chain
        (``val`` == owning table — the distributed per-shard layout,
        driven with ``cfg.L == 1``), so folds dedupe per (id, table)."""
        self.cfg, self.lsh_cfg, self.main_cfg = cfg, lsh_cfg, main_cfg
        self.main_tcfg = main_tcfg
        self.mixed_lsh = mixed_lsh
        self.store = SegmentStore(root)
        self.lsh_gids: list[list[int]] = [[] for _ in range(cfg.L)]
        self.main_gids: list[int] = []
        E = cfg.cold_cache_slots
        self._lsh_tags: list = [None] * E       # (table, cold idx) per slot
        self._main_tags: list = [None] * E
        self._lsh_use = [0] * E
        self._main_use = [0] * E
        self._tick = 0
        self._gen = 0                 # bumps on every cold-layout mutation
        self._futile_gen = -1         # layout gen a fold failed to shrink
        self._on_sync = on_sync or (lambda: None)
        self._worker: threading.Thread | None = None
        self._worker_out: _FoldResult | None = None
        self._lock = threading.Lock()
        from repro.obs import NULL_OBS
        self.obs = NULL_OBS          # rebound by PFOIndex.set_obs
        self.counters = {
            "spills": 0, "fetches": 0, "fetch_rounds": 0,
            "query_rounds": 0, "incomplete_query_rounds": 0,
            "compactions": 0, "cold_merges": 0,
            "lsh_wanted": 0, "lsh_missing": 0, "lsh_probed": 0,
            "lsh_fp": 0, "main_wanted": 0, "main_missing": 0,
            "main_probed": 0, "main_fp": 0,
            "staged_ranked": 0, "ranked_total": 0,
            "vec_fetch_bytes": 0, "vec_evictions": 0,
        }

    # -- observability --------------------------------------------------
    def set_obs(self, obs) -> None:
        """Bind an observability handle (the compaction spans); the
        tier's counters are read through :meth:`stats`."""
        self.obs = obs

    @property
    def n_cold(self) -> int:
        return len(self.main_gids)

    def record_query_round(self, info: np.ndarray) -> None:
        """Accumulate one round's (10,) cold-info vector."""
        self.counters["query_rounds"] += 1
        for j, key in enumerate(("lsh_wanted", "lsh_missing", "lsh_probed",
                                 "lsh_fp", "main_wanted", "main_missing",
                                 "main_probed", "main_fp",
                                 "staged_ranked", "ranked_total")):
            self.counters[key] += int(info[j])

    def stats(self) -> dict:
        c = self.counters
        wanted = c["lsh_wanted"] + c["main_wanted"]
        missing = c["lsh_missing"] + c["main_missing"]
        probed = c["lsh_probed"] + c["main_probed"]
        fp = c["lsh_fp"] + c["main_fp"]
        qr = max(c["query_rounds"], 1)
        return {
            "cold_segments": self.n_cold,
            "segments_spilled": c["spills"],
            "fetches": c["fetches"],
            "fetch_rounds": c["fetch_rounds"],
            "fetches_per_query_round": round(c["fetches"] / qr, 4),
            # rounds answered without all matched cold segments (cache
            # undersized / fetch budget exhausted): should stay 0
            "incomplete_query_rounds": c["incomplete_query_rounds"],
            "cache_hit_rate": round(1.0 - missing / wanted, 4)
            if wanted else 1.0,
            "bloom_probed": probed,
            "bloom_false_positives": fp,
            "bloom_fp_rate": round(fp / probed, 4) if probed else 0.0,
            "compactions": c["compactions"],
            "cold_merges": c["cold_merges"],
            "store_bytes_written": self.store.bytes_written,
            "backing": "files" if self.store.root else "ram",
            # vector payload tiering (the staging arena)
            "staged_ranked": c["staged_ranked"],
            "ranked_total": c["ranked_total"],
            # share of all ranked candidates served from the staging
            # arena rather than the hot store
            "vec_staging_hit_rate": round(
                c["staged_ranked"] / c["ranked_total"], 4)
            if c["ranked_total"] else 0.0,
            "vec_fetch_bytes": c["vec_fetch_bytes"],
            "vec_evictions": c["vec_evictions"],
            "vec_resident_pages": sum(
                1 for t in self._main_tags if t is not None),
        }

    # -- spill ----------------------------------------------------------
    def spill(self, state):
        """One spill epoch: oldest ring segment of every tier -> host."""
        if self.n_cold >= self.cfg.cold_segments:
            # the device scatter at n_cold would be dropped out-of-bounds
            # and the segment's ids would silently vanish from queries —
            # refuse loudly instead (compaction already ran and could
            # not shrink the layout: the tier is genuinely full)
            raise RuntimeError(
                f"cold routing table full ({self.n_cold}/"
                f"{self.cfg.cold_segments} segments) and compaction "
                "cannot shrink it; raise PFOConfig.cold_segments or the "
                "snapshot capacities")
        lsh2, main2, cold2, store2, pl, pm = spill_device(
            state.lsh_snaps, state.main_snaps, state.cold, state.store,
            state.main_forest, state.tombstones,
            self.lsh_cfg, self.main_cfg, self.main_tcfg)
        self._on_sync()
        pl_h, pm_h = jax.device_get((pl, pm))
        for l in range(self.cfg.L):
            gid = self.store.put(pl_h["keys"][l], pl_h["ids"][l],
                                 pl_h["vals"][l], pl_h["count"][l],
                                 pl_h["stamp"][l])
            self.lsh_gids[l].append(gid)
        self.main_gids.append(
            self.store.put(pm_h["keys"], pm_h["ids"], pm_h["vals"],
                           pm_h["count"], pm_h["stamp"],
                           payload=pm_h["payload"]))
        self._gen += 1
        self.counters["spills"] += 1
        return state._replace(lsh_snaps=lsh2, main_snaps=main2,
                              cold=cold2, store=store2)

    def adopt_spill(self, pl_h, pm_h) -> None:
        """Persist one spill epoch's popped segments when the device
        pop already ran elsewhere (the distributed backend's shard-local
        spill program): host bookkeeping only.  ``pl_h`` arrays carry a
        leading table axis (size ``cfg.L``), ``pm_h`` arrays are flat —
        the same layout :meth:`spill` reads back."""
        if self.n_cold >= self.cfg.cold_segments:
            raise RuntimeError(
                f"cold routing table full ({self.n_cold}/"
                f"{self.cfg.cold_segments} segments) and compaction "
                "cannot shrink it; raise PFOConfig.cold_segments or the "
                "snapshot capacities")
        for l in range(self.cfg.L):
            self.lsh_gids[l].append(
                self.store.put(pl_h["keys"][l], pl_h["ids"][l],
                               pl_h["vals"][l], pl_h["count"][l],
                               pl_h["stamp"][l]))
        self.main_gids.append(
            self.store.put(pm_h["keys"], pm_h["ids"], pm_h["vals"],
                           pm_h["count"], pm_h["stamp"],
                           payload=pm_h["payload"]))
        self._gen += 1
        self.counters["spills"] += 1

    # -- fetch ----------------------------------------------------------
    def _pick_slot(self, tags: list, use: list, needed: set) -> int | None:
        """Free slot first, else the LRU slot not needed this round."""
        for e, tag in enumerate(tags):
            if tag is None:
                return e
        cands = [e for e, tag in enumerate(tags) if tag not in needed]
        if not cands:
            return None                        # cache thrash guard
        return min(cands, key=lambda e: use[e])

    def fetch(self, state, wanted_l, missing_l, wanted_m, missing_m):
        """Load Bloom-matched, non-resident segments into the cache.

        wanted/missing are the round's host (numpy bool) masks —
        (L, C) for the LSH tier, (C,) for the MainTable tier.  Issues
        every ``device_put`` before the first install so the transfers
        overlap; evicts LRU slots, never one wanted by this round.
        """
        return state._replace(cold=self.fetch_cold(
            state.cold, wanted_l, missing_l, wanted_m, missing_m))

    def fetch_cold(self, cold: ColdState, wanted_l, missing_l,
                   wanted_m, missing_m) -> ColdState:
        """:meth:`fetch` against a bare (shard-local) cold state — the
        distributed backend slices one shard out of the stacked state,
        fetches, and scatters the result back."""
        self._tick += 1
        # LRU touch for segments this round actually used
        for e, tag in enumerate(self._lsh_tags):
            if tag is not None and wanted_l[tag[0], tag[1]]:
                self._lsh_use[e] = self._tick
        for e, tag in enumerate(self._main_tags):
            if tag is not None and wanted_m[tag[1]]:
                self._main_use[e] = self._tick

        needed_l = {(int(l), int(c)) for l, c in zip(*np.nonzero(wanted_l))}
        needed_m = {(0, int(c)) for c in np.nonzero(wanted_m)[0]}
        plan = []                              # (kind, slot, tag, arrays)
        for l, c in zip(*np.nonzero(missing_l)):
            slot = self._pick_slot(self._lsh_tags, self._lsh_use, needed_l)
            if slot is None:
                break
            gid = self.lsh_gids[int(l)][int(c)]
            k, i, v = self.store.get(gid)
            meta = self.store.meta(gid)
            self._lsh_tags[slot] = (int(l), int(c))
            self._lsh_use[slot] = self._tick
            plan.append(("lsh", slot, (int(l), int(c)), meta["stamp"],
                         jax.device_put(np.ascontiguousarray(k)),
                         jax.device_put(np.ascontiguousarray(i)),
                         jax.device_put(np.ascontiguousarray(v))))
        for c in np.nonzero(missing_m)[0]:
            slot = self._pick_slot(self._main_tags, self._main_use,
                                   needed_m)
            if slot is None:
                break
            gid = self.main_gids[int(c)]
            k, i, v = self.store.get(gid)
            p = self.store.get_payload(gid)
            meta = self.store.meta(gid)
            if self._main_tags[slot] is not None:
                self.counters["vec_evictions"] += 1
            self._main_tags[slot] = (0, int(c))
            self._main_use[slot] = self._tick
            self.counters["vec_fetch_bytes"] += int(p.nbytes)
            plan.append(("main", slot, (0, int(c)), meta["stamp"],
                         jax.device_put(np.ascontiguousarray(k)),
                         jax.device_put(np.ascontiguousarray(i)),
                         jax.device_put(np.ascontiguousarray(v)),
                         jax.device_put(np.ascontiguousarray(p))))
        # transfers are now all in flight; install them
        for kind, slot, tag, stamp, dk, di, dv, *dp in plan:
            cache = cold.lsh_cache if kind == "lsh" else cold.main_cache
            cache = cache_install(cache, jnp.int32(slot), dk, di, dv,
                                  jnp.int32(stamp),
                                  jnp.int32(tag[0] if kind == "lsh" else 0),
                                  jnp.int32(tag[1]),
                                  vecs=dp[0] if dp else None)
            cold = cold._replace(**{("lsh_cache" if kind == "lsh"
                                     else "main_cache"): cache})
            self.counters["fetches"] += 1
        if plan:
            self.counters["fetch_rounds"] += 1
        return cold

    # -- compaction / merge --------------------------------------------
    def _collect(self, gids: list[int], with_payload: bool = False):
        """Concatenate a gid list's entries (keys, ids, vals, stamps
        [, payloads])."""
        ks, is_, vs, ss, ps = [], [], [], [], []
        for gid in gids:
            k, i, v = self.store.get(gid)
            meta = self.store.meta(gid)
            ks.append(np.asarray(k))
            is_.append(np.asarray(i))
            vs.append(np.asarray(v))
            ss.append(np.full(k.shape, meta["stamp"], np.int32))
            if with_payload:
                ps.append(np.asarray(self.store.get_payload(gid)))
        if not ks:
            z = np.zeros((0,), np.int32)
            base = (z.astype(np.uint32), z, z, z)
            return base + (np.zeros((0, self.cfg.dim), np.float32),) \
                if with_payload else base
        base = (np.concatenate(ks), np.concatenate(is_),
                np.concatenate(vs), np.concatenate(ss))
        return base + (np.concatenate(ps),) if with_payload else base

    def _fold_all(self, dead: np.ndarray,
                  ring_extra=None, ring_extra_main=None) -> _FoldResult:
        """Fold cold segments (plus optional drained ring segments) into
        fresh write-once segments.  Reads immutable inputs only."""
        gen = self._gen
        lsh_out, main_out = [], []
        for l in range(self.cfg.L):
            k, i, v, s = self._collect(self.lsh_gids[l])
            if ring_extra is not None:
                rk, ri, rv, rs = ring_extra[l]
                k, i, v, s = (np.concatenate([k, rk]),
                              np.concatenate([i, ri]),
                              np.concatenate([v, rv]),
                              np.concatenate([s, rs]))
            lsh_out.append(_fold_entries(
                k, i, v, s, dead, self.lsh_cfg.snapshot_capacity,
                self.lsh_cfg.snap_prefix_bits,
                self.lsh_cfg.bloom_hashes_eff,
                self.lsh_cfg.bloom_bits_eff,
                group_by_val=self.mixed_lsh))
        k, i, v, s, p = self._collect(self.main_gids, with_payload=True)
        if ring_extra_main is not None:
            rk, ri, rv, rs, rp = ring_extra_main
            k, i, v, s, p = (np.concatenate([k, rk]),
                             np.concatenate([i, ri]),
                             np.concatenate([v, rv]),
                             np.concatenate([s, rs]),
                             np.concatenate([p, rp]))
        main_out = _fold_entries(
            k, i, v, s, dead, self.main_cfg.snapshot_capacity,
            self.main_cfg.snap_prefix_bits,
            self.main_cfg.bloom_hashes_eff, self.main_cfg.bloom_bits_eff,
            payloads=p)
        return _FoldResult(gen, lsh_out, main_out)

    def _install_fold(self, state, fold: _FoldResult,
                      mark_futile: bool = False):
        """Swap the cold layout to a fold result: rewrite the gid lists,
        rebuild the device routing table, flush the cache.
        ``mark_futile``: this was a *shrink* attempt (compaction) — if
        it did not shrink, arm the backoff."""
        routing = self.install_layout(fold, mark_futile=mark_futile)
        return state._replace(cold=self.routed_cold_state(routing))

    def install_layout(self, fold: _FoldResult,
                       mark_futile: bool = False):
        """Host half of the fold install: rewrite the gid lists and
        build the fresh routing arrays.  Returns the numpy routing
        tuple ``(lb, ls, lc, mb, ms, mc, n_cold)`` — the single-chip
        path converts it straight to a device ``ColdState``
        (:meth:`routed_cold_state`); the distributed backend stacks one
        tuple per shard before the device write."""
        cfg = self.cfg
        n_cold = max([len(s) for s in fold.lsh_segments]
                     + [len(fold.main_segments)])
        if n_cold > cfg.cold_segments:
            raise RuntimeError(
                f"cold tier overflow: compaction still needs {n_cold} "
                f"segments but cold_segments={cfg.cold_segments}; raise "
                "PFOConfig.cold_segments (or snapshot capacities)")
        old_n_cold = self.n_cold
        old_gids = [g for row in self.lsh_gids for g in row] + \
            list(self.main_gids)
        Wl = self.lsh_cfg.bloom_bits_eff // 32
        Wm = self.main_cfg.bloom_bits_eff // 32
        C = cfg.cold_segments
        lb = np.zeros((cfg.L, C, Wl), np.uint32)
        ls = np.zeros((cfg.L, C), np.int32)
        lc = np.zeros((cfg.L, C), np.int32)
        mb = np.zeros((C, Wm), np.uint32)
        ms = np.zeros((C,), np.int32)
        mc = np.zeros((C,), np.int32)
        self.lsh_gids = [[] for _ in range(cfg.L)]
        for l, segs in enumerate(fold.lsh_segments):
            for c, seg in enumerate(segs):
                self.lsh_gids[l].append(self.store.put(
                    seg["keys"], seg["ids"], seg["vals"], seg["count"],
                    seg["stamp"]))
                lb[l, c], ls[l, c], lc[l, c] = (seg["bloom"], seg["stamp"],
                                                seg["count"])
            # lockstep padding: empty trailing segments (bloom 0 never hits)
            while len(self.lsh_gids[l]) < n_cold:
                self.lsh_gids[l].append(self._put_empty(self.lsh_cfg))
        self.main_gids = []
        for c, seg in enumerate(fold.main_segments):
            self.main_gids.append(self.store.put(
                seg["keys"], seg["ids"], seg["vals"], seg["count"],
                seg["stamp"], payload=seg["payload"]))
            mb[c], ms[c], mc[c] = seg["bloom"], seg["stamp"], seg["count"]
        while len(self.main_gids) < n_cold:
            self.main_gids.append(self._put_empty(self.main_cfg,
                                                  dim=self.cfg.dim))
        for gid in old_gids:
            self.store.delete(gid)
        self._gen += 1
        if mark_futile and old_n_cold and n_cold >= old_n_cold:
            # the fold did not shrink the layout: re-folding this same
            # generation would just rewrite every segment and flush the
            # cache again — back off until a spill/merge moves it
            self._futile_gen = self._gen
        E = cfg.cold_cache_slots
        self._lsh_tags = [None] * E
        self._main_tags = [None] * E
        return lb, ls, lc, mb, ms, mc, n_cold

    def routed_cold_state(self, routing) -> ColdState:
        """Fresh device cold state for an installed layout (routing
        tables from :meth:`install_layout`, empty caches)."""
        lb, ls, lc, mb, ms, mc, n_cold = routing
        return ColdState(
            lsh_route=ColdRouting(blooms=jnp.asarray(lb),
                                  stamps=jnp.asarray(ls),
                                  counts=jnp.asarray(lc)),
            main_route=ColdRouting(blooms=jnp.asarray(mb),
                                   stamps=jnp.asarray(ms),
                                   counts=jnp.asarray(mc)),
            lsh_cache=_empty_cache(self.cfg,
                                   self.lsh_cfg.snapshot_capacity),
            main_cache=_empty_cache(self.cfg,
                                    self.main_cfg.snapshot_capacity,
                                    dim=self.cfg.dim),
            n_cold=jnp.int32(n_cold))

    def _put_empty(self, tier_cfg: PFOConfig, dim: int | None = None) -> int:
        cap = tier_cfg.snapshot_capacity
        return self.store.put(np.full((cap,), _PAD_KEY, np.uint32),
                              np.full((cap,), -1, np.int32),
                              np.zeros((cap,), np.int32), 0, 0,
                              payload=None if dim is None
                              else np.zeros((cap, dim), np.float32))

    def compact(self, state):
        """Synchronous cold-only compaction (no tombstones, no ring)."""
        self._discard_worker()
        with self.obs.span("compaction", mode="sync"):
            state = self._install_fold(
                state, self._fold_all(np.zeros((0,), np.int32)),
                mark_futile=True)
        self.counters["compactions"] += 1
        return state

    # -- background compaction -----------------------------------------
    def compact_start_async(self) -> bool:
        """Kick the worker if idle; returns whether a fold is running.
        No-ops while the layout generation is one a previous fold
        already failed to shrink (COLD_FULL re-arms every round — the
        backoff stops a futile rewrite-everything loop)."""
        if self._gen == self._futile_gen:
            return False
        if self._worker is not None and self._worker.is_alive():
            return True
        if self._worker_out is not None:
            return True                        # result awaiting install

        def run():
            # worker-thread span: lands on its own track in the trace
            with self.obs.span("compaction", mode="background"):
                out = self._fold_all(np.zeros((0,), np.int32))
            with self._lock:
                self._worker_out = out

        self._worker = threading.Thread(target=run, daemon=True)
        self._worker.start()
        return True

    def compact_maybe_install(self, state):
        """Install a finished background fold if the cold layout has not
        moved since it was computed (else discard — it is stale)."""
        with self._lock:
            out, self._worker_out = self._worker_out, None
        if out is None:
            return state
        if out.gen != self._gen:
            return state                       # raced a spill/merge: drop
        state = self._install_fold(state, out, mark_futile=True)
        self.counters["compactions"] += 1
        return state

    def _discard_worker(self) -> None:
        if self._worker is not None and self._worker.is_alive():
            self._worker.join()
        with self._lock:
            self._worker_out = None

    # -- merge epoch (tombstone drain) ---------------------------------
    def merge_cold(self, state, tombs: np.ndarray):
        """The cold-enabled merge epoch: drain the whole device ring to
        host, fold ring + cold segments with the drained tombstones
        (dead ids physically dropped everywhere sealed), reset the ring.

        Synchronous by design — the device-side tombstone buffer resets
        in the same epoch, so queries can never observe the window
        where a tombstone is gone but its sealed copy still live."""
        with self.obs.span("cold_merge"):
            return self._merge_cold_impl(state, tombs)

    def _merge_cold_impl(self, state, tombs: np.ndarray):
        self._discard_worker()
        # drain the ring's vector payloads device-side (and free the
        # drained entries' store slots) before reading the ring back —
        # the payloads ride the same device_get as the index arrays
        drain_p, drain_cur, store2 = ring_payload_drain(
            state.main_snaps, state.store, state.main_forest,
            jnp.asarray(tombs), self.main_cfg, self.main_tcfg)
        state = state._replace(store=store2)
        self._on_sync()
        ls, ms, ring_pay = jax.device_get(
            (state.lsh_snaps, state.main_snaps, drain_p))
        n_ring = int(np.max(ls.n_snaps))
        ring_l = []
        for l in range(self.cfg.L):
            segs = [(ls.keys[l][s], ls.ids[l][s], ls.vals[l][s],
                     np.full(ls.keys[l][s].shape, ls.stamps[l][s],
                             np.int32)) for s in range(n_ring)]
            ring_l.append(tuple(
                np.concatenate([seg[j] for seg in segs]) if segs
                else np.zeros((0,), np.int32) for j in range(4)))
        n_ring_m = int(ms.n_snaps)
        segs = [(ms.keys[s], ms.ids[s], ms.vals[s],
                 np.full(ms.keys[s].shape, ms.stamps[s], np.int32),
                 ring_pay[s])
                for s in range(n_ring_m)]
        ring_m = tuple(
            np.concatenate([seg[j] for seg in segs]) if segs
            else (np.zeros((0, self.cfg.dim), np.float32) if j == 4
                  else np.zeros((0,), np.int32)) for j in range(5))

        dead = np.asarray(tombs)
        dead = dead[dead >= 0]
        fold = self._fold_all(dead, ring_extra=ring_l,
                              ring_extra_main=ring_m)
        fresh_l = jax.vmap(
            lambda _: snap_mod.init_snapshots(self.lsh_cfg))(
            jnp.arange(self.cfg.L))
        fresh_m = snap_mod.init_snapshots(self.main_cfg)
        state = state._replace(lsh_snaps=fresh_l, main_snaps=fresh_m)
        state = self._install_fold(state, fold)
        self.counters["cold_merges"] += 1
        return state

    # -- checkpoint manifest -------------------------------------------
    def manifest(self) -> dict:
        """JSON-serializable cold layout (segment metadata by tier)."""
        def entry(gid):
            return {"gid": gid, **self.store.meta(gid)}
        return {
            "lsh": [[entry(g) for g in row] for row in self.lsh_gids],
            "main": [entry(g) for g in self.main_gids],
            "counters": dict(self.counters),
        }

    def adopt_manifest(self, man: dict, src_paths: dict) -> None:
        """Rebuild the gid lists from a checkpoint manifest;
        ``src_paths`` maps old gid -> segment file path."""
        self.lsh_gids = []
        for row in man["lsh"]:
            self.lsh_gids.append([
                self.store.import_file(src_paths[e["gid"]], e)
                for e in row])
        self.main_gids = [
            self.store.import_file(src_paths[e["gid"]], e)
            for e in man["main"]]
        self.counters.update(man.get("counters", {}))
        self._gen += 1
