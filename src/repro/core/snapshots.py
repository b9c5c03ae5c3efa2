"""Sealed snapshot tier (paper §3.2.2) — the device-resident sealed ring.

When a hot (HBM-resident) partition fills past its threshold, its live
entries are *sealed* into an immutable snapshot segment: entries are
sorted by compound key (a bucket-major, read-friendly layout — the
paper's Index+Data files), a Bloom filter over the occupied
``snap_prefix_bits``-bit bucket prefixes is attached, and the hot arena
resets.  Queries walk snapshots newest-first, probing all Bloom filters
in one vectorized shot and binary-searching only segments whose filter
matched.  Updates never touch a sealed segment (write-once ==
sequential flash writes); staleness is resolved by (a) newest-first
precedence and (b) periodic *merge compaction* that folds segments
together dropping superseded/deleted ids.

This ring is the *staging* level of the hierarchy, not the paper's
flash level: it is a fixed-capacity stacked pytree in device memory so
the probe path is a single jitted program over (S, cap) arrays.  The
actual flash analogue is ``core.coldtier`` — when the ring fills (and
``PFOConfig.cold_segments > 0``) the oldest segment spills verbatim to
a host-resident segment store while its Bloom filter stays
device-resident for routing; :func:`pop_oldest` implements the
device half of that spill.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import bloom as bloom_mod
from .config import PFOConfig
from .membership import member_sorted


class SnapshotSet(NamedTuple):
    keys: jax.Array     # u32 (S, cap) sorted per segment; pad = 0xFFFFFFFF
    ids: jax.Array      # i32 (S, cap) vector ids; -1 pad
    vals: jax.Array     # i32 (S, cap) payloads
    counts: jax.Array   # i32 (S,) live entries per segment
    blooms: jax.Array   # u32 (S, W) packed filters
    n_snaps: jax.Array  # i32 () segments in use (newest == n_snaps-1)
    stamps: jax.Array   # i32 (S,) seal sequence number S_ij's j


_PAD_KEY = jnp.uint32(0xFFFFFFFF)


def init_snapshots(cfg: PFOConfig) -> SnapshotSet:
    S, cap = cfg.max_snapshots, cfg.snapshot_capacity
    return SnapshotSet(
        keys=jnp.full((S, cap), _PAD_KEY, jnp.uint32),
        ids=jnp.full((S, cap), -1, jnp.int32),
        vals=jnp.zeros((S, cap), jnp.int32),
        counts=jnp.zeros((S,), jnp.int32),
        blooms=jnp.zeros((S, cfg.bloom_bits_eff // 32), jnp.uint32),
        n_snaps=jnp.int32(0),
        stamps=jnp.zeros((S,), jnp.int32),
    )


def _prefix(keys: jax.Array, bits: int) -> jax.Array:
    return keys.astype(jnp.uint32) >> jnp.uint32(32 - bits)


def probe_prefixes(hs: jax.Array, cfg: PFOConfig) -> jax.Array:
    """Multi-probe bucket prefixes for query keys: (N,) -> (N, P) uint32.

    Column 0 is the landing prefix; columns 1..P-1 are its xor-adjacent
    neighbors (nearest key-distance first — the same ordering
    ``sibling_probe`` uses inside a directory node).  Fixed trip count:
    the probe shape is static in ``snap_probes``, so vmapped rows stay
    in lockstep and P == 1 reduces to the paper's single-bucket probe.
    """
    pfx = _prefix(hs, cfg.snap_prefix_bits)                      # (N,)
    return pfx[:, None] ^ jnp.arange(cfg.snap_probes, dtype=jnp.uint32)


@jax.named_scope("reseal")
def seal(snaps: SnapshotSet, keys: jax.Array, ids: jax.Array,
         vals: jax.Array, mask: jax.Array, stamp: jax.Array,
         cfg: PFOConfig) -> SnapshotSet:
    """Seal live hot-tier entries into the next segment.

    keys/ids/vals: flat (N,) arrays with ``mask`` marking live rows;
    N must be <= snapshot_capacity.  Sorting by key produces the
    bucket-major read-friendly layout; the Bloom filter is built on the
    occupied bucket prefixes (paper: "the indices of all non-empty
    buckets as the keys of Bloom Filters").
    """
    cap = cfg.snapshot_capacity
    n = keys.shape[0]
    assert n <= cap, f"seal batch {n} exceeds snapshot capacity {cap}"
    sort_key = jnp.where(mask, keys.astype(jnp.uint32), _PAD_KEY)
    order = jnp.argsort(sort_key)
    skeys = sort_key[order]
    sids = jnp.where(mask[order], ids[order], -1)
    svals = vals[order]
    count = jnp.sum(mask.astype(jnp.int32))

    pad = cap - n
    skeys = jnp.concatenate([skeys, jnp.full((pad,), _PAD_KEY, jnp.uint32)])
    sids = jnp.concatenate([sids, jnp.full((pad,), -1, jnp.int32)])
    svals = jnp.concatenate([svals, jnp.zeros((pad,), jnp.int32)])

    filt = bloom_mod.build(_prefix(skeys, cfg.snap_prefix_bits),
                           cfg.bloom_hashes_eff, cfg.bloom_bits_eff,
                           mask=sids >= 0)

    s = snaps.n_snaps
    return snaps._replace(
        keys=snaps.keys.at[s].set(skeys),
        ids=snaps.ids.at[s].set(sids),
        vals=snaps.vals.at[s].set(svals),
        counts=snaps.counts.at[s].set(count),
        blooms=snaps.blooms.at[s].set(filt),
        stamps=snaps.stamps.at[s].set(stamp),
        n_snaps=s + 1,
    )


def span_gather(keys_s: jax.Array, ids_s: jax.Array, vals_s: jax.Array,
                act_s: jax.Array, pfx: jax.Array, cfg: PFOConfig):
    """Gather one segment's bucket spans for flat probe prefixes.

    keys_s/ids_s/vals_s: one segment's (cap,) arrays (sorted keys);
    act_s/pfx: (M,) probe activity mask and bucket prefixes.  Returns
    (cids, cvals, cpos, matched): (M, budget) candidate ids/vals/entry
    positions (-1 pad) and an (M,) bool marking probes whose span was
    non-empty (a *real* bucket hit — used by the cold tier's Bloom
    false-positive accounting).  ``cpos`` is each candidate's row index
    within the segment — the cold tier uses it to address the matching
    vector payload row in its device staging arena.
    """
    cap = keys_s.shape[0]
    budget = cfg.snap_budget_per_probe
    shift = jnp.uint32(32 - cfg.snap_prefix_bits)
    lo_key = (pfx << shift)
    hi_key = lo_key + (jnp.uint32(1) << shift)
    lo = jnp.searchsorted(keys_s, lo_key)                        # (M,)
    # the all-ones prefix's upper bound wraps to 0 in uint32 — its span
    # runs to the end of the segment instead (pad rows there carry
    # id == -1, so they mask out of the gathered window naturally)
    max_pfx = jnp.uint32((1 << cfg.snap_prefix_bits) - 1)
    hi = jnp.where(pfx == max_pfx, cap,
                   jnp.searchsorted(keys_s, hi_key))
    span = jnp.arange(budget)
    pos = lo[:, None] + span[None, :]                            # (M, B)
    ok = (pos < hi[:, None]) & act_s[:, None] & (pos < cap)
    safe = jnp.where(ok, pos, 0)
    cids = jnp.where(ok, ids_s[safe], -1)
    cvals = jnp.where(ok, vals_s[safe], -1)
    cpos = jnp.where(ok, pos, -1)
    return cids, cvals, cpos, act_s & (hi > lo)


def probe(snaps: SnapshotSet, hs: jax.Array, cfg: PFOConfig):
    """Search every segment for bucket-prefix matches of query keys.

    hs: (N,) uint32 query compound keys; each contributes
    ``snap_probes`` xor-adjacent bucket prefixes (fixed-trip masked
    multi-probe — P == 1 is the paper's single-bucket probe).
    Returns (ids, vals): (N, S * P * budget) candidate ids (-1 pad),
    ordered newest-segment-first per query (paper: reversed time
    order), landing probe first within a segment.
    """
    S, cap = snaps.keys.shape
    n, P = hs.shape[0], cfg.snap_probes
    pfx = probe_prefixes(hs, cfg).reshape(-1)                    # (N*P,)

    # One vectorized Bloom pass across all segments (paper's batching).
    hit = bloom_mod.contains_multi(snaps.blooms, pfx,
                                   cfg.bloom_hashes_eff)         # (S, N*P)
    active = (jnp.arange(S)[:, None] < snaps.n_snaps) & hit

    cids, cvals, _, _ = jax.vmap(
        lambda k, i, v, a: span_gather(k, i, v, a, pfx, cfg))(
        snaps.keys, snaps.ids, snaps.vals, active)               # (S, N*P, B)
    # newest-first ordering along the segment axis
    rev = jnp.arange(S - 1, -1, -1)

    def flat(c):                                                 # -> (N, S*P*B)
        c = jnp.transpose(c[rev], (1, 0, 2)).reshape(n, P, S, -1)
        return jnp.transpose(c, (0, 2, 1, 3)).reshape(n, -1)

    return flat(cids), flat(cvals)


def pop_oldest(snaps: SnapshotSet, cfg: PFOConfig):
    """Pop the ring's oldest segment (index 0 — stamps are nondecreasing
    with index: seal appends, merge folds to one oldest-stamp-max slot,
    and spill always removes index 0).  Returns (shifted_set, popped)
    where ``popped`` is a dict of the evicted segment's arrays — the
    device half of a cold-tier spill (the host persists keys/ids/vals;
    the Bloom/stamp/count move into the cold routing table).

    Caller must ensure ``n_snaps > 0`` (flag-gated in ``index.py``).
    """
    popped = {
        "keys": snaps.keys[0], "ids": snaps.ids[0], "vals": snaps.vals[0],
        "count": snaps.counts[0], "bloom": snaps.blooms[0],
        "stamp": snaps.stamps[0],
    }

    def shift(a, fill):
        return jnp.roll(a, -1, axis=0).at[-1].set(fill)

    shifted = SnapshotSet(
        keys=shift(snaps.keys, _PAD_KEY),
        ids=shift(snaps.ids, -1),
        vals=shift(snaps.vals, 0),
        counts=shift(snaps.counts, 0),
        blooms=shift(snaps.blooms, 0),
        stamps=shift(snaps.stamps, 0),
        n_snaps=jnp.maximum(snaps.n_snaps - 1, 0),
    )
    return shifted, popped


@jax.named_scope("ring_lookup")
def lookup_exact(snaps: SnapshotSet, h: jax.Array, vid: jax.Array):
    """Exact (key, id) lookup over the live segments, newest first
    (MainTable path): scalars or (N,) arrays -> (val, found), val -1
    where not found.

    Precondition: a key is a bijection of its id, as
    ``lsh.main_table_keys`` gives (``murmur3_fmix32(id)``).  A segment
    is sorted by key, so an id can only sit at the first entry whose
    key equals its own: one ``searchsorted`` of the full key per
    segment finds it, and the id comparison confirms it.  The LSH
    tier's prefix-bucket :func:`probe` is not needed here, and its
    fixed window would miss an id past the ``snap_budget_per_probe``-th
    entry of its bucket.  The loop runs ``n_snaps`` trips on the
    device; a hit in a newer segment takes precedence, and segments at
    index ``>= n_snaps`` are never read.
    """
    last = snaps.keys.shape[1] - 1

    def segment(j, carry):
        val, found = carry
        s = snaps.n_snaps - 1 - j
        pos = jnp.minimum(jnp.searchsorted(snaps.keys[s], h), last)
        hit = (~found & (vid >= 0) & (snaps.keys[s, pos] == h)
               & (snaps.ids[s, pos] == vid))
        return jnp.where(hit, snaps.vals[s, pos], val), found | hit

    init = (jnp.full(h.shape, -1, jnp.int32), jnp.zeros(h.shape, bool))
    return jax.lax.fori_loop(0, snaps.n_snaps, segment, init)


def merge(snaps: SnapshotSet, cfg: PFOConfig,
          deleted_ids: jax.Array | None = None,
          group_by_val: bool = False) -> SnapshotSet:
    """Merge compaction (paper's periodic maintenance): fold all segments
    into one, newest version of each (key_prefix, id) wins, deleted ids
    dropped.  Returns a fresh set with a single segment.

    ``group_by_val`` dedupes by (val, id) instead of id alone — the
    distributed tier seals all of a chip's trees into ONE mixed segment
    set with the LSH table id stored in ``vals``, and an id must
    survive once per table there, not once overall.  Tombstones still
    match by raw id.
    """
    S, cap = snaps.keys.shape
    seg_rank = jnp.broadcast_to(snaps.stamps[:, None], (S, cap))
    keys = snaps.keys.reshape(-1)
    ids = snaps.ids.reshape(-1)
    vals = snaps.vals.reshape(-1)
    rank = seg_rank.reshape(-1)
    with jax.named_scope("merge_filter"):
        live = ids >= 0
        if deleted_ids is not None and deleted_ids.shape[0] > 0:
            dead = member_sorted(ids, deleted_ids)
            live = live & ~dead

    with jax.named_scope("merge_sort"):
        # newest (highest stamp) version of an id wins
        ikey = jnp.where(live, ids, jnp.int32(2**31 - 1))
        gkey = jnp.where(live, vals, 0) if group_by_val \
            else jnp.zeros_like(ids)
        order = jnp.lexsort((-rank, ikey, gkey))
        sids = jnp.where(live[order], ids[order], -1)
        sgrp = gkey[order]
        first_of_id = jnp.concatenate(
            [jnp.array([True]),
             (sids[1:] != sids[:-1]) | (sgrp[1:] != sgrp[:-1])]) \
            & (sids >= 0)

        keep_keys = jnp.where(first_of_id, keys[order], _PAD_KEY)
        keep_ids = jnp.where(first_of_id, sids, -1)
        keep_vals = jnp.where(first_of_id, vals[order], 0)

        merged = init_snapshots(cfg)
        take = min(cap, keep_keys.shape[0])
        # Keep at most one segment's worth (overflow counted for
        # observability).
        korder = jnp.argsort(jnp.where(keep_ids >= 0, jnp.uint32(0),
                                       jnp.uint32(1)))
        keep_keys, keep_ids, keep_vals = (keep_keys[korder][:take],
                                          keep_ids[korder][:take],
                                          keep_vals[korder][:take])
    return seal(merged, keep_keys, keep_ids, keep_vals, keep_ids >= 0,
                jnp.max(snaps.stamps), cfg)
