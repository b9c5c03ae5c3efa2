"""Adaptive hash tree (paper §5.1), array-encoded for SPMD execution.

The paper's tree is a pointer structure in manually-managed off-heap
memory: non-leaf (directory) nodes are integer arrays of length ``l``
whose slots hold offsets of either a leaf chain or a child node; leaves
are (KEY, VALUE, NEXT) records.  Inserts consume ``log2(l)`` key bits
per level, chain into a slot, and when more than ``t`` leaves share a
slot they are *spread* one level down — a strictly local rewrite, never
a B-Tree-style upward rebalance (reconstruction-free, §5).

TPU adaptation: the off-heap segments become pre-allocated int32/uint32
arrays (structure-of-arrays) and offsets become indices; traversal is a
``lax.while_loop`` over gathers, and the single-writer actor discipline
becomes *sequential application within a tree* (``lax.scan``) combined
with *parallelism across trees* (``vmap`` / ``shard_map``) — see
``dispatch.py``.

Slot encoding (int32):
    0   -> empty
    v>0 -> head of leaf chain at leaf index v-1
    v<0 -> child directory node at node index -v-1

Leaf ``next`` uses the same "v>0 == leaf v-1, 0 == end" encoding, and
doubles as the free-list link for reclaimed leaves (paper §3.2.1's
RECLAIMED_LIST, single size class here — the size-classed variant lives
in ``store.py`` where records really are variable-sized).

Static-trip / masking discipline (read path)
--------------------------------------------
Two traversal modes exist for the read path (``TreeConfig.traversal``):

``"loop"``
    The original data-dependent ``lax.while_loop`` walks.  Correct, but
    under ``vmap`` every query row is locked to the *slowest* chain
    walk in the batch and each trip re-evaluates the convergence
    predicate — per-row query cost grows with batch size.

``"masked"`` (default)
    Fixed trip counts everywhere: the directory descent unrolls to the
    static ``max_depth`` bound (a descent can never legally be deeper —
    spreads require ``depth + 1 < max_depth``), and chain walks become
    a static ``max_chain``-step ``lax.scan`` that gathers the chain's
    leaf indices densely and masks exhausted positions instead of
    branching.  Every vmapped row executes the identical instruction
    stream, so XLA emits plain batched gathers and large query batches
    amortize instead of penalize.  ``max_chain`` bounds the walk: with
    ``max_chain >= max_candidates`` (the default via
    ``PFOConfig.max_chain = 0``) a chain can never contribute more
    leaves than the loop path could collect before its cumulative
    ``max_candidates`` cutoff, so both *query* modes return
    bit-identical results (asserted differentially in
    tests/test_traversal_equiv.py).  The exact-id *lookup* path has no
    cumulative cutoff in the legacy walk, so there its equivalence
    holds only while bucket chains stay within ``max_chain`` — a
    chain can exceed it only when more than ``max_chain`` records
    share every key bit the tree can consume, which for the MainTable
    (distinct ids -> distinct fmix32 keys, a bijection) requires that
    many ids colliding on the full consumed prefix: adversarial-only,
    and the bounded-bucket spread discipline (§5.1) assumes it away.

The write path applies each tree's mailbox sequentially (the actor
mailbox scan).  The single-tree ``tree_insert`` / ``tree_delete`` keep
their while_loops; the forest insert that the index runs
(``forest_insert_dispatched``) advances every tree one request per
scan step with the same fixed-trip, flat-indexed discipline as the
read path, because a vmap of ``tree_insert`` turns each of its conds
and loops into a select over whole arenas — every step rewrote the
forest (~56 ms per step, measured on a TPU v5e with 2,560 trees).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .lsh import key_bits


class TreeConfig(NamedTuple):
    """Static traversal parameters (hashable; safe as a jit static arg)."""
    skip_bits: int      # bits consumed before the tree (m for LSHTables)
    log2_l: int         # bits per level
    l: int              # slots per directory node
    t: int              # spread threshold
    max_depth: int      # directory levels available
    max_nodes: int
    max_leaves: int
    max_candidates: int  # leaves returned per probe
    # beyond-paper (EXPERIMENTS.md §Paper-figures): when the landing
    # bucket holds fewer than max_candidates leaves, also harvest the
    # landing node's sibling slots in Gray-adjacent order — a
    # multi-probe pass confined to one directory node.
    sibling_probe: bool = False
    # read-path traversal mode: "masked" (fixed-trip, lockstep-friendly)
    # or "loop" (legacy while_loop walks) — see the module docstring.
    traversal: str = "masked"
    # static chain-gather bound for the masked mode; 0 == max_candidates
    # (the bit-identical-equivalence default).
    max_chain: int = 0

    @property
    def max_chain_eff(self) -> int:
        return self.max_chain or self.max_candidates


class TreeState(NamedTuple):
    """One hash tree's arena. vmap a leading axis for a forest."""
    slots: jax.Array      # i32 (max_nodes, l)
    leaf_key: jax.Array   # u32 (max_leaves,)
    leaf_id: jax.Array    # i32 (max_leaves,)  vector id; -1 == invalid
    leaf_val: jax.Array   # i32 (max_leaves,)  payload (store slot / id)
    leaf_next: jax.Array  # i32 (max_leaves,)
    node_cnt: jax.Array   # i32 () allocated directory nodes (>=1: root)
    leaf_cnt: jax.Array   # i32 () bump cursor
    free_head: jax.Array  # i32 () leaf free-list head (slot encoding)
    n_items: jax.Array    # i32 () live leaves
    overflow: jax.Array   # i32 () arena-exhaustion events (observability)


def init_tree(cfg: TreeConfig) -> TreeState:
    return TreeState(
        slots=jnp.zeros((cfg.max_nodes, cfg.l), jnp.int32),
        leaf_key=jnp.zeros((cfg.max_leaves,), jnp.uint32),
        leaf_id=jnp.full((cfg.max_leaves,), -1, jnp.int32),
        leaf_val=jnp.zeros((cfg.max_leaves,), jnp.int32),
        leaf_next=jnp.zeros((cfg.max_leaves,), jnp.int32),
        node_cnt=jnp.int32(1),
        leaf_cnt=jnp.int32(0),
        free_head=jnp.int32(0),
        n_items=jnp.int32(0),
        overflow=jnp.int32(0),
    )


def init_forest(cfg: TreeConfig, n_trees: int) -> TreeState:
    """Stacked arenas: every field gains a leading (n_trees,) axis."""
    one = init_tree(cfg)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_trees, *x.shape)).copy(), one)


# ----------------------------------------------------------------------
# traversal
# ----------------------------------------------------------------------
def _descend(st: TreeState, h: jax.Array, cfg: TreeConfig):
    """Walk directory nodes until the slot holds a leaf chain or is empty.

    Returns (node, depth, slot_idx, slot_val).
    """
    def cond(c):
        _, _, _, v = c
        return v < 0

    def body(c):
        node, depth, _, v = c
        node = -v - 1
        depth = depth + 1
        sl = key_bits(h, cfg.skip_bits + depth * cfg.log2_l, cfg.log2_l)
        return node, depth, sl, st.slots[node, sl]

    sl0 = key_bits(h, cfg.skip_bits, cfg.log2_l)
    init = (jnp.int32(0), jnp.int32(0), sl0, st.slots[0, sl0])
    return jax.lax.while_loop(cond, body, init)


def _chain_len(st: TreeState, head: jax.Array, cap: jax.Array) -> jax.Array:
    """Length of a leaf chain, counting at most ``cap`` (enough for >t test)."""
    def cond(c):
        cur, n = c
        return (cur > 0) & (n < cap)

    def body(c):
        cur, n = c
        return st.leaf_next[cur - 1], n + 1

    _, n = jax.lax.while_loop(cond, body, (head, jnp.int32(0)))
    return n


# ----------------------------------------------------------------------
# fixed-trip (masked) traversal — see module docstring
# ----------------------------------------------------------------------
def _descend_masked(st: TreeState, h: jax.Array, cfg: TreeConfig):
    """Fixed-trip directory descent: exactly ``max_depth - 1`` steps.

    Same contract as ``_descend`` — returns (node, depth, slot_idx,
    slot_val) — but every step executes unconditionally and a step that
    has already landed (slot_val >= 0) just carries its state forward,
    so vmapped rows stay in lockstep.  A descent can never legally need
    more steps: spreads require ``depth + 1 < max_depth``.
    """
    sl = key_bits(h, cfg.skip_bits, cfg.log2_l)
    node = jnp.int32(0)
    depth = jnp.int32(0)
    v = st.slots[0, sl]
    for d in range(1, cfg.max_depth):
        go = v < 0
        node = jnp.where(go, -v - 1, node)
        sl = jnp.where(go, key_bits(h, cfg.skip_bits + d * cfg.log2_l,
                                    cfg.log2_l), sl)
        depth = depth + go.astype(jnp.int32)
        v = jnp.where(go, st.slots[node, sl], v)
    return node, depth, sl, v


def _chain_slots_masked(st: TreeState, head: jax.Array,
                        max_chain: int) -> jax.Array:
    """Gather a leaf chain's indices densely: (max_chain,) i32, -1 pad.

    A static-length ``lax.scan`` over the ``leaf_next`` links — the
    fixed-trip replacement for the chain while_loops.  Position ``j``
    holds the chain's j-th leaf index (newest first, since inserts
    prepend) or -1 once the chain is exhausted.
    """
    def step(cur, _):
        alive = cur > 0
        leaf = jnp.where(alive, cur - 1, 0)
        out = jnp.where(alive, leaf, -1)
        nxt = jnp.where(alive, st.leaf_next[leaf], 0)
        return nxt, out

    _, idxs = jax.lax.scan(step, head, None, length=max_chain)
    return idxs


def _compact_candidates(st: TreeState, leaf_idx: jax.Array, cap: int):
    """Masked stable compaction: dense leaf indices -> (ids, vals, n).

    ``leaf_idx`` is a flat, order-significant block of leaf indices
    (-1 == invalid).  Valid entries keep their relative order and are
    packed to the front of a ``cap``-sized output; entries past ``cap``
    are dropped — exactly the loop path's cumulative truncation.
    """
    valid = leaf_idx >= 0
    safe = jnp.maximum(leaf_idx, 0)
    ids_all = jnp.where(valid, st.leaf_id[safe], -1)
    vals_all = jnp.where(valid, st.leaf_val[safe], -1)
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    tgt = jnp.where(valid, pos, cap)         # invalid / overflow -> dropped
    ids = jnp.full((cap,), -1, jnp.int32).at[tgt].set(ids_all, mode="drop")
    vals = jnp.full((cap,), -1, jnp.int32).at[tgt].set(vals_all, mode="drop")
    n = jnp.minimum(jnp.sum(valid.astype(jnp.int32)), cap)
    return ids, vals, n


def tree_query_masked(st: TreeState, h: jax.Array, cfg: TreeConfig):
    """Fixed-trip probe: (ids, vals, count) — identical to the loop path.

    Gathers the landing bucket's chain (and, under ``sibling_probe``,
    every sibling slot's chain in xor order) as one dense
    ``[n_slots, max_chain]`` candidate block, then compacts the valid
    entries in order.
    """
    node, _, sl, v = _descend_masked(st, h, cfg)
    mc = cfg.max_chain_eff

    if cfg.sibling_probe:
        sls = sl ^ jnp.arange(cfg.l, dtype=jnp.int32)    # j=0 == landing
        vs = st.slots[node, sls]
        heads = jnp.where(vs > 0, vs, 0)
        flat = jax.vmap(
            lambda hd: _chain_slots_masked(st, hd, mc))(heads).reshape(-1)
    else:
        flat = _chain_slots_masked(st, jnp.where(v > 0, v, 0), mc)
    return _compact_candidates(st, flat, cfg.max_candidates)


def tree_lookup_masked(st: TreeState, h: jax.Array, vid: jax.Array,
                       cfg: TreeConfig):
    """Fixed-trip exact-id lookup; newest (first) match wins.

    Scans the first ``max_chain_eff`` chain entries (newest-first) —
    records buried deeper are missed; see the module docstring for why
    that depth is adversarial-only under the spread discipline.
    """
    _, _, _, v = _descend_masked(st, h, cfg)
    flat = _chain_slots_masked(st, jnp.where(v > 0, v, 0),
                               cfg.max_chain_eff)
    valid = flat >= 0
    safe = jnp.maximum(flat, 0)
    hit = valid & (st.leaf_id[safe] == vid)
    found = jnp.any(hit)
    first = jnp.argmax(hit)                  # first True == newest version
    val = jnp.where(found, st.leaf_val[safe[first]], -1)
    return val, found


# ----------------------------------------------------------------------
# forest-level masked traversal (flat batched indexing)
#
# The vmap-over-trees wrappers below slice one tree's whole arena per
# row (``jax.tree.map(lambda a: a[tid], forest)``).  Under vmap that
# slice lowers to a gather, and XLA cannot fuse a gather whose operand
# is itself a gather's output — the per-row arena copies materialize,
# and the read path's memory traffic grows with the probe count.  The
# masked traversal needs no per-tree view: every step is a plain
# batched gather ``array[tree_id, idx]`` into the *stacked* arenas, so
# these flat implementations index the forest directly and touch only
# the elements they read.
# ----------------------------------------------------------------------
def _forest_descend_masked(forest: TreeState, tids: jax.Array,
                           hs: jax.Array, cfg: TreeConfig):
    """Batched fixed-trip descent: tids/hs (N,) -> (node, depth, sl, v)
    (N,) — :func:`_descend_masked` row for row."""
    sl = key_bits(hs, cfg.skip_bits, cfg.log2_l)
    node = jnp.zeros_like(tids)
    depth = jnp.zeros_like(tids)
    v = forest.slots[tids, node, sl]
    for d in range(1, cfg.max_depth):
        go = v < 0
        node = jnp.where(go, -v - 1, node)
        sl = jnp.where(go, key_bits(hs, cfg.skip_bits + d * cfg.log2_l,
                                    cfg.log2_l), sl)
        depth = depth + go.astype(jnp.int32)
        v = jnp.where(go, forest.slots[tids, node, sl], v)
    return node, depth, sl, v


def _forest_chain_slots(forest: TreeState, tids: jax.Array,
                        heads: jax.Array, max_chain: int) -> jax.Array:
    """Batched chain gather: heads (...,) -> leaf indices (..., max_chain),
    -1 pad.  ``tids`` broadcasts against ``heads``."""
    tids = jnp.broadcast_to(tids, heads.shape)

    def step(cur, _):
        alive = cur > 0
        leaf = jnp.where(alive, cur - 1, 0)
        out = jnp.where(alive, leaf, -1)
        nxt = jnp.where(alive, forest.leaf_next[tids, leaf], 0)
        return nxt, out

    _, idxs = jax.lax.scan(step, heads, None, length=max_chain)
    return jnp.moveaxis(idxs, 0, -1)


def forest_query_masked(forest: TreeState, tids: jax.Array, hs: jax.Array,
                        cfg: TreeConfig):
    """Batched fixed-trip probes: (N,) -> ids/vals (N, max_candidates), n
    (N,).  Row-for-row identical to vmapping the single-tree query."""
    n = tids.shape[0]
    node, _, sl, v = _forest_descend_masked(forest, tids, hs, cfg)
    mc = cfg.max_chain_eff
    if cfg.sibling_probe:
        sls = sl[:, None] ^ jnp.arange(cfg.l, dtype=jnp.int32)[None, :]
        vs = forest.slots[tids[:, None], node[:, None], sls]     # (N, l)
        heads = jnp.where(vs > 0, vs, 0)
        chains = _forest_chain_slots(forest, tids[:, None], heads, mc)
        flat = chains.reshape(n, -1)                     # (N, l*mc)
        flat_tids = jnp.repeat(tids[:, None], cfg.l * mc, axis=1)
    else:
        heads = jnp.where(v > 0, v, 0)
        flat = _forest_chain_slots(forest, tids, heads, mc)      # (N, mc)
        flat_tids = jnp.broadcast_to(tids[:, None], flat.shape)

    valid = flat >= 0
    safe = jnp.maximum(flat, 0)
    ids_all = jnp.where(valid, forest.leaf_id[flat_tids, safe], -1)
    vals_all = jnp.where(valid, forest.leaf_val[flat_tids, safe], -1)

    cap = cfg.max_candidates
    pos = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    tgt = jnp.where(valid, pos, cap)
    rows = jnp.arange(n)[:, None]
    ids = jnp.full((n, cap), -1, jnp.int32).at[rows, tgt].set(
        ids_all, mode="drop")
    vals = jnp.full((n, cap), -1, jnp.int32).at[rows, tgt].set(
        vals_all, mode="drop")
    cnt = jnp.minimum(jnp.sum(valid.astype(jnp.int32), axis=1), cap)
    return ids, vals, cnt


def forest_lookup_masked(forest: TreeState, tids: jax.Array, hs: jax.Array,
                         vids: jax.Array, cfg: TreeConfig):
    """Batched fixed-trip exact-id lookup: (N,) -> (val, found) (N,)."""
    _, _, _, v = _forest_descend_masked(forest, tids, hs, cfg)
    heads = jnp.where(v > 0, v, 0)
    flat = _forest_chain_slots(forest, tids, heads, cfg.max_chain_eff)
    valid = flat >= 0
    safe = jnp.maximum(flat, 0)
    flat_tids = jnp.broadcast_to(tids[:, None], flat.shape)
    hit = valid & (forest.leaf_id[flat_tids, safe] == vids[:, None])
    found = jnp.any(hit, axis=1)
    first = jnp.argmax(hit, axis=1)          # first True == newest version
    leaf = jnp.take_along_axis(safe, first[:, None], axis=1)[:, 0]
    val = jnp.where(found, forest.leaf_val[tids, leaf], -1)
    return val, found


def _alloc_leaf(st: TreeState):
    """Pop the free list, else bump the cursor. Returns (state, idx, ok)."""
    use_free = st.free_head > 0
    free_idx = st.free_head - 1
    bump_ok = st.leaf_cnt < st.leaf_key.shape[0]
    idx = jnp.where(use_free, free_idx, st.leaf_cnt)
    ok = use_free | bump_ok
    new_free = jnp.where(use_free, st.leaf_next[free_idx], st.free_head)
    new_cnt = jnp.where(use_free | ~bump_ok, st.leaf_cnt, st.leaf_cnt + 1)
    st = st._replace(free_head=jnp.where(ok, new_free, st.free_head),
                     leaf_cnt=new_cnt)
    return st, jnp.where(ok, idx, 0), ok


# ----------------------------------------------------------------------
# insert (paper §5.1 steps 1-4)
# ----------------------------------------------------------------------
def tree_insert(st: TreeState, h: jax.Array, vid: jax.Array,
                val: jax.Array, cfg: TreeConfig) -> TreeState:
    """Insert one (key, id, value) record; spreads the bucket if > t."""
    node, depth, sl, v = _descend(st, h, cfg)

    st, new_leaf, ok = _alloc_leaf(st)

    # Step 2/3: prepend to the chain (v >= 0 here: empty or chain head).
    st2 = st._replace(
        leaf_key=st.leaf_key.at[new_leaf].set(h.astype(jnp.uint32)),
        leaf_id=st.leaf_id.at[new_leaf].set(vid),
        leaf_val=st.leaf_val.at[new_leaf].set(val),
        leaf_next=st.leaf_next.at[new_leaf].set(v),
        n_items=st.n_items + 1,
    )
    st2 = st2._replace(slots=st2.slots.at[node, sl].set(new_leaf + 1))

    # Step 4: spread the bucket to the next level when it exceeds t and
    # unconsumed key bits remain and a directory node can be allocated.
    head = new_leaf + 1
    clen = _chain_len(st2, head, jnp.int32(cfg.t + 1))
    can_deepen = depth + 1 < cfg.max_depth
    can_alloc = st2.node_cnt < cfg.max_nodes
    do_split = (clen > cfg.t) & can_deepen & can_alloc

    def split(s: TreeState) -> TreeState:
        nn = s.node_cnt                       # new directory node index
        s = s._replace(node_cnt=s.node_cnt + 1)

        def body(c):
            s, cur = c
            leaf = cur - 1
            nxt = s.leaf_next[leaf]
            child_sl = key_bits(s.leaf_key[leaf],
                                cfg.skip_bits + (depth + 1) * cfg.log2_l,
                                cfg.log2_l)
            s = s._replace(
                leaf_next=s.leaf_next.at[leaf].set(s.slots[nn, child_sl]),
                slots=s.slots.at[nn, child_sl].set(cur),
            )
            return s, nxt

        s, _ = jax.lax.while_loop(lambda c: c[1] > 0, body, (s, head))
        return s._replace(slots=s.slots.at[node, sl].set(-(nn + 1)))

    st2 = jax.lax.cond(do_split, split, lambda s: s, st2)

    # Arena exhaustion: drop the record, count the overflow (the host
    # seals the partition into a snapshot and retries — see index.py).
    out = jax.tree.map(lambda a, b: jnp.where(ok, a, b), st2,
                       st._replace(overflow=st.overflow + 1,
                                   n_items=st.n_items))
    return out


# ----------------------------------------------------------------------
# query (paper: same walk; returns the resident leaf chain as A(q))
# ----------------------------------------------------------------------
def tree_query_loop(st: TreeState, h: jax.Array, cfg: TreeConfig):
    """Legacy while_loop probe: (ids, vals, count) — padded with -1.

    Lands on the bucket addressed by successive log2(l)-bit digits of
    ``h`` and returns its leaf chain (the paper's A(q) contribution from
    this tree).  Kept for differential testing against the masked path
    (``TreeConfig.traversal``).
    """
    node, _, sl, v = _descend(st, h, cfg)

    ids = jnp.full((cfg.max_candidates,), -1, jnp.int32)
    vals = jnp.full((cfg.max_candidates,), -1, jnp.int32)

    def chain_body(c):
        ids, vals, cur, n = c
        leaf = cur - 1
        ids = ids.at[n].set(st.leaf_id[leaf])
        vals = vals.at[n].set(st.leaf_val[leaf])
        return ids, vals, st.leaf_next[leaf], n + 1

    def chain_cond(c):
        _, _, cur, n = c
        return (cur > 0) & (n < cfg.max_candidates)

    ids, vals, _, n = jax.lax.while_loop(
        chain_cond, chain_body, (ids, vals, jnp.where(v > 0, v, 0),
                                 jnp.int32(0)))

    if cfg.sibling_probe:
        # sibling slots of the landing node, nearest key-distance
        # first (xor-ordered), leaf chains only (children skipped)
        def sib_body(j, c):
            ids, vals, n = c
            sl2 = sl ^ jnp.int32(j)
            v2 = st.slots[node, sl2]

            def walk(c2):
                ids, vals, cur, n = c2
                leaf = cur - 1
                ids = ids.at[n].set(st.leaf_id[leaf])
                vals = vals.at[n].set(st.leaf_val[leaf])
                return ids, vals, st.leaf_next[leaf], n + 1

            ids, vals, _, n = jax.lax.while_loop(
                chain_cond, walk,
                (ids, vals, jnp.where(v2 > 0, v2, 0), n))
            return ids, vals, n

        ids, vals, n = jax.lax.fori_loop(1, cfg.l, sib_body,
                                         (ids, vals, n))
    return ids, vals, n


def tree_query(st: TreeState, h: jax.Array, cfg: TreeConfig):
    """Probe with key ``h``: (ids, vals, count) — padded with -1.

    Dispatches on ``cfg.traversal`` ("masked" fixed-trip default vs the
    legacy "loop" walks); both modes return identical results.
    """
    if cfg.traversal == "masked":
        return tree_query_masked(st, h, cfg)
    return tree_query_loop(st, h, cfg)


def tree_lookup_loop(st: TreeState, h: jax.Array, vid: jax.Array,
                     cfg: TreeConfig):
    """Legacy while_loop exact-id lookup (MainTable read path).

    Returns (val, found) for the *newest* record with leaf_id == vid.
    Newest wins because inserts prepend (paper §3.2.1 update semantics:
    a new version is written and the index repointed).
    """
    _, _, _, v = _descend(st, h, cfg)

    def body(c):
        cur, val, found = c
        leaf = cur - 1
        hit = (~found) & (st.leaf_id[leaf] == vid)
        val = jnp.where(hit, st.leaf_val[leaf], val)
        return st.leaf_next[leaf], val, found | hit

    def cond(c):
        cur, _, found = c
        return (cur > 0) & (~found)

    _, val, found = jax.lax.while_loop(
        cond, body, (jnp.where(v > 0, v, 0), jnp.int32(-1), jnp.bool_(False)))
    return val, found


def tree_lookup(st: TreeState, h: jax.Array, vid: jax.Array, cfg: TreeConfig):
    """Exact-id lookup within the bucket chain; newest version wins.

    Dispatches on ``cfg.traversal`` like :func:`tree_query`.
    """
    if cfg.traversal == "masked":
        return tree_lookup_masked(st, h, vid, cfg)
    return tree_lookup_loop(st, h, vid, cfg)


# ----------------------------------------------------------------------
# delete / unlink (reclaims the leaf onto the free list)
# ----------------------------------------------------------------------
def tree_delete(st: TreeState, h: jax.Array, vid: jax.Array,
                cfg: TreeConfig) -> tuple[TreeState, jax.Array]:
    """Unlink the newest record with id ``vid`` under key ``h``.

    Returns (state, found).  The freed leaf is pushed on the free list;
    directory nodes are never reclaimed (matching the paper: spreads are
    one-way; the structure is reconstruction-free, and node arenas reset
    wholesale when a partition seals into a snapshot).
    """
    node, depth, sl, v = _descend(st, h, cfg)

    # Find the leaf and its predecessor in the chain.
    def body(c):
        cur, prev, target, tprev, found = c
        leaf = cur - 1
        hit = (~found) & (st.leaf_id[leaf] == vid)
        target = jnp.where(hit, cur, target)
        tprev = jnp.where(hit, prev, tprev)
        return st.leaf_next[leaf], cur, target, tprev, found | hit

    def cond(c):
        cur, _, _, _, found = c
        return (cur > 0) & (~found)

    head = jnp.where(v > 0, v, 0)
    _, _, target, tprev, found = jax.lax.while_loop(
        cond, body, (head, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                     jnp.bool_(False)))

    def unlink(s: TreeState) -> TreeState:
        leaf = target - 1
        nxt = s.leaf_next[leaf]
        # head removal repoints the slot; mid removal repoints predecessor
        s = jax.lax.cond(
            tprev == 0,
            lambda s: s._replace(slots=s.slots.at[node, sl].set(nxt)),
            lambda s: s._replace(leaf_next=s.leaf_next.at[tprev - 1].set(nxt)),
            s)
        return s._replace(
            leaf_id=s.leaf_id.at[leaf].set(-1),
            leaf_next=s.leaf_next.at[leaf].set(s.free_head),
            free_head=target,
            n_items=s.n_items - 1,
        )

    st = jax.lax.cond(found, unlink, lambda s: s, st)
    return st, found


# ----------------------------------------------------------------------
# headroom (device-side; folded into the jitted round flags — index.py)
# ----------------------------------------------------------------------
def forest_headroom(forest: TreeState) -> tuple[jax.Array, jax.Array]:
    """Worst-tree arena cursors: (max leaf_cnt, max node_cnt), i32 ().

    A dispatch round adds at most ``capacity`` leaves/nodes per tree, so
    the host can decide "would the next round exhaust any arena?" from
    these two scalars alone — they stay on device and are packed into
    the round's flag word rather than read back individually.
    """
    return jnp.max(forest.leaf_cnt), jnp.max(forest.node_cnt)


# ----------------------------------------------------------------------
# batched / forest-level wrappers
# ----------------------------------------------------------------------
def forest_insert_dispatched(forest: TreeState, per_tree_h: jax.Array,
                             per_tree_id: jax.Array, per_tree_val: jax.Array,
                             cfg: TreeConfig) -> TreeState:
    """Apply pre-dispatched requests: (T, K) arrays, -1 id == padding.

    Each tree consumes its K-slot segment sequentially (the actor's
    single-writer mailbox, as a scan over K); every step advances all
    trees by one request.  A step is :func:`tree_insert` row for row,
    written as flat batched gathers/scatters into the stacked arenas
    (the forest-level masked traversal's idiom): it touches only the
    (T,) elements it reads and writes, where a vmap of the per-tree
    insert turns each cond and while_loop into selects over whole
    arenas — every step would then rewrite the forest.
    """
    T = forest.slots.shape[0]
    tid = jnp.arange(T, dtype=jnp.int32)
    ml, mn = cfg.max_leaves, cfg.max_nodes

    def step(f: TreeState, x):
        h, vid, val = x
        h = h.astype(jnp.uint32)
        valid = vid >= 0
        node, depth, sl, v = _forest_descend_masked(f, tid, h, cfg)

        # leaf allocation (_alloc_leaf): pop the free list, else bump
        use_free = f.free_head > 0
        free_idx = f.free_head - 1
        ok = use_free | (f.leaf_cnt < ml)
        act = valid & ok
        leaf = jnp.where(use_free, free_idx, f.leaf_cnt)
        popped = f.leaf_next[tid, jnp.maximum(free_idx, 0)]
        w = jnp.where(act, leaf, ml)             # masked rows -> dropped

        # prepend the record to the landing chain
        f = f._replace(
            leaf_key=f.leaf_key.at[tid, w].set(h, mode="drop"),
            leaf_id=f.leaf_id.at[tid, w].set(vid, mode="drop"),
            leaf_val=f.leaf_val.at[tid, w].set(val, mode="drop"),
            leaf_next=f.leaf_next.at[tid, w].set(v, mode="drop"),
            free_head=jnp.where(act & use_free, popped, f.free_head),
            leaf_cnt=f.leaf_cnt + (act & ~use_free).astype(jnp.int32),
            n_items=f.n_items + act.astype(jnp.int32),
            overflow=f.overflow + (valid & ~ok).astype(jnp.int32))

        # spread test: chain length from the new head, counted to t + 1
        clen = jnp.ones_like(tid)
        cur = v
        for _ in range(cfg.t):
            alive = cur > 0
            clen = clen + alive.astype(jnp.int32)
            cur = jnp.where(alive, f.leaf_next[tid, jnp.maximum(cur - 1, 0)],
                            0)
        nn = f.node_cnt
        split = (act & (clen > cfg.t) & (depth + 1 < cfg.max_depth)
                 & (nn < mn))
        head = jnp.where(split, -(nn + 1), leaf + 1)
        f = f._replace(
            slots=f.slots.at[tid, jnp.where(act, node, mn), sl].set(
                head, mode="drop"),
            node_cnt=nn + split.astype(jnp.int32))

        # spread the chain into the new node one level down.  A chain
        # being spread at depth d holds at most t + 1 + d leaves (a
        # spread hands a child slot at most the chain it moved, and the
        # next insert there spreads again), so t + max_depth trips walk
        # it whole.
        cur = jnp.where(split, leaf + 1, 0)
        start = cfg.skip_bits + (depth + 1) * cfg.log2_l
        nn_r = jnp.minimum(nn, mn - 1)
        for _ in range(cfg.t + cfg.max_depth):
            alive = cur > 0
            lf = jnp.maximum(cur - 1, 0)
            nxt = f.leaf_next[tid, lf]
            csl = key_bits(f.leaf_key[tid, lf], start, cfg.log2_l)
            prev = f.slots[tid, nn_r, csl]
            f = f._replace(
                leaf_next=f.leaf_next.at[tid, jnp.where(alive, lf, ml)].set(
                    prev, mode="drop"),
                slots=f.slots.at[tid, jnp.where(alive, nn, mn), csl].set(
                    cur, mode="drop"))
            cur = jnp.where(alive, nxt, 0)
        return f, ()

    xs = (per_tree_h.T, per_tree_id.T, per_tree_val.T)
    forest, _ = jax.lax.scan(step, forest, xs)
    return forest


def forest_query(forest: TreeState, tree_ids: jax.Array, hs: jax.Array,
                 cfg: TreeConfig):
    """Fully-parallel probes: tree_ids/hs (N,) -> ids/vals (N, max_cand).

    Masked mode uses the flat batched traversal (direct indexing of the
    stacked arenas); loop mode vmaps the per-tree walk over sliced
    arena views (the legacy lockstep-penalized path).
    """
    if cfg.traversal == "masked":
        return forest_query_masked(forest, tree_ids, hs, cfg)

    def one(tid, h):
        st = jax.tree.map(lambda a: a[tid], forest)
        return tree_query(st, h, cfg)

    return jax.vmap(one)(tree_ids, hs)


def forest_lookup(forest: TreeState, tree_ids: jax.Array, hs: jax.Array,
                  vids: jax.Array, cfg: TreeConfig):
    if cfg.traversal == "masked":
        return forest_lookup_masked(forest, tree_ids, hs, vids, cfg)

    def one(tid, h, vid):
        st = jax.tree.map(lambda a: a[tid], forest)
        return tree_lookup(st, h, vid, cfg)

    return jax.vmap(one)(tree_ids, hs, vids)


def forest_delete_dispatched(forest: TreeState, per_tree_h: jax.Array,
                             per_tree_id: jax.Array,
                             cfg: TreeConfig) -> TreeState:
    def per_tree(st, hs, vids):
        def step(st, x):
            h, vid = x
            st = jax.lax.cond(
                vid >= 0,
                lambda s: tree_delete(s, h, vid, cfg)[0],
                lambda s: s, st)
            return st, ()
        st, _ = jax.lax.scan(step, st, (hs, vids))
        return st

    return jax.vmap(per_tree)(forest, per_tree_h, per_tree_id)
