"""Streaming request engine — the paper's online serving loop (§4.2).

Paper terminology -> this module:

* **actors / mailboxes** — every hash tree is an actor whose mailbox is
  one row of the dense ``(T, K)`` dispatch buffer (``core.dispatch``).
  The engine is the layer *in front* of dispatch: the global request
  stream that the paper's router thread drains.
* **rounds** — one jitted step applies one micro-batch; mailbox
  overflow is re-submitted next round (the actor's bounded inbox).
  Steady-state rounds are device-resident: the only host<->device
  traffic is ONE packed i32 flag word (pending/seal/merge signals,
  ``core.dispatch.pack_round_flags``) read back per round.
* **maintenance epochs** — seal (hot tier -> sealed snapshots), merge
  (compaction + tombstone drain) and, with a cold tier
  (``PFOConfig.cold_segments > 0``), *spill* (oldest ring segment ->
  host segment store) run between rounds as explicit engine events,
  exactly when the flag word asks, never via ad-hoc device readbacks.
  Query rounds against a cold-tier index carry their cold
  wanted/missing masks inside the round's single result pickup: a
  round that touches only cache-resident segments costs zero extra
  transfers, a miss round fetches and re-probes
  (``core.coldtier``); delete rounds signal misses via the
  ``FLAG_COLD_MISS`` bit and the ``after_flags`` backend hook.

Backend interface
-----------------
The bucket/ordering/flag-word machinery is device-topology agnostic:
:class:`StreamEngine` drives an abstract backend that owns the device
state and the jitted steps.  Two backends implement the contract:

* :class:`LocalBackend` — wraps a single-chip :class:`PFOIndex`
  (``core.index`` steps, the PR-2 engine unchanged);
* :class:`DistBackend` — a mesh-sharded ``PFOState`` driven through
  the ``core.distributed`` shard_map rounds (trees + MainTable over
  ``model``, query rows over the batch axes).
  :class:`DistStreamEngine` is the one-line assembly of engine +
  distributed backend.

A backend supplies: per-bucket dispatch capacities, one jitted
insert/delete round per bucket returning the packed flag word, a
query step, forced/flagged seal + merge epochs, and the carried-flag
bookkeeping (``ensure_flags`` / ``read_flags`` — ``sync_count`` counts
every explicit scalar readback, asserted one-per-round in tests).  The
engine never touches device state directly, so both topologies share
the exact window/strict semantics below — the distributed engine is
trace-differential-equal to the single-chip one
(``tests/test_dist_stream.py``).

Async double-buffered rounds: while the device executes micro-batch
``t``, the host packs micro-batch ``t+1`` (the ``overlap`` hook fires
between the round's dispatch and its flag-word readback), so host
batch building hides under device execution; results block only at
pickup (``StreamConfig.async_rounds``).

Multi-client ingestion
----------------------
:meth:`StreamEngine.client` opens a :class:`StreamClient` with its own
**ticket space**: tickets are ``(client_id << 40) | seq``
(``core.dispatch.client_ticket``), so K independent submitters never
coordinate on ticket allocation.  At flush time the per-client queues
merge into ONE round via ``core.dispatch.merge_client_queues`` — fair
round-robin across clients, FIFO *within* each client (the router
thread of §4.2).  The ordering contract below then applies to the
merged round: per-client submission order is always respected;
cross-client order is the deterministic round-robin interleave.

Request-grain accounting + deadlines
------------------------------------
Every ticket is stamped with the host wall-clock at enqueue (the
fourth element of the ``(ticket, kind, payload, t_enq)`` queue tuple),
and when its micro-batch completes the engine decomposes the request's
end-to-end latency into three host-clock phases::

    req.e2e_ms{kind=}  =  req.queue_wait_ms   (enqueue -> flush start)
                        + req.batch_wait_ms   (flush start -> its
                                               batch's dispatch)
                        + req.service_ms      (dispatch -> its batch's
                                               result pickup/flag ack)

All four are plain host histograms — the accounting adds ZERO device
readbacks to a round (transfer-guard tested with it enabled).  With
tracing on, the flush also records each request's exact phases as
spans on the tracer (``req_queue``, ``req_batch``, ``req_service``,
``req_hold``: the last runs from its batch's completion to the flush's
return), sharing the ticket as id and naming the round's ``dispatch``
span as parent.  Clients
opened with ``client(deadline_ms=...)`` join that bound's **deadline
class**: completions feed ``slo.requests`` / ``slo.violations``
counters and snapshot-time burn-rate gauges (``repro.obs.slo``), and a
``window``-mode flush reorders its *query* half earliest-deadline-
first (``slo.edf_order`` — safe because every query in the window
probes the same post-update state), so deadline-critical requests form
the window's first micro-batch buckets.  The update half and
``strict`` mode are never reordered.

The engine coalesces an *interleaved* stream of query / insert /
delete / update requests into fixed-shape micro-batches.  Batch shapes
are drawn from a small set of power-of-two **size buckets** and the
dispatch capacities for every bucket are precomputed, so the number of
compiled step variants is bounded by ``len(buckets)`` per operation —
the jit cache cannot grow with traffic.  Ragged tails are padded with
inactive rows (``active=False`` masks), which the jitted steps already
treat as no-ops.

Consistency (``StreamConfig.ordering``):

* ``"window"`` (default) — the paper's round semantics: every flush is
  one epoch; the window's updates apply first, then ALL of the
  window's queries probe the post-update state.  A query therefore
  sees every update submitted before it (read-your-writes) and
  possibly updates submitted later in the same window (bounded
  staleness in the *fresh* direction).  Within the update half, ops
  coalesce **by kind** (one delete batch, one update pair, one insert
  batch) because a dispatch round's cost is set by mailbox capacity,
  not row count; whenever an id is touched by two conflicting ops the
  epoch splits at that point, so per-id semantics always match the
  sequential order.  This is what lets a randomly interleaved stream
  collapse into a handful of micro-batches per window.
* ``"strict"`` — exact submission order: only runs of consecutive
  same-kind requests batch together, and an engine-fed index answers
  bit-identically to per-request ``PFOIndex`` calls — asserted in
  ``tests/test_stream_engine.py``.

Either way updates never reorder relative to each other, so the final
index state always equals the sequential one.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import (FLAG_ANY_PENDING, FLAG_COLD_FULL,
                                 FLAG_COLD_MISS, FLAG_COLD_SPILL,
                                 FLAG_NAMES, FLAG_NEED_SEAL,
                                 FLAG_SNAPS_FULL, FLAG_TOMBS_FULL,
                                 client_ticket, merge_client_queues,
                                 ticket_client)
from repro.core.index import (PFOIndex, delete_step, delete_step_cold,
                              insert_step, merge_step,
                              query_step, query_step_cold, round_flags,
                              seal_step)
from repro.obs import Obs
from repro.obs import report as obs_report
from repro.obs import slo as obs_slo

QUERY, INSERT, DELETE, UPDATE = "query", "insert", "delete", "update"


def _pow2_buckets(lo: int, hi: int) -> tuple[int, ...]:
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


#: legacy query cap applied when the index runs the "loop" traversal
#: (vmapped while-loop walks penalize large query batches — ROADMAP).
LOOP_QUERY_MAX_BATCH = 16


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    max_batch: int = 256          # largest update micro-batch (power of two)
    min_batch: int = 8            # smallest size bucket (power of two)
    # Query chunk cap.  ``None`` (default) lets the engine decide from
    # the index's traversal mode: the fixed-trip masked traversal runs
    # query rows in lockstep over identical trip counts, so big query
    # buckets amortize and queries follow ``max_batch``; the legacy
    # "loop" traversal serializes to the slowest chain walk, so queries
    # stay capped at LOOP_QUERY_MAX_BATCH (the old workaround).
    query_max_batch: int | None = None
    default_k: int = 10           # top-k for queries submitted without k
    ordering: str = "window"      # "window" (round epochs) | "strict"
    # results already returned by flush() are retained for result()
    # lookups up to this many tickets, then evicted oldest-first —
    # bounds engine memory in a long-running serving loop.
    max_retained_results: int = 4096
    # double-buffered rounds: pack micro-batch t+1 on the host while
    # the device executes micro-batch t (see module docstring)
    async_rounds: bool = True

    def __post_init__(self):
        qmb = (self.max_batch if self.query_max_batch is None
               else self.query_max_batch)
        for v in (self.max_batch, self.min_batch, qmb):
            assert v & (v - 1) == 0, "buckets must be powers of two"
        assert self.min_batch <= self.max_batch
        assert self.min_batch <= qmb, \
            "query_max_batch below min_batch would dispatch off-bucket " \
            "shapes warmup never compiled"
        assert self.ordering in ("window", "strict")

    @property
    def buckets(self) -> tuple[int, ...]:
        return _pow2_buckets(self.min_batch, self.max_batch)

    def query_cap(self, traversal: str) -> int:
        """Resolved query chunk cap for an index's traversal mode."""
        if self.query_max_batch is not None:
            return min(self.query_max_batch, self.max_batch)
        if traversal == "masked":
            return self.max_batch
        return min(max(LOOP_QUERY_MAX_BATCH, self.min_batch),
                   self.max_batch)


# ======================================================================
# backends — the device contract the engine drives
# ======================================================================
class LocalBackend:
    """Single-chip backend: a :class:`PFOIndex` and the ``core.index``
    jitted steps (the original engine's device path, verbatim)."""

    def __init__(self, index: PFOIndex):
        self.index = index
        self.cfg = index.cfg
        self._cap_cache: dict[int, tuple[int, int]] = {}
        self._flags_caps = (0, 0)

    # -- observability --------------------------------------------------
    @property
    def obs(self) -> Obs:
        return self.index.obs

    def set_obs(self, obs: Obs) -> None:
        self.index.set_obs(obs)

    # -- capacities / flags --------------------------------------------
    def capacities(self, bucket: int) -> tuple[int, int]:
        """(main_capacity, lsh_capacity) for a bucket size."""
        if bucket not in self._cap_cache:
            self._cap_cache[bucket] = (self.index._main_capacity(bucket),
                                       self.index._lsh_capacity(bucket))
        return self._cap_cache[bucket]

    def set_flags_caps(self, fm: int, fl: int) -> None:
        self._flags_caps = (fm, fl)

    @property
    def sync_count(self) -> int:
        return self.index.sync_count

    @property
    def maintenance_log(self) -> list:
        return self.index.maintenance_log

    def ensure_flags(self) -> int:
        fm, fl = self._flags_caps
        return self.index._ensure_flags(fm, fl)

    def read_flags(self, fw) -> int:
        return self.index._read_flags(fw, self._flags_caps)

    def maintain(self, flags: int) -> None:
        self.index._maintain(flags)

    # -- rounds ---------------------------------------------------------
    def query_rows(self, qvecs, k: int, overlap=None):
        """One query round.  ``overlap`` (the engine's double-buffer
        hook) is invoked after the first device dispatch and before any
        blocking pickup, so host packing of batch t+1 hides under
        batch t's device execution on both the cold and non-cold
        paths."""
        if self.index.cold is not None:
            # cold fetch loop: masks ride in the round's single pickup;
            # returns host arrays (the engine's device_get is a no-op)
            return self.index._query_cold(qvecs, k, overlap=overlap)
        out = query_step(self.index.state, qvecs, self.cfg, k)
        if overlap is not None:
            overlap()                 # dispatch in flight; pickup later
        return out

    def insert_begin(self, bucket: int):
        return jnp.full((bucket,), -2, jnp.int32)   # slots: unallocated

    def insert_round(self, ids, vecs, carry, main_active, lsh_active,
                     bucket: int):
        mcap, lcap = self.capacities(bucket)
        fm, fl = self._flags_caps
        st, slots, ma, la, fw = insert_step(
            self.index.state, ids, vecs, carry, main_active, lsh_active,
            self.cfg, mcap, lcap, fm, fl)
        self.index.state = st
        return slots, ma, la, fw

    def delete_round(self, ids, active, bucket: int):
        mcap, lcap = self.capacities(bucket)
        fm, fl = self._flags_caps
        if self.index.cold is not None:
            st, pending, fw, wm, mm = delete_step_cold(
                self.index.state, ids, active, self.cfg, mcap, lcap,
                fm, fl)
            self.index.state = st
            self.index._delete_miss = (wm, mm)
            return pending, fw
        st, pending, fw = delete_step(self.index.state, ids, active,
                                      self.cfg, mcap, lcap, fm, fl)
        self.index.state = st
        return pending, fw

    def after_flags(self, flags: int) -> None:
        """Post-readback hook: service a delete round's COLD_MISS (fetch
        the missing cold segments before the retry round)."""
        self.index.fetch_delete_miss(flags)

    def cold_stats(self) -> dict | None:
        return self.index.cold.stats() if self.index.cold else None

    def count_insert(self, n: int) -> None:
        self.index.n_inserted += n

    @property
    def n_inserted(self) -> int:
        return self.index.n_inserted

    # -- epochs ---------------------------------------------------------
    def force_seal(self) -> None:
        self.index.state = seal_step(self.index.state, self.cfg)
        self.index._flags = None

    def force_merge(self) -> None:
        self.index.state = merge_step(self.index.state, self.cfg)
        self.index._flags = None

    # -- warmup ---------------------------------------------------------
    def warmup(self, buckets, qcap: int, default_k: int) -> None:
        idx, cfg = self.index, self.cfg
        fm, fl = self._flags_caps
        cold = idx.cold is not None
        for b in buckets:
            mcap, lcap = self.capacities(b)
            ids = jnp.zeros((b,), jnp.int32)
            vecs = jnp.zeros((b, cfg.dim), jnp.float32)
            off = jnp.zeros((b,), bool)
            r = insert_step(idx.state, ids, vecs,
                            jnp.full((b,), -2, jnp.int32), off,
                            jnp.zeros((b * cfg.L,), bool), cfg, mcap, lcap,
                            fm, fl)
            jax.block_until_ready(r[-1])
            r = (delete_step_cold if cold else delete_step)(
                idx.state, ids, off, cfg, mcap, lcap, fm, fl)
            jax.block_until_ready(r[2])
            if b <= qcap:
                step = query_step_cold if cold else query_step
                jax.block_until_ready(
                    step(idx.state, vecs, cfg, default_k))
        jax.block_until_ready(round_flags(idx.state, cfg, fm, fl))
        # epoch programs run on the live state with their results
        # discarded (the state is untouched): no second full-size state
        # is ever allocated, so warmup peaks at one epoch's footprint
        sealed = seal_step(idx.state, cfg)
        if cold:
            # compile the spill program so the first real spill epoch
            # does not pay a jit compile
            from repro.core.coldtier import spill_device
            from repro.core.index import (_snap_cfg_lsh, _snap_cfg_main,
                                          main_tree_config)
            jax.block_until_ready(spill_device(
                sealed.lsh_snaps, sealed.main_snaps, sealed.cold,
                sealed.store, sealed.main_forest, sealed.tombstones,
                _snap_cfg_lsh(cfg), _snap_cfg_main(cfg),
                main_tree_config(cfg))[:4])
        else:
            jax.block_until_ready(sealed)
            del sealed
            jax.block_until_ready(merge_step(idx.state, cfg))


class DistBackend:
    """Mesh-sharded backend: a distributed ``PFOState`` driven through
    the ``core.distributed`` shard_map stream rounds.

    Jitted-variant bookkeeping matches the single-chip path: one
    insert/delete round per bucket (static mailbox capacities derive
    from the bucket), one query program per k, one seal/merge/flags
    program — the jit cache is bounded by the bucket table, never by
    traffic.  The flag-word thresholds are computed against the same
    worst-case-bucket capacities as :class:`LocalBackend`, so seal and
    merge epochs fire at the same rounds for the same trace (the
    differential tests assert this end to end).
    """

    #: jitted programs memoized per (dcfg, mesh, variant) so a second
    #: engine over the same topology reuses compiles (mirrors the
    #: process-global jit cache the single-chip steps get for free)
    _FN_CACHE: dict = {}

    def __init__(self, dcfg, mesh, seed: int = 0,
                 cold_dir: str | None = None):
        from repro.core import distributed as dist

        self._dist = dist
        self.dcfg = dcfg
        self.mesh = mesh
        self.cfg = dcfg.pfo
        self.state = dist.dist_init_state(dcfg, jax.random.PRNGKey(seed),
                                          mesh)
        self.sync_count = 0
        self.maintenance_log: list[str] = []
        self.n_inserted = 0
        self.obs = Obs()              # metrics on / tracing off default
        self.obs.on_snapshot("dist", self._mirror_obs)
        # device-resident accumulator of query candidates dropped by
        # owner-mailbox skew overflow (queries have no retry round);
        # read back only when stats() is asked for
        self._query_drops = jnp.int32(0)
        self._flags: int | None = None
        self._flags_caps = (0, 0)
        self._ins: dict[int, Any] = {}
        self._del: dict[int, Any] = {}
        self._qry: dict[int, Any] = {}
        self._seal_fn = self._cached(("seal",),
                                     lambda: dist.make_dist_seal(dcfg, mesh))
        self._merge_fn = self._cached(
            ("merge",), lambda: dist.make_dist_merge(dcfg, mesh))
        self._flags_fn = None
        # per-shard cold tier: each shard owns one mixed-table segment
        # chain (its own ColdManager, SegmentStore subdir, routing table
        # and staging arena) — spill/merge/compaction stay shard-local
        self.cold_mgrs = None
        self._delete_miss = None
        if self.cfg.cold_enabled:
            import os
            from repro.core.coldtier import ColdManager
            from repro.core.index import main_tree_config

            def _sync():
                self.sync_count += 1

            self.cold_mgrs = [
                ColdManager(dist.shard_cold_cfg(dcfg),
                            dist.shard_snap_cfg(dcfg),
                            dist.shard_main_snap_cfg(dcfg),
                            main_tree_config(self.cfg),
                            root=None if cold_dir is None
                            else os.path.join(cold_dir, f"shard{s}"),
                            on_sync=_sync, mixed_lsh=True)
                for s in range(dcfg.n_model)]
            self._spill_fn = self._cached(
                ("spill",), lambda: dist.make_dist_spill(dcfg, mesh))
            self._drain_fn = self._cached(
                ("drain",), lambda: dist.make_dist_ring_drain(dcfg, mesh))

    #: FIFO bound so a process cycling meshes/configs cannot pin every
    #: compiled program (and its Mesh key) forever
    _FN_CACHE_MAX = 256

    def _cached(self, key: tuple, builder):
        full = (self.dcfg, self.mesh) + key
        fn = DistBackend._FN_CACHE.get(full)
        if fn is None:
            cache = DistBackend._FN_CACHE
            while len(cache) >= self._FN_CACHE_MAX:
                cache.pop(next(iter(cache)))
            fn = cache[full] = builder()
        return fn

    # -- capacities / flags --------------------------------------------
    def capacities(self, bucket: int) -> tuple[int, int]:
        """Receive-side per-tree capacities == single-chip formulas, so
        the per-tree mailbox scan stays as short as on one chip."""
        cfg = self.cfg
        total = cfg.L * cfg.n_trees
        lsh = int(max(8, 2 * -(-bucket * cfg.L // total)))
        main = int(max(8, 2 * -(-bucket // cfg.main_n_trees)))
        return main, lsh

    def route_capacities(self, bucket: int) -> tuple[int, int]:
        """Per-destination-shard send mailboxes: sized for ~2x the even
        spread; skew overflows surface as pending and retry."""
        S = self.dcfg.n_model
        rmain = int(max(8, 2 * -(-bucket // (S * S))))
        rlsh = int(max(8, 2 * -(-bucket * self.cfg.L // (S * S))))
        return rmain, rlsh

    def set_flags_caps(self, fm: int, fl: int) -> None:
        self._flags_caps = (fm, fl)
        self._flags_fn = self._cached(
            ("flags", fm, fl),
            lambda: self._dist.make_dist_round_flags(self.dcfg, self.mesh,
                                                     fm, fl))

    def ensure_flags(self) -> int:
        if self._flags is not None:
            return self._flags
        self.sync_count += 1
        self._flags = int(jax.device_get(self._flags_fn(self.state)))
        return self._flags

    def read_flags(self, fw) -> int:
        self.sync_count += 1
        self._flags = int(jax.device_get(fw))
        return self._flags

    # -- observability --------------------------------------------------
    def set_obs(self, obs: Obs) -> None:
        """Bind an observability handle; the readback count mirrors
        into a gauge lazily at snapshot time."""
        self.obs = obs
        obs.on_snapshot("dist", self._mirror_obs)

    def _mirror_obs(self) -> None:
        self.obs.gauge("index.readbacks").set(self.sync_count)

    def _epoch(self, name: str, fn, *args):
        with self.obs.span(name):
            return fn(*args)

    def maintain(self, flags: int) -> None:
        if flags & FLAG_NEED_SEAL:
            if self.cold_mgrs is not None and flags & FLAG_COLD_SPILL:
                # capacity relief with a cold tier: spill, never merge
                # (lockstep rings — every shard spills this epoch)
                self._epoch("spill", self._spill)
                self.maintenance_log.append("spill")
            elif flags & FLAG_SNAPS_FULL:
                self.state = self._epoch("merge", self._merge_fn, self.state)
                self.maintenance_log.append("merge")
            self.state = self._epoch("seal", self._seal_fn, self.state)
            self.maintenance_log.append("seal")
        if flags & FLAG_TOMBS_FULL:
            if self.cold_mgrs is not None:
                self._epoch("merge", self._merge_with_cold)
            else:
                self.state = self._epoch("merge", self._merge_fn, self.state)
            self.maintenance_log.append("merge")
        if self.cold_mgrs is not None and flags & FLAG_COLD_FULL:
            # proactive shrink at the watermark, synchronous per shard
            # (folds are host-only numpy; shards in futile backoff skip)
            self._compact()
        if flags & (FLAG_NEED_SEAL | FLAG_TOMBS_FULL):
            self._flags = None       # state changed; carried word stale

    # -- cold epochs (per-shard host halves) ----------------------------
    def _spill(self) -> None:
        """Distributed spill epoch: one device program pops every
        shard's oldest ring segments, the host persists each shard's
        popped arrays through that shard's ColdManager."""
        if any(m.n_cold >= self.cfg.cold_segments for m in self.cold_mgrs):
            self._compact(only_full=True)
        st, pl, pm = self._spill_fn(self.state)
        self.sync_count += 1
        pl_h, pm_h = jax.device_get((pl, pm))
        for s, mgr in enumerate(self.cold_mgrs):
            # pl rows keep a leading L==1 table axis (the mixed chain);
            # pm rows are flat — the layout adopt_spill expects
            mgr.adopt_spill({k2: v[s:s + 1] for k2, v in pl_h.items()},
                            {k2: v[s] for k2, v in pm_h.items()})
        self.state = st
        self._flags = None

    def _merge_with_cold(self) -> None:
        """Distributed cold merge: drain every shard's ring payloads on
        device, read the rings back once, fold ring + cold per shard
        with the drained tombstones (host numpy, shard-local), install
        the fresh layouts and reset rings + tombstones."""
        self.sync_count += 1
        tombs = np.asarray(jax.device_get(self.state.tombstones))
        dead = tombs[tombs >= 0]
        st, pay, _cur = self._drain_fn(self.state)
        self.sync_count += 1
        ls, ms, pay_h = jax.device_get((st.lsh_snaps, st.main_snaps, pay))
        dim = self.cfg.dim
        cold_states = []
        for s, mgr in enumerate(self.cold_mgrs):
            # shard s's ring: stacked leaves are (S, R, cap...), one
            # mixed chain per shard (table id in vals)
            lk, li, lv, lst = (ls.keys[s], ls.ids[s], ls.vals[s],
                               ls.stamps[s])
            n_ring = int(ls.n_snaps[s])
            if n_ring:
                ring_l = (np.concatenate(lk[:n_ring]),
                          np.concatenate(li[:n_ring]),
                          np.concatenate(lv[:n_ring]),
                          np.concatenate([np.full(lk[r].shape, lst[r],
                                                  np.int32)
                                          for r in range(n_ring)]))
            else:
                z = np.zeros((0,), np.int32)
                ring_l = (z.astype(np.uint32), z, z, z)
            n_ring_m = int(ms.n_snaps[s])
            if n_ring_m:
                ring_m = (np.concatenate(ms.keys[s][:n_ring_m]),
                          np.concatenate(ms.ids[s][:n_ring_m]),
                          np.concatenate(ms.vals[s][:n_ring_m]),
                          np.concatenate([np.full(ms.keys[s][r].shape,
                                                  ms.stamps[s][r], np.int32)
                                          for r in range(n_ring_m)]),
                          np.concatenate(pay_h[s][:n_ring_m]))
            else:
                z = np.zeros((0,), np.int32)
                ring_m = (z.astype(np.uint32), z, z, z,
                          np.zeros((0, dim), np.float32))
            mgr._discard_worker()
            fold = mgr._fold_all(dead, ring_extra=[ring_l],
                                 ring_extra_main=ring_m)
            cold_states.append(
                mgr.routed_cold_state(mgr.install_layout(fold)))
            mgr.counters["cold_merges"] += 1
        dist = self._dist
        lsnaps, msnaps = dist.dist_fresh_rings(self.dcfg, self.mesh)
        self.state = st._replace(
            lsh_snaps=lsnaps, main_snaps=msnaps,
            cold=dist.dist_put_cold(self.dcfg, self.mesh, cold_states),
            tombstones=jnp.full_like(st.tombstones, -1),
            n_tombstones=jnp.int32(0))

    def _compact(self, only_full: bool = False) -> None:
        """Synchronous per-shard cold compaction.  ``only_full``
        restricts the fold to shards whose routing table is at hard
        capacity (the pre-spill guard); otherwise every shard not in
        futile backoff folds.  Shards that do not fold keep their
        current device cold state (cache included)."""
        ran = False
        cold_states = []
        for s, mgr in enumerate(self.cold_mgrs):
            full = mgr.n_cold >= self.cfg.cold_segments
            skip = (not full) if only_full \
                else (mgr._gen == mgr._futile_gen)
            if skip:
                cold_states.append(
                    jax.tree.map(lambda a: a[s], self.state.cold))
                continue
            fold = mgr._fold_all(np.zeros((0,), np.int32))
            cold_states.append(mgr.routed_cold_state(
                mgr.install_layout(fold, mark_futile=True)))
            mgr.counters["compactions"] += 1
            ran = True
        if not ran:
            return
        self.state = self.state._replace(
            cold=self._dist.dist_put_cold(self.dcfg, self.mesh,
                                          cold_states))
        self.maintenance_log.append("cold_compact")
        self._flags = None

    # -- rounds ---------------------------------------------------------
    def _insert_fn(self, bucket: int):
        if bucket not in self._ins:
            tm, tl = self.capacities(bucket)
            rm, rl = self.route_capacities(bucket)
            fm, fl = self._flags_caps
            self._ins[bucket] = self._cached(
                ("insert", rm, tm, rl, tl, fm, fl),
                lambda: self._dist.make_dist_insert_round(
                    self.dcfg, self.mesh, route_main=rm, tree_main=tm,
                    route_lsh=rl, tree_lsh=tl, flags_main=fm, flags_lsh=fl))
        return self._ins[bucket]

    def _delete_fn(self, bucket: int):
        if bucket not in self._del:
            tm, tl = self.capacities(bucket)
            _, rl = self.route_capacities(bucket)
            fm, fl = self._flags_caps
            self._del[bucket] = self._cached(
                ("delete", tm, rl, tl, fm, fl),
                lambda: self._dist.make_dist_delete_round(
                    self.dcfg, self.mesh, tree_main=tm, route_lsh=rl,
                    tree_lsh=tl, flags_main=fm, flags_lsh=fl))
        return self._del[bucket]

    def query_rows(self, qvecs, k: int, overlap=None):
        if k not in self._qry:
            self._qry[k] = self._cached(
                ("query", k),
                lambda: self._dist.make_dist_query(self.dcfg, self.mesh, k,
                                                   with_drop_count=True))
        fn = self._qry[k]
        if self.cold_mgrs is None:
            ids, dists, dropped = fn(self.state, qvecs)
            self._query_drops = self._query_drops + dropped  # on device
            if overlap is not None:
                overlap()             # dispatch in flight; pickup later
            return ids, dists
        # cold fetch loop (mirrors PFOIndex._query_cold): the per-shard
        # wanted/missing masks ride the round's single pickup; only a
        # miss round fetches (into the owning shard's cache) and
        # re-probes.  Aggregated (psum'd) round info lands on shard 0's
        # manager — cold_stats() reads the cluster totals from there.
        mgr0 = self.cold_mgrs[0]
        for attempt in range(self.cfg.cold_fetch_rounds + 1):
            out = fn(self.state, qvecs)
            if attempt == 0 and overlap is not None:
                overlap()            # first dispatch is in flight
            ids, dists, dropped, wl, ml, wm, mm, info = jax.device_get(out)
            self._query_drops = self._query_drops + int(dropped)
            mgr0.record_query_round(info)
            if not (ml.any() or mm.any()):
                break
            if attempt == self.cfg.cold_fetch_rounds:
                mgr0.counters["incomplete_query_rounds"] += 1
                break
            before = sum(m.counters["fetches"] for m in self.cold_mgrs)
            with self.obs.span("cold_fetch", attempt=attempt):
                self._fetch_shards(wl, ml, wm, mm)
            if sum(m.counters["fetches"]
                   for m in self.cold_mgrs) == before:
                # every cache slot is wanted by this round on every
                # missing shard: the miss set can never drain
                mgr0.counters["incomplete_query_rounds"] += 1
                break
        return ids, dists

    def _fetch_shards(self, wl, ml, wm, mm) -> None:
        """Fetch Bloom-matched non-resident segments shard by shard:
        slice shard s's cold state out of the stacked leaves, run its
        manager's fetch, scatter the result back.  Masks are (S, C)."""
        cold = self.state.cold
        for s, mgr in enumerate(self.cold_mgrs):
            if not (ml[s].any() or mm[s].any()):
                continue
            shard = jax.tree.map(lambda a: a[s], cold)
            shard = mgr.fetch_cold(shard, wl[s][None], ml[s][None],
                                   wm[s], mm[s])
            cold = jax.tree.map(lambda g, v: g.at[s].set(v), cold, shard)
        self.state = self.state._replace(cold=cold)

    def insert_begin(self, bucket: int):
        return None                       # slots live at the owner shard

    def after_flags(self, flags: int) -> None:
        """COLD_MISS service: a delete round's MainTable probe matched a
        non-resident cold segment on some shard — read the stashed
        (S, C) masks (the only extra readback, and only on miss rounds)
        and fetch into the owning shards before the retry round."""
        if self.cold_mgrs is None or not flags & FLAG_COLD_MISS \
                or self._delete_miss is None:
            return
        self.sync_count += 1
        wm, mm = jax.device_get(self._delete_miss)
        self._delete_miss = None
        S, C = self.dcfg.n_model, self.cfg.cold_segments
        zeros = np.zeros((S, C), bool)
        before = sum(m.counters["fetches"] for m in self.cold_mgrs)
        with self.obs.span("cold_fetch", path="delete"):
            self._fetch_shards(zeros, zeros, np.asarray(wm),
                               np.asarray(mm))
        if np.any(mm) and sum(m.counters["fetches"]
                              for m in self.cold_mgrs) == before:
            raise RuntimeError(
                f"delete cannot resolve: its Bloom route spans "
                f"{int(np.sum(wm))} cold segments but cold_cache_slots="
                f"{self.cfg.cold_cache_slots} cannot hold them at once; "
                "raise PFOConfig.cold_cache_slots")

    def cold_stats(self) -> dict | None:
        if self.cold_mgrs is None:
            return None
        # query accounting (the psum'd info vectors) lives on shard 0's
        # manager and is already cluster-total; structural counters
        # (spills, fetches, segments, bytes) are per-shard and sum —
        # shard 0's info-derived rates stay correct, and its structural
        # shares just gain the other shards' zero-info contributions
        stats = [m.stats() for m in self.cold_mgrs]
        out = dict(stats[0])
        for s in stats[1:]:
            for k2 in ("cold_segments", "segments_spilled", "fetches",
                       "fetch_rounds", "compactions", "cold_merges",
                       "store_bytes_written", "vec_fetch_bytes",
                       "vec_evictions", "vec_resident_pages"):
                out[k2] += s[k2]
        qr = max(self.cold_mgrs[0].counters["query_rounds"], 1)
        out["fetches_per_query_round"] = round(out["fetches"] / qr, 4)
        out["shards"] = len(self.cold_mgrs)
        return out

    def insert_round(self, ids, vecs, carry, main_active, lsh_active,
                     bucket: int):
        self.state, ma, la, fw = self._insert_fn(bucket)(
            self.state, ids, vecs, main_active, lsh_active)
        return carry, ma, la, fw

    def delete_round(self, ids, active, bucket: int):
        if self.cold_mgrs is not None:
            self.state, pending, fw, wm, mm = self._delete_fn(bucket)(
                self.state, ids, active)
            self._delete_miss = (wm, mm)
            return pending, fw
        self.state, pending, fw = self._delete_fn(bucket)(self.state, ids,
                                                          active)
        return pending, fw

    def count_insert(self, n: int) -> None:
        self.n_inserted += n

    # -- epochs ---------------------------------------------------------
    def force_seal(self) -> None:
        self.state = self._seal_fn(self.state)
        self._flags = None

    def force_merge(self) -> None:
        self.state = self._merge_fn(self.state)
        self._flags = None

    # -- warmup ---------------------------------------------------------
    def warmup(self, buckets, qcap: int, default_k: int) -> None:
        cfg = self.cfg
        for b in buckets:
            ids = jnp.zeros((b,), jnp.int32)
            vecs = jnp.zeros((b, cfg.dim), jnp.float32)
            off = jnp.zeros((b,), bool)
            r = self._insert_fn(b)(self.state, ids, vecs, off,
                                   jnp.zeros((b * cfg.L,), bool))
            jax.block_until_ready(r[-1])           # state discarded
            r = self._delete_fn(b)(self.state, ids, off)
            jax.block_until_ready(r[-1])
            if b <= qcap:
                if default_k not in self._qry:
                    self._qry[default_k] = self._cached(
                        ("query", default_k),
                        lambda: self._dist.make_dist_query(
                            self.dcfg, self.mesh, default_k,
                            with_drop_count=True))
                # raw program, not query_rows: the cold path's fetch
                # loop would count warmup rounds into the managers
                jax.block_until_ready(
                    self._qry[default_k](self.state, vecs)[:2])
        jax.block_until_ready(self._flags_fn(self.state))
        # epoch programs run on the live state, results discarded (see
        # LocalBackend.warmup): no second full-size state
        sealed = self._seal_fn(self.state)
        if self.cold_mgrs is not None:
            # cold rings never merge on device (spill relieves capacity,
            # TOMBS_FULL folds on host) — precompile spill + drain so
            # the first real epoch pays no jit compile
            jax.block_until_ready(self._spill_fn(sealed)[1])
            jax.block_until_ready(self._drain_fn(sealed)[1])
        else:
            jax.block_until_ready(sealed)
            del sealed
            jax.block_until_ready(self._merge_fn(self.state))

    def stats(self) -> dict:
        st = self.state
        return {
            "items_hot": int(np.asarray(st.main_forest.n_items).sum()),
            "lsh_leaves": int(np.asarray(st.lsh_forest.n_items).sum()),
            "snapshots": int(np.asarray(st.main_snaps.n_snaps).max()),
            "tombstones": int(st.n_tombstones),
            "store_free": int(np.asarray(st.store.free_top).sum()),
            "overflow_events": int(np.asarray(st.lsh_forest.overflow).sum()),
            "query_candidate_drops": int(jax.device_get(self._query_drops)),
            "stamp": int(st.stamp),
        }


# ======================================================================
# multi-client handles (per-client ticket spaces — module docstring)
# ======================================================================
class StreamClient:
    """A submitter handle with its own FIFO queue and ticket space.

    ``deadline_ms`` (set via :meth:`StreamEngine.client`) places every
    request this client submits in that deadline class — see the
    request-grain accounting section of the module docstring."""

    def __init__(self, engine: "StreamEngine", cid: int,
                 deadline_ms: float | None = None):
        self._engine = engine
        self.cid = cid
        self.deadline_ms = deadline_ms
        self._buf: list[tuple[int, str, Any, float]] = []
        self._seq = 0

    def _enqueue(self, kind: str, payload,
                 t_arrival: float | None = None) -> int:
        t = client_ticket(self.cid, self._seq)
        self._seq += 1
        # the enqueue stamp rides the queue tuple (host wall-clock):
        # request-grain latency accounting starts here.  ``t_arrival``
        # (a time.perf_counter() value) backdates the stamp to when the
        # request actually arrived — an upstream front-end stamps at
        # socket receive so queue_wait covers its backlog too, and the
        # open-loop benchmark stamps the Poisson arrival clock.
        self._buf.append((t, kind, payload,
                          time.perf_counter() if t_arrival is None
                          else t_arrival))
        self._engine.n_requests += 1
        return t

    def query(self, vec, k: int | None = None,
              t_arrival: float | None = None) -> int:
        e = self._engine
        vec = np.asarray(vec, np.float32).reshape(e._dim)
        return self._enqueue(QUERY, (vec, int(k or e.scfg.default_k)),
                             t_arrival)

    def insert(self, vid: int, vec,
               t_arrival: float | None = None) -> int:
        vec = np.asarray(vec, np.float32).reshape(self._engine._dim)
        return self._enqueue(INSERT, (int(vid), vec), t_arrival)

    def delete(self, vid: int, t_arrival: float | None = None) -> int:
        return self._enqueue(DELETE, int(vid), t_arrival)

    def update(self, vid: int, vec,
               t_arrival: float | None = None) -> int:
        vec = np.asarray(vec, np.float32).reshape(self._engine._dim)
        return self._enqueue(UPDATE, (int(vid), vec), t_arrival)

    def pending(self) -> int:
        return len(self._buf)

    def result(self, ticket: int):
        return self._engine.result(ticket)


# ======================================================================
# the engine
# ======================================================================
class StreamEngine:
    """Online query/update front-end over a backend (see module doc).

    Submission enqueues and returns a ticket immediately; :meth:`flush`
    drains the stream in order and materializes results.  ``stats()``
    exposes round/readback/maintenance counters — including per-kind
    round counts and readbacks-per-round, so the one-readback-per-round
    invariant is assertable from tests.
    """

    MAX_ROUNDS = PFOIndex.MAX_ROUNDS

    def __init__(self, index, scfg: StreamConfig | None = None,
                 obs: Obs | None = None):
        self.backend = index if hasattr(index, "insert_round") \
            else LocalBackend(index)
        self.index = getattr(self.backend, "index", None)
        self.scfg = scfg or StreamConfig()
        cfg = self.backend.cfg
        mb = self.scfg.max_batch
        # flag-word headroom is computed against the worst-case bucket
        # so one carried word stays valid across bucket sizes
        self.backend.set_flags_caps(*self.backend.capacities(mb))
        # query chunk cap resolved against the index's traversal mode
        # (masked traversal: queries follow max_batch — no lockstep
        # penalty left to work around)
        self._query_cap = self.scfg.query_cap(cfg.traversal)
        self._clients: list[StreamClient] = []
        self._self_client = StreamClient(self, 0)
        # deadline classes (client id -> deadline_ms) + the pluggable
        # window-mode flush policy over the query half (slo.edf_order:
        # earliest-deadline-first; only consulted when a deadline
        # client exists, so deadline-free engines skip the sort)
        self._deadlines: dict[int, float] = {}
        self.flush_policy = obs_slo.edf_order
        self._t_flush = time.perf_counter()
        self._results: dict[int, Any] = {}
        self.events: list[tuple[str, int]] = []        # (epoch kind, flush#)
        self.n_flushes = 0
        self.n_batches = 0
        self.n_rounds = 0
        self.n_requests = 0
        self.n_rounds_by_kind = {QUERY: 0, INSERT: 0, DELETE: 0, UPDATE: 0}
        self._dim = cfg.dim
        # observability: inherit the backend's handle unless an explicit
        # one is supplied (then the backend — index, cold manager — is
        # rebound to it).  All recording is host-side; see repro.obs.
        if obs is not None:
            self.backend.set_obs(obs)
        self._bind_obs()

    # ------------------------------------------------------------------
    # observability binding (metric handles cached off the hot path)
    # ------------------------------------------------------------------
    def set_obs(self, obs: Obs) -> None:
        """Rebind engine + backend to a new observability handle."""
        self.backend.set_obs(obs)
        self._bind_obs()

    def _bind_obs(self) -> None:
        o = self.obs = self.backend.obs
        self._obs_on = o.active
        self._tracing = o.tracing
        self._req_spans: list = []     # this flush's rounds, traced only
        # request-grain lifecycle histograms (module docstring): e2e is
        # per kind; the decomposition shares one histogram each so the
        # metric count stays flat
        self._h_e2e = {k: o.histogram("req.e2e_ms", kind=k)
                       for k in (QUERY, INSERT, DELETE, UPDATE)}
        self._h_queue_wait = o.histogram("req.queue_wait_ms")
        self._h_batch_wait = o.histogram("req.batch_wait_ms")
        self._h_service = o.histogram("req.service_ms")
        self._slo = obs_slo.SLOTracker(o)
        self._c_flags = tuple(
            (bit, o.counter("stream.flag_fired", flag=name))
            for bit, name in FLAG_NAMES.items())
        o.on_snapshot("stream", self._mirror_obs)

    def _mirror_obs(self) -> None:
        """Lazy snapshot mirror: engine counters -> gauges, only when a
        snapshot is taken — zero double bookkeeping per round."""
        o = self.obs
        o.gauge("stream.requests").set(self.n_requests)
        o.gauge("stream.flushes").set(self.n_flushes)
        o.gauge("stream.rounds").set(self.n_rounds)
        for k, v in self.n_rounds_by_kind.items():
            o.gauge("stream.rounds", kind=k).set(v)

    # ------------------------------------------------------------------
    # warmup: precompile every (op, bucket) variant + maintenance steps
    # ------------------------------------------------------------------
    def warmup(self) -> None:
        """Compile all step variants the engine can ever dispatch, so no
        jit compile lands inside a serving round.  Uses all-inactive
        batches (state untouched) and a scratch state for seal/merge."""
        self.backend.warmup(self.scfg.buckets, self._query_cap,
                            self.scfg.default_k)

    # ------------------------------------------------------------------
    # submission (the request stream)
    # ------------------------------------------------------------------
    def client(self, deadline_ms: float | None = None) -> StreamClient:
        """Open a new client handle with its own ticket space (see the
        multi-client contract in the module docstring).

        ``deadline_ms`` assigns the client a deadline class: its
        completed requests feed the ``slo.*`` violation counters and
        burn-rate gauges, and window-mode flushes prioritize its
        queries earliest-deadline-first (``repro.obs.slo``)."""
        if deadline_ms is not None:
            deadline_ms = float(deadline_ms)
            assert deadline_ms > 0, "deadline_ms must be positive"
        c = StreamClient(self, len(self._clients) + 1,
                         deadline_ms=deadline_ms)
        self._clients.append(c)
        if deadline_ms is not None:
            self._deadlines[c.cid] = deadline_ms
        return c

    def query(self, vec, k: int | None = None) -> int:
        return self._self_client.query(vec, k)

    def insert(self, vid: int, vec) -> int:
        return self._self_client.insert(vid, vec)

    def delete(self, vid: int) -> int:
        return self._self_client.delete(vid)

    def update(self, vid: int, vec) -> int:
        """Online update (paper §5): new version written, old reclaimed."""
        return self._self_client.update(vid, vec)

    # ------------------------------------------------------------------
    # draining
    # ------------------------------------------------------------------
    def pending(self) -> int:
        return (len(self._self_client._buf)
                + sum(len(c._buf) for c in self._clients))

    def result(self, ticket: int):
        """Result for ``ticket`` (flushes if still queued)."""
        if ticket not in self._results:
            self.flush()
        return self._results.pop(ticket)

    def _ingest(self) -> list:
        """Merge the per-client queues into this flush's round."""
        queues = [self._self_client._buf] + [c._buf for c in self._clients]
        live = [q for q in queues if q]
        merged = list(live[0]) if len(live) == 1 \
            else merge_client_queues(live)
        for q in queues:
            q.clear()
        return merged

    def flush(self) -> dict[int, Any]:
        """Drain the queue; returns {ticket: result} for every request
        processed by this flush.  ``window`` ordering applies the
        window's updates first (in order), then all queries; ``strict``
        keeps exact submission order (see module docstring)."""
        queue = self._ingest()
        t0 = time.perf_counter()
        self._t_flush = t0                # queue_wait / batch_wait pivot
        with self.obs.span("flush", depth=len(queue)):
            out: dict[int, Any] = {}
            if self.scfg.ordering == "window":
                updates = [r for r in queue if r[1] != QUERY]
                queries = [r for r in queue if r[1] == QUERY]
                if self._deadlines:
                    # deadline-aware bucket priority: the window's
                    # queries all probe the same post-update state, so
                    # reordering them is semantics-free (module doc)
                    queries = self.flush_policy(queries, self._deadlines)
                self._drain_updates_coalesced(updates, out)
                self._drain_in_runs(queries, out)
            else:
                self._drain_in_runs(queue, out)
            self._results.update(out)
            while len(self._results) > self.scfg.max_retained_results:
                self._results.pop(next(iter(self._results)))  # oldest first
            self.n_flushes += 1
        if self._req_spans:
            self._record_requests(t0, time.perf_counter())
        return out

    def _drain_updates_coalesced(self, updates: list, out: dict) -> None:
        """Window mode: coalesce the update half by kind.

        Ops land in per-kind epochs — deletes, then updates, then
        inserts — which is order-equivalent to submission order as long
        as no id is touched twice with conflicting kinds inside one
        epoch; on conflict (or an UPDATE repeat, whose delete half must
        see the previous version) the epoch is flushed first.  Repeated
        same-kind inserts/deletes are submission-stable within a batch
        (dispatch sorts stably), so they need no split."""
        epoch: dict[str, list] = {DELETE: [], UPDATE: [], INSERT: []}
        touched: dict[int, str] = {}
        for req in updates:
            kind, payload = req[1], req[2]
            vid = payload if kind == DELETE else payload[0]
            prev = touched.get(vid)
            if prev is not None and (prev != kind or kind == UPDATE):
                self._flush_epoch(epoch, out)
                epoch = {DELETE: [], UPDATE: [], INSERT: []}
                touched = {}
            touched[vid] = kind
            epoch[kind].append(req)
        self._flush_epoch(epoch, out)

    def _flush_epoch(self, epoch: dict, out: dict) -> None:
        for kind in (DELETE, UPDATE, INSERT):
            if epoch[kind]:
                self._run(epoch[kind], kind, out)

    def _drain_in_runs(self, queue: list, out: dict) -> None:
        """Batch maximal runs of same-kind (and same-k, for queries)
        consecutive requests; never reorders within ``queue``."""
        i = 0
        while i < len(queue):
            kind = queue[i][1]
            key = (kind, queue[i][2][1]) if kind == QUERY else kind
            j = i
            while j < len(queue) and queue[j][1] == kind and (
                    kind != QUERY or queue[j][2][1] == key[1]):
                j += 1
            self._run(queue[i:j], kind, out)
            i = j

    # -- micro-batching -------------------------------------------------
    def _bucket(self, n: int, cap: int) -> int:
        for b in self.scfg.buckets:
            if n <= b:
                return min(b, cap)
        return cap

    def _chunks(self, run: list, cap: int):
        i = 0
        while i < len(run):
            take = min(len(run) - i, cap)
            yield run[i:i + take], self._bucket(take, cap)
            i += take

    def _run(self, run: list, kind: str, out: dict) -> None:
        if kind == UPDATE:
            # An update chunk is one delete batch + one insert batch, so
            # repeated ids inside a chunk would leave the stale version
            # live (its delete half sees only the pre-chunk state) —
            # split the run so each id appears once per chunk.
            sub: list = []
            seen: set = set()
            for req in run:
                if req[2][0] in seen:
                    self._run_chunks(sub, kind, out)
                    sub, seen = [], set()
                sub.append(req)
                seen.add(req[2][0])
            self._run_chunks(sub, kind, out)
        else:
            self._run_chunks(run, kind, out)

    def _cap_for(self, kind: str) -> int:
        return self._query_cap if kind == QUERY else self.scfg.max_batch

    def _run_chunks(self, run: list, kind: str, out: dict) -> None:
        chunks = list(self._chunks(run, self._cap_for(kind)))
        if not chunks:
            return
        with self.obs.span("pack", kind=kind):
            packed = self._pack(kind, *chunks[0])
        for i, (chunk, bucket) in enumerate(chunks):
            # double-buffer hook: the batch methods call this between
            # their first device dispatch and the first (blocking)
            # flag/result readback, so batch t+1's host packing hides
            # under batch t's device execution
            hold: dict = {}
            overlap = None
            if self.scfg.async_rounds and i + 1 < len(chunks):
                nxt = chunks[i + 1]

                def overlap(nxt=nxt, hold=hold):
                    with self.obs.span("pack", kind=kind):
                        hold["p"] = self._pack(kind, *nxt)

            t_disp = time.perf_counter()
            # ``disp``: id of the chunk's first dispatch span (traced)
            if kind == QUERY:
                disp = self._query_batch(packed, chunk, bucket, out,
                                         overlap)
            elif kind == INSERT:
                disp = self._insert_batch(packed, chunk, bucket, out,
                                          INSERT, overlap)
            elif kind == DELETE:
                disp = self._delete_batch(packed, chunk, bucket, out,
                                          DELETE, overlap)
            else:                                           # UPDATE
                disp = self._delete_batch(packed["del"], chunk, bucket,
                                          None, UPDATE, overlap)
                self._insert_batch(packed["ins"], chunk, bucket, out,
                                   UPDATE, None)
            self.n_batches += 1
            if self._obs_on:
                self._account(chunk, kind, t_disp, time.perf_counter(),
                              disp)
            if i + 1 < len(chunks):
                packed = hold.get("p")
                if packed is None:
                    with self.obs.span("pack", kind=kind):
                        packed = self._pack(kind, *chunks[i + 1])

    # ------------------------------------------------------------------
    # request-grain lifecycle accounting (module docstring): pure host
    # arithmetic on the enqueue stamp riding each queue tuple — never
    # touches a device value, so it is transfer-guard-safe by
    # construction
    # ------------------------------------------------------------------
    def _account(self, chunk: list, kind: str, t_disp: float,
                 t_done: float, disp) -> None:
        if self._tracing:
            self._req_spans.append((chunk, t_disp, t_done, disp))
        h_e2e = self._h_e2e[kind]
        t_flush = self._t_flush
        batch_wait_ms = (t_disp - t_flush) * 1e3
        service_ms = (t_done - t_disp) * 1e3
        deadlines = self._deadlines
        for req in chunk:
            t_enq = req[3]
            e2e_ms = (t_done - t_enq) * 1e3
            h_e2e.observe(e2e_ms)
            self._h_queue_wait.observe((t_flush - t_enq) * 1e3)
            self._h_batch_wait.observe(batch_wait_ms)
            self._h_service.observe(service_ms)
            if deadlines:
                dl = deadlines.get(ticket_client(req[0]))
                if dl is not None:
                    self._slo.observe(dl, e2e_ms)

    def _record_requests(self, t_flush: float, t_ret: float) -> None:
        """Traced only: four spans per request answered by this flush,
        ``req_queue`` (arrival -> flush start), ``req_batch`` (-> its
        round's dispatch), ``req_service`` (-> result pickup or last
        flag readback) and ``req_hold`` (-> the flush's return).  They
        tile the request's latency; each takes the ticket as its id and
        the round's first ``dispatch`` span as its parent."""
        f0, f1 = int(t_flush * 1e9), int(t_ret * 1e9)
        spans = []
        for chunk, t_disp, t_done, disp in self._req_spans:
            d0, d1 = int(t_disp * 1e9), int(t_done * 1e9)
            for ticket, kind, _, t_enq in chunk:
                args = {"kind": kind}
                spans += (("req_queue", int(t_enq * 1e9), f0, ticket, disp,
                           args),
                          ("req_batch", f0, d0, ticket, disp, args),
                          ("req_service", d0, d1, ticket, disp, args),
                          ("req_hold", d1, f1, ticket, disp, args))
        self.obs.tracer.record_many(spans)
        self._req_spans.clear()

    # ------------------------------------------------------------------
    # host-side batch packing (the half that double-buffers)
    # ------------------------------------------------------------------
    def _pack(self, kind: str, chunk: list, bucket: int):
        if kind == QUERY:
            q = np.zeros((bucket, self._dim), np.float32)
            for r, (_, _, (vec, _), _) in enumerate(chunk):
                q[r] = vec
            return (jnp.asarray(q), chunk[0][2][1])
        if kind == INSERT or kind == UPDATE:
            ids = np.zeros((bucket,), np.int32)
            vecs = np.zeros((bucket, self._dim), np.float32)
            mask = np.zeros((bucket,), bool)
            for r, (_, _, (vid, vec), _) in enumerate(chunk):
                ids[r], vecs[r], mask[r] = vid, vec, True
            ins = (jnp.asarray(ids), jnp.asarray(vecs), jnp.asarray(mask))
            if kind == INSERT:
                return ins
            return {"del": (ins[0], ins[2]), "ins": ins}
        # DELETE
        ids = np.zeros((bucket,), np.int32)
        mask = np.zeros((bucket,), bool)
        for r, (_, rkind, payload, _) in enumerate(chunk):
            ids[r] = payload if rkind == DELETE else payload[0]
            mask[r] = True
        return (jnp.asarray(ids), jnp.asarray(mask))

    # ------------------------------------------------------------------
    # device rounds (all flag-word driven; see module docstring)
    # ------------------------------------------------------------------
    def _maintain(self, flags: int) -> None:
        before = len(self.backend.maintenance_log)
        self.backend.maintain(flags)
        for ev in self.backend.maintenance_log[before:]:
            self.events.append((ev, self.n_flushes))

    def _query_batch(self, packed, chunk: list, bucket: int, out: dict,
                     overlap=None) -> int | None:
        q_d, k = packed
        # the backend invokes overlap() itself, right after its first
        # device dispatch (the cold fetch loop would otherwise block to
        # completion before the engine could start packing batch t+1)
        with self.obs.span("dispatch", kind=QUERY, bucket=bucket,
                           rows=len(chunk)) as disp:
            ids, dists = self.backend.query_rows(q_d, k, overlap=overlap)
        self.n_rounds_by_kind[QUERY] += 1
        with self.obs.span("result_pickup", kind=QUERY):
            ids, dists = jax.device_get((ids, dists))
        for r, (ticket, _, _, _) in enumerate(chunk):
            out[ticket] = (ids[r], dists[r])
        return disp.id

    def _insert_batch(self, packed, chunk: list, bucket: int, out,
                      stat_kind: str = INSERT, overlap=None) -> int | None:
        be = self.backend
        ids_d, vecs_d, mask = packed
        carry = be.insert_begin(bucket)
        main_active = mask
        lsh_active = jnp.repeat(mask, be.cfg.L)
        flags = be.ensure_flags()
        for r in range(self.MAX_ROUNDS):
            self._maintain(flags)
            with self.obs.span("dispatch", kind=stat_kind, bucket=bucket,
                               rows=len(chunk)) as disp:
                carry, main_active, lsh_active, fw = be.insert_round(
                    ids_d, vecs_d, carry, main_active, lsh_active, bucket)
            if r == 0:
                first = disp.id
            self.n_rounds += 1
            self.n_rounds_by_kind[stat_kind] += 1
            if r == 0 and overlap is not None:
                overlap()
            with self.obs.span("flag_readback", kind=stat_kind):
                flags = be.read_flags(fw)
            be.after_flags(flags)
            if self._obs_on and flags:
                for bit, c in self._c_flags:
                    if flags & bit:
                        c.inc()
            if not flags & FLAG_ANY_PENDING:
                break
        be.count_insert(len(chunk))
        if out is not None:
            for ticket, _, _, _ in chunk:
                out[ticket] = "ok"
        return first

    def _delete_batch(self, packed, chunk: list, bucket: int, out,
                      stat_kind: str = DELETE, overlap=None) -> int | None:
        be = self.backend
        ids_d, active = packed
        flags = be.ensure_flags()
        for r in range(self.MAX_ROUNDS):
            self._maintain(flags)
            with self.obs.span("dispatch", kind=stat_kind, bucket=bucket,
                               rows=len(chunk)) as disp:
                pending, fw = be.delete_round(ids_d, active, bucket)
            if r == 0:
                first = disp.id
            self.n_rounds += 1
            self.n_rounds_by_kind[stat_kind] += 1
            if r == 0 and overlap is not None:
                overlap()
            with self.obs.span("flag_readback", kind=stat_kind):
                flags = be.read_flags(fw)
            be.after_flags(flags)
            if self._obs_on and flags:
                for bit, c in self._c_flags:
                    if flags & bit:
                        c.inc()
            if not flags & FLAG_ANY_PENDING:
                break
            active = pending
        if out is not None:
            for ticket, _, _, _ in chunk:
                out[ticket] = "ok"
        return first

    # ------------------------------------------------------------------
    # explicit epochs + stats
    # ------------------------------------------------------------------
    def seal(self) -> None:
        """Force a seal epoch (hot tier -> sealed snapshots)."""
        self.backend.force_seal()
        self.events.append(("seal", self.n_flushes))

    def merge(self) -> None:
        """Force a merge epoch (compaction + tombstone drain)."""
        self.backend.force_merge()
        self.events.append(("merge", self.n_flushes))

    def stats(self) -> dict:
        update_rounds = self.n_rounds
        readbacks = self.backend.sync_count
        return {
            "requests": self.n_requests,
            "flushes": self.n_flushes,
            "batches": self.n_batches,
            "rounds": self.n_rounds,
            "rounds_by_kind": dict(self.n_rounds_by_kind),
            "readbacks": readbacks,
            # steady state this is exactly 1.0; warmup/capacity-growth
            # flag probes can push it epsilon above (assert on deltas).
            # The derivation (incl. the zero-rounds guard) lives in
            # repro.obs.report so this view and Obs.snapshot() agree.
            "readbacks_per_round": obs_report.per_round(readbacks,
                                                        update_rounds),
            "syncs": readbacks,
            "seals": sum(1 for e, _ in self.events if e == "seal"),
            "merges": sum(1 for e, _ in self.events if e == "merge"),
            "spills": sum(1 for e, _ in self.events if e == "spill"),
            "buckets": list(self.scfg.buckets),
            "clients": 1 + len(self._clients),
            "deadline_clients": len(self._deadlines),
            "cold": self.backend.cold_stats(),
        }


class DistStreamEngine(StreamEngine):
    """Distributed stream engine: the same bucket/ordering/flag-word
    machinery serving an interleaved stream against a mesh-sharded
    ``PFOState`` (see the backend-interface section of the module
    docstring).  Construct with a ``core.distributed.DistConfig`` and a
    ``(data, model)`` mesh (``sharding.policy.stream_mesh`` builds one
    on host-platform virtual devices for tests/CI)."""

    def __init__(self, dcfg, mesh=None, scfg: StreamConfig | None = None,
                 seed: int = 0, obs: Obs | None = None,
                 cold_dir: str | None = None):
        if mesh is None:
            from repro.sharding.policy import stream_mesh
            mesh = stream_mesh(dcfg.n_model)
        scfg = scfg or StreamConfig()
        n_data = int(np.prod([mesh.devices.shape[mesh.axis_names.index(a)]
                              for a in dcfg.batch_axes]))
        assert scfg.min_batch % n_data == 0, \
            "query buckets must divide across the batch axes"
        super().__init__(DistBackend(dcfg, mesh, seed=seed,
                                     cold_dir=cold_dir), scfg, obs=obs)


# ======================================================================
# closed-loop driver (benchmarks / examples)
# ======================================================================
def drive(engine: StreamEngine, requests: list[tuple], flush_every: int = 0):
    """Feed ``(kind, *args)`` request tuples through the engine.

    ``flush_every`` > 0 flushes after that many submissions (latency
    mode); 0 flushes once at the end (throughput mode).  Returns
    ({ticket: result}, elapsed seconds, per-flush latencies).
    """
    results: dict[int, Any] = {}
    lat: list[float] = []
    t0 = time.perf_counter()
    n = 0
    for req in requests:
        kind, args = req[0], req[1:]
        getattr(engine, kind)(*args)
        n += 1
        if flush_every and n % flush_every == 0:
            f0 = time.perf_counter()
            results.update(engine.flush())
            lat.append(time.perf_counter() - f0)
    if engine.pending():
        f0 = time.perf_counter()
        results.update(engine.flush())
        lat.append(time.perf_counter() - f0)
    return results, time.perf_counter() - t0, lat
