"""Public jit'd wrappers around the Pallas kernels.

Responsibilities: shape padding to kernel alignment, interpret-mode
selection (on the CPU backend the kernel bodies run in Pallas
interpret mode; on any other backend they are always compiled), and
small epilogues (distance finalize, masking) that don't belong in the
kernels.  The ranking hot path is :func:`gather_rank`: a DMA row
gather that leaves the store (and the cold tier's staging arena) in
HBM and fetches each candidate row by slot id into VMEM
(``kernels/gather_rank.py``).  ``REPRO_PALLAS=off`` falls back to the
ref.py oracles end-to-end, which is also the path the 512-device
dry-run uses (Pallas does not lower on the host platform).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref
from .gather_rank import gather_rank_pallas, gather_rank_staged_pallas
from .hamming import hamming_pallas
from .lsh_hash import lsh_hash_pallas, pack_weights
from .pair_dist import pair_dist_pallas
from .rank_candidates import rank_dots_pallas


def _use_pallas() -> bool:
    return os.environ.get("REPRO_PALLAS", "on") != "off"


def _interpret() -> bool:
    """Interpret mode exactly on the CPU backend: a chip always runs
    the compiled kernels."""
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ----------------------------------------------------------------------
def lsh_hash(x: jax.Array, table_proj: jax.Array, M: int = 32) -> jax.Array:
    """(N, d) -> (N, L) uint32 compound keys (L = P // M columns)."""
    n, d = x.shape
    p = table_proj.shape[1]
    assert p % M == 0 and M == 32
    if not _use_pallas():
        return ref.ref_lsh_hash(x, table_proj)
    bn, bk = 128, 128
    xp = _pad_to(_pad_to(x, 0, bn), 1, bk)
    ap = _pad_to(_pad_to(table_proj, 0, bk), 1, 128)
    hi, lo = _pack_weights(ap.shape[1])
    out = lsh_hash_pallas(xp, ap, hi, lo, bn=bn, bk=bk,
                          interpret=_interpret())
    return out[:n, :p // 32]


@functools.lru_cache(maxsize=None)
def _pack_weights(p: int):
    words = p // 32
    return pack_weights(p, words + (-words) % 128)


def rank_dots(q: jax.Array, x: jax.Array) -> jax.Array:
    """(Q, d) x (Q, C, d) -> (Q, C) inner products."""
    nq, d = q.shape
    c = x.shape[1]
    if not _use_pallas():
        return ref.ref_rank_dots(q, x)
    bq, bc, bk = 8, 128, 128
    qp = _pad_to(_pad_to(q, 0, bq), 1, bk)
    xp = _pad_to(_pad_to(_pad_to(x, 0, bq), 1, bc), 2, bk)
    out = rank_dots_pallas(qp, xp, bq=bq, bc=bc, bk=bk,
                           interpret=_interpret())
    return out[:nq, :c]


def pair_dist_sq(q: jax.Array, x: jax.Array) -> jax.Array:
    """(Q, d) x (N, d) -> (Q, N) squared L2 distances."""
    nq, n = q.shape[0], x.shape[0]
    if not _use_pallas():
        return ref.ref_pair_dist(q, x)
    bq, bn, bk = 128, 128, 256
    qp = _pad_to(_pad_to(q, 0, bq), 1, bk)
    xp = _pad_to(_pad_to(x, 0, bn), 1, bk)
    out = pair_dist_pallas(qp, xp, bq=bq, bn=bn, bk=bk,
                           interpret=_interpret())
    return out[:nq, :n]


def hamming(a: jax.Array, b: jax.Array) -> jax.Array:
    """(Q, W) u32 x (N, W) u32 -> (Q, N) i32 bit differences."""
    nq, n = a.shape[0], b.shape[0]
    if not _use_pallas():
        return ref.ref_hamming(a, b)
    bq, bn = 128, 128
    ap = _pad_to(a, 0, bq)
    bp = _pad_to(b, 0, bn)
    out = hamming_pallas(ap, bp, bq=bq, bn=bn, interpret=_interpret())
    return out[:nq, :n]


def gather_rank(q: jax.Array, store: jax.Array, slots: jax.Array,
                valid: jax.Array, metric: str,
                staging: jax.Array | None = None) -> jax.Array:
    """Fused candidate gather + exact re-rank distances.

    (Q, d), (N, d) store, (Q, C) i32 slot ids, (Q, C) bool -> (Q, C)
    f32 distances, +inf where invalid.  Candidate vectors are gathered
    by slot id inside the kernel — no (Q, C, d) block materializes.
    ``staging`` (M, d) enables the tiered-store path: slots ``>= N``
    gather staging row ``slot - N`` (the cold tier's device payload
    arena).  ``staging=None`` keeps the exact pre-tiered program.
    """
    nq, c = slots.shape
    if not _use_pallas():
        return ref.ref_gather_rank(q, store, slots, valid, metric,
                                   staging=staging)
    if metric == "angular":
        q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
    bq, bc = 8, 128
    qp = _pad_to(q.astype(jnp.float32), 0, bq)
    sp = _pad_to(_pad_to(slots.astype(jnp.int32), 0, bq), 1, bc)
    vp = _pad_to(_pad_to(valid.astype(jnp.int32), 0, bq), 1, bc)
    if staging is None:
        out = gather_rank_pallas(qp, store.astype(jnp.float32), sp, vp,
                                 bq=bq, angular=(metric == "angular"),
                                 interpret=_interpret())
    else:
        out = gather_rank_staged_pallas(
            qp, store.astype(jnp.float32), staging.astype(jnp.float32),
            sp, vp, bq=bq, angular=(metric == "angular"),
            interpret=_interpret())
    return out[:nq, :c]


def gather_rank_topk(q: jax.Array, store: jax.Array, slots: jax.Array,
                     valid: jax.Array, k: int, metric: str,
                     staging: jax.Array | None = None):
    """One fused call for the ranking hot path: gather by slot id,
    distance, masked top-k.  Returns (idx (Q, k) into the candidate
    axis, dists (Q, k) with +inf past the valid set)."""
    d = gather_rank(q, store, slots, valid, metric, staging=staging)
    neg, idx = jax.lax.top_k(-d, k)
    return idx, -neg


# ----------------------------------------------------------------------
# epilogues used by core.index
# ----------------------------------------------------------------------
def pairwise_rank(q: jax.Array, cand: jax.Array, valid: jax.Array,
                  metric: str) -> jax.Array:
    """Exact re-rank distances: (Q,d), (Q,C,d), (Q,C) -> (Q,C) f32.

    Invalid candidates get +inf so downstream top-k drops them.
    """
    if metric == "angular":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        xn = cand / jnp.maximum(
            jnp.linalg.norm(cand, axis=-1, keepdims=True), 1e-9)
        dots = rank_dots(qn, xn)
        d = 1.0 - dots
    else:
        dots = rank_dots(q, cand)
        qs = jnp.sum(q * q, axis=-1)[:, None]
        xs = jnp.sum(cand * cand, axis=-1)
        d = jnp.maximum(qs + xs - 2.0 * dots, 0.0)
    return jnp.where(valid, d, jnp.inf)


def brute_force_topk(q: jax.Array, x: jax.Array, k: int, metric: str,
                     valid: jax.Array | None = None):
    """Oracle kNN over the whole store: (Q,d),(N,d) -> ids,d (Q,k)."""
    if metric == "angular":
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-9)
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)
        # for unit vectors |q-x|^2 = 2 - 2 cos => angular = |q-x|^2 / 2
        d = 0.5 * pair_dist_sq(qn, xn)
    else:
        d = pair_dist_sq(q, x)
    if valid is not None:
        d = jnp.where(valid[None, :], d, jnp.inf)
    neg, idx = jax.lax.top_k(-d, k)
    return idx, -neg
