"""Pallas TPU kernel: fused candidate gather + exact re-rank (§3.1).

The masked bucket traversal (core/hash_tree.py) hands query_step a
dense ``(Q, C)`` block of store *slot ids* plus a validity mask.  This
kernel finishes the read path in one pass: per query block it gathers
the candidate vectors straight out of the ``(N, d)`` store by slot id,
contracts them against the query rows, converts to the metric's
distance, and masks invalid slots to +inf — the ``(Q, C, d)``
candidate tensor never exists in HBM.

Grid: (Q/bq,) — one program per block of ``bq`` queries.  The store
stays in HBM (``memory_space=pl.ANY``); the block's ``bq*C`` slot ids
arrive in SMEM, and the program issues one row DMA per candidate into
a ``(bq*C, d)`` f32 VMEM scratch, waits for all of them, then ranks
from VMEM.  Each query's distances are one ``(bq, d) x (C, d)^T`` MXU
contraction at full f32 precision (the row of the query's own block is
kept); the row norms come from a ones-vector contraction, so every
per-candidate quantity lands lane-major in the ``(bq, C)`` output tile.

ops.py adds the masked top-k epilogue (``gather_rank_topk``) so
callers see one fused call.

The **staged** variant (``gather_rank_staged_pallas``) is the tiered
vector store's ranking path: slot ids ``>= n_rows`` address rows of a
second *staging arena* (the cold tier's cache-resident payload pages,
also left in HBM) at offset ``slot - n_rows``.  Only the DMA source
differs per candidate; the row lands in the same VMEM scratch and the
distance arithmetic is the plain kernel's, so a candidate served from
staging ranks bit-identically to the same vector in the dense store —
the cold-vs-all-device differential harness relies on that.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # contract both operands' last dim


def _row_copy(src_ref, row, buf_ref, j, sem):
    return pltpu.make_async_copy(src_ref.at[pl.ds(row, 1), :],
                                 buf_ref.at[pl.ds(j, 1), :], sem)


def _rank(q_ref, valid_ref, out_ref, buf_ref, *, c: int, angular: bool):
    """Distances of each query row against its ``c`` gathered rows."""
    q = q_ref[...].astype(jnp.float32)                   # (bq, d)
    bq, dim = q.shape
    ones = jnp.ones((bq, dim), jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, c), 0)
    dots = jnp.zeros((bq, c), jnp.float32)
    xs = jnp.zeros((bq, c), jnp.float32)
    for i in range(bq):
        x = buf_ref[pl.ds(i * c, c), :]                  # (c, d)
        mine = rows == i
        dots = jnp.where(mine, jax.lax.dot_general(
            q, x, _NT, precision=_HIGHEST,
            preferred_element_type=jnp.float32), dots)
        xs = jnp.where(mine, jax.lax.dot_general(
            ones, x * x, _NT, precision=_HIGHEST,
            preferred_element_type=jnp.float32), xs)
    if angular:
        # queries arrive pre-normalized (ops.py); normalize the rows
        d = 1.0 - dots / jnp.maximum(jnp.sqrt(xs), 1e-9)
    else:
        qs = jnp.sum(q * q, axis=-1, keepdims=True)
        d = jnp.maximum(qs + xs - 2.0 * dots, 0.0)
    out_ref[...] = jnp.where(valid_ref[...] != 0, d, jnp.inf)


def _kernel(slots_ref, q_ref, valid_ref, store_ref, out_ref, buf_ref, sem,
            *, n_rows: int, c: int, angular: bool):
    n = buf_ref.shape[0]

    def start(j, carry):
        row = jnp.clip(slots_ref[j], 0, n_rows - 1)
        _row_copy(store_ref, row, buf_ref, j, sem).start()
        return carry

    def wait(j, carry):
        _row_copy(store_ref, 0, buf_ref, j, sem).wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    _rank(q_ref, valid_ref, out_ref, buf_ref, c=c, angular=angular)


def _kernel_staged(slots_ref, q_ref, valid_ref, store_ref, staging_ref,
                   out_ref, buf_ref, sem, *, n_rows: int, n_staging: int,
                   c: int, angular: bool):
    n = buf_ref.shape[0]

    def start(j, carry):
        slot = slots_ref[j]

        @pl.when(slot < n_rows)
        def _hot():
            row = jnp.clip(slot, 0, n_rows - 1)
            _row_copy(store_ref, row, buf_ref, j, sem).start()

        @pl.when(slot >= n_rows)
        def _staged():
            row = jnp.clip(slot - n_rows, 0, n_staging - 1)
            _row_copy(staging_ref, row, buf_ref, j, sem).start()

        return carry

    def wait(j, carry):
        _row_copy(store_ref, 0, buf_ref, j, sem).wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)
    _rank(q_ref, valid_ref, out_ref, buf_ref, c=c, angular=angular)


def _call(kernel, name, q, slots, valid, arenas, *, bq: int,
          interpret: bool):
    nq, dim = q.shape
    nq2, c = slots.shape
    assert nq == nq2 and slots.shape == valid.shape
    assert nq % bq == 0 and c % 128 == 0
    assert all(a.shape[1] == dim and a.dtype == jnp.float32
               for a in arenas)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid=(nq // bq,),
        in_specs=[
            pl.BlockSpec((bq * c,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, dim), lambda i: (i, 0)),
            pl.BlockSpec((bq, c), lambda i: (i, 0)),
        ] + [hbm] * len(arenas),
        out_specs=pl.BlockSpec((bq, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nq, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq * c, dim), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
        interpret=interpret,
        name=name,
    )(slots.reshape(-1), q, valid, *arenas)


@functools.partial(jax.jit,
                   static_argnames=("bq", "angular", "interpret"))
def gather_rank_pallas(q: jax.Array, store: jax.Array, slots: jax.Array,
                       valid: jax.Array, *, bq: int = 8,
                       angular: bool = True,
                       interpret: bool = False) -> jax.Array:
    """(Q, d) f32, (N, d) f32, (Q, C) i32, (Q, C) i32 -> (Q, C) f32.

    Distances of each query against the store rows named by its slot
    ids; invalid (mask == 0) positions come back +inf.  Requires
    Q % bq == 0 and C % 128 == 0 (ops.py pads).
    """
    kernel = functools.partial(_kernel, n_rows=store.shape[0],
                               c=slots.shape[1], angular=angular)
    return _call(kernel, "gather_rank", q, slots, valid, [store], bq=bq,
                 interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("bq", "angular", "interpret"))
def gather_rank_staged_pallas(q: jax.Array, store: jax.Array,
                              staging: jax.Array, slots: jax.Array,
                              valid: jax.Array, *, bq: int = 8,
                              angular: bool = True,
                              interpret: bool = False) -> jax.Array:
    """Tiered-store variant: slots ``>= store rows`` gather from the
    ``staging`` arena at ``slot - n_rows``.  Same shapes/semantics as
    :func:`gather_rank_pallas` otherwise."""
    kernel = functools.partial(_kernel_staged, n_rows=store.shape[0],
                               n_staging=staging.shape[0],
                               c=slots.shape[1], angular=angular)
    return _call(kernel, "gather_rank_staged", q, slots, valid,
                 [store, staging], bq=bq, interpret=interpret)
