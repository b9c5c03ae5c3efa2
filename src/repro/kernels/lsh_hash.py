"""Pallas TPU kernel: fused LSH compound-key computation (paper §2.1).

Computes ``pack_bits(sign(X @ A))`` — the hot path of every PFO insert
and query (both PHF levels re-hash through it).  The matmul rides the
MXU; sign+bitpack fuse into the epilogue so the (N, P) f32 projection
matrix never round-trips to HBM — only the packed uint32 keys leave
VMEM.  That epilogue fusion is the TPU counterpart of the paper's
"compute hash values in the computing threads before dispatch" (§4.2):
hashing is bandwidth-lean, dispatch-ready output.

Grid: (N/bn, d/bk), k innermost; an f32 VMEM scratch accumulates the
(bn, P) projection tile across k steps.  The whole (padded) P extent
is one block, so the epilogue sees every bit of a row at once.

Bit packing runs on the MXU: the 0/1 sign bits are contracted against
two constant weight matrices that place bit ``p`` of word ``p // 32``
at weight ``2^(15 - p % 16)`` — ``pack_hi`` holds the word's 16 MSBs,
``pack_lo`` its 16 LSBs (MSB-first, matching Def. 2's prefix order).
Every product is a power of two times 0/1 and every partial sum stays
below 2^16, so the contraction is exact at any MXU precision; the
halves combine with int32 shifts and one bitcast to uint32 — no
unsigned reduction and no lane-splitting reshape.  The packed output
is ``W`` lanes wide (``W`` a multiple of 128, lane-dense stores);
ops.py slices the ``P // 32`` real words.

Alignment contract: bn % 8 == 0, P % 128 == 0, bk % 128 == 0, W % 128
== 0; callers pad (see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pack_weights(p: int, w: int) -> tuple[np.ndarray, np.ndarray]:
    """(P, W) f32 hi/lo bit-pack matrices for P sign bits into W words."""
    bit = np.arange(p)
    word, pos = bit // 32, bit % 32
    hi = np.zeros((p, w), np.float32)
    lo = np.zeros((p, w), np.float32)
    upper = pos < 16
    hi[bit[upper], word[upper]] = 2.0 ** (15 - pos[upper])
    lo[bit[~upper], word[~upper]] = 2.0 ** (31 - pos[~upper])
    return hi, lo


def _kernel(x_ref, a_ref, hi_ref, lo_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # full f32 contraction: a reduced-precision pass flips the sign of
    # projections near zero, and the key is sign(X @ A) as ref.py has it
    acc_ref[...] += jnp.dot(x_ref[...], a_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        bits = (acc_ref[...] >= 0.0).astype(jnp.float32)       # (bn, P)
        hi = jnp.dot(bits, hi_ref[...], preferred_element_type=jnp.float32)
        lo = jnp.dot(bits, lo_ref[...], preferred_element_type=jnp.float32)
        word = (hi.astype(jnp.int32) << 16) | lo.astype(jnp.int32)
        out_ref[...] = jax.lax.bitcast_convert_type(word, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def lsh_hash_pallas(x: jax.Array, a: jax.Array, pack_hi: jax.Array,
                    pack_lo: jax.Array, *, bn: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """(N, d) f32 x (d, P) f32 -> (N, W) uint32 packed sign keys, with
    ``pack_hi``/``pack_lo`` the (P, W) matrices of :func:`pack_weights`.

    Requires N % bn == 0, P % 128 == 0, d % bk == 0 (ops.py pads).
    """
    n, d = x.shape
    d2, p = a.shape
    w = pack_hi.shape[1]
    assert d == d2 and pack_hi.shape == pack_lo.shape == (p, w)
    assert n % bn == 0 and d % bk == 0 and p % 128 == 0 and w % 128 == 0
    n_k = d // bk

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, p), lambda i, k: (k, 0)),
            pl.BlockSpec((p, w), lambda i, k: (0, 0)),
            pl.BlockSpec((p, w), lambda i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, w), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((bn, p), jnp.float32)],
        interpret=interpret,
        name="lsh_hash",
    )(x, a, pack_hi, pack_lo)
