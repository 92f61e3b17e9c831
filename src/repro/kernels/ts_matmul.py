"""Pallas TPU kernels: tall-skinny matrix products V = A·B and Y = Aᵀ·B.

These are the paper's lines 6/12 hot spots — the only operations that touch
the (huge) local data block A_ij.  The skinny operand (k columns, k ≪ m, n)
stays VMEM-resident per grid row while A streams through once:

  * ``ts_matmul``  : (bm × bn) A-tiles × (bn × k) B-tiles, accumulate over n;
  * ``ts_matmul_t``: (bm × bn) A-tiles × (bm × k) B-tiles, accumulate over m,
    contracting A's *row* dimension so Aᵀ is never materialised in HBM
    (the H-step needs AᵀW; a physical transpose of A would double the
    iteration's HBM traffic).

Accumulation is fp32 in VMEM (out tile revisited across the contraction
grid dimension, which is innermost so the output block stays resident).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.backends.base import ATB, dot


def _mask_tail(a, start, size: int, axis: int):
    """Zero the entries of tile ``a`` at global index ``start + i >= size``
    along ``axis``: the edge tile of a ragged grid reads past the array, and
    what it reads there is undefined (interpret mode fills it with NaN)."""
    idx = start + lax.broadcasted_iota(jnp.int32, a.shape, axis)
    return jnp.where(idx < size, a, jnp.zeros_like(a))


def _ab_kernel(a_ref, b_ref, o_ref, *, n: int, block_n: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    if n % block_n:
        a = _mask_tail(a, j * block_n, n, axis=1)
    o_ref[...] += dot(a, b_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def ts_matmul(A: jax.Array, B: jax.Array, *, block_m: int = 256,
              block_n: int = 512, interpret: bool = False) -> jax.Array:
    """A (m, n) @ B (n', k) -> (m, k) fp32.

    The grid is ``cdiv`` in both dims, so A is never padded (a copy of A
    would double its footprint): the ragged last row tile writes only its
    in-bounds rows, and the ragged last contraction tile is masked.  B
    carries ``n' >= n`` rows, zero beyond n, a whole number of
    ``block_n`` tiles."""
    m, n = A.shape
    n2, k = B.shape
    assert n2 == pl.cdiv(n, block_n) * block_n, (A.shape, B.shape, block_n)
    return pl.pallas_call(
        functools.partial(_ab_kernel, n=n, block_n=block_n),
        grid=(pl.cdiv(m, block_m), pl.cdiv(n, block_n)),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(A, B)


def _atb_kernel(a_ref, b_ref, o_ref, *, m: int, block_m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]
    if m % block_m:
        a = _mask_tail(a, j * block_m, m, axis=0)
    o_ref[...] += dot(a, b_ref[...], ATB)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def ts_matmul_t(A: jax.Array, B: jax.Array, *, block_m: int = 512,
                block_n: int = 256, interpret: bool = False) -> jax.Array:
    """Aᵀ·B for A (m, n), B (m', k) -> (n, k) fp32, streaming A
    untransposed and unpadded; B carries ``m' >= m`` rows, zero beyond m,
    a whole number of ``block_m`` tiles (see ``ts_matmul``)."""
    m, n = A.shape
    m2, k = B.shape
    assert m2 == pl.cdiv(m, block_m) * block_m, (A.shape, B.shape, block_m)
    return pl.pallas_call(
        functools.partial(_atb_kernel, m=m, block_m=block_m),
        grid=(pl.cdiv(n, block_n), pl.cdiv(m, block_m)),
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (j, i)),
            pl.BlockSpec((block_m, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(A, B)
