"""Pallas TPU kernel: Gram matrix C = XᵀX of a tall-skinny operand.

This is the paper's lines 3/9 hot spot (local Gram of the factor panel).
The factor has k ≪ m columns, so the k×k accumulator lives in VMEM for the
whole pass and X streams HBM→VMEM in row panels of ``block_m`` — a single
read of X, the roofline optimum for this memory-bound shape.

Tiling: grid over row panels; X tile (block_m, k) feeds the MXU as a
(k × block_m)·(block_m × k) contraction with fp32 accumulation.  k is padded
to a multiple of 128 by ops.py so the MXU systolic array is fully used.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backends.base import ATB, dot


def _gram_kernel(x_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    o_ref[...] += dot(x, x, ATB)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def gram(X: jax.Array, *, block_m: int = 512, interpret: bool = False
         ) -> jax.Array:
    """XᵀX for X of shape (m, k); m % block_m == 0, k MXU-aligned (ops.py
    handles padding for arbitrary shapes)."""
    m, k = X.shape
    assert m % block_m == 0, (m, block_m)
    return pl.pallas_call(
        _gram_kernel,
        grid=(m // block_m,),
        in_specs=[pl.BlockSpec((block_m, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((k, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.float32),
        interpret=interpret,
    )(X)
