"""Relative-error computation for NMF via the trace trick (paper §6.2).

relative_error = ||A − WH||_F / ||A||_F, expanded as

    ||A − WH||² = ||A||² − 2·tr(Wᵀ A Hᵀ) + tr((WᵀW)(HHᵀ))

so it never materialises WH (m×n) and, in the distributed setting, reuses
the iteration's byproducts:  tr(WᵀA·Hᵀ) = Σ (WᵀA ⊙ H) — both already
distributed column-wise — and the two k×k Grams.  ||A||² is computed once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def sq_frobenius(A: jax.Array) -> jax.Array:
    """‖A‖_F² in fp32, compiled so the squares fuse into the sum: run op by
    op, ``A * A`` would first materialise a second copy of A."""
    A32 = A.astype(jnp.float32)
    return jnp.sum(A32 * A32)


def sq_error_from_products(normA_sq: jax.Array, WtA: jax.Array, H: jax.Array,
                           WtW: jax.Array, HHt: jax.Array) -> jax.Array:
    """||A − WH||² from byproducts.  WtA, H are (k, n_local) shards (or full),
    WtW/HHt are the replicated k×k Grams of the *current* W and H."""
    cross = jnp.sum(WtA.astype(jnp.float32) * H.astype(jnp.float32))
    quad = jnp.sum(WtW.astype(jnp.float32) * HHt.astype(jnp.float32))
    return normA_sq - 2.0 * cross + quad


def relative_error(A: jax.Array, W: jax.Array, H: jax.Array) -> jax.Array:
    """Direct (serial, small-problem) relative error."""
    normA_sq = sq_frobenius(A)
    WtA = W.T @ A
    sq = sq_error_from_products(normA_sq, WtA, H, W.T @ W, H @ H.T)
    return jnp.sqrt(jnp.maximum(sq, 0.0)) / jnp.sqrt(normA_sq)
