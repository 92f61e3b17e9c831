"""Seeded data generators, kept with the benchmark so that no change to the
program can move the yardstick.  Both draw on the device.

``video_like_matrix`` is the paper's dense video use case (Table I): a
static rank-20 background and sparse moving-object outliers.  Row i is a
function of (key, i) alone, so any row range or device block is drawn
without the rest.  ``erdos_renyi_triplets`` is the paper's sec. 6.1.1
sparse synthetic: uniform positions and U(0, 1) values, repeats dropped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def video_like_matrix(key, m: int, n: int, *, rank: int = 20,
                      motion: float = 0.05, sharding=None):
    """Rows 0 .. m-1 of the video-like fp32 matrix; ``sharding`` (a
    ``NamedSharding``) makes each device generate its own block."""
    return _video_rows(m, n, rank, float(motion), sharding)(key)


@functools.lru_cache(maxsize=None)
def _video_rows(m, n, rank, motion, sharding):
    def gen(key):
        kh, kw, km, ko = jax.random.split(key, 4)
        H = jax.random.uniform(kh, (rank, n), jnp.float32)
        rows = jnp.arange(m, dtype=jnp.int32)

        def per_row(k, draw):
            return jax.vmap(lambda i: draw(jax.random.fold_in(k, i)))(rows)

        W = per_row(kw, lambda k: jax.random.uniform(k, (rank,), jnp.float32))
        mask = per_row(km, lambda k: jax.random.bernoulli(k, motion, (n,)))
        obj = per_row(ko, lambda k: jax.random.uniform(k, (n,), jnp.float32))
        A = jnp.matmul(W, H, precision=HIGHEST)
        return jnp.where(mask, A + obj, A)

    return jax.jit(gen, out_shardings=sharding)


def erdos_renyi_triplets(key, m: int, n: int, density: float):
    """Row-major sorted host triplets (rows, cols, vals) of an m x n
    Erdős–Rényi matrix: ``round(density·m·n)`` uniform positions drawn on
    the device, repeats and zero values dropped on the host."""
    count = int(round(density * m * n))
    kr, kc, kv = jax.random.split(key, 3)
    rows = np.asarray(jax.random.randint(kr, (count,), 0, m), np.int64)
    cols = np.asarray(jax.random.randint(kc, (count,), 0, n), np.int64)
    vals = np.asarray(jax.random.uniform(kv, (count,), jnp.float32))
    _, first = np.unique(rows * n + cols, return_index=True)
    first = first[vals[first] != 0.0]
    return (rows[first].astype(np.int32), cols[first].astype(np.int32),
            vals[first])


def initial_factors(key, m: int, n: int, k: int, positive_w: bool):
    """W0 (m, k) and H0 (k, n), fp32: H0 uniform (paper sec. 6.1.3); W0
    uniform in [0.1, 1) for multiplicative rules, else zero (rules that
    solve for W ignore it)."""
    H0 = jax.random.uniform(key, (k, n), jnp.float32)
    if positive_w:
        W0 = jax.random.uniform(jax.random.fold_in(key, 1), (m, k),
                                jnp.float32, minval=0.1, maxval=1.0)
    else:
        W0 = jnp.zeros((m, k), jnp.float32)
    return W0, H0
