"""Block Principal Pivoting (BPP) solver for nonnegative least squares.

Solves, for each right-hand side b (a row of ``R``):

    min_{x >= 0} || C x - b ||_2

given the precomputed normal-equation matrices ``G = CᵀC`` (k×k) and
``R = (CᵀB)ᵀ`` (r×k, one row per right-hand side), exactly as the paper's
``SolveBPP(CᵀC, CᵀB)`` subroutine (Kim & Park 2011, Algorithm 2).

The KKT conditions for a single column are

    y = G x - r,   x >= 0,   y >= 0,   x ⊙ y = 0,

with complementary supports: the *passive* set P holds indices with x_i free
(y_i = 0) and the *active* set holds x_i = 0 (y_i free).  BPP greedily swaps
infeasible indices between the two sets — full exchanges while they keep
shrinking the infeasible set, falling back to Murty's single-index rule
(largest infeasible index) to guarantee finite termination.

This implementation is a faithful, fully vectorised JAX port:

* all right-hand sides are solved simultaneously (state tensors carry a
  leading ``r`` axis) under a single ``jax.lax.while_loop``;
* the passive-set least-squares solve uses the masked normal equations
  ``(G ⊙ PPᵀ + δ·diag(P) + diag(¬P)) x = r ⊙ P`` so every column is one
  batched k×k symmetric positive-definite solve (Gauss–Jordan, fp32
  elementwise; k ≪ m, n per the paper); δ is a jitter at the Gram's
  rounding level that keeps the passive blocks of a rank-deficient G
  solvable;
* converged columns are frozen with ``jnp.where`` so stragglers don't
  perturb finished solutions.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.backends.base import dot


class _BPPState(NamedTuple):
    x: jax.Array        # (r, k) primal iterate
    y: jax.Array        # (r, k) dual iterate  y = G x - r
    passive: jax.Array  # (r, k) bool, passive set P
    alpha: jax.Array    # (r,) int32 remaining full-exchange credits
    beta: jax.Array     # (r,) int32 best (smallest) infeasible count seen
    done: jax.Array     # (r,) bool
    it: jax.Array       # () int32 rounds run
    row_rounds: jax.Array  # () int32 rows kept from each round, summed


def _masked_solve(G: jax.Array, passive: jax.Array, rhs: jax.Array,
                  ridge: jax.Array) -> jax.Array:
    """Solve G[P,P] x_P = rhs[P] for each row's passive set P.

    Implemented as a dense masked system so it batches:  rows/cols outside P
    are replaced by identity, giving x_i = 0 there.
    """
    pf = passive.astype(G.dtype)                       # (r, k)
    # (r, k, k): G on P×P; 1.0 on the diagonal for non-passive rows (identity
    # fill) so x_i = 0 outside P; the (k,) ridge on passive diagonal entries.
    mask2 = pf[:, :, None] * pf[:, None, :]
    eye = jnp.eye(G.shape[-1], dtype=G.dtype)
    M = (G[None] * mask2
         + eye[None] * (1.0 - pf)[:, :, None]
         + (ridge * eye)[None] * pf[:, :, None])
    b = rhs * pf
    return _spd_solve(M, b) * pf


def _spd_solve(M: jax.Array, b: jax.Array) -> jax.Array:
    """Solve the batched symmetric positive-definite systems M x = b,
    (r, k, k) and (r, k), by Gauss–Jordan elimination without pivoting
    (none is needed on SPD matrices: every pivot is a Schur complement's
    diagonal, positive).

    Elementwise updates only, so the arithmetic is fp32 on every platform
    and stays off the matmul unit's precision settings.  On a TPU v5e, a
    whole 3-iteration BPP fit of the 126,675 × 13,824 video block took
    14.7 s with it, where ``jnp.linalg.solve``'s batched LU had taken about
    70 s per iteration."""
    k = M.shape[-1]
    lane = jnp.arange(k)

    def eliminate(j, carry):
        M, b = carry
        row = lax.dynamic_index_in_dim(M, j, axis=1, keepdims=False)
        col = lax.dynamic_index_in_dim(M, j, axis=2, keepdims=False)
        piv = lax.dynamic_index_in_dim(row, j, axis=1)            # (r, 1)
        f = jnp.where(lane[None, :] == j, 0.0, col / piv)         # (r, k)
        bj = lax.dynamic_index_in_dim(b, j, axis=1)               # (r, 1)
        return M - f[:, :, None] * row[:, None, :], b - f * bj

    M, b = lax.fori_loop(0, k, eliminate, (M, b))
    return b / jnp.diagonal(M, axis1=1, axis2=2)


#: Tikhonov weights, relative to each column's G_jj, for the rows whose
#: pivoting reaches no KKT point.  Passive blocks of a Gram with condition
#: ~1e6 and above are numerically singular in fp32, the sign tests that
#: drive the pivoting turn to noise, and the row cycles to ``max_iter``: on
#: a TPU v5e, 3,879 of 13,824 rows of an H-step of the 126,675 × 13,824
#: video block (k = 50, Gram condition 6.3e6).  Re-solved at 1e-3 none was
#: left off its KKT point; rows that finish keep their exact solution.
#: Rows re-solved here, and rows no weight brings to a KKT point (clamped
#: at zero), are counted: ``solve_bpp(..., counts=True)``, and
#: ``NMFResult.extras["rule_state"]`` after a BPP fit.
ESCALATION = (1e-3, 1e-2)

#: Right-hand sides pivoted together.  The loop carries a few batched
#: (rows, k, k) systems, so this bounds its memory: 126,675 rows at k = 50
#: in one batch need ~9.5 GB of temporaries, 8,192 rows ~0.6 GB.
ROW_CHUNK = 8192


def solve_bpp(G: jax.Array, R: jax.Array, *, max_iter: int | None = None,
              counts: bool = False):
    """Solve min_{X>=0} ||C Xᵀ - B||_F given G = CᵀC and R = (CᵀB)ᵀ.

    Args:
      G: (k, k) Gram matrix CᵀC (symmetric PSD; may be rank-deficient).
      R: (r, k) — row i is (Cᵀb_i)ᵀ for right-hand side i.
      max_iter: pivoting iteration cap; default ``5 * k + 10``.

    Returns:
      X: (r, k) with X >= 0, KKT-optimal per row (up to fp tolerance).
      Rows are solved in chunks of ``ROW_CHUNK``, one after another.
      With ``counts``, ``(X, counts)``, where ``counts`` holds:

      * ``regularised_rows``, ``clamped_rows``: int32 counts of the rows
        re-solved with a Tikhonov term (``ESCALATION``) and of those left
        off a KKT point even then;
      * ``pivot_rounds``: int32, the rounds the pivoting loop ran, summed
        over the row chunks and the escalation passes that ran;
      * ``pivot_row_rounds``: float32, the row-solves those rounds needed:
        per round, the rows still off their KKT point at its start (the
        rows whose new iterate the round keeps).  Each round computes a
        solve for every row of its chunk, so ``pivot_row_rounds ÷
        (pivot_rounds × rows per chunk)`` is the share of that work that
        was needed.  float32 because a half-step of the 126,675 × 13,824
        video block can add up to ~1e8 (16 chunks × 8,192 rows × 260
        rounds × 3 passes), so an int32 sum could overflow within a short
        fit; float32 is exact to 2^24 and rounds at 6e-8 relative
        beyond.
    """
    r, k = R.shape
    if max_iter is None:
        max_iter = 5 * k + 10
    dtype = jnp.result_type(G.dtype, R.dtype)
    G = G.astype(dtype)
    R = R.astype(dtype)
    # A rank-deficient C (k above the data's rank) makes passive blocks of
    # G singular, and the solve then returns inf/NaN.  A jitter at the
    # level of each column's own rounding error (k·eps·G_jj, per column so
    # that it is blind to how C's columns are scaled) keeps every block
    # positive definite and moves well-posed solutions by O(k·eps); ``tiny``
    # keeps an all-zero column's pivot nonzero (its rhs is zero too).
    finfo = jnp.finfo(dtype)
    ridge = lambda rho: rho * jnp.diag(G) + finfo.tiny

    def solve(Rb):
        x, done, work = _solve_rows(G, Rb, ridge(k * finfo.eps), max_iter)
        regularised = _missed(done)
        for rho in ESCALATION:
            def retry(state, rho=rho):
                x, done, work = state
                x2, done2, work2 = _solve_rows(G, Rb, ridge(rho), max_iter,
                                               done)
                return (jnp.where(done[:, None], x, x2), done | done2,
                        work + work2)
            x, done, work = lax.cond(jnp.all(done), lambda s: s, retry,
                                     (x, done, work))
        rows = jnp.stack([regularised, _missed(done), work[0]])
        return jnp.maximum(x, 0.0), rows, work[1].astype(jnp.float32)

    if r <= ROW_CHUNK:
        X, c, row_rounds = solve(R)
    else:
        # Zero right-hand sides are optimal at x = 0 and finish at once.
        Rc = jnp.pad(R, ((0, (-r) % ROW_CHUNK), (0, 0))).reshape(
            -1, ROW_CHUNK, k)
        X, c, row_rounds = lax.map(solve, Rc)
        X, c, row_rounds = X.reshape(-1, k)[:r], c.sum(axis=0), \
            row_rounds.sum()
    if counts:
        return X, {"regularised_rows": c[0], "clamped_rows": c[1],
                   "pivot_rounds": c[2], "pivot_row_rounds": row_rounds}
    return X


def _missed(done: jax.Array) -> jax.Array:
    return jnp.sum(~done, dtype=jnp.int32)


def _solve_rows(G: jax.Array, R: jax.Array, ridge: jax.Array,
                max_iter: int, skip: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The pivoting loop over one batch of right-hand sides (rows in
    ``skip`` are left at zero); returns the iterate, which rows reached
    a KKT point, and the loop's work as int32 ``[rounds, row-solves]``:
    the rounds it ran and, summed over them, the rows off their KKT point
    at the start of each (those whose new iterate the round keeps)."""
    def cond(st: _BPPState) -> jax.Array:
        return (~jnp.all(st.done)) & (st.it < max_iter)

    st = lax.while_loop(cond, functools.partial(_pivot_round, G, R, ridge),
                        _initial_state(R, skip))
    return (st.x, st.done | ~jnp.any(_infeasible(st), axis=1),
            jnp.stack([st.it, st.row_rounds]))


def _initial_state(R: jax.Array, skip: jax.Array | None = None) -> _BPPState:
    r, k = R.shape
    return _BPPState(
        x=jnp.zeros((r, k), R.dtype),
        y=-R,                                        # y = G·0 − r
        passive=jnp.zeros((r, k), bool),
        alpha=jnp.full((r,), 3, jnp.int32),
        beta=jnp.full((r,), k + 1, jnp.int32),
        done=jnp.all(-R >= 0, axis=1) | (False if skip is None else skip),
        it=jnp.zeros((), jnp.int32),
        row_rounds=jnp.zeros((), jnp.int32),
    )


def _infeasible(st: _BPPState) -> jax.Array:
    # Strict sign tests, as in the reference code.
    return (st.passive & (st.x < 0)) | (~st.passive & (st.y < 0))


def _pivot_round(G: jax.Array, R: jax.Array, ridge: jax.Array,
                 st: _BPPState) -> _BPPState:
    """One round of the pivoting loop over every row of the batch."""
    k = R.shape[1]
    V = _infeasible(st)                              # (r, k)
    ninf = jnp.sum(V, axis=1).astype(jnp.int32)      # (r,)
    col_done = ninf == 0

    improved = ninf < st.beta
    use_full = improved | (st.alpha > 0)
    new_beta = jnp.where(improved, ninf, st.beta)
    new_alpha = jnp.where(improved, 3, jnp.where(use_full, st.alpha - 1, st.alpha))

    # Backup rule: flip only the largest infeasible index.
    idx = jnp.arange(k)[None, :]
    largest = jnp.max(jnp.where(V, idx, -1), axis=1)    # (r,)
    single = idx == largest[:, None]
    flip = jnp.where(use_full[:, None], V, V & single)

    passive = st.passive ^ flip
    x = _masked_solve(G, passive, R, ridge)
    y = dot(x, G, ((1,), (1,))).astype(R.dtype) - R
    y = jnp.where(passive, 0.0, y)
    x = jnp.where(passive, x, 0.0)

    # Freeze finished columns.
    done = st.done | col_done
    keep = done[:, None]
    return _BPPState(
        x=jnp.where(keep, st.x, x),
        y=jnp.where(keep, st.y, y),
        passive=jnp.where(keep, st.passive, passive),
        alpha=jnp.where(done, st.alpha, new_alpha),
        beta=jnp.where(done, st.beta, new_beta),
        done=done,
        it=st.it + 1,
        row_rounds=st.row_rounds + _missed(done),
    )


@functools.partial(jax.jit, static_argnames=("max_iter",))
def solve_bpp_jit(G: jax.Array, R: jax.Array, max_iter: int = 0) -> jax.Array:
    return solve_bpp(G, R, max_iter=max_iter or None)
