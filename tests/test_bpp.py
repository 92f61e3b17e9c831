"""BPP solver: KKT optimality (property-based) + agreement with the
unconstrained solution when it is feasible."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bpp import solve_bpp


def _kkt_residuals(G, R, X):
    Y = X @ G.T - R
    comp = jnp.abs(X * Y)
    return (float(jnp.min(X)), float(jnp.min(Y)), float(jnp.max(comp)))


def test_kkt_basic():
    key = jax.random.PRNGKey(0)
    C = jax.random.normal(key, (200, 12))
    B = jax.random.normal(jax.random.fold_in(key, 1), (200, 64))
    G, R = C.T @ C, (C.T @ B).T
    X = solve_bpp(G, R)
    xmin, ymin, comp = _kkt_residuals(G, R, X)
    assert xmin >= -1e-6
    assert ymin >= -1e-3
    assert comp < 1e-4 * float(jnp.max(jnp.abs(R)) + 1)


def test_interior_solution_matches_lstsq():
    """If the unconstrained solution is positive, BPP must return it."""
    key = jax.random.PRNGKey(3)
    k = 6
    Q = jax.random.normal(key, (40, k))
    G = Q.T @ Q + jnp.eye(k)
    x_true = jax.random.uniform(jax.random.fold_in(key, 1), (5, k)) + 0.5
    R = x_true @ G.T
    X = solve_bpp(G, R)
    np.testing.assert_allclose(np.asarray(X), np.asarray(x_true),
                               rtol=2e-4, atol=2e-4)


def test_zero_rhs():
    G = jnp.eye(4)
    X = solve_bpp(G, jnp.zeros((3, 4)))
    assert float(jnp.max(jnp.abs(X))) == 0.0


def test_all_negative_rhs_gives_zero():
    G = jnp.eye(4)
    R = -jnp.ones((3, 4))
    X = solve_bpp(G, R)          # y = -r >= 0 at x=0: already optimal
    assert float(jnp.max(jnp.abs(X))) == 0.0


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(1, 12), st.integers(0, 10 ** 6))
def test_kkt_property(k, r, seed):
    key = jax.random.PRNGKey(seed)
    C = jax.random.normal(key, (3 * k, k))
    B = jax.random.normal(jax.random.fold_in(key, 1), (3 * k, r)) * 3.0
    G, R = C.T @ C, (C.T @ B).T
    X = solve_bpp(G, R)
    scale = float(jnp.max(jnp.abs(R))) + 1.0
    xmin, ymin, comp = _kkt_residuals(G, R, X)
    assert xmin >= -1e-5 * scale
    assert ymin >= -5e-3 * scale
    assert comp < 5e-3 * scale


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_bpp_objective_not_worse_than_projection(seed):
    """BPP's objective must beat (or match) the clipped least squares."""
    key = jax.random.PRNGKey(seed)
    k = 8
    C = jax.random.normal(key, (32, k))
    b = jax.random.normal(jax.random.fold_in(key, 1), (32, 1))
    G, R = C.T @ C, (C.T @ b).T
    X = solve_bpp(G, R)
    naive = jnp.maximum(jnp.linalg.lstsq(C, b)[0].T, 0.0)
    f = lambda x: float(jnp.sum((C @ x.T - b) ** 2))
    assert f(X) <= f(naive) + 1e-4 * f(naive)


def test_rank_deficient_gram_gives_finite_solution():
    """k above the data's rank makes passive blocks of G singular; the
    solve must stay finite and still fit an exactly representable B."""
    key = jax.random.PRNGKey(5)
    C = (jax.random.uniform(key, (30, 3))
         @ jax.random.uniform(jax.random.fold_in(key, 1), (3, 8)))
    X_true = jax.random.uniform(jax.random.fold_in(key, 2), (6, 8))
    B = C @ X_true.T
    G, R = C.T @ C, (C.T @ B).T
    X = solve_bpp(G, R)
    assert np.isfinite(np.asarray(X)).all()
    assert float(jnp.min(X)) >= 0.0
    resid = jnp.linalg.norm(C @ X.T - B) / jnp.linalg.norm(B)
    assert float(resid) < 1e-3


def test_row_chunks_match_one_batch(monkeypatch):
    """Right-hand sides are pivoted in row chunks; each row's solution is
    independent of which chunk it lands in (incl. the zero-padded tail)."""
    from repro.core import bpp
    key = jax.random.PRNGKey(9)
    C = jax.random.normal(key, (60, 7))
    B = jax.random.normal(jax.random.fold_in(key, 1), (60, 37))
    G, R = C.T @ C, (C.T @ B).T
    one = bpp.solve_bpp(G, R)
    monkeypatch.setattr(bpp, "ROW_CHUNK", 16)
    chunked = bpp.solve_bpp(G, R)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(one),
                               rtol=0, atol=1e-6)


def test_column_scaling_leaves_the_fit_unchanged():
    """NNLS is blind to the scale of C's columns (x_j absorbs it), so the
    fit must be too: with factor columns 1000× apart, a regulariser sized
    by the largest column swamped the small ones (fits 12% worse)."""
    rng = np.random.RandomState(0)
    C0 = rng.rand(400, 50)
    B = C0 @ (rng.rand(50, 64) * (rng.rand(50, 64) > 0.5)) \
        + 0.01 * rng.rand(400, 64)
    C = C0 * 10.0 ** rng.uniform(-3, 0, 50)[None, :]

    def fit(C):
        G = jnp.asarray(C.T @ C, jnp.float32)
        R = jnp.asarray((C.T @ B).T, jnp.float32)
        X = np.asarray(solve_bpp(G, R), np.float64)
        return np.sum((C @ X.T - B) ** 2, axis=0)

    f0, f = fit(C0), fit(C)
    assert np.all(f <= f0 * (1 + 1e-4) + 1e-9)


def test_ill_conditioned_gram_rows_reach_kkt():
    """Collinear factor columns (k = 50 over rank-20 data, Gram condition
    ~1e8) leave fp32 passive solves numerically singular: rows whose
    pivoting cycles are re-solved with a small Tikhonov term, so every row
    ends at (or within 1e-3 of) its KKT point, none clamped mid-pivot."""
    rng = np.random.RandomState(1)
    C = rng.rand(2000, 20) @ rng.rand(20, 50) + 0.01 * rng.rand(2000, 50)
    B = rng.rand(2000, 20) @ rng.rand(20, 300)
    G, R = C.T @ C, (C.T @ B).T
    X = np.asarray(solve_bpp(jnp.asarray(G, jnp.float32),
                             jnp.asarray(R, jnp.float32)), np.float64)
    Y = X @ G - R
    scale = np.abs(R).max(axis=1, keepdims=True)
    viol = np.where(X > 0, np.abs(Y), np.maximum(-Y, 0)) / scale
    assert X.min() >= 0 and viol.max() < 1e-3


def _collinear_problem():
    rng = np.random.RandomState(1)
    C = rng.rand(2000, 20) @ rng.rand(20, 50) + 0.01 * rng.rand(2000, 50)
    B = rng.rand(2000, 20) @ rng.rand(20, 300)
    return (jnp.asarray(C.T @ C, jnp.float32),
            jnp.asarray((C.T @ B).T, jnp.float32))


def test_counts_regularised_and_clamped_rows():
    """``counts=True`` reports the rows re-solved with a Tikhonov term and
    the rows clamped: none on a well-conditioned Gram; on a collinear one
    the cycling rows are counted as re-solved, and none is clamped."""
    key = jax.random.PRNGKey(3)
    C = jax.random.uniform(key, (80, 6))
    B = jax.random.uniform(jax.random.fold_in(key, 1), (80, 40))
    X, c = solve_bpp(C.T @ C, (C.T @ B).T, counts=True)
    rows = ("regularised_rows", "clamped_rows")
    assert {k: int(c[k]) for k in rows} == {"regularised_rows": 0,
                                            "clamped_rows": 0}
    np.testing.assert_array_equal(np.asarray(X),
                                  np.asarray(solve_bpp(C.T @ C, (C.T @ B).T)))
    _, c = solve_bpp(*_collinear_problem(), counts=True)
    assert int(c["regularised_rows"]) > 0 and int(c["clamped_rows"]) == 0


def test_fit_reports_bpp_row_counts():
    """A BPP fit carries the counts through its compiled loop into
    ``extras["rule_state"]``, summed over the half-steps."""
    from repro.core.engine import NMFSolver
    A = jax.random.uniform(jax.random.PRNGKey(4), (40, 30))
    res = NMFSolver(4, algo="bpp", max_iters=3).fit(A)
    st = res.extras["rule_state"]
    assert set(st) == {"regularised_rows", "clamped_rows", "pivot_rounds",
                       "pivot_row_rounds"}
    assert 0 <= int(st["clamped_rows"]) <= int(st["regularised_rows"])
    assert st["pivot_row_rounds"].dtype == np.float32
    # Every half-step of every iteration runs at least one round, and a
    # round keeps at most the 40 (W) or 30 (H) rows of its batch.
    assert int(st["pivot_rounds"]) >= 6
    assert 0 < float(st["pivot_row_rounds"]) <= 40 * int(st["pivot_rounds"])


def _host_pivot_counts(G, R, max_iter=None):
    """``solve_bpp``'s pivoting counts from a plain host loop: one jitted
    round at a time, with the escalation and the row chunks of
    ``solve_bpp`` spelled out, counting on the host the rows off their KKT
    point at the start of each round."""
    from repro.core import bpp
    r, k = R.shape
    max_iter = 5 * k + 10 if max_iter is None else max_iter
    tiny, eps = np.finfo(np.float32).tiny, np.finfo(np.float32).eps
    one_round = jax.jit(bpp._pivot_round)
    rounds = row_rounds = 0
    for lo in range(0, r, bpp.ROW_CHUNK):
        Rb = R[lo:lo + bpp.ROW_CHUNK]
        done = None
        for rho in (k * eps,) + bpp.ESCALATION:
            if done is not None and done.all():
                break
            ridge = rho * jnp.diag(G) + tiny
            st = bpp._initial_state(Rb, None if done is None
                                    else jnp.asarray(done))
            for _ in range(max_iter):
                if bool(jnp.all(st.done)):
                    break
                off = ~(np.asarray(st.done)
                        | ~np.asarray(bpp._infeasible(st)).any(axis=1))
                rounds += 1
                row_rounds += int(off.sum())
                st = one_round(G, Rb, ridge, st)
            reached = np.asarray(st.done) | \
                ~np.asarray(bpp._infeasible(st)).any(axis=1)
            done = reached if done is None else done | reached
    return rounds, row_rounds


@pytest.mark.parametrize("problem", ["small", "collinear"])
def test_pivot_counts_match_a_host_loop(problem, monkeypatch):
    """``pivot_rounds`` and ``pivot_row_rounds`` count what a host loop
    over the same rounds counts: on a small problem in three row chunks,
    and on the collinear one, whose cycling rows run to the round cap and
    through the escalation passes."""
    from repro.core import bpp
    if problem == "small":
        key = jax.random.PRNGKey(5)
        C = jax.random.uniform(key, (80, 6))
        B = jax.random.uniform(jax.random.fold_in(key, 1), (80, 40))
        G, R = C.T @ C, (C.T @ B).T
        monkeypatch.setattr(bpp, "ROW_CHUNK", 16)
    else:
        G, R = _collinear_problem()
    _, c = solve_bpp(G, R, counts=True)
    rounds, row_rounds = _host_pivot_counts(G, R)
    assert c["pivot_row_rounds"].dtype == jnp.float32
    assert (int(c["pivot_rounds"]), float(c["pivot_row_rounds"])) == \
        (rounds, row_rounds)
    assert 0 < row_rounds <= rounds * min(R.shape[0], bpp.ROW_CHUNK)
    if problem == "collinear":
        assert rounds > 5 * R.shape[1] + 10     # the cap, then escalation
