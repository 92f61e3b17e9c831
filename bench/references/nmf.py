"""Plain reference of alternating-update NMF, written apart from the
program: it imports nothing of it and takes nothing it made.

One iteration, from W (m, k) and Ht (n, k) (Ht is H transposed):

    W  <- rule(G = HtᵀHt, R = A·Ht,  W)
    Ht <- rule(G = WᵀW,   R = Aᵀ·W, Ht)
    rel = ‖A − W·Htᵀ‖_F / ‖A‖_F, the residual summed directly by blocks

Every fp32 product runs at the ``precision`` given (``"highest"``, as the
configurations state, or ``"high"`` for the control), and long
contractions are summed in row blocks of at most ``ROW_BLOCK`` rows, so
that the reference's own rounding stays far below the gaps it judges.
Dense A may be sharded on a (pr, pc) mesh: products and residual then run
per device under ``shard_map`` and are summed across it.  Sparse A is
given as its triplets.  The BPP rule solves each right-hand side's
nonnegative least squares in float64 on the host by block principal
pivoting (Kim & Park 2011), with the configuration's re-solve rule for
rows that reach no KKT point.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

ROW_BLOCK = 8192
NNZ_CHUNK = 1 << 20
EPS = math.sqrt(float(np.finfo(np.float32).tiny))

HIGHEST = jax.lax.Precision.HIGHEST


def _split(x):
    """x as hi + lo, each with bfloat16's 8-bit significand, held in fp32.
    The low bits are cleared by masking, not by a round trip through
    bfloat16, which a compiler may fold away."""
    def bf16(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = bf16(x)
    return hi, bf16(x - hi)


def product(precision: str):
    """``f(spec, a, b)``: the einsum ``spec`` of a and b in fp32 at
    ``"highest"``, or at ``"high"`` as three bf16 passes (hi·hi + hi·lo +
    lo·hi, the arithmetic of ``Precision.HIGH``) worked out explicitly,
    so that it is the same on every platform."""
    def exact(spec, a, b):
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    if precision == "highest":
        return exact
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def three_pass(spec, a, b):
        ah, al = _split(a)
        bh, bl = _split(b)
        return exact(spec, ah, bh) + (exact(spec, ah, bl)
                                      + exact(spec, al, bh))
    return three_pass


def _row_sum(f, X, *args):
    """Σ over row blocks of f(X_block, *args_block); X and args share
    their leading (row) dimension."""
    m = X.shape[0]
    full, rem = divmod(m, ROW_BLOCK)
    out = None
    if full:
        def body(i, acc):
            start = i * ROW_BLOCK
            blk = [jax.lax.dynamic_slice_in_dim(a, start, ROW_BLOCK, 0)
                   for a in (X,) + args]
            return acc + f(*blk)
        shape = jax.eval_shape(f, *[a[:ROW_BLOCK] for a in (X,) + args])
        out = jax.lax.fori_loop(0, full, body,
                                jnp.zeros(shape.shape, shape.dtype))
    if rem:
        tail = f(*[a[full * ROW_BLOCK:] for a in (X,) + args])
        out = tail if out is None else out + tail
    return out


class DenseProblem:
    """Dense fp32 A, on one device or block-sharded P('pr', 'pc')."""

    def __init__(self, A, mesh, precision: str = "highest"):
        self.A, self.mesh = A, mesh
        self.m, self.n = A.shape
        self.dot = product(precision)
        self.row_sh = NamedSharding(mesh, P("pr", None))
        self.col_sh = NamedSharding(mesh, P("pc", None))
        self._build()

    def _build(self):
        dot, mesh = self.dot, self.mesh

        def at_b(a, b):
            return dot("rn,rk->nk", a, b)

        def gram(a):
            return dot("rk,rl->kl", a, a)

        smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)

        @jax.jit
        @functools.partial(smap, in_specs=(P("pr", "pc"), P("pc", None)),
                           out_specs=P("pr", None))
        def a_ht(A, Ht):                       # (m, k): A · Ht
            return jax.lax.psum(dot("mn,nk->mk", A, Ht), "pc")

        @jax.jit
        @functools.partial(smap, in_specs=(P("pr", "pc"), P("pr", None)),
                           out_specs=P("pc", None))
        def at_w(A, W):                        # (n, k): Aᵀ · W
            return jax.lax.psum(_row_sum(at_b, A, W), "pr")

        @jax.jit
        @functools.partial(smap, in_specs=(P("pr", "pc"), P("pr", None),
                                           P("pc", None)),
                           out_specs=P())
        def residual(A, W, Ht):                # ‖A − W Htᵀ‖² by blocks
            def sq(a, w):
                d = a - dot("rk,nk->rn", w, Ht)
                return jnp.sum(d * d)
            return jax.lax.psum(_row_sum(sq, A, W), ("pr", "pc"))

        @jax.jit
        @functools.partial(smap, in_specs=(P("pr", "pc"),), out_specs=P())
        def norm_sq(A):
            return jax.lax.psum(_row_sum(lambda a: jnp.sum(a * a), A),
                                ("pr", "pc"))

        self._a_ht, self._at_w, self._residual = a_ht, at_w, residual
        self._gram = jax.jit(lambda X: _row_sum(gram, X))
        self.normA = float(norm_sq(self.A))

    def place_w(self, W):
        return jax.device_put(W, self.row_sh)

    def place_ht(self, Ht):
        return jax.device_put(Ht, self.col_sh)

    def mm(self, Ht):
        return self._a_ht(self.A, Ht)

    def mm_t(self, W):
        return self._at_w(self.A, W)

    def gram(self, X):
        return self._gram(X)

    def rel(self, W, Ht) -> float:
        sq = float(self._residual(self.A, W, Ht))
        return math.sqrt(max(sq, 0.0) / self.normA)


class SparseProblem:
    """Sparse A from its host triplets (rows, cols, vals), one device."""

    def __init__(self, rows, cols, vals, shape, precision: str = "highest"):
        self.m, self.n = shape
        dot = product(precision)

        def mul(a, b):                 # elementwise, at ``precision``
            return dot("...,...->...", a, b)
        nnz = len(vals)
        pad = (-nnz) % NNZ_CHUNK
        # Padding entries have value 0 and index 0: they add nothing.
        self.rows = jnp.asarray(np.pad(rows, (0, pad)).reshape(-1, NNZ_CHUNK))
        self.cols = jnp.asarray(np.pad(cols, (0, pad)).reshape(-1, NNZ_CHUNK))
        self.vals = jnp.asarray(np.pad(vals, (0, pad)).reshape(-1, NNZ_CHUNK))
        self.normA = float(np.sum(np.asarray(vals, np.float64) ** 2))
        m, n = self.m, self.n

        @jax.jit
        def a_ht(rows, cols, vals, Ht):
            def body(acc, chunk):
                r, c, v = chunk
                return acc + jax.ops.segment_sum(mul(v[:, None], Ht[c]), r,
                                                 m), None
            acc = jnp.zeros((m, Ht.shape[1]), jnp.float32)
            return jax.lax.scan(body, acc, (rows, cols, vals))[0]

        @jax.jit
        def at_w(rows, cols, vals, W):
            def body(acc, chunk):
                r, c, v = chunk
                return acc + jax.ops.segment_sum(mul(v[:, None], W[r]), c,
                                                 n), None
            acc = jnp.zeros((n, W.shape[1]), jnp.float32)
            return jax.lax.scan(body, acc, (rows, cols, vals))[0]

        @jax.jit
        def cross(rows, cols, vals, W, Ht):    # Σ a_ij (W Htᵀ)_ij, per chunk
            def body(_, chunk):
                r, c, v = chunk
                wh = jnp.sum(mul(W[r], Ht[c]), axis=1)
                return None, jnp.sum(mul(v, wh))
            return jax.lax.scan(body, None, (rows, cols, vals))[1]

        self._a_ht, self._at_w, self._cross = a_ht, at_w, cross
        self._gram = jax.jit(lambda X: _row_sum(
            lambda a: dot("rk,rl->kl", a, a), X))

    def place_w(self, W):
        return jnp.asarray(W)

    place_ht = place_w

    def mm(self, Ht):
        return self._a_ht(self.rows, self.cols, self.vals, Ht)

    def mm_t(self, W):
        return self._at_w(self.rows, self.cols, self.vals, W)

    def gram(self, X):
        return self._gram(X)

    def rel(self, W, Ht) -> float:
        cross = float(np.sum(np.asarray(
            self._cross(self.rows, self.cols, self.vals, W, Ht), np.float64)))
        Gw = np.asarray(self.gram(W), np.float64)
        Gh = np.asarray(self.gram(Ht), np.float64)
        sq = self.normA - 2.0 * cross + float(np.vdot(Gw, Gh))
        return math.sqrt(max(sq, 0.0) / self.normA)


# ---------------------------------------------------------------------------
# Update rules: (G, R, X) -> X, for the W-step (normalize) and the H-step.
# ---------------------------------------------------------------------------

def _mu(dot):
    @jax.jit
    def mu(G, R, X):
        return X * (R / (dot("rk,kl->rl", X, G) + EPS))
    return lambda G, R, X, normalize: mu(G, R, X)


def _hals(dot):
    def make(normalize):
        @jax.jit
        def hals(G, R, X):
            def col(i, X):
                gii = G[i, i]
                xg = dot("rk,k->r", X, G[:, i])
                if normalize:
                    x = jnp.maximum(X[:, i] * gii + R[:, i] - xg, 0.0)
                    nrm = jnp.sqrt(jnp.sum(x * x))
                    x = jnp.where(nrm > 0, x / jnp.maximum(nrm, EPS), x)
                else:
                    x = jnp.maximum(
                        X[:, i] + (R[:, i] - xg) / jnp.maximum(gii, EPS), 0.0)
                return X.at[:, i].set(x)
            return jax.lax.fori_loop(0, G.shape[0], col, X)
        return hals
    w, h = make(True), make(False)
    return lambda G, R, X, normalize: (w if normalize else h)(G, R, X)


#: Tikhonov weights, relative to each column's G_jj, with which a row whose
#: pivoting reaches no KKT point is solved again (the configuration's
#: re-solve rule); a row that none brings to one is clamped at zero.
BPP_RESOLVE = (1e-3, 1e-2)


def nnls_bpp(G, R, *, max_iter: int | None = None, ridge: float = 0.0):
    """min_{x ≥ 0} ½xᵀGx − rᵀx for each row r of R, by block principal
    pivoting in float64: full exchanges while the infeasible set shrinks,
    three more tries after it stops, then the single largest infeasible
    index.  Returns (X, converged)."""
    G = np.asarray(G, np.float64)
    R = np.asarray(R, np.float64)
    r, k = R.shape
    if max_iter is None:
        max_iter = 5 * k + 10
    Gr = G + np.diag(ridge * np.diag(G) + np.finfo(np.float64).tiny)
    X = np.zeros((r, k))
    Y = -R.copy()
    Pset = np.zeros((r, k), bool)
    alpha = np.full(r, 3)
    beta = np.full(r, k + 1)
    done = np.zeros(r, bool)
    eye = np.eye(k)
    for _ in range(max_iter):
        act = np.flatnonzero(~done)
        if act.size == 0:
            break
        Pa, Xa, Ya = Pset[act], X[act], Y[act]
        V = (Pa & (Xa < 0)) | (~Pa & (Ya < 0))
        ninf = V.sum(axis=1)
        fin = ninf == 0
        done[act[fin]] = True
        go = ~fin
        act, V, ninf, Pa = act[go], V[go], ninf[go], Pa[go]
        if act.size == 0:
            break
        better = ninf < beta[act]
        full = better | (alpha[act] > 0)
        beta[act] = np.where(better, ninf, beta[act])
        alpha[act] = np.where(better, 3,
                              np.where(full, alpha[act] - 1, alpha[act]))
        last = k - 1 - np.argmax(V[:, ::-1], axis=1)
        single = np.zeros_like(V)
        single[np.arange(act.size), last] = True
        Pa = Pa ^ np.where(full[:, None], V, single)
        pf = Pa.astype(np.float64)
        M = Gr[None] * (pf[:, :, None] * pf[:, None, :]) \
            + eye[None] * (1.0 - pf)[:, :, None]
        x = np.linalg.solve(M, (R[act] * pf)[:, :, None])[:, :, 0] * pf
        y = np.where(Pa, 0.0, x @ G - R[act])
        X[act], Y[act], Pset[act] = x, y, Pa
    V = (Pset & (X < 0)) | (~Pset & (Y < 0))
    return X, ~V.any(axis=1)


def _nnls_resolved(G, R):
    X, ok = nnls_bpp(G, R)
    for rho in BPP_RESOLVE:
        bad = np.flatnonzero(~ok)
        if bad.size == 0:
            break
        Xb, okb = nnls_bpp(G, R[bad], ridge=rho)
        X[bad], ok[bad] = Xb, okb
    return np.maximum(X, 0.0)


def bpp_rule(G, R, X=None, normalize=False):
    """The BPP half-step on the host, G and R from the device; rows are
    solved in chunks on a thread per core (LAPACK runs without the
    interpreter lock)."""
    del X, normalize
    from concurrent.futures import ThreadPoolExecutor
    G = np.asarray(G, np.float64)
    R = np.asarray(R, np.float64)
    chunks = np.array_split(R, max(1, -(-len(R) // 4096)))
    with ThreadPoolExecutor(os.cpu_count() or 1) as ex:
        X = np.concatenate(list(ex.map(lambda r: _nnls_resolved(G, r),
                                       chunks)))
    return jnp.asarray(X, jnp.float32)


def rule_for(algo: str, precision: str = "highest"):
    if algo == "mu":
        return _mu(product(precision))
    if algo == "hals":
        return _hals(product(precision))
    if algo == "bpp":
        return bpp_rule
    raise ValueError(f"the reference has no rule {algo!r}")


def fit(problem, algo: str, W0, Ht0, iters: int, precision: str = "highest"):
    """``iters`` iterations from (W0, Ht0); returns (W, Ht, rels) with the
    factors as float64 host arrays and one relative error per iteration."""
    rule = rule_for(algo, precision)
    W, Ht = problem.place_w(W0), problem.place_ht(Ht0)
    rels = []
    for _ in range(iters):
        W = problem.place_w(rule(problem.gram(Ht), problem.mm(Ht), W, True))
        Ht = problem.place_ht(rule(problem.gram(W), problem.mm_t(W), Ht,
                                   False))
        rels.append(problem.rel(W, Ht))
    return np.asarray(W, np.float64), np.asarray(Ht, np.float64), rels
