"""Update rules as first-class plugins: the ``UpdateRule`` interface and
algorithm registry.

The paper's central claim is that the FAUN framework is *algorithm-agnostic*
— "able to leverage a variety of NMF and NLS algorithms" — because every
AU-NMF algorithm updates the factors from the same four matrix products.
This module is the contract for the algorithm half, mirroring what
``repro.backends.LocalOps`` is for local compute:

    update_w(G, R, X, state)   W half-update  (per-column normalisation for
    update_h(G, R, X, state)   H half-update   the HALS family, threaded
                                               through ``norm_psum``)
    fold_in(G, R, X0)          serving half-update against a FIXED factor
                               (repro.serve.foldin)
    partial_update_h(G, R, X, mask, state)
                               touched-block H refresh (repro.online) —
                               defaults to a full sweep merged on ``mask``
    init_state(m, n, k)        optional carry for stateful rules — threaded
                               through the engine's lax.scan / lax.while_loop
    luc_flops(m, n, k)         F(m, n, k) of the paper's Table III
    extra_latency_words(k, p)  (messages, wire words) of any collectives the
                               rule itself needs beyond the schedule's six —
                               e.g. HALS's k·log p column-norm reductions
    positive_init              MU-family rules need a strictly positive W0
    l1 / l2                    regularisation, applied uniformly to (G, R)

Both half-updates use a single "row-factor" convention (paper §4):

    X ∈ R_+^{r×k}  (rows of W, or columns of H transposed)
    G ∈ R^{k×k}    (Gram of the *fixed* factor: HHᵀ or WᵀW)
    R ∈ R^{r×k}    (cross product block: (AHᵀ) rows, or (WᵀA)ᵀ rows)

so one rule works unchanged for the W-step and the H-step, and unchanged
between serial and distributed (shard_map) execution: LUC is local, only
the matrix products — and the rule's declared extras, like the HALS column
norms — communicate.

Built-in rules (resolved by name through the registry):

  * ``mu``              Lee & Seung multiplicative update (paper §4.1).
  * ``hals``            Cichocki et al. hierarchical ALS (paper §4.2).
  * ``bpp``             exact ANLS via block principal pivoting (§4.3;
                        aliases ``abpp`` / ``anls``).
  * ``amu`` / ``ahals`` Gillis & Glineur's accelerated MU / HALS
                        (arXiv:1107.5194): repeated inner LUC sweeps reuse
                        the same (G, R) — the expensive products — with a
                        dynamic stopping heuristic on the inner change norm.

Custom rules plug in exactly like custom backends:

    from repro.core.rules import UpdateRule, register_algorithm

    class MyRule(UpdateRule):
        name = "mine"
        def _update_w(self, G, R, X, state, *, norm_psum): ...
        def _update_h(self, G, R, X, state, *, norm_psum): ...

    register_algorithm("mine", MyRule)
    NMFSolver(k, algo="mine")            # or algo=MyRule()

A registered rule runs on every schedule × backend cell and in serving
fold-in for free — no ``algo ==`` branches exist outside this module.
"""

from __future__ import annotations

import math
from typing import Callable, Type, Union

import jax
import jax.numpy as jnp
from jax import lax

from repro.backends.base import dot
from repro.core.bpp import solve_bpp


def eps_for(dtype) -> float:
    """Division-guard epsilon that survives ``dtype``'s exponent range.

    A fixed 1e-16 underflows to zero under an fp16 factor carry (min
    subnormal ≈ 6e-8), silently reintroducing the divide-by-zero it guards;
    ``sqrt(tiny)`` sits halfway down the exponent range of every IEEE
    format, so it is representable AND small quotients ``q / eps`` stay
    finite (fp32/bf16: ≈1.1e-19; fp16: ≈7.8e-3).
    """
    return math.sqrt(float(jnp.finfo(jnp.dtype(dtype)).tiny))


def _identity(v):
    return v


# ---------------------------------------------------------------------------
# The primitive update computations (LUC bodies).  Kept as plain functions so
# the rule classes, the legacy ``algorithms`` shims, and the benchmarks all
# share one numeric implementation.
# ---------------------------------------------------------------------------

def update_mu(G: jax.Array, R: jax.Array, X: jax.Array) -> jax.Array:
    """X ← X ⊙ R / (X G + ε)   (paper eq. (3); F = 2rk² flops)."""
    denom = dot(X, G).astype(X.dtype) + eps_for(X.dtype)
    return X * (R / denom)


def update_hals(G: jax.Array, R: jax.Array, X: jax.Array, *,
                normalize: bool = False,
                norm_psum: Callable[[jax.Array], jax.Array] = _identity,
                ) -> jax.Array:
    """Sequential HALS column sweep (paper eq. (5); F = 2rk² flops).

    W-step (normalize=True):   w^i ← [w^i·G_ii + R^i − X G^i]_+ ;  w^i ← w^i/‖w^i‖
    H-step (normalize=False):  h_i ← [h_i + (R^i − X G^i)/G_ii]_+

    This is Cichocki & Phan's fast-HALS (their Algorithm 2).  The paper's
    eq. (5) writes the unscaled form, which is the same rule under its
    convention that W's columns are unit-normalised after every update
    (then (WᵀW)_ii = 1); we keep the G_ii factors explicit so the sweep is
    correct for *any* scaling — including the first iteration, where W is
    not yet normalised.  Columns are updated in order so later columns see
    earlier updates — the defining property of HALS as 2k-block BCD.

    ``norm_psum`` threads the W-step's per-column norm reduction: identity
    for serial, ``lax.psum`` over the grid for distributed — keeping serial
    and distributed bit-identical (the paper charges this as HALS's extra
    k·log p latency).
    """
    k = G.shape[0]
    eps = eps_for(X.dtype)

    def col(i, X):
        gii = G[i, i]
        if normalize:
            xi = X[:, i] * gii + R[:, i] - dot(X, G[:, i]).astype(X.dtype)
            xi = jnp.maximum(xi, 0.0)
            sq = norm_psum(jnp.sum(jnp.square(xi.astype(jnp.float32))))
            nrm = jnp.sqrt(sq).astype(xi.dtype)
            # Guard the all-zero column (paper's code resets to machine eps).
            xi = jnp.where(nrm > 0, xi / jnp.maximum(nrm, eps), xi)
        else:
            xi = X[:, i] + ((R[:, i] - dot(X, G[:, i]).astype(X.dtype))
                            / jnp.maximum(gii, eps))
            xi = jnp.maximum(xi, 0.0)
        return X.at[:, i].set(xi.astype(X.dtype))

    return lax.fori_loop(0, k, col, X, unroll=False)


def update_bpp(G: jax.Array, R: jax.Array, X: jax.Array, *,
               max_iter: int | None = None) -> jax.Array:
    """Exact NLS via block principal pivoting; X is only a shape/dtype hint."""
    del X  # BPP re-solves from scratch (ANLS is memoryless per half-update)
    return solve_bpp(G, R, max_iter=max_iter)


# ---------------------------------------------------------------------------
# The UpdateRule interface
# ---------------------------------------------------------------------------

class UpdateRule:
    """Abstract update rule.  Subclass and implement ``_update_w`` /
    ``_update_h``; everything else defaults sensibly.

    The public ``update_w`` / ``update_h`` are template methods: they apply
    the rule's regularisation to (G, R) uniformly, then dispatch to the
    ``_update_*`` hooks.  Signature of the hooks and the public methods:

        update_w(G, R, X, state=None, *, norm_psum=identity) -> (X, state)

    ``state`` is the rule's carry pytree (``init_state``'s output, or None
    for stateless rules), threaded by the engine through its compiled
    ``lax.scan`` / ``lax.while_loop`` — so stateful rules (the accelerated
    family's inner-sweep accounting, for one) never force a host
    round-trip.  Inside shard_map schedules the state travels replicated
    (PartitionSpec ``P()``), so keep its leaves small (scalars/k-vectors)
    and device-invariant (derive them from ``norm_psum``-reduced values).
    """

    #: registry key and the ``NMFSolver(...).algo`` string
    name: str = "abstract"

    #: MU-family rules are multiplicative — W must start strictly positive
    #: (``aunmf.init_w`` consults this; zeros init is fine otherwise)
    positive_init: bool = False

    #: whether ``update_w`` performs per-column norm reductions over the
    #: grid (the HALS family) — ``extra_latency_words`` then charges the
    #: paper's k·log p normalisation latency
    normalizes_w: bool = False

    def __init__(self, *, l1: float = 0.0, l2: float = 0.0):
        if l1 < 0 or l2 < 0:
            raise ValueError(f"regularisation weights must be >= 0, got "
                             f"l1={l1}, l2={l2}")
        self.l1, self.l2 = float(l1), float(l2)

    # -- regularisation ------------------------------------------------------

    def regularize(self, G, R):
        """Fold L2 (ridge) and L1 (sparsity) penalties into the normal-
        equation pair: minimising ½‖a − xC‖² + l1·Σx + ½·l2·‖x‖² over x ≥ 0
        is the plain problem with G ← G + l2·I and R ← R − l1.  Applied
        uniformly to both half-updates and to serving fold-in, so every
        rule — including BPP's exact solve — optimises the same penalised
        objective.  Multiplicative rules override to clamp the shifted R
        at zero (the standard sparse-MU form)."""
        if self.l2:
            G = G + jnp.asarray(self.l2, G.dtype) * jnp.eye(G.shape[0],
                                                            dtype=G.dtype)
        if self.l1:
            R = R - jnp.asarray(self.l1, R.dtype)
        return G, R

    # -- state ---------------------------------------------------------------

    def init_state(self, m: int, n: int, k: int, dtype=jnp.float32):
        """Carry pytree threaded through the engine loop (None = stateless).
        ``m``/``n``/``k`` are the GLOBAL problem dimensions."""
        del m, n, k, dtype
        return None

    def prepare_global(self, m: int, n: int, k: int) -> "UpdateRule":
        """Hook called once per fit / lower / predict_cost with the GLOBAL
        problem dimensions, before any tracing; return ``self`` or a
        configured clone.  Rules that derive configuration from the problem
        size resolve it here — the accelerated family turns
        ``inner_iters=None`` into the Gillis–Glineur flop-ratio budget.  The
        returned rule is what the engine runs and what feeds its compiled-
        run cache key, so size-derived configuration participates in
        compilation identity."""
        del m, n, k
        return self

    # -- the two half-updates ------------------------------------------------

    def update_w(self, G, R, X, state=None, *, norm_psum=_identity):
        G, R = self.regularize(G, R)
        return self._update_w(G, R, X, state, norm_psum=norm_psum)

    def update_h(self, G, R, X, state=None, *, norm_psum=_identity):
        G, R = self.regularize(G, R)
        return self._update_h(G, R, X, state, norm_psum=norm_psum)

    def _update_w(self, G, R, X, state, *, norm_psum):
        raise NotImplementedError

    def _update_h(self, G, R, X, state, *, norm_psum):
        raise NotImplementedError

    # -- partial (touched-block) refresh -------------------------------------

    def partial_update_h(self, G, R, X, mask=None, state=None, *,
                         norm_psum=_identity):
        """DID-style touched-block H refresh (Gao & Chu, arXiv:1802.08938):
        update only the rows of X (columns of H in the row convention)
        selected by the boolean ``mask`` (r,), returning the unselected rows
        bit-identical to their input.

        The default falls back to a FULL ``update_h`` sweep and merges the
        selected rows — always correct.  For every built-in rule the H
        half-update is row-separable (MU and BPP solve each row of X
        independently; the HALS H column sweep touches row r of X only
        through row r itself), so callers holding a compact gather of the
        touched rows can equivalently pass the gathered (G, R_t, X_t) with
        ``mask=None`` and pay only O(r_touched) — the cheap refresh
        ``repro.online`` runs between full refactorizations.  Rules whose H
        update couples rows (a future symmetric/graph-regularised rule)
        must override this to stay correct under gathering.
        """
        Xn, state = self.update_h(G, R, X, state, norm_psum=norm_psum)
        if mask is None:
            return Xn, state
        return jnp.where(mask[:, None], Xn, X), state

    # -- serving fold-in -----------------------------------------------------

    def _fold_setup(self, G, R, X0):
        """(X0, sweep) for iterative fold-in; exact solvers skip this by
        overriding ``fold_in`` directly."""
        raise NotImplementedError

    def fold_in(self, G, R, X0=None, *, iters: int = 100):
        """Project rows onto a FIXED factor: x_i = argmin_{x≥0} ‖a_i − xH‖
        given G = HHᵀ and R = A_new Hᵀ — the paper's ``SolveBPP(HHᵀ, HAᵀ)``
        serving half-update.  Iterative rules run ``iters`` sweeps; the
        returned value is jit-safe (no data-dependent python control flow),
        which ``repro.serve.foldin`` relies on to compile one callable per
        padded batch bucket."""
        G, R = self.regularize(G, R)
        X, sweep = self._fold_setup(G, R, X0)
        return lax.fori_loop(0, iters, lambda _, X: sweep(X), X)

    # -- cost hooks (paper Table III) ---------------------------------------

    def luc_flops(self, m: float, n: float, k: float, *,
                  bpp_iters: float = 1.0) -> float:
        """F(m, n, k): flops of the two local update computations per
        iteration.  ``bpp_iters`` is the empirical pivot-round knob only the
        BPP family consumes (the paper leaves C_BPP symbolic)."""
        del bpp_iters
        return 2.0 * (m + n) * k * k

    def extra_latency_words(self, k: float, p: int) -> tuple[float, float]:
        """(messages, wire words) per iteration of any collectives the RULE
        itself performs beyond the schedule's matrix-product collectives.
        The HALS family's per-column norm all-reduces are the paper's
        example: k messages of log p latency each, one scalar of wire."""
        if p <= 1 or not self.normalizes_w:
            return 0.0, 0.0
        return k * math.log2(p), 2.0 * k * (p - 1) / p

    # -- identity ------------------------------------------------------------

    def cache_key(self):
        """Hashable identity for the engine's compiled-run cache; keyed on
        the concrete class OBJECT (like ``LocalOps.cache_key``) so a
        redefined class under the same name invalidates cached runs.
        Stateful configuration must extend this."""
        return (type(self), self.name, self.l1, self.l2)

    def __repr__(self) -> str:
        return f"{type(self).__name__}(name={self.name!r})"


class _FunctionRule(UpdateRule):
    """Adapter wrapping plain ``(G, R, X) -> X`` closures (the legacy
    ``get_update_fns`` contract) into the UpdateRule surface.  Stateless;
    ``norm_psum`` must already be baked into the closures."""

    name = "function"

    def __init__(self, update_w: Callable, update_h: Callable):
        super().__init__()
        self._w, self._h = update_w, update_h

    def _update_w(self, G, R, X, state, *, norm_psum):
        return self._w(G, R, X), state

    def _update_h(self, G, R, X, state, *, norm_psum):
        return self._h(G, R, X), state


# ---------------------------------------------------------------------------
# Built-in rules
# ---------------------------------------------------------------------------

class MURule(UpdateRule):
    """Lee & Seung multiplicative update (paper §4.1)."""

    name = "mu"
    positive_init = True

    def regularize(self, G, R):
        G, R = super().regularize(G, R)
        if self.l1:
            # Multiplicative rules need a nonnegative numerator: clamp the
            # l1-shifted cross product (the standard sparse-MU rule).
            R = jnp.maximum(R, 0.0)
        return G, R

    def _update_w(self, G, R, X, state, *, norm_psum):
        return update_mu(G, R, X), state

    _update_h = _update_w

    def _fold_setup(self, G, R, X0):
        # The multiplicative rule is only defined for positive iterates:
        # start from a strictly positive Jacobi init (R_i / G_ii).
        Rp = jnp.maximum(R, 0.0)        # nonneg data ⇒ R ≥ 0 already
        if X0 is None:
            eps = eps_for(R.dtype)
            d = jnp.maximum(jnp.diag(G), eps)
            X0 = jnp.maximum(Rp / d, eps)
        return X0, lambda X: update_mu(G, Rp, X)


class HALSRule(UpdateRule):
    """Cichocki et al. hierarchical ALS (paper §4.2).  The W-step
    normalises each column right after updating it (the paper's
    convention); the H-step never does."""

    name = "hals"
    normalizes_w = True

    def _update_w(self, G, R, X, state, *, norm_psum):
        return update_hals(G, R, X, normalize=True,
                           norm_psum=norm_psum), state

    def _update_h(self, G, R, X, state, *, norm_psum):
        return update_hals(G, R, X, normalize=False), state

    def _fold_setup(self, G, R, X0):
        X0 = jnp.zeros_like(R) if X0 is None else X0
        return X0, lambda X: update_hals(G, R, X, normalize=False)


class BPPRule(UpdateRule):
    """Exact ANLS via block principal pivoting (paper §4.3, core/bpp.py).
    ``max_iter`` bounds the pivot rounds (None = the solver default)."""

    name = "bpp"

    def __init__(self, *, max_iter: int | None = None,
                 l1: float = 0.0, l2: float = 0.0):
        super().__init__(l1=l1, l2=l2)
        self.max_iter = max_iter

    def init_state(self, m, n, k, dtype=jnp.float32):
        """``solve_bpp``'s counts, summed over the grid and the iterations:
        rows of the fit's half-steps that BPP re-solved with a Tikhonov
        term, rows it clamped (``core.bpp.ESCALATION``), pivoting rounds
        (int32) and the row-solves they needed (``pivot_row_rounds``,
        float32: an int32 sum overflows within a short fit on the video
        block)."""
        del m, n, k, dtype
        zero = jnp.zeros((), jnp.int32)
        return {"regularised_rows": zero, "clamped_rows": zero,
                "pivot_rounds": zero,
                "pivot_row_rounds": jnp.zeros((), jnp.float32)}

    def _update_w(self, G, R, X, state, *, norm_psum):
        X, counts = solve_bpp(G, R, max_iter=self.max_iter, counts=True)
        if not isinstance(state, dict) or not counts.keys() <= state.keys():
            return X, state     # stateless callers, or a subclass's carry
        return X, {**state, **{key: state[key] + norm_psum(c)
                               for key, c in counts.items()}}

    _update_h = _update_w

    def fold_in(self, G, R, X0=None, *, iters: int = 100):
        del X0, iters               # exact solve, no warm start needed
        G, R = self.regularize(G, R)
        return solve_bpp(G, R, max_iter=self.max_iter)

    def luc_flops(self, m, n, k, *, bpp_iters: float = 1.0):
        # `bpp_iters` passes of a k×k solve per column: ~k³/3 + 2k² flops
        # per column per pivot round (empirically 1–3 rounds dominate).
        per_col = bpp_iters * (k ** 3 / 3.0 + 2.0 * k * k)
        return (m + n) * per_col

    def cache_key(self):
        return super().cache_key() + (self.max_iter,)


class _AcceleratedRule(UpdateRule):
    """Gillis & Glineur acceleration (arXiv:1107.5194), shared machinery.

    The four matrix products cost O(mnk) per iteration while one MU/HALS
    LUC sweep costs only O((m+n)k²) — so repeat the cheap sweep up to
    ``inner_iters`` times reusing the SAME (G, R), stopping early once the
    inner progress stalls:

        stop after sweep l when ‖X^(l+1) − X^(l)‖_F ≤ delta · ‖X^(2) − X^(1)‖_F

    (their eq. (9) criterion; ``delta=0.0`` disables the early stop —
    exactly ``inner_iters`` sweeps run as a plain ``fori_loop`` with no
    change norms computed at all, so reproducible runs also skip the
    stall collectives — while ``delta>=1`` stops right after the mandatory
    first sweep that establishes the baseline).  The change norms reduce
    through ``norm_psum`` so serial and distributed sweeps stop in lockstep;
    ``extra_latency_words`` charges those extra reductions.  The carried
    state counts the inner sweeps actually executed per half (``inner_w`` /
    ``inner_h``), surfaced after a fit in
    ``NMFResult.extras["rule_state"]`` — with an early stop the counts are
    data-dependent, which is exactly what the state carry exists for.

    Serving fold-in reuses the same machinery with the separate (much
    tighter) ``fold_delta``: training tolerates a sloppy inner solve
    because the next outer iteration refreshes (G, R), but a fold is a
    one-shot NNLS solve whose early exit must not cost accuracy.

    At ``inner_iters=1`` the accelerated rules are bit-identical to their
    plain counterparts.

    ``inner_iters=None`` derives the budget from the problem size at solve
    time — Gillis & Glineur's §3.2 heuristic: the W-half may spend up to
    ``1 + ⌊α·ρ_W⌋`` sweeps where ρ_W = 1 + (mn + nk)/(mk + m) is the ratio
    of the products' cost to one sweep's cost (ρ_H swaps m ↔ n), and α is
    the rule-specific ``accel_alpha`` they fit empirically (2.0 for MU, 0.5
    for HALS).  The derivation happens in ``prepare_global`` — the engine
    calls it with the global (m, n, k) before compiling — which returns a
    clone carrying per-half budgets ``_budget_w`` / ``_budget_h``; the
    cost hooks raise until then, since an unprepared ``None`` has no flop
    count.
    """

    #: Gillis–Glineur α of the derived inner budget 1 + ⌊α·ρ⌋ (their §3.2
    #: empirical settings: 2.0 for accelerated MU, 0.5 for accelerated HALS)
    accel_alpha: float = 2.0

    def __init__(self, *, inner_iters: int | None = 4, delta: float = 0.01,
                 fold_delta: float = 1e-6, l1: float = 0.0, l2: float = 0.0):
        super().__init__(l1=l1, l2=l2)
        if inner_iters is not None and inner_iters < 1:
            raise ValueError(f"inner_iters must be >= 1 or None (derive the "
                             f"Gillis–Glineur budget), got {inner_iters}")
        if delta < 0 or fold_delta < 0:
            raise ValueError(f"delta must be >= 0, got {delta}/{fold_delta}")
        self.inner_iters = None if inner_iters is None else int(inner_iters)
        self.delta = float(delta)
        self.fold_delta = float(fold_delta)
        # Per-half sweep budgets; fixed inner_iters applies to both halves,
        # None resolves in prepare_global.
        self._budget_w = self._budget_h = self.inner_iters

    def _derived_budget(self, rows: int, cols: int, k: int) -> int:
        rho = 1.0 + (rows * cols + cols * k) / (rows * k + rows)
        return 1 + int(self.accel_alpha * rho)

    def prepare_global(self, m, n, k):
        if self.inner_iters is not None:
            return self
        import copy
        rule = copy.copy(self)
        rule._budget_w = self._derived_budget(m, n, k)
        rule._budget_h = self._derived_budget(n, m, k)
        return rule

    def _budgets(self) -> tuple[int, int]:
        if self._budget_w is None:
            raise RuntimeError(
                f"{self.name}: inner_iters=None derives the sweep budget "
                f"from the global problem size; call prepare_global(m, n, k) "
                f"first (NMFSolver does this at fit/lower/predict time)")
        return self._budget_w, self._budget_h

    def init_state(self, m, n, k, dtype=jnp.float32):
        del m, n, k, dtype
        return {"inner_w": jnp.zeros((), jnp.int32),
                "inner_h": jnp.zeros((), jnp.int32)}

    def _accelerate(self, sweep, X, norm_psum, *, budget: int, delta: float):
        """Run up to ``budget`` sweeps with the stall criterion; returns
        (X, sweeps_executed).  ``delta=0`` runs exactly ``budget`` sweeps
        as a fori_loop — fixed trip count, and no change norms (hence no
        stall collectives) are computed at all.  Shared by the training
        half-updates (delta=self.delta, grid-reduced norms) and serving
        fold-in (delta=self.fold_delta, identity norms)."""
        one = jnp.asarray(1, jnp.int32)
        X1 = sweep(X)
        if budget <= 1:
            return X1, one
        if delta == 0.0:
            X = lax.fori_loop(1, budget, lambda _, X: sweep(X), X1)
            return X, jnp.asarray(budget, jnp.int32)

        def change(Xn, X):
            d = jnp.sum(jnp.square((Xn - X).astype(jnp.float32)))
            return jnp.sqrt(norm_psum(d))

        d0 = change(X1, X)

        def cond(carry):
            _, d, l = carry
            return (l < budget) & (d > delta * d0)

        def body(carry):
            X, _, l = carry
            Xn = sweep(X)
            return Xn, change(Xn, X), l + 1

        X, _, l = lax.while_loop(cond, body, (X1, d0, one))
        return X, l

    def _count(self, state, key, sweeps):
        if state is None:           # legacy stateless callers
            return None
        return {**state, key: state[key] + sweeps}

    def _update_w(self, G, R, X, state, *, norm_psum):
        X, l = self._accelerate(lambda X: self._sweep_w(G, R, X, norm_psum),
                                X, norm_psum, budget=self._budgets()[0],
                                delta=self.delta)
        return X, self._count(state, "inner_w", l)

    def _update_h(self, G, R, X, state, *, norm_psum):
        X, l = self._accelerate(lambda X: self._sweep_h(G, R, X, norm_psum),
                                X, norm_psum, budget=self._budgets()[1],
                                delta=self.delta)
        return X, self._count(state, "inner_h", l)

    def fold_in(self, G, R, X0=None, *, iters: int = 100):
        # The same stall machinery applied to serving: up to ``iters``
        # sweeps, early exit at the tighter fold_delta (while_loop:
        # jit-safe).  Serving batches are single-device, so the change
        # norms need no reduction.
        G, R = self.regularize(G, R)
        X, sweep = self._fold_setup(G, R, X0)
        X, _ = self._accelerate(sweep, X, _identity, budget=max(iters, 1),
                                delta=self.fold_delta)
        return X

    def luc_flops(self, m, n, k, *, bpp_iters: float = 1.0):
        # Budgeted (worst-case) flops: the early stop can only spend less.
        del bpp_iters
        bw, bh = self._budgets()
        return bw * 2.0 * m * k * k + bh * 2.0 * n * k * k

    def extra_latency_words(self, k, p):
        if p <= 1:
            return 0.0, 0.0
        # The base rule's per-sweep reductions (HALS: k column norms, a
        # W-step property) are paid on every inner W sweep; the stall-norm
        # all-reduce (one scalar per sweep, both halves) only exists when
        # the stall exit is live — at a budget of 1 or delta=0 no change
        # norm is ever computed, keeping the prediction honest for
        # configurations that execute exactly like their plain
        # counterparts.
        bw, bh = self._budgets()
        base_m, base_w = super().extra_latency_words(k, p)
        msgs, words = bw * base_m, bw * base_w
        if max(bw, bh) > 1 and self.delta > 0.0:
            msgs += (bw + bh) / 2.0 * math.log2(p)
            words += (bw + bh) * (p - 1) / p
        return msgs, words

    def cache_key(self):
        return super().cache_key() + (self.inner_iters, self.delta,
                                      self.fold_delta, self._budget_w,
                                      self._budget_h)


class AcceleratedMURule(_AcceleratedRule, MURule):
    """Gillis & Glineur accelerated MU: repeated multiplicative sweeps per
    (G, R) with the inner stall criterion."""

    name = "amu"
    accel_alpha = 2.0

    def _sweep_w(self, G, R, X, norm_psum):
        return update_mu(G, R, X)

    _sweep_h = _sweep_w


class AcceleratedHALSRule(_AcceleratedRule, HALSRule):
    """Gillis & Glineur accelerated HALS: repeated column sweeps per
    (G, R) with the inner stall criterion (the W-step keeps the paper's
    per-column normalisation on every sweep)."""

    name = "ahals"
    accel_alpha = 0.5

    def _sweep_w(self, G, R, X, norm_psum):
        return update_hals(G, R, X, normalize=True, norm_psum=norm_psum)

    def _sweep_h(self, G, R, X, norm_psum):
        return update_hals(G, R, X, normalize=False)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

RuleSpec = Union[str, UpdateRule, Type[UpdateRule]]

_REGISTRY: dict[str, Callable[[], UpdateRule]] = {}


def register_algorithm(name: str, factory: Callable[[], UpdateRule],
                       *, overwrite: bool = False) -> None:
    """Register an ``UpdateRule`` factory (a class or zero-arg callable)
    under ``name`` so ``NMFSolver(algo=name)`` finds it."""
    name = name.lower()
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"algorithm {name!r} is already registered; pass "
                         f"overwrite=True to replace it")
    _REGISTRY[name] = factory


def available_algorithms() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_rule(spec: RuleSpec) -> UpdateRule:
    """Resolve an algorithm name / instance / class to an ``UpdateRule``."""
    if isinstance(spec, UpdateRule):
        return spec
    if isinstance(spec, type) and issubclass(spec, UpdateRule):
        return spec()
    if isinstance(spec, str):
        try:
            factory = _REGISTRY[spec.lower()]
        except KeyError:
            raise ValueError(
                f"unknown NMF algorithm {spec!r}; choose from "
                f"{available_algorithms()} or register_algorithm() your own"
            ) from None
        return factory()
    raise TypeError(f"algo must be a name, UpdateRule instance, or "
                    f"UpdateRule subclass; got {type(spec).__name__}")


register_algorithm("mu", MURule)
register_algorithm("hals", HALSRule)
register_algorithm("bpp", BPPRule)
register_algorithm("abpp", BPPRule)        # the paper's name for ANLS-BPP
register_algorithm("anls", BPPRule)
register_algorithm("amu", AcceleratedMURule)
register_algorithm("ahals", AcceleratedHALSRule)
