"""Serial AU-NMF (paper Algorithm 1) — the single-device oracle.

This is the reference implementation every parallel path (core/faun.py,
core/naive.py, GSPMD variant) is tested against for *bit-level* agreement
given the same initial H: the parallel schedules reorganise the same
floating-point matrix products, and with matched reduction orders they agree
to fp tolerance.

The data matrix appears only inside the three local products (A·Hᵀ, AᵀW,
and the factor Grams), which ``aunmf_step`` takes as hooks — the engine
fills them from a ``repro.backends.LocalOps`` backend (dense XLA, Pallas
kernels, or sparse SpMM), so sparsity and kernel choice are contained in
that layer, exactly as in the paper where only the local SpMM changes.

``fit`` is a thin compatibility wrapper over ``core.engine.NMFSolver`` with
``schedule="serial"``; the iteration body (``aunmf_step``) and the factor
initialisers live here and are what the engine composes.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.error import sq_error_from_products


@dataclass
class NMFResult:
    W: Any
    H: Any
    rel_errors: Any       # (iters,) relative error after each full iteration
    algo: str = "bpp"
    iters: int = 0
    extras: dict = field(default_factory=dict)

    def save_artifact(self, path: str, **meta) -> str:
        """Persist the trained factors as a serving artifact (factors +
        precomputed Gram + metadata) — see ``repro.serve.artifact``."""
        from repro.serve.artifact import FactorArtifact
        return FactorArtifact.from_result(self, **meta).save(path)


def init_h(key: jax.Array, n: int, k: int, dtype=jnp.float32) -> jax.Array:
    """Paper §6.1.3: H initialised uniform at random (W derived on iter 1)."""
    return jax.random.uniform(key, (k, n), dtype=dtype)


def init_w(key: jax.Array, m: int, k: int, algo, dtype=jnp.float32):
    """W needs no init for additive / re-solving rules (HALS, BPP, ...);
    multiplicative rules (the MU family) declare ``positive_init`` and get
    a strictly positive seed (paper's code seeds it uniform as well).
    ``algo`` is anything ``rules.get_rule`` resolves — a registered name or
    an ``UpdateRule`` instance."""
    from repro.core import rules
    if rules.get_rule(algo).positive_init:
        return jax.random.uniform(key, (m, k), dtype=dtype, minval=0.1, maxval=1.0)
    return jnp.zeros((m, k), dtype)


def aunmf_step_rule(A, W, H, rule, state, normA_sq, *,
                    mm: Callable | None = None, mm_t: Callable | None = None,
                    gram: Callable | None = None, norm_psum=lambda v: v):
    """One full AU-NMF iteration through an ``UpdateRule``; returns
    (W, H, sq_error, state).

    ``rule`` is a ``repro.core.rules.UpdateRule`` and ``state`` its carry
    pytree (None for stateless rules) — the engine threads it through the
    compiled loop.  ``mm``/``mm_t``/``gram`` are the
    ``repro.backends.LocalOps`` local products (``mm(A, B) -> A @ B``,
    ``mm_t(A, B) -> Aᵀ @ B``, ``gram(X) -> XᵀX``); the engine always
    supplies them from the selected backend.  None falls back to plain XLA
    (with the BCOO-aware default for sparse A: (AᵀW)ᵀ keeps A
    un-transposed) for direct callers.

    Each phase runs under ``jax.named_scope`` with its key from
    ``repro.obs.phases.expected_phases("serial")`` (``gram_w``, ``mm_w``,
    ``luc_w``, ..., ``error``), so every op of the compiled step names
    its phase in its HLO ``op_name`` and in a device profile.
    """
    with jax.named_scope("gram_w"):
        HHt = gram(H.T) if gram is not None else H @ H.T
    with jax.named_scope("mm_w"):
        AHt = mm(A, H.T) if mm is not None else A @ H.T
    with jax.named_scope("luc_w"):
        W, state = rule.update_w(HHt, AHt, W, state, norm_psum=norm_psum)
    with jax.named_scope("gram_h"):
        WtW = gram(W) if gram is not None else W.T @ W
    with jax.named_scope("mm_h"):
        if mm_t is not None:
            WtA = mm_t(A, W).T
        elif isinstance(A, jax.Array):
            WtA = W.T @ A
        else:  # BCOO: (Aᵀ W)ᵀ via transposed matvec path
            WtA = (A.T @ W).T
    with jax.named_scope("luc_h"):
        Ht, state = rule.update_h(WtW, WtA.T, H.T, state,
                                  norm_psum=norm_psum)
        H = Ht.T
    with jax.named_scope("error"):
        HHt_new = gram(H.T) if gram is not None else H @ H.T
        sq = sq_error_from_products(normA_sq, WtA, H, WtW, HHt_new)
    return W, H, sq, state


def aunmf_step(A, W, H, update_w, update_h, normA_sq, *,
               mm: Callable | None = None, mm_t: Callable | None = None,
               gram: Callable | None = None):
    """Stateless legacy spelling of ``aunmf_step_rule``: plain
    ``(G, R, X) -> X`` update closures (e.g. ``algorithms.get_update_fns``
    output), no rule state; returns (W, H, sq_error)."""
    from repro.core import rules
    rule = rules._FunctionRule(update_w, update_h)
    W, H, sq, _ = aunmf_step_rule(A, W, H, rule, None, normA_sq,
                                  mm=mm, mm_t=mm_t, gram=gram)
    return W, H, sq


def fit(A, k: int, *, algo: str = "bpp", iters: int = 30,
        key: jax.Array | None = None, H0: jax.Array | None = None,
        W0: jax.Array | None = None) -> NMFResult:
    """Run AU-NMF for a fixed number of iterations (the paper's stopping
    criterion for all benchmarks).  Dense arrays use the dense backend; BCOO
    input routes through the sparse backend unchanged."""
    from repro.backends import infer_backend
    from repro.core.engine import NMFSolver
    solver = NMFSolver(k, algo=algo, schedule="serial",
                       backend=infer_backend(A), max_iters=iters)
    return solver.fit(A, key=key, H0=H0, W0=W0)
