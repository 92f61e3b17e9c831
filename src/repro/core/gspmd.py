"""GSPMD-auto AU-NMF: the same iteration as core/faun.py but written as a
plain global-view jit program with only input/output shardings annotated —
XLA's SPMD partitioner chooses the collective schedule.

This is the natural control experiment for the paper's claim: does a
modern auto-partitioner re-derive the hand-scheduled algorithm?
MEASURED ANSWER (benchmarks/results/perf/nmf_gspmd_vs_faithful.json, video
workload on the 128×2 grid): **no — GSPMD moves 121× more wire bytes**
(531.5 MB vs 4.39 MB per iteration per chip).  XLA keeps the Gram
all-reduces but reshards the big products with all-to-alls instead of the
paper's panel-gather → local-GEMM → reduce-scatter pipeline.  The 2016
communication-optimal schedule still has to be written by hand — which is
exactly what core/faun.py's shard_map build does, and the strongest
empirical justification of the paper's contribution this repo produces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import rules as _rules
from repro.core.aunmf import NMFResult
from repro.core.error import sq_error_from_products
from repro.core.faun import FaunGrid


def gspmd_iteration(A, W, Ht, normA_sq, state, *, algo, ops=None,
                    compress=None):
    """Global-view AU-NMF iteration; no explicit collectives anywhere.

    ``ops`` supplies the A-products on the *global* representation: dense
    arrays for DenseOps/PallasOps, or one nnz-sharded BlockCOO for
    SparseOps — XLA's partitioner then keeps the triplets local and
    all-reduces the k-width partial products (the engine's distributed
    checks assert this in the lowered HLO).  The update rule sees global
    factors, so its reductions need no psum (``norm_psum`` stays identity);
    ``state`` is the rule's carry pytree (None for stateless rules).

    ``compress`` is a NUMERICS-ONLY emulation here: XLA owns gspmd's wire,
    so the quantise→dequantise (+ error feedback) runs where the hand
    schedules' collectives sit — on the four reduced products — and the
    carry becomes ``(rule_state, residuals)`` with global-shaped residual
    leaves the partitioner shards like the products themselves.  Wire-byte
    claims for compression apply to faun/naive only (see
    ``Int8PanelCompressor.simulate``).
    """
    if ops is None:
        from repro.backends import DenseOps
        ops = DenseOps()
    rule = _rules.get_rule(algo)
    res = None
    if compress is not None:
        state, res = state[0], dict(state[1])
    # Each phase under its ``repro.obs.phases.expected_phases("gspmd")``
    # key; the collectives XLA inserts take the phase that forced them.
    scope = jax.named_scope
    with scope("gram_w"):
        HHt = ops.gram(Ht)
        if compress is not None:
            HHt, res["gram_w"] = compress.simulate_gram(HHt, res["gram_w"])
    with scope("mm_w"):
        AHt = ops.mm(A, Ht)
        if compress is not None:
            AHt, res["rs_w"] = compress.simulate(AHt, res["rs_w"])
    with scope("luc_w"):
        W, state = rule.update_w(HHt, AHt, W, state)
    with scope("gram_h"):
        WtW = ops.gram(W)
        if compress is not None:
            WtW, res["gram_h"] = compress.simulate_gram(WtW, res["gram_h"])
    with scope("mm_h"):
        WtA_t = ops.mm_t(A, W)
        if compress is not None:
            WtA_t, res["rs_h"] = compress.simulate(WtA_t, res["rs_h"])
    with scope("luc_h"):
        Ht, state = rule.update_h(WtW, WtA_t, Ht, state)
    with scope("error"):
        sq = sq_error_from_products(normA_sq, WtA_t.T, Ht.T, WtW,
                                    ops.gram(Ht))
    if compress is not None:
        state = (state, res)
    return W, Ht, sq, state


def init_gspmd_residuals(m: int, n: int, k: int):
    """Zero error-feedback residuals for the emulated compression of the
    four global products (global-shaped; the partitioner shards them)."""
    return {"gram_w": jnp.zeros((k, k), jnp.float32),
            "rs_w": jnp.zeros((m, k), jnp.float32),
            "gram_h": jnp.zeros((k, k), jnp.float32),
            "rs_h": jnp.zeros((n, k), jnp.float32)}


def fit(A, k: int, *, grid: FaunGrid, algo: str = "bpp", iters: int = 30,
        key: jax.Array | None = None, H0: jax.Array | None = None,
        W0: jax.Array | None = None, backend: str | None = None,
        panel_compression: str | None = None) -> NMFResult:
    """Run the GSPMD-auto variant end to end (XLA picks the collectives).
    Thin wrapper over ``core.engine.NMFSolver(schedule="gspmd")``."""
    from repro.backends import infer_backend
    from repro.core.engine import NMFSolver
    if backend is None:
        backend = infer_backend(A)
    solver = NMFSolver(k, algo=algo, schedule="gspmd", grid=grid,
                       backend=backend, max_iters=iters,
                       panel_compression=panel_compression)
    return solver.fit(A, key=key, H0=H0, W0=W0)


def lower_step(grid: FaunGrid, m: int, n: int, k: int, *, algo: str = "mu",
               dtype=jnp.float32, backend: str = "dense",
               nnz: int | None = None):
    """Lower one GSPMD-auto iteration with the paper's data layouts as
    in/out shardings (same layouts as faun.lower_step, no shard_map)."""
    from repro.core.engine import NMFSolver
    solver = NMFSolver(k, algo=algo, schedule="gspmd", grid=grid,
                       backend=backend)
    return solver.lower_step(m, n, dtype=dtype, nnz=nnz)
