"""Naive-Parallel-AUNMF (paper Algorithm 2; Fairbanks et al. scheme).

The communication-inefficient baseline the paper measures against:

  * A is stored TWICE — once row-distributed (A_i of m/p × n) and once
    column-distributed (Aⁱ of m × n/p); with ``backend="sparse"`` the two
    copies are a row-blocked (p, 1) and a column-blocked (1, p)
    ``core.blocksparse.BlockCOO``, so even the naive schedule never ships
    A's nonzeros — only its factor gathers are wasteful;
  * each half-iteration all-gathers the ENTIRE fixed factor
    (O((m+n)k) words vs FAUN's O(√(mnk²/p)));
  * every processor redundantly computes the k×k Gram of the full factor.

We reproduce it faithfully (including the redundant Gram) on a 1-D mesh so
benchmarks/bench_cost_table.py can show measured-HLO communication words of
Naive vs FAUN, mirroring the paper's Figure 5/Table III comparison.  The
local products come from a ``repro.backends.LocalOps`` backend, same as
every other schedule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import rules as _rules
from repro.core.aunmf import NMFResult
from repro.util.compat import shard_map


def naive_iteration(Arow, Acol, W_blk, Ht_blk, normA_sq, state, *, axis: str,
                    algo, ops=None, compress=None):
    """One iteration of Algorithm 2 on local blocks (inside shard_map).

    Arow: (m/p, n)   row block of A          W_blk: (m/p, k)
    Acol: (m, n/p)   column block of A       Ht_blk: (n/p, k)
    (both A blocks in whatever representation ``ops`` understands);
    ``state`` is the update rule's carry pytree (None for stateless rules),
    replicated over the mesh.  Under ``compress`` the carry is
    ``(rule_state, residuals)``: the two full-factor all-gathers — the
    schedule's only panel collectives — move int8 payloads with error
    feedback, and the residuals travel device-local (stacked leading mesh
    dim).
    """
    if ops is None:
        from repro.backends import DenseOps
        ops = DenseOps()
    rule = _rules.get_rule(algo)
    res = None
    if compress is not None:
        state, res_stacked = state
        res = {key: v.reshape(v.shape[1:]) for key, v in res_stacked.items()}

    def norm_psum(v):
        return lax.psum(v, axis)

    def panel_allgather(x, key):
        if compress is None:
            return lax.all_gather(x, axis, axis=0, tiled=True)
        y, res[key] = compress.all_gather(x, (axis,), res[key])
        return y

    # Each phase under its ``repro.obs.phases.expected_phases("naive")``
    # key, so every op of the compiled step names its phase.
    scope = jax.named_scope

    # --- W given H: all-gather whole H, redundant Gram (paper lines 3-4) ---
    with scope("allgather_h"):
        Ht = panel_allgather(Ht_blk, "gather_h")              # (n, k)
    with scope("gram_w"):
        HHt = ops.gram(Ht)                                    # redundant k×k
    with scope("mm_w"):
        AHt_blk = ops.mm(Arow, Ht)                            # (m/p, k)
    with scope("luc_w"):
        W_blk, state = rule.update_w(HHt, AHt_blk, W_blk, state,
                                     norm_psum=norm_psum)

    # --- H given W: all-gather whole W, redundant Gram (lines 5-6) ---
    with scope("allgather_w"):
        W = panel_allgather(W_blk, "gather_w")                # (m, k)
    with scope("gram_h"):
        WtW = ops.gram(W)
    with scope("mm_h"):
        WtA_t_blk = ops.mm_t(Acol, W)                         # (n/p, k)
    with scope("luc_h"):
        Ht_blk, state = rule.update_h(WtW, WtA_t_blk, Ht_blk, state,
                                      norm_psum=norm_psum)

    # --- error from byproducts ---
    with scope("error"):
        HHt_new = lax.psum(ops.gram(Ht_blk), axis)
        cross = lax.psum(jnp.sum(WtA_t_blk.astype(jnp.float32)
                                 * Ht_blk.astype(jnp.float32)), axis)
        quad = jnp.sum(WtW.astype(jnp.float32)
                       * HHt_new.astype(jnp.float32))
        sq_err = normA_sq - 2.0 * cross + quad
    if compress is not None:
        state = (state, {key: v[None] for key, v in res.items()})
    return W_blk, Ht_blk, sq_err, state


def naive_residual_spec(axis: str) -> P:
    """Spec of one stacked residual leaf: (p, local_rows, k), device-local."""
    return P(axis, None, None)


def init_naive_residuals(p: int, m: int, n: int, k: int):
    """Zero error-feedback residuals for Algorithm 2's two factor gathers."""
    return {"gather_h": jnp.zeros((p, n // p, k), jnp.float32),
            "gather_w": jnp.zeros((p, m // p, k), jnp.float32)}


def build_naive_step(mesh: Mesh, *, algo, axis: str = "p", ops=None,
                     panel_compression: str | None = None):
    from repro.backends import get_backend
    ops = get_backend(ops if ops is not None else "dense")
    compress = None
    state_spec = P()
    if panel_compression is not None:
        from repro.distributed.compression import get_compressor
        compress = get_compressor(panel_compression, dict(mesh.shape))
        state_spec = (P(), naive_residual_spec(axis))
    body = functools.partial(naive_iteration, axis=axis,
                             algo=_rules.get_rule(algo), ops=ops,
                             compress=compress)
    extra = (None,) * (ops.block_leaf_ndim - 2)   # BlockCOO triplet dim
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None, *extra), P(None, axis, *extra),
                  P(axis, None), P(axis, None), P(), state_spec),
        out_specs=(P(axis, None), P(axis, None), P(), state_spec),
    )


def fit(A, k: int, *, mesh: Mesh, algo: str = "bpp", iters: int = 30,
        key: jax.Array | None = None, H0: jax.Array | None = None,
        W0: jax.Array | None = None, axis: str = "p",
        backend: str | None = None,
        panel_compression: str | None = None) -> NMFResult:
    """Thin wrapper over ``core.engine.NMFSolver(schedule="naive")``; sparse
    input (BCOO / BlockCOO) routes through the block-local SpMM backend."""
    from repro.backends import infer_backend
    from repro.core.engine import NMFSolver
    if backend is None:
        backend = infer_backend(A)
    solver = NMFSolver(k, algo=algo, schedule="naive", backend=backend,
                       mesh=mesh, axis=axis, max_iters=iters,
                       panel_compression=panel_compression)
    return solver.fit(A, key=key, H0=H0, W0=W0)


def lower_step(mesh: Mesh, m: int, n: int, k: int, *, algo: str = "bpp",
               dtype=jnp.float32, axis: str = "p", backend: str = "dense",
               nnz: int | None = None):
    """AOT-lower one Naive iteration for HLO accounting."""
    from repro.core.engine import NMFSolver
    solver = NMFSolver(k, algo=algo, schedule="naive", backend=backend,
                       mesh=mesh, axis=axis)
    return solver.lower_step(m, n, dtype=dtype, nnz=nnz)
