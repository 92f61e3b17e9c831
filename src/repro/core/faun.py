"""MPI-FAUN (paper Algorithm 3) on a TPU mesh via shard_map.

Layouts (paper Fig. 2), for a pr × pc grid with p = pr·pc:

    A    (m, n)  → P("pr", "pc")            A_ij   is (m/pr, n/pc)
    W    (m, k)  → P(("pr", "pc"), None)    (W_i)_j is (m/p, k)
    H    (k, n)  → P(None, ("pc", "pr"))    (H^j)^i is (k, n/p)

Per-iteration schedule (exactly the paper's six collectives):

  W-step:
    U_ij = (H^j)^i (H^j)^iᵀ            local Gram               [line 3]
    HHᵀ  = all-reduce(U_ij)            psum over ("pr","pc")    [line 4]
    H^j  = all-gather_{pr}((H^j)^i)    panel gather             [line 5]
    V_ij = A_ij · H^jᵀ                 local GEMM (Pallas-able) [line 6]
    (AHᵀ)_i = reduce-scatter_{pc}(V)   psum_scatter over rows   [line 7]
    (W_i)_j = UpdateW(HHᵀ, ·)          LUC                      [line 8]
  H-step: symmetric with pr ↔ pc                                [lines 9–14]

The multi-pod mesh adds a leading "pod" axis folded into the row dimension of
the grid (pr_eff = pod·pr): FAUN is grid-shape agnostic, so multi-pod is just
a taller processor grid whose slow inter-pod hops carry only factor panels
(never A) — the paper's "never communicate the data matrix" invariant is what
makes cross-pod NMF viable at all.

Relative error uses the byproduct trick (core/error.py): per-iteration cost
is one extra k×k local Gram + scalars in the existing all-reduce.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import rules as _rules
from repro.core.aunmf import NMFResult
from repro.util.compat import shard_map


# ---------------------------------------------------------------------------
# The paper's three communication primitives, reused by distributed/ for FSDP.
# ---------------------------------------------------------------------------

def gram_allreduce(X_loc: jax.Array, axes: Sequence[str],
                   transpose: bool = True) -> jax.Array:
    """k×k Gram of a distributed tall-skinny matrix: local XᵀX + all-reduce."""
    G = X_loc.T @ X_loc if transpose else X_loc @ X_loc.T
    return lax.psum(G, tuple(axes))


def allgather_panel(X_loc: jax.Array, axis: str, *, concat_axis: int) -> jax.Array:
    """All-gather a factor panel along one grid axis (paper lines 5/11)."""
    return lax.all_gather(X_loc, axis, axis=concat_axis, tiled=True)


def matmul_reducescatter(Y_loc: jax.Array, axis: str, *,
                         scatter_axis: int) -> jax.Array:
    """Reduce-scatter a local GEMM result along one grid axis (lines 7/13)."""
    return lax.psum_scatter(Y_loc, axis, scatter_dimension=scatter_axis,
                            tiled=True)


# ---------------------------------------------------------------------------
# FAUN iteration body (runs inside shard_map; everything below is per-device)
# ---------------------------------------------------------------------------

def faun_iteration(A_blk, W_blk, Ht_blk, normA_sq, state, *, row_axes,
                   col_axis, algo, ops=None, panel_dtype=None,
                   compress=None):
    """One AU-NMF iteration of Algorithm 3 on local blocks.

    A_blk  : (m/prE, n/pc)  local data block (prE = pod*pr on multi-pod),
                            in whatever representation ``ops`` understands
                            (dense array, BlockCOO triplets, ...)
    W_blk  : (m/p, k)       local W rows
    Ht_blk : (n/p, k)       local Hᵀ rows  (H column block, transposed)
    state  : the update rule's carry pytree (None for stateless rules),
             replicated across the grid; under ``compress`` the carry is
             ``(rule_state, residuals)`` with the error-feedback residuals
             stacked over leading mesh-axis dims (device-local)
    row_axes: mesh axis name(s) forming the grid-row dimension ("pod","pr")
    col_axis: mesh axis name for grid columns ("pc")
    algo   : a registered algorithm name or ``repro.core.rules.UpdateRule``
    ops    : repro.backends.LocalOps supplying the local products
             (None = DenseOps, plain XLA)
    compress: a ``repro.distributed.compression`` panel compressor (None =
             the exact collectives, bit-identical to the pre-compression
             path)

    Returns (W_blk, Ht_blk, sq_err, state).
    """
    all_axes = tuple(row_axes) + (col_axis,)
    if ops is None:
        from repro.backends import DenseOps
        ops = DenseOps()
    rule = _rules.get_rule(algo)
    res = None
    if compress is not None:
        # Unstack the per-device residual carry: leaves arrive with
        # singleton leading mesh-axis dims (one per grid axis).
        state, res_stacked = state
        n_lead = len(all_axes)
        res = {key: v.reshape(v.shape[n_lead:])
               for key, v in res_stacked.items()}
    mm, mm_t, gram = ops.mm, ops.mm_t, ops.gram
    if panel_dtype is not None:
        # Beyond-paper: ship factor panels over the wire in bf16 (half the
        # all-gather bytes); the backend accumulates fp32 on the MXU and
        # casts its local A block to match.
        cast = lambda x: ops.cast_block(x, panel_dtype)
    else:
        cast = lambda x: x

    def norm_psum(v):  # rule-level reductions (HALS column norms,
        return lax.psum(v, all_axes)        # accelerated stall norms, ...)

    # Low-precision panel gathers: ship the bf16 *bit pattern* (u16) so CPU
    # XLA's f32-dot legalization cannot commute the widening convert back
    # across the collective (on TPU bf16 dots are native and the bitcasts
    # are free views — wire bytes are what we measure here either way).
    if panel_dtype is not None:
        bits = jnp.uint16 if panel_dtype == jnp.bfloat16 else None

        def gather_low(x, axis):
            xl = x.astype(panel_dtype)
            if bits is not None:
                xl = lax.bitcast_convert_type(xl, bits)
            g = allgather_panel(xl, axis, concat_axis=0)
            if bits is not None:
                g = lax.bitcast_convert_type(g, panel_dtype)
            return g
    else:
        def gather_low(x, axis):
            return allgather_panel(x, axis, concat_axis=0)

    # The four panel collectives route through one indirection: exact
    # psum / all-gather / psum_scatter, or the int8 + error-feedback
    # equivalents (each threading its residual through ``res``).
    if compress is None:
        def panel_allreduce(x, axes, _key):
            return lax.psum(x, tuple(axes))

        def panel_allgather(x, axes, _key):
            g = gather_low(x, axes[0])
            for ax in axes[1:]:
                g = allgather_panel(g, ax, concat_axis=0) \
                    if panel_dtype is None else gather_low(g, ax)
            return g

        def panel_reduce_scatter(x, axes, _key):
            # Scatter outer-to-inner to land in the staged block layout.
            for ax in axes:
                x = matmul_reducescatter(x, ax, scatter_axis=0)
            return x
    else:
        def panel_allreduce(x, axes, key):
            y, res[key] = compress.allreduce(x, tuple(axes), res[key])
            return y

        def panel_allgather(x, axes, key):
            y, res[key] = compress.all_gather(x, tuple(axes), res[key])
            return y

        def panel_reduce_scatter(x, axes, key):
            y, res[key] = compress.reduce_scatter(x, tuple(axes), res[key])
            return y

    # Each phase under its ``repro.obs.phases.expected_phases("faun")``
    # key, so every op of the compiled step names its phase.
    scope = jax.named_scope

    # ---- W given H (paper lines 3–8) ----
    with scope("gram_w"):
        U = gram(Ht_blk)
    with scope("allreduce_gram_w"):
        HHt = panel_allreduce(U, all_axes, "gram_w")              # k×k
    with scope("allgather_h"):
        # Gather innermost-axis first (multi-pod finishes across pods).
        Hj_t = panel_allgather(Ht_blk, tuple(reversed(row_axes)),
                               "gather_h")
    with scope("mm_w"):
        V = mm(cast(A_blk), Hj_t)                                 # (m/prE, k)
    with scope("reduce_scatter_w"):
        AHt_blk = panel_reduce_scatter(V, (col_axis,), "rs_w")    # (m/p, k)
    with scope("luc_w"):
        W_blk, state = rule.update_w(HHt, AHt_blk, W_blk, state,
                                     norm_psum=norm_psum)

    # ---- H given W (paper lines 9–14) ----
    with scope("gram_h"):
        U = gram(W_blk)
    with scope("allreduce_gram_h"):
        WtW = panel_allreduce(U, all_axes, "gram_h")              # k×k
    with scope("allgather_w"):
        Wi = panel_allgather(W_blk, (col_axis,), "gather_w")      # (m/prE, k)
    with scope("mm_h"):
        Yt = mm_t(cast(A_blk), Wi)                                # (n/pc, k)
    with scope("reduce_scatter_h"):
        # Scatter outer-to-inner (pod, then pr) to land in the
        # (pc,pod,pr) layout.
        WtA_t_blk = panel_reduce_scatter(Yt, tuple(row_axes), "rs_h")
    with scope("luc_h"):
        Ht_blk, state = rule.update_h(WtW, WtA_t_blk, Ht_blk, state,
                                      norm_psum=norm_psum)

    # ---- relative error from byproducts (one extra k×k Gram) ----
    with scope("error"):
        HHt_new = lax.psum(gram(Ht_blk), all_axes)
        cross = lax.psum(
            jnp.sum(WtA_t_blk.astype(jnp.float32)
                    * Ht_blk.astype(jnp.float32)), all_axes)
        quad = jnp.sum(WtW.astype(jnp.float32)
                       * HHt_new.astype(jnp.float32))
        sq_err = normA_sq - 2.0 * cross + quad
    if compress is not None:
        state = (state, {key: v.reshape((1,) * len(all_axes) + v.shape)
                         for key, v in res.items()})
    return W_blk, Ht_blk, sq_err, state


# ---------------------------------------------------------------------------
# Host-level driver
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FaunGrid:
    """Names the mesh axes used as the FAUN processor grid."""
    mesh: Mesh
    row_axes: tuple[str, ...] = ("pr",)    # ("pod","pr") on multi-pod meshes
    col_axis: str = "pc"

    @property
    def pr(self) -> int:
        return math.prod(self.mesh.shape[a] for a in self.row_axes)

    @property
    def pc(self) -> int:
        return self.mesh.shape[self.col_axis]

    @property
    def p(self) -> int:
        return self.pr * self.pc

    # Global-array shardings implied by the paper's Fig. 2 layouts.
    def spec_A(self) -> P:
        return P(self.row_axes if len(self.row_axes) > 1 else self.row_axes[0],
                 self.col_axis)

    def spec_A_sparse(self) -> P:
        """Layout for BlockCOO leaves (gr, gc, nnz): grid dims sharded, the
        per-block triplet dim replicated (each device holds its own block)."""
        return P(self.row_axes if len(self.row_axes) > 1 else self.row_axes[0],
                 self.col_axis, None)

    def spec_W(self) -> P:
        return P(tuple(self.row_axes) + (self.col_axis,), None)

    def spec_Ht(self) -> P:
        return P((self.col_axis,) + tuple(self.row_axes), None)

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)


def faun_residual_spec(grid: FaunGrid) -> P:
    """PartitionSpec of one stacked error-feedback residual leaf: every
    leaf is a per-device (rows, k) panel stacked over leading mesh-axis
    dims (one per grid axis), so residuals travel device-local through
    shard_map instead of replicated like rule state."""
    return P(*grid.row_axes, grid.col_axis, None, None)


def init_faun_residuals(grid: FaunGrid, m: int, n: int, k: int):
    """Zero error-feedback residuals for the six compressed collectives of
    one FAUN iteration, keyed like ``faun_iteration`` consumes them.  Leaf
    layout: (*mesh_axis_sizes, local_rows, k) fp32."""
    lead = tuple(grid.mesh.shape[a] for a in grid.row_axes) \
        + (grid.mesh.shape[grid.col_axis],)
    pr, pc, p = grid.pr, grid.pc, grid.p
    z = lambda *s: jnp.zeros(lead + s, jnp.float32)
    return {
        "gram_w": z(k, k),            # HHᵀ all-reduce
        "gather_h": z(n // p, k),     # H panel all-gather
        "rs_w": z(m // pr, k),        # A·Hᵀ reduce-scatter
        "gram_h": z(k, k),            # WᵀW all-reduce
        "gather_w": z(m // p, k),     # W panel all-gather
        "rs_h": z(n // pc, k),        # WᵀA reduce-scatter
    }


def make_faun_mesh(pr: int, pc: int, *, devices=None) -> FaunGrid:
    devices = devices if devices is not None else jax.devices()
    assert len(devices) >= pr * pc, (len(devices), pr, pc)
    import numpy as np
    mesh = Mesh(np.asarray(devices[: pr * pc]).reshape(pr, pc), ("pr", "pc"))
    return FaunGrid(mesh=mesh)


def build_faun_step(grid: FaunGrid, *, algo, ops=None,
                    backend: str | None = None, use_pallas: bool = False,
                    panel_dtype=None, panel_compression: str | None = None):
    """Returns step(A, W, Ht, normA_sq, state) -> (W, Ht, sq_err, state) as
    a shard_mapped, jit-compatible callable over *global* arrays.

    ``ops`` is the ``repro.backends.LocalOps`` backend computing the local
    products (and defining A's blocked representation — for SparseOps, A
    enters as a core.blocksparse.BlockCOO and never crosses the wire);
    ``algo`` is a registered algorithm name or an UpdateRule instance,
    whose carry pytree travels replicated (the ``P()`` specs).
    ``backend="dense"|"pallas"|"sparse"`` and ``use_pallas=True`` are the
    legacy spellings, resolved through the same registry.

    With ``panel_compression="int8"`` the step's carry is
    ``(rule_state, residuals)`` — build the residual half with
    ``init_faun_residuals(grid, m, n, k)`` — and the panel collectives move
    int8 payloads with fp32 row-scale sidecars and error feedback.
    """
    from repro.backends import get_backend
    if ops is None:
        ops = get_backend(backend or ("pallas" if use_pallas else "dense"))
    if panel_dtype is not None and not ops.supports_panel_dtype:
        raise ValueError(f"low-precision panels are not supported on the "
                         f"{ops.name!r} backend")
    compress = None
    state_spec = P()
    if panel_compression is not None:
        from repro.distributed.compression import get_compressor
        compress = get_compressor(panel_compression, dict(grid.mesh.shape))
        state_spec = (P(), faun_residual_spec(grid))

    body = functools.partial(
        faun_iteration, row_axes=grid.row_axes, col_axis=grid.col_axis,
        algo=_rules.get_rule(algo), ops=ops, panel_dtype=panel_dtype,
        compress=compress)

    return shard_map(
        body, mesh=grid.mesh,
        in_specs=(ops.spec_A(grid), grid.spec_W(), grid.spec_Ht(), P(),
                  state_spec),
        out_specs=(grid.spec_W(), grid.spec_Ht(), P(), state_spec),
    )


def fit(A, k: int, *, grid: FaunGrid, algo: str = "bpp", iters: int = 30,
        key: jax.Array | None = None, H0: jax.Array | None = None,
        W0: jax.Array | None = None, use_pallas: bool = False,
        panel_dtype=None, panel_compression: str | None = None,
        donate: bool = True) -> NMFResult:
    """Distributed AU-NMF.  Bit-compatible with core.aunmf.fit given the same
    (W0, H0) up to collective reduction-order rounding.

    Thin wrapper over ``core.engine.NMFSolver(schedule="faun")``; sparse
    input (BCOO / BlockCOO) routes through the block-local SpMM backend.
    """
    from repro.backends import infer_backend
    from repro.core.engine import NMFSolver
    backend = "pallas" if use_pallas else infer_backend(A)
    solver = NMFSolver(k, algo=algo, schedule="faun", backend=backend,
                       grid=grid, max_iters=iters, panel_dtype=panel_dtype,
                       panel_compression=panel_compression, donate=donate)
    return solver.fit(A, key=key, H0=H0, W0=W0)


def lower_step(grid: FaunGrid, m: int, n: int, k: int, *, algo: str = "bpp",
               dtype=jnp.float32, use_pallas: bool = False, panel_dtype=None,
               panel_compression: str | None = None,
               backend: str | None = None, nnz: int | None = None):
    """AOT-lower one FAUN iteration for dry-run / roofline analysis."""
    from repro.core.engine import NMFSolver
    if backend is None:
        backend = "pallas" if use_pallas else "dense"
    solver = NMFSolver(k, algo=algo, schedule="faun", backend=backend,
                       grid=grid, panel_dtype=panel_dtype,
                       panel_compression=panel_compression)
    return solver.lower_step(m, n, dtype=dtype, nnz=nnz)
