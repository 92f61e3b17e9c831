"""Sparse backend: block-local COO SpMM — A's nonzeros never cross the wire.

Canonical representation is ``core.blocksparse.BlockCOO`` (a 1×1 grid for
serial execution, the processor grid for distributed schedules), so the same
``mm``/``mm_t`` serve every schedule: inside shard_map they see the local
block's triplets; in a global-view (gspmd) program they see the whole matrix
as one nnz-sharded block and XLA's partitioner keeps the triplets local.

Three SpMM lowerings, selected by ``spmm_impl``:

    "scatter"  jnp scatter-add (XLA scatter) — the CPU/GPU path
    "pallas"   kernels/spmm.spmm — the unsorted triplet-streaming TPU
               kernel; no preprocessing, but the whole (m_blk, k) output
               tile stays VMEM-resident
    "sorted"   kernels/spmm.spmm_sorted — the row-sorted scalar-prefetch
               TPU kernel; ``prepare``/``blockify`` call
               ``BlockCOO.sort_rows()`` so the triplets carry per-row
               segment offsets, and output rows stream through a small
               accumulator tile instead of pinning m_blk × k in VMEM
    "auto"     (default) on TPU, "sorted" when the BlockCOO already
               carries sort_rows metadata and "pallas" otherwise; off TPU,
               always "scatter".  Note "auto" never sorts on its own —
               pass spmm_impl="sorted" to opt into the sort-time
               preprocessing (and ``autotune=True`` for measured block
               sizes on either Pallas impl).

Factor panels stay dense, so ``gram`` is inherited dense fp32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.backends.base import LocalOps
from repro.core import blocksparse
from repro.obs import trace as _trace

_IMPLS = ("auto", "scatter", "pallas", "sorted")


def _is_bcoo(A) -> bool:
    return type(A).__name__ == "BCOO"


class SparseOps(LocalOps):
    name = "sparse"
    supports_panel_dtype = False     # scatter-add SpMM accumulates fp32 only
    block_leaf_ndim = 3              # BlockCOO leaves are (gr, gc, nnz)

    def __init__(self, spmm_impl: str = "auto", autotune: bool = False,
                 align: int = blocksparse.DEFAULT_ALIGN):
        if spmm_impl not in _IMPLS:
            raise ValueError(f"spmm_impl must be one of {_IMPLS}, "
                             f"got {spmm_impl!r}")
        self.spmm_impl = spmm_impl
        self.autotune = autotune
        self.align = align

    def cache_key(self):
        return super().cache_key() + (self.spmm_impl, self.autotune,
                                      self.align)

    def global_view_ops(self) -> "SparseOps":
        """Under the gspmd auto-partitioner only the XLA scatter-add is
        partitionable (a pallas_call would pin the nnz-sharded triplets to
        one device), so force impl="scatter" for global-view programs; the
        factor Grams contract in one dot there (``LocalOps``)."""
        ops = self if self.spmm_impl == "scatter" else SparseOps(
            spmm_impl="scatter")
        return LocalOps.global_view_ops(ops)

    def _impl(self, A=None, need: str = "both") -> str:
        """Effective impl for one product.  ``need`` names the sorted-layout
        orientation that product consumes ("rows" for mm, "cols" for mm_t) —
        one-orientation copies (``blockify_for`` with a single product) must
        still dispatch to the sorted kernel under "auto"."""
        if self.spmm_impl != "auto":
            return self.spmm_impl
        if jax.default_backend() == "tpu":
            if isinstance(A, blocksparse.BlockCOO):
                ok = {"rows": A.has_sorted_rows, "cols": A.has_sorted_cols,
                      "both": A.is_sorted}[need]
                if ok:
                    return "sorted"
            return "pallas"
        return "scatter"

    def _sort(self, blk: blocksparse.BlockCOO,
              orient: str = "both") -> blocksparse.BlockCOO:
        if self.spmm_impl != "sorted":
            return blk
        need_rows = orient != "cols"
        need_cols = orient != "rows"
        if (blk.align == self.align
                and (blk.has_sorted_rows or not need_rows)
                and (blk.has_sorted_cols or not need_cols)):
            return blk
        with _trace.span("nmf.sort_rows"):
            return blk.sort_rows(align=self.align, orient=orient)

    # -- products -----------------------------------------------------------

    def mm(self, A, B):
        if isinstance(A, blocksparse.BlockCOO):
            return blocksparse.local_spmm(A, B,
                                          impl=self._impl(A, need="rows"),
                                          autotune=self.autotune)
        if _is_bcoo(A):
            return A @ B
        raise ValueError(f"sparse mm needs BlockCOO/BCOO, got "
                         f"{type(A).__name__}")

    def mm_t(self, A, B):
        if isinstance(A, blocksparse.BlockCOO):
            return blocksparse.local_spmm_t(A, B,
                                            impl=self._impl(A, need="cols"),
                                            autotune=self.autotune)
        if _is_bcoo(A):
            return A.T @ B
        raise ValueError(f"sparse mm_t needs BlockCOO/BCOO, got "
                         f"{type(A).__name__}")

    # -- representation -----------------------------------------------------

    def prepare(self, A):
        """Serial canonical form: the whole matrix as one 1×1 block, so the
        serial path shares the distributed SpMM code and AOT-lowers.  With
        spmm_impl="sorted" the block is row-sorted here, at prepare time —
        never inside jit."""
        return self._sort(blocksparse.blockify(A, 1, 1))

    def blockify(self, A, gr: int, gc: int):
        return self._sort(blocksparse.blockify(A, gr, gc))

    def blockify_for(self, A, gr: int, gc: int,
                     products: tuple[str, ...] = ("mm", "mm_t")):
        """Skip the unused sorted orientation when the schedule promises a
        copy only ever runs one product (the naive schedule's row-blocked
        copy sees only ``mm``, its column-blocked copy only ``mm_t``) —
        halves the sorted layout's host-side sort work and its device
        footprint for those copies.  The hint must come from the SCHEDULE:
        inferring it from the grid shape here would be wrong (1-D faun
        grids run both products on the same blocks)."""
        prods = set(products)
        if not prods or not prods <= {"mm", "mm_t"}:
            raise ValueError(f"products must be a non-empty subset of "
                             f"('mm', 'mm_t'), got {products!r}")
        if prods == {"mm"}:
            orient = "rows"
        elif prods == {"mm_t"}:
            orient = "cols"
        else:
            orient = "both"
        return self._sort(blocksparse.blockify(A, gr, gc), orient=orient)

    def pre_blockify(self, A):
        """Run the expensive dense→COO conversion once; blockify then packs
        each layout straight from the BCOO triplets."""
        if isinstance(A, blocksparse.BlockCOO) or _is_bcoo(A):
            return A
        from jax.experimental import sparse as jsparse
        return jsparse.BCOO.fromdense(self._require_dense(A))

    def pad_global(self, A, p: int):
        return blocksparse.pad_nnz(A, p)

    def abstract_global_A(self, m: int, n: int, dtype, nnz: int | None,
                          p: int):
        Aabs = self.abstract_A(m, n, dtype, nnz, 1, 1)
        gr, gc, nnz_max = Aabs.vals.shape
        nnz_pad = nnz_max + (-nnz_max) % p
        sds = lambda dt: jax.ShapeDtypeStruct((gr, gc, nnz_pad), dt)
        return blocksparse.BlockCOO(
            vals=sds(dtype), rows=sds(jnp.int32), cols=sds(jnp.int32),
            shape=Aabs.shape, block_shape=Aabs.block_shape, nnz=Aabs.nnz)

    def norm_sq(self, A) -> jax.Array:
        if isinstance(A, blocksparse.BlockCOO):
            return blocksparse.sq_norm(A)
        if _is_bcoo(A):
            d = A.data.astype(jnp.float32)
            return jnp.sum(d * d)
        from repro.core.error import sq_frobenius
        return sq_frobenius(A)

    def abstract_A(self, m: int, n: int, dtype, nnz: int | None,
                   gr: int, gc: int):
        nnz = int(nnz) if nnz else max(m * n // 100, 1)
        nnz_max = max(-(-nnz // (gr * gc)), 1)
        sds = jax.ShapeDtypeStruct
        extra = {}
        if self.spmm_impl == "sorted":
            # Self-consistent stand-in for the sorted layout (the true
            # packed length is data-dependent): U units of `align` slots.
            mb, nb = m // gr, n // gc
            a = self.align
            U = max(-(-nnz_max // a), 1)
            unit = blocksparse.unit_rows_for(nnz_max, mb, nb, a)
            L = U * a
            extra = dict(
                row_offsets=sds((gr, gc, mb + 1), jnp.int32),
                row_tiles=sds((gr, gc, U), jnp.int32),
                row_valid=sds((gr, gc, U), jnp.int32),
                t_vals=sds((gr, gc, L), dtype),
                t_rows=sds((gr, gc, L), jnp.int32),
                t_cols=sds((gr, gc, L), jnp.int32),
                col_offsets=sds((gr, gc, nb + 1), jnp.int32),
                col_tiles=sds((gr, gc, U), jnp.int32),
                col_valid=sds((gr, gc, U), jnp.int32),
                align=a, unit_rows=unit)
            nnz_max = L
        return blocksparse.BlockCOO(
            vals=sds((gr, gc, nnz_max), dtype),
            rows=sds((gr, gc, nnz_max), jnp.int32),
            cols=sds((gr, gc, nnz_max), jnp.int32),
            shape=(m, n), block_shape=(m // gr, n // gc), nnz=nnz, **extra)

    def spec_A(self, grid):
        return grid.spec_A_sparse()

    def spec_rows(self, axis: str):
        """Row-blocked BlockCOO on a 1-D serve mesh: the (gr, gc, nnz)
        leaves shard over their leading (row-block) grid dim, triplets
        stay device-local — a request batch's nonzeros never move."""
        from jax.sharding import PartitionSpec as P
        return P(axis, None, None)

    def cast_block(self, A, dtype):
        raise ValueError("low-precision panels are not supported on the "
                         "sparse backend (scatter-add SpMM is fp32)")

    # -- cost model ---------------------------------------------------------

    def mm_flops(self, m: float, n: float, k: float,
                 nnz: float = 0.0) -> float:
        """2·nnz·k per product, two products per iteration."""
        return 4.0 * nnz * k

    def storage_words(self, m: float, n: float, nnz: float = 0.0) -> float:
        """COO triplets: value + row + col per nonzero.  The sorted layout
        stores the triplets twice (row- and column-sorted copies) plus the
        per-row/-col segment offsets."""
        coo = 3.0 * nnz
        if self.spmm_impl == "sorted":
            return 2.0 * coo + (m + 1) + (n + 1)
        return coo

    def mm_traffic_words(self, m: float, n: float, k: float,
                         nnz: float = 0.0) -> float:
        """HBM words moved by the two A-products per iteration.  The
        unsorted scatter path re-reads AND re-writes an output row per
        nonzero (read-modify-write, 2k words); the sorted path streams each
        output tile exactly once, so the quadratic-in-nnz output term
        collapses to one m·k (resp. n·k) pass — the memory-traffic
        difference that motivates sort_rows."""
        triplets = 3.0 * nnz
        if self.spmm_impl == "sorted":
            #   per product: triplets + one B row per nnz + output streamed
            return 2.0 * triplets + 2.0 * nnz * k + (m + n) * k
        return 2.0 * triplets + 2.0 * nnz * k + 4.0 * nnz * k
