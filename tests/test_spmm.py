"""BlockCOO SpMM property tests: the scatter-add path, the unsorted
Pallas triplet-streaming kernel, and the row-sorted scalar-prefetch kernel
(kernels/spmm.py, interpret mode on CPU) against the dense reference,
across grid shapes, dtypes, duplicate/padded triplets, ragged nnz, and
all-empty blocks — plus the ``sort_rows`` layout invariants.

The grid sweep emulates what shard_map does on a pr×pc mesh: each block's
triplets multiply only that block's panel slice, and block-row/-column
results accumulate — so these tests pin the per-device semantics every
schedule builds on without needing fake devices.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import blocksparse
from repro.data.pipeline import erdos_renyi_matrix
from repro.kernels import ops as kops

KEY = jax.random.PRNGKey(0)
DTYPES = [jnp.float32, jnp.bfloat16]
IMPLS = ["scatter", "pallas", "sorted"]
SORT_ALIGN = 16          # small align keeps interpret-mode loops cheap


def _tol(dt):
    return 1e-5 if dt == jnp.float32 else 2e-2


def _for_impl(blk: blocksparse.BlockCOO, impl: str) -> blocksparse.BlockCOO:
    """The representation each impl consumes: impl="sorted" needs the
    sort_rows metadata (SparseOps adds it at blockify time)."""
    return blk.sort_rows(align=SORT_ALIGN) if impl == "sorted" else blk


def _block(blk: blocksparse.BlockCOO, i: int, j: int) -> blocksparse.BlockCOO:
    """The (i, j) grid block as its own 1×1 BlockCOO (what a device holds
    inside shard_map) — slicing every leaf, sort metadata included."""
    fields = {f.name: getattr(blk, f.name)[i:i + 1, j:j + 1]
              for f in dataclasses.fields(blk)
              if f.name not in ("shape", "block_shape", "nnz", "align",
                                "unit_rows")
              and getattr(blk, f.name) is not None}
    return blocksparse.BlockCOO(shape=blk.block_shape,
                                block_shape=blk.block_shape, nnz=blk.nnz,
                                align=blk.align, unit_rows=blk.unit_rows,
                                **fields)


def _grid_spmm(blk, B, impl):
    """Σ_j A_ij @ B_j per block row — the faun W-step local products."""
    (gr, gc), (mb, nb) = blk.grid, blk.block_shape
    out = np.zeros((blk.shape[0], B.shape[1]), np.float32)
    for i in range(gr):
        for j in range(gc):
            loc = blocksparse.local_spmm(_block(blk, i, j),
                                         B[j * nb:(j + 1) * nb], impl=impl)
            out[i * mb:(i + 1) * mb] += np.asarray(loc)
    return out


def _grid_spmm_t(blk, C, impl):
    """Σ_i A_ijᵀ @ C_i per block column — the faun H-step local products."""
    (gr, gc), (mb, nb) = blk.grid, blk.block_shape
    out = np.zeros((blk.shape[1], C.shape[1]), np.float32)
    for i in range(gr):
        for j in range(gc):
            loc = blocksparse.local_spmm_t(_block(blk, i, j),
                                           C[i * mb:(i + 1) * mb], impl=impl)
            out[j * nb:(j + 1) * nb] += np.asarray(loc)
    return out


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(1, 3), st.integers(1, 12),
       st.integers(0, 10 ** 6))
def test_blockcoo_spmm_matches_dense(gr, gc, k, seed):
    key = jax.random.PRNGKey(seed)
    m, n = gr * 16, gc * 12
    for dt in DTYPES:
        Ad = erdos_renyi_matrix(key, m, n, 0.25, dtype=dt)
        blk = blocksparse.blockify(Ad, gr, gc)
        B = jax.random.normal(jax.random.fold_in(key, 1), (n, k),
                              jnp.float32).astype(dt)
        C = jax.random.normal(jax.random.fold_in(key, 2), (m, k),
                              jnp.float32).astype(dt)
        A32 = np.asarray(Ad, np.float32)
        for impl in IMPLS:
            rep = _for_impl(blk, impl)
            np.testing.assert_allclose(_grid_spmm(rep, B, impl),
                                       A32 @ np.asarray(B, np.float32),
                                       atol=_tol(dt), rtol=_tol(dt))
            np.testing.assert_allclose(_grid_spmm_t(rep, C, impl),
                                       A32.T @ np.asarray(C, np.float32),
                                       atol=_tol(dt), rtol=_tol(dt))


@pytest.mark.parametrize("impl", IMPLS)
def test_blockcoo_spmm_all_empty_blocks(impl):
    """A block (and a whole matrix) with zero nonzeros must produce exact
    zeros — the padding triplets are no-ops by construction."""
    blk = _for_impl(blocksparse.blockify(jnp.zeros((32, 24)), 2, 2), impl)
    B = jax.random.normal(KEY, (24, 5))
    C = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 5))
    assert np.abs(_grid_spmm(blk, B, impl)).max() == 0.0
    assert np.abs(_grid_spmm_t(blk, C, impl)).max() == 0.0


@pytest.mark.parametrize("impl", IMPLS)
def test_blockcoo_spmm_ragged_blocks(impl):
    """One dense-ish block next to empty blocks: per-block nnz padding must
    not leak across blocks."""
    Ad = np.zeros((32, 24), np.float32)
    rng = np.random.RandomState(3)
    Ad[:16, :12] = rng.rand(16, 12) * (rng.rand(16, 12) < 0.5)
    Ad = jnp.asarray(Ad)
    blk = _for_impl(blocksparse.blockify(Ad, 2, 2), impl)
    B = jax.random.normal(KEY, (24, 7))
    np.testing.assert_allclose(_grid_spmm(blk, B, impl),
                               np.asarray(Ad @ B), atol=1e-5)


@pytest.mark.parametrize("impl", ["sorted"])
def test_sorted_ragged_nnz_rows(impl):
    """Heavily skewed per-row nnz (one hot row, many empty rows) exercises
    the tile-aligned packing: multi-unit segments, empty tiles the grid
    never visits (masked to exact zero), and partial last units."""
    m, n, k = 40, 24, 5
    Ad = np.zeros((m, n), np.float32)
    rng = np.random.RandomState(7)
    Ad[3, :] = rng.rand(n)                      # hot row: nnz ≫ align
    Ad[17, 5] = 1.25                            # lone nonzero mid-matrix
    blk = blocksparse.blockify(jnp.asarray(Ad), 1, 1).sort_rows(align=8)
    B = rng.rand(n, k).astype(np.float32)
    out = blocksparse.local_spmm(blk, jnp.asarray(B), impl=impl)
    np.testing.assert_allclose(np.asarray(out), Ad @ B, atol=1e-5)
    empty = np.setdiff1d(np.arange(m), [3, 17])
    assert np.abs(np.asarray(out)[empty]).max() == 0.0


def test_sort_rows_round_trips_bit_for_bit():
    """sort_rows must represent the SAME matrix bit-for-bit (stable sort,
    zero-padding no-ops) and leave the original untouched."""
    Ad = erdos_renyi_matrix(jax.random.PRNGKey(11), 48, 36, 0.2)
    blk = blocksparse.blockify(Ad, 3, 2)
    dense_before = blk.todense()
    srt = blk.sort_rows(align=SORT_ALIGN)
    assert srt.is_sorted and not blk.is_sorted
    assert np.array_equal(srt.todense(), dense_before)
    assert np.array_equal(blk.todense(), dense_before)
    assert srt.nnz == blk.nnz and srt.shape == blk.shape
    # fp32 norm identical: padding values are exact zeros
    assert float(blocksparse.sq_norm(srt)) == float(blocksparse.sq_norm(blk))


def test_sort_rows_layout_invariants():
    """Per-block invariants the sorted kernel relies on: rows
    non-decreasing within each valid segment, offsets consistent with the
    per-row counts, tile ids non-decreasing, valid ≤ align, and packed
    segments that never cross a row unit's boundary."""
    Ad = erdos_renyi_matrix(jax.random.PRNGKey(5), 64, 40, 0.15)
    srt = blocksparse.blockify(Ad, 2, 2).sort_rows(align=SORT_ALIGN)
    gr, gc = srt.grid
    mb = srt.block_shape[0]
    dense = np.asarray(Ad)
    for i in range(gr):
        for j in range(gc):
            offs = np.asarray(srt.row_offsets[i, j])
            tiles = np.asarray(srt.row_tiles[i, j])
            valid = np.asarray(srt.row_valid[i, j])
            rows = np.asarray(srt.rows[i, j])
            blk_dense = dense[i * mb:(i + 1) * mb,
                              j * srt.block_shape[1]:(j + 1)
                              * srt.block_shape[1]]
            counts = offs[1:] - offs[:-1]
            # offsets count every stored triplet of the block (incl. the
            # _pack_triplets zero padding, which sorts into its row segment)
            assert offs[0] == 0 and offs[-1] >= np.count_nonzero(blk_dense)
            assert (counts >= 0).all()
            assert (np.diff(tiles) >= 0).all()
            assert ((valid >= 0) & (valid <= SORT_ALIGN)).all()
            unit = srt.unit_rows
            for u, t in enumerate(tiles):
                seg = rows[u * SORT_ALIGN:u * SORT_ALIGN + valid[u]]
                assert (np.diff(seg) >= 0).all()
                # all valid rows of a unit live inside the unit's rows
                assert t * 8 % unit == 0
                assert ((seg >= t * 8) & (seg < t * 8 + unit)).all()


def test_sorted_row_units_span_several_tiles():
    """A sparse block packs row units of several 8-row tiles (so a large
    align pads a fraction of the data, not a multiple of it); the products
    are unchanged and every unit's rows stay inside the unit."""
    Ad = erdos_renyi_matrix(jax.random.PRNGKey(6), 256, 64, 0.02)
    srt = blocksparse.blockify(Ad, 1, 1).sort_rows(align=SORT_ALIGN)
    assert srt.unit_rows == 16
    offs = np.asarray(srt.row_offsets[0, 0])
    per_tile = offs[8::8] - offs[:-8:8]            # nonzeros per 8 rows
    narrow = int((-(-per_tile // SORT_ALIGN)).sum()) * SORT_ALIGN
    assert srt.vals.shape[-1] < narrow
    rows, tiles = np.asarray(srt.rows[0, 0]), np.asarray(srt.row_tiles[0, 0])
    valid = np.asarray(srt.row_valid[0, 0])
    for u, t in enumerate(tiles):
        seg = rows[u * SORT_ALIGN:u * SORT_ALIGN + valid[u]]
        assert ((seg >= t * 8) & (seg < t * 8 + srt.unit_rows)).all()
    B = jax.random.normal(KEY, (64, 5))
    C = jax.random.normal(jax.random.fold_in(KEY, 1), (256, 5))
    Ad = np.asarray(Ad, np.float32)
    np.testing.assert_allclose(
        np.asarray(blocksparse.local_spmm(srt, B, impl="sorted")),
        Ad @ np.asarray(B), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(blocksparse.local_spmm_t(srt, C, impl="sorted")),
        Ad.T @ np.asarray(C), atol=1e-5)


def test_unit_rows_for_sparse_phase_block():
    """The compiled kernel's align (1,024) on the one-chip sparse block
    (43,200 × 28,800 at density 1e-3) gives 64-row units: ~1,843 nonzeros
    a unit in the sparser orientation, where an 8-row tile holds ~230."""
    assert blocksparse.unit_rows_for(1_243_546, 43_200, 28_800, 1024) == 64
    assert blocksparse.unit_rows_for(10 ** 9, 100, 100, 1024) == 8
    assert blocksparse.unit_rows_for(0, 100, 100, 1024) == \
        blocksparse.MAX_UNIT_ROWS


def test_sorted_requires_metadata():
    blk = blocksparse.blockify(jnp.zeros((16, 8)).at[3, 2].set(1.0), 1, 1)
    B = jnp.ones((8, 4))
    with pytest.raises(ValueError, match="sort_rows"):
        blocksparse.local_spmm(blk, B, impl="sorted")
    with pytest.raises(ValueError, match="sort_rows"):
        blocksparse.local_spmm_t(blk, jnp.ones((16, 4)), impl="sorted")


def test_sort_rows_single_orientation():
    """orient="rows"/"cols" stores only that orientation's arrays (half the
    host work and device memory when a copy runs one product only), and
    the other product's sorted impl refuses with a clear error."""
    Ad = erdos_renyi_matrix(jax.random.PRNGKey(9), 32, 24, 0.2)
    blk = blocksparse.blockify(Ad, 1, 1)
    B = jax.random.normal(KEY, (24, 5))
    C = jax.random.normal(jax.random.fold_in(KEY, 1), (32, 5))
    rows_only = blk.sort_rows(align=SORT_ALIGN, orient="rows")
    assert rows_only.has_sorted_rows and not rows_only.has_sorted_cols
    assert rows_only.t_vals is None and not rows_only.is_sorted
    np.testing.assert_allclose(
        np.asarray(blocksparse.local_spmm(rows_only, B, impl="sorted")),
        np.asarray(Ad, np.float32) @ np.asarray(B), atol=1e-5)
    with pytest.raises(ValueError, match="orient"):
        blocksparse.local_spmm_t(rows_only, C, impl="sorted")
    cols_only = blk.sort_rows(align=SORT_ALIGN, orient="cols")
    assert cols_only.has_sorted_cols and not cols_only.has_sorted_rows
    np.testing.assert_allclose(
        np.asarray(blocksparse.local_spmm_t(cols_only, C, impl="sorted")),
        np.asarray(Ad, np.float32).T @ np.asarray(C), atol=1e-5)
    with pytest.raises(ValueError, match="orient"):
        blocksparse.local_spmm(cols_only, B, impl="sorted")


def test_blockify_for_prunes_unused_orientation():
    """The naive schedule's product hint must reach sort_rows(orient=...):
    a copy promised to only run mm stores no transposed arrays (and vice
    versa), while the default / both-products path keeps both — and the
    one-orientation copies still produce correct products."""
    from repro.backends import DenseOps, SparseOps
    Ad = erdos_renyi_matrix(jax.random.PRNGKey(11), 32, 24, 0.2)
    ops = SparseOps(spmm_impl="sorted", align=SORT_ALIGN)
    row_copy = ops.blockify_for(Ad, 2, 1, products=("mm",))
    col_copy = ops.blockify_for(Ad, 1, 2, products=("mm_t",))
    both = ops.blockify_for(Ad, 2, 2)
    assert row_copy.has_sorted_rows and not row_copy.has_sorted_cols
    assert col_copy.has_sorted_cols and not col_copy.has_sorted_rows
    assert both.is_sorted
    with pytest.raises(ValueError, match="products"):
        ops.blockify_for(Ad, 1, 1, products=("gram",))
    # dense backends ignore the hint (delegates to plain blockify)
    np.testing.assert_array_equal(
        np.asarray(DenseOps().blockify_for(Ad, 2, 1, products=("mm",))),
        np.asarray(Ad))
    # the engine feeds the hint from the naive schedule; parity holds
    from repro.core.engine import NMFSolver
    key = jax.random.PRNGKey(0)
    ref = NMFSolver(4, algo="mu", schedule="naive",
                    backend=SparseOps(spmm_impl="scatter"),
                    max_iters=4).fit(Ad, key=key)
    got = NMFSolver(4, algo="mu", schedule="naive", backend=ops,
                    max_iters=4).fit(Ad, key=key)
    np.testing.assert_allclose(np.asarray(got.rel_errors),
                               np.asarray(ref.rel_errors), atol=1e-5)


def test_pad_nnz_drops_sort_metadata():
    """gspmd's nnz padding breaks the tile-aligned layout, so it must
    strip the sorted fields rather than ship a stale layout."""
    Ad = erdos_renyi_matrix(jax.random.PRNGKey(2), 32, 24, 0.2)
    srt = blocksparse.blockify(Ad, 1, 1).sort_rows(align=SORT_ALIGN)
    padded = blocksparse.pad_nnz(srt, 7)
    assert not padded.is_sorted
    assert np.array_equal(padded.todense(), srt.todense())


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 64), st.integers(1, 48), st.integers(1, 16),
       st.integers(0, 300), st.integers(0, 10 ** 6))
def test_pallas_spmm_scatter_semantics(m, n, k, nnz, seed):
    """kernels/ops.spmm on raw triplets (with duplicate indices) must match
    np.add.at densification — true scatter-ADD semantics, any shape."""
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, m, size=nnz).astype(np.int32)
    cols = rng.randint(0, n, size=nnz).astype(np.int32)
    vals = rng.rand(nnz).astype(np.float32)
    B = rng.rand(n, k).astype(np.float32)
    C = rng.rand(m, k).astype(np.float32)
    Ad = np.zeros((m, n), np.float32)
    np.add.at(Ad, (rows, cols), vals)
    got = kops.spmm(jnp.asarray(vals), jnp.asarray(rows), jnp.asarray(cols),
                    jnp.asarray(B), m)
    np.testing.assert_allclose(np.asarray(got), Ad @ B, atol=1e-4)
    got_t = kops.spmm_t(jnp.asarray(vals), jnp.asarray(rows),
                        jnp.asarray(cols), jnp.asarray(C), n)
    np.testing.assert_allclose(np.asarray(got_t), Ad.T @ C, atol=1e-4)


def test_sorted_spmm_duplicate_indices():
    """Duplicate (row, col) triplets must accumulate in the sorted layout
    too (stable sort keeps them adjacent, the kernel adds them all)."""
    rows = np.array([5, 5, 5, 2, 5], np.int32)
    cols = np.array([1, 1, 3, 0, 1], np.int32)
    vals = np.array([1.0, 2.0, 4.0, 8.0, 16.0], np.float32)
    blk = blocksparse._pack_triplets(vals, rows, cols, 16, 8, 1, 1, nnz=5)
    srt = blk.sort_rows(align=8)
    B = np.eye(8, 3, dtype=np.float32)
    Ad = np.zeros((16, 8), np.float32)
    np.add.at(Ad, (rows, cols), vals)
    out = blocksparse.local_spmm(srt, jnp.asarray(B), impl="sorted")
    np.testing.assert_allclose(np.asarray(out), Ad @ B, atol=1e-6)


@pytest.mark.parametrize("schedule", ["serial", "faun", "naive"])
def test_sorted_backend_matches_scatter_through_engine(schedule):
    """spmm_impl="sorted" must match the scatter oracle on every schedule
    it is reachable from (gspmd forces scatter via global_view_ops)."""
    from repro.backends import SparseOps
    from repro.core.engine import NMFSolver
    A = erdos_renyi_matrix(jax.random.PRNGKey(3), 48, 32, 0.1)
    key = jax.random.PRNGKey(0)
    ref = NMFSolver(4, algo="mu", schedule=schedule,
                    backend=SparseOps(spmm_impl="scatter"),
                    max_iters=5).fit(A, key=key)
    got = NMFSolver(4, algo="mu", schedule=schedule,
                    backend=SparseOps(spmm_impl="sorted", align=16),
                    max_iters=5).fit(A, key=key)
    np.testing.assert_allclose(np.asarray(got.rel_errors),
                               np.asarray(ref.rel_errors), atol=1e-5)
