"""Block-local sparse storage for the distributed-sparse NMF path.

The paper's invariant is that the data matrix A is **never communicated** —
only k-width factor panels cross the wire.  For sparse A on a pr × pc
processor grid we therefore store each grid block A_ij as block-local COO
triplets, padded to the max per-block nnz so the three ``(gr, gc, nnz_max)``
arrays shard cleanly over the mesh: every device holds exactly its own
block's triplets and nothing else.  Padding entries are ``(row=0, col=0,
val=0)`` and contribute nothing to the scatter-add SpMM, so they are safe by
construction (same trick as the Pallas kernels' zero padding).

The local SpMM kernels below are the ONLY sparse-aware compute — exactly
how PL-NMF (arXiv:1904.07935) and DID (arXiv:1802.08938) contain sparsity.
They back ``repro.backends.SparseOps``, the sparse ``LocalOps``
implementation, so every schedule in core/engine.py (serial, faun, naive,
gspmd) runs unchanged on top of them: the serial path uses a 1×1 grid, faun
the pr×pc grid, naive a row-blocked (p, 1) plus a column-blocked (1, p)
copy, and gspmd one nnz-sharded 1×1 block under the auto-partitioner.  On
TPU the scatter-add lowers to a Pallas kernel (kernels/spmm.py) via
``impl="pallas"`` (unsorted triplet streaming) or ``impl="sorted"`` (the
locality-optimized variant — requires ``BlockCOO.sort_rows()`` metadata,
see below).

Row sorting (``sort_rows``) reorders each block's triplets by row at
blockify time and records three per-block index arrays per orientation:

  * ``row_offsets`` (mb+1,)  CSR-style prefix counts — offsets of each
    row's triplet segment in the *unpadded* sorted order;
  * ``row_tiles``   (U,)     the first 8-row output tile of the row unit
    (``unit_rows`` rows) each ``align``-sized packed unit of triplets
    belongs to;
  * ``row_valid``   (U,)     how many triplets of each unit are real
    (the rest are zero-padding no-ops).

plus a transposed copy (``t_vals``/``t_rows``/``t_cols`` with
``col_offsets``/``col_tiles``/``col_valid``) holding the same nonzeros
sorted by column, so Aᵀ·B runs through the *same* sorted kernel with the
(rows ↔ cols) swap trick and Aᵀ is never materialised.  The packed layout
pads each row unit's segment to a multiple of ``align`` so a kernel nnz
chunk never spans two output tiles — that is what lets kernels/spmm.py
stream output rows through a small accumulator tile with scalar prefetch
instead of pinning the whole (m_blk, k) output in VMEM.  A row unit is
``unit_rows`` rows, 8 or a power-of-two multiple of 8 chosen at sort time
so that a unit holds about ``align`` nonzeros or more: the padding stays a
fraction of the data even when ``align`` is the compiled kernel's 1,024.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


#: Default packed-segment alignment for ``sort_rows`` (triplets per unit).
#: The sorted kernel's nnz chunk size must divide it, and the compiled
#: kernel streams chunks of a multiple of 1024 (XLA's tile of a 1-D array
#: in SMEM); interpret-mode tests pass smaller aligns to keep loops cheap.
DEFAULT_ALIGN = 1024

#: Least output-row granularity of the sorted layout (the fp32 sublane
#: count): segments are aligned per row unit of ``unit_rows`` rows, a
#: power-of-two multiple of ROW_TILE, so any accumulator height that is a
#: multiple of the unit keeps chunks inside one output tile.
ROW_TILE = 8

#: Most rows one unit may span (the sorted kernel's accumulator tile is at
#: least a unit tall: 1,024 × 128 fp32 is 512 KiB of VMEM).
MAX_UNIT_ROWS = 1024


def unit_rows_for(nnz: float, mb: int, nb: int, align: int) -> int:
    """Rows per packed unit for a block of ``nnz`` nonzeros over (mb, nb):
    the least ROW_TILE·2^j (≤ MAX_UNIT_ROWS) whose mean count in the
    sparser orientation reaches ``align``.  At align 1,024 the 43,200 ×
    28,800 block at density 1e-3 gets 64-row units, ~1,843 and ~2,765
    nonzeros a unit in its two orientations, padded to 2,048 and 3,072
    (1.11×); per 8-row tile (~230 and ~346) it would be 4.4× and 3.0×."""
    per_line = nnz / max(mb, nb, 1)
    t = ROW_TILE
    while t < MAX_UNIT_ROWS and t * per_line < align:
        t *= 2
    return t

# Sorted-orientation array-field names (children of the pytree, all rank 3).
_SORT_FIELDS = ("row_offsets", "row_tiles", "row_valid",
                "t_vals", "t_rows", "t_cols",
                "col_offsets", "col_tiles", "col_valid")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockCOO:
    """A (gr, gc)-blocked sparse matrix as padded block-local COO triplets.

    vals/rows/cols are (gr, gc, nnz_max); rows/cols are int32 indices
    *within* the block.  ``shape`` is the global (m, n); ``block_shape`` is
    (m/gr, n/gc); ``nnz`` the true (pre-padding) nonzero count.

    After ``sort_rows()`` the triplets are row-sorted in the tile-aligned
    packed layout and the nine optional metadata leaves (see the module
    docstring) are populated; ``align`` records the packing alignment
    (0 ⇒ unsorted) and ``unit_rows`` the rows of one packed unit.  All
    leaves keep the leading (gr, gc) grid dims so one PartitionSpec shards
    the whole pytree.
    """

    vals: Any
    rows: Any
    cols: Any
    shape: tuple[int, int]
    block_shape: tuple[int, int]
    nnz: int
    # -- sort_rows metadata (None until sorted; all (gr, gc, X) int32
    #    except t_vals which matches vals' dtype) --
    row_offsets: Any = None
    row_tiles: Any = None
    row_valid: Any = None
    t_vals: Any = None
    t_rows: Any = None
    t_cols: Any = None
    col_offsets: Any = None
    col_tiles: Any = None
    col_valid: Any = None
    align: int = 0
    unit_rows: int = ROW_TILE

    @property
    def dtype(self):
        return self.vals.dtype

    @property
    def grid(self) -> tuple[int, int]:
        return (self.shape[0] // self.block_shape[0],
                self.shape[1] // self.block_shape[1])

    @property
    def has_sorted_rows(self) -> bool:
        return self.row_offsets is not None

    @property
    def has_sorted_cols(self) -> bool:
        return self.col_offsets is not None

    @property
    def is_sorted(self) -> bool:
        """Full (both-orientation) sort metadata — what mm AND mm_t need."""
        return self.has_sorted_rows and self.has_sorted_cols

    def tree_flatten(self):
        return ((self.vals, self.rows, self.cols)
                + tuple(getattr(self, f) for f in _SORT_FIELDS),
                (self.shape, self.block_shape, self.nnz, self.align,
                 self.unit_rows))

    @classmethod
    def tree_unflatten(cls, aux, children):
        shape, block_shape, nnz, align, unit_rows = aux
        return cls(*children[:3], shape, block_shape, nnz,
                   *children[3:], align=align, unit_rows=unit_rows)

    def sort_rows(self, *, align: int = DEFAULT_ALIGN,
                  orient: str = "both") -> "BlockCOO":
        """Row-sorted copy with scalar-prefetch metadata (host-side; see
        module-level ``sort_rows``).  Bit-for-bit the same matrix."""
        return sort_rows(self, align=align, orient=orient)

    def todense(self) -> np.ndarray:
        """Host-side densification (tests / small problems only)."""
        gr, gc = self.grid
        mb, nb = self.block_shape
        out = np.zeros(self.shape, dtype=np.asarray(self.vals).dtype)
        V = np.asarray(self.vals)
        R = np.asarray(self.rows)
        C = np.asarray(self.cols)
        for i in range(gr):
            for j in range(gc):
                # += so duplicate (padding) indices accumulate like the SpMM
                np.add.at(out[i * mb:(i + 1) * mb, j * nb:(j + 1) * nb],
                          (R[i, j], C[i, j]), V[i, j])
        return out


def _pack_triplets(vals, rows, cols, m: int, n: int, gr: int, gc: int,
                   nnz: int) -> BlockCOO:
    """Pack host-side global COO triplets into the padded per-block layout.
    Zero-valued entries (BCOO padding, zeros that survived a cast) are kept
    — they are no-ops under scatter-add, same as our own padding."""
    if m % gr or n % gc:
        raise ValueError(f"A of shape {(m, n)} does not tile a "
                         f"{gr}×{gc} grid")
    mb, nb = m // gr, n // gc
    flat = (rows // mb) * gc + (cols // nb)
    order = np.argsort(flat, kind="stable")
    flat_s = flat[order]
    counts = np.bincount(flat_s, minlength=gr * gc)
    nnz_max = max(int(counts.max()) if counts.size else 0, 1)
    starts = np.concatenate([[0], np.cumsum(counts)])
    slot = np.arange(flat_s.size) - starts[flat_s]

    V = np.zeros((gr * gc, nnz_max), dtype=vals.dtype)
    R = np.zeros((gr * gc, nnz_max), dtype=np.int32)
    C = np.zeros((gr * gc, nnz_max), dtype=np.int32)
    V[flat_s, slot] = vals[order]
    R[flat_s, slot] = rows[order] % mb
    C[flat_s, slot] = cols[order] % nb

    return BlockCOO(
        vals=jnp.asarray(V.reshape(gr, gc, nnz_max)),
        rows=jnp.asarray(R.reshape(gr, gc, nnz_max)),
        cols=jnp.asarray(C.reshape(gr, gc, nnz_max)),
        shape=(m, n), block_shape=(mb, nb), nnz=nnz)


def from_bcoo(A, gr: int, gc: int) -> BlockCOO:
    """Blockify a ``jax.experimental.sparse.BCOO`` matrix for a gr×gc grid."""
    idx = np.asarray(A.indices)
    vals = np.asarray(A.data)
    return _pack_triplets(vals, idx[:, 0], idx[:, 1], A.shape[0], A.shape[1],
                          gr, gc, nnz=int(vals.size))


def _global_triplets(blk: BlockCOO):
    """Host-side flat global-index triplets of a BlockCOO, padding
    stripped.

    The stored arrays carry zero-valued no-op entries — the per-block
    nnz_max padding, and after ``sort_rows`` also the tile-alignment
    padding and ``_stack_padded`` tails.  Re-blockifying those as if they
    were real triplets inflates the new blocking's nnz_max on every grid
    change (each remesh compounding the last), so drop them here: ALL
    padding has val == 0 exactly, and zero-valued triplets are no-ops
    under the scatter-add semantics, so this is lossless.  (Explicit
    zero-valued entries from user BCOO data are dropped too — same
    no-op argument; ``nnz`` metadata travels separately.)"""
    gr, gc = blk.grid
    mb, nb = blk.block_shape
    bi = np.arange(gr, dtype=np.int64)[:, None, None]
    bj = np.arange(gc, dtype=np.int64)[None, :, None]
    vals = np.asarray(blk.vals).reshape(-1)
    rows = (np.asarray(blk.rows, np.int64) + bi * mb).reshape(-1)
    cols = (np.asarray(blk.cols, np.int64) + bj * nb).reshape(-1)
    keep = vals != 0
    return vals[keep], rows[keep], cols[keep]


def blockify(A, gr: int, gc: int) -> BlockCOO:
    """BlockCOO from dense, BCOO, or a BlockCOO (re-blocked if its grid
    differs — the data is converted once and repacked per layout, e.g. the
    naive schedule's row- and column-blocked copies)."""
    if isinstance(A, BlockCOO):
        if A.grid == (gr, gc):
            return A
        vals, rows, cols = _global_triplets(A)
        return _pack_triplets(vals, rows, cols, A.shape[0], A.shape[1],
                              gr, gc, nnz=A.nnz)
    if isinstance(A, np.ndarray):
        A = jnp.asarray(A)
    if isinstance(A, jax.Array):
        from jax.experimental import sparse as jsparse
        A = jsparse.BCOO.fromdense(A)
    return from_bcoo(A, gr, gc)


def sq_norm(A: BlockCOO) -> jax.Array:
    """||A||_F² in fp32, the same bits for every layout of the same matrix.

    Squares are first summed per row of each block, in stored order, then
    over the fixed-length row vector.  Padding adds exact zeros, and
    ``sort_rows`` is a stable sort, so a row's nonzeros meet in the same
    order in the unsorted and sorted layouts; a flat sum over the stored
    triplets would group them by position and differ in the last bit."""
    gr, gc = A.grid
    mb = A.block_shape[0]
    v = A.vals.astype(jnp.float32)
    block = jnp.arange(gr * gc, dtype=jnp.int32).reshape(gr, gc, 1)
    seg = (block * mb + A.rows).reshape(-1)
    per_row = jax.ops.segment_sum((v * v).reshape(-1), seg,
                                  num_segments=gr * gc * mb)
    return jnp.sum(per_row)


def pad_nnz(blk: BlockCOO, multiple: int) -> BlockCOO:
    """Pad each block's triplet dim to a multiple (zero no-op entries), so
    the nnz dimension can be sharded evenly — the gspmd sparse layout.
    Drops any ``sort_rows`` metadata: tail padding breaks the tile-aligned
    packed layout (gspmd forces the scatter impl anyway)."""
    nnz_max = blk.vals.shape[-1]
    pad = (-nnz_max) % multiple
    if pad == 0 and not blk.align:
        return blk
    widths = ((0, 0), (0, 0), (0, pad))
    return BlockCOO(vals=jnp.pad(blk.vals, widths),
                    rows=jnp.pad(blk.rows, widths),
                    cols=jnp.pad(blk.cols, widths),
                    shape=blk.shape, block_shape=blk.block_shape, nnz=blk.nnz)


# ---------------------------------------------------------------------------
# Row sorting — the locality-optimized layout for kernels/spmm.spmm_sorted.
# ---------------------------------------------------------------------------

def _sorted_layout(vals, rows, cols, dim: int, align: int, unit_rows: int):
    """Sort ONE block's triplets by ``rows`` and pack them per row unit of
    ``unit_rows`` rows, each unit's segment zero-padded to a multiple of
    ``align``.

    Returns numpy arrays (pv, pr, pc, offsets, tiles, valid): the packed
    triplets (length U·align), CSR prefix offsets over the *unpadded*
    sorted order (dim+1,), and per-unit first 8-row tile ids / valid counts
    (U,).  Padding entries are (row = the unit's first row, col = 0,
    val = 0) — no-ops for both the sorted kernel (skipped via ``valid``)
    and scatter-add.
    """
    order = np.argsort(rows, kind="stable")
    sv, sr, sc = vals[order], rows[order], cols[order]
    offs = np.searchsorted(sr, np.arange(dim + 1)).astype(np.int32)
    ntiles = -(-dim // unit_rows)
    bounds = np.minimum(np.arange(ntiles + 1) * unit_rows, dim)
    t_start, t_end = offs[bounds[:-1]], offs[bounds[1:]]
    units = -(-(t_end - t_start) // align)          # 0 ⇒ empty tile, skipped
    U = int(units.sum())
    pv = np.zeros(U * align, dtype=vals.dtype)
    pr = np.zeros(U * align, dtype=np.int32)
    pc = np.zeros(U * align, dtype=np.int32)
    tiles = np.zeros(U, dtype=np.int32)
    valid = np.zeros(U, dtype=np.int32)
    u = pos = 0
    for t in np.flatnonzero(units):
        s, e = int(t_start[t]), int(t_end[t])
        ln, nu = e - s, int(units[t])
        pv[pos:pos + ln] = sv[s:e]
        pr[pos:pos + ln] = sr[s:e]
        pc[pos:pos + ln] = sc[s:e]
        pr[pos + ln:pos + nu * align] = t * unit_rows
        tiles[u:u + nu] = t * (unit_rows // ROW_TILE)
        valid[u:u + nu] = np.minimum(
            np.maximum(ln - np.arange(nu) * align, 0), align)
        u += nu
        pos += nu * align
    return pv, pr, pc, offs, tiles, valid


def _stack_padded(blocks, gr: int, gc: int, pad_tiles):
    """Stack per-block 1-D arrays into (gr, gc, X), zero-padding each to the
    longest.  ``pad_tiles`` gives, per block, the tile id tail padding should
    carry (the last real unit's tile — keeps the grid on one output block)."""
    out = []
    for arrs, fill_from_tiles in blocks:
        L = max(a.shape[0] for a in arrs)
        padded = []
        for idx, a in enumerate(arrs):
            pad = L - a.shape[0]
            if pad and fill_from_tiles:
                a = np.concatenate(
                    [a, np.full(pad, pad_tiles[idx], dtype=a.dtype)])
            elif pad:
                a = np.concatenate([a, np.zeros(pad, dtype=a.dtype)])
            padded.append(a)
        out.append(jnp.asarray(
            np.stack(padded).reshape(gr, gc, L)))
    return out


def sort_rows(blk: BlockCOO, *, align: int = DEFAULT_ALIGN,
              orient: str = "both") -> BlockCOO:
    """Row-sorted copy of ``blk`` carrying per-row segment offsets — the
    layout ``kernels/spmm.spmm_sorted`` streams with scalar prefetch.

    Host-side (numpy), like ``blockify`` — call it at blockify time on
    concrete arrays, never inside jit.  The result is the same matrix
    bit-for-bit (stable sort; zero-padding adds are no-ops), still valid
    for the scatter and triplet-streaming impls, plus:

      * vals/rows/cols re-packed row-sorted and tile-aligned (see
        ``_sorted_layout``) with ``row_offsets``/``row_tiles``/``row_valid``;
      * a column-sorted *transposed* copy (``t_vals``/``t_rows``/``t_cols``
        hold Aᵀ's triplets with ``col_offsets``/``col_tiles``/``col_valid``)
        so ``local_spmm_t`` uses the identical kernel — the (rows ↔ cols)
        swap trick at the storage level.

    ``orient`` limits the work to one orientation when the caller knows
    only one product runs on this copy: "rows" (mm only) skips the
    transposed arrays, "cols" (mm_t only) skips the row re-pack — e.g. the
    naive schedule's row-blocked copy only ever sees mm.  Default "both".
    The rows of a packed unit come from the block's density
    (``unit_rows_for``).
    """
    if align <= 0 or align % ROW_TILE:
        raise ValueError(f"align must be a positive multiple of {ROW_TILE}, "
                         f"got {align}")
    gr, gc = blk.grid
    mb, nb = blk.block_shape
    unit_rows = unit_rows_for(blk.nnz / (gr * gc), mb, nb, align)
    if orient not in ("both", "rows", "cols"):
        raise ValueError(f"orient must be both|rows|cols, got {orient!r}")
    V = np.asarray(blk.vals).reshape(gr * gc, -1)
    R = np.asarray(blk.rows).reshape(gr * gc, -1)
    C = np.asarray(blk.cols).reshape(gr * gc, -1)
    last_tile = lambda lay: [int(x[4][-1]) if x[4].size else 0 for x in lay]
    kw: dict = {}
    if orient != "cols":
        row = [_sorted_layout(V[b], R[b], C[b], mb, align, unit_rows)
               for b in range(gr * gc)]
        (pv, pr, pc, r_tiles, r_valid) = _stack_padded(
            [([x[0] for x in row], False), ([x[1] for x in row], False),
             ([x[2] for x in row], False), ([x[4] for x in row], True),
             ([x[5] for x in row], False)], gr, gc, last_tile(row))
        kw.update(vals=pv, rows=pr, cols=pc, row_tiles=r_tiles,
                  row_valid=r_valid, row_offsets=jnp.asarray(
                      np.stack([x[3] for x in row]).reshape(gr, gc, -1)))
    if orient != "rows":
        col = [_sorted_layout(V[b], C[b], R[b], nb, align,   # Aᵀ: cols drive
                              unit_rows)
               for b in range(gr * gc)]
        (tv, tr, tc, c_tiles, c_valid) = _stack_padded(
            [([x[0] for x in col], False), ([x[1] for x in col], False),
             ([x[2] for x in col], False), ([x[4] for x in col], True),
             ([x[5] for x in col], False)], gr, gc, last_tile(col))
        kw.update(t_vals=tv, t_rows=tr, t_cols=tc, col_tiles=c_tiles,
                  col_valid=c_valid, col_offsets=jnp.asarray(
                      np.stack([x[3] for x in col]).reshape(gr, gc, -1)))
    return dataclasses.replace(blk, align=align, unit_rows=unit_rows, **kw)


# ---------------------------------------------------------------------------
# Local SpMM kernels — what repro.backends.SparseOps.mm/mm_t lower to.
# Run inside shard_map on the device-local block (leaves are (1, 1, nnz)),
# or on the whole matrix for the serial (1×1 grid) and gspmd (global-view,
# nnz-sharded) paths.
# ---------------------------------------------------------------------------

def _local_triplets(blk: BlockCOO):
    return (blk.vals.reshape(-1), blk.rows.reshape(-1), blk.cols.reshape(-1))


def _require_sorted(blk: BlockCOO, orientation: bool, leaf) -> None:
    if not orientation:
        raise ValueError(
            "impl='sorted' needs the sorted layout for this orientation — "
            "call BlockCOO.sort_rows() at blockify time (SparseOps"
            "(spmm_impl='sorted') does this for you; orient='rows' covers "
            "mm only, 'cols' mm_t only); sorting is host-side and cannot "
            "run inside jit")
    # Check the LEAF dims, not the shape-derived grid: inside shard_map the
    # leaves are sliced to (1, 1, ·) while the static `shape` aux stays
    # global, so blk.grid still reports the full mesh there.
    if leaf.shape[:2] != (1, 1):
        raise ValueError(
            f"local_spmm(impl='sorted') operates on ONE local block; got "
            f"leaves blocked {leaf.shape[0]}×{leaf.shape[1]} — slice out "
            f"the device's block first (shard_map leaves are (1, 1, ...))")


def local_spmm(blk: BlockCOO, B: jax.Array, *, impl: str = "scatter",
               autotune: bool = False) -> jax.Array:
    """A_blk @ B: (m_blk, n_blk) sparse × (n_blk, k) -> (m_blk, k) fp32.

    impl="scatter" is the XLA scatter-add (CPU/GPU); impl="pallas" lowers
    to the unsorted triplet-streaming kernel in kernels/spmm.py and
    impl="sorted" to its row-sorted scalar-prefetch variant (both interpret
    mode off-TPU; "sorted" requires ``sort_rows`` metadata).  ``autotune``
    turns on measured block sizes for the two Pallas impls.
    """
    if impl == "sorted":
        _require_sorted(blk, blk.has_sorted_rows, blk.vals)
        from repro.kernels import ops as kops
        return kops.spmm_sorted(
            blk.vals.reshape(-1), blk.rows.reshape(-1),
            blk.cols.reshape(-1), blk.row_offsets.reshape(-1),
            blk.row_tiles.reshape(-1), blk.row_valid.reshape(-1), B,
            blk.block_shape[0], align=blk.align, unit_rows=blk.unit_rows,
            autotune=autotune)
    v, r, c = _local_triplets(blk)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.spmm(v, r, c, B, blk.block_shape[0], autotune=autotune)
    out = jnp.zeros((blk.block_shape[0], B.shape[-1]), jnp.float32)
    return out.at[r].add(v.astype(jnp.float32)[:, None]
                         * B[c].astype(jnp.float32))


def local_spmm_t(blk: BlockCOO, B: jax.Array, *, impl: str = "scatter",
                 autotune: bool = False) -> jax.Array:
    """A_blkᵀ @ B without transposing storage: scatter into columns, or —
    for impl="sorted" — the same streaming kernel over the column-sorted
    transposed triplet copy ``sort_rows`` stored (the rows ↔ cols swap
    applied at the storage level)."""
    if impl == "sorted":
        _require_sorted(blk, blk.has_sorted_cols, blk.t_vals)
        from repro.kernels import ops as kops
        return kops.spmm_sorted(
            blk.t_vals.reshape(-1), blk.t_rows.reshape(-1),
            blk.t_cols.reshape(-1), blk.col_offsets.reshape(-1),
            blk.col_tiles.reshape(-1), blk.col_valid.reshape(-1), B,
            blk.block_shape[1], align=blk.align, unit_rows=blk.unit_rows,
            autotune=autotune)
    v, r, c = _local_triplets(blk)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.spmm_t(v, r, c, B, blk.block_shape[1], autotune=autotune)
    out = jnp.zeros((blk.block_shape[1], B.shape[-1]), jnp.float32)
    return out.at[c].add(v.astype(jnp.float32)[:, None]
                         * B[r].astype(jnp.float32))
