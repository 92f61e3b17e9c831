"""Pallas TPU kernels: block-local SpMM out = A_blk · B from COO triplets.

This is the sparse analogue of ts_matmul — the hot spot of the paper's
sparse workloads (HPC-NMF arXiv:1509.09313 and PL-NMF arXiv:1904.07935 both
measure the local SpMM dominating at scale).  TWO variants live here, both
reachable through ``repro.backends.SparseOps(spmm_impl=...)``:

``spmm`` — unsorted triplet streaming (impl="pallas").
    The block's triplets (vals, rows, cols) stream through SMEM in chunks
    while the dense operand B (n_blk × k) and the full MXU-tile-aligned
    fp32 accumulator (m_blk × k, k padded to the 128 lane width by ops.py)
    stay VMEM-resident for the whole pass; each nonzero issues one
    dynamic-slice row read of B and one scatter-add row update of the
    output.  No preprocessing needed, but B and the whole output block
    are pinned in VMEM: the kernel raises the compiler's scoped-VMEM limit
    to what they need, up to ``VMEM_LIMIT`` (n_blk + m_blk ≈ 190k rows at
    k = 128), and refuses taller blocks.

``spmm_sorted`` — row-sorted + scalar prefetch (impl="sorted").
    Requires the ``BlockCOO.sort_rows()`` layout (core/blocksparse.py):
    triplets pre-sorted by row, packed so no nnz chunk spans two output
    row tiles.  The per-chunk output-tile ids and valid-triplet counts —
    both derived at trace time from the sorted layout's per-row segment
    offsets — are scalar-prefetched (``pltpu.PrefetchScalarGridSpec``), so
    the output index map walks tile by tile: only a small (block_m × k)
    accumulator tile is VMEM-resident at a time and finished output rows
    stream back to HBM.  This is how the paper's shared-memory baselines
    use caches — the sorted order turns the scatter into sequential
    streaming writes — and it also skips padding slots entirely (the
    per-chunk valid count bounds the inner loop).

Zero-padding safety (the invariant every repro.kernels kernel keeps): padded
triplets are val=0 and add 0·B[c] to some in-range row — a no-op — so
ragged nnz, ragged k, and all-empty blocks are all safe by construction.

Aᵀ·B needs no second kernel in either variant: swapping (rows ↔ cols)
scatters into columns, so Aᵀ is never materialised.  For ``spmm_sorted``
the swap happens at sort time — ``sort_rows`` stores a column-sorted
transposed triplet copy — because the streamed output dim must be the
sorted one.

On CPU (no Mosaic) the same kernel bodies run under interpret=True; the
production CPU path is the XLA scatter-add in core/blocksparse.py — these
kernels exist so ``backend="sparse"`` can use the TPU memory system the way
the dense kernels do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: XLA tiles a 1-D 32-bit array on TPU by 1024 elements, so a triplet
#: chunk streamed into SMEM as a 1-D block is a whole number of such tiles.
SMEM_TILE = 1024

#: Scoped-VMEM ceiling for the resident operands (v5e has 128 MiB of VMEM;
#: the compiler's default scope is 16 MiB).  Beyond it a block is too tall
#: for these kernels.
VMEM_LIMIT = 100 * 1024 * 1024


def _resident(shape):
    """BlockSpec of an operand that stays in VMEM for the whole grid: its
    block never moves, so a single buffer suffices."""
    index = lambda j, *_: (0,) * len(shape)
    return pl.BlockSpec(shape, index, pipeline_mode=pl.Buffered(1))


def _vmem_params(resident_bytes: int) -> pltpu.CompilerParams:
    need = resident_bytes + 4 * 1024 * 1024     # + tiles and scratch
    if need > VMEM_LIMIT:
        raise ValueError(
            f"SpMM block needs {need / 2**20:.0f} MiB of VMEM (> "
            f"{VMEM_LIMIT / 2**20:.0f} MiB); use a finer processor grid")
    return pltpu.CompilerParams(vmem_limit_bytes=max(need, 16 * 2**20))


def _spmm_kernel(vals_ref, rows_ref, cols_ref, b_ref, o_ref, *,
                 block_nnz: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body(t, carry):
        v = vals_ref[t].astype(jnp.float32)
        r = rows_ref[t]
        c = cols_ref[t]
        b = b_ref[pl.ds(c, 1), :].astype(jnp.float32)
        o_ref[pl.ds(r, 1), :] += v * b
        return carry

    lax.fori_loop(0, block_nnz, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("m_out", "block_nnz", "interpret"))
def spmm(vals: jax.Array, rows: jax.Array, cols: jax.Array, B: jax.Array, *,
         m_out: int, block_nnz: int = 512,
         interpret: bool = False) -> jax.Array:
    """Scatter-add SpMM: (m_out, k) fp32 from flat COO triplets and B (n, k).

    Shape contract (ops.py legalises arbitrary shapes): m_out and B's rows
    are multiples of 8 and k a multiple of 128 on TPU; triplets may be any
    length (padded to ``block_nnz`` internally with no-op zeros).
    """
    (nnz,) = vals.shape
    n, k = B.shape
    if nnz == 0:
        return jnp.zeros((m_out, k), jnp.float32)
    pad = (-nnz) % block_nnz
    if pad:
        vals = jnp.pad(vals, (0, pad))
        rows = jnp.pad(rows, (0, pad))
        cols = jnp.pad(cols, (0, pad))
    chunks = (nnz + pad) // block_nnz
    smem = functools.partial(pl.BlockSpec, (block_nnz,), lambda j: (j,),
                             memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_spmm_kernel, block_nnz=block_nnz),
        grid=(chunks,),
        in_specs=[smem(), smem(), smem(), _resident((n, k))],
        out_specs=_resident((m_out, k)),
        out_shape=jax.ShapeDtypeStruct((m_out, k), jnp.float32),
        compiler_params=_vmem_params((n + m_out) * k * 4),
        interpret=interpret,
    )(vals, rows, cols, B)


def _spmm_sorted_kernel(ids_ref, lens_ref, vals_ref, rows_ref, cols_ref,
                        b_ref, o_ref, *, block_m: int):
    """One grid step = one nnz chunk, guaranteed to lie inside output tile
    ``ids_ref[j]`` (the sorted layout's alignment invariant).  The chunk's
    first-in-tile test re-zeroes the accumulator tile exactly when the
    output index map moves to a fresh tile; ``lens_ref[j]`` bounds the loop
    so packed padding slots cost nothing."""
    j = pl.program_id(0)
    t = ids_ref[j]

    @pl.when(jnp.logical_or(j == 0, t != ids_ref[jnp.maximum(j - 1, 0)]))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    base = t * block_m

    def body(s, carry):
        v = vals_ref[s].astype(jnp.float32)
        r = rows_ref[s] - base
        c = cols_ref[s]
        o_ref[pl.ds(r, 1), :] += v * b_ref[pl.ds(c, 1), :].astype(jnp.float32)
        return carry

    lax.fori_loop(0, lens_ref[j], body, 0)


@functools.partial(jax.jit, static_argnames=("m_out", "align", "block_m",
                                             "block_nnz", "interpret"))
def spmm_sorted(vals: jax.Array, rows: jax.Array, cols: jax.Array,
                tiles: jax.Array, valid: jax.Array, B: jax.Array, *,
                m_out: int, align: int, block_m: int = 8,
                block_nnz: int = 64, interpret: bool = False) -> jax.Array:
    """Row-sorted scalar-prefetch SpMM: (m_out, k) fp32 from the packed
    ``sort_rows`` layout.

    ``vals``/``rows``/``cols`` are the tile-aligned packed triplets (length
    U·align); ``tiles``/``valid`` the per-align-unit first 8-row tile ids
    and valid counts.  Shape contract (ops.py legalises): m_out a multiple
    of block_m, block_m a multiple of the layout's row unit (8 or more)
    dividing m_out, block_nnz dividing align, B's rows ≥ max col + 1 and k
    a multiple of 128 on TPU.

    Rows that own no nonzeros may land in output tiles the grid never
    visits; the ops.py wrapper masks them to zero from the row offsets.
    """
    (L,) = vals.shape
    n, k = B.shape
    if L == 0:
        return jnp.zeros((m_out, k), jnp.float32)
    if L % align:
        raise ValueError(f"packed triplet length {L} must be a multiple of "
                         f"align={align} (the sort_rows layout guarantees "
                         f"this; truncating would silently drop nonzeros)")
    if align % block_nnz:
        raise ValueError(f"block_nnz={block_nnz} must divide align={align}")
    if block_m % 8 or m_out % block_m:
        raise ValueError(f"block_m={block_m} must be a multiple of 8 "
                         f"dividing m_out={m_out}")
    U = L // align
    rep = align // block_nnz
    chunks = U * rep
    # Per-CHUNK scalar-prefetch arrays from the per-UNIT sorted metadata:
    # the output tile id at block_m granularity, and how many of the
    # chunk's slots hold real triplets.
    ids = jnp.repeat(tiles.astype(jnp.int32) // (block_m // 8), rep)
    lens = jnp.clip(jnp.repeat(valid.astype(jnp.int32), rep)
                    - jnp.tile(jnp.arange(rep, dtype=jnp.int32) * block_nnz,
                               U), 0, block_nnz)
    smem = functools.partial(pl.BlockSpec, (block_nnz,),
                             lambda j, ids, lens: (j,),
                             memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(chunks,),
        in_specs=[smem(), smem(), smem(), _resident((n, k))],
        out_specs=pl.BlockSpec((block_m, k),
                               lambda j, ids, lens: (ids[j], 0)),
    )
    return pl.pallas_call(
        functools.partial(_spmm_sorted_kernel, block_m=block_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_out, k), jnp.float32),
        compiler_params=_vmem_params((n + 2 * block_m) * k * 4),
        interpret=interpret,
    )(ids, lens, vals, rows.astype(jnp.int32), cols.astype(jnp.int32), B)
