"""Public jit'd wrappers around the Pallas kernels.

Responsibilities:
  * shape legalisation — pad the k-wide operands (factor panels, COO
    triplets) to the MXU lane width (128) and to whole tiles with zeros
    (all kernels are zero-padding-safe by construction; see each module's
    docstring), then slice back.  The data matrix A is never padded: the
    tall-skinny products run a ``cdiv`` grid whose ragged edge tiles mask
    what they read past A, since a padded copy would double A's footprint;
  * backend dispatch — compiled Pallas (Mosaic) when the default backend
    is TPU, ``interpret=True`` on any other backend, where the same kernel
    body runs in Python for correctness tests.  The TPU path is guarded
    by ``tests/test_chip_compile.py``, which compiles each kernel for a
    described v5e at the widths ``chip_smoke.py`` runs;
  * block-size selection — hand heuristics sized for ~16 MB VMEM working
    sets by default, or the *measured* choice from kernels/autotune.py when
    ``autotune=True`` (the search always includes the heuristic, so tuning
    is never slower; results persist in the autotune JSON cache).

These back ``repro.backends.PallasOps`` (ts_matmul / ts_matmul_t / gram;
``PallasOps(autotune=True)`` turns the tuner on) and the Pallas lowerings of
``repro.backends.SparseOps`` (spmm / spmm_t for the unsorted streaming
kernel, spmm_sorted for the row-sorted scalar-prefetch kernel); the
engine's schedules call them only through that ``LocalOps`` layer.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels import gram as _gram
from repro.kernels import hals_sweep as _hals
from repro.kernels import mu_update as _mu
from repro.kernels import spmm as _spmm
from repro.kernels import ts_matmul as _ts

LANE = 128          # MXU/VREG lane width: pad k to this multiple
_MAX_INTERP_BLOCK = 64   # keep interpret-mode (pure python) loops small


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block(size: int, target: int) -> int:
    """Largest divisor of `size` that is <= target (after padding, size is a
    multiple of LANE or the target itself, so this terminates quickly)."""
    b = min(target, size)
    while size % b:
        b -= 1
    return b


def _candidates(size: int, default: int, interp_targets, tpu_targets,
                interpret: bool, *, pick=_block) -> list[int]:
    """Divisor-legal candidate block sizes for one dimension, always
    including the hand heuristic ``default``."""
    targets = interp_targets if interpret else tpu_targets
    return sorted({pick(size, t) for t in targets} | {default})


def _synth(shape, dtype, *, lo: float = 0.0, hi: float = 1.0,
           seed: int = 0) -> jax.Array:
    """Concrete pseudo-random array for tuning runs.  MUST be called from
    the tuner's worker thread (inside the ``run`` callable), never on a
    thread with an active trace — there the ``astype`` would silently
    produce a tracer and the search would time tracing, not compute."""
    arr = np.random.RandomState(seed).uniform(lo, hi, size=shape)
    return jnp.asarray(arr.astype(np.float32)).astype(dtype)


def _cached_params(op, key, *checks) -> tuple | None:
    """Cached tuning result, validated before use: the autotune cache is a
    shared artifact (env-pointed file, restored from CI), so a stale or
    hand-edited entry must degrade to a re-tune, never crash the fit.
    ``checks`` are per-position predicates; arity is implied by their
    count."""
    cached = _at.lookup(op, key)
    if cached is None or len(cached) != len(checks):
        return None
    if all(isinstance(p, int) and p > 0 and chk(p)
           for p, chk in zip(cached, checks)):
        return cached
    return None


def _isynth(shape, n: int, *, seed: int = 0) -> jax.Array:
    arr = np.random.RandomState(seed).randint(0, max(n, 1), size=shape)
    return jnp.asarray(arr.astype(np.int32))


def gram(X: jax.Array, *, block_m: int | None = None,
         autotune: bool = False) -> jax.Array:
    """XᵀX (fp32) for arbitrary (m, k).  X is a k-wide factor panel, so
    padding its rows to a whole number of row panels is cheap."""
    interpret = not _on_tpu()
    m, k = X.shape
    Xp = _pad_to(_pad_to(X, 1, LANE), 0, 8 if interpret else 512)
    default = _block(Xp.shape[0], _MAX_INTERP_BLOCK if interpret else 512)
    bm = block_m or default
    if block_m is None and autotune:
        key = (Xp.shape, Xp.dtype)
        # hot path: validated cache hit needs no synthetic inputs
        cached = _cached_params("gram", key, lambda b: Xp.shape[0] % b == 0)
        if cached is not None:
            (bm,) = cached
        else:
            cands = _candidates(Xp.shape[0], default, (16, 32, 64),
                                (128, 256, 512, 1024), interpret)
            Xs = functools.cache(lambda: _synth(Xp.shape, Xp.dtype))
            (bm,) = _at.tune("gram", key, [(c,) for c in cands],
                             lambda p: _gram.gram(Xs(), block_m=p[0],
                                                  interpret=interpret))
    out = _gram.gram(Xp, block_m=bm, interpret=interpret)
    return out[:k, :k]


def _tile(size: int, target: int, mult: int) -> int:
    """Tile length for a ``cdiv`` grid over ``size``: the whole dimension
    when it fits ``target`` (always a legal block), else ``target`` rounded
    down to the layout multiple ``mult`` (8 sublanes / 128 lanes)."""
    t = max(mult, target - target % mult)
    return size if size <= t else t


def _tile_ok(size: int, mult: int):
    return lambda b: b == size or (b % mult == 0 and b < size)


def _ts_call(fn, A, B, bm: int, bn: int, interpret: bool, contract: int):
    """Run a ts_matmul kernel on unpadded A: B (k-wide, cheap to copy) is
    padded to the MXU lane width and to a whole number of contraction
    tiles, with zeros."""
    tile = bn if contract == 1 else bm
    Bp = _pad_to(_pad_to(B, 1, LANE), 0, tile)
    return fn(A, Bp, block_m=bm, block_n=bn, interpret=interpret)


def _ts_blocks(name, fn, A, B, contract, interpret, default_m, default_n,
               autotune):
    m, n = A.shape
    bm = _tile(m, _MAX_INTERP_BLOCK if interpret else default_m, 8)
    bn = _tile(n, _MAX_INTERP_BLOCK if interpret else default_n, LANE)
    if not autotune:
        return bm, bn
    key = (A.shape, B.shape, A.dtype)
    # hot path: validated cache hit needs no synthetic inputs
    cached = _cached_params(name, key, _tile_ok(m, 8), _tile_ok(n, LANE))
    if cached is not None:
        return cached
    cands_m = sorted({_tile(m, t, 8) for t in
                      ((16, 32, 64) if interpret else (128, 256, 512))}
                     | {bm})
    cands_n = sorted({_tile(n, t, LANE) for t in
                      ((128,) if interpret else (128, 256, 512, 1024))}
                     | {bn})
    syn = functools.cache(lambda: (_synth(A.shape, A.dtype),
                                   _synth(B.shape, B.dtype, seed=1)))
    return _at.tune(name, key,
                    [(cm, cn) for cm in cands_m for cn in cands_n],
                    lambda p: _ts_call(fn, *syn(), p[0], p[1], interpret,
                                       contract))


def ts_matmul(A: jax.Array, B: jax.Array, *, block_m: int | None = None,
              block_n: int | None = None,
              autotune: bool = False) -> jax.Array:
    """A @ B (fp32) for arbitrary (m, n) × (n, k); A is never copied."""
    interpret = not _on_tpu()
    k = B.shape[1]
    bm, bn = _ts_blocks("ts_matmul", _ts.ts_matmul, A, B, 1, interpret,
                        256, 512,
                        autotune and block_m is None and block_n is None)
    out = _ts_call(_ts.ts_matmul, A, B, block_m or bm, block_n or bn,
                   interpret, contract=1)
    return out[:, :k]


def ts_matmul_t(A: jax.Array, B: jax.Array, *, block_m: int | None = None,
                block_n: int | None = None,
                autotune: bool = False) -> jax.Array:
    """Aᵀ @ B (fp32) for arbitrary (m, n) × (m, k); A is never copied."""
    interpret = not _on_tpu()
    k = B.shape[1]
    bm, bn = _ts_blocks("ts_matmul_t", _ts.ts_matmul_t, A, B, 0, interpret,
                        512, 256,
                        autotune and block_m is None and block_n is None)
    out = _ts_call(_ts.ts_matmul_t, A, B, block_m or bm, block_n or bn,
                   interpret, contract=0)
    return out[:, :k]


def spmm(vals: jax.Array, rows: jax.Array, cols: jax.Array, B: jax.Array,
         m_out: int, *, block_nnz: int | None = None,
         autotune: bool = False) -> jax.Array:
    """A_blk @ B (fp32) from flat COO triplets, for arbitrary (n, k) B —
    the unsorted triplet-streaming kernel (full output VMEM-resident)."""
    interpret = not _on_tpu()
    n, k = B.shape
    Bp = _pad_to(_pad_to(B, 1, LANE), 0, 8)
    m_pad = m_out + (-m_out) % 8
    default = _MAX_INTERP_BLOCK if interpret else _spmm.SMEM_TILE
    bnz = block_nnz or default
    if block_nnz is None and autotune and vals.shape[0]:
        key = (vals.shape[0], m_pad, Bp.shape, vals.dtype)
        # hot path: validated cache hit needs no synthetic inputs
        cached = _cached_params("spmm", key, lambda b: True)
        if cached is not None:
            (bnz,) = cached
        else:
            tile = _spmm.SMEM_TILE
            cands = _candidates(vals.shape[0], default, (16, 32, 64),
                                (tile, 2 * tile, 4 * tile), interpret,
                                pick=lambda s, t: min(s + (-s) % 8, t))
            syn = functools.cache(
                lambda: (_synth(vals.shape, vals.dtype),
                         _isynth(vals.shape, m_pad),
                         _isynth(vals.shape, Bp.shape[0], seed=1),
                         _synth(Bp.shape, Bp.dtype, seed=2)))
            (bnz,) = _at.tune(
                "spmm", key, [(c,) for c in cands],
                lambda p: _spmm.spmm(*syn(), m_out=m_pad,
                                     block_nnz=p[0], interpret=interpret))
    out = _spmm.spmm(vals, rows.astype(jnp.int32), cols.astype(jnp.int32),
                     Bp, m_out=m_pad, block_nnz=bnz, interpret=interpret)
    return out[:m_out, :k]


def spmm_t(vals: jax.Array, rows: jax.Array, cols: jax.Array, B: jax.Array,
           n_out: int, *, block_nnz: int | None = None,
           autotune: bool = False) -> jax.Array:
    """A_blkᵀ @ B (fp32): the same scatter-add with rows ↔ cols swapped, so
    Aᵀ is never materialised."""
    return spmm(vals, cols, rows, B, n_out, block_nnz=block_nnz,
                autotune=autotune)


def _synth_sorted(L, align, unit_rows, m_pad, Bp, dtype):
    """Consistent synthetic sort_rows layout for tuning runs: U full units
    with non-decreasing tile ids and rows inside each unit's rows."""
    U = L // align
    rng = np.random.RandomState(0)
    units = np.sort(rng.randint(0, m_pad // unit_rows, size=U))
    tiles = (units * (unit_rows // 8)).astype(np.int32)
    rows = (np.repeat(units, align) * unit_rows
            + rng.randint(0, unit_rows, size=L)).astype(np.int32)
    cols = rng.randint(0, Bp.shape[0], size=L).astype(np.int32)
    valid = np.full(U, align, np.int32)
    return (_synth((L,), dtype), jnp.asarray(rows), jnp.asarray(cols),
            jnp.asarray(tiles), jnp.asarray(valid),
            _synth(Bp.shape, Bp.dtype, seed=2))


def spmm_sorted(vals: jax.Array, rows: jax.Array, cols: jax.Array,
                offsets: jax.Array, tiles: jax.Array, valid: jax.Array,
                B: jax.Array, m_out: int, *, align: int, unit_rows: int = 8,
                block_m: int | None = None, block_nnz: int | None = None,
                autotune: bool = False) -> jax.Array:
    """A_blk @ B (fp32) from the row-sorted ``sort_rows`` packed layout —
    the scalar-prefetch kernel whose output streams tile by tile.

    ``offsets`` is the (m_out+1,) per-row segment-offset array; rows that
    own no triplets may sit in output tiles the kernel never visits, so
    they are masked to exact zeros here.  ``unit_rows`` is the layout's
    row unit: the accumulator tile (``block_m``) is a multiple of it.
    """
    interpret = not _on_tpu()
    n, k = B.shape
    Bp = _pad_to(_pad_to(B, 1, LANE), 0, 8)
    m_pad = m_out + (-m_out) % unit_rows
    units = lambda s, t: unit_rows * _block(s // unit_rows,
                                            max(t // unit_rows, 1))
    default_m = unit_rows if interpret else units(m_pad, 64)
    if interpret:
        default_nnz = _block(align, _MAX_INTERP_BLOCK)
    elif align % _spmm.SMEM_TILE:
        raise ValueError(f"the compiled sorted SpMM streams triplets in "
                         f"chunks of a multiple of {_spmm.SMEM_TILE}; sort "
                         f"with an align that is one (got align={align})")
    else:
        default_nnz = _spmm.SMEM_TILE
    bm = block_m or default_m
    bnz = block_nnz or default_nnz
    if block_m is None and block_nnz is None and autotune and vals.shape[0]:
        key = (vals.shape[0], align, unit_rows, m_pad, Bp.shape, vals.dtype)
        # hot path: validated cache hit needs no synthetic inputs
        cached = _cached_params(
            "spmm_sorted", key,
            lambda b: b % unit_rows == 0 and m_pad % b == 0,
            lambda b: align % b == 0
            and (interpret or b % _spmm.SMEM_TILE == 0))
        if cached is not None:
            bm, bnz = cached
        else:
            cands_m = _candidates(m_pad, default_m, (8, 16, 32),
                                  (64, 128, 256, 512), interpret,
                                  pick=units)
            tile = _spmm.SMEM_TILE
            cands_z = _candidates(
                align, default_nnz, (16, 32, 64), (tile, 2 * tile),
                interpret, pick=_block if interpret else
                lambda s, t: tile * _block(s // tile, t // tile))
            syn = functools.cache(
                lambda: _synth_sorted(vals.shape[0], align, unit_rows,
                                      m_pad, Bp, vals.dtype))
            bm, bnz = _at.tune(
                "spmm_sorted", key,
                [(cm, cz) for cm in cands_m for cz in cands_z],
                lambda p: _spmm.spmm_sorted(*syn(), m_out=m_pad, align=align,
                                            block_m=p[0], block_nnz=p[1],
                                            interpret=interpret))
    out = _spmm.spmm_sorted(vals, rows.astype(jnp.int32),
                            cols.astype(jnp.int32), tiles, valid, Bp,
                            m_out=m_pad, align=align, block_m=bm,
                            block_nnz=bnz, interpret=interpret)
    counts = offsets[1:] - offsets[:-1]
    return jnp.where(counts[:, None] > 0, out[:m_out, :k], 0.0)


def mu_update(X: jax.Array, G: jax.Array, R: jax.Array, *,
              block_r: int | None = None) -> jax.Array:
    """Fused MU LUC for arbitrary (r, k)."""
    interpret = not _on_tpu()
    r, k = X.shape
    Xp = _pad_to(_pad_to(X, 1, LANE), 0, 8)
    Gp = _pad_to(_pad_to(G, 0, LANE), 1, LANE)
    Rp = _pad_to(_pad_to(R, 1, LANE), 0, 8)
    br = block_r or _block(Xp.shape[0], _MAX_INTERP_BLOCK if interpret else 512)
    out = _mu.mu_update(Xp, Gp, Rp, block_r=br, interpret=interpret)
    return out[:r, :k]


def hals_sweep(X: jax.Array, G: jax.Array, R: jax.Array, *,
               block_r: int | None = None) -> jax.Array:
    """Fused HALS sweep (H-step form) for arbitrary (r, k).

    NOTE: k is *not* padded here — padding G's diagonal with zeros would
    change which columns the sweep visits; instead the kernel loops exactly
    k columns and only rows are padded.
    """
    interpret = not _on_tpu()
    r, k = X.shape
    Xp = _pad_to(X, 0, 8)
    Rp = _pad_to(R, 0, 8)
    br = block_r or _block(Xp.shape[0], _MAX_INTERP_BLOCK if interpret else 512)
    out = _hals.hals_sweep(Xp, G, Rp, block_r=br, interpret=interpret)
    return out[:r, :k]
