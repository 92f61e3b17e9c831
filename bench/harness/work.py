"""The work an iteration needs in its local products, from the shapes:
what the algorithm has to compute and move, whatever implements it.

Per iteration the two products A·Hᵀ and Aᵀ·W each read A once (the
H-update needs the new W, so the reads cannot be shared), read the factor
panel once and write their (rows, k) result once.  Bytes are counted at
A's dtype as the configuration states it.  The least time is
max(FLOP / peak FLOP/s, bytes / HBM bandwidth), and the term that sets it
is reported with it.
"""

from __future__ import annotations


def _bound(kind: str, flops: float, nbytes: float, peaks: dict) -> dict:
    t_flop = flops / peaks["flops"]
    t_hbm = nbytes / peaks["hbm_bytes_per_s"]
    return {"kind": kind, "flops": flops, "bytes": nbytes,
            "seconds": max(t_flop, t_hbm),
            "bound": "hbm" if t_hbm >= t_flop else "flops"}


def dense_products(m: int, n: int, k: int, peaks: dict,
                   itemsize: int = 4) -> dict:
    """One iteration's two dense products on an (m, n) block."""
    flops = 2 * (2.0 * m * n * k)
    nbytes = itemsize * ((m * n + n * k + m * k) + (m * n + m * k + n * k))
    return _bound("dense", flops, nbytes, peaks)


def spmm_products(nnz: int, m: int, n: int, k: int, peaks: dict,
                  itemsize: int = 4, index_bytes: int = 4) -> dict:
    """One iteration's two sparse products: each reads the nonzeros' value
    and two indices once, its dense operand once, and writes its result."""
    flops = 2 * (2.0 * nnz * k)
    triplets = nnz * (itemsize + 2 * index_bytes)
    nbytes = (triplets + itemsize * (n * k + m * k)) \
        + (triplets + itemsize * (m * k + n * k))
    return _bound("spmm", flops, nbytes, peaks)
