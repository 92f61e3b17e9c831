"""Dense backend: plain XLA GEMMs with fp32 accumulation.

``mm_t`` contracts A's *row* dimension (dot_general, not ``A.T @ B``) so the
H-step never materialises Aᵀ; with fp32 ``preferred_element_type`` the same
three ops serve the low-precision panel path (bf16 in, fp32 accumulate on
the MXU).

Both products run at ``base.PRECISION``, and ``mm_t`` sums A's rows in
blocks (``base.contract_rows``): one long fp32 dot drifts low on TPU,
enough to move the trace-trick relative error (core/error.py) by 1e-4 at
rel ≈ 0.08 on the 126,675-row video block.  The global-view (gspmd)
variant contracts in one dot.
"""

from __future__ import annotations

from repro.backends.base import LocalOps, contract_rows, dot


class DenseOps(LocalOps):
    name = "dense"

    def mm(self, A, B):
        return dot(A, B)

    def mm_t(self, A, B):
        return contract_rows(A, B, self.row_block)
